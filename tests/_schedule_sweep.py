"""Shared schedule-equivalence sweep for the XYZ collective matmul.

One parametrized harness replaces the old ad-hoc per-schedule checks: for
every swept ``(schedule x x_layout x Y x Z x epilogue)`` combination on
the 8-fake-device mesh it asserts

  (a) BITWISE fp32 equality across all schedules (the determinism
      contract of ``core/maxeva_matmul.py``: shared chunk GEMMs +
      rank-order reductions make 'allreduce', 'reduce_scatter', 'ring'
      and 'bidir_ring' interchangeable bit-for-bit), and
  (b) closeness to the ``kernels.ref`` oracle (einsum + the shared
      ``apply_epilogue`` mirror).

Run either as registered checks from ``tests/_multidev_checks.py`` (the
reduced tier-1 subset) or directly as a subprocess from
``tests/test_schedule_equivalence.py`` (the full multidev-marked grid):

    python tests/_schedule_sweep.py --ys 2,4 --layouts ksharded \
        --epilogues bias_gelu --schedules all --shape 4,8,32,64 --seed 0

Every combination prints one ``ok equiv[...]`` line, so the CI multidev
log names each check individually for triage.
"""
import os
import sys

if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
    )

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.core.maxeva_matmul import (  # noqa: E402
    SCHEDULES,
    XYZConfig,
    shard_weight_xyz,
    xyz_matmul,
)
from repro.kernels.epilogue import Epilogue  # noqa: E402

MODEL = 4
DEFAULT_SHAPE = (4, 8, 32, 64)  # (b, s, k, n)

# epilogue grid: name -> Epilogue spec (None = raw GEMM).  Operands
# (bias / residual) are derived from the spec by the runner.
EPILOGUES = {
    "none": None,
    "bias_gelu": Epilogue(bias=True, activation="gelu"),
    "bias_gelu_residual": Epilogue(bias=True, activation="gelu",
                                   residual=True),
    "quantize": Epilogue(activation="silu", quantize=True),
    # v2 two-operand gate: silu(g) * u on the accumulator; with Y > 1
    # the gate applies post-reduction inside the shard_map (elementwise,
    # so the bitwise schedule-invariance contract must keep holding)
    "gate_silu": Epilogue(gate="silu"),
    "gate_silu_residual": Epilogue(gate="silu", residual=True),
}


def make_mesh():
    from repro.launch.mesh import make_mesh as mk
    return mk(2, MODEL)


def _data(b, s, k, n, seed):
    kx, kw, kb, kr, kg = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(kx, (b, s, k), jnp.float32)
    w = jax.random.normal(kw, (k, n), jnp.float32) / np.sqrt(k)
    bias = jax.random.normal(kb, (n,), jnp.float32)
    res = jax.random.normal(kr, (b, s, n), jnp.float32)
    op2 = jax.random.normal(kg, (b, s, n), jnp.float32)
    return x, w, bias, res, op2


def _flat(out):
    return list(out) if isinstance(out, tuple) else [out]


def _oracle_check(ep_name, ep, outs, x, w, bias, res, op2, tag):
    """(b): the swept result matches the unsharded einsum + shared
    ``apply_epilogue`` mirror within fp32 tolerance."""
    from repro.kernels.epilogue import apply_epilogue
    base = jnp.einsum("bsk,kn->bsn", x, w)
    got = outs
    if ep is None:
        np.testing.assert_allclose(np.asarray(got), np.asarray(base),
                                   rtol=2e-5, atol=2e-5, err_msg=tag)
        return
    if ep.quantize:
        q, s = got
        act = np.asarray(apply_epilogue(
            base, Epilogue(activation=ep.activation)))
        n = act.shape[-1]
        nloc = n // MODEL
        assert q.shape == act.shape and q.dtype == np.int8, (q.shape, q.dtype)
        assert s.shape == (*act.shape[:-1], MODEL), s.shape
        for c in range(MODEL):
            shard = act[..., c * nloc:(c + 1) * nloc]
            back = q[..., c * nloc:(c + 1) * nloc] * s[..., c:c + 1]
            absmax = np.max(np.abs(shard), axis=-1, keepdims=True)
            assert np.all(np.abs(back - shard) <= absmax / 254 + 1e-5), \
                (tag, c)
        return
    want = apply_epilogue(base, ep, bias=bias if ep.bias else None,
                          residual=res if ep.residual else None,
                          operand2=op2 if ep.gate != "none" else None)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5, err_msg=tag)


def run_combo(mesh, *, y, layout, ep_name, schedules=None,
              shape=DEFAULT_SHAPE, seed=0):
    """Run one (Y, layout, epilogue) cell across ``schedules`` and assert
    the bitwise + oracle invariants.  Returns the per-schedule outputs."""
    b, s, k, n = shape
    schedules = list(schedules or SCHEDULES)
    ep = EPILOGUES[ep_name]
    x, w, bias, res, op2 = _data(b, s, k, n, seed)
    w_xyz = shard_weight_xyz(w, MODEL, y)
    kwargs = {}
    if ep is not None and ep.bias:
        kwargs["bias"] = bias
    if ep is not None and ep.residual:
        kwargs["residual"] = res
    if ep is not None and ep.gate != "none":
        kwargs["operand2"] = op2

    outs = {}
    for sched in schedules:
        cfg = XYZConfig(y=y, schedule=sched, x_layout=layout, epilogue=ep)
        with jax.set_mesh(mesh):
            out = xyz_matmul(x, w_xyz, mesh=mesh, cfg=cfg, **kwargs)
        outs[sched] = [np.asarray(o) for o in _flat(out)]

    z = MODEL // y
    tag = (f"y={y} z={z} layout={layout} ep={ep_name} "
           f"shape={b}x{s}x{k}x{n} seed={seed}")
    # (a) bitwise fp32 equality across schedules (int8 q and f32 scales
    # must match exactly too under the quantize epilogue)
    ref_sched = ("reduce_scatter" if "reduce_scatter" in schedules
                 else schedules[0])
    for sched in schedules:
        for got, want in zip(outs[sched], outs[ref_sched]):
            np.testing.assert_array_equal(
                got, want,
                err_msg=f"{sched} != {ref_sched} bitwise [{tag}]")
    # (b) oracle
    ref_out = outs[ref_sched]
    _oracle_check(ep_name, ep, tuple(ref_out) if len(ref_out) > 1
                  else ref_out[0], x, w, bias, res, op2, tag)
    print(f"ok equiv[{tag} schedules={','.join(schedules)}]")
    return outs


def run_sweep(mesh=None, *, ys=(1, 2, 4), layouts=("replicated", "ksharded"),
              epilogues=("none",), schedules=None, shape=DEFAULT_SHAPE,
              seed=0):
    """The full cartesian sweep.  At Y == 1 there is no reduction, so the
    schedule dimension collapses — every schedule still runs (same single
    GEMM path) when explicitly requested, but the default sweep visits it
    once to keep the check cheap."""
    mesh = mesh or make_mesh()
    for ep_name in epilogues:
        for layout in layouts:
            for y in ys:
                scheds = list(schedules or SCHEDULES)
                if y == 1 and schedules is None:
                    scheds = ["reduce_scatter"]
                run_combo(mesh, y=y, layout=layout, ep_name=ep_name,
                          schedules=scheds, shape=shape, seed=seed)


def _parse_args(argv):
    import argparse
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--ys", default="1,2,4")
    ap.add_argument("--layouts", default="replicated,ksharded")
    ap.add_argument("--epilogues", default="none")
    ap.add_argument("--schedules", default="all",
                    help="'all' or a comma list from "
                         f"{','.join(SCHEDULES)}")
    ap.add_argument("--shape", default=",".join(map(str, DEFAULT_SHAPE)),
                    help="b,s,k,n")
    ap.add_argument("--seed", type=int, default=0)
    return ap.parse_args(argv)


if __name__ == "__main__":
    args = _parse_args(sys.argv[1:])
    assert jax.device_count() == 8, jax.device_count()
    scheds = None if args.schedules == "all" else args.schedules.split(",")
    run_sweep(
        ys=tuple(int(v) for v in args.ys.split(",")),
        layouts=tuple(args.layouts.split(",")),
        epilogues=tuple(args.epilogues.split(",")),
        schedules=scheds,
        shape=tuple(int(v) for v in args.shape.split(",")),
        seed=args.seed,
    )
    print("SWEEP_OK")
