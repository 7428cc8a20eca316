"""Multi-device correctness checks, run in a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=8 (set by the caller —
never globally, per the dry-run isolation rule).

Usage: python tests/_multidev_checks.py <check_name>
Exits 0 on success; raises (non-zero exit) on failure.
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
    XYZConfig,
    shard_weight_xyz,
    unshard_weight_xyz,
    xyz_matmul,
    xyz_matmul_replicated_out,
)


def make_mesh():
    from repro.launch.mesh import make_mesh as mk
    return mk(2, 4)


def _data(b=4, s=8, k=32, n=64, seed=0):
    kx, kw = jax.random.split(jax.random.PRNGKey(seed))
    x = jax.random.normal(kx, (b, s, k), jnp.float32)
    w = jax.random.normal(kw, (k, n), jnp.float32) / np.sqrt(k)
    return x, w


def check_weight_layout_roundtrip():
    _, w = _data(k=32, n=64)
    for y in (1, 2, 4):
        w_xyz = shard_weight_xyz(w, 4, y)
        back = unshard_weight_xyz(w_xyz, y)
        np.testing.assert_allclose(np.asarray(back), np.asarray(w))
    print("ok weight_layout_roundtrip")


def check_schedule_equivalence():
    """The registered schedule-equivalence sweep (raw GEMM): every
    (schedule x x_layout x Y x Z) cell is bitwise fp32 equal across
    schedules AND matches the ref oracle.  Replaces the old ad-hoc
    xyz_forward_all_schedules + ring_bitwise_matches_reduce_scatter
    checks; the full epilogue grid runs in tests/test_schedule_equivalence
    under the multidev CI job."""
    import _schedule_sweep as sweep
    mesh = make_mesh()
    sweep.run_sweep(mesh, ys=(1, 2, 4), epilogues=("none",))
    # extra seeds on the reduction-heavy cells (the old ring-bitwise
    # check swept 3 seeds; keep that depth on the new schedules)
    for seed in (1, 2):
        for y in (2, 4):
            sweep.run_combo(mesh, y=y, layout="replicated",
                            ep_name="none", shape=(4, 8, 64, 128),
                            seed=seed)
    print("ok schedule_equivalence")


def check_schedule_equivalence_epilogue():
    """Fused-epilogue cells of the equivalence sweep (reduced grid: the
    full one runs under pytest -m multidev)."""
    import _schedule_sweep as sweep
    mesh = make_mesh()
    for ep_name in ("bias_gelu_residual", "quantize", "gate_silu"):
        for layout in ("replicated", "ksharded"):
            for y in (2, 4):
                sweep.run_combo(mesh, y=y, layout=layout, ep_name=ep_name,
                                schedules=("reduce_scatter", "bidir_ring"))
    print("ok schedule_equivalence_epilogue")


def check_xyz_replicated_out():
    mesh = make_mesh()
    x, w = _data()
    want = np.asarray(jnp.einsum("bsk,kn->bsn", x, w))
    for layout in ("replicated", "ksharded"):
        cfg = XYZConfig(y=4, schedule="allreduce", x_layout=layout)
        w_xyz = shard_weight_xyz(w, 4, 4)
        with jax.set_mesh(mesh):
            got = xyz_matmul_replicated_out(x, w_xyz, mesh=mesh, cfg=cfg)
        np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5,
                                   atol=2e-5, err_msg=layout)
    print("ok xyz_replicated_out")


def check_overlapped_gather_hlo():
    """The 'ksharded' Z>1 Y>1 path must contain NO barrier all-gather of A
    in its compiled HLO — the chunked ppermute gather replaced it — and
    the ppermute chain must not trip the weight-concat detector."""
    from repro.launch.hlo_analysis import weight_concat_count
    mesh = make_mesh()
    x, w = _data(b=4, s=8, k=64, n=128)
    for sched in ("bidir_ring", "ring", "reduce_scatter"):
        cfg = XYZConfig(y=2, schedule=sched, x_layout="ksharded")
        w_xyz = shard_weight_xyz(w, 4, 2)
        f = jax.jit(lambda xx: xyz_matmul(xx, w_xyz, mesh=mesh, cfg=cfg))
        with jax.set_mesh(mesh):
            txt = f.lower(x).compile().as_text()
        assert "all-gather" not in txt, f"{sched}: barrier all-gather of A"
        assert "collective-permute" in txt, sched
        assert weight_concat_count(txt, w.shape[0]) == 0, sched
    # Y == 1 keeps the barrier gather on purpose (whole epilogue stays
    # fused in the kernel store phase; nothing to overlap with)
    cfg1 = XYZConfig(y=1, x_layout="ksharded")
    w1 = shard_weight_xyz(w, 4, 1)
    f1 = jax.jit(lambda xx: xyz_matmul(xx, w1, mesh=mesh, cfg=cfg1))
    with jax.set_mesh(mesh):
        txt1 = f1.lower(x).compile().as_text()
    assert "all-gather" in txt1
    print("ok overlapped_gather_hlo")


def check_xyz_epilogue():
    """Fused epilogues through the sharded path match the unfused
    reference (einsum + bias/act/residual) for every schedule."""
    from repro.kernels.epilogue import Epilogue
    mesh = make_mesh()
    x, w = _data()
    n = w.shape[1]
    kb, kr = jax.random.split(jax.random.PRNGKey(7))
    bias = jax.random.normal(kb, (n,), jnp.float32)
    res = jax.random.normal(kr, (*x.shape[:-1], n), jnp.float32)

    base = jnp.einsum("bsk,kn->bsn", x, w)
    for y, sched in [(1, "reduce_scatter"), (2, "ring"),
                     (4, "reduce_scatter"), (4, "ring"), (2, "allreduce"),
                     (2, "bidir_ring"), (4, "bidir_ring")]:
        ep = Epilogue(bias=True, activation="gelu", residual=True)
        want = jax.nn.gelu(base + bias) + res
        cfg = XYZConfig(y=y, schedule=sched, epilogue=ep)
        w_xyz = shard_weight_xyz(w, 4, y)
        with jax.set_mesh(mesh):
            got = xyz_matmul(x, w_xyz, mesh=mesh, cfg=cfg, bias=bias,
                             residual=res)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5,
            err_msg=f"y={y} sched={sched}")

    # fused rowwise int8 quantize: per-N-shard scales, [..., model]
    epq = Epilogue(activation="silu", quantize=True)
    cfgq = XYZConfig(y=2, schedule="ring", epilogue=epq)
    w_xyz = shard_weight_xyz(w, 4, 2)
    with jax.set_mesh(mesh):
        q, s = xyz_matmul(x, w_xyz, mesh=mesh, cfg=cfgq)
    assert q.shape == base.shape and q.dtype == jnp.int8, (q.shape, q.dtype)
    assert s.shape == (*base.shape[:-1], 4) and s.dtype == jnp.float32
    act = np.asarray(jax.nn.silu(base))
    nloc = n // 4
    for c in range(4):
        shard = act[..., c * nloc:(c + 1) * nloc]
        sc = np.asarray(s)[..., c:c + 1]
        back = np.asarray(q)[..., c * nloc:(c + 1) * nloc] * sc
        absmax = np.max(np.abs(shard), axis=-1, keepdims=True)
        assert np.all(np.abs(back - shard) <= absmax / 254 + 1e-5), c

    # replicated-out epilogue (full-row bias, replicated residual)
    epr = Epilogue(bias=True, activation="relu")
    cfgr = XYZConfig(y=4, schedule="allreduce", epilogue=epr)
    w_xyz = shard_weight_xyz(w, 4, 4)
    with jax.set_mesh(mesh):
        got = xyz_matmul_replicated_out(x, w_xyz, mesh=mesh, cfg=cfgr,
                                        bias=bias)
    want = jax.nn.relu(base + bias)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    print("ok xyz_epilogue")


def check_grads():
    mesh = make_mesh()
    x, w = _data(k=16, n=32)

    for y, sched, layout in [
            (1, "allreduce", "replicated"), (4, "reduce_scatter", "replicated"),
            (2, "ring", "replicated"), (4, "ring", "replicated"),
            (4, "allreduce", "replicated"), (2, "bidir_ring", "replicated"),
            (4, "bidir_ring", "replicated"),
            # the overlapped-gather path: ppermute gather + K-piece GEMMs
            # must transpose correctly under AD
            (2, "bidir_ring", "ksharded"), (2, "reduce_scatter", "ksharded")]:
        cfg = XYZConfig(y=y, schedule=sched, x_layout=layout)
        w_xyz = shard_weight_xyz(w, 4, y)

        def loss_sharded(xx, ww):
            out = xyz_matmul(xx, ww, mesh=mesh, cfg=cfg)
            return jnp.sum(jnp.sin(out))

        def loss_ref(xx, ww):
            return jnp.sum(jnp.sin(jnp.einsum("bsk,kn->bsn", xx,
                                              unshard_weight_xyz(ww, y))))

        with jax.set_mesh(mesh):
            gx, gw = jax.grad(loss_sharded, argnums=(0, 1))(x, w_xyz)
        gx_r, gw_r = jax.grad(loss_ref, argnums=(0, 1))(x, w_xyz)
        np.testing.assert_allclose(np.asarray(gx), np.asarray(gx_r),
                                   rtol=1e-4, atol=1e-4,
                                   err_msg=f"gx y={y} {sched}")
        np.testing.assert_allclose(np.asarray(gw), np.asarray(gw_r),
                                   rtol=1e-4, atol=1e-4,
                                   err_msg=f"gw y={y} {sched}")

    # gradients THROUGH the fused epilogue, with the overlapped ring
    from repro.kernels.epilogue import Epilogue
    kb = jax.random.PRNGKey(11)
    bias = jax.random.normal(kb, (w.shape[1],), jnp.float32)
    for y, sched in [(2, "ring"), (4, "ring"), (4, "reduce_scatter"),
                     (2, "bidir_ring"), (4, "bidir_ring")]:
        ep = Epilogue(bias=True, activation="gelu")
        cfg = XYZConfig(y=y, schedule=sched, epilogue=ep)
        w_xyz = shard_weight_xyz(w, 4, y)

        def loss_fused(xx, ww, bb):
            out = xyz_matmul(xx, ww, mesh=mesh, cfg=cfg, bias=bb)
            return jnp.sum(jnp.sin(out))

        def loss_unfused(xx, ww, bb):
            h = jnp.einsum("bsk,kn->bsn", xx, unshard_weight_xyz(ww, y))
            return jnp.sum(jnp.sin(jax.nn.gelu(h + bb)))

        with jax.set_mesh(mesh):
            gx, gw, gb = jax.grad(loss_fused, argnums=(0, 1, 2))(
                x, w_xyz, bias)
        gx_r, gw_r, gb_r = jax.grad(loss_unfused, argnums=(0, 1, 2))(
            x, w_xyz, bias)
        for got, want, nm in [(gx, gx_r, "gx"), (gw, gw_r, "gw"),
                              (gb, gb_r, "gb")]:
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4,
                err_msg=f"epilogue {nm} y={y} {sched}")
    print("ok grads")


def check_packed_qkv_fused_sp():
    """Packed single-dispatch QKV through the fused SP shard_map on the
    2x4 mesh matches the per-view einsum reference (each model shard's
    local packed columns are [wq_i | wk_i | wv_i])."""
    from repro.configs.base import ArchConfig
    from repro.models import param as pm
    from repro.models.attention import attn_defs, fused_qkv_sp
    from repro.models.layers import TPCtx
    cfg = ArchConfig(name="t", family="dense", n_layers=2, d_model=32,
                     n_heads=8, n_kv_heads=4, head_dim=8, d_ff=64,
                     vocab=100)
    mesh = make_mesh()
    ctx = TPCtx(mesh=mesh, sp=True, compute_dtype=jnp.float32)
    params = pm.initialize({"a": attn_defs(cfg, 4, "float32", False)},
                           seed=5)["a"]
    views = pm.split_views(
        attn_defs(cfg, 4, "float32", False)["wqkv"], params["wqkv"])
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 16, cfg.d_model),
                          jnp.float32)
    with jax.set_mesh(mesh):
        q, k, v = fused_qkv_sp(params, x, cfg, ctx)
    b, s = x.shape[:2]
    for got, w, n in ((q, views["wq"], cfg.n_heads),
                      (k, views["wk"], cfg.n_kv_heads),
                      (v, views["wv"], cfg.n_kv_heads)):
        want = jnp.einsum("bsd,dn->bsn", x, w).reshape(b, s, n, cfg.hd)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)
    print("ok packed_qkv_fused_sp")


def check_packed_model_forward():
    """Full smoke-model train forward on the 2x4 mesh with packed QKV:
    finite, and bitwise-stable across two jit calls."""
    from repro.configs import get_config
    from repro.models.lm import Model
    mesh = make_mesh()
    cfg = get_config("internlm2-1.8b", smoke=True)
    model = Model(cfg, mesh)
    with jax.set_mesh(mesh):
        params = model.init_params(0)
        k1, k2 = jax.random.split(jax.random.PRNGKey(0))
        batch = {"tokens": jax.random.randint(k1, (4, 32), 0, cfg.vocab,
                                              jnp.int32),
                 "targets": jax.random.randint(k2, (4, 32), 0, cfg.vocab,
                                               jnp.int32)}
        f = jax.jit(model.loss)
        l1 = np.asarray(f(params, batch))
        l2 = np.asarray(f(params, batch))
    assert np.isfinite(l1), l1
    np.testing.assert_array_equal(l1, l2)
    print("ok packed_model_forward", float(l1))


def check_mlp_composition():
    """col-parallel up (Y=1) -> gelu -> row-parallel down (Y=model,
    ksharded): the Megatron pair with zero intermediate resharding."""
    mesh = make_mesh()
    x, w1 = _data(k=32, n=64)
    w2 = jax.random.normal(jax.random.PRNGKey(9), (64, 32), jnp.float32) / 8.0

    up = XYZConfig(y=1)
    down = XYZConfig(y=4, schedule="reduce_scatter", x_layout="ksharded")
    w1x = shard_weight_xyz(w1, 4, 1)
    w2x = shard_weight_xyz(w2, 4, 4)

    @jax.jit
    def mlp(xx):
        h = xyz_matmul(xx, w1x, mesh=mesh, cfg=up)
        h = jax.nn.gelu(h)
        return xyz_matmul(h, w2x, mesh=mesh, cfg=down)

    with jax.set_mesh(mesh):
        got = mlp(x)
    want = jnp.einsum("bsk,kn->bsn", jax.nn.gelu(jnp.einsum(
        "bsk,kn->bsn", x, w1)), w2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5,
                               atol=2e-5)
    # verify the HLO contains no all-gather between the two GEMMs beyond
    # the reduce-scatter (composition is resharding-free)
    txt = jax.jit(mlp).lower(x).compile().as_text()
    assert txt.count("all-gather") <= 1, txt.count("all-gather")
    print("ok mlp_composition")


def check_collective_bytes_ordering():
    """reduce_scatter must move fewer wire bytes than allreduce (the P2 <
    P1 economics), measured from compiled HLO."""
    from repro.launch.roofline import collective_wire_bytes
    mesh = make_mesh()
    x, w = _data(b=8, s=32, k=128, n=256)

    def run(sched):
        cfg = XYZConfig(y=4, schedule=sched)
        w_xyz = shard_weight_xyz(w, 4, 4)
        f = jax.jit(lambda xx: xyz_matmul(xx, w_xyz, mesh=mesh, cfg=cfg))
        with jax.set_mesh(mesh):
            comp = f.lower(x).compile()
        return collective_wire_bytes(comp.as_text())["total_wire_bytes"]

    ar = run("allreduce")
    rs = run("reduce_scatter")
    assert rs < ar, (rs, ar)
    # bidir moves the same TOTAL bytes as ring (each direction carries
    # half) — the win is per-link concurrency, not volume
    ring = run("ring")
    bidir = run("bidir_ring")
    assert abs(bidir - ring) <= 0.01 * ring, (bidir, ring)
    print("ok collective_bytes_ordering", rs, ar, ring, bidir)


CHECKS = {k[len("check_"):]: v for k, v in list(globals().items())
          if k.startswith("check_")}

if __name__ == "__main__":
    names = sys.argv[1:] or list(CHECKS)
    assert jax.device_count() == 8, jax.device_count()
    for nm in names:
        CHECKS[nm]()
    print("ALL_OK")
