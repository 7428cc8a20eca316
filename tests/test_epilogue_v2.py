"""Epilogue algebra v2: two-operand gate and rmsnorm-fused outputs.

Property-style coverage of the v2 stages against the f64-capable oracle
(``ref.matmul_fused_ref`` with float64 inputs keeps the whole chain —
dot AND epilogue — at f64), plus the bitwise composition contracts:

  * gate      fused silu(g) * u == the unfused sequence, bit for bit on
              the XLA path; within an eps-derived budget of the f64
              oracle in BOTH kernel modes (xla / interpret), including
              non-divisible blocks, 1-column tiles and single-row gates.
  * norm      the value output is bitwise the plain cast GEMM, and the
              normed output is bitwise ``models.layers.rmsnorm(value)``
              — fusing deletes the HBM round trip, never a bit.
  * int8      the gated up-GEMM's fused ``(q, scale)`` handoff is exact
              across kernel modes (integer accumulation has no rounding).

Spec validation (``Epilogue.__post_init__`` raises ``ValueError``, not
``assert``, so invalid specs die under ``python -O`` too) and the
planner's v2 HBM accounting ride along.  Gradients flow through both new
stages on the XLA path (Pallas has no VJP).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.epilogue import Epilogue, apply_epilogue
from repro.models.layers import rmsnorm

# ---------------------------------------------------------------------------
# satellite: spec validation (ValueError, one test per rejection)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kwargs,match", [
    (dict(activation="tanh"), "activation"),
    (dict(gate="swish"), "gate"),
    (dict(norm="layernorm"), "norm"),
    (dict(quantize_axis="tile"), "quantize_axis"),
    (dict(quantize=True, norm="rmsnorm"), "mutually"),
    (dict(norm_eps=0.0), "norm_eps"),
    (dict(norm_eps=-1e-6), "norm_eps"),
], ids=["bad-act", "bad-gate", "bad-norm", "bad-qaxis", "q-and-norm",
        "eps-zero", "eps-negative"])
def test_epilogue_spec_rejections(kwargs, match):
    with pytest.raises(ValueError, match=match):
        Epilogue(**kwargs)


def test_epilogue_spec_valid_v2_fields():
    """The v2 fields round-trip through the frozen dataclass."""
    ep = Epilogue(gate="silu", quantize=True)
    assert ep.n_outputs == 2 and ep.out_itemsize() == 1
    ep = Epilogue(residual=True, norm="rmsnorm", norm_eps=1e-5,
                  out_dtype=jnp.bfloat16)
    assert ep.n_outputs == 2 and ep.out_itemsize() == 2
    assert not Epilogue(gate="mul").is_identity
    assert not Epilogue(norm="rmsnorm").is_identity


# ---------------------------------------------------------------------------
# shared operands / budgets
# ---------------------------------------------------------------------------

# edge shapes: non-divisible blocks (the 32-block kernel pads every axis),
# 1-column output tiles, single-row gates, and a K smaller than the block
EDGE_SHAPES = [(32, 32, 32), (7, 13, 129), (1, 5, 1), (33, 64, 31),
               (100, 130, 70)]

GATE_EPILOGUES = [
    Epilogue(gate="silu"),
    Epilogue(gate="mul"),
    Epilogue(gate="gelu", bias=True),
    Epilogue(gate="silu", residual=True),
    Epilogue(gate="silu", out_dtype=jnp.bfloat16),
    Epilogue(activation="relu", gate="silu", residual=True),
]
_GATE_IDS = ["silu", "mul", "gelu+b", "silu+r", "silu+cast", "relu+silu+r"]

NORM_EPILOGUES = [
    Epilogue(norm="rmsnorm"),
    Epilogue(residual=True, norm="rmsnorm"),
    Epilogue(residual=True, norm="rmsnorm", out_dtype=jnp.bfloat16),
    Epilogue(bias=True, norm="rmsnorm", norm_eps=1e-5),
]
_NORM_IDS = ["n", "r+n", "r+n+cast", "b+n+eps"]


def _operands(m, k, n, seed=0, dtype=jnp.float32):
    ka, kb, kc, kd, kg, kn = jax.random.split(jax.random.PRNGKey(seed), 6)
    a = jax.random.normal(ka, (m, k), dtype)
    b = jax.random.normal(kb, (k, n), dtype) / np.sqrt(k)
    bias = jax.random.normal(kc, (n,), jnp.float32)
    res = jax.random.normal(kd, (m, n), jnp.float32)
    op2 = jax.random.normal(kg, (m, n), jnp.float32)
    nsc = jax.random.normal(kn, (n,), jnp.float32) * 0.1
    return a, b, bias, res, op2, nsc


def _kw(ep, bias, res, op2, nsc):
    return dict(bias=bias if ep.bias else None,
                residual=res if ep.residual else None,
                operand2=op2 if ep.gate != "none" else None,
                norm_scale=nsc if ep.norm != "none" else None)


def _oracle_f64(a, b, ep, **kw):
    """The f64 oracle: same spec, every operand upcast to float64, so the
    dot and the whole epilogue chain run at f64 (no hand-tuned ref)."""
    ep64 = Epilogue(**{**{f.name: getattr(ep, f.name)
                          for f in Epilogue.__dataclass_fields__.values()},
                       "out_dtype": jnp.float64})
    with ref.x64():
        up = {k: (None if v is None
                  else jnp.asarray(np.asarray(v, np.float64)))
              for k, v in kw.items()}
        out = ref.matmul_fused_ref(
            jnp.asarray(np.asarray(a, np.float64)),
            jnp.asarray(np.asarray(b, np.float64)), ep64, **up)
        if isinstance(out, tuple):
            return tuple(np.asarray(o, np.float64) for o in out)
        return np.asarray(out, np.float64)


def _f64_budget(k, want64):
    """eps-derived fp32 budget: accumulation + epilogue rounding, scaled
    to the oracle's magnitude — nothing hand-tuned per shape."""
    scale = max(1.0, float(np.max(np.abs(want64))))
    return 64 * np.finfo(np.float32).eps * np.sqrt(max(k, 2)) * scale


def _assert_close_f64(got, want64, k, bf16=False, tag=""):
    tol = _f64_budget(k, want64)
    if bf16:
        tol = max(tol, 1.5 * float(np.max(np.abs(want64)))
                  * np.finfo(np.float32).eps * 2 ** 16)
    err = float(np.max(np.abs(np.asarray(got, np.float64) - want64)))
    assert err <= tol, f"{tag}: err={err:.3e} > budget={tol:.3e}"


# ---------------------------------------------------------------------------
# gate stage vs the f64 oracle, both kernel modes, edge shapes
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("ep", GATE_EPILOGUES, ids=_GATE_IDS)
@pytest.mark.parametrize("mkn", EDGE_SHAPES)
def test_gate_epilogue_vs_f64_oracle_xla(mkn, ep):
    m, k, n = mkn
    a, b, bias, res, op2, nsc = _operands(m, k, n, seed=m + n)
    kw = _kw(ep, bias, res, op2, nsc)
    got = ops.matmul(a, b, mode="xla", epilogue=ep, **kw)
    want64 = _oracle_f64(a, b, ep, **kw)
    _assert_close_f64(got, want64, k, bf16=ep.out_dtype == jnp.bfloat16,
                      tag=f"xla {mkn}")


@pytest.mark.parametrize("ep", GATE_EPILOGUES, ids=_GATE_IDS)
@pytest.mark.parametrize("mkn", EDGE_SHAPES)
def test_gate_epilogue_vs_f64_oracle_interpret(mkn, ep):
    """The Pallas store phase (interpret mode; padded, non-divisible
    tiles) lands inside the same eps budget of the f64 oracle."""
    m, k, n = mkn
    a, b, bias, res, op2, nsc = _operands(m, k, n, seed=m + n)
    kw = _kw(ep, bias, res, op2, nsc)
    got = ops.matmul(a, b, block=(32, 32, 32), mode="interpret",
                     epilogue=ep, **kw)
    want64 = _oracle_f64(a, b, ep, **kw)
    _assert_close_f64(got, want64, k, bf16=ep.out_dtype == jnp.bfloat16,
                      tag=f"interpret {mkn}")


@pytest.mark.parametrize("seed", [0, 1, 2, 3, 4, 5, 6, 7])
def test_gate_epilogue_random_shape_sweep(seed):
    """Seeded random-shape property sweep (hypothesis is unavailable in
    this image): xla and interpret agree and both track the f64 oracle."""
    rng = np.random.RandomState(1000 + seed)
    m = int(rng.randint(1, 70))
    k = int(rng.randint(1, 150))
    n = int(rng.randint(1, 150))
    ep = GATE_EPILOGUES[seed % len(GATE_EPILOGUES)]
    a, b, bias, res, op2, nsc = _operands(m, k, n, seed=seed)
    kw = _kw(ep, bias, res, op2, nsc)
    x = ops.matmul(a, b, mode="xla", epilogue=ep, **kw)
    p = ops.matmul(a, b, block=(32, 32, 32), mode="interpret",
                   epilogue=ep, **kw)
    want64 = _oracle_f64(a, b, ep, **kw)
    shape = (m, k, n)
    bf16 = ep.out_dtype == jnp.bfloat16
    _assert_close_f64(x, want64, k, bf16=bf16, tag=f"xla {shape}")
    _assert_close_f64(p, want64, k, bf16=bf16, tag=f"interpret {shape}")


def test_gate_fused_equals_unfused_sequence_xla():
    """On the XLA path fusion only moves op boundaries: the fused gate ==
    plain GEMM -> apply_epilogue, bit for bit."""
    a, b, bias, res, op2, nsc = _operands(64, 96, 128, seed=5)
    for ep in GATE_EPILOGUES:
        kw = _kw(ep, bias, res, op2, nsc)
        fused = ops.matmul(a, b, mode="xla", epilogue=ep, **kw)
        acc = ops.matmul(a, b, mode="xla")
        unfused = apply_epilogue(acc, ep, **kw)
        np.testing.assert_array_equal(np.asarray(fused, np.float32),
                                      np.asarray(unfused, np.float32))


# ---------------------------------------------------------------------------
# rmsnorm stage: bitwise composition + f64 oracle, both kernel modes
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("ep", NORM_EPILOGUES, ids=_NORM_IDS)
def test_norm_value_and_normed_bitwise_composition(ep):
    """The two-output contract: ``value`` is bitwise the same GEMM
    without the norm stage, and ``normed`` is bitwise
    ``models.layers.rmsnorm(value)`` — the fold deletes the residual
    stream's HBM round trip without changing one bit."""
    import dataclasses
    a, b, bias, res, op2, nsc = _operands(48, 64, 40, seed=9)
    kw = _kw(ep, bias, res, op2, nsc)
    value, normed = ops.matmul(a, b, mode="xla", epilogue=ep, **kw)
    plain_ep = dataclasses.replace(ep, norm="none")
    plain = ops.matmul(a, b, mode="xla", epilogue=plain_ep,
                       **{**kw, "norm_scale": None})
    np.testing.assert_array_equal(np.asarray(value, np.float32),
                                  np.asarray(plain, np.float32))
    renormed = rmsnorm(value, nsc, ep.norm_eps)
    np.testing.assert_array_equal(np.asarray(normed, np.float32),
                                  np.asarray(renormed, np.float32))


@pytest.mark.parametrize("ep", NORM_EPILOGUES, ids=_NORM_IDS)
@pytest.mark.parametrize("mkn", EDGE_SHAPES)
def test_norm_epilogue_vs_f64_oracle_both_modes(mkn, ep):
    """Both outputs track the f64 oracle on edge shapes in both kernel
    modes (interpret pads the N tile; norm_n keeps the mean exact)."""
    m, k, n = mkn
    a, b, bias, res, op2, nsc = _operands(m, k, n, seed=m * 3 + n)
    kw = _kw(ep, bias, res, op2, nsc)
    want_v, want_n = _oracle_f64(a, b, ep, **kw)
    bf16 = ep.out_dtype == jnp.bfloat16
    for mode in ("xla", "interpret"):
        mkw = dict(kw)
        if mode == "interpret":
            mkw["block"] = (32, 32, 32)
        got_v, got_n = ops.matmul(a, b, mode=mode, epilogue=ep, **mkw)
        _assert_close_f64(got_v, want_v, k, bf16=bf16,
                          tag=f"{mode} value {mkn}")
        # the normed output divides by rms ~ O(1); same budget class,
        # with one extra reduction over n folded in
        _assert_close_f64(got_n, want_n, k + n, bf16=bf16,
                          tag=f"{mode} normed {mkn}")


# ---------------------------------------------------------------------------
# int8: the gated up-GEMM's fused (q, scale) handoff
# ---------------------------------------------------------------------------


def test_int8_gate_quantize_handoff_exact_across_modes():
    """silu(g) * (sa * sb * int32 acc) -> rowwise (q, scale): the fused
    handoff the int8 gated MLP feeds to the down GEMM.  Integer
    accumulation has no rounding, so xla and interpret agree exactly on
    q; scales are f32-identical math."""
    ka, kb, kg = jax.random.split(jax.random.PRNGKey(0), 3)
    a = jax.random.normal(ka, (64, 96), jnp.float32)
    b = jax.random.normal(kb, (96, 64), jnp.float32) / np.sqrt(96)
    g = jax.random.normal(kg, (64, 64), jnp.float32)
    qa, sa = ref.quantize_rowwise_ref(a)
    qb, sb = ref.quantize_colwise_ref(b)
    ep = Epilogue(gate="silu", quantize=True)
    qx, sx = ops.int8_matmul(qa, sa, qb, sb, mode="xla", epilogue=ep,
                             operand2=g)
    qi, si = ops.int8_matmul(qa, sa, qb, sb, block=(32, 32, 32),
                             mode="interpret", epilogue=ep, operand2=g)
    qr, sr = ref.int8_matmul_ref(qa, sa, qb, sb, ep, operand2=g)
    assert qx.dtype == jnp.int8 and sx.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(qx), np.asarray(qr))
    np.testing.assert_allclose(np.asarray(sx), np.asarray(sr), rtol=1e-6)
    dq = np.abs(np.asarray(qi, np.int32) - np.asarray(qr, np.int32))
    assert dq.max() <= 1, dq.max()  # tile-order rounding: one q step max
    np.testing.assert_allclose(np.asarray(si), np.asarray(sr), rtol=1e-5)


def test_int8_norm_fold_matches_ref():
    """The int8 down GEMM's residual + rmsnorm fold (the serving path's
    block boundary) against the shared-epilogue int8 oracle."""
    ka, kb, kr = jax.random.split(jax.random.PRNGKey(3), 3)
    a = jax.random.normal(ka, (32, 64), jnp.float32)
    b = jax.random.normal(kb, (64, 48), jnp.float32) / 8.0
    res = jax.random.normal(kr, (32, 48), jnp.float32)
    nsc = jnp.linspace(-0.1, 0.1, 48, dtype=jnp.float32)
    qa, sa = ref.quantize_rowwise_ref(a)
    qb, sb = ref.quantize_colwise_ref(b)
    ep = Epilogue(residual=True, norm="rmsnorm", out_dtype=jnp.bfloat16)
    got_v, got_n = ops.int8_matmul(qa, sa, qb, sb, mode="xla",
                                   epilogue=ep, residual=res,
                                   norm_scale=nsc)
    want_v, want_n = ref.int8_matmul_ref(qa, sa, qb, sb, ep, residual=res,
                                         norm_scale=nsc)
    np.testing.assert_array_equal(np.asarray(got_v, np.float32),
                                  np.asarray(want_v, np.float32))
    np.testing.assert_array_equal(np.asarray(got_n, np.float32),
                                  np.asarray(want_n, np.float32))
    # and the normed output is bitwise the standalone-norm composition
    renormed = rmsnorm(got_v, nsc, ep.norm_eps)
    np.testing.assert_array_equal(np.asarray(got_n, np.float32),
                                  np.asarray(renormed, np.float32))


# ---------------------------------------------------------------------------
# gradients (XLA path: Pallas has no VJP)
# ---------------------------------------------------------------------------


def test_gate_epilogue_gradients_match_unfused():
    """d/d{a, b, g, res} of the fused gate == the unfused composition."""
    a, b, bias, res, op2, nsc = _operands(24, 32, 16, seed=7)
    ep = Epilogue(gate="silu", residual=True)

    def loss_fused(a, b, g, res):
        out = ops.matmul(a, b, mode="xla", epilogue=ep, operand2=g,
                         residual=res)
        return jnp.sum(jnp.sin(out))

    def loss_unfused(a, b, g, res):
        acc = jnp.dot(a, b, preferred_element_type=jnp.float32)
        return jnp.sum(jnp.sin(jax.nn.silu(g) * acc + res))

    gf = jax.grad(loss_fused, argnums=(0, 1, 2, 3))(a, b, op2, res)
    gu = jax.grad(loss_unfused, argnums=(0, 1, 2, 3))(a, b, op2, res)
    for got, want in zip(gf, gu):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-6)


def test_norm_epilogue_gradients_match_unfused():
    """Grads flow through BOTH outputs of the norm fold and match the
    store-then-rmsnorm composition."""
    a, b, bias, res, op2, nsc = _operands(16, 24, 20, seed=11)
    ep = Epilogue(residual=True, norm="rmsnorm")

    def loss_fused(a, b, res, nsc):
        value, normed = ops.matmul(a, b, mode="xla", epilogue=ep,
                                   residual=res, norm_scale=nsc)
        return jnp.sum(jnp.sin(normed)) + jnp.sum(jnp.cos(value))

    def loss_unfused(a, b, res, nsc):
        value = jnp.dot(a, b, preferred_element_type=jnp.float32) + res
        normed = rmsnorm(value, nsc, ep.norm_eps)
        return jnp.sum(jnp.sin(normed)) + jnp.sum(jnp.cos(value))

    gf = jax.grad(loss_fused, argnums=(0, 1, 2, 3))(a, b, res, nsc)
    gu = jax.grad(loss_unfused, argnums=(0, 1, 2, 3))(a, b, res, nsc)
    for got, want in zip(gf, gu):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# planner accounting for the v2 stages
# ---------------------------------------------------------------------------


def test_planner_gate_and_norm_accounting():
    from repro.core.perf_model import fused_epilogue_savings
    from repro.core.planner import epilogue_hbm_bytes
    m, n = 4096, 14336
    # gate: the g read is paid either way; unfused also re-reads the
    # output and re-writes the product (one extra elementwise pass)
    ep = Epilogue(gate="silu", out_dtype=jnp.bfloat16)
    item = ep.out_itemsize()
    fused = epilogue_hbm_bytes(m, n, ep, fused=True)
    unfused = epilogue_hbm_bytes(m, n, ep, fused=False)
    assert fused == m * n * item + m * n * item  # out + g operand
    assert unfused - fused == 2 * 4 * m * n + 2 * m * n * item
    # norm: second [m, n] output + [n] scale either way; unfused adds the
    # residual stream's standalone read + write
    ep = Epilogue(residual=True, norm="rmsnorm", out_dtype=jnp.bfloat16)
    fused = epilogue_hbm_bytes(m, n, ep, fused=True)
    unfused = epilogue_hbm_bytes(m, n, ep, fused=False)
    assert fused == 3 * m * n * item + 4 * n  # value + normed + residual
    assert unfused - fused == 2 * 4 * m * n + 2 * m * n * item
    sav = fused_epilogue_savings(m, n, ep)
    assert sav["bytes_saved"] == unfused - fused
    assert sav["seconds_saved"] > 0
