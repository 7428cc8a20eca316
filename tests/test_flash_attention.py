"""Flash-attention kernel validation (PR 9).

Three layers of checks:

  * oracle agreement — the Pallas prefill/decode/paged kernels (interpret
    mode) and their tiled XLA mirrors land within a consistency budget of
    the f64-anchored plain-softmax oracles in ``kernels/ref.py``.  The
    budget mirrors ``test_archs_smoke``: the f64 reference anchors an f32
    oracle run, and the kernel must sit within a small multiple of the
    f32 rounding distance plus the output-dtype quantization step.
  * bitwise contracts — decode output is bitwise-invariant to the
    flash-decode split count (the rank-order combine makes the partial
    fold order independent of which program computed which tile), and
    trash-page / idle-lane rows contribute exact zeros.
  * HLO regression — the traced decode step's pre-optimization module
    contains no full-cache fp32 upcast (the einsum bug this PR fixed),
    and the detector demonstrably fires on the old pattern.

The property-based section needs ``hypothesis`` (see requirements-dev.txt)
and degrades to a fixed-example smoke subset when it is absent.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # degrade to the fixed-example smoke subset below
    HAVE_HYPOTHESIS = False

from repro.kernels import flash_attention as fa
from repro.kernels import ops as kops
from repro.kernels import ref
from repro.models import attention as A


def _bf16(key, *shape):
    return jax.random.normal(key, shape, jnp.float32).astype(jnp.bfloat16)


def _budget(got, want64, want32):
    """Consistency budget vs the f64 anchor: the kernel may be at most
    4x as far from f64 truth as the f32 oracle, plus the output dtype's
    quantization step (kernels return q.dtype = bf16; the f32 oracle
    does not pay that rounding)."""
    g = np.asarray(got, np.float64)
    w64 = np.asarray(want64, np.float64)
    w32 = np.asarray(want32, np.float64)
    scale = max(1.0, float(np.max(np.abs(w64))))
    eps_out = float(jnp.finfo(got.dtype).eps)
    err32 = float(np.max(np.abs(w32 - w64)))
    err = float(np.max(np.abs(g - w64)))
    assert err <= 4.0 * err32 + 4.0 * eps_out * scale, \
        f"err={err:.3e} budget={4.0 * err32 + 4.0 * eps_out * scale:.3e}"


def _f64_prefill_ref(q, k, v, **kw):
    with ref.x64():
        return ref.flash_attention_ref(
            jnp.asarray(np.asarray(q, np.float64)),
            jnp.asarray(np.asarray(k, np.float64)),
            jnp.asarray(np.asarray(v, np.float64)), **kw)


# ---------------------------------------------------------------------------
# prefill kernel vs oracle (and the einsum-scan production fallback)
# ---------------------------------------------------------------------------

def _check_prefill(b, sq, n_h, n_kv, hd, kind, seed, *, window=0,
                   prefix_len=0, softcap=None, block=8):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = _bf16(keys[0], b, sq, n_h, hd)
    k = _bf16(keys[1], b, sq, n_kv, hd)
    v = _bf16(keys[2], b, sq, n_kv, hd)
    kw = dict(kind=kind, window=window, prefix_len=prefix_len,
              softcap=softcap)
    want64 = _f64_prefill_ref(q, k, v, **kw)
    want32 = ref.flash_attention_ref(q.astype(jnp.float32),
                                     k.astype(jnp.float32),
                                     v.astype(jnp.float32), **kw)
    got = fa.flash_attention_pallas(q, k, v, block_q=block, block_k=block,
                                    interpret=True, **kw)
    assert got.shape == q.shape and got.dtype == q.dtype
    _budget(got, want64, want32)
    # the production einsum-scan fallback must satisfy the same budget
    # (it takes head-expanded k/v) — this pins the sq % q_chunk != 0
    # right-pad fix: before it, the last partial q-chunk's clamped
    # dynamic slice attended through mislabeled positions
    g = n_h // n_kv
    scan = A.flash_attention(q, jnp.repeat(k, g, axis=2),
                             jnp.repeat(v, g, axis=2), q_chunk=4,
                             kv_chunk=4, **kw)
    _budget(scan, want64, want32)


PREFILL_CASES = [
    # (b, sq, n_h, n_kv, hd, kind, seed, extra)
    (1, 10, 4, 2, 16, "global", 0, {}),
    (2, 12, 2, 2, 16, "local", 1, dict(window=4)),     # g=1 GQA edge
    (1, 10, 4, 2, 16, "chunked", 2, dict(window=4)),
    (1, 10, 4, 2, 16, "prefix", 3, dict(prefix_len=3)),
    (1, 10, 4, 2, 16, "full", 4, {}),
    (1, 10, 4, 2, 16, "global", 5, dict(softcap=5.0)),
    (1, 1, 4, 2, 16, "global", 6, {}),                 # S=1 prefill
    (1, 6, 4, 2, 12, "global", 7, {}),                 # hd % 8 != 0
    (1, 10, 4, 4, 20, "local", 8, dict(window=3)),     # sq % q_chunk != 0
    (2, 5, 2, 1, 16, "global", 9, {}),                 # KV < one tile
]


@pytest.mark.parametrize("b,sq,n_h,n_kv,hd,kind,seed,extra", PREFILL_CASES)
def test_prefill_matches_oracle(b, sq, n_h, n_kv, hd, kind, seed, extra):
    _check_prefill(b, sq, n_h, n_kv, hd, kind, seed, **extra)


# ---------------------------------------------------------------------------
# dense flash decode: oracle agreement + split-count bitwise invariance
# ---------------------------------------------------------------------------

def _decode_inputs(b, kv_len, n_kv, g, hd, seed):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = _bf16(keys[0], b, 1, n_kv, g, hd)
    kc = _bf16(keys[1], b, kv_len, n_kv, hd)
    vc = _bf16(keys[2], b, kv_len, n_kv, hd)
    return q, kc, vc


def _check_decode(b, kv_len, n_kv, g, hd, pos, seed, *, kind="global",
                  kv_tile=8):
    q, kc, vc = _decode_inputs(b, kv_len, n_kv, g, hd, seed)
    with ref.x64():
        want64 = ref.flash_decode_ref(
            jnp.asarray(np.asarray(q, np.float64)),
            jnp.asarray(np.asarray(kc, np.float64)),
            jnp.asarray(np.asarray(vc, np.float64)), pos, kind=kind)
    want32 = ref.flash_decode_ref(q.astype(jnp.float32),
                                  kc.astype(jnp.float32),
                                  vc.astype(jnp.float32), pos, kind=kind)
    xla = fa.flash_decode_xla(q, kc, vc, jnp.int32(pos), kind=kind,
                              kv_tile=kv_tile)
    _budget(xla, want64, want32)
    outs = []
    for ns in (1, 2, 4):
        pal = fa.flash_decode_pallas(q, kc, vc, jnp.int32(pos), kind=kind,
                                     kv_tile=kv_tile, n_splits=ns,
                                     interpret=True)
        _budget(pal, want64, want32)
        outs.append(np.asarray(pal.astype(jnp.float32)))
    # THE determinism contract: n_splits only changes which program
    # computes which tile partials; the ascending rank-order combine
    # makes the result bitwise-identical across split counts
    assert np.array_equal(outs[0], outs[1])
    assert np.array_equal(outs[1], outs[2])


DECODE_CASES = [
    # (b, kv_len, n_kv, g, hd, pos, seed)
    (2, 22, 2, 2, 16, 13, 0),
    (1, 7, 2, 2, 16, 6, 1),     # KV < one tile
    (1, 33, 1, 1, 12, 32, 2),   # g=1, hd % 8 != 0, tile straddle
    (2, 16, 2, 4, 16, 0, 3),    # pos=0: single valid slot
    (1, 40, 2, 2, 20, 25, 4),
]


@pytest.mark.parametrize("b,kv_len,n_kv,g,hd,pos,seed", DECODE_CASES)
def test_decode_matches_oracle_and_split_invariant(b, kv_len, n_kv, g, hd,
                                                   pos, seed):
    _check_decode(b, kv_len, n_kv, g, hd, pos, seed)


def test_decode_full_kind():
    _check_decode(1, 22, 2, 2, 16, 4, 5, kind="full")


def test_decode_einsum_fallback_same_budget():
    """The fixed einsum fallback stays within the same budget (it is the
    ring-buffer path's production implementation)."""
    q, kc, vc = _decode_inputs(2, 22, 2, 2, 16, 0)
    with ref.x64():
        want64 = ref.flash_decode_ref(
            jnp.asarray(np.asarray(q, np.float64)),
            jnp.asarray(np.asarray(kc, np.float64)),
            jnp.asarray(np.asarray(vc, np.float64)), 13)
    want32 = ref.flash_decode_ref(q.astype(jnp.float32),
                                  kc.astype(jnp.float32),
                                  vc.astype(jnp.float32), 13)
    got = A.decode_attention_einsum(q, kc, vc, jnp.int32(13))
    _budget(got, want64, want32)


# ---------------------------------------------------------------------------
# paged flash decode: oracle agreement + exact-zero isolation
# ---------------------------------------------------------------------------

def _paged_inputs(n_pool, ps, n_kv, g, hd, table, positions, seed):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    b = len(table)
    q = _bf16(keys[0], b, 1, n_kv, g, hd)
    kp = _bf16(keys[1], n_pool, ps, n_kv, hd)
    vp = _bf16(keys[2], n_pool, ps, n_kv, hd)
    return (q, kp, vp, jnp.asarray(table, jnp.int32),
            jnp.asarray(positions, jnp.int32))


def _check_paged(n_pool, ps, n_kv, g, hd, table, positions, seed):
    q, kp, vp, tab, pos = _paged_inputs(n_pool, ps, n_kv, g, hd, table,
                                        positions, seed)
    with ref.x64():
        want64 = ref.paged_flash_decode_ref(
            jnp.asarray(np.asarray(q, np.float64)),
            jnp.asarray(np.asarray(kp, np.float64)),
            jnp.asarray(np.asarray(vp, np.float64)), tab, pos)
    want32 = ref.paged_flash_decode_ref(q.astype(jnp.float32),
                                        kp.astype(jnp.float32),
                                        vp.astype(jnp.float32), tab, pos)
    xla = fa.paged_flash_decode_xla(q, kp, vp, tab, pos, kv_tile=8)
    _budget(xla, want64, want32)
    pal = fa.paged_flash_decode_pallas(q, kp, vp, tab, pos.reshape(-1),
                                       interpret=True)
    _budget(pal, want64, want32)
    return xla, pal


PAGED_CASES = [
    # (n_pool, ps, table, positions, seed)
    (9, 8, [[0, 1, -1, -1], [3, 4, 5, -1]], [[13], [20]], 0),
    (5, 8, [[0, -1], [2, 3]], [[7], [15]], 1),
    (3, 4, [[1]], [[2]], 2),               # single page, KV < one tile
]


@pytest.mark.parametrize("n_pool,ps,table,positions,seed", PAGED_CASES)
def test_paged_matches_oracle(n_pool, ps, table, positions, seed):
    _check_paged(n_pool, ps, 2, 2, 16, table, positions, seed)


def test_paged_idle_lane_exact_zero():
    """An unmapped lane (all pages -1, position -1) produces EXACT zeros
    on both the Pallas kernel and the XLA mirror — the PR 8 bitwise
    lane-isolation invariant depends on it."""
    q, kp, vp, tab, pos = _paged_inputs(
        9, 8, 2, 2, 16, [[0, 1, -1, -1], [-1, -1, -1, -1]],
        [[13], [-1]], 3)
    xla = fa.paged_flash_decode_xla(q, kp, vp, tab, pos, kv_tile=8)
    pal = fa.paged_flash_decode_pallas(q, kp, vp, tab, pos.reshape(-1),
                                       interpret=True)
    assert float(jnp.max(jnp.abs(xla[1].astype(jnp.float32)))) == 0.0
    assert float(jnp.max(jnp.abs(pal[1].astype(jnp.float32)))) == 0.0


def test_paged_neighbor_isolation_bitwise():
    """Lane 0's output is bitwise independent of what lane 1's pages
    hold — remapping lane 1 must not change lane 0."""
    q, kp, vp, tab, pos = _paged_inputs(
        9, 8, 2, 2, 16, [[0, 1, -1, -1], [3, 4, 5, -1]], [[13], [20]], 4)
    a = fa.paged_flash_decode_xla(q, kp, vp, tab, pos, kv_tile=8)
    tab2 = tab.at[1].set(jnp.asarray([6, 7, -1, -1], jnp.int32))
    pos2 = pos.at[1].set(9)
    b = fa.paged_flash_decode_xla(q, kp, vp, tab2, pos2, kv_tile=8)
    np.testing.assert_array_equal(
        np.asarray(a[0].astype(jnp.float32)),
        np.asarray(b[0].astype(jnp.float32)))


def test_paged_prefill_chunk_s_gt_1():
    """The XLA mirror serves chunked prefill (S > 1) — same oracle."""
    keys = jax.random.split(jax.random.PRNGKey(5), 3)
    q = _bf16(keys[0], 1, 4, 2, 2, 16)
    kp = _bf16(keys[1], 5, 8, 2, 16)
    vp = _bf16(keys[2], 5, 8, 2, 16)
    tab = jnp.asarray([[0, 1]], jnp.int32)
    pos = jnp.asarray([[8, 9, 10, 11]], jnp.int32)
    with ref.x64():
        want64 = ref.paged_flash_decode_ref(
            jnp.asarray(np.asarray(q, np.float64)),
            jnp.asarray(np.asarray(kp, np.float64)),
            jnp.asarray(np.asarray(vp, np.float64)), tab, pos)
    want32 = ref.paged_flash_decode_ref(q.astype(jnp.float32),
                                        kp.astype(jnp.float32),
                                        vp.astype(jnp.float32), tab, pos)
    got = kops.paged_flash_decode(q, kp, vp, tab, pos, mode="xla")
    _budget(got, want64, want32)


# ---------------------------------------------------------------------------
# properties — random sweeps under hypothesis, fixed smoke subset without
# ---------------------------------------------------------------------------

if HAVE_HYPOTHESIS:
    @settings(max_examples=10, deadline=None)
    @given(sq=st.integers(1, 14), n_kv=st.sampled_from([1, 2]),
           g=st.sampled_from([1, 2]), hd=st.sampled_from([12, 16, 20]),
           kind=st.sampled_from(["global", "local", "full"]),
           seed=st.integers(0, 2 ** 16))
    def test_prefill_shape_sweep(sq, n_kv, g, hd, kind, seed):
        _check_prefill(1, sq, n_kv * g, n_kv, hd, kind, seed,
                       window=3 if kind == "local" else 0)

    @settings(max_examples=10, deadline=None)
    @given(kv_len=st.integers(1, 40), n_kv=st.sampled_from([1, 2]),
           g=st.sampled_from([1, 2, 4]), hd=st.sampled_from([12, 16]),
           frac=st.floats(0.0, 1.0), seed=st.integers(0, 2 ** 16))
    def test_decode_shape_sweep(kv_len, n_kv, g, hd, frac, seed):
        pos = min(kv_len - 1, int(frac * kv_len))
        _check_decode(1, kv_len, n_kv, g, hd, pos, seed)


# ---------------------------------------------------------------------------
# HLO regression: no full-cache fp32 upcast in the traced decode step
# ---------------------------------------------------------------------------

def _decode_unopt_hlo(model, b, s, new):
    from repro.serve.engine import ServeConfig, ServeEngine
    scfg = ServeConfig(max_new_tokens=new, guards=False,
                       on_nonfinite="off")
    lowered, _ = ServeEngine.decode_step_lowered(model, scfg, b, s)
    return lowered.as_text(dialect="hlo")


def _big_upcasts(hlo_text, limit):
    from repro.analysis.hlo_graph import parse_hlo
    from repro.analysis.passes import dtype_flow_pass
    findings, metrics = dtype_flow_pass(
        parse_hlo(hlo_text), {"forbid_big_upcast_elems": limit})
    return ([f for f in findings if f.code == "full-pool-upcast"],
            metrics)


def _smoke_model():
    import dataclasses as dc
    from repro.configs import get_config
    from repro.launch.mesh import make_mesh
    from repro.models.lm import Model
    cfg = dc.replace(get_config("internlm2-1.8b", smoke=True), d_ff=96)
    return Model(cfg, make_mesh(1, 1))


def test_decode_trace_has_no_full_cache_upcast():
    """Satellite-1 regression: with flash decode wired in, the traced
    decode step's program never widens a whole KV cache in one convert.
    max_len=64 spans two kv tiles, so the per-tile converts (<= 2048
    elems) sit well under the full-view threshold (4096)."""
    b, s, new = 2, 16, 48
    model = _smoke_model()
    limit = b * (s + new) * model.cfg.n_kv_heads * model.cfg.head_dim
    assert A.use_flash_attention()
    found, metrics = _big_upcasts(_decode_unopt_hlo(model, b, s, new),
                                  limit)
    assert not found, [f.format() for f in found]
    # the parse actually saw the program (guard against a silent
    # parser miss making this test vacuous)
    assert metrics["float_widening_converts"] > 0
    assert 0 < metrics["max_widening_convert_elems"] < limit


def test_full_cache_upcast_detector_fires_on_old_pattern():
    """Negative control: resurrect the pre-fix einsum decode (explicit
    .astype(f32) on the whole cache) and assert the detector fires —
    without this, the positive test could pass vacuously."""
    b, s, new = 2, 16, 48
    model = _smoke_model()
    limit = b * (s + new) * model.cfg.n_kv_heads * model.cfg.head_dim

    def buggy(q, k_cache, v_cache, pos, *, kind="global", window=0,
              softcap=None):
        hd = q.shape[-1]
        qf = q.astype(jnp.float32) * (hd ** -0.5)
        s_ = jnp.einsum("bqkgd,bKkd->bkgqK", qf,
                        k_cache.astype(jnp.float32))
        slots = jnp.arange(k_cache.shape[1])
        valid = slots >= 0 if kind == "full" else slots <= pos
        s_ = jnp.where(valid[None, None, None, None, :], s_, A._NEG)
        m = jnp.max(s_, axis=-1, keepdims=True)
        p = jnp.where(valid[None, None, None, None, :],
                      jnp.exp(s_ - m), 0.0)
        out = jnp.einsum("bkgqK,bKkd->bkgqd", p,
                         v_cache.astype(jnp.float32))
        out = out / jnp.maximum(jnp.sum(p, axis=-1)[..., None], 1e-30)
        return jnp.einsum("bkgqd->bqkgd", out).astype(q.dtype)

    orig_flash, orig_einsum = A.use_flash_attention(), \
        A.decode_attention_einsum
    A.set_flash_attention(False)
    A.decode_attention_einsum = buggy
    try:
        found, _ = _big_upcasts(_decode_unopt_hlo(model, b, s, new),
                                limit)
    finally:
        A.set_flash_attention(orig_flash)
        A.decode_attention_einsum = orig_einsum
    assert len(found) >= 2, [f.format() for f in found]  # K and V pools


# ---------------------------------------------------------------------------
# dispatch + toggle plumbing
# ---------------------------------------------------------------------------

def test_flash_toggle_roundtrip():
    on = A.use_flash_attention()
    try:
        A.set_flash_attention(False)
        assert not A.use_flash_attention()
        A.set_flash_attention(True)
        assert A.use_flash_attention()
    finally:
        A.set_flash_attention(on)


def test_decode_dispatch_flash_vs_einsum_agree():
    """decode_attention routes global/full kinds to flash_decode; the
    two implementations must agree within the oracle budget of each
    other (they share the masked-softmax semantics)."""
    q, kc, vc = _decode_inputs(2, 22, 2, 2, 16, 7)
    flash = A.decode_attention(q, kc, vc, jnp.int32(13))
    ein = A.decode_attention_einsum(q, kc, vc, jnp.int32(13))
    np.testing.assert_allclose(
        np.asarray(flash, np.float32), np.asarray(ein, np.float32),
        atol=3e-2, rtol=0)
