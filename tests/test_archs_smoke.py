"""Per-arch smoke tests: REDUCED config of the same family, one forward +
train-loss step + prefill/decode consistency on CPU; asserts shapes and
finiteness (no NaNs)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config
from repro.launch.mesh import make_mesh
from repro.models.lm import Model

SEQ = 32


def _batch(cfg, b=2, s=SEQ, seed=0):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    text = s - (cfg.prefix_tokens or 0)
    batch = {
        "tokens": jax.random.randint(k1, (b, text), 0, cfg.vocab, jnp.int32),
        "targets": jax.random.randint(k2, (b, text), 0, cfg.vocab,
                                      jnp.int32),
    }
    if cfg.prefix_tokens:
        batch["patches"] = jax.random.normal(
            jax.random.PRNGKey(3), (b, cfg.prefix_tokens, cfg.d_model),
            jnp.float32)
    if cfg.encdec:
        batch["frames"] = jax.random.normal(
            jax.random.PRNGKey(4), (b, cfg.enc_frames, cfg.d_model),
            jnp.float32)
    return batch


@pytest.fixture(scope="module")
def mesh():
    return make_mesh(1, 1)


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_forward_and_loss(arch, mesh):
    cfg = get_config(arch, smoke=True)
    model = Model(cfg, mesh)
    params = model.init_params(0)
    batch = _batch(cfg)

    h, _, _ = jax.jit(
        lambda p, b: model.forward(p, b, mode="train"))(params, batch)
    assert h.shape == (2, SEQ, cfg.d_model)
    assert bool(jnp.all(jnp.isfinite(h.astype(jnp.float32))))

    loss = jax.jit(model.loss)(params, batch)
    assert loss.shape == ()
    assert bool(jnp.isfinite(loss))
    # untrained model should sit near uniform over the vocab
    assert 0.2 * np.log(cfg.vocab) < float(loss) < 3.0 * np.log(
        cfg.padded_vocab())


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_train_step_grads_finite(arch, mesh):
    cfg = get_config(arch, smoke=True)
    model = Model(cfg, mesh)
    params = model.init_params(0)
    batch = _batch(cfg)
    loss, grads = jax.jit(jax.value_and_grad(model.loss))(params, batch)
    assert bool(jnp.isfinite(loss))
    flat = jax.tree.leaves(grads)
    assert all(bool(jnp.all(jnp.isfinite(g.astype(jnp.float32))))
               for g in flat)
    # gradients actually flow (at least one nonzero leaf per tree)
    assert any(float(jnp.max(jnp.abs(g.astype(jnp.float32)))) > 0
               for g in flat)


def f64_reference_logits(cfg, params, fbatch, mesh):
    """Last-token teacher-forced logits with f64 params + f64 compute: the
    precision reference the consistency budget is measured against (norms
    still run their internal fp32 stages — the GEMM chain, where prefill
    vs decode rounding can diverge, is what runs at f64)."""
    import dataclasses as dc
    from repro.kernels.ref import x64
    from repro.models.loss import vocab_parallel_logits
    with x64():
        cfg64 = dc.replace(cfg, compute_dtype="float64",
                           param_dtype="float64")
        model64 = Model(cfg64, mesh)
        params64 = jax.tree.map(
            lambda x: x.astype(jnp.float64)
            if jnp.issubdtype(x.dtype, jnp.floating) else x, params)
        fbatch64 = {k: (v.astype(jnp.float64)
                        if jnp.issubdtype(v.dtype, jnp.floating) else v)
                    for k, v in fbatch.items()}
        h64, _, _ = model64.forward(params64, fbatch64, mode="train")
        ref64 = vocab_parallel_logits(h64[:, -1:],
                                      model64.head_weights(params64),
                                      model64.ctx, cfg.final_softcap)[:, 0]
        return np.asarray(ref64, np.float64)


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_prefill_decode_consistency(arch, mesh):
    """Greedy decode after prefill must match the teacher-forced forward.

    Budget policy (see ROADMAP): both paths are compared against an f64
    reference of the same computation; decode may be at most a small
    multiple of the teacher-forced path's own measured rounding error.
    The budget is derived from the pipeline's noise, not hand-tuned — a
    QKV-path change that rounds differently between prefill and decode
    (e.g. the old apply-time wq/wk/wv concat) blows it."""
    cfg = get_config(arch, smoke=True)
    model = Model(cfg, mesh)
    params = model.init_params(0)
    batch = _batch(cfg)

    logits_p, cache = jax.jit(
        lambda p, b: model.prefill(p, b, max_len=SEQ + 8))(params, batch)
    vp = cfg.padded_vocab()
    assert logits_p.shape == (2, vp)
    assert bool(jnp.all(jnp.isfinite(logits_p)))

    # one decode step
    tok = jnp.argmax(logits_p[:, :cfg.vocab], axis=-1)[:, None] \
        .astype(jnp.int32)
    pos = jnp.asarray(SEQ, jnp.int32)
    logits_d, cache2 = jax.jit(model.decode_step)(params, cache, tok, pos)
    assert logits_d.shape == (2, vp)
    assert bool(jnp.all(jnp.isfinite(logits_d)))

    # consistency: decode logits at step S for token t_S == forward logits
    # at position S when the same token is appended (teacher forcing)
    from repro.models.loss import vocab_parallel_logits
    full_tokens = jnp.concatenate([batch["tokens"], tok], axis=1)
    fbatch = dict(batch, tokens=full_tokens)
    h, _, _ = jax.jit(lambda p, b: model.forward(p, b, mode="train"))(
        params, fbatch)
    ref = vocab_parallel_logits(h[:, -1:], model.head_weights(params),
                                model.ctx, cfg.final_softcap)[:, 0]

    ref64 = f64_reference_logits(cfg, params, fbatch, mesh)
    scale = max(1.0, float(np.max(np.abs(ref64))))
    err_fwd = float(np.max(np.abs(np.asarray(ref, np.float64) - ref64)))
    err_dec = float(np.max(np.abs(np.asarray(logits_d, np.float64)
                                  - ref64)))
    # the low-precision pipeline itself must sit near the f64 reference
    assert err_fwd < 0.25 * scale, (err_fwd, scale)
    # decode accuracy within a small multiple of the forward path's own
    # rounding noise (floor: a few fp32 ulps of the logit scale)
    budget = 4.0 * err_fwd + 64 * np.finfo(np.float32).eps * scale
    assert err_dec <= budget, (
        f"decode drifted from the f64 reference: err_dec={err_dec:.3e} "
        f"> budget={budget:.3e} (err_fwd={err_fwd:.3e})")
