"""The plain float32 reference: the configuration's forward pass in
``jax.numpy`` at ``highest`` matmul precision, with no kernel, cache or
batching.  It imports nothing of the program and takes nothing the
program made: its weights come from ``weights.make`` and the seed.

It follows the configuration as it is run (``bench/configs/*.json``):
token embedding times ``embedding_multiplier``; per layer an RMSNorm
``x_hat * (1 + w)`` with ``rms_norm_eps``, GQA attention with rotary
embeddings (the first and second halves of each head rotated as a pair,
``rope_theta``), scores scaled by ``attention_multiplier``, causal
softmax, the output projection added to the residual (times
``residual_multiplier``), then a second norm and the SwiGLU MLP
``down(silu(gate x) * up x)`` added the same way; a final norm and
logits against the output head (the embedding where the configuration
ties them), divided by ``logits_scaling``.

``served_gaps`` is the comparison that decides ``correct``: for each
token a request was served, the gap by which the reference's logit of
that token lies below the reference's best logit at the same position.
Under greedy decoding a served token is right when that gap is zero up
to the rounding of the precision it was computed in.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 512


def _rms(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + w)


def _rope(x, pos, theta):
    """x [S, n, hd]; pos [S]."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None, None] * freq
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _attention(q, k, v, scale):
    """Causal GQA attention, q [S, H, hd], k/v [S, KV, hd], in query
    blocks so that the scores of one block are all that is held."""
    s, h, hd = q.shape
    g = h // k.shape[1]
    ke = jnp.repeat(k, g, axis=1)
    ve = jnp.repeat(v, g, axis=1)
    nb = s // Q_BLOCK
    kpos = jnp.arange(s)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK)
        sc = jnp.einsum("qhd,khd->hqk", qb, ke) * scale
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        sc = jnp.where(kpos[None, None, :] <= qpos[None, :, None], sc,
                       -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, ve)

    return jax.lax.map(block, jnp.arange(nb)).reshape(s, h, hd)


def forward_hidden(cfg: Dict, w: Dict, tokens):
    """Final normed hidden states [S, d] of one sequence (S a multiple of
    ``Q_BLOCK``), float32 throughout."""
    f32 = jnp.float32
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    h_, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    res = cfg["residual_multiplier"]
    s = tokens.shape[0]
    pos = jnp.arange(s)
    x = w["embed"][tokens].astype(f32) * cfg["embedding_multiplier"]

    def layer(x, lw):
        lw = {k: a.astype(f32) for k, a in lw.items()}
        y = _rms(x, lw["ln1"], eps)
        q = _rope((y @ lw["wq"]).reshape(s, h_, hd), pos, theta)
        k = _rope((y @ lw["wk"]).reshape(s, kv, hd), pos, theta)
        v = (y @ lw["wv"]).reshape(s, kv, hd)
        a = _attention(q, k, v, cfg["attention_multiplier"])
        x = x + res * (a.reshape(s, h_ * hd) @ lw["wo"])
        y = _rms(x, lw["ln2"], eps)
        x = x + res * ((jax.nn.silu(y @ lw["gate"]) * (y @ lw["up"]))
                       @ lw["down"])
        return x, None

    names = ("ln1", "wq", "wk", "wv", "wo", "ln2", "gate", "up", "down")
    x, _ = jax.lax.scan(layer, x, {k: w[k] for k in names})
    return _rms(x, w["final_norm"].astype(f32), eps)


@functools.lru_cache(maxsize=None)
def _program(cfg_items: Tuple, seq: int, rows: int):
    cfg = dict(cfg_items)

    def run(w, tokens, at, targets):
        with jax.default_matmul_precision("highest"):
            hf = forward_hidden(cfg, w, tokens)[at]          # [R, d]
            head = w["head"] if "head" in w else w["embed"]
            logits = (hf @ head.astype(jnp.float32).T         # [R, V]
                      / cfg["logits_scaling"])
            best = jnp.max(logits, axis=-1)
            chosen = jnp.take_along_axis(logits, targets[:, None], 1)[:, 0]
            return best, chosen

    return jax.jit(run)


def padded_len(n: int) -> int:
    return -(-n // Q_BLOCK) * Q_BLOCK


def served_gaps(cfg: Dict, w: Dict, prompt: np.ndarray,
                served: np.ndarray, seq: int, rows: int) -> np.ndarray:
    """Per served token, the reference's best logit minus its logit of the
    served token, at the position that produced it.  ``seq`` and ``rows`` fix
    the program's shapes (the cell's longest sequence and output), so one
    compiled program serves every request of a cell."""
    n = len(served)
    if len(prompt) + n - 1 > seq or n > rows:
        raise ValueError(f"request of {len(prompt)}+{n} tokens exceeds the "
                         f"reference's {seq} positions / {rows} rows")
    toks = np.zeros((seq,), np.int32)
    full = np.concatenate([prompt, served[:-1]]).astype(np.int32)
    toks[:len(full)] = full
    at = np.zeros((rows,), np.int32)
    at[:n] = len(prompt) - 1 + np.arange(n)
    tg = np.zeros((rows,), np.int32)
    tg[:n] = served
    items = tuple(sorted(cfg.items(), key=lambda kv: kv[0]))
    items = tuple((k, v) for k, v in items if not isinstance(v, (dict, list)))
    best, chosen = _program(items, seq, rows)(w, toks, at, tg)
    return (np.asarray(best, np.float64) - np.asarray(chosen, np.float64))[:n]


def max_gap(cfg: Dict, w: Dict, pairs: Sequence[Tuple[np.ndarray,
                                                       np.ndarray]],
            seq: int, rows: int) -> Tuple[float, List[float]]:
    """The widest gap over every served token of ``pairs`` (prompt,
    served tokens), and each request's own widest gap."""
    per = [float(np.max(served_gaps(cfg, w, p, t, seq, rows)))
           for p, t in pairs]
    return (max(per) if per else float("nan")), per
