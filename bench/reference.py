"""The plain float32 reference: the configuration's forward pass in
``jax.numpy`` at ``highest`` matmul precision, with no kernel, cache or
batching.  It imports nothing of the program and takes nothing the
program made: its weights come from ``weights.make`` and the seed.

The forward pass is the architecture module's (``bench/arch/<arch>.py``,
``forward_hidden``), which builds on the helpers here: ``rms`` (RMSNorm
``x_hat * (1 + w)``), ``rope`` (the first and second halves of each head
rotated as a pair) and ``attention`` (causal GQA, optionally inside a
sliding window).  ``logits`` is the default head: the final hidden
states against the output head (the embedding where the configuration
ties them), divided by ``logits_scaling``; a module exports its own
``logits`` where its head differs.

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

import weights

Q_BLOCK = 512


def rms(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + w)


def rope(x, pos, theta):
    """x [S, n, hd]; pos [S]."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None, None] * freq
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def attention(q, k, v, scale, window=None):
    """Causal GQA attention, q [S, H, hd], k/v [S, KV, hd], in query
    blocks so that the scores of one block are all that is held.  With
    ``window`` a query at position p sees the keys at p - window + 1 ..
    p (the program's 'local' kind); ``window`` may be traced."""
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
        keep = kpos[None, None, :] <= qpos[None, :, None]
        if window is not None:
            keep &= qpos[None, :, None] - kpos[None, None, :] < window
        sc = jnp.where(keep, sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, ve)

    return jax.lax.map(block, jnp.arange(nb)).reshape(s, h, hd)


def logits(cfg: Dict, w: Dict, hidden):
    """Logits [R, V] of final hidden states [R, d] against the output
    head (the embedding where the configuration ties them)."""
    head = w["head"] if "head" in w else w["embed"]
    return hidden @ head.astype(jnp.float32).T / cfg["logits_scaling"]


@functools.lru_cache(maxsize=None)
def _program(arch, cfg_items: Tuple, seq: int, rows: int):
    cfg = dict(cfg_items)
    head = getattr(arch, "logits", logits)

    def run(w, tokens, at, targets):
        with jax.default_matmul_precision("highest"):
            hf = arch.forward_hidden(cfg, w, tokens)[at]    # [R, d]
            lg = head(cfg, w, hf)                             # [R, V]
            best = jnp.max(lg, axis=-1)
            chosen = jnp.take_along_axis(lg, targets[:, None], 1)[:, 0]
            return best, chosen

    return jax.jit(run)


def padded_len(n: int) -> int:
    return -(-n // Q_BLOCK) * Q_BLOCK


def served_gaps(arch, cfg: Dict, w: Dict, prompt: np.ndarray,
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
    best, chosen = _program(arch, weights.scalars(cfg), seq, rows)(
        w, toks, at, tg)
    return (np.asarray(best, np.float64) - np.asarray(chosen, np.float64))[:n]


def max_gap(arch, cfg: Dict, w: Dict,
            pairs: Sequence[Tuple[np.ndarray, np.ndarray]],
            seq: int, rows: int) -> Tuple[float, List[float]]:
    """The widest gap over every served token of ``pairs`` (prompt,
    served tokens), and each request's own widest gap."""
    per = [float(np.max(served_gaps(arch, cfg, w, p, t, seq, rows)))
           for p, t in pairs]
    return (max(per) if per else float("nan")), per
