"""The benchmark's weights: drawn on the device from ``--seed`` in one
jitted call, in the dtype they are served in.

The layout is the plain one of the published architecture (separate
q/k/v/o, gate/up/down, per-layer norms stacked on a leading layer axis,
an output head of its own unless the configuration ties it to the
embedding), so the reference reads it as it is; ``serve_adapter`` packs
it into the program's own tree.  Random weights are enough for speed and
for agreement with the reference.

Scales: every matrix N(0, 1/fan_in) (fan_in = its input width; the
embedding and the head, whose rows are model-width vectors, N(0,
1/hidden)), each norm's learned offset N(0, 0.1^2) (the configurations
apply a norm as x_hat * (1 + w)).
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

NORM_STD = 0.1


def shapes(cfg: Dict) -> Dict[str, Tuple[Tuple[int, ...], float, str]]:
    """name -> (shape, std, dtype) of every leaf, in a fixed order."""
    d, ff = cfg["hidden_size"], cfg["intermediate_size"]
    h, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    n, v = cfg["num_hidden_layers"], cfg["vocab_size"]
    wd = cfg["param_dtype"]
    out = {
        "embed": ((v, d), 1 / math.sqrt(d), wd),
        "final_norm": ((d,), NORM_STD, "float32"),
        "ln1": ((n, d), NORM_STD, "float32"),
        "wq": ((n, d, h * hd), 1 / math.sqrt(d), wd),
        "wk": ((n, d, kv * hd), 1 / math.sqrt(d), wd),
        "wv": ((n, d, kv * hd), 1 / math.sqrt(d), wd),
        "wo": ((n, h * hd, d), 1 / math.sqrt(h * hd), wd),
        "ln2": ((n, d), NORM_STD, "float32"),
        "gate": ((n, d, ff), 1 / math.sqrt(d), wd),
        "up": ((n, d, ff), 1 / math.sqrt(d), wd),
        "down": ((n, ff, d), 1 / math.sqrt(ff), wd),
    }
    if not cfg["tie_word_embeddings"]:
        out["head"] = ((v, d), 1 / math.sqrt(d), wd)
    return out


def seed_words(seed: int) -> Tuple[np.uint32, np.uint32]:
    """A seed of up to 64 bits as two 32-bit words (traced arguments, so
    one compiled program serves every seed)."""
    s = int(seed) & (2**64 - 1)
    return np.uint32(s & 0xFFFFFFFF), np.uint32(s >> 32)


def draw(cfg: Dict, lo, hi, transform=None):
    """Traceable: every leaf of ``shapes(cfg)`` from the seed words;
    ``transform`` (traced too) may repack the result."""
    key = jax.random.fold_in(jax.random.fold_in(jax.random.PRNGKey(0), lo),
                             hi)
    out = {}
    for i, (name, (shape, std, dt)) in enumerate(shapes(cfg).items()):
        x = jax.random.normal(jax.random.fold_in(key, i), shape,
                              jnp.float32)
        out[name] = (x * std).astype(jnp.dtype(dt))
    return transform(out) if transform is not None else out


@functools.lru_cache(maxsize=None)
def _jitted(cfg_items: Tuple):
    cfg = dict(cfg_items)
    return jax.jit(lambda lo, hi: draw(cfg, lo, hi))


def make(cfg: Dict, seed: int) -> Dict[str, jax.Array]:
    """The plain-layout weights of ``seed`` on the default device."""
    key = tuple(sorted((k, cfg[k]) for k in
                       ("hidden_size", "intermediate_size",
                        "num_attention_heads", "num_key_value_heads",
                        "head_dim", "num_hidden_layers", "vocab_size",
                        "param_dtype", "tie_word_embeddings")))
    return _jitted(key)(*seed_words(seed))
