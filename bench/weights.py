"""The benchmark's weights: drawn on the device from ``--seed`` in one
jitted call, in the dtype they are served in.

The layout is the architecture module's (``bench/arch/<arch>.py``,
``shapes``): the plain one of the published architecture, so the
reference reads it as it is; the module's ``to_program`` packs it into
the program's own tree.  Random weights are enough for speed and for
agreement with the reference.

Scales, as the modules draw them: every matrix N(0, 1/fan_in) (fan_in =
its input width; the embedding and the head, whose rows are model-width
vectors, N(0, 1/hidden)), each norm's learned offset N(0, NORM_STD^2)
(the configurations apply a norm as x_hat * (1 + w)).
"""
from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

NORM_STD = 0.1


def seed_words(seed: int) -> Tuple[np.uint32, np.uint32]:
    """A seed of up to 64 bits as two 32-bit words (traced arguments, so
    one compiled program serves every seed)."""
    s = int(seed) & (2**64 - 1)
    return np.uint32(s & 0xFFFFFFFF), np.uint32(s >> 32)


def scalars(cfg: Dict) -> Tuple:
    """Every scalar of a configuration, sorted: a cache key that changes
    with any size or constant the file states."""
    return tuple((k, v) for k, v in sorted(cfg.items())
                 if not isinstance(v, (dict, list)))


def draw(arch, cfg: Dict, lo, hi, transform=None):
    """Traceable: every leaf of ``arch.shapes(cfg)`` from the seed words;
    ``transform`` (traced too) may repack the result."""
    key = jax.random.fold_in(jax.random.fold_in(jax.random.PRNGKey(0), lo),
                             hi)
    out = {}
    for i, (name, (shape, std, dt)) in enumerate(arch.shapes(cfg).items()):
        x = jax.random.normal(jax.random.fold_in(key, i), shape,
                              jnp.float32)
        out[name] = (x * std).astype(jnp.dtype(dt))
    return transform(out) if transform is not None else out


@functools.lru_cache(maxsize=None)
def _jitted(arch, cfg_items: Tuple):
    cfg = dict(cfg_items)
    return jax.jit(lambda lo, hi: draw(arch, cfg, lo, hi))


def make(arch, cfg: Dict, seed: int) -> Dict[str, jax.Array]:
    """The plain-layout weights of ``seed`` on the default device."""
    return _jitted(arch, scalars(cfg))(*seed_words(seed))
