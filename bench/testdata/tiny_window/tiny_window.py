"""A test architecture (``"arch": "tiny_window"``), added beside
``dense_gqa`` as new files only: internlm2's layers with sliding-window
('local') and 'global' attention alternating, layer 0 local
(``block_pattern=("local", "global")``), the window the configuration's
``sliding_window``.

It keeps the dense module's plain layout, constants, projection counts
and pages (the program's pool holds whole pages for local layers too),
and brings its own reference, program tree and attention counts.  The
window is the program's ('local' in ``models/attention.py`` and
``_paged_live_pages``): a query at position p sees the keys at
p - window + 1 .. p.
"""
from __future__ import annotations

import dataclasses
import os
from typing import Dict, Iterable, List, Optional, Tuple

import jax
import jax.numpy as jnp

import flops
import manifest
import reference

PATTERN = ("local", "global")
dense = manifest.arch_module(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))), "dense_gqa")

shapes = dense.shapes
page_bytes = dense.page_bytes
decode_gemm_cost = dense.decode_gemm_cost


def windows(cfg: Dict) -> List[Optional[int]]:
    """Each layer's window; None for a global layer."""
    return [cfg["sliding_window"] if PATTERN[i % len(PATTERN)] == "local"
            else None for i in range(cfg["num_hidden_layers"])]


def forward_hidden(cfg: Dict, w: Dict, tokens):
    """The dense layer with each layer's window, float32 throughout."""
    f32 = jnp.float32
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    h_, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    res = cfg["residual_multiplier"]
    s = tokens.shape[0]
    pos = jnp.arange(s)
    x = w["embed"][tokens].astype(f32) * cfg["embedding_multiplier"]
    for i, win in enumerate(windows(cfg)):
        lw = {k: w[k][i].astype(f32) for k in dense.LAYER}
        y = reference.rms(x, lw["ln1"], eps)
        q = reference.rope((y @ lw["wq"]).reshape(s, h_, hd), pos, theta)
        k = reference.rope((y @ lw["wk"]).reshape(s, kv, hd), pos, theta)
        v = (y @ lw["wv"]).reshape(s, kv, hd)
        a = reference.attention(q, k, v, cfg["attention_multiplier"], win)
        x = x + res * (a.reshape(s, h_ * hd) @ lw["wo"])
        y = reference.rms(x, lw["ln2"], eps)
        x = x + res * ((jax.nn.silu(y @ lw["gate"]) * (y @ lw["up"]))
                       @ lw["down"])
    return reference.rms(x, w["final_norm"].astype(f32), eps)


def arch_config(cfg: Dict):
    if cfg["num_hidden_layers"] % len(PATTERN):
        raise ValueError(f"{cfg['name']}: layers are not whole patterns")
    return dataclasses.replace(dense.arch_config(cfg), block_pattern=PATTERN,
                               window=cfg["sliding_window"])


def to_program(model, w: Dict) -> Dict:
    """The dense packing once per block of the pattern, over the layers
    that block holds (layer i is block i % 2 of group i // 2)."""
    n = len(PATTERN)
    per = [dense.to_program(model, {k: (a[i::n] if k in dense.LAYER else a)
                                    for k, a in w.items()})
           for i in range(n)]
    out = per[0]
    out["groups"] = {f"b{i}": p["groups"]["b0"] for i, p in enumerate(per)}
    return out


def _keys(win: Optional[int], position: int) -> int:
    return position + 1 if win is None else min(position + 1, win)


def token_flops(cfg: Dict, position: int, logits: bool) -> int:
    _, _, h, _, hd, _, _ = flops.dims(cfg)
    keys = sum(_keys(win, position) for win in windows(cfg))
    f = 2 * flops.matmul_params(cfg) + 4 * h * hd * keys
    return f + (flops.head_flops(cfg) if logits else 0)


def chunk_flops(cfg: Dict, start: int, n: int, last: bool) -> int:
    return sum(token_flops(cfg, p, last and p == start + n - 1)
               for p in range(start, start + n))


def paged_attention_cost(cfg: Dict, lanes: Iterable[Tuple[int, int]],
                         page_size: int, itemsize: int) -> Tuple[int, int]:
    """As ``flops.paged_attention_cost``; a local layer reads the pages
    from the first one its first query's window reaches."""
    _, _, h, kv, hd, _, _ = flops.dims(cfg)
    fl = by = 0
    for start, n in lanes:
        for win in windows(cfg):
            fl += 4 * h * hd * sum(_keys(win, p)
                                   for p in range(start, start + n))
            first = 0 if win is None else max(start - win + 1, 0) // page_size
            pages = -(-(start + n) // page_size) - first
            by += itemsize * (2 * pages * page_size * kv * hd
                              + 2 * n * h * hd)
    return fl, by
