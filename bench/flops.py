"""Operations and bytes of a dense GQA model's work (every layer's
attention global, its whole MLP active), from a configuration's shapes
alone.  ``bench/arch/dense_gqa.py`` exports these counts; a module of
another layout brings its own, built on the helpers here where they fit.

Counts are of useful work: real tokens at their real context lengths,
never the padding rows or idle lanes a step may carry, so they stay the
same whatever implements the step.  A multiply-add is 2 operations.

``cfg`` is a configuration file's dict (``bench/configs/<name>.json``).
"""
from __future__ import annotations

from typing import Dict, Iterable, List, Tuple


def dims(cfg: Dict) -> Tuple[int, int, int, int, int, int, int]:
    return (cfg["hidden_size"], cfg["intermediate_size"],
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"], cfg["num_hidden_layers"], cfg["vocab_size"])


def projections(cfg: Dict) -> List[Tuple[str, int, int]]:
    """(name, K, N) of every weight GEMM of one layer."""
    d, ff, h, kv, hd, _, _ = dims(cfg)
    return [("wqkv", d, (h + 2 * kv) * hd), ("wo", h * hd, d),
            ("gate", d, ff), ("up", d, ff), ("down", ff, d)]


def layer_matmul_params(cfg: Dict) -> int:
    return sum(k * n for _, k, n in projections(cfg))


def matmul_params(cfg: Dict) -> int:
    """Weights a token is multiplied through: every layer's projections
    (the head is counted apart, only for tokens whose logits are used)."""
    return dims(cfg)[5] * layer_matmul_params(cfg)


def head_flops(cfg: Dict) -> int:
    d, *_, v = dims(cfg)
    return 2 * d * v


def attention_flops(cfg: Dict, n_keys: int) -> int:
    """One query attending to ``n_keys`` keys, over all layers: QK^T and
    PV, 2 * 2 * heads * head_dim per key."""
    _, _, h, _, hd, n, _ = dims(cfg)
    return n * 4 * h * hd * n_keys


def token_flops(cfg: Dict, position: int, logits: bool) -> int:
    """One token at 0-based ``position`` (it attends to position + 1
    keys); ``logits`` adds the head, as for a prompt's last token and for
    every decoded token."""
    f = 2 * matmul_params(cfg) + attention_flops(cfg, position + 1)
    return f + (head_flops(cfg) if logits else 0)


def chunk_flops(cfg: Dict, start: int, n: int, last: bool) -> int:
    """A prefill chunk of ``n`` prompt tokens at positions start..start+n-1;
    ``last`` when it ends the prompt (its last token's logits are used)."""
    _, _, h, _, hd, layers, _ = dims(cfg)
    keys = n * start + n * (n + 1) // 2           # sum of (p + 1)
    f = 2 * matmul_params(cfg) * n + layers * 4 * h * hd * keys
    return f + (head_flops(cfg) if last else 0)


def gemm_cost(m: int, k: int, n: int, itemsize: int) -> Tuple[int, int]:
    """(operations, bytes) of one [m, k] x [k, n] GEMM with every operand
    and the result at ``itemsize`` bytes a value."""
    return 2 * m * k * n, itemsize * (m * k + k * n + m * n)


def decode_gemm_cost(cfg: Dict, m: int, itemsize: int) -> Tuple[int, int]:
    """(operations, bytes) of the layer projections of one decode step of
    ``m`` rows, over all layers."""
    layers = dims(cfg)[5]
    fl = by = 0
    for _, k, n in projections(cfg):
        f, b = gemm_cost(m, k, n, itemsize)
        fl, by = fl + f, by + b
    return layers * fl, layers * by


def paged_attention_cost(cfg: Dict, lanes: Iterable[Tuple[int, int]],
                         page_size: int, itemsize: int) -> Tuple[int, int]:
    """(operations, bytes) of the paged attention of one prefill chunk,
    over all layers.  ``lanes`` holds (start, n) of each lane that
    prefills: queries at positions start..start+n-1 against the lane's
    keys up to start+n-1 (causal).  Bytes: the K and V of the pages the
    lane holds up to its last position, its queries and its outputs."""
    _, _, h, kv, hd, layers, _ = dims(cfg)
    fl = by = 0
    for start, n in lanes:
        keys = n * start + n * (n + 1) // 2
        fl += 4 * h * hd * keys
        pages = -(-(start + n) // page_size)
        by += itemsize * (2 * pages * page_size * kv * hd + 2 * n * h * hd)
    return layers * fl, layers * by


def least_seconds(flops: float, nbytes: float, peak_flops: float,
                  peak_bw: float) -> float:
    """The roofline: the least time the chip could take."""
    return max(flops / peak_flops, nbytes / peak_bw)
