"""Dense GQA decoder (``"arch": "dense_gqa"``; internlm2): every layer
global attention over grouped KV heads and a SwiGLU MLP.

Plain layout (``shapes``): separate q/k/v/o and gate/up/down, per-layer
norms stacked on a leading layer axis, an output head of its own unless
the configuration ties it to the embedding.

Reference (``forward_hidden``), as the configuration is run: token
embedding times ``embedding_multiplier``; per layer an RMSNorm with
``rms_norm_eps``, GQA attention with rotary embeddings (``rope_theta``),
scores scaled by ``attention_multiplier``, causal softmax, the output
projection added to the residual (times ``residual_multiplier``), then a
second norm and the SwiGLU MLP ``down(silu(gate x) * up x)`` added the
same way; a final norm.  The default head (``reference.logits``) follows.

The program imports stay inside ``arch_config`` and ``to_program``, so
the reference's half of this module imports nothing of the program.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

import reference
from flops import (chunk_flops, decode_gemm_cost,  # noqa: F401
                   paged_attention_cost, token_flops)
from weights import NORM_STD

# per-layer leaves, stacked on a leading layer axis
LAYER = ("ln1", "wq", "wk", "wv", "wo", "ln2", "gate", "up", "down")


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


def forward_hidden(cfg: Dict, w: Dict, tokens):
    """Final normed hidden states [S, d] of one sequence (S a multiple of
    ``reference.Q_BLOCK``), float32 throughout."""
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
        y = reference.rms(x, lw["ln1"], eps)
        q = reference.rope((y @ lw["wq"]).reshape(s, h_, hd), pos, theta)
        k = reference.rope((y @ lw["wk"]).reshape(s, kv, hd), pos, theta)
        v = (y @ lw["wv"]).reshape(s, kv, hd)
        a = reference.attention(q, k, v, cfg["attention_multiplier"])
        x = x + res * (a.reshape(s, h_ * hd) @ lw["wo"])
        y = reference.rms(x, lw["ln2"], eps)
        x = x + res * ((jax.nn.silu(y @ lw["gate"]) * (y @ lw["up"]))
                       @ lw["down"])
        return x, None

    x, _ = jax.lax.scan(layer, x, {k: w[k] for k in LAYER})
    return reference.rms(x, w["final_norm"].astype(f32), eps)


def arch_config(cfg: Dict):
    """The program's ``ArchConfig`` for a configuration file: the
    registry's entry with the file's sizes, dtypes and constants."""
    from repro.configs import get_config
    base = get_config(cfg["registry"])
    arch = dataclasses.replace(
        base, n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
        norm_eps=cfg["rms_norm_eps"], rope_theta=cfg["rope_theta"],
        param_dtype=cfg["param_dtype"], compute_dtype=cfg["compute_dtype"],
        block_pattern=("global",), gated_mlp=True, moe=False,
        tie_embeddings=cfg["tie_word_embeddings"], attn_softcap=None,
        final_softcap=None, rope_theta_global=None)
    # constants the program fixes in code: the file must state them as run
    want = {"embedding_multiplier": math.sqrt(arch.d_model),
            "attention_multiplier": arch.hd ** -0.5,
            "residual_multiplier": 1.0, "logits_scaling": 1.0}
    for k, v in want.items():
        if not math.isclose(cfg[k], v, rel_tol=1e-12):
            raise ValueError(f"{cfg['name']}: {k} is {cfg[k]}, the program "
                             f"runs {v}")
    return arch


def to_program(model, w: Dict) -> Dict:
    """Traceable: the plain layout -> the program's parameter tree
    (packed QKV, one-shard MLP weights, padded vocab)."""
    from repro.models import param as pm
    cfg = model.cfg
    defs = model.param_defs()
    vp = cfg.padded_vocab()
    qkv = pm.pack_views(defs["groups"]["b0"]["attn"]["wqkv"],
                        {"wq": w["wq"], "wk": w["wk"], "wv": w["wv"]})
    out = {
        "embed": jnp.pad(w["embed"], ((0, vp - cfg.vocab), (0, 0))),
        "final_norm": w["final_norm"],
        "groups": {"b0": {
            "ln1": w["ln1"],
            "attn": {"wqkv": qkv, "wo": w["wo"]},
            "ln2": w["ln2"],
            "ffn": {"up": w["up"][:, None], "gate": w["gate"][:, None],
                    "down": w["down"][:, None]},
        }},
        "tail": {},
    }
    if "head" in w:
        out["head"] = jnp.pad(w["head"], ((0, vp - cfg.vocab), (0, 0)))
    return out


def page_bytes(cfg: Dict, page_size: int) -> int:
    """Device bytes of one page: K and V of every layer, bf16."""
    return (cfg["num_hidden_layers"] * 2 * page_size
            * cfg["num_key_value_heads"] * cfg["head_dim"] * 2)
