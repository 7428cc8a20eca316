"""The yardstick reads the same with the architecture in a module of its
own: weights, program parameters, reference gaps, FLOP counts and page
bytes of the dense configurations, against values recorded from the
harness before ``bench/arch/`` existed (tiny: on the CPU)."""
import hashlib
import json
import os

import jax
import numpy as np
import pytest

import tinyroot
import manifest
import reference
import weights

SEED = 2**31 + 11
PROMPT = [7740, 5120, 5604, 7349, 4737, 6354, 6829, 1844, 454, 2458, 2335,
          7156, 7476, 43, 4094, 6727, 1076, 6529, 975, 3833, 6688, 2482,
          2798, 2280, 5893, 2087, 8113, 3646, 3916, 4133, 4772, 4534, 4173,
          8155, 6616, 6493, 5736, 5096, 2793, 8101]
SERVED = [3819, 1763, 6922, 1312, 7024, 5017, 939, 359]
GAPS = ["0x1.a161e64000000p+1", "0x1.7c51698000000p+1",
        "0x1.2c04f30000000p+2", "0x1.0693f11a38000p+2",
        "0x1.45678b0000000p+2", "0x1.6c1e528000000p+1",
        "0x1.0f8e4fe000000p+2", "0x1.5962d38000000p+2"]
WEIGHTS = "203acea82f9a3591c4c02665b484c35883b157332ee55eed6e12531c5b513bbe"
PARAMS = "c8856bc0ec575953d57b465a00c19e63952ee97e04b493fd459e8f4a4199d7c8"
# (position, logits) -> token_flops; (start, n, last) -> chunk_flops
TOKENS = [(0, False), (37, True), (4000, True)]
CHUNKS = [(0, 64, False), (960, 64, True)]
COUNTS = {
    "tiny": ([2361344, 6631424, 14747648], [155254784, 285278208], 16384),
    "internlm2-1.8b": ([3020095488, 3406430208, 4185587712],
                       [193682472960, 206141128704], 1572864),
}
FILES = {"tiny": os.path.join(tinyroot.TINY, "tiny.json"),
         "internlm2-1.8b": os.path.join(tinyroot.BENCH, "configs",
                                        "internlm2-1.8b.json")}


def _cfg(name):
    with open(FILES[name]) as f:
        c = json.load(f)
    c["name"] = name
    return c


def _digest(tree) -> str:
    h = hashlib.sha256()
    for path, a in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = np.asarray(a)
        for part in (jax.tree_util.keystr(path), str(a.dtype), str(a.shape)):
            h.update(part.encode())
        h.update(a.tobytes())
    return h.hexdigest()


@pytest.fixture(scope="module")
def dense():
    return manifest.arch_module(tinyroot.REPO, "dense_gqa")


@pytest.fixture(scope="module")
def tiny_weights(dense):
    return weights.make(dense, _cfg("tiny"), SEED)


def test_weights_are_the_same_bits(tiny_weights):
    assert _digest(tiny_weights) == WEIGHTS


def test_program_params_are_the_same_bits(dense):
    import serve_adapter
    from repro.launch.mesh import make_mesh
    from repro.models.lm import Model
    cfg = _cfg("tiny")
    model = Model(dense.arch_config(cfg), make_mesh(1, 1))
    assert _digest(serve_adapter.program_params(model, dense, cfg,
                                                SEED)) == PARAMS


def test_reference_gaps_are_the_same(dense, tiny_weights):
    got = reference.served_gaps(dense, _cfg("tiny"), tiny_weights,
                                np.array(PROMPT, np.int32),
                                np.array(SERVED, np.int32),
                                reference.padded_len(144), 24)
    assert [float(g).hex() for g in got] == GAPS


@pytest.mark.parametrize("name", sorted(COUNTS))
def test_counts_are_the_same(dense, name):
    cfg = _cfg(name)
    tokens, chunks, page = COUNTS[name]
    assert [dense.token_flops(cfg, p, lg) for p, lg in TOKENS] == tokens
    assert [dense.chunk_flops(cfg, *c) for c in CHUNKS] == chunks
    assert dense.page_bytes(cfg, 16) == page
