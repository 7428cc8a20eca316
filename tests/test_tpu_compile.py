"""Compile the main-path kernels for a TPU v5e at internlm2-1.8b widths.

No chip is needed: the TPU compiler compiles for a described ``v5e:2x2``
topology, and refuses what the chip would refuse (block shapes off the
tiling, more scoped VMEM than a kernel may use).  Interpret mode cannot
see either.  Kernels are called directly with ``interpret=False`` and the
blocks ``kernels.ops`` plans for them; the ``ops`` wrappers themselves
would take their CPU branch here.

The topology is described inside a module fixture (never at import): only
one process may load the TPU library at a time, and a test file that
loaded it while being collected would break every other worker.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import ops
from repro.kernels.epilogue import Epilogue
from repro.kernels.flash_attention import (flash_attention_pallas,
                                           flash_decode_pallas,
                                           paged_flash_decode_pallas)
from repro.kernels.matmul import matmul_pallas
from repro.kernels.quantize import quantize_rowwise_pallas

CFG = get_config("internlm2-1.8b")
D, F = CFG.d_model, CFG.d_ff                       # 2048, 8192
QKV = CFG.q_dim + 2 * CFG.kv_dim                   # 4096
KV, G, HD = CFG.n_kv_heads, CFG.n_heads // CFG.n_kv_heads, CFG.hd
LANES, PAGE, PAGES_PER_LANE = 8, 16, 34            # 544-position lanes
ROWS = (LANES, LANES * 128)                        # decode, prefill chunk
BF, F32, I8 = jnp.bfloat16, jnp.float32, jnp.int8


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, sharding, *shapes):
    """Compile ``fn`` for the described chip; returns the HLO text."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _gemm(m, k, n, in_dtype, ep, **operands):
    """matmul_pallas at the block ops.matmul would plan for this call."""
    block = ops._clamped_default_block(m, k, n, jnp.dtype(in_dtype).name,
                                       ep)
    return lambda a, b, *rest: matmul_pallas(
        a, b, block=block, epilogue=ep,
        **dict(zip(operands, rest)))


# (name, k, n, input dtype, epilogue, extra operands as name -> shape fn)
GEMMS = {
    "qkv_bf16": (D, QKV, BF, Epilogue(out_dtype=BF), {}),
    "up_gated_bf16": (D, F, BF, Epilogue(gate="silu", out_dtype=BF),
                      {"operand2": lambda m: ((m, F), BF)}),
    "down_residual_rmsnorm_bf16": (
        F, D, BF, Epilogue(residual=True, norm="rmsnorm", out_dtype=BF),
        {"residual": lambda m: ((m, D), BF),
         "norm_scale": lambda m: ((D,), F32)}),
    "qkv_int8": (D, QKV, I8, Epilogue(out_dtype=BF),
                 {"a_scale": lambda m: ((m, 1), F32),
                  "b_scale": lambda m: ((1, QKV), F32)}),
    "up_gated_quantize_int8": (
        D, F, I8, Epilogue(gate="silu", quantize=True),
        {"a_scale": lambda m: ((m, 1), F32),
         "b_scale": lambda m: ((1, F), F32),
         "operand2": lambda m: ((m, F), BF)}),
    "down_residual_rmsnorm_int8": (
        F, D, I8, Epilogue(residual=True, norm="rmsnorm", out_dtype=BF),
        {"a_scale": lambda m: ((m, 1), F32),
         "b_scale": lambda m: ((1, D), F32),
         "residual": lambda m: ((m, D), BF),
         "norm_scale": lambda m: ((D,), F32)}),
}


@pytest.mark.parametrize("rows", ROWS)
@pytest.mark.parametrize("name", sorted(GEMMS))
def test_matmul_compiles(one_chip, name, rows):
    k, n, dt, ep, extra = GEMMS[name]
    shapes = [((rows, k), dt), ((k, n), dt)] + [
        shape(rows) for shape in extra.values()]
    hlo = _compile(_gemm(rows, k, n, dt, ep, **extra), one_chip, *shapes)
    assert hlo.count("tpu_custom_call") == 1


@pytest.mark.parametrize("rows", ROWS)
def test_quantize_rowwise_compiles(one_chip, rows):
    hlo = _compile(lambda x: quantize_rowwise_pallas(
        x, block_rows=min(256, rows)), one_chip, ((rows, D), BF))
    assert hlo.count("tpu_custom_call") == 1


def test_flash_prefill_compiles(one_chip):
    hlo = _compile(flash_attention_pallas, one_chip,
                   ((1, 512, CFG.n_heads, HD), BF), ((1, 512, KV, HD), BF),
                   ((1, 512, KV, HD), BF))
    assert hlo.count("tpu_custom_call") == 1


@pytest.mark.parametrize("n_splits", (1, 2))
def test_flash_decode_compiles(one_chip, n_splits):
    cache = ((LANES, PAGE * PAGES_PER_LANE, KV, HD), BF)
    hlo = _compile(lambda q, k, v, p: flash_decode_pallas(
        q, k, v, p, n_splits=n_splits), one_chip,
        ((LANES, 1, KV, G, HD), BF), cache, cache, ((), jnp.int32))
    assert hlo.count("tpu_custom_call") == 1


def test_paged_flash_decode_compiles(one_chip):
    pool = ((LANES * PAGES_PER_LANE + 1, PAGE, KV, HD), BF)
    hlo = _compile(paged_flash_decode_pallas, one_chip,
                   ((LANES, 1, KV, G, HD), BF), pool, pool,
                   ((LANES, PAGES_PER_LANE), jnp.int32),
                   ((LANES,), jnp.int32))
    assert hlo.count("tpu_custom_call") == 1


@pytest.mark.parametrize("int8,kernels", ((False, 6), (True, 9)))
def test_paged_decode_step_compiles(one_chip, monkeypatch, int8, kernels):
    """The whole paged decode step the scheduler serves, at full widths:
    every projection, the paged attention and (int8) the rowwise
    quantizes are Pallas kernels, and the program fits one chip."""
    from repro.launch.mesh import make_mesh
    from repro.models.lm import Model
    from repro.serve.engine import _paged_decode_jit
    monkeypatch.setattr(ops, "_MODE", "pallas")
    model = Model(CFG, make_mesh(1, 1))
    params = model.abstract_params()
    if int8:
        params = jax.eval_shape(model.quantize_params_for_serving, params)
    pools = model.abstract_paged_cache(LANES * PAGES_PER_LANE, PAGE)
    place = lambda t: jax.tree.map(lambda s: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=one_chip), t)
    ints = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)
    compiled = _paged_decode_jit(model).lower(
        place(params), place(pools), ints(LANES, 1), ints(LANES),
        ints(LANES, PAGES_PER_LANE)).compile()
    assert compiled.as_text().count("tpu_custom_call") == kernels
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
             + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    assert total < 16 * 2 ** 30, total
