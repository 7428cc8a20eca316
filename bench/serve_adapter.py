"""The one module of the benchmark that drives the program.

It builds the program's model from a cell's configuration file through
the configuration's architecture module (``bench/arch/<arch>.py``:
``arch_config``, ``to_program``, ``page_bytes``), puts the benchmark's
weights (``weights.py``) into the program's parameter tree, sizes the
page pool, builds ``ServeEngine`` and warms its programs, and then
drives it through ``ServeEngine.submit`` / ``ServeEngine.step``.

Per-request progress (prompt tokens prefilled, tokens picked) is read
from ``engine.last_step.lanes``, the record of the latest ``step()``,
which has synchronised on the token pick.  In traced runs the engine's
dispatch callables are wrapped from the outside in
``jax.profiler.TraceAnnotation`` spans named ``bench.<callable>``
(``bench.prefill_chunk``, ``bench.decode_paged``, ``bench.pick_paged``,
``bench.inject_rows``).
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import sys
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import manifest
import weights as bench_weights

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if os.path.join(ROOT, "src") not in sys.path:
    sys.path.insert(0, os.path.join(ROOT, "src"))

from repro.launch.mesh import make_mesh  # noqa: E402
from repro.models.lm import Model  # noqa: E402
from repro.serve.api import Request, SamplingParams  # noqa: E402
from repro.serve.engine import ServeConfig, ServeEngine  # noqa: E402

STATUS_OK = "ok"
# dispatch callables of the engine wrapped in host spans when traced
DISPATCH = ("_prefill_chunk", "_decode_paged", "_pick_paged",
            "_inject_rows")
# room left on the device beside the weights, the pool and the step
# programs' own temporaries: the allocator's rounding and small buffers
MARGIN_BYTES = 512 * 2**20
MARGIN_SHARE = 0.02


def program_params(model: Model, arch, cfg: Dict, seed: int):
    """The seed's weights in the program's tree, in one jitted call."""
    fn = jax.jit(lambda lo, hi: bench_weights.draw(
        arch, cfg, lo, hi, lambda w: arch.to_program(model, w)))
    lo, hi = bench_weights.seed_words(seed)
    got = jax.eval_shape(fn, lo, hi)
    want = model.abstract_params()
    if jax.tree.structure(got) != jax.tree.structure(want) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want))):
        raise ValueError("benchmark weights do not match the program's "
                         "parameter tree")
    return fn(lo, hi)


def _lowered_step_programs(model: Model, scfg: ServeConfig, n_pages: int):
    """The paged decode and prefill-chunk programs at the cell's lanes,
    chunk and page table over a pool of ``n_pages``, lowered abstractly
    (the engine's own jits hit the same compiled programs later)."""
    aparams = model.abstract_params()
    if scfg.int8:
        aparams = jax.eval_shape(model.quantize_params_for_serving, aparams)
    pool = model.abstract_paged_cache(n_pages, scfg.page_size)
    ppl = -(-scfg.max_seq_len // scfg.page_size)
    L, C = scfg.n_lanes, scfg.prefill_chunk
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    dec = jax.jit(model.decode_step_paged, donate_argnums=(1,)).lower(
        aparams, pool, i32(L, 1), i32(L), i32(L, ppl))
    pre = jax.jit(model.prefill_chunk, donate_argnums=(1,)).lower(
        aparams, pool, i32(L, C), i32(L, C), i32(L, ppl), i32(L))
    return dec, pre


def _extra_bytes(compiled) -> int:
    """Device bytes a program needs beside its arguments."""
    ma = compiled.memory_analysis()
    return (ma.temp_size_in_bytes + ma.output_size_in_bytes
            - ma.alias_size_in_bytes)


@dataclasses.dataclass
class Sizing:
    n_pages: int
    pages_per_lane: int
    page_bytes: int
    bytes_limit: Optional[int] = None
    bytes_after_weights: Optional[int] = None
    pages_by_memory: Optional[int] = None
    refused: Tuple[int, ...] = ()


class _Store:
    """What pool sizing learnt, kept beside the compile cache: the pool
    sizes whose programs the compiler refused (refusals are not cached
    by JAX, so without this every run would pay them again), and the
    pool chosen for a cell, keyed by its lowered step program and the
    device memory it started from.  A changed program or memory state
    misses and sizes anew."""

    def __init__(self):
        d = jax.config.jax_compilation_cache_dir
        self.path = os.path.join(d, "bench_sizing.json") if d else None
        self.data = {"refused": {}, "sizes": {}}
        if self.path and os.path.exists(self.path):
            with open(self.path) as f:
                self.data = json.load(f)

    def save(self) -> None:
        if self.path:
            os.makedirs(os.path.dirname(self.path), exist_ok=True)
            with open(self.path, "w") as f:
                json.dump(self.data, f)


def _key(*parts) -> str:
    h = hashlib.sha256()
    for p in parts:
        h.update(str(p).encode())
    return h.hexdigest()[:32]


def _compiles(lowered, store: _Store):
    """The compiled programs, or None where the compiler refuses one for
    want of memory (HBM, or VMEM beside a large pool)."""
    out = []
    for lw in lowered:
        key = _key(lw.as_text())
        if key in store.data["refused"]:
            return None
        try:
            out.append(lw.compile())
        except jax.errors.JaxRuntimeError as e:
            if "RESOURCE_EXHAUSTED" not in str(e):
                raise
            store.data["refused"][key] = True
            store.save()
            return None
    return out


def size_pool(model: Model, arch, cfg: Dict, scfg: ServeConfig,
              stats: Optional[Dict] = None) -> Sizing:
    """Pages that fill the memory left after the weights: the step
    programs' needs beside their arguments grow with the pool, so they are
    read (``memory_analysis``) at two small pools and extended in a line;
    with ``memory_stats`` that gives the largest pool that fits, at most
    every lane's full page table.  The compiler may still refuse that
    pool (it holds more of it on the device than the line says, or a
    kernel runs out of VMEM beside it): then 10% fewer pages, until both
    programs compile.  A backend that reports no memory (the CPU) gets
    the full tables.  ``stats`` stands in for ``memory_stats`` in tests."""
    ppl = -(-scfg.max_seq_len // scfg.page_size)
    full = scfg.n_lanes * ppl
    pb = arch.page_bytes(cfg, scfg.page_size)
    stats = stats or jax.devices()[0].memory_stats()
    if not stats or "bytes_limit" not in stats:
        return Sizing(full, ppl, pb)
    limit, used = stats["bytes_limit"], stats["bytes_in_use"]
    store = _Store()
    p0, p1 = max(1, ppl // 2), ppl
    low0 = _lowered_step_programs(model, scfg, p0)
    key = _key(low0[0].as_text(), low0[1].as_text(), limit,
               used // 2**26, full)
    if key in store.data["sizes"]:
        return Sizing(**store.data["sizes"][key])
    room = limit - used - MARGIN_BYTES - int(MARGIN_SHARE * limit)
    small = [_compiles(low0, store),
             _compiles(_lowered_step_programs(model, scfg, p1), store)]
    if small[0] is None or small[1] is None:
        raise RuntimeError(f"{cfg['name']}: the step programs do not "
                           f"compile even over one lane's {p1} pages")
    n_mem = full
    for c0, c1 in zip(*small):
        e0, e1 = _extra_bytes(c0), _extra_bytes(c1)
        slope = max(0.0, (e1 - e0) / (p1 - p0))
        # used + n * pb + e0 + (n - p0) * slope <= limit - margins
        n_mem = min(n_mem, int((room - e0 + p0 * slope) // (pb + slope)))
    n, refused = max(ppl, n_mem), []
    while n > ppl:
        if _compiles(_lowered_step_programs(model, scfg, n),
                     store) is not None:
            break
        refused.append(n)
        n = max(ppl, int(n * 0.9))
    sz = Sizing(n, ppl, pb, limit, used, n_mem, tuple(refused))
    store.data["sizes"][key] = dataclasses.asdict(sz)
    store.save()
    return sz


class Session:
    """One engine serving one cell (``manifest.cell``).  ``step()``
    returns what changed."""

    def __init__(self, cell: Dict, seed: int, *, int8: bool = False,
                 traced: bool = False):
        cfg, geometry = cell["config_file"], cell["geometry"]
        arch = manifest.arch(cell)
        self.cfg = cfg
        self.model = Model(arch.arch_config(cfg), make_mesh(1, 1))
        if not self.model.supports_paged_serving:
            raise RuntimeError(f"{cfg['name']}: no paged serving path")
        scfg = ServeConfig(int8=int8, n_lanes=geometry["n_lanes"],
                           page_size=geometry["page_size"],
                           prefill_chunk=geometry["prefill_chunk"],
                           max_seq_len=geometry["max_seq_len"])
        self.engine = ServeEngine(
            self.model, program_params(self.model, arch, cfg, seed), scfg)
        jax.block_until_ready(self.engine.params)
        self.sizing = size_pool(self.model, arch, cfg, scfg)
        scfg.n_pages = self.sizing.n_pages
        self.engine.scheduler                  # allocates the page pool
        self._warm()
        self.traced = traced
        if traced:
            for name in DISPATCH:
                setattr(self.engine, name,
                        _annotated(f"bench.{name.lstrip('_')}",
                                   getattr(self.engine, name)))

    # -- set-up ---------------------------------------------------------------

    def _warm(self) -> None:
        """Compile every program the cell's steps use, at its shapes: a
        chunk, a pick, then a decode beside a second request's final
        chunk (which injects its logits row)."""
        sp = SamplingParams(greedy=True, max_new_tokens=3)
        self.engine.submit(Request(id="_warm0", tokens=np.zeros(1, np.int32),
                                   sampling=sp))
        self.engine.step()
        self.engine.submit(Request(id="_warm1", tokens=np.zeros(1, np.int32),
                                   sampling=sp))
        self.engine.step()
        self.engine.drain()
        self.engine.collect()

    # -- driving --------------------------------------------------------------

    def submit(self, rid: int, prompt: np.ndarray, max_new: int) -> None:
        self.engine.submit(Request(
            id=rid, tokens=prompt,
            sampling=SamplingParams(greedy=True, max_new_tokens=max_new)))

    @property
    def has_work(self) -> bool:
        return self.engine.pending

    def progress(self) -> Dict[int, Tuple[int, int, int]]:
        """Request id -> (prompt tokens prefilled, tokens picked, prompt
        length) of every request in a lane."""
        rec = self.engine.last_step
        return {rid: (pre, picked, plen)
                for rid, pre, picked, plen in (rec.lanes if rec else ())}

    def step(self) -> List[Tuple[int, str, np.ndarray]]:
        """One scheduler iteration; returns (id, status, tokens) of the
        requests that finished in it."""
        outs = self.engine.step()
        self.engine.collect()
        return [(o.id, o.status, np.asarray(o.tokens)) for o in outs]

    def close(self) -> None:
        """Free the engine's weights, pool and programs' buffers."""
        self.engine = None
        self.model = None


def _annotated(name: str, fn):
    def call(*a, **k):
        with jax.profiler.TraceAnnotation(name):
            return fn(*a, **k)
    return call
