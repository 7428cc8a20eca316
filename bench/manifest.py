"""Finds every piece of the benchmark by its name in ``BENCHMARK.json``.

  cell ``<w>``        ``bench/workloads/<w>.json``
  configuration       ``bench/configs/<config>.json``
  traffic mix         ``bench/traffic/<traffic>.json``
  per-layer metric    ``bench/metrics/<metric>.py``
  architecture        ``bench/arch/<arch>.py``, named by the
                      configuration's required ``"arch"`` key

A later cell, mix, configuration, architecture or metric is a new file
and a new entry in ``BENCHMARK.json``; no existing file changes.
``BENCHMARK.json`` alone declares a metric (unit, layer, what it moves,
its cells); the metric's file holds only its reader, ``read(run)``, and
the names it matches.

An architecture module holds all that the harness knows of a layout:

  ``shapes(cfg)``            leaf name -> (shape, std, dtype) of the plain
                             layout, in a fixed order (``weights.draw``
                             folds the leaf's index into the seed)
  ``forward_hidden(cfg, w, tokens)``  the plain float32 forward pass up
                             to the final norm (``reference`` supplies
                             ``rms``, ``rope`` and ``attention``)
  ``logits(cfg, w, hidden)`` optional; the default is ``reference.logits``
  ``arch_config(cfg)``       the program's ``ArchConfig``, after checking
                             the constants the program fixes
  ``to_program(model, w)``   traceable: plain layout -> the program's tree
  ``page_bytes(cfg, page_size)``  device bytes of one page of the pool
  ``token_flops``, ``chunk_flops``, ``decode_gemm_cost``,
  ``paged_attention_cost``   the counts of ``flops.py``, for this layout
"""
from __future__ import annotations

import importlib.util
import json
import os
import re
from typing import Dict, List

BENCH = "bench"
ARCH_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def load(root: str) -> Dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _json(root: str, kind: str, name: str) -> Dict:
    with open(os.path.join(root, BENCH, kind, f"{name}.json")) as f:
        return json.load(f)


def cell(root: str, bench: Dict, name: str) -> Dict:
    """The cell ``name``: its workload file, with its configuration and
    traffic mix under ``config`` and ``mix``."""
    entries = [w for w in bench["workloads"] if w["name"] == name]
    if len(entries) != 1:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    c = _json(root, "workloads", name)
    entry = entries[0]
    if (c["config"], c["traffic"]) != (entry["config"], entry["traffic"]):
        raise ValueError(f"{name}: workload file and BENCHMARK.json "
                         f"disagree on config or traffic")
    c["name"] = name
    c["root"] = root
    c["chips"] = entry["chips"]
    c["config_file"] = _json(root, "configs", c["config"])
    c["config_file"]["name"] = c["config"]
    arch = c["config_file"].get("arch")
    if not (isinstance(arch, str) and ARCH_NAME.match(arch)
            and os.path.exists(_arch_path(root, arch))):
        raise ValueError(
            f"{os.path.join(BENCH, 'configs', c['config'] + '.json')}: "
            f"'arch' is {arch!r}; it must name a module bench/arch/<arch>.py")
    c["mix"] = _json(root, "traffic", c["traffic"])
    return c


def end_to_end(bench: Dict, name: str) -> List[Dict]:
    """The end-to-end metrics cell ``name`` reports."""
    return [m for m in bench["end_to_end"]
            if name in m.get("workloads", [name])]


def per_layer(bench: Dict, name: str) -> List[Dict]:
    """The per-layer metrics cell ``name`` reports: those that list it,
    and those without a list whose ``moves`` the cell reports."""
    e2e = {m["name"] for m in end_to_end(bench, name)}
    return [m for m in bench["per_layer"]
            if (name in m["workloads"] if "workloads" in m
                else m["moves"] in e2e)]


def metric_module(root: str, name: str):
    """Import ``bench/metrics/<name>.py`` (names may hold dots)."""
    path = os.path.join(root, BENCH, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _arch_path(root: str, name: str) -> str:
    return os.path.join(root, BENCH, "arch", f"{name}.py")


_ARCHS: Dict[str, object] = {}


def arch_module(root: str, name: str):
    """Import ``bench/arch/<name>.py``, once per file: the compiled
    programs of ``weights`` and ``reference`` are cached by module."""
    path = os.path.abspath(_arch_path(root, name))
    if path not in _ARCHS:
        spec = importlib.util.spec_from_file_location(
            "bench_arch_" + name.replace(".", "_").replace("-", "_"), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _ARCHS[path] = mod
    return _ARCHS[path]


def arch(cell: Dict):
    """The architecture module of ``cell``: its configuration's ``arch``."""
    return arch_module(cell["root"], cell["config_file"]["arch"])
