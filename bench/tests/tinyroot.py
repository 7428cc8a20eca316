"""A benchmark root holding the test cell ``tiny.chat`` beside the real
ones: a copy of ``bench/`` plus ``bench/testdata/tiny``'s files, and a
``BENCHMARK.json`` with the test cell and its metrics added."""
from __future__ import annotations

import json
import os
import shutil
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
TINY = os.path.join(BENCH, "testdata", "tiny")
CELL = "tiny.chat"

for p in (BENCH, os.path.join(REPO, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


def make(dest: str) -> str:
    """Build the root under ``dest``; returns it."""
    root = os.path.join(dest, "root")
    shutil.copytree(BENCH, os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    for kind, name in (("configs", "tiny"), ("traffic", "tinymix"),
                       ("workloads", CELL)):
        shutil.copy(os.path.join(TINY, f"{name}.json"),
                    os.path.join(root, "bench", kind, f"{name}.json"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        b = json.load(f)
    b["workloads"].append({"name": CELL, "config": "tiny",
                           "traffic": "tinymix", "chips": 1, "why": "test"})
    for m in b["end_to_end"]:
        if m["name"] == "itl_p95_ms":
            m["workloads"].append(CELL)
    for m in b["per_layer"]:
        if "internlm2-1.8b.chat" in m["workloads"]:
            m["workloads"].append(CELL)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(b, f)
    return root
