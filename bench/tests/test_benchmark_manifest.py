"""BENCHMARK.json keeps the benchmark's rules, agrees with the files it
names, and a new cell or metric is picked up from files alone."""
import filecmp
import json
import math
import os
import re

import numpy as np

import tinyroot
import manifest

with open(os.path.join(tinyroot.REPO, "BENCHMARK.json")) as f:
    B = json.load(f)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
LINE = re.compile(r"^[^\n\t]{1,200}$")


def test_top_level_keys_and_limits():
    assert set(B) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert B["paths"] == ["bench"] and B["command"][1] == "bench/run.py"
    rs = B["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    # a full check of 24 cells fits its 43200 s
    assert (2 + 14 * 24) * (rs + 60) + 24 * 180 + 1200 <= 43200
    assert len(json.dumps(B)) <= 64 * 1024


def test_names_units_and_lines():
    names = [e["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for e in B[k]]
    assert all(NAME.match(n) for n in names), names
    for k in ("configs", "workloads", "end_to_end", "per_layer"):
        ns = [e["name"] for e in B[k]]
        assert len(ns) == len(set(ns))
    for m in B["end_to_end"] + B["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for e in B["workloads"] + B["configs"]:
        assert LINE.match(e["why"])
    for m in B["per_layer"]:
        assert LINE.match(m["layer"])
        if "roofline" in m["name"] or "mfu" in m["name"]:
            assert m["unit"] == "%"
    for c in B["configs"]:
        assert all(NAME.match(k) for k in c["reduced"])
        assert len(c["reduced"]) <= 16
        # no width: a size, a _dim or _rank, a head size, experts per token
        assert not any(k.endswith(("_dim", "_rank", "_size"))
                       or k == "num_experts_per_tok" for k in c["reduced"])


def test_end_to_end_bounds():
    names = {m["name"] for m in B["end_to_end"]}
    assert names == {"itl_p95_ms", "setup_s"}
    for m in B["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25


def test_every_cell_reports_what_it_must():
    for w in B["workloads"]:
        e2e = {m["name"] for m in manifest.end_to_end(B, w["name"])}
        assert "setup_s" in e2e and len(e2e) >= 2, w["name"]
        assert manifest.per_layer(B, w["name"]), w["name"]
        assert w["chips"] == 1


def test_per_layer_metrics_move_what_their_cells_report():
    cells = {w["name"] for w in B["workloads"]}
    for m in B["per_layer"]:
        assert m["workloads"] and set(m["workloads"]) <= cells
        for w in m["workloads"]:
            reported = {e["name"] for e in manifest.end_to_end(B, w)}
            assert m["moves"] in reported, (m["name"], w)


def test_metric_files_agree_with_the_manifest():
    """Every per-layer metric BENCHMARK.json declares has its reader."""
    for m in B["per_layer"]:
        mod = manifest.metric_module(tinyroot.REPO, m["name"])
        assert callable(mod.read)
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    layers = {}
    for m in B["per_layer"]:
        layers.setdefault(m["layer"].lower(), set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values())


def test_every_configuration_has_a_cell_and_a_file():
    used = {w["config"] for w in B["workloads"]}
    for c in B["configs"]:
        assert c["name"] in used
        assert c["file"] == f"bench/configs/{c['name']}.json"
        with open(os.path.join(tinyroot.REPO, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["reduced"] == c["reduced"] and cfg["source"] == c["source"]
        # a whole published model: nothing is cut
        assert c["reduced"] == []
    for w in B["workloads"]:
        cell = manifest.cell(tinyroot.REPO, B, w["name"])
        assert cell["why"] == w["why"]
        geo = cell["geometry"]
        mix = cell["mix"]
        assert mix["prompt"]["max"] + mix["output"]["max"] <= \
            geo["max_seq_len"]


def _synthetic_run():
    import run
    cell = {"name": "new-cell", "geometry": {"n_lanes": 4, "page_size": 16}}
    r = run.Rec(0, 1.0, np.zeros(8, np.int32), 2, True, admitted=1.25,
                status="ok", times=[1.5, 1.6])
    return run.Run(cell, {}, {}, 10.0, (0.0, 10.0), {0: r}, [])


def test_new_cell_and_metric_are_files(tmp_path):
    """A later change adds a mix, a cell and a metric as new files and new
    entries in BENCHMARK.json; no existing file under bench/ changes."""
    root = tinyroot.make(str(tmp_path))
    before = str(tmp_path / "before")
    import shutil
    shutil.copytree(os.path.join(root, "bench"), before)
    bdir = os.path.join(root, "bench")
    with open(os.path.join(bdir, "traffic", "bursty.json"), "w") as f:
        json.dump({"arrival": "poisson",
                   "prompt": {"dist": "lognormal", "median": 30,
                              "sigma": 0.5, "min": 4, "max": 60},
                   "output": {"dist": "lognormal", "median": 5,
                              "sigma": 0.5, "min": 2, "max": 10}}, f)
    with open(os.path.join(bdir, "workloads", "tiny.bursty.json"), "w") as f:
        json.dump({"config": "tiny", "traffic": "bursty", "why": "new",
                   "rate_per_s": 5.0, "warmup_s": 1, "drain_s": 5,
                   "geometry": {"n_lanes": 2, "prefill_chunk": 16,
                                "page_size": 16, "max_seq_len": 80},
                   "check": {"tokens": 10, "min_requests": 1,
                             "max_requests": 4,
                             "limits": {"max_logit_gap": 0.05}}}, f)
    with open(os.path.join(bdir, "metrics", "admit_wait_max_ms.py"),
              "w") as f:
        f.write("def read(run):\n"
                "    w = [r.admitted - r.due for r in run.window_recs()]\n"
                "    return 1e3 * max(w) if w else None\n")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        b = json.load(f)
    b["workloads"].append({"name": "tiny.bursty", "config": "tiny",
                           "traffic": "bursty", "chips": 1, "why": "new"})
    b["end_to_end"][0]["workloads"].append("tiny.bursty")
    b["per_layer"].append({"name": "admit_wait_max_ms", "unit": "ms",
                           "better": "lower", "source": "host_clock",
                           "layer": "serve scheduler",
                           "moves": "itl_p95_ms",
                           "workloads": ["tiny.bursty"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(b, f)
    cell = manifest.cell(root, b, "tiny.bursty")
    assert cell["mix"]["prompt"]["median"] == 30
    assert cell["config_file"]["hidden_size"] == 256
    assert [m["name"] for m in manifest.per_layer(b, "tiny.bursty")] == \
        ["admit_wait_max_ms"]
    mod = manifest.metric_module(root, "admit_wait_max_ms")
    assert math.isclose(mod.read(_synthetic_run()), 250.0)
    cmp = filecmp.dircmp(before, bdir)
    changed = []

    def walk(c):
        changed.extend(c.diff_files)
        for sub in c.subdirs.values():
            walk(sub)
    walk(cmp)
    assert changed == []
    assert sorted(cmp.subdirs["workloads"].right_only) == ["tiny.bursty.json"]
