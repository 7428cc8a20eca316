"""An architecture is a module found by the configuration's ``arch``: a
second one (``bench/testdata/tiny_window``: local and global attention
alternating) is added as new files only and held to its own reference,
and a configuration that names no module is refused by its file."""
import filecmp
import json
import os
import shutil

import pytest

import tinyroot
import manifest
import run

SEED = 2**31 + 11
CELL = "tiny_window.chat"
WINDOW = os.path.join(tinyroot.BENCH, "testdata", "tiny_window")
NEW_FILES = {"arch": "tiny_window.py", "configs": "tiny_window.json",
             "workloads": f"{CELL}.json"}
INTERFACE = ("shapes", "forward_hidden", "arch_config", "to_program",
             "page_bytes", "token_flops", "chunk_flops", "decode_gemm_cost",
             "paged_attention_cost")


def _window_root(dest: str) -> str:
    """A test root with the windowed cell added: three new files and its
    entries in BENCHMARK.json."""
    root = tinyroot.make(dest)
    for kind, name in NEW_FILES.items():
        shutil.copy(os.path.join(WINDOW, name),
                    os.path.join(root, "bench", kind, name))
    b = manifest.load(root)
    b["workloads"].append({"name": CELL, "config": "tiny_window",
                           "traffic": "tinymix", "chips": 1, "why": "test"})
    for m in b["end_to_end"] + b["per_layer"]:
        if "workloads" in m:
            m["workloads"].append(CELL)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(b, f)
    return root


def _run(root, **kw):
    return run.execute(root, CELL, SEED, 2.0, False, require_tpu=False,
                       compile_cache=False, **kw)


@pytest.fixture(scope="module")
def sound(tmp_path_factory):
    root = _window_root(str(tmp_path_factory.mktemp("bench")))
    seen = {}

    def look(sess):
        seen["arch"] = sess.model.cfg
    res, r = _run(root, hook=look)
    return root, res, r, seen["arch"]


def test_second_architecture_is_new_files_only(tmp_path):
    before = tinyroot.make(str(tmp_path / "before"))
    root = _window_root(str(tmp_path / "after"))
    changed, added = [], []

    def walk(c, rel):
        changed.extend(os.path.join(rel, n) for n in c.diff_files)
        added.extend(os.path.join(rel, n) for n in c.right_only)
        assert not c.left_only
        for name, sub in c.subdirs.items():
            walk(sub, os.path.join(rel, name))
    walk(filecmp.dircmp(os.path.join(before, "bench"),
                        os.path.join(root, "bench")), "")
    assert changed == []
    assert sorted(added) == sorted(os.path.join(k, n)
                                   for k, n in NEW_FILES.items())


def test_second_architecture_is_correct(sound):
    root, res, r, arch = sound
    assert arch.block_pattern == ("local", "global") and arch.window == 32
    assert res["correct"] is True
    assert res["attempted"] == 40 and res["failed"] == 0
    # the checked requests run past the window
    picked = run.sample(r, SEED)
    assert max(len(p.prompt) + len(p.tokens) for p in picked) > 4 * 32
    assert sum(len(p.prompt) > 32 for p in picked) >= 10


def test_reference_without_window_is_not_correct(tmp_path, monkeypatch):
    """A planted fault: the same module's reference runs its local layers
    as global."""
    root = _window_root(str(tmp_path))
    mod = manifest.arch_module(root, "tiny_window")
    monkeypatch.setattr(mod, "windows",
                        lambda cfg: [None] * cfg["num_hidden_layers"])
    res, _ = _run(root)
    gap = res["checks"]["mean_request_gap"]
    assert res["correct"] is False and gap["value"] > 10 * gap["limit"]


@pytest.mark.parametrize("arch", [None, "no_such_arch", "../arch/dense_gqa"],
                         ids=["missing", "unknown", "path"])
def test_configuration_must_name_its_module(tmp_path, arch):
    root = tinyroot.make(str(tmp_path))
    path = os.path.join(root, "bench", "configs", "tiny.json")
    with open(path) as f:
        cfg = json.load(f)
    cfg.pop("arch")
    if arch is not None:
        cfg["arch"] = arch
    with open(path, "w") as f:
        json.dump(cfg, f)
    with pytest.raises(ValueError, match="bench/configs/tiny.json"):
        run.execute(root, tinyroot.CELL, SEED, 1.0, False, require_tpu=False,
                    compile_cache=False)


def test_every_configuration_has_a_whole_module(sound):
    root = sound[0]
    b = manifest.load(root)
    names = set()
    for w in b["workloads"]:
        cell = manifest.cell(root, b, w["name"])
        mod = manifest.arch(cell)
        assert all(callable(getattr(mod, f)) for f in INTERFACE), w["name"]
        assert manifest.arch(cell) is mod
        names.add(cell["config_file"]["arch"])
    assert names == {"dense_gqa", "tiny_window"}


def test_windowed_counts_by_hand(sound):
    root = sound[0]
    win = manifest.arch_module(root, "tiny_window")
    dense = manifest.arch_module(root, "dense_gqa")
    with open(os.path.join(WINDOW, "tiny_window.json")) as f:
        c = json.load(f)
    # four layers, two of them local: at position 100 they see 32 keys each
    # and the global ones 101
    extra = 4 * 2 * 128
    assert win.token_flops(c, 100, False) == \
        dense.token_flops(c, 100, False) - 2 * extra * (101 - 32)
    # inside the window the layouts agree
    assert win.token_flops(c, 31, True) == dense.token_flops(c, 31, True)
    assert win.chunk_flops(c, 16, 16, False) == \
        dense.chunk_flops(c, 16, 16, False)
    whole = win.chunk_flops(c, 64, 32, last=True)
    assert whole == sum(win.token_flops(c, p, p == 95)
                        for p in range(64, 96))
    # a lane at 64..95, page 16: a local layer reads pages 2..5 (its first
    # query's window starts at 33), a global one pages 0..5
    fl, by = win.paged_attention_cost(c, [(64, 32)], 16, 2)
    assert fl == 2 * extra * (32 * 32) + 2 * extra * sum(range(65, 97))
    q = 2 * 32 * 2 * 128
    assert by == 2 * 2 * (2 * 64 * 128 + q) + 2 * 2 * (2 * 96 * 128 + q)
    assert win.paged_attention_cost(c, [(0, 16)], 16, 2) == \
        dense.paged_attention_cost(c, [(0, 16)], 16, 2)
