"""The harness end to end on the CPU at test widths (``tiny.chat``), with
the look for a chip skipped: a sound run is correct, and the check
catches the int8 control and each fault the serving cells can have."""
import os
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

import tinyroot
import run

SEED = 2**31 + 11


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tinyroot.make(str(tmp_path_factory.mktemp("bench")))


def _run(root, trace=False, **kw):
    return run.execute(root, tinyroot.CELL, SEED, 2.0, trace,
                       require_tpu=False, compile_cache=False, **kw)


@pytest.fixture(scope="module")
def sound(root):
    return _run(root)


def test_sound_run_is_correct(sound):
    res, _ = sound
    checks = res["checks"]
    assert list(res) == ["correct", "attempted", "failed", "metrics",
                         "device", "checks"]
    assert res["correct"] is True
    assert res["attempted"] == 40 and res["failed"] == 0
    assert set(res["metrics"]) == {"itl_p95_ms", "setup_s"}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    gap = checks["mean_request_gap"]
    assert 0 <= gap["value"] <= gap["limit"]
    assert res["device"]["platform"] == "cpu" and res["device"]["count"] >= 1


def test_int8_control_is_not_correct(root):
    res, _ = _run(root, int8=True)
    checks = res["checks"]
    assert res["correct"] is False
    gap = checks["mean_request_gap"]
    assert gap["value"] > gap["limit"]


def _alter_token(sess):
    """A token altered where it is produced: the pick returns the next id."""
    pick, v = sess.engine._pick_paged, sess.cfg["vocab_size"]

    def bad(logits, *a):
        tok, *rest = pick(logits, *a)
        return ((tok + 1) % v, *rest)
    sess.engine._pick_paged = bad


def _state_unchanged(sess):
    """A step that returns its state unchanged: prefill chunks leave the
    page pools as they found them."""
    chunk = sess.engine._prefill_chunk

    def bad(params, pools, *a):
        keep = jax.tree.map(jnp.copy, pools)
        rows, _ = chunk(params, pools, *a)
        return rows, keep
    sess.engine._prefill_chunk = bad


@pytest.mark.parametrize("fault", [_alter_token, _state_unchanged],
                         ids=["token_altered", "state_unchanged"])
def test_fault_is_not_correct(root, fault):
    res, _ = _run(root, hook=fault)
    assert res["correct"] is False


def test_traced_run_reports_per_layer_metrics(root):
    import manifest
    res, _ = _run(root, trace=True)
    assert res["correct"] is True
    # on the CPU no metric finds a device trace or peaks to read
    layer = {m["name"] for m in manifest.per_layer(manifest.load(root),
                                                   tinyroot.CELL)}
    assert set(res["metrics"]) <= layer
    assert {"busy_s", "window_s"} <= set(res["device"])
    # the profiler records the window's last trace_s (0.5 s), one step more
    # at most
    assert 0.45 < res["device"]["window_s"] < 0.8
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    assert list(res)[-1] == "checks"


def test_no_tpu_exits_nonzero_and_prints_nothing(capsys):
    with pytest.raises(SystemExit) as e:
        run.main(["--workload", "internlm2-1.8b.chat", "--seed", "1",
                  "--seconds", "1", "--trace", "0"])
    assert e.value.code not in (0, None)
    assert capsys.readouterr().out == ""


def test_bare_checkout_exits_nonzero(tmp_path):
    shutil.copytree(tinyroot.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(tinyroot.REPO, "BENCHMARK.json"), tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "internlm2-1.8b.chat", "--seed", "3", "--seconds",
                        "1", "--trace", "0"], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and p.stdout == ""
