"""Sharded XYZ matmul correctness, run on an 8-device CPU mesh in a
subprocess (the main test process must keep a single device)."""
import os
import subprocess
import sys

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SCRIPT = os.path.join(_ROOT, "tests", "_multidev_checks.py")


def _run(*checks):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(_ROOT, "src")
    env["JAX_PLATFORMS"] = "cpu"  # fake-device CPU mesh, never the chip
    r = subprocess.run(
        [sys.executable, _SCRIPT, *checks],
        capture_output=True, text=True, timeout=600, env=env,
    )
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert "ALL_OK" in r.stdout


def test_weight_layout_roundtrip():
    _run("weight_layout_roundtrip")


def test_schedule_equivalence():
    """Registered sweep: bitwise fp32 equality across all four schedules
    (incl. 'bidir_ring') + ref-oracle closeness, both layouts, Y in
    {1, 2, 4}.  The full epilogue grid runs under ``pytest -m multidev``
    (scripts/ci.sh multidev)."""
    _run("schedule_equivalence")


def test_schedule_equivalence_epilogue():
    _run("schedule_equivalence_epilogue")


def test_replicated_out():
    _run("xyz_replicated_out")


def test_overlapped_gather_hlo():
    """ksharded Z>1 Y>1: no barrier all-gather of A in the compiled HLO
    (the chunked ppermute gather replaced it)."""
    _run("overlapped_gather_hlo")


def test_xyz_epilogue():
    _run("xyz_epilogue")


def test_grads():
    _run("grads")


def test_mlp_composition():
    _run("mlp_composition")


def test_collective_bytes_ordering():
    _run("collective_bytes_ordering")


# ---------------------------------------------------------------------------
# config validation (pure; no mesh needed)
# ---------------------------------------------------------------------------

def test_unknown_schedule_raises():
    """A typo like 'ring ' must raise, not silently run some default
    schedule (the regression this pins: the old if/elif chain fell
    through for Y == 1 and the model==1 path never looked at the
    string)."""
    from repro.core.maxeva_matmul import SCHEDULES, XYZConfig
    for bad in ("ring ", "Ring", "reduce-scatter", "none", "", "bidir"):
        with pytest.raises(ValueError, match="schedule"):
            XYZConfig(y=2, schedule=bad)
    for good in SCHEDULES:
        XYZConfig(y=2, schedule=good)  # all four construct cleanly


def test_unknown_x_layout_raises():
    from repro.core.maxeva_matmul import X_LAYOUTS, XYZConfig
    for bad in ("replicatedd", "k_sharded", "KSHARDED", ""):
        with pytest.raises(ValueError, match="x_layout"):
            XYZConfig(y=2, x_layout=bad)
    for good in X_LAYOUTS:
        XYZConfig(y=2, x_layout=good)


def test_dataclasses_replace_revalidates():
    """dataclasses.replace re-runs __post_init__, so a plan mutated with
    a bad schedule string still fails loudly."""
    import dataclasses
    from repro.core.maxeva_matmul import XYZConfig
    cfg = XYZConfig(y=2, schedule="bidir_ring")
    with pytest.raises(ValueError, match="schedule"):
        dataclasses.replace(cfg, schedule="ringg")
