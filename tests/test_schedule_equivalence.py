"""Schedule-equivalence property harness (the full multidev grid).

Parametrizes ``tests/_schedule_sweep.py`` over every
``(schedule x x_layout x Y x Z x epilogue)`` cell on the 8-fake-device
mesh and asserts, per cell,

  (a) bitwise fp32 equality across 'allreduce' / 'reduce_scatter' /
      'ring' / 'bidir_ring' (int8 q + f32 scales exactly equal under the
      quantize epilogue), and
  (b) closeness to the ``kernels.ref`` oracle.

Shapes are hypothesis-driven when hypothesis is installed (edge cases
like 1-column chunks, where 'bidir_ring' falls back to the
unidirectional merge, get generated) and fixed-seed otherwise.  Each test
runs the sweep in its own subprocess so this process keeps a single jax
device (the dry-run isolation rule); the subprocess prints one
``ok equiv[...]`` line per cell, surfaced by ``pytest -m multidev -v``
in the CI multidev job.
"""
import os
import subprocess
import sys

import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # degrade to the fixed-seed grid below
    HAVE_HYPOTHESIS = False

pytestmark = pytest.mark.multidev

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SWEEP = os.path.join(_ROOT, "tests", "_schedule_sweep.py")

LAYOUTS = ("replicated", "ksharded")
EPILOGUES = ("none", "bias_gelu", "bias_gelu_residual", "quantize",
             "gate_silu", "gate_silu_residual")


def _run_sweep(*args):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(_ROOT, "src")
    env["JAX_PLATFORMS"] = "cpu"  # fake-device CPU mesh, never the chip
    r = subprocess.run([sys.executable, _SWEEP, *args],
                       capture_output=True, text=True, timeout=1200,
                       env=env)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert "SWEEP_OK" in r.stdout
    # surface the per-cell check names in the pytest log
    for line in r.stdout.splitlines():
        if line.startswith("ok equiv["):
            print(line)


@pytest.mark.parametrize("layout", LAYOUTS)
@pytest.mark.parametrize("epilogue", EPILOGUES)
def test_schedule_equivalence_grid(layout, epilogue):
    """One (layout, epilogue) column of the grid: Y in {1, 2, 4} x all
    four schedules, bitwise + oracle, fixed seed."""
    _run_sweep("--layouts", layout, "--epilogues", epilogue,
               "--ys", "1,2,4", "--schedules", "all")


def test_schedule_equivalence_multi_seed_reduction_cells():
    """Extra seeds on the reduction-heavy raw-GEMM cells (the successor
    of the old 3-seed ring-bitwise check, now across all schedules)."""
    for seed in (1, 2, 3):
        _run_sweep("--layouts", "replicated,ksharded", "--epilogues",
                   "none", "--ys", "2,4", "--schedules", "all",
                   "--shape", "4,8,64,128", "--seed", str(seed))


if HAVE_HYPOTHESIS:
    @settings(max_examples=3, deadline=None)
    @given(
        s=st.integers(min_value=1, max_value=8),
        # K and N at model granularity; small multipliers generate the
        # 1-column-chunk edge where bidir_ring's split collapses
        k_mult=st.integers(min_value=1, max_value=16),
        n_mult=st.integers(min_value=1, max_value=16),
        seed=st.integers(min_value=0, max_value=2 ** 16),
        y=st.sampled_from([2, 4]),
        layout=st.sampled_from(LAYOUTS),
    )
    def test_schedule_equivalence_hypothesis_shapes(s, k_mult, n_mult,
                                                    seed, y, layout):
        _run_sweep("--layouts", layout, "--epilogues", "none",
                   "--ys", str(y), "--schedules", "all",
                   "--shape", f"4,{s},{4 * k_mult},{4 * n_mult}",
                   "--seed", str(seed))
else:
    @pytest.mark.parametrize("shape,seed,y,layout", [
        ("4,3,4,4", 7, 4, "replicated"),      # 1-column chunks: bidir
                                              # split-merge fallback
        ("4,1,8,16", 11, 2, "ksharded"),      # single-row, odd chunk=4
        ("4,5,64,32", 13, 4, "ksharded"),     # K-heavy, narrow N
    ])
    def test_schedule_equivalence_fixed_shapes(shape, seed, y, layout):
        """Fixed-seed stand-ins for the hypothesis shape generator
        (hypothesis unavailable), covering the same edge cells."""
        _run_sweep("--layouts", layout, "--epilogues", "none",
                   "--ys", str(y), "--schedules", "all",
                   "--shape", shape, "--seed", str(seed))
