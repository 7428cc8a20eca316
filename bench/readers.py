"""Readers the per-layer metric files share.  A reader takes a finished
``run.Run`` and returns the metric's value, or ``None`` when the run
holds nothing to read (no trace, no matching program or kernel, no
peaks for the device): the harness then leaves the metric out.

Program and kernel name patterns are the metric files' own; they match
an event's name (``xtrace``: a module's, or an operation's own name).
"""
from __future__ import annotations

from typing import List, Optional

import manifest
import xtrace as tr


def _traced_execs(run, program: str) -> List[tr.Event]:
    """Every execution of ``program`` that starts in the traced window."""
    t = run.trace
    if t is None or not t.devices:
        return []
    return [e for e in tr.executions(t.devices[0], program)
            if t.start <= e.start < t.end]


def program_ms(program: str):
    """Mean device time of one execution of ``program`` in the trace."""
    def read(run) -> Optional[float]:
        ex = _traced_execs(run, program)
        return 1e3 * sum(e.dur for e in ex) / len(ex) if ex else None
    return read


def step_mfu(run) -> Optional[float]:
    """Model operations of the useful tokens of the window's iterations
    (the counts of the cell's architecture module) over (their summed
    wall time x the bf16 peak), in %."""
    peak = run.peaks.get("bf16_flops")
    its = run.window_iters()
    wall = sum(it.t1 - it.t0 for it in its)
    if not peak or wall <= 0:
        return None
    cfg, arch = run.cfg, manifest.arch(run.cell)
    f = sum(arch.chunk_flops(cfg, s, n, last)
            for it in its for s, n, last in it.chunks)
    f += sum(arch.token_flops(cfg, p, True) for it in its
             for p in it.decodes)
    return 100.0 * f / (wall * peak)


def device_idle_share(run) -> Optional[float]:
    """1 - (union of device-operation intervals / traced window), in %."""
    t = run.trace
    if t is None or t.window_s <= 0 or not t.devices:
        return None
    return 100.0 * (1.0 - tr.busy_s(t) / t.window_s)
