"""Reduction of a profiler trace (``.xplane.pb``) to what the per-layer
metrics read: device operations, program executions and the host spans
the benchmark opened, on one clock.

Device planes are ``/device:TPU:<n>``; on each, the ``XLA Modules`` line
holds one event per program execution and the ``XLA Ops`` line one per
operation run.  Host spans are the ``bench.*`` ``TraceAnnotation``s the
harness and the adapter open.  Everything here reads the trace only; the
names that pick out one program or kernel live in the metric files.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

from stats import union_length

HOST_PREFIX = "bench."


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float            # seconds, on the trace's clock
    end: float

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class Device:
    name: str
    modules: List[Event]
    ops: List[Event]


@dataclasses.dataclass
class Trace:
    devices: List[Device]
    spans: List[Event]      # host spans named bench.*
    start: float            # the traced window, from the harness's spans
    end: float

    @property
    def window_s(self) -> float:
        return self.end - self.start


def _short(name: str) -> str:
    """An XLA op event is named by its whole HLO instruction; keep the
    instruction's own name (``%fusion.3 = ...`` -> ``fusion.3``)."""
    return name.split(" = ", 1)[0].lstrip("%") if " = " in name else name


def _event(ev) -> Event:
    s = ev.start_ns * 1e-9
    return Event(_short(ev.name), s, s + ev.duration_ns * 1e-9)


def find_xplane(directory: str) -> str:
    found = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise FileNotFoundError(f"{len(found)} .xplane.pb under {directory}")
    return found[0]


def load(path: str, window_span: str = "bench.traced") -> Trace:
    """Read a trace file.  The traced window is the host span
    ``window_span`` the harness opens from the first to the last traced
    step; without it, the extent of the device events."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, spans = [], []
    for plane in pd.planes:
        if re.fullmatch(r"/device:TPU:\d+", plane.name):
            mods, ops = [], []
            for line in plane.lines:
                if line.name == "XLA Modules":
                    mods = [_event(e) for e in line.events]
                elif line.name == "XLA Ops":
                    ops = [_event(e) for e in line.events]
            devices.append(Device(plane.name, mods, ops))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [_event(e) for e in line.events
                          if e.name.startswith(HOST_PREFIX)]
    spans.sort(key=lambda e: e.start)
    win = [s for s in spans if s.name == window_span]
    if win:
        start, end = win[0].start, win[-1].end
    else:
        evs = [e for d in devices for e in d.ops]
        start = min((e.start for e in evs), default=0.0)
        end = max((e.end for e in evs), default=0.0)
    return Trace(devices, spans, start, end)


def clipped(events: Sequence[Event], lo: float, hi: float
            ) -> List[Tuple[float, float]]:
    out = []
    for e in events:
        s, t = max(e.start, lo), min(e.end, hi)
        if t > s:
            out.append((s, t))
    return out


def busy_s(tr: Trace) -> float:
    """Seconds in which some operation ran, averaged over the devices."""
    if not tr.devices:
        return 0.0
    return sum(union_length(clipped(d.ops, tr.start, tr.end))
               for d in tr.devices) / len(tr.devices)


# control flow: an event that spans the operations it runs
CONTAINERS = re.compile(r"^(while|conditional|call)(\.\d+)?$")


def executions(dev: Device, pattern: str) -> List[Event]:
    """Program executions whose name matches ``pattern``, in order."""
    rx = re.compile(pattern)
    return sorted((m for m in dev.modules if rx.search(m.name)),
                  key=lambda m: m.start)


def ops_within(dev: Device, ex: Event, pattern: Optional[str] = None
               ) -> List[Event]:
    """Operations run inside one program execution, without control-flow
    containers (optionally only those whose name matches ``pattern``)."""
    rx = re.compile(pattern) if pattern else None
    return [o for o in dev.ops
            if o.start >= ex.start and o.end <= ex.end
            and not CONTAINERS.match(o.name)
            and (rx is None or rx.search(o.name))]


def top_ops(tr: Trace, n: int = 10) -> List[List]:
    """The ``n`` operation names with the most device time in the window,
    as [name, seconds] (summed over devices; control-flow containers,
    which span the operations they run, left out)."""
    tot: Dict[str, float] = {}
    for d in tr.devices:
        for o in d.ops:
            if CONTAINERS.match(o.name):
                continue
            s, t = max(o.start, tr.start), min(o.end, tr.end)
            if t > s:
                tot[o.name] = tot.get(o.name, 0.0) + (t - s)
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])
            ][:n]


def idle_gaps(tr: Trace, n: int = 10) -> List[List]:
    """The ``n`` longest stretches of the first device's window with no
    operation running, as [label, seconds]: the label is the innermost
    host span open at the stretch's middle, or ``no host span``."""
    if not tr.devices:
        return []
    iv = sorted(clipped(tr.devices[0].ops, tr.start, tr.end))
    gaps, cur = [], tr.start
    for s, t in iv:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, t)
    if tr.end > cur:
        gaps.append((cur, tr.end))
    gaps.sort(key=lambda g: -(g[1] - g[0]))
    out = []
    for s, t in gaps[:n]:
        mid = 0.5 * (s + t)
        open_ = [sp for sp in tr.spans if sp.start <= mid < sp.end
                 and sp.name != "bench.traced"]
        label = (min(open_, key=lambda sp: sp.dur).name if open_
                 else "no host span")
        out.append([label, t - s])
    return out
