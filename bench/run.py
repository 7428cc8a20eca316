#!/usr/bin/env python3
"""The on-chip serving benchmark.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One run is one process.  It finds the cell by name in ``BENCHMARK.json``
(see ``manifest.py``), fails unless JAX sees a TPU with the chips the
cell asks for, builds the engine with weights drawn from the seed, warms
its programs (set-up), serves a warm-up stretch of the cell's traffic,
then measures for ``--seconds``.  Every request is timed from its due
time; requests due in the window that are not finished when the drain
ends count as failed.

Afterwards the engine is freed and the plain float32 reference
(``reference.py``) scores a seeded sample of the finished requests: for
each served token, the gap by which its logit lies below the reference's
best.  ``correct`` holds when each number the cell's ``check.limits``
names is within its limit: ``max_logit_gap``, the widest gap, or
``mean_request_gap``, the mean over the sampled requests of each one's
widest gap.

``--trace 1`` profiles the last ``trace_s`` seconds of the window and
reports the cell's per-layer metrics (``bench/metrics/<name>.py``)
instead of its end-to-end ones.  The profiler stops after the window
closes, so its collection, which takes tens of seconds, stalls the drain
and not a step inside the window; Python function tracing and HLO protos
are off.  The last line of stdout is one JSON object; the numbers
compared are also the last lines of stderr.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from typing import Callable, Dict, List, Optional, Tuple  # noqa: E402

import numpy as np  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
if BENCH_DIR not in sys.path:
    sys.path.insert(0, BENCH_DIR)

import manifest  # noqa: E402
import stats  # noqa: E402
import traffic  # noqa: E402

CACHE_DIR = os.path.join(ROOT, ".jax_cache")


def log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# records
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Rec:
    """One request as the harness saw it; times on the host's clock."""
    id: int
    due: float
    prompt: np.ndarray
    max_new: int
    in_window: bool
    submitted: Optional[float] = None
    admitted: Optional[float] = None
    times: List[float] = dataclasses.field(default_factory=list)
    status: Optional[str] = None
    tokens: Optional[np.ndarray] = None

    @property
    def ok(self) -> bool:
        return self.status == "ok"


@dataclasses.dataclass
class Iteration:
    """One scheduler iteration: its host interval and its useful work."""
    t0: float
    t1: float
    chunks: List[Tuple[int, int, bool]]     # (start, n, ends the prompt)
    decodes: List[int]                      # positions decoded


@dataclasses.dataclass
class Run:
    """What a run measured; the per-layer metric readers take this."""
    cell: Dict
    cfg: Dict
    peaks: Dict
    seconds: float
    window: Tuple[float, float]
    recs: Dict[int, Rec]
    iters: List[Iteration]
    trace: object = None                    # trace.Trace of a traced run
    sizing: object = None
    lag: List[float] = dataclasses.field(default_factory=list)
    window_compiles: int = 0
    checked: List[float] = dataclasses.field(default_factory=list)

    def window_recs(self) -> List[Rec]:
        return [r for r in self.recs.values() if r.in_window]

    def window_iters(self) -> List[Iteration]:
        ws, we = self.window
        return [it for it in self.iters if ws <= it.t0 < we]


# ---------------------------------------------------------------------------
# driving the engine
# ---------------------------------------------------------------------------

def _annotate(name: str, on: bool):
    import contextlib
    if not on:
        return contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation(name)


class Tracer:
    """Profiles the last ``seconds`` of the window into ``directory``: the
    profiler starts at the first step boundary from ``we - seconds`` on,
    with the host span ``bench.traced``, and stops at the first one after
    the window.  The seconds that starting and stopping took are kept for
    the ``run:`` line."""

    def __init__(self, directory: str, seconds: float):
        self.dir, self.seconds = directory, seconds
        self.on = self.done = False
        self._span = None
        self.start_s = self.stop_s = None

    def tick(self, now: float, we: float) -> None:
        import jax
        if self.on and now >= we:
            self._span.__exit__(None, None, None)
            t = time.perf_counter()
            jax.profiler.stop_trace()
            self.stop_s = time.perf_counter() - t
            self.on, self.done = False, True
        elif not self.on and not self.done and now >= we - self.seconds:
            opts = jax.profiler.ProfileOptions()
            opts.host_tracer_level = 1          # the bench.* spans
            opts.python_tracer_level = 0
            opts.enable_hlo_proto = False
            t = time.perf_counter()
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self.start_s = time.perf_counter() - t
            self._span = jax.profiler.TraceAnnotation("bench.traced")
            self._span.__enter__()
            self.on = True


def drive(sess, cell: Dict, seed: int, seconds: float,
          tracer: Optional[Tracer]) -> Run:
    """Serve the cell's traffic: a warm-up stretch, the window, then (open
    loop) the drain of the window's requests."""
    mix, vocab = cell["mix"], cell["config_file"]["vocab_size"]
    warm = float(cell["warmup_s"])
    rate = float(cell["rate_per_s"])
    sched = (traffic.open_loop(mix, rate, 0.0, warm, seed, 1, vocab)
             + traffic.open_loop(mix, rate, warm, seconds, seed, 2,
                                 vocab, first_id=1_000_000))
    origin = time.perf_counter()
    ws, we = origin + warm, origin + warm + seconds
    drain_until = we + float(cell.get("drain_s", 0))
    recs: Dict[int, Rec] = {}
    iters: List[Iteration] = []
    lag: List[float] = []
    next_i = 0

    def submit(q: traffic.Req, due: float, now: float) -> None:
        r = Rec(q.id, due, q.prompt, q.max_new, in_window=(ws <= due < we))
        r.submitted = now
        recs[q.id] = r
        lag.append(now - due)
        sess.submit(q.id, q.prompt, q.max_new)

    compiles = _CompileCounter()
    while True:
        now = time.perf_counter()
        if tracer is not None:
            tracer.tick(now, we)
        tracing = tracer is not None and tracer.on
        if now >= we and not compiles.closed:
            compiles.close()
        with _annotate("bench.submit", tracing):
            while next_i < len(sched) and origin + sched[next_i].due <= now:
                q = sched[next_i]
                submit(q, origin + q.due, now)
                next_i += 1
        pending = [r for r in recs.values() if r.in_window
                   and r.status is None]
        if now >= we and (not pending or now >= drain_until):
            break
        if not sess.has_work:
            nxt = (origin + sched[next_i].due if next_i < len(sched)
                   else we)
            with _annotate("bench.wait", tracing):
                time.sleep(max(0.0, min(nxt - now, 0.002)))
            continue
        if now >= ws:
            compiles.open()
        before = sess.progress()
        t0 = time.perf_counter()
        with _annotate("bench.step", tracing):
            done = sess.step()
        t1 = time.perf_counter()
        iters.append(_account(before, sess.progress(), done, recs, t0, t1))
    if tracer is not None:
        tracer.tick(float("inf"), we)
    compiles.close()
    for r in recs.values():
        if r.in_window and r.status is None:
            r.status = "unfinished"
    run = Run(cell, cell["config_file"], {}, seconds, (ws, we), recs, iters,
              lag=lag)
    run.window_compiles = compiles.count
    return run


def _account(before, after, done, recs, t0, t1) -> Iteration:
    """Book one iteration: new tokens get the step's return time, and the
    prompt chunks and decodes it ran become its useful work."""
    fin = {rid: (status, toks) for rid, status, toks in done}
    chunks, decodes = [], []
    ids = set(after) | set(fin)
    for rid in ids:
        r = recs.get(rid)
        if r is None:
            continue
        plen = len(r.prompt)
        b_pre, b_tok, _ = before.get(rid, (0, 0, plen))
        if rid in after:
            a_pre, a_tok, _ = after[rid]
        else:
            a_pre, a_tok = plen, len(fin[rid][1])
        if rid not in before and r.admitted is None:
            r.admitted = t0
        if a_pre > b_pre:
            chunks.append((b_pre, a_pre - b_pre, a_pre == plen))
        if b_pre == plen and b_tok >= 1 and a_tok > b_tok:
            decodes.append(plen + b_tok - 1)
        r.times.extend([t1] * (a_tok - b_tok))
        if rid in fin:
            r.status, r.tokens = fin[rid][0], fin[rid][1]
    return Iteration(t0, t1, chunks, decodes)


class _CompileCounter:
    """Counts traces and compiles JAX starts inside the window."""
    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.count, self.active, self.closed = 0, False, False

        def listen(event, *_a, **_k):
            if self.active and event in self.EVENTS:
                self.count += 1
        self._listen = listen
        jax.monitoring.register_event_duration_secs_listener(listen)

    def open(self):
        if not self.closed:
            self.active = True

    def close(self):
        import jax
        if not self.closed:
            jax.monitoring.unregister_event_duration_listener(self._listen)
        self.active, self.closed = False, True


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------

def end_to_end(run: Run, setup_s: float) -> Dict[str, float]:
    """Every end-to-end quantity a cell may report, by metric name."""
    gaps: List[float] = []
    for r in run.window_recs():
        gaps += (stats.token_gaps(r.times) if r.ok else [stats.MISSING])
    return {"setup_s": setup_s,
            "itl_p95_ms": 1e3 * stats.percentile(gaps, 95)}


def earlier_line(run: Run, tracer: Optional[Tracer] = None) -> Dict:
    """Medians, counts, the generator's lateness and the whole iteration's
    model FLOP utilisation (in traced and untraced runs alike, so that
    the tracer's cost shows): information printed on stderr, not
    compared."""
    import readers
    wr = run.window_recs()
    ok = [r for r in wr if r.ok]
    t = stats.ttfts([r.due for r in wr],
                    [r.times[0] if (r.ok and r.times) else None for r in wr])
    waits = [stats.MISSING if r.admitted is None else r.admitted - r.due
             for r in wr]
    gaps = [g for r in ok for g in stats.token_gaps(r.times)]
    in_win = run.window_iters()
    return {
        "requests_in_window": len(wr), "ok": len(ok),
        "ttft_p50_ms": 1e3 * stats.percentile(t, 50) if t else None,
        "ttft_p90_ms": 1e3 * stats.percentile(t, 90) if t else None,
        "ttft_samples_beyond_p90": stats.beyond(len(wr), 90),
        "queue_wait_p90_ms": 1e3 * stats.percentile(waits, 90)
        if waits else None,
        "itl_p50_ms": 1e3 * stats.percentile(gaps, 50) if gaps else None,
        "itl_samples": len(gaps),
        "iterations_in_window": len(in_win),
        "mean_iteration_ms": 1e3 * sum(it.t1 - it.t0 for it in in_win)
        / len(in_win) if in_win else None,
        "step_mfu": readers.step_mfu(run),
        "generator_lag_max_ms": 1e3 * max(run.lag, default=0.0),
        "generator_lag_p99_ms": 1e3 * stats.percentile(run.lag, 99)
        if run.lag else None,
        "window_compiles": run.window_compiles,
        "sizing": dataclasses.asdict(run.sizing) if run.sizing else None,
        "profiler_start_s": tracer.start_s if tracer else None,
        "profiler_stop_s": tracer.stop_s if tracer else None,
    }


def per_layer(root: str, bench: Dict, run: Run) -> Dict[str, Dict]:
    out = {}
    for m in manifest.per_layer(bench, run.cell["name"]):
        v = manifest.metric_module(root, m["name"]).read(run)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


# ---------------------------------------------------------------------------
# correctness
# ---------------------------------------------------------------------------

def sample(run: Run, seed: int) -> List[Rec]:
    """A seeded sample of the finished window requests with the longest
    among them, until it holds ``check.tokens`` served tokens in at least
    ``check.min_requests`` requests, or ``check.max_requests`` requests."""
    chk = run.cell["check"]
    done = sorted((r for r in run.window_recs() if r.ok and len(r.tokens)),
                  key=lambda r: r.id)
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r.prompt) + len(r.tokens), r.id))
    rest = [r for r in done if r is not longest]
    order = np.random.default_rng([int(seed) & (2**63 - 1), 9]
                                  ).permutation(len(rest))
    out, n = [longest], len(longest.tokens)
    for i in order:
        if len(out) >= chk["max_requests"] or (
                n >= chk["tokens"] and len(out) >= chk["min_requests"]):
            break
        out.append(rest[i])
        n += len(rest[i].tokens)
    return out


def check(cell: Dict, seed: int, picked: List[Rec]) -> Dict:
    """Score ``picked`` against the reference with the seed's weights."""
    import reference
    import weights
    geo, cfg = cell["geometry"], cell["config_file"]
    arch = manifest.arch(cell)
    w = weights.make(arch, cfg, seed)
    seq = reference.padded_len(geo["max_seq_len"])
    rows = cell["mix"]["output"]["max"]
    gap, per = reference.max_gap(arch, cfg, w,
                                 [(r.prompt, r.tokens) for r in picked],
                                 seq, rows)
    del w
    return {"max_logit_gap": gap, "per_request": per,
            "mean_request_gap": float(np.mean(per)),
            "requests": len(picked),
            "tokens": int(sum(len(r.tokens) for r in picked))}


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def device_info(chips: int, require_tpu: bool):
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise SystemExit(f"bench: no TPU (JAX sees {devs[0].platform!r})")
    if require_tpu and len(devs) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX sees "
                         f"{len(devs)}")
    return devs


def enable_cache() -> str:
    import jax
    d = os.environ.get("JAX_COMPILATION_CACHE_DIR") or CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", d)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return d


def execute(root: str, workload: str, seed: int, seconds: float,
            trace: bool, *, require_tpu: bool = True, int8: bool = False,
            hook: Optional[Callable] = None, keep_trace: Optional[str] = None,
            compile_cache: bool = True,
            t_process: float = T_PROCESS) -> Tuple[Dict, Run]:
    """One run.  Returns the result line and what was measured.
    ``require_tpu``, ``hook`` (called with the built session) and
    ``compile_cache`` are for tests on the CPU; ``int8`` switches the
    program's int8 path on, the correctness control."""
    bench = manifest.load(root)
    cell = manifest.cell(root, bench, workload)
    devs = device_info(cell["chips"], require_tpu)
    kind = devs[0].device_kind
    with open(os.path.join(root, manifest.BENCH, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if require_tpu and kind not in table:
        raise SystemExit(f"bench: no peaks for device kind {kind!r}")
    if compile_cache:
        enable_cache()
    import serve_adapter
    sess = serve_adapter.Session(cell, seed, int8=int8, traced=trace)
    if hook is not None:
        hook(sess)
    tdir = tracer = None
    if trace:
        tdir = tempfile.mkdtemp(prefix="bench-trace-")
        tracer = Tracer(tdir, float(cell["trace_s"]))
    run = drive(sess, cell, seed, seconds, tracer)
    run.peaks = table.get(kind, {})
    run.sizing = sess.sizing
    setup_s = run.window[0] - t_process
    import jax
    mem = devs[0].memory_stats() or {}
    peak = mem.get("peak_bytes_in_use", 0)
    picked = sample(run, seed)
    sess.close()
    del sess
    gc.collect()
    info = earlier_line(run, tracer)
    if trace:
        import xtrace as trace_mod
        t = time.perf_counter()
        path = trace_mod.find_xplane(tdir)
        info["trace_bytes"] = os.path.getsize(path)
        run.trace = trace_mod.load(path)
        info["trace_load_s"] = time.perf_counter() - t
        if keep_trace:
            shutil.copytree(tdir, keep_trace, dirs_exist_ok=True)
        shutil.rmtree(tdir, ignore_errors=True)
    chk = check(cell, seed, picked) if picked else {
        "max_logit_gap": float("nan"), "mean_request_gap": float("nan"),
        "per_request": [], "requests": 0, "tokens": 0}
    run.checked = chk["per_request"]
    checks = {name: {"value": chk[name], "limit": float(limit)}
              for name, limit in cell["check"]["limits"].items()}
    correct = bool(picked) and all(
        np.isfinite(c["value"]) and c["value"] <= c["limit"]
        for c in checks.values())
    info.update({k: chk[k] for k in ("requests", "tokens")})
    log("run: " + json.dumps(info))
    log("check: per_request_max_gap "
        + " ".join(f"{g:.6g}" for g in chk["per_request"]))
    wr = run.window_recs()
    device = {"platform": devs[0].platform, "kind": kind,
              "count": len(jax.devices()), "memory_peak_bytes": int(peak)}
    if trace:
        import xtrace as trace_mod
        device["busy_s"] = trace_mod.busy_s(run.trace)
        device["window_s"] = run.trace.window_s
        metrics = per_layer(root, bench, run)
    else:
        e2e = end_to_end(run, setup_s)
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in manifest.end_to_end(bench, workload)}
    result = {"correct": bool(correct), "attempted": len(wr),
              "failed": sum(1 for r in wr if not r.ok),
              "metrics": metrics, "device": device}
    if trace:
        import xtrace as trace_mod
        result["breakdown"] = {"device_ops": trace_mod.top_ops(run.trace),
                               "idle_gaps": trace_mod.idle_gaps(run.trace)}
    result["checks"] = checks
    return result, run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    result, _ = execute(ROOT, args.workload, args.seed, args.seconds,
                        bool(args.trace))
    for name, c in result["checks"].items():
        log(f"check: {name} {c['value']:.6g} limit {c['limit']:.6g}")
    print(json.dumps(_finite(result), allow_nan=False), flush=True)
    return 0


def _finite(x):
    """A number JSON cannot hold (a tail that fell on a failed request,
    a gap with nothing compared) is printed as null."""
    if isinstance(x, dict):
        return {k: _finite(v) for k, v in x.items()}
    if isinstance(x, list):
        return [_finite(v) for v in x]
    if isinstance(x, float) and not np.isfinite(x):
        return None
    return x


if __name__ == "__main__":
    sys.exit(main())
