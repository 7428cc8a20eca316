"""Host spans of the serving path, on the device trace's clock.

Every span is a ``jax.profiler.TraceAnnotation``.  With a profiler
running (``jax.profiler.trace`` / ``start_trace``) it lands in the
trace's host plane, on the same clock as the device's ``XLA Modules``
and ``XLA Ops`` events, so a device-idle stretch can be attributed to
the host phase that was running in it.  With no profiler running an
annotation costs well under a microsecond, so the spans are always on.

``serve.step`` is one ``PagedScheduler.step``.  Its children tile it in
the order of ``STEP_PHASES``; a phase with nothing to do in a step (no
prompt chunk, no decode, no pick) opens no span.  ``serve.submit`` is
one ``ServeEngine.submit``.
"""
from __future__ import annotations

import jax

STEP = "serve.step"
STEP_PHASES = (
    "serve.admit",              # queue -> lanes, page allocation, shedding
    "serve.chunk_build",        # the step's inputs built and uploaded
    "serve.prefill_dispatch",   # the [lanes, chunk] prefill program
    "serve.decode_dispatch",    # the [lanes] decode (and its fp fallback)
    "serve.inject",             # final-chunk logits rows -> pick buffer
    "serve.deadlines",          # stalls, timeouts, logit faults
    "serve.pick_dispatch",      # the fused pick + health probe
    "serve.pick_sync",          # host reads that wait for the device
    "serve.commit",             # guards, token append, retire
)
SUBMIT = "serve.submit"


def span(name: str) -> jax.profiler.TraceAnnotation:
    """A host span named ``name``, used as a context manager."""
    return jax.profiler.TraceAnnotation(name)
