"""The measured window: call the cell's unit of work until the time is up.

With ``--trace 1`` the profiler runs over the window, which a traffic
file may shorten with ``trace_seconds`` (a trace of every round of a
long window is large).  Nothing is compiled in the window: the drivers
warm up every shape before it opens.
"""

from __future__ import annotations

import dataclasses
import time

from bench.trace import Profile, Spans


@dataclasses.dataclass
class Measured:
    units: int            # rounds or solves completed in the window
    seconds: float        # host clock, first dispatch to last readback
    spans: Spans          # the harness spans opened in the window
    trace: object = None  # TraceSummary with --trace 1


def measure(one, seconds: float, trace: bool, span_names,
            trace_seconds: float | None = None) -> Measured:
    """Call ``one(i, spans)`` for i = 0, 1, ... until ``seconds`` (or,
    traced, ``min(seconds, trace_seconds)``) have passed; each call
    completes its unit (its result is read back) before it returns."""
    if trace and trace_seconds is not None:
        seconds = min(seconds, trace_seconds)
    spans = Spans()
    profile = None
    if trace:
        profile = Profile()
        profile.start()
        spans.tracing = True
    n = 0
    t0 = time.perf_counter()
    while True:
        one(n, spans)
        n += 1
        if time.perf_counter() - t0 >= seconds:
            break
    elapsed = time.perf_counter() - t0
    summary = None
    if profile is not None:
        spans.tracing = False
        summary = profile.stop_and_reduce(span_names)
    return Measured(units=n, seconds=elapsed, spans=spans, trace=summary)
