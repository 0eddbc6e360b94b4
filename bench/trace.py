"""Host spans, the profiler trace, and their reduction to device metrics.

The harness opens a span around each call it makes into the program
(``Spans.span``).  Each span is timed on the host clock, always, and is
also written into the profiler's trace as a ``TraceAnnotation`` when a
trace is being taken, so that the device's idle gaps can be laid
against what the host was doing.

:func:`reduce_trace` reads one ``.xplane.pb`` with nothing but JAX and
returns the device's busy time (the union of the intervals in which an
operation ran, averaged over the chips), the traced window (first to
last harness span), the operations that took most device time, and the
longest idle gaps, each labelled with the harness span that overlapped
it most.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import glob
import os
import shutil
import tempfile
import time

# trace lines that hold one event per device operation; a module line
# ("XLA Modules") would count a whole program as one busy block
OP_LINES = ("XLA Ops",)
DEVICE_PLANE_PREFIX = "/device:TPU:"
NO_SPAN = "outside harness spans"


class Spans:
    """Named host spans with their total host time and count."""

    def __init__(self):
        self.total_s = collections.defaultdict(float)
        self.count = collections.defaultdict(int)
        self.tracing = False

    @contextlib.contextmanager
    def span(self, name: str):
        ann = contextlib.nullcontext()
        if self.tracing:
            import jax

            ann = jax.profiler.TraceAnnotation(name)
        t0 = time.perf_counter()
        with ann:
            yield
        self.total_s[name] += time.perf_counter() - t0
        self.count[name] += 1


class Profile:
    """One profiler trace of part of the window, in a directory of its
    own under ``TMPDIR``, removed once it has been reduced."""

    def __init__(self):
        self.dir = None

    def start(self):
        import jax

        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        jax.profiler.start_trace(self.dir)

    def stop_and_reduce(self, span_names):
        import jax

        jax.profiler.stop_trace()
        try:
            paths = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                              recursive=True)
            if len(paths) != 1:
                raise RuntimeError(f"expected one .xplane.pb under "
                                   f"{self.dir}, found {len(paths)}")
            return reduce_trace(paths[0], span_names)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


@dataclasses.dataclass
class TraceSummary:
    busy_s: float            # device busy time, mean over chips
    window_s: float          # first to last harness span in the trace
    n_chips: int
    device_ops: list         # [[op, seconds]], most time first, <= 10;
    #                          loops and calls that hold other ops left out
    idle_gaps: list          # [[span label, seconds]], longest first
    idle_by_span: dict       # span label -> idle seconds, summed

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def _merge(intervals):
    """Union of ``(start, end)`` intervals, sorted and disjoint."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def _events(plane, line_names):
    for line in plane.lines:
        if line.name in line_names:
            for ev in line.events:
                yield ev.name, ev.start_ns, ev.start_ns + ev.duration_ns


def op_name(text: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    return text.split(" = ", 1)[0].lstrip("%")


# ops that only hold other ops (a scan's while loop spans its whole
# body): they count towards busy time, not in the list of costly ops
WRAPPERS = (" while(", " conditional(", " call(")


def read_trace(path: str, span_names):
    """``(spans, devices)`` of one trace file: the harness spans as
    ``(start_ns, end_ns, name)`` and, per chip, its operations as
    ``(name, start_ns, end_ns)``."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    span_names = set(span_names)
    spans, devices = [], []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            evs = list(_events(plane, OP_LINES))
            if evs:
                devices.append(evs)
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in span_names:
                    spans.append((ev.start_ns,
                                  ev.start_ns + ev.duration_ns, ev.name))
    if not spans:
        raise RuntimeError("the trace holds none of the harness spans "
                           f"{sorted(span_names)}")
    if not devices:
        raise RuntimeError("the trace holds no device operation: no "
                           f"plane named {DEVICE_PLANE_PREFIX}* with a "
                           f"line in {OP_LINES}")
    return spans, devices


def reduce_events(spans, devices, top: int = 10) -> TraceSummary:
    """Busy time, idle gaps and the costliest operations over the window
    from the first harness span's start to the last one's end."""
    lo = min(s for s, _, _ in spans)
    hi = max(e for _, e, _ in spans)
    busy, per_op, gaps = [], collections.defaultdict(float), []
    for evs in devices:
        merged = _merge(_clip([(s, e) for _, s, e in evs], lo, hi))
        busy.append(sum(e - s for s, e in merged) * 1e-9)
        for name, s, e in evs:
            ov = min(e, hi) - max(s, lo)
            if ov > 0 and not any(w in name for w in WRAPPERS):
                per_op[op_name(name)] += ov * 1e-9 / len(devices)
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        gaps.extend((edges[i], edges[i + 1])
                    for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i])

    idle_by_span = collections.defaultdict(float)
    labelled = []
    for s, e in gaps:
        best, label = 0, NO_SPAN
        for ss, se, name in spans:
            ov = min(e, se) - max(s, ss)
            if ov > best:
                best, label = ov, name
        dur = (e - s) * 1e-9 / len(devices)
        idle_by_span[label] += dur
        labelled.append([label, dur])
    labelled.sort(key=lambda x: -x[1])
    ops = sorted(per_op.items(), key=lambda x: -x[1])[:top]
    return TraceSummary(
        busy_s=sum(busy) / len(busy), window_s=(hi - lo) * 1e-9,
        n_chips=len(devices), device_ops=[[n, t] for n, t in ops],
        idle_gaps=labelled[:top], idle_by_span=dict(idle_by_span))


def reduce_trace(path: str, span_names, top: int = 10) -> TraceSummary:
    """Reduce one trace file; ``span_names`` are the harness's spans."""
    return reduce_events(*read_trace(path, span_names), top=top)
