#!/usr/bin/env python3
"""A trace reduced by the program's own spans and scopes.

    python3 bench/scopes.py --workload <name> --seed <n> --seconds <s> [--trace 0] [--keep <file>]

runs one cell as ``bench/run.py --trace 1`` does (``--trace 0``:
untraced, telemetry only) and prints its result line, with the numbers
that read the program's telemetry (``repro.fed.telemetry``) besides:
``solver_ms.round``,
``edges_ms.round``, ``step_host_ms.round`` (model cells),
``compile_ms.solve`` and ``compiles.solve`` (convex cells), and in
``breakdown`` the ``clock_offset_ms``, ``device_by_scope`` and
``idle_by_program_span`` of :func:`reduce_scoped`.  The benchmark's own
files are used as they are: this script wraps, in its own process,
``window.measure`` (a telemetry snapshot before and after the window),
``trace.reduce_trace`` (the scoped reduction of the same trace file,
before it is deleted; ``--keep`` copies the file) and
``ModelTrainer.step`` (the round's argument shapes, which
``ModelTrainer.lower`` compiles after the window into the op -> scope
map).

The reduction:

* **Clock offset.**  Host and device clocks in a trace differ.  Each
  device run (``XLA Modules`` event, with its ``run_id``) starts after
  its host enqueue (``DoEnqueueProgram``, same ``run_id``) and ends
  before its host completion callback (``CompleteCallbacks``), which
  bounds ``host - device`` from both sides; the offset is the middle of
  the tightest bounds.  Device intervals are shifted by it before they
  are laid against host spans.
* **Device time per scope.**  Each op of the trace (``XLA Ops``) is
  joined to its scope through ``(module, op)``, the module being the
  enclosing ``XLA Modules`` event, since op names repeat across
  modules.  A scope's time is the union of its ops' intervals in the
  window, wrappers (``trace.WRAPPERS``) left out; ops with no scope go
  to ``unscoped``.
* **Idle time by program span.**  Each idle gap of the shifted device
  is booked to the ``fedplt.*`` span that overlaps it most, else to the
  harness span that does, else to ``trace.NO_SPAN``.
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import time

START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import trace  # noqa: E402

PROGRAM_PREFIX = "fedplt."
MODULE_LINES = ("XLA Modules",)
ENQUEUE, COMPLETE = "DoEnqueueProgram", "CompleteCallbacks"
UNSCOPED = "unscoped"
EDGES = ("fedplt.uplink", "fedplt.aggregate", "fedplt.downlink",
         "fedplt.compress")


@dataclasses.dataclass
class ScopedTrace:
    spans: list       # (start_ns, end_ns, name): harness and program spans
    enqueue: dict     # run_id -> host start of its enqueue
    complete: dict    # run_id -> host start of its completion callback
    modules: list     # per chip: [(start_ns, end_ns, module, run_id)], sorted
    ops: list         # per chip: [(module, op, start_ns, end_ns, wrapper)]


def module_name(event_name: str) -> str:
    """``jit_train_step(1552...)`` -> ``jit_train_step``."""
    return event_name.split("(", 1)[0]


def read_scoped(path: str, span_names) -> ScopedTrace:
    """The events of one trace file that the scoped reduction needs."""
    import jax

    span_names = set(span_names)
    out = ScopedTrace([], {}, {}, [], [])
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith(trace.DEVICE_PLANE_PREFIX):
            mods, ops = [], []
            for line in plane.lines:
                if line.name in MODULE_LINES:
                    mods += [(ev.start_ns, ev.start_ns + ev.duration_ns,
                              module_name(ev.name),
                              dict(ev.stats).get("run_id"))
                             for ev in line.events]
                elif line.name in trace.OP_LINES:
                    ops += [(ev.name, ev.start_ns,
                             ev.start_ns + ev.duration_ns)
                            for ev in line.events]
            if not ops:
                continue
            mods.sort()
            starts = [m[0] for m in mods]
            joined = []
            for text, s, e in ops:
                i = bisect.bisect_right(starts, s) - 1
                mod = mods[i][2] if i >= 0 and s <= mods[i][1] else ""
                joined.append((mod, trace.op_name(text), s, e,
                               any(w in text for w in trace.WRAPPERS)))
            out.modules.append(mods)
            out.ops.append(joined)
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in span_names or \
                        ev.name.startswith(PROGRAM_PREFIX):
                    out.spans.append((ev.start_ns,
                                      ev.start_ns + ev.duration_ns, ev.name))
                elif ev.name in (ENQUEUE, COMPLETE):
                    rid = dict(ev.stats).get("run_id")
                    book = out.enqueue if ev.name == ENQUEUE else out.complete
                    if rid is not None:
                        book.setdefault(rid, ev.start_ns)
    if not out.ops:
        raise RuntimeError("the trace holds no device operation")
    return out


def clock_bounds_ns(t: ScopedTrace):
    """``(lower, upper)`` bounds of ``host - device`` in ns from the runs
    that have both host events; ``None`` where a side has no run."""
    lo = hi = None
    for mods in t.modules:
        for s, e, _, rid in mods:
            if rid in t.enqueue:
                b = t.enqueue[rid] - s
                lo = b if lo is None else max(lo, b)
            if rid in t.complete:
                b = t.complete[rid] - e
                hi = b if hi is None else min(hi, b)
    return lo, hi


def clock_offset_ns(t: ScopedTrace) -> float:
    """Host minus device clock: the middle of :func:`clock_bounds_ns`
    (the one bound there is, or 0 with none)."""
    lo, hi = clock_bounds_ns(t)
    if lo is None and hi is None:
        return 0.0
    if lo is None or hi is None:
        return float(lo if hi is None else hi)
    return (lo + hi) / 2


def _window(t: ScopedTrace, window_names):
    inside = [(s, e) for s, e, n in t.spans if n in window_names]
    if not inside:
        raise RuntimeError(f"the trace holds none of {sorted(window_names)}")
    return min(s for s, _ in inside), max(e for _, e in inside)


def _length(intervals) -> float:
    return sum(e - s for s, e in trace._merge(intervals))


def device_by_scope(t: ScopedTrace, op_map: dict, lo, hi,
                    offset: float = 0.0) -> dict:
    """Seconds of device time per scope in the host window ``[lo, hi]``,
    mean over chips: the union of the scope's op intervals, wrappers
    left out.  ``op_map`` is ``{(module, op): scope}``."""
    out = collections.defaultdict(float)
    for ops in t.ops:
        by = collections.defaultdict(list)
        for mod, op, s, e, wrapper in ops:
            if not wrapper:
                by[op_map.get((mod, op), UNSCOPED)].append(
                    (s + offset, e + offset))
        for scope, ivs in by.items():
            out[scope] += _length(trace._clip(ivs, lo, hi)) * 1e-9 \
                / len(t.ops)
    return {k: v for k, v in out.items() if v > 0}


def scoped_share(t: ScopedTrace, op_map: dict, lo, hi,
                 offset: float = 0.0) -> float:
    """Share of the device's busy time (wrappers included, as
    ``busy_s``) covered by ops that have a scope."""
    scoped = busy = 0.0
    for ops in t.ops:
        ivs = [(s + offset, e + offset) for _, _, s, e, _ in ops]
        busy += _length(trace._clip(ivs, lo, hi))
        scoped += _length(trace._clip(
            [(s + offset, e + offset) for mod, op, s, e, w in ops
             if not w and (mod, op) in op_map], lo, hi))
    return scoped / busy if busy else 0.0


def _overlap_finder(spans):
    """``find(s, e)``: the name of the span in ``spans`` that overlaps
    ``[s, e]`` most, or None; bisects on the starts, so a long window of
    short gaps and spans stays linear."""
    spans = sorted(spans)
    starts = [s for s, _, _ in spans]
    longest = max((e - s for s, e, _ in spans), default=0)

    def find(s, e):
        best, label = 0, None
        for ss, se, name in spans[bisect.bisect_left(starts, s - longest):
                                  bisect.bisect_left(starts, e)]:
            ov = min(e, se) - max(s, ss)
            if ov > best:
                best, label = ov, name
        return label
    return find


def idle_by_program_span(t: ScopedTrace, lo, hi, offset: float = 0.0,
                         harness_names=()) -> dict:
    """Idle seconds of the shifted device in ``[lo, hi]``, mean over
    chips, by the span each gap overlaps most: a program span first,
    then a harness span, else ``trace.NO_SPAN``."""
    program = _overlap_finder(
        [x for x in t.spans if x[2].startswith(PROGRAM_PREFIX)])
    harness = _overlap_finder([x for x in t.spans if x[2] in harness_names])
    out = collections.defaultdict(float)
    for ops in t.ops:
        busy = trace._merge(trace._clip(
            [(s + offset, e + offset) for _, _, s, e, _ in ops], lo, hi))
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for i in range(0, len(edges), 2):
            s, e = edges[i], edges[i + 1]
            if e > s:
                label = program(s, e) or harness(s, e) or trace.NO_SPAN
                out[label] += (e - s) * 1e-9 / len(t.ops)
    return dict(out)


def reduce_scoped(path: str, harness_names, op_map: dict) -> dict:
    """The scoped reduction of one trace file over the window from the
    first harness span to the last (module docstring)."""
    t = read_scoped(path, harness_names)
    lo, hi = _window(t, set(harness_names))
    off = clock_offset_ns(t)
    bounds = clock_bounds_ns(t)
    counts = collections.Counter(n for s, e, n in t.spans
                                 if s >= lo and e <= hi)
    return {
        "clock_offset_ms": off * 1e-6,
        "clock_bounds_ms": [None if b is None else b * 1e-6
                            for b in bounds],
        "device_by_scope": device_by_scope(t, op_map, lo, hi, off),
        "scoped_share": scoped_share(t, op_map, lo, hi, off),
        "idle_by_program_span": idle_by_program_span(
            t, lo, hi, off, set(harness_names)),
        "span_counts": dict(counts),
    }


# ---------------------------------------------------------------------------
# the numbers that read the program's telemetry
# ---------------------------------------------------------------------------

def per_layer(scoped: dict | None, telemetry: dict | None) -> dict:
    """The five numbers, each where what it reads is there."""
    out = {}
    if scoped:
        rounds = scoped["span_counts"].get("round.dispatch")
        dev = scoped["device_by_scope"]
        if rounds and len(dev) > 1:
            out["solver_ms.round"] = \
                1e3 * dev.get("fedplt.local_solver", 0.0) / rounds
            out["edges_ms.round"] = \
                1e3 * sum(dev.get(s, 0.0) for s in EDGES) / rounds
    step = (telemetry or {}).get("fedplt.step")
    if step and step["count"]:
        out["step_host_ms.round"] = 1e3 * step["host_s"] / step["count"]
    run = (telemetry or {}).get("fedplt.run")
    if run and run["count"]:
        out["compile_ms.solve"] = 1e3 * (run["trace_s"] + run["lower_s"]
                                         + run["backend_compile_s"]) \
            / run["count"]
        out["compiles.solve"] = run["compiles"] / run["count"]
    return out


def _shape(a):
    import jax

    return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding)


def run_scoped(workload: str, seed: int, seconds: float, keep=None, *,
               start: float, trace_run: bool = True,
               **run_cell_kw) -> dict:
    """One run of a cell, traced unless ``trace_run`` is false, with the
    scoped reduction: its result line, with the telemetry numbers added
    (module docstring).  ``run_cell_kw`` are ``bench.run.run_cell``'s
    test-only options."""
    from bench import harness, window
    from bench import run as bench_run

    if str(harness.ROOT / "src") not in sys.path:
        sys.path.insert(0, str(harness.ROOT / "src"))
    import jax

    from repro.fed import api, telemetry

    got = {}
    measure, reduce_trace, step = (window.measure, trace.reduce_trace,
                                   api.ModelTrainer.step)

    def window_telemetry():
        got["telemetry"] = telemetry.diff(telemetry.snapshot(),
                                          got["before"])

    def measured(*a, **k):
        got["before"] = telemetry.snapshot()
        m = measure(*a, **k)
        if "telemetry" not in got:
            window_telemetry()
        return m

    def reduced(path, span_names, top=10):
        window_telemetry()      # the window, not the op map's compile
        summary = reduce_trace(path, span_names, top)
        op_map = {}
        if "round" in got:
            trainer, shapes = got["round"]
            op_map = telemetry.op_scopes(
                trainer.lower(*shapes).compile().as_text())
            if not op_map:
                # a load from the persistent cache may hold no op
                # metadata: compile once more without the cache
                jax.config.update("jax_enable_compilation_cache", False)
                try:
                    op_map = telemetry.op_scopes(
                        trainer.lower(*shapes).compile().as_text())
                finally:
                    jax.config.update("jax_enable_compilation_cache", True)
        got["scoped"] = reduce_scoped(path, span_names, op_map)
        got["scoped"]["ops_scoped"] = len(op_map)
        if keep:
            shutil.copy(path, keep)
        return summary

    def noted(self, state, batch, key, *rest):
        if "round" not in got:
            got["round"] = (self, jax.tree_util.tree_map(
                _shape, (state, batch, key)))
        return step(self, state, batch, key, *rest)

    window.measure, trace.reduce_trace = measured, reduced
    api.ModelTrainer.step = noted
    try:
        cell, devices, out = bench_run.run_cell(
            workload, seed, seconds, trace_run, start=start,
            **run_cell_kw)
    finally:
        window.measure, trace.reduce_trace = measure, reduce_trace
        api.ModelTrainer.step = step
    line = harness.result_line(cell, out, devices, trace_run)
    scoped = got.get("scoped")
    for name, value in per_layer(scoped, got.get("telemetry")).items():
        line["metrics"][name] = {"value": value}
    if scoped:
        line.setdefault("breakdown", {}).update(
            {k: scoped[k] for k in ("clock_offset_ms", "clock_bounds_ms",
                                    "device_by_scope", "scoped_share",
                                    "idle_by_program_span", "ops_scoped")},
            idle_by_span=out.trace.idle_by_span)
    line["telemetry"] = got.get("telemetry")
    harness.print_checks(out.checks, out.failed, out.attempted)
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    ap.add_argument("--keep", help="copy the trace file here")
    args = ap.parse_args(argv)
    from bench.harness import NoResult

    try:
        line = run_scoped(args.workload, args.seed, args.seconds, args.keep,
                          start=START, trace_run=bool(args.trace))
    except NoResult as e:
        print(f"bench: no result: {e}", file=sys.stderr)
        return 2
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
