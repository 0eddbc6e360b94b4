"""The program's own measurements: host spans, compile counters, and the
map from compiled ops to the round's named scopes.

* :func:`span` times a call on the host and writes the same name into a
  profiler trace (``jax.profiler.TraceAnnotation``; near free when no
  trace is taken).  An in-memory registry keeps, per span name, its
  count, host seconds, self seconds (host seconds less the time its
  child spans cover) and the name of its parent span.
* Compile events that JAX reports through ``jax.monitoring`` are booked
  to the innermost open span: ``trace_s``, ``lower_s``,
  ``backend_compile_s`` (a compile or a load from the persistent cache;
  ``cache_load_s`` is the load's part of it), ``compiles`` (one per
  executable obtained, compiled or loaded), ``cache_hits`` and
  ``cache_misses``.  Events with no open span go to :data:`NO_SPAN`.
* :func:`snapshot` returns the registry as a plain dict; a window's
  numbers are the difference of two snapshots (:func:`diff`).
* :func:`op_scopes` reads a compiled executable's HLO text and maps each
  op to the innermost ``fedplt.*`` scope of its metadata -- the join
  from a device trace's ops to the phases of the round.
* :func:`compiled_memory` reads a compiled executable's memory plan:
  how much of its arguments its outputs alias, and how many ops XLA's
  rematerialisation pass cloned to fit the program in memory.

Device-side scopes are :data:`SCOPES`, put on the round engine's shared
functions with :func:`scope`.  A span never reads a device value, so it
adds no sync.
"""

from __future__ import annotations

import collections
import functools
import re
import threading
import time

import jax

SCOPES = ("fedplt.uplink", "fedplt.aggregate", "fedplt.local_solver",
          "fedplt.downlink", "fedplt.compress")
NO_SPAN = "outside spans"

_DURATIONS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    "/jax/core/compile/backend_compile_duration": "backend_compile_s",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_load_s",
}
_COUNTS = {
    "/jax/core/compile/backend_compile_duration": "compiles",
    "/jax/compilation_cache/cache_hits": "cache_hits",
    "/jax/compilation_cache/cache_misses": "cache_misses",
}
FIELDS = ("count", "host_s", "self_s") + tuple(_DURATIONS.values()) \
    + tuple(_COUNTS.values())

_lock = threading.Lock()
_local = threading.local()
_registry: dict = collections.defaultdict(
    lambda: dict.fromkeys(FIELDS, 0) | {"parent": None})


def _stack() -> list:
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


def _innermost() -> str:
    stack = _stack()
    return stack[-1][0] if stack else NO_SPAN


class span:
    """Context manager: time the enclosed host work under ``name``.
    ``step=True`` marks one round: the trace gets a
    ``StepTraceAnnotation`` numbered by this span's own host-side count.
    ``ids`` go into the trace annotation."""

    __slots__ = ("name", "ann", "frame", "t0")

    def __init__(self, name: str, *, step: bool = False, **ids):
        self.name = name
        if step:
            self.ann = jax.profiler.StepTraceAnnotation(
                name, step_num=_registry[name]["count"], **ids)
        else:
            self.ann = jax.profiler.TraceAnnotation(name, **ids)

    def __enter__(self):
        self.frame = [self.name, 0.0]   # [name, seconds its children took]
        _stack().append(self.frame)
        self.ann.__enter__()
        self.t0 = time.perf_counter()

    def __exit__(self, *exc):
        dur = time.perf_counter() - self.t0
        self.ann.__exit__(*exc)
        stack = _stack()
        stack.pop()
        parent = stack[-1] if stack else None
        if parent is not None:
            parent[1] += dur
        with _lock:
            rec = _registry[self.name]
            rec["count"] += 1
            rec["host_s"] += dur
            rec["self_s"] += dur - self.frame[1]
            rec["parent"] = parent[0] if parent is not None else None


def scope(name: str):
    """Decorator: trace the function under ``jax.named_scope(name)``,
    which only names its ops (metadata); the computation is unchanged."""
    def wrap(fn):
        @functools.wraps(fn)
        def scoped(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return scoped
    return wrap


def _on_duration(event: str, duration: float, **_):
    field = _DURATIONS.get(event)
    if field is None:
        return
    name = _innermost()
    with _lock:
        rec = _registry[name]
        rec[field] += duration
        if event in _COUNTS:
            rec[_COUNTS[event]] += 1


def _on_event(event: str, **_):
    field = _COUNTS.get(event)
    if field is None:
        return
    name = _innermost()
    with _lock:
        _registry[name][field] += 1


jax.monitoring.register_event_duration_secs_listener(_on_duration)
jax.monitoring.register_event_listener(_on_event)


def snapshot() -> dict:
    """The registry as ``{span name: {field: value, "parent": name}}``."""
    with _lock:
        return {k: dict(v) for k, v in _registry.items()}


def diff(after: dict, before: dict) -> dict:
    """What happened between two snapshots, per span name; a name with
    nothing new is left out."""
    out = {}
    for name, rec in after.items():
        old = before.get(name, {})
        d = {f: rec[f] - old.get(f, 0) for f in FIELDS}
        if any(d.values()):
            out[name] = d | {"parent": rec["parent"]}
    return out


_MODULE = re.compile(r"^HloModule ([^\s,]+)", re.M)
_OP = re.compile(r"^\s*(?:ROOT )?%?([^\s=]+) = .*?metadata=\{[^}]*?"
                 r"op_name=\"([^\"]*)\"", re.M)
_SCOPE = re.compile(r"fedplt\.[A-Za-z_]+")


def op_scopes(compiled_text: str) -> dict:
    """``{(module, op): scope}`` of a compiled executable's HLO text
    (``jax.stages.Compiled.as_text()``): ``scope`` is the innermost
    ``fedplt.*`` name in the op's ``metadata={op_name=...}``.  Ops with
    no such name are left out."""
    m = _MODULE.search(compiled_text)
    module = m.group(1) if m else ""
    out = {}
    for op, op_name in _OP.findall(compiled_text):
        found = _SCOPE.findall(op_name)
        if found:
            out[(module, op)] = found[-1]
    return out


_REMAT = re.compile(r"^\s*(?:ROOT )?%?[^\s=]*\.remat[^\s=]* = ", re.M)


def compiled_memory(compiled) -> dict:
    """The memory plan of a ``jax.stages.Compiled``, in bytes:
    ``argument_bytes``, ``output_bytes``, ``alias_bytes`` (outputs that
    reuse a donated argument's buffer), ``temp_bytes`` and
    ``peak_bytes`` (the compiler's own peak; 0 where the backend does
    not plan one), and ``remat_clones``: HLO instructions whose name
    holds ``.remat``, the copies XLA's rematerialisation pass makes when
    the program would not fit in the device's memory otherwise."""
    m = compiled.memory_analysis()
    return {"argument_bytes": m.argument_size_in_bytes,
            "output_bytes": m.output_size_in_bytes,
            "alias_bytes": m.alias_size_in_bytes,
            "temp_bytes": m.temp_size_in_bytes,
            "peak_bytes": m.peak_memory_in_bytes,
            "remat_clones": len(_REMAT.findall(compiled.as_text()))}
