"""What every cell shares: finding its files by name, the platform
check, the compile cache, the per-layer readers and the result line."""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import os
import sys
from pathlib import Path
from typing import Any, Optional

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


class NoResult(Exception):
    """The run cannot give a result; the message says why."""


def load_json(path: Path) -> Any:
    if not path.is_file():
        raise NoResult(f"missing {path.relative_to(ROOT)}")
    return json.loads(path.read_text())


def load_module(path: Path, name: str):
    """Import one file by path (names may hold dots)."""
    if not path.is_file():
        raise NoResult(f"missing {path.relative_to(ROOT)}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    """One workload of BENCHMARK.json with its files loaded."""

    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list     # BENCHMARK.json entries this cell reports
    per_layer: list

    @property
    def fed(self) -> dict:
        """The federation the cell runs: the configuration's, with what
        the traffic mix states on top."""
        return {**self.config.get("federation", {}),
                **self.traffic.get("federation", {})}


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(workload: str, overrides: Optional[dict] = None) -> Cell:
    bench = load_json(ROOT / "BENCHMARK.json")
    entry = next((w for w in bench["workloads"] if w["name"] == workload),
                 None)
    if entry is None:
        raise NoResult(f"no workload {workload!r} in BENCHMARK.json")
    config = load_json(BENCH / "configs" / f"{entry['config']}.json")
    traffic = load_json(BENCH / "traffic" / f"{entry['traffic']}.json")
    limits = load_json(BENCH / "limits" / f"{workload}.json")
    for key, part in (overrides or {}).items():
        {"config": config, "traffic": traffic, "limits": limits}[key]\
            .update(part)
    e2e = [m for m in bench["end_to_end"] if _reports(m, workload)]
    moved = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if (workload in m["workloads"] if "workloads" in m
                 else m["moves"] in moved)]
    return Cell(workload, entry["chips"], config, traffic, limits, e2e,
                layer)


def enable_compile_cache() -> str:
    """JAX's persistent cache at a fixed path inside the checkout, or
    where ``JAX_COMPILATION_CACHE_DIR`` says; every program is kept."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def find_devices(chips: int):
    """The TPU devices; anything else is no result."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoResult(f"needs a TPU; JAX's first device is "
                       f"{devices[0].platform!r} ({devices[0].device_kind})")
    if len(devices) < chips:
        raise NoResult(f"needs {chips} TPU chips, found {len(devices)}")
    return devices[:chips]


@dataclasses.dataclass
class Check:
    """One number compared with the reference, beside its limit."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


@dataclasses.dataclass
class Outcome:
    """What a driver hands back to the harness."""

    end_to_end: dict          # metric name -> value
    attempted: int
    failed: int
    checks: list              # [Check]
    readings: dict            # what the per-layer readers read
    setup_s: float
    trace: Any = None         # bench.trace.TraceSummary of --trace 1
    peak_bytes: Optional[int] = None


def read_per_layer(cell: Cell, readings: dict) -> dict:
    out = {}
    for m in cell.per_layer:
        mod = load_module(BENCH / "metrics" / f"{m['name']}.py",
                          f"bench_metric_{m['name'].replace('.', '_')}")
        value = mod.read(readings)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def result_line(cell: Cell, out: Outcome, devices, trace: bool) -> dict:
    if trace:
        metrics = read_per_layer(cell, out.readings)
    else:
        metrics = {}
        for m in cell.end_to_end:
            value = (out.setup_s if m["name"] == "setup_s"
                     else out.end_to_end[m["name"]])
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": out.peak_bytes}
    line = {"correct": out.failed == 0 and all(c.ok for c in out.checks),
            "attempted": out.attempted, "failed": out.failed,
            "metrics": metrics, "device": device}
    if trace and out.trace is not None:
        device["busy_s"] = out.trace.busy_s
        device["window_s"] = out.trace.window_s
        line["breakdown"] = {"device_ops": out.trace.device_ops,
                             "idle_gaps": out.trace.idle_gaps}
    line["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                      for c in out.checks}
    return line


def print_checks(checks, failed: int, attempted: int) -> None:
    err = sys.stderr
    print(f"failed {failed} of {attempted} attempted (limit 0)", file=err)
    for c in checks:
        print(f"{c.name} {c.value!r} limit {c.limit!r}"
              f"{'' if c.ok else '  FAILS'}", file=err)
    err.flush()
