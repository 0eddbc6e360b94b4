#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout.  The workload is looked up in
``BENCHMARK.json``; its configuration, traffic mix, limits, driver and
per-layer readers are files under ``bench/`` found by name.  Set-up
(imports, weights and inputs made from the seed, compilation, warm-up)
is timed from the start of this process to the start of the measured
window.  With ``--trace 1`` part of the window is traced and the result
line carries the per-layer metrics instead of the end-to-end ones.

The last line of standard output is one JSON object; the numbers
compared with the reference are the last lines of standard error.  A
run that finds no TPU, or fewer chips than the cell asks for, exits
non-zero and prints no result line.
"""

from __future__ import annotations

import time

START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import harness  # noqa: E402


class Run:
    """One run's settings, handed to the cell's driver."""

    def __init__(self, cell, seed, seconds, trace, devices, start):
        self.cell = cell
        self.seed = seed
        self.seconds = seconds
        self.trace = trace
        self.devices = devices
        self.start = start


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             start: float, require_tpu: bool = True,
             overrides: dict | None = None):
    """Everything but the printing; returns ``(cell, devices, outcome)``.

    ``require_tpu=False`` and ``overrides`` are for the benchmark's own
    tests, which drive a tiny cell on the CPU."""
    cell = harness.load_cell(workload, overrides)
    src = harness.ROOT / "src"
    if not (src / "repro").is_dir():
        raise harness.NoResult(f"no program under {src}")
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))
    harness.enable_compile_cache()
    if require_tpu:
        devices = harness.find_devices(cell.chips)
    else:
        import jax

        devices = jax.devices()[:cell.chips]
    driver = harness.load_module(
        harness.BENCH / "drivers" / f"{cell.config['driver']}.py",
        f"bench_driver_{cell.config['driver']}")
    outcome = driver.run(Run(cell, seed, seconds, trace, devices, start))
    return cell, devices, outcome


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell, devices, out = run_cell(args.workload, args.seed,
                                      args.seconds, bool(args.trace),
                                      start=START)
    except harness.NoResult as e:
        print(f"bench: no result: {e}", file=sys.stderr)
        return 2
    line = harness.result_line(cell, out, devices, bool(args.trace))
    harness.print_checks(out.checks, out.failed, out.attempted)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
