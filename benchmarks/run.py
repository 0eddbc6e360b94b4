"""Benchmark driver: one harness per paper table + kernel microbench.

Prints ``table,name,value...`` CSV rows (time-to-threshold in the paper's
(t_G, t_C) units, final criterion, hit rate).

  PYTHONPATH=src python -m benchmarks.run [--full] [--only table2,...]
                                          [--json PATH]

``--json PATH`` additionally writes a machine-readable dict of every
module that returned a structured payload (``run`` returning
``(rows, payload)`` instead of bare rows) -- the committed
``BENCH_compress.json`` baseline is produced by
``--only compress_bench --json BENCH_compress.json`` so future PRs can
regress per-case wall times and speedups.
"""

import argparse
import json
import sys
import time

from benchmarks import (compress_bench, engine_bench, kernel_bench,
                        privacy_bounds, roofline_report, table2_comparison,
                        table3_tc_sweep, table4_solvers_pp, table5_large_n,
                        table6_participation, table7_privacy_noise,
                        table8_rho, table9_ne)
from repro.launch.cache import enable_compile_cache

MODULES = {
    "table2": table2_comparison,
    "table3": table3_tc_sweep,
    "table4": table4_solvers_pp,
    "table5": table5_large_n,
    "table6": table6_participation,
    "table7": table7_privacy_noise,
    "table8": table8_rho,
    "table9": table9_ne,
    "privacy": privacy_bounds,
    "compress_bench": compress_bench,
    "engine": engine_bench,
    "kernel": kernel_bench,
    "roofline": roofline_report,
}


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="more Monte-Carlo seeds (slower)")
    ap.add_argument("--only", default=None)
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write structured per-case results (wall "
                         "times, speedups, shapes) as JSON")
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else None

    print("table,name,time_or_value,final_or_aux,extra")
    failures = 0
    payloads = {}
    for name, mod in MODULES.items():
        if only and name not in only:
            continue
        t0 = time.time()
        try:
            result = mod.run(quick=not args.full)
            rows, payload = (result if isinstance(result, tuple)
                             else (result, None))
            for row in rows:
                print(row)
            if payload is not None:
                payloads[name] = payload
        except Exception as e:  # noqa: BLE001
            failures += 1
            print(f"{name},ERROR,{type(e).__name__}: {e}")
        print(f"# {name} done in {time.time() - t0:.1f}s", file=sys.stderr)
    if args.json is not None:
        with open(args.json, "w") as f:
            json.dump(payloads, f, indent=2, sort_keys=True)
        print(f"# wrote {args.json}", file=sys.stderr)
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
