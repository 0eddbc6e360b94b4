"""The chip benchmark of Fed-PLT: one harness, cells named in BENCHMARK.json.

Run from the root of a checkout:

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Each cell is a configuration (``bench/configs/<config>.json``) under a
traffic mix (``bench/traffic/<mix>.json``), run by the driver its
configuration names (``bench/drivers/<driver>.py``) and checked against
limits of its own (``bench/limits/<workload>.json``).  Each per-layer
metric is read by ``bench/metrics/<metric>.py``.
"""
