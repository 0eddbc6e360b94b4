#!/usr/bin/env python3
"""Readings that the limits of ``bench/limits/<workload>.json`` are set
from; the benchmark's own runs never run this.

    python3 bench/calibrate.py --workload <name> --seeds 101-112 \\
        [--control 3] [--faults 3] [--out calib.json]

For each seed, in one process (one compile): the program's numbers
against the reference, exactly as a run compares them (the lower
reading is the largest of these).  On the first ``--control`` seeds the
control: the reference put in the program's place one precision step
below the configuration's float32 (bfloat16 matmul operands and state
for the model cells, bfloat16 after every operation for the convex
ones).  On the first ``--faults`` seeds, faults planted in the program:
half of each agent's batch left out (the mean taken over the rest), the
agent mean of the uplink left out (each agent reads agent 0's state:
the exchange), and for the convex cells an answer altered where it is
produced.  A state left unchanged reads 1 on the gaps of
norms, and 1 on ``consensus_err``, and needs no run.

Prints one JSON line per seed and a summary; ``--out`` keeps them.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import harness  # noqa: E402


def seed_list(text: str) -> list:
    out = []
    for part in text.split(","):
        lo, _, hi = part.partition("-")
        out.extend(range(int(lo), int(hi or lo) + 1))
    return out


@contextlib.contextmanager
def no_exchange():
    """Plant the fault: the uplink's agent mean returns agent 0's rows."""
    import jax

    from repro.fed import engine

    saved = engine.agent_mean
    engine.agent_mean = lambda z: jax.tree_util.tree_map(lambda l: l[0], z)
    try:
        yield
    finally:
        engine.agent_mean = saved


def half_batch(batch):
    """Half of each agent's batch: the sequences, or the tokens of a
    single sequence."""
    import jax

    def cut(a):
        if a.shape[1] >= 2:
            return a[:, :a.shape[1] // 2]
        return a[:, :, :a.shape[2] // 2]

    return jax.tree_util.tree_map(cut, batch)


def model_rows(cell, seeds, n_control, n_faults):
    from bench import compare, traffic
    from bench.drivers import model_rounds as mr

    model, fed = cell.config, mr.cell_fed(cell)
    trainer = mr.build(model, fed, mr.keys(seeds[0])[0])
    for n, seed in enumerate(seeds):
        wkey, rkey = mr.keys(seed)
        pool = traffic.lm_pool(seed, model["vocab_size"], fed, cell.traffic)
        t0 = time.perf_counter()
        state, prog = mr.first_rounds(trainer, model, wkey, rkey, pool)
        del state
        t1 = time.perf_counter()
        ref = mr.reference_rounds(model, fed, wkey, pool)
        t2 = time.perf_counter()
        row = {"seed": seed, "program": mr.gaps(prog, ref),
               "losses": prog[0], "ref_losses": ref[0],
               "update1_leaves": compare.leaf_gaps(prog[1], ref[1]),
               "change3_leaves": compare.leaf_gaps(prog[2], ref[2]),
               "program_s": t1 - t0, "reference_s": t2 - t1}
        if n < n_control:
            ctrl = mr.reference_rounds(
                model, fed, wkey, pool,
                mr.ref.CONTROL[model["torch_dtype"]])
            row["control"] = mr.gaps(ctrl, ref)
            row["control_losses"] = ctrl[0]
        if n < n_faults:
            half = [half_batch(b) for b in pool[:mr.CHECK_ROUNDS]]
            state, got = mr.first_rounds(trainer, model, wkey, rkey, half)
            del state
            row["half_batch"] = mr.gaps(got, ref)
            with no_exchange():
                broken = mr.build(model, fed, wkey)
                state, got = mr.first_rounds(broken, model, wkey, rkey, pool)
            del state, broken
            row["no_exchange"] = mr.gaps(got, ref)
        yield row


def dense_rows(cell, seeds, n_control, n_faults):
    import jax
    import numpy as np

    from bench import traffic
    from bench.drivers import dense_solve as ds
    from bench.reference import logreg as ref

    cfg, fed, tr = cell.config, cell.fed, cell.traffic
    for n, k in enumerate(seeds):
        data, trainer = ds.build(cfg, fed, k)
        key = traffic.seed_key(k)
        _, crit = trainer.run(key, tr["max_rounds"])
        R = ds.hitting_round(jax.device_get(crit), tr["threshold"])
        state, crit = trainer.run(key, R)
        crit, X = jax.device_get((crit, state.x))
        row = {"problem": k, "R": R,
               "program": ds.reference_gaps(cfg, fed, data, crit, X)}
        A = np.asarray(jax.device_get(data[0]), np.float64)
        b = np.asarray(jax.device_get(data[1]), np.float64)
        if n < n_control:
            Xc, cc = ref.fed_plt(A, b, cfg["eps"], fed["rho"],
                                 fed["n_epochs"], R, damping=fed["damping"],
                                 dtype="bf16")
            row["control"] = ds.reference_gaps(cfg, fed, data, cc, Xc)
        if n < n_faults:
            from repro.core.problem import LogRegProblem
            from repro.fed import api

            q = cfg["q"] // 2
            half = api.build_trainer(
                LogRegProblem(A=data[0][:, :q], b=data[1][:, :q],
                              eps=cfg["eps"]), trainer.spec)
            s, c = half.run(key, R)
            row["half_batch"] = ds.reference_gaps(
                cfg, fed, data, *jax.device_get((c, s.x)))
            with no_exchange():
                _, broken = ds.build(cfg, fed, k)
                s, c = broken.run(key, R)
                c, x = jax.device_get((c, s.x))
            row["no_exchange"] = ds.reference_gaps(cfg, fed, data, c, x)
            Xa = np.array(X, np.float64)
            Xa[:, 0] *= 1.01
            row["altered_answer"] = ds.reference_gaps(cfg, fed, data, crit,
                                                      Xa)
        yield row


def summary(rows) -> dict:
    """Per number: the lower reading (largest of the program's) and the
    smallest reading of the control and of each fault."""
    out = {}
    names = rows[0]["program"].keys()
    for name in names:
        entry = {"lower": max(r["program"][name] for r in rows)}
        for kind in ("control", "half_batch", "no_exchange",
                     "altered_answer"):
            vals = [r[kind][name] for r in rows if kind in r]
            if vals:
                entry[kind] = min(vals)
        out[name] = entry
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, type=seed_list)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--faults", type=int, default=3)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    sys.path.insert(0, str(harness.ROOT / "src"))
    harness.enable_compile_cache()
    rows_of = {"model_rounds": model_rows,
               "dense_solve": dense_rows}[cell.config["driver"]]
    rows = []
    for row in rows_of(cell, args.seeds, args.control, args.faults):
        print(json.dumps(row), flush=True)
        rows.append(row)
    summ = summary(rows)
    print(json.dumps({"summary": summ}), flush=True)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps({"workload": args.workload,
                                              "rows": rows,
                                              "summary": summ}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
