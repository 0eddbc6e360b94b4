"""Driver of the paper's convex cells: Fed-PLT solves of a logistic
regression to the paper's criterion, through the program's front door
(``FedSpec`` -> ``build_trainer`` -> ``run``).

The traffic is a fixed set of ``problems`` federations, problem ``k``
made from data seed ``k`` by the benchmark's copy of the paper's
generator; ``--seed`` sets the order in which the window solves them.
So every seed does the same work, and the rounds each problem needs
(R_k, the first round whose criterion ``||sum_i grad f_i(mean_i x_i)||^2``
is at most the threshold) do not move the time from seed to seed.

Set-up builds one trainer per problem, runs each once for up to
``max_rounds`` rounds to find R_k, then runs ``trainer.run(key, R_k)``
twice, to compile and warm that length.  The window repeats those
solves, each from a fresh init, and reads each solve's criterion
history and final state back to the host.

Compared, after the window, with the float64 reference
(``bench/reference/logreg.py``), worst over the problems solved:

* ``traj_gap``: largest relative gap of the criterion over the first
  three rounds (the local solver and both edges);
* ``crit_gap``: relative gap between the criterion the program reports
  at round R_k and the reference's criterion at the program's final
  state;
* ``consensus_err``: distance of the program's consensus from the
  problem's solution (Newton's method in float64), as a share of the
  solution's norm.

A solve whose own criterion at round R_k is above the threshold, or not
finite, counts as failed; a problem whose criterion never reaches the
threshold within ``max_rounds`` is solved for ``max_rounds`` rounds, and
fails.
"""

from __future__ import annotations

import math
import time

import jax
import numpy as np

from bench import compare, harness, traffic, window
from bench.reference import logreg as ref

SPANS = ("solve.dispatch", "solve.readback")
CHECK_ROUNDS = 3


def hitting_round(crit, threshold):
    """First round (1-based) whose criterion is at most ``threshold``."""
    hit = np.flatnonzero(np.asarray(crit) <= threshold)
    return int(hit[0]) + 1 if hit.size else None


def build(cfg: dict, fed: dict, k: int):
    """Problem ``k``'s data and the program's trainer over it."""
    from repro.core.problem import LogRegProblem
    from repro.fed import api

    A, b = traffic.logreg_data(k, cfg["n_agents"], cfg["q"], cfg["dim"],
                               cfg["heterogeneity"])
    problem = LogRegProblem(A=A, b=b, eps=cfg["eps"], nonconvex=False)
    spec = api.FedSpec(rho=fed["rho"], participation=fed["participation"],
                       damping=fed["damping"], solver=fed["solver"],
                       n_epochs=fed["n_epochs"])
    return (A, b), api.build_trainer(problem, spec)


def reference_gaps(cfg, fed, data, crit, X) -> dict:
    """The three numbers of one problem's solve (module docstring)."""
    A = np.asarray(jax.device_get(data[0]), np.float64)
    b = np.asarray(jax.device_get(data[1]), np.float64)
    _, ref_crit = ref.fed_plt(A, b, cfg["eps"], fed["rho"],
                              fed["n_epochs"], CHECK_ROUNDS,
                              damping=fed["damping"])
    X = np.asarray(X, np.float64)
    x_star = ref.solution(A, b, cfg["eps"])
    R = len(crit)
    return {
        "traj_gap": compare.rel_gap(
            [float(c) for c in crit[:CHECK_ROUNDS]], ref_crit.tolist()),
        "crit_gap": compare.rel_gap(
            [ref.criterion(A, b, X, cfg["eps"])], [float(crit[R - 1])]),
        "consensus_err": float(np.linalg.norm(X.mean(axis=0) - x_star)
                               / np.linalg.norm(x_star)),
    }


def run(r) -> harness.Outcome:
    cell = r.cell
    cfg, fed, tr = cell.config, cell.fed, cell.traffic
    threshold = tr["threshold"]
    problems = []
    for k in range(tr["problems"]):
        data, trainer = build(cfg, fed, k)
        key = traffic.seed_key(k)
        _, crit = trainer.run(key, tr["max_rounds"])
        # a program that never reaches the threshold solves for the
        # whole budget, and every such solve counts as failed
        R = hitting_round(jax.device_get(crit), threshold) \
            or tr["max_rounds"]
        problems.append((data, trainer, key, R))
    for _, trainer, key, R in problems:
        for _ in range(2):
            jax.block_until_ready(trainer.run(key, R))
    rng = np.random.default_rng(r.seed)
    order = []
    setup_s = time.perf_counter() - r.start

    # -- the window ------------------------------------------------------
    last = {}
    failed = []

    def one(j, spans):
        if j % len(problems) == 0:
            order.extend(rng.permutation(len(problems)).tolist())
        k = order[j]
        _, trainer, key, R = problems[k]
        with spans.span("solve.dispatch"):
            state, crit = trainer.run(key, R)
        with spans.span("solve.readback"):
            crit, x = jax.device_get((crit, state.x))
        c = float(crit[R - 1])
        failed.append(not (math.isfinite(c) and c <= threshold))
        last[k] = (crit, x)

    meas = window.measure(one, r.seconds, r.trace, SPANS,
                          tr.get("trace_seconds"))
    peak = int(r.devices[0].memory_stats()["peak_bytes_in_use"]) \
        if r.devices[0].platform == "tpu" else None

    # -- the reference, after the window: every problem solved ----------
    found = {}
    for k, (crit, x) in last.items():
        for name, v in reference_gaps(cfg, fed, problems[k][0], crit,
                                      x).items():
            found[name] = max(found.get(name, 0.0), v)
    checks = [harness.Check(k, v, cell.limits[k]) for k, v in found.items()]
    readings = {"window_s": meas.seconds, "units": meas.units,
                "spans": meas.spans, "trace": meas.trace,
                "rounds_to_target": sum(p[3] for p in problems)
                / len(problems), "peak_bytes": peak,
                "device_kind": r.devices[0].device_kind}
    return harness.Outcome(
        end_to_end={"time_to_target_s": meas.seconds / meas.units},
        attempted=meas.units, failed=sum(failed), checks=checks,
        readings=readings, setup_s=setup_s, trace=meas.trace,
        peak_bytes=peak)
