"""Tests of the benchmark harness.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q bench/tests

They run on the CPU: the cells' files resolve by name, the FLOP count
agrees with a hand count, the trace reduction reads a trace recorded
on the chip, the harness refuses to run without a TPU, and a tiny cell
driven through the harness's test-only path comes out correct, and not
correct once its timed path is broken underneath or once the control
takes the program's place.
"""

from __future__ import annotations

import contextlib
import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench import calibrate, flops, harness, trace  # noqa: E402
from bench import run as bench_run  # noqa: E402

BENCHMARK = json.loads((ROOT / "BENCHMARK.json").read_text())

# a decoder small enough for the CPU, in float32 so that the program and
# the reference agree to float32 rounding
TINY_MODEL = {"hidden_size": 64, "intermediate_size": 128,
              "num_attention_heads": 4, "num_key_value_heads": 2,
              "vocab_size": 256, "num_hidden_layers": 2,
              "torch_dtype": "float32"}
TINY = {
    "phi4mini": {"config": TINY_MODEL,
                 "traffic": {"seq_len": 32, "pool": 4}},
    "logreg": {"traffic": {"problems": 2, "max_rounds": 100}},
}


def tiny(workload: str) -> dict:
    return TINY[workload.split(".")[0]]


def workloads(prefix=""):
    return [w["name"] for w in BENCHMARK["workloads"]
            if w["name"].startswith(prefix)]


# ---------------------------------------------------------------------------
# files found by name
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("workload", workloads())
def test_every_workload_resolves_its_files(workload):
    cell = harness.load_cell(workload)
    bench = ROOT / "bench"
    assert (bench / "drivers" / f"{cell.config['driver']}.py").is_file()
    for m in cell.per_layer:
        assert (bench / "metrics" / f"{m['name']}.py").is_file(), m["name"]
    names = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert cell.per_layer, "every cell reports a per-layer metric"
    for m in cell.per_layer:
        assert m["moves"] in names, (m["name"], m["moves"])
    assert set(cell.limits) == {"loss_gap", "update1_gap", "change3_gap"} \
        or set(cell.limits) == {"traj_gap", "crit_gap", "consensus_err"}


def test_configs_and_paths():
    files = {c["name"]: c["file"] for c in BENCHMARK["configs"]}
    used = {w["config"] for w in BENCHMARK["workloads"]}
    assert used == set(files)
    assert len(set(files.values())) == len(files)
    for name, path in files.items():
        assert path.startswith("bench/") and (ROOT / path).is_file()
        assert json.loads((ROOT / path).read_text())["name"] == name
    assert BENCHMARK["paths"] == ["bench"]


# ---------------------------------------------------------------------------
# FLOPs against a hand count
# ---------------------------------------------------------------------------

def test_flops_match_a_hand_count():
    model = {"hidden_size": 8, "num_attention_heads": 2,
             "num_key_value_heads": 1, "head_dim": 4,
             "intermediate_size": 16, "vocab_size": 10,
             "num_hidden_layers": 1}
    fed = {"seq_len": 4, "n_agents": 2, "n_epochs": 3, "seqs_per_agent": 5}
    # per token: q 8x8, k 8x4, v 8x4, o 8x8, wi 8x32, wo 16x8, head 10x8
    params = 64 + 32 + 32 + 64 + 256 + 128 + 80
    # causal pairs of 4 positions: 10; scores + values, 2 heads of 4
    attn = 2 * (2 * 10 * 2 * 4)
    fwd_seq = 2 * 4 * params + attn
    assert flops.train_flops_per_round(model, fed) == 3 * fwd_seq * 2 * 3 * 5


# ---------------------------------------------------------------------------
# trace reduction
# ---------------------------------------------------------------------------

def test_reduction_by_hand():
    spans = [(0, 40, "round.dispatch"), (40, 100, "round.readback")]
    ops = [("a", 10, 30), ("b", 20, 55), ("a", 70, 80), ("c", 95, 130)]
    s = trace.reduce_events(spans, [ops])
    # busy [10, 55] + [70, 80] + [95, 100] = 60 of a 100 ns window
    assert s.window_s == pytest.approx(100e-9)
    assert s.busy_s == pytest.approx(60e-9)
    assert s.idle_share == pytest.approx(0.4)
    assert s.idle_gaps == [["round.readback", pytest.approx(15e-9)],
                           ["round.readback", pytest.approx(15e-9)],
                           ["round.dispatch", pytest.approx(10e-9)]]
    assert s.idle_by_span == {"round.readback": pytest.approx(30e-9),
                              "round.dispatch": pytest.approx(10e-9)}
    assert s.device_ops[0] == ["b", pytest.approx(35e-9)]
    assert dict(map(tuple, s.device_ops))["c"] == pytest.approx(5e-9)


CHIP_TRACE = ROOT / "bench" / "testdata" / "tiny.xplane.pb"


@pytest.mark.skipif(not CHIP_TRACE.is_file(), reason="no recorded trace")
def test_reduction_of_a_trace_recorded_on_the_chip():
    spans, devices = trace.read_trace(str(CHIP_TRACE),
                                      ("round.dispatch", "round.readback"))
    assert len(devices) == 1 and len(spans) == 6
    s = trace.reduce_events(spans, devices)
    assert 0.0 < s.busy_s < s.window_s
    assert {g[0] for g in s.idle_gaps} <= {"round.dispatch",
                                           "round.readback",
                                           trace.NO_SPAN}
    # every op interval lies inside the busy union, so the union is at
    # most the sum of the ops and at least the longest op
    total = sum(t for _, t in s.device_ops)
    assert max(t for _, t in s.device_ops) <= s.busy_s <= total + 1e-12
    assert sum(s.idle_by_span.values()) == pytest.approx(
        s.window_s - s.busy_s, rel=1e-9)


# ---------------------------------------------------------------------------
# no TPU, no result
# ---------------------------------------------------------------------------

def _run(cwd, env_extra=None):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", **(env_extra or {})}
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "phi4mini.local-heavy", "--seed", "3", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=300)


def test_no_tpu_exits_nonzero_without_a_result_line():
    p = _run(ROOT)
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "needs a TPU" in p.stderr


def test_alone_in_a_directory_it_gives_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert "{" not in p.stdout


# ---------------------------------------------------------------------------
# a tiny cell through the harness, sound and broken
# ---------------------------------------------------------------------------

def drive(workload: str):
    cell, devices, out = bench_run.run_cell(
        workload, 5, 0.5, False, start=time.perf_counter(),
        require_tpu=False, overrides=tiny(workload))
    return harness.result_line(cell, out, devices, False)


@pytest.mark.parametrize("workload", workloads())
def test_tiny_cell_is_correct(workload):
    line = drive(workload)
    assert line["correct"], line["checks"]
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert list(line)[-1] == "checks"


class FrozenTrainer:
    """A trainer whose round or solve returns the state it was given."""

    def __init__(self, inner):
        self._inner = inner
        self.spec = inner.spec

    def init(self, key):
        return self._inner.init(key)

    def step(self, state, batch, key):
        _, metrics = self._inner.step(state, batch, key)
        return state, metrics

    def run(self, key, n_rounds):
        state, crit = self._inner.run(key, n_rounds)
        start = self._inner.init(key)
        return start, crit


class HalfBatchTrainer(FrozenTrainer):
    """Half of each agent's batch is left out; the loss is the mean over
    the rest."""

    def step(self, state, batch, key):
        return self._inner.step(state, calibrate.half_batch(batch), key)

    def run(self, key, n_rounds):
        from repro.core.problem import LogRegProblem
        from repro.fed import api

        p = self._inner.problem
        q = p.A.shape[1] // 2
        half = LogRegProblem(A=p.A[:, :q], b=p.b[:, :q], eps=p.eps)
        return api.DenseTrainer(half, self.spec).run(key, n_rounds)


class AlteredAnswerTrainer(FrozenTrainer):
    """The answer is altered where it is produced."""

    def step(self, state, batch, key):
        state, metrics = self._inner.step(state, batch, key)
        return state, {**metrics, "loss": metrics["loss"] * 1.05}

    def run(self, key, n_rounds):
        state, crit = self._inner.run(key, n_rounds)
        return state._replace(x=state.x * 1.01), crit


@contextlib.contextmanager
def broken(kind):
    """Break the timed path underneath the harness."""
    from repro.fed import api

    if kind == "no_exchange":
        with calibrate.no_exchange():
            yield
        return
    wrap = {"frozen": FrozenTrainer, "half_batch": HalfBatchTrainer,
            "altered_answer": AlteredAnswerTrainer}[kind]
    saved = api.build_trainer
    api.build_trainer = lambda *a, **k: wrap(saved(*a, **k))
    try:
        yield
    finally:
        api.build_trainer = saved


FAULTS = [(w, f) for w in workloads()
          for f in ("frozen", "half_batch", "no_exchange", "altered_answer")]


@pytest.mark.parametrize("workload,fault", FAULTS)
def test_a_broken_timed_path_is_not_correct(workload, fault):
    with broken(fault):
        line = drive(workload)
    assert not line["correct"], line["checks"]


# ---------------------------------------------------------------------------
# the control: the reference one precision step down, in the program's
# place, fails at least one number of each cell
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("workload", workloads())
def test_the_control_is_not_correct(workload):
    over = tiny(workload)
    cell = harness.load_cell(workload, over)
    rows_of = (calibrate.model_rows if cell.config["driver"] ==
               "model_rounds" else calibrate.dense_rows)
    for row in rows_of(cell, [5], n_control=1, n_faults=0):
        failing = [k for k, v in row["control"].items()
                   if not harness.Check(k, v, cell.limits[k]).ok]
        assert failing, (row["control"], cell.limits)


def test_cells_share_no_state_between_calls():
    # two tiny runs of one seed give the same numbers: nothing the first
    # run left behind feeds the second
    a = drive("phi4mini.local-heavy")["checks"]
    b = drive("phi4mini.local-heavy")["checks"]
    assert a == b


def test_setup_span_names_are_the_readers():
    from bench.drivers import dense_solve, model_rounds

    assert set(model_rounds.SPANS) == {"round.dispatch", "round.readback"}
    assert set(dense_solve.SPANS) == {"solve.dispatch", "solve.readback"}
    for m in BENCHMARK["per_layer"]:
        text = (ROOT / "bench" / "metrics" / f"{m['name']}.py").read_text()
        assert "def read(" in text


def test_check_limits_are_numbers():
    for path in (ROOT / "bench" / "limits").glob("*.json"):
        for name, limit in json.loads(path.read_text()).items():
            assert isinstance(limit, float) and limit > 0, (path, name)
