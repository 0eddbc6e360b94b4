"""Tests of the scoped trace reduction (``bench/scopes.py``).

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q bench/tests

The harness's own reduction of the trace recorded on the chip stays what
it was; the clock offset estimated from that trace lies between the
bounds its runs set; device time per scope and idle time per program
span come out right on events made by hand; and a trace of a toy
``ModelTrainer`` run recorded on the chip holds every program span and
every round scope.

``testdata/scoped.xplane.pb`` is one v5e trace of a warm
``trainer.run(key, 2, batches)``: gemma2-2b ``.reduced()``, N=3, N_e=1,
int8 compression, ``trimmed_mean`` f=1, the Python tracer off.  Its
``/host:metadata`` plane (HLO protos, 1.8 MB) was dropped and the
source paths in its metadata made neutral; ``scoped.ops.json`` is
``telemetry.op_scopes`` of the same round compiled there, cut to the
ops in the trace.
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench import scopes, trace  # noqa: E402

TESTDATA = ROOT / "bench" / "testdata"
TINY = TESTDATA / "tiny.xplane.pb"
SCOPED = TESTDATA / "scoped.xplane.pb"
SCOPED_OPS = TESTDATA / "scoped.ops.json"
ROUND_SPANS = ("round.dispatch", "round.readback")


def test_harness_reduction_of_the_chip_trace_is_unchanged():
    s = trace.reduce_trace(str(TINY), ROUND_SPANS)
    assert (s.busy_s, s.window_s, s.n_chips) == (
        pytest.approx(4.1775e-4, rel=1e-12),
        pytest.approx(0.121895343, rel=1e-12), 1)
    assert [n for n, _ in s.device_ops] == [
        "fusion", "convolution_tanh_fusion", "copy-done",
        "dynamic_slice.1", "copy-start"]
    assert s.device_ops[0][1] == pytest.approx(1.81753e-4, rel=1e-12)
    assert s.idle_gaps[0] == ["round.readback",
                              pytest.approx(0.060248799, rel=1e-12)]
    assert s.idle_by_span == {
        "round.readback": pytest.approx(0.119738418, rel=1e-12),
        trace.NO_SPAN: pytest.approx(0.001739175, rel=1e-12)}


def test_clock_offset_of_the_chip_trace():
    t = scopes.read_scoped(str(TINY), ROUND_SPANS)
    lo, hi = scopes.clock_bounds_ns(t)
    off = scopes.clock_offset_ns(t)
    # 9 device runs, each enqueued and completed on the host
    assert len(t.modules[0]) == 9 and len(t.enqueue) == len(t.complete) + 1
    assert lo < off < hi and hi - lo < 0.25e6
    assert off == pytest.approx(1.69e6, abs=0.01e6)
    # shifted, every run lies between its enqueue and its callback
    for s, e, _, rid in t.modules[0]:
        assert t.enqueue[rid] <= s + off
        if rid in t.complete:
            assert e + off <= t.complete[rid]


def synthetic():
    """Two modules whose ops share names; a while loop that wraps two
    ops; a program span inside each harness span."""
    ops = [("jit_a", "fusion.1", 10, 20, False),
           ("jit_a", "while.0", 20, 60, True),
           ("jit_a", "fusion.2", 20, 40, False),
           ("jit_a", "fusion.3", 35, 50, False),
           ("jit_b", "fusion.1", 70, 80, False),
           ("jit_b", "copy.1", 80, 85, False)]
    modules = [(10, 60, "jit_a", 1), (70, 85, "jit_b", 2)]
    spans = [(0, 30, "round.dispatch"), (5, 25, "fedplt.step"),
             (30, 100, "round.readback")]
    return scopes.ScopedTrace(spans, {1: 8, 2: 69}, {1: 63, 2: 90},
                              [modules], [ops])


OP_MAP = {("jit_a", "fusion.1"): "fedplt.uplink",
          ("jit_a", "fusion.2"): "fedplt.local_solver",
          ("jit_a", "fusion.3"): "fedplt.local_solver",
          ("jit_b", "fusion.1"): "fedplt.downlink",
          ("jit_a", "while.0"): "fedplt.local_solver"}


def test_device_time_per_scope_by_hand():
    t = synthetic()
    got = scopes.device_by_scope(t, OP_MAP, 0, 100)
    # local solver: [20, 40] u [35, 50]; the while wrapper is left out
    assert got == {"fedplt.uplink": pytest.approx(10e-9),
                   "fedplt.local_solver": pytest.approx(30e-9),
                   "fedplt.downlink": pytest.approx(10e-9),
                   scopes.UNSCOPED: pytest.approx(5e-9)}
    # shifted by 5 and clipped to the window [0, 60]: the first module only
    got = scopes.device_by_scope(t, OP_MAP, 0, 60, offset=5)
    assert got == {"fedplt.uplink": pytest.approx(10e-9),
                   "fedplt.local_solver": pytest.approx(30e-9)}
    # busy [10, 60] u [70, 85] = 65; scoped ops cover 10 + 30 + 10 = 50
    assert scopes.scoped_share(t, OP_MAP, 0, 100) == pytest.approx(50 / 65)


def test_clock_bounds_by_hand():
    t = synthetic()
    # enqueue - start: -2, -1; callback - end: 3, 5
    assert scopes.clock_bounds_ns(t) == (-1, 3)
    assert scopes.clock_offset_ns(t) == 1.0


def test_idle_time_per_program_span_by_hand():
    t = synthetic()
    got = scopes.idle_by_program_span(t, 0, 100, 0, ROUND_SPANS)
    # gaps [0, 10] (fedplt.step covers 5 of it), [60, 70], [85, 100]
    assert got == {"fedplt.step": pytest.approx(10e-9),
                   "round.readback": pytest.approx(25e-9)}


def test_per_layer_numbers_by_hand():
    scoped = {"span_counts": {"round.dispatch": 4},
              "device_by_scope": {"fedplt.local_solver": 0.4,
                                  "fedplt.uplink": 0.01,
                                  "fedplt.downlink": 0.03,
                                  scopes.UNSCOPED: 0.02}}
    tel = {"fedplt.step": {"count": 4, "host_s": 0.01},
           "fedplt.run": {"count": 2, "trace_s": 0.1, "lower_s": 0.2,
                          "backend_compile_s": 0.3, "compiles": 2}}
    got = scopes.per_layer(scoped, tel)
    assert got == {"solver_ms.round": pytest.approx(100.0),
                   "edges_ms.round": pytest.approx(10.0),
                   "step_host_ms.round": pytest.approx(2.5),
                   "compile_ms.solve": pytest.approx(300.0),
                   "compiles.solve": pytest.approx(1.0)}
    # a program with no telemetry and no scopes gives none of them
    assert scopes.per_layer({"span_counts": {"round.dispatch": 4},
                             "device_by_scope": {scopes.UNSCOPED: 1.0}},
                            {}) == {}


@pytest.mark.skipif(not SCOPED.is_file(), reason="no recorded trace")
def test_every_scope_and_span_in_a_trace_recorded_on_the_chip():
    from repro.fed import telemetry

    op_map = {(m, op): s for m, op, s in json.loads(SCOPED_OPS.read_text())}
    t = scopes.read_scoped(str(SCOPED), ("fedplt.run",))
    names = {n for _, _, n in t.spans}
    assert {"fedplt.run", "fedplt.init", "fedplt.step"} <= names
    got = scopes.reduce_scoped(str(SCOPED), ("fedplt.run",), op_map)
    assert got["span_counts"]["fedplt.step"] == 2
    assert set(telemetry.SCOPES) <= set(got["device_by_scope"])
    assert all(got["device_by_scope"][s] > 0 for s in telemetry.SCOPES)
    lo, hi = got["clock_bounds_ms"]
    assert lo <= got["clock_offset_ms"] <= hi


# ---------------------------------------------------------------------------
# the tool's wiring, on a tiny model cell on the CPU
# ---------------------------------------------------------------------------

TINY_CELL = {"config": {"hidden_size": 64, "intermediate_size": 128,
                        "num_attention_heads": 4, "num_key_value_heads": 2,
                        "vocab_size": 256, "num_hidden_layers": 2,
                        "torch_dtype": "float32"},
             "traffic": {"seq_len": 32, "pool": 4}}


def test_tool_reports_the_telemetry_numbers(monkeypatch):
    """The CPU trace has no TPU plane, so both reductions read events
    made by hand; everything else is the tool's own path."""
    import time

    import types

    from bench import peaks

    fake = synthetic()
    monkeypatch.setattr(peaks, "peaks_for", lambda kind: types.SimpleNamespace(
        bf16_flops=1e12))
    monkeypatch.setattr(trace, "read_trace", lambda path, names: (
        [x for x in fake.spans if x[2] in names],
        [[("%" + op + " = f32[] op()", s, e) for _, op, s, e, _ in fake.ops[0]]]))
    monkeypatch.setattr(scopes, "read_scoped", lambda path, names: fake)
    line = scopes.run_scoped("phi4mini.comm-heavy", 5, 0.5, start=time.perf_counter(),
                             require_tpu=False, overrides=TINY_CELL)
    assert line["correct"], line["checks"]
    m = line["metrics"]
    assert m["step_host_ms.round"]["value"] > 0
    # the real round compiled on the CPU names none of the hand-made ops
    assert "solver_ms.round" not in m and line["breakdown"]["ops_scoped"] > 0
    assert line["breakdown"]["clock_offset_ms"] == pytest.approx(1e-6)
    assert line["telemetry"]["fedplt.step"]["compiles"] == 0


def test_tool_untraced_reports_telemetry_only(monkeypatch):
    import time

    line = scopes.run_scoped("phi4mini.comm-heavy", 5, 0.5,
                             start=time.perf_counter(), trace_run=False,
                             require_tpu=False, overrides=TINY_CELL)
    assert line["correct"], line["checks"]
    assert {"setup_s", "round_ms", "step_host_ms.round"} <= set(line["metrics"])
    assert "breakdown" not in line
    assert line["telemetry"]["fedplt.step"]["count"] >= 1
