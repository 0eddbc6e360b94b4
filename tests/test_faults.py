"""Fault-tolerant federation runtime: the fault-injection contract.

Three layers under test, mirroring the broker/engine numerics-timing
split:

* :mod:`repro.fed.faults` -- seeded deterministic ``FaultPlan`` /
  ``FaultRecord`` artifacts (JSON round-trippable, NaN included);
* the in-jit fault overrides -- ``corrupt`` injection, the
  ``guard_increments`` uplink screen (a quarantined row IS a
  non-arrival), the survivor mean under ``live`` masks -- all BITWISE
  no-ops when disabled, on every layout x backend combo;
* the hardened :class:`repro.fed.broker.IncrementBroker` -- gate
  timeouts, retry/backoff, eviction, rejoin, and the bit-for-bit
  replay of faulty runs from ``(ArrivalSchedule, FaultRecord)``.

Plus the crash-safe checkpoint layer (atomic tmp-then-rename saves,
key-set validation, resume-bitwise) that rides in the same PR.
"""

import dataclasses
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import (find_latest_checkpoint, is_checkpoint,
                              restore_checkpoint, save_checkpoint)
from repro.core.fedplt import FedPLT
from repro.core.problem import make_quadratic_problem
from repro.fed import async_engine, engine, runtime
from repro.fed.api import (CompressionSpec, FedSpec, PrivacySpec,
                           build_trainer, effective_privacy_report,
                           spec_from_args)
from repro.fed.broker import ArrivalSchedule, IncrementBroker, replay
from repro.fed.engine import RoundConfig
from repro.fed.faults import FaultEvent, FaultPlan, FaultRecord

N_AGENTS = 4

multi_device = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs 8 devices (XLA_FLAGS=--xla_force_host_platform_"
           "device_count=8)")


@pytest.fixture(scope="module")
def quad():
    return make_quadratic_problem(n_agents=N_AGENTS, dim=8, seed=3)


def _algo(quad, compression="none", **kw):
    base = dict(solver="gd", n_epochs=2, gamma=0.05, damping=0.7,
                compression=CompressionSpec(name=compression,
                                            backend="xla"))
    base.update(kw)
    return FedPLT(quad, FedSpec(**base))


def _assert_state_equal(a, b, fields=("x", "z", "y")):
    for f in fields:
        va, vb = getattr(a, f), getattr(b, f)
        if va is None:
            assert vb is None
            continue
        np.testing.assert_array_equal(np.asarray(va), np.asarray(vb),
                                      err_msg=f"field {f}")


# ---------------------------------------------------------------------------
# FaultPlan / FaultRecord artifacts
# ---------------------------------------------------------------------------

def test_fault_event_validation():
    with pytest.raises(ValueError, match="unknown fault kind"):
        FaultEvent("explode", 0, 0)
    with pytest.raises(ValueError, match="agent must be >= 0"):
        FaultEvent("crash", -1, 0)
    with pytest.raises(ValueError, match="must exceed round"):
        FaultEvent("crash", 0, 3, until=3)
    with pytest.raises(ValueError, match="delay must be >= 0"):
        FaultEvent("stall", 0, 0, delay=-0.1)


def test_fault_plan_queries():
    plan = FaultPlan((FaultEvent("crash", 1, 2, until=5),
                      FaultEvent("drop", 0, 1),
                      FaultEvent("corrupt", 2, 3, value=float("nan")),
                      FaultEvent("stall", 3, 0, delay=0.25)))
    assert plan.needs_timeout()
    assert not plan.crashed(1, 1)
    assert plan.crashed(1, 2) and plan.crashed(1, 4)
    assert not plan.crashed(1, 5)          # until is exclusive
    assert plan.rejoins_at(5) == [1]
    assert plan.dropped(0, 1, attempt=0)
    assert not plan.dropped(0, 1, attempt=1)   # one drop eats one try
    assert math.isnan(plan.corrupt_value(2, 3))
    assert plan.corrupt_value(2, 4) is None
    assert plan.stall_delay(3, 0) == 0.25
    lat = plan.wrap_latency(lambda a, r: 0.1)
    assert lat(3, 0) == pytest.approx(0.35) and lat(3, 1) == 0.1
    with pytest.raises(ValueError, match="only 3 agents"):
        plan.check_agents(3)
    # a corrupt-only plan never loses work: no timeout needed
    assert not FaultPlan((FaultEvent("corrupt", 0, 0),)).needs_timeout()


def test_fault_plan_json_roundtrip_with_nan(tmp_path):
    plan = FaultPlan((FaultEvent("corrupt", 0, 1, value=float("nan")),
                      FaultEvent("corrupt", 1, 2, value=float("inf")),
                      FaultEvent("crash", 2, 0, until=4)),
                     n_agents=3, seed=7)
    path = tmp_path / "plan.json"
    plan.save(path)
    loaded = FaultPlan.load(path)
    assert loaded.n_agents == 3 and loaded.seed == 7
    assert math.isnan(loaded.corrupt_value(0, 1))
    assert math.isinf(loaded.corrupt_value(1, 2))
    assert loaded.crashed(2, 3) and not loaded.crashed(2, 4)


def test_fault_plan_generate_deterministic():
    kw = dict(p_crash=0.05, crash_length=2, p_drop=0.1, p_corrupt=0.1,
              p_stall=0.1)
    a = FaultPlan.generate(11, n_agents=8, n_rounds=20, **kw)
    b = FaultPlan.generate(11, n_agents=8, n_rounds=20, **kw)
    assert a.events == b.events and len(a.events) > 0
    c = FaultPlan.generate(12, n_agents=8, n_rounds=20, **kw)
    assert a.events != c.events
    # no new faults are scheduled for an agent while it is down
    for e in a.events:
        assert not any(o.kind == "crash" and o.agent == e.agent
                       and o is not e and o.round <= e.round
                       and (o.until is None or e.round < o.until)
                       for o in a.events)


# the satellite acceptance matrix: every byzantine JSON shape that must
# load, and every malformed one that must be rejected with the same
# error a directly-constructed event raises
_BYZ_JSON_ACCEPT = [
    # (payload, probe(event) -> bool)
    ({"kind": "sign_flip", "agent": 0, "round": 2},
     lambda e: e.byzantine_pair() == (-1.0, 0.0) and e.until is None),
    ({"kind": "sign_flip", "agent": 1, "round": 0, "until": 5},
     lambda e: e.active_at(4) and not e.active_at(5)),
    ({"kind": "scale", "agent": 2, "round": 1, "value": -5.0},
     lambda e: e.byzantine_pair() == (-5.0, 0.0)),
    ({"kind": "scale", "agent": 0, "round": 0, "value": 0.25,
      "until": 3},
     lambda e: e.byzantine_pair() == (0.25, 0.0)),
    ({"kind": "drift", "agent": 3, "round": 7, "value": 0.1},
     lambda e: e.byzantine_pair() == (1.0, 0.1)),
]

_BYZ_JSON_REJECT = [
    # (payload, error-pattern)
    ({"kind": "sign_flip", "agent": 0, "round": 0, "value": 2.0},
     "takes no value"),
    ({"kind": "scale", "agent": 0, "round": 0}, "finite nonzero"),
    ({"kind": "scale", "agent": 0, "round": 0, "value": 0.0},
     "finite nonzero"),
    ({"kind": "drift", "agent": 0, "round": 0,
      "value": float("inf")}, "finite value"),
    ({"kind": "sign_flip", "agent": 0, "round": 0, "delay": 0.5},
     "carry no delay"),
]


@pytest.mark.parametrize("payload,probe", _BYZ_JSON_ACCEPT)
def test_byzantine_json_accepts(payload, probe):
    e = FaultEvent.from_json(payload)
    assert e.byzantine
    assert probe(e)
    # the round trip is exact: dumping re-yields the canonical payload
    assert FaultEvent.from_json(e.to_json()) == e
    assert json.loads(json.dumps(e.to_json())) == e.to_json()


@pytest.mark.parametrize("payload,pattern", _BYZ_JSON_REJECT)
def test_byzantine_json_rejects(payload, pattern):
    with pytest.raises(ValueError, match=pattern):
        FaultEvent.from_json(payload)


def test_byzantine_plan_json_roundtrip_and_generate():
    plan = FaultPlan.generate(21, n_agents=8, n_rounds=10,
                              n_byzantine=3, byzantine_kind="scale",
                              byzantine_value=-2.0, byzantine_start=2)
    again = FaultPlan.generate(21, n_agents=8, n_rounds=10,
                               n_byzantine=3, byzantine_kind="scale",
                               byzantine_value=-2.0, byzantine_start=2)
    assert plan.events == again.events and plan.has_byzantine
    byz = [e for e in plan.events if e.byzantine]
    assert len(byz) == 3
    assert len({e.agent for e in byz}) == 3
    assert all(e.round == 2 and e.until is None for e in byz)
    loaded = FaultPlan.from_json(plan.to_json())
    assert loaded.events == plan.events
    with pytest.raises(ValueError, match="unknown byzantine kind"):
        FaultPlan.generate(0, n_agents=4, n_rounds=2, n_byzantine=1,
                           byzantine_kind="gaussian")
    with pytest.raises(ValueError, match="needs a byzantine_value"):
        FaultPlan.generate(0, n_agents=4, n_rounds=2, n_byzantine=1,
                           byzantine_kind="scale")
    with pytest.raises(ValueError, match="n_byzantine"):
        FaultPlan.generate(0, n_agents=2, n_rounds=2, n_byzantine=3)


def test_fault_plan_indexes_match_scan():
    """Satellite 1: corrupt_value / byzantine_at answer from the
    (agent, round) indexes built at construction; the pre-index linear
    scans are kept as the regression oracle."""
    rng = np.random.default_rng(4)
    events = []
    for _ in range(60):
        kind = rng.choice(["corrupt", "sign_flip", "scale", "drift"])
        agent, rnd = int(rng.integers(6)), int(rng.integers(12))
        until = None if rng.random() < 0.5 else rnd + int(
            rng.integers(1, 4))
        if kind == "corrupt":
            events.append(FaultEvent("corrupt", agent, rnd, until=until,
                                     value=float(rng.normal())))
        elif kind == "sign_flip":
            events.append(FaultEvent("sign_flip", agent, rnd,
                                     until=until))
        elif kind == "scale":
            events.append(FaultEvent("scale", agent, rnd, until=until,
                                     value=float(rng.normal()) or 1.0))
        else:
            events.append(FaultEvent("drift", agent, rnd, until=until,
                                     value=float(rng.normal())))
    plan = FaultPlan(tuple(events))
    for agent in range(6):
        for rnd in range(14):
            assert plan.corrupt_value(agent, rnd) == \
                plan._corrupt_value_scan(agent, rnd)
            assert plan.byzantine_at(agent, rnd) == \
                plan._byzantine_at_scan(agent, rnd)


def test_fault_record_live_index_matches_scan():
    """Satellite 1 (record side): live_row binary-searches cumulative
    snapshots; the linear scan stays as the oracle, including the
    out-of-round-order fallback."""
    rec = FaultRecord(n_agents=5)
    rec.note_eviction(1, 2)
    rec.note_eviction(3, 4)
    rec.note_rejoin(1, 6)
    rec.note_eviction(0, 9)
    for r in range(12):
        got = rec.live_row(r)
        want = rec._live_row_scan(r)
        if want is None:
            assert got is None
        else:
            np.testing.assert_array_equal(got, want)
    # a mutated record invalidates and rebuilds the cache
    rec.note_eviction(4, 10)
    np.testing.assert_array_equal(rec.live_row(11),
                                  rec._live_row_scan(11))
    # out-of-round-order events: the index detects it and falls back
    rec2 = FaultRecord(n_agents=3)
    rec2.note_eviction(0, 5)
    rec2.note_eviction(1, 2)           # earlier round appended later
    for r in range(8):
        got = rec2.live_row(r)
        want = rec2._live_row_scan(r)
        if want is None:
            assert got is None
        else:
            np.testing.assert_array_equal(got, want)


def test_fault_record_live_rows_and_json(tmp_path):
    rec = FaultRecord(n_agents=3)
    assert not rec.has_faults and rec.live_row(5) is None
    rec.note_eviction(1, 2)
    rec.note_rejoin(1, 4)
    rec.note_retry(1, 2, 1)
    rec.note_drop(0, 1)
    rec.note_error(2, 3, RuntimeError("boom"))
    rec.note_corrupt_row(2, np.asarray([0.0, 0.0, float("nan")]))
    assert rec.has_faults
    assert rec.live_row(1) is None      # before the first eviction
    np.testing.assert_array_equal(rec.live_row(2), [1.0, 0.0, 1.0])
    np.testing.assert_array_equal(rec.live_row(4), [1.0, 1.0, 1.0])
    lm = rec.live_matrix(5)
    np.testing.assert_array_equal(lm[:, 1], [1, 1, 0, 0, 1])
    path = tmp_path / "record.json"
    rec.save(path)
    loaded = FaultRecord.load(path)
    assert loaded.evictions == [(1, 2)] and loaded.rejoins == [(1, 4)]
    assert loaded.retries == [(1, 2, 1)] and loaded.drops == [(0, 1)]
    assert "boom" in loaded.errors[0][2]
    assert math.isnan(loaded.corrupt_row(2)[2])
    np.testing.assert_array_equal(loaded.live_row(3), rec.live_row(3))


# ---------------------------------------------------------------------------
# In-jit guards: bitwise no-op when clean, quarantine == non-arrival
# ---------------------------------------------------------------------------

GUARD_CASES = [
    dict(state_layout=layout, engine_backend=backend, compression=comp)
    for layout in ("tree", "packed")
    for backend in ("xla", "pallas")
    for comp in ("none", "topk")
]


@pytest.mark.parametrize(
    "kw", GUARD_CASES,
    ids=[f"{k['state_layout']}-{k['engine_backend']}-{k['compression']}"
         for k in GUARD_CASES])
def test_guards_on_clean_run_is_bitwise_noop(quad, kw):
    key = jax.random.PRNGKey(21)
    plain = _algo(quad, participation=0.6, **kw)
    guarded = _algo(quad, participation=0.6, guard_increments=True,
                    guard_norm_bound=1e6, **kw)
    s0, c0 = plain.run(key, 6)
    s1, c1 = guarded.run(key, 6)
    _assert_state_equal(s0, s1, fields=("x", "z", "t", "y"))
    np.testing.assert_array_equal(np.asarray(c0), np.asarray(c1))


@pytest.mark.parametrize("layout,backend", [
    ("tree", "xla"), ("tree", "pallas"),
    ("packed", "xla"), ("packed", "pallas")])
def test_quarantine_equals_non_arrival_sync(quad, layout, backend):
    # run A: everyone participates, agent 2's increment arrives as NaN,
    # the guard screens it; run B: agent 2 simply never participates.
    # The screened round must be bitwise the non-participation round.
    key = jax.random.PRNGKey(5)
    kw = dict(state_layout=layout, engine_backend=backend,
              guard_increments=True)
    a = _algo(quad, **kw)
    b = _algo(quad, participation=(1.0, 1.0, 0.0, 1.0), **kw)
    corrupt = np.zeros(N_AGENTS, np.float32)
    corrupt[2] = np.nan
    sa, sb = a.init(key), b.init(key)
    for _ in range(3):
        sa, ua = a.round_with_faults(sa, None, jnp.asarray(corrupt), None)
        sb, ub = b.round_with_faults(sb, None, None, None)
        np.testing.assert_array_equal(np.asarray(ua), np.asarray(ub))
    _assert_state_equal(sa, sb)
    assert np.isfinite(np.asarray(sa.x)).all()


def test_quarantine_async_discards_poisoned_work(quad):
    # K > 0: a quarantined agent must NOT keep its poisoned local state
    # (keep &= ok), while a clean non-arriver DOES keep training -- so
    # (z, staleness) agree bitwise and only the straggler's x differs
    key = jax.random.PRNGKey(9)
    algo = _algo(quad, async_mode="stale", max_staleness=1,
                 guard_increments=True)
    corrupt = np.zeros(N_AGENTS, np.float32)
    corrupt[1] = np.inf
    ones = jnp.ones(N_AGENTS)
    miss = jnp.asarray([1.0, 0.0, 1.0, 1.0])
    sa = sb = algo.init(key)
    sa, ua = algo.round_with_faults(sa, ones, jnp.asarray(corrupt), None)
    sb, ub = algo.round_with_faults(sb, miss, None, None)
    np.testing.assert_array_equal(np.asarray(ua), np.asarray(ub))
    np.testing.assert_array_equal(np.asarray(sa.z), np.asarray(sb.z))
    np.testing.assert_array_equal(np.asarray(sa.staleness),
                                  np.asarray(sb.staleness))
    assert np.isfinite(np.asarray(sa.x)).all()   # poison discarded...
    np.testing.assert_array_equal(          # ...x pinned at its old value
        np.asarray(sa.x[1]), np.asarray(algo.init(key).x[1]))
    # the clean straggler kept its local progress instead
    assert not np.array_equal(np.asarray(sb.x[1]),
                              np.asarray(algo.init(key).x[1]))


def test_norm_bound_guard_vs_finiteness_only(quad):
    key = jax.random.PRNGKey(2)
    corrupt = np.zeros(N_AGENTS, np.float32)
    corrupt[0] = 1e4          # large but finite: norm-bound territory
    bounded = _algo(quad, guard_increments=True, guard_norm_bound=100.0)
    unbounded = _algo(quad, guard_increments=True)   # inf: finite-only
    dropped = _algo(quad, guard_increments=True, guard_norm_bound=100.0,
                    participation=(0.0, 1.0, 1.0, 1.0))
    s_b, _ = bounded.round_with_faults(bounded.init(key), None,
                                       jnp.asarray(corrupt), None)
    s_d, _ = dropped.round_with_faults(dropped.init(key), None, None,
                                       None)
    _assert_state_equal(s_b, s_d)       # over-norm row == non-arrival
    s_u, u_u = unbounded.round_with_faults(unbounded.init(key), None,
                                           jnp.asarray(corrupt), None)
    assert float(u_u[0]) == 1.0         # finite -> passes the inf bound
    assert float(jnp.max(jnp.abs(s_u.x))) > 1e2   # and poisons the state


def test_corrupt_without_guard_poisons_consensus(quad):
    algo = _algo(quad)
    corrupt = np.zeros(N_AGENTS, np.float32)
    corrupt[3] = np.nan
    s, u = algo.round_with_faults(algo.init(jax.random.PRNGKey(0)), None,
                                  jnp.asarray(corrupt), None)
    assert float(u[3]) == 1.0
    assert np.isnan(np.asarray(s.x[3])).any()
    assert np.isnan(np.asarray(algo.x_bar(s))).any()


def test_survivor_mean_input_algebra():
    cfg = RoundConfig(n_agents=4)
    z = jnp.asarray(np.random.default_rng(0).normal(size=(4, 5)),
                    jnp.float32)
    live = jnp.asarray([1.0, 0.0, 1.0, 1.0])
    scaled = engine.survivor_mean_input(cfg, z, live)
    np.testing.assert_allclose(
        np.asarray(jnp.mean(scaled, axis=0)),
        np.asarray(jnp.mean(z[jnp.asarray([0, 2, 3])], axis=0)),
        rtol=1e-6)
    assert engine.survivor_mean_input(cfg, z, None) is z


def test_live_mask_drops_evicted_agents_from_round(quad):
    algo = _algo(quad, async_mode="stale", max_staleness=1)
    live = jnp.asarray([1.0, 1.0, 0.0, 1.0])
    s, u = algo.round_with_faults(algo.init(jax.random.PRNGKey(4)),
                                  jnp.ones(N_AGENTS), None, live)
    assert float(u[2]) == 0.0           # forced arrival loses to death
    np.testing.assert_array_equal(np.asarray(s.staleness)[2], 0)
    # the dead agent's state is frozen
    np.testing.assert_array_equal(
        np.asarray(s.z[2]),
        np.asarray(algo.init(jax.random.PRNGKey(4)).z[2]))


@multi_device
@pytest.mark.parametrize("shards", [1, 8])
def test_quarantine_equals_non_arrival_sharded(shards):
    from jax.sharding import Mesh

    prob = make_quadratic_problem(n_agents=8, dim=8, seed=1)
    mesh = Mesh(np.asarray(jax.devices()[:shards]).reshape(shards, 1),
                ("agent", "model"))
    spec = FedSpec(solver="gd", n_epochs=2, gamma=0.05, damping=0.7,
                   state_layout="packed", engine_backend="pallas",
                   guard_increments=True)
    a = FedPLT(prob, spec, mesh=mesh)
    b = FedPLT(prob, dataclasses.replace(
        spec, participation=(1.0,) * 5 + (0.0,) + (1.0,) * 2), mesh=mesh)
    corrupt = np.zeros(8, np.float32)
    corrupt[5] = np.nan
    key = jax.random.PRNGKey(3)
    sa, _ = a.round_with_faults(a.init(key), None, jnp.asarray(corrupt),
                                None)
    sb, _ = b.round_with_faults(b.init(key), None, None, None)
    _assert_state_equal(sa, sb)
    assert np.isfinite(np.asarray(sa.x)).all()


# ---------------------------------------------------------------------------
# Broker fault tolerance: timeout -> retry -> evict -> replay
# ---------------------------------------------------------------------------

def _fault_step(algo):
    return lambda s, u, c, l: algo.round_with_faults(s, u, c, l)[0]


def test_broker_crash_eviction_completes_and_replays_bitwise(quad):
    # 2 of 64 agents crash mid-training: the run completes, evicts them
    # after the retry budget, and the (schedule, record) pair replays
    # the whole trajectory bit-for-bit
    prob = make_quadratic_problem(n_agents=64, dim=4, seed=0)
    algo = FedPLT(prob, FedSpec(
        solver="gd", n_epochs=1, gamma=0.05,
        damping=0.7, async_mode="stale", max_staleness=1))
    plan = FaultPlan((FaultEvent("crash", 3, 2),
                      FaultEvent("crash", 7, 2)))
    broker = IncrementBroker(
        64, max_staleness=1, latency_fn=lambda a, r: 0.0005,
        grace=0.005, gate_timeout=0.05, max_retries=1)
    step = _fault_step(algo)
    key = jax.random.PRNGKey(0)
    final, sched = broker.run(step, algo.init(key), 8, faults=plan)
    rec = broker.record
    assert sorted(a for a, _ in rec.evictions) == [3, 7]
    assert all(r >= 2 for _, r in rec.evictions)
    assert rec.retries                   # the budget was consumed first
    assert sched.live is not None
    first = rec.first_eviction_round()
    assert (sched.arrivals[first:, [3, 7]] == 0.0).all()
    assert (sched.live[first:, [3, 7]] == 0.0).all()
    assert (sched.live[:, [0, 1, 2]] == 1.0).all()
    assert np.isfinite(np.asarray(final.x)).all()
    r_state = replay(step, algo.init(key), sched, record=rec)
    _assert_state_equal(final, r_state, fields=("x", "z", "staleness"))


def test_broker_crash_window_rejoins(quad):
    algo = _algo(quad, async_mode="stale", max_staleness=0)
    plan = FaultPlan((FaultEvent("crash", 1, 1, until=3),))
    broker = IncrementBroker(
        N_AGENTS, max_staleness=0, latency_fn=lambda a, r: 0.001,
        gate_timeout=0.04, max_retries=1)
    step = _fault_step(algo)
    key = jax.random.PRNGKey(1)
    final, sched = broker.run(step, algo.init(key), 5, faults=plan)
    rec = broker.record
    assert rec.evictions == [(1, 1)]
    assert rec.rejoins == [(1, 3)]
    np.testing.assert_array_equal(sched.arrivals[:, 1], [1, 0, 0, 1, 1])
    np.testing.assert_array_equal(sched.live[:, 1], [1, 0, 0, 1, 1])
    r_state = replay(step, algo.init(key), sched, record=rec)
    _assert_state_equal(final, r_state, fields=("x", "z"))


def test_broker_drop_is_recovered_by_redispatch(quad):
    algo = _algo(quad, async_mode="stale", max_staleness=0)
    plan = FaultPlan((FaultEvent("drop", 0, 1),))
    broker = IncrementBroker(
        N_AGENTS, max_staleness=0, latency_fn=lambda a, r: 0.001,
        gate_timeout=0.05, max_retries=2)
    step = _fault_step(algo)
    key = jax.random.PRNGKey(2)
    final, sched = broker.run(step, algo.init(key), 4, faults=plan)
    rec = broker.record
    assert rec.drops == [(0, 1)]
    assert any(a == 0 and r == 1 for a, r, _n in rec.retries)
    assert not rec.evictions and sched.live is None
    # the redispatch got through: nobody missed a synchronous round
    np.testing.assert_array_equal(sched.arrivals,
                                  np.ones((4, N_AGENTS), np.float32))
    r_state = replay(step, algo.init(key), sched, record=rec)
    _assert_state_equal(final, r_state, fields=("x", "z"))


def test_broker_corrupt_plan_is_quarantined_and_replays(quad):
    algo = _algo(quad, async_mode="stale", max_staleness=1,
                 guard_increments=True)
    plan = FaultPlan((FaultEvent("corrupt", 2, 1, value=float("nan")),))
    broker = IncrementBroker(N_AGENTS, max_staleness=1,
                             latency_fn=lambda a, r: 0.001, grace=0.01)
    step = _fault_step(algo)
    key = jax.random.PRNGKey(3)
    final, sched = broker.run(step, algo.init(key), 4, faults=plan)
    rec = broker.record
    assert list(rec.corrupt_rows) == [1]
    assert math.isnan(rec.corrupt_rows[1][2])
    assert not rec.evictions
    assert np.isfinite(np.asarray(final.x)).all()    # guard held
    r_state = replay(step, algo.init(key), sched, record=rec)
    _assert_state_equal(final, r_state, fields=("x", "z", "staleness"))


def test_broker_requires_timeout_for_lossy_plans(quad):
    algo = _algo(quad, async_mode="stale", max_staleness=0)
    plan = FaultPlan((FaultEvent("crash", 0, 0),))
    broker = IncrementBroker(N_AGENTS, max_staleness=0)
    with pytest.raises(ValueError, match="needs a broker gate_timeout"):
        broker.run(_fault_step(algo), algo.init(jax.random.PRNGKey(0)),
                   2, faults=plan)


def test_broker_plan_agent_bounds_checked(quad):
    algo = _algo(quad, async_mode="stale", max_staleness=0)
    plan = FaultPlan((FaultEvent("corrupt", 9, 0),))
    broker = IncrementBroker(N_AGENTS, max_staleness=0)
    with pytest.raises(ValueError, match="only 4 agents"):
        broker.run(_fault_step(algo), algo.init(jax.random.PRNGKey(0)),
                   1, faults=plan)


def test_broker_legacy_round_fn_rejected_on_faulty_rows(quad):
    algo = _algo(quad, async_mode="stale", max_staleness=0)
    plan = FaultPlan((FaultEvent("corrupt", 0, 0, value=2.0),))
    broker = IncrementBroker(N_AGENTS, max_staleness=0,
                             latency_fn=lambda a, r: 0.001)
    step2 = lambda s, u: algo.round_with_arrival(s, u)[0]  # noqa: E731
    with pytest.raises(TypeError, match="4-arg form"):
        broker.run(step2, algo.init(jax.random.PRNGKey(0)), 2,
                   faults=plan)


def test_broker_raising_latency_fn_without_timeout_is_loud(quad):
    algo = _algo(quad, async_mode="stale", max_staleness=0)

    def bad_latency(a, r):
        if a == 1:
            raise OSError("link down")
        return 0.001

    broker = IncrementBroker(N_AGENTS, max_staleness=0,
                             latency_fn=bad_latency)
    step = lambda s, u: algo.round_with_arrival(s, u)[0]  # noqa: E731
    with pytest.raises(RuntimeError, match="agent 1 worker failed"):
        broker.run(step, algo.init(jax.random.PRNGKey(0)), 3)


def test_broker_raising_latency_fn_with_timeout_evicts(quad):
    algo = _algo(quad, async_mode="stale", max_staleness=0)

    def bad_latency(a, r):
        if a == 1:
            raise OSError("link down")
        return 0.001

    broker = IncrementBroker(N_AGENTS, max_staleness=0,
                             latency_fn=bad_latency, gate_timeout=0.2,
                             max_retries=0)
    final, sched = broker.run(
        _fault_step(algo), algo.init(jax.random.PRNGKey(0)), 3)
    rec = broker.record
    assert rec.evictions and rec.evictions[0][0] == 1
    assert rec.errors and "link down" in rec.errors[0][2]
    assert (sched.arrivals[:, 1] == 0.0).all() or \
        sched.arrivals[0, 1] == 0.0


def test_broker_evicting_everyone_raises(quad):
    algo = _algo(quad, async_mode="stale", max_staleness=0)
    plan = FaultPlan(tuple(FaultEvent("crash", a, 0)
                           for a in range(N_AGENTS)))
    broker = IncrementBroker(N_AGENTS, max_staleness=0,
                             latency_fn=lambda a, r: 0.001,
                             gate_timeout=0.02, max_retries=0)
    with pytest.raises(RuntimeError, match="no survivors"):
        broker.run(_fault_step(algo), algo.init(jax.random.PRNGKey(0)),
                   2, faults=plan)


# ---------------------------------------------------------------------------
# Broker edge cases (satellites): fresh buffers, grace drain, degenerate
# shapes
# ---------------------------------------------------------------------------

def test_broker_runs_do_not_share_buffers(quad):
    # regression: a straggler worker outliving its run's join timeout
    # must not be able to submit into a LATER run's buffer.  Agent 0's
    # only submission lands after run 1 returns; if the buffer were an
    # instance attribute, run 2's round 0 would consume it as a
    # perfectly-valid (agent 0, round 0) arrival.
    algo = _algo(quad, async_mode="stale", max_staleness=1)
    broker = IncrementBroker(
        N_AGENTS, max_staleness=1, grace=0.01, join_timeout=0.01,
        latency_fn=lambda a, r: 0.25 if a == 0 else 0.001)
    step = _fault_step(algo)
    key = jax.random.PRNGKey(0)
    _, sched1 = broker.run(step, algo.init(key), 1)
    _, sched2 = broker.run(step, algo.init(key), 1)
    np.testing.assert_array_equal(sched1.arrivals, sched2.arrivals)
    assert sched1.arrivals[0, 0] == 0.0


def test_broker_grace_drains_everything_ready(quad):
    algo = _algo(quad, async_mode="stale", max_staleness=2)
    broker = IncrementBroker(N_AGENTS, max_staleness=2, grace=0.05,
                             latency_fn=lambda a, r: 0.001)
    _, sched = broker.run(_fault_step(algo),
                          algo.init(jax.random.PRNGKey(0)), 3)
    # nobody is must-arrive before staleness 2, but the grace window is
    # long enough that every round drains every agent anyway
    np.testing.assert_array_equal(sched.arrivals,
                                  np.ones((3, N_AGENTS), np.float32))


def test_broker_zero_rounds(quad):
    algo = _algo(quad, async_mode="stale", max_staleness=1)
    state = algo.init(jax.random.PRNGKey(0))
    broker = IncrementBroker(N_AGENTS, max_staleness=1)
    out, sched = broker.run(_fault_step(algo), state, 0)
    assert out is state
    assert sched.arrivals.shape == (0, N_AGENTS)
    assert sched.live is None and broker.record.has_faults is False


def test_broker_single_agent_with_staleness():
    prob = make_quadratic_problem(n_agents=1, dim=4, seed=0)
    algo = FedPLT(prob, FedSpec(
        solver="gd", n_epochs=1, gamma=0.05,
        async_mode="stale", max_staleness=2))
    broker = IncrementBroker(1, max_staleness=2, grace=0.01,
                             latency_fn=lambda a, r: 0.001)
    final, sched = broker.run(_fault_step(algo),
                              algo.init(jax.random.PRNGKey(0)), 5)
    assert sched.arrivals.shape == (5, 1)
    sched.validate()
    assert sched.arrivals.sum() > 0
    assert np.isfinite(np.asarray(final.x)).all()


# ---------------------------------------------------------------------------
# ArrivalSchedule.load validation (satellite)
# ---------------------------------------------------------------------------

def _write(tmp_path, name, payload):
    p = tmp_path / name
    p.write_text(json.dumps(payload))
    return p


def test_schedule_load_rejects_malformed_files(tmp_path):
    ok = {"max_staleness": 1, "arrivals": [[1, 0], [1, 1]]}
    ArrivalSchedule.load(_write(tmp_path, "ok.json", ok))
    with pytest.raises(ValueError, match="need 'arrivals'"):
        ArrivalSchedule.load(_write(tmp_path, "a.json",
                                    {"arrivals": [[1]]}))
    with pytest.raises(ValueError, match="need 'arrivals'"):
        ArrivalSchedule.load(_write(tmp_path, "b.json", [[1, 0]]))
    with pytest.raises(ValueError, match="non-negative integer"):
        ArrivalSchedule.load(_write(
            tmp_path, "c.json", dict(ok, max_staleness=-1)))
    with pytest.raises(ValueError, match="non-negative integer"):
        ArrivalSchedule.load(_write(
            tmp_path, "d.json", dict(ok, max_staleness=1.5)))
    with pytest.raises(ValueError, match="non-negative integer"):
        ArrivalSchedule.load(_write(
            tmp_path, "e.json", dict(ok, max_staleness=True)))
    with pytest.raises(ValueError, match="must be 0 or 1"):
        ArrivalSchedule.load(_write(
            tmp_path, "f.json", dict(ok, arrivals=[[1, 2], [1, 1]])))
    with pytest.raises(ValueError, match="inconsistent lengths"):
        ArrivalSchedule.load(_write(
            tmp_path, "g.json", dict(ok, arrivals=[[1, 0], [1]])))
    with pytest.raises(ValueError, match="got shape"):
        ArrivalSchedule.load(_write(
            tmp_path, "h.json", dict(ok, arrivals=[1, 0, 1])))
    with pytest.raises(ValueError, match="does not match arrivals"):
        ArrivalSchedule.load(_write(
            tmp_path, "i.json", dict(ok, live=[[1, 1]])))
    with pytest.raises(ValueError, match="violates max_staleness"):
        ArrivalSchedule.load(_write(
            tmp_path, "j.json",
            {"max_staleness": 1, "arrivals": [[1, 0], [1, 0], [1, 0]]}))


def test_schedule_save_load_roundtrip_with_live(tmp_path):
    sched = ArrivalSchedule(
        arrivals=np.asarray([[1, 1], [1, 0], [1, 0]], np.float32),
        max_staleness=0,
        live=np.asarray([[1, 1], [1, 0], [1, 0]], np.float32))
    path = tmp_path / "sched.json"
    sched.save(path)
    loaded = ArrivalSchedule.load(path)
    np.testing.assert_array_equal(loaded.arrivals, sched.arrivals)
    np.testing.assert_array_equal(loaded.live, sched.live)


def test_validate_schedule_rejects_ghost_arrivals():
    arr = np.asarray([[1, 1], [1, 1]], np.float32)
    live = np.asarray([[1, 1], [1, 0]], np.float32)
    with pytest.raises(ValueError, match="while evicted"):
        async_engine.validate_schedule(arr, 0, live=live)


def test_effective_counts_exempt_dead_agents_but_keep_releases():
    # agent 0 releases 2 rounds, then is evicted: the charges for the
    # released work stay; the dead rounds neither arrive nor violate
    arr = np.asarray([[1, 1], [1, 1], [0, 1], [0, 1]], np.float32)
    live = np.asarray([[1, 1], [1, 1], [0, 1], [0, 1]], np.float32)
    arrivals, released = async_engine.effective_counts(arr, 0, live=live)
    np.testing.assert_array_equal(arrivals, [2, 4])
    np.testing.assert_array_equal(released, [2, 4])
    sched = ArrivalSchedule(arrivals=arr, max_staleness=0, live=live)
    a2, r2 = sched.validate().effective_counts()
    np.testing.assert_array_equal(a2, arrivals)
    np.testing.assert_array_equal(r2, released)


def test_evicted_agent_still_charged_in_privacy_report():
    spec = FedSpec(n_agents=2, gamma=0.05, n_epochs=3, rho=1.0,
                   privacy=PrivacySpec(tau=0.5, clip=1.0),
                   async_mode="stale", max_staleness=0)
    arr = np.asarray([[1, 1], [1, 1], [0, 1], [0, 1]], np.float32)
    rep = effective_privacy_report(spec, arr, 50)
    a0, a1 = rep.per_agent
    assert a0.K == 2 and a1.K == 4       # released rounds still charged
    assert 0 < a0.adp_eps < a1.adp_eps


# ---------------------------------------------------------------------------
# Spec / config plumbing for the guard knobs
# ---------------------------------------------------------------------------

def test_guard_knobs_thread_through_every_front_end(quad):
    spec = spec_from_args(["--guard-increments",
                           "--guard-norm-bound", "50.0",
                           "--n-agents", str(N_AGENTS)])
    assert spec.guard_increments and spec.guard_norm_bound == 50.0
    ecfg = build_trainer(quad, spec).algo._ecfg
    assert ecfg.guard_increments and ecfg.guard_norm_bound == 50.0
    # defaults stay off (and are bitwise no-ops -- tested above)
    assert not spec_from_args([]).guard_increments
    assert math.isinf(spec_from_args([]).guard_norm_bound)


def test_guard_bound_validation():
    with pytest.raises(ValueError, match="guard_norm_bound"):
        FedSpec(n_agents=4, guard_norm_bound=0.0).validate()
    with pytest.raises(ValueError, match="guard_norm_bound"):
        RoundConfig(n_agents=4, guard_norm_bound=-1.0)
    with pytest.raises(ValueError, match="guard_norm_bound"):
        RoundConfig(n_agents=4, guard_norm_bound=float("nan"))
    cfg = RoundConfig(n_agents=4, guard_increments=1,
                      guard_norm_bound=np.float64(3.0))
    assert cfg.guard_increments is True
    assert cfg.guard_norm_bound == 3.0


# ---------------------------------------------------------------------------
# Crash-safe checkpoints
# ---------------------------------------------------------------------------

def _tree(val):
    return {"a": np.full((2, 3), val, np.float32),
            "b": {"c": np.full((4,), val + 1, np.float32)}}


def test_restore_lists_missing_and_extra_keys_together(tmp_path):
    path = str(tmp_path / "ck")
    save_checkpoint(path, _tree(1.0))
    bad_like = {"a": np.zeros((2, 3), np.float32),
                "d": np.zeros((4,), np.float32)}
    with pytest.raises(ValueError) as ei:
        restore_checkpoint(path, bad_like)
    msg = str(ei.value)
    assert "missing from checkpoint: d" in msg
    assert "unexpected in checkpoint: b/c" in msg


def test_save_checkpoint_failure_preserves_previous(tmp_path,
                                                    monkeypatch):
    path = str(tmp_path / "ck")
    save_checkpoint(path, _tree(1.0), step=1)
    assert is_checkpoint(path)

    def boom(*a, **kw):
        raise RuntimeError("disk full")

    monkeypatch.setattr(np, "savez", boom)
    with pytest.raises(RuntimeError, match="disk full"):
        save_checkpoint(path, _tree(2.0), step=2)
    monkeypatch.undo()
    # the old checkpoint is fully intact and no tmp debris is left
    assert is_checkpoint(path)
    got = restore_checkpoint(path, _tree(0.0))
    np.testing.assert_array_equal(got["a"], _tree(1.0)["a"])
    assert not [n for n in os.listdir(tmp_path) if ".ckpt-tmp-" in n]


def test_save_checkpoint_failure_on_fresh_path_leaves_nothing(
        tmp_path, monkeypatch):
    path = str(tmp_path / "fresh")

    def boom(*a, **kw):
        raise RuntimeError("disk full")

    monkeypatch.setattr(np, "savez", boom)
    with pytest.raises(RuntimeError):
        save_checkpoint(path, _tree(1.0))
    monkeypatch.undo()
    assert not os.path.exists(path)
    assert not [n for n in os.listdir(tmp_path) if ".ckpt-tmp-" in n]


def test_find_latest_checkpoint_skips_debris(tmp_path):
    root = str(tmp_path)
    assert find_latest_checkpoint(root) is None
    save_checkpoint(os.path.join(root, "step-000002"), _tree(1.0), step=2)
    save_checkpoint(os.path.join(root, "step-000010"), _tree(2.0),
                    step=10)
    os.makedirs(os.path.join(root, "step-000099.ckpt-tmp-x"))
    os.makedirs(os.path.join(root, "not-a-checkpoint"))
    latest = find_latest_checkpoint(root)
    assert latest is not None and latest.endswith("step-000010")
    # a direct checkpoint path is itself the answer
    assert find_latest_checkpoint(latest) == latest
    assert find_latest_checkpoint(str(tmp_path / "missing")) is None


class _TinyModel:
    def init(self, key):
        return {"w": jnp.zeros(6, jnp.float32)}

    def loss_fn(self, params, batch, remat=False):
        return 0.5 * jnp.sum((params["w"] - batch["target"]) ** 2)


def test_checkpoint_resume_is_bitwise(tmp_path):
    # 6 straight rounds == 3 rounds + atomic save + restore + 3 rounds,
    # bit for bit (per-round keys are fold_in-derived, as in the driver)
    model = _TinyModel()
    spec = FedSpec(n_agents=4, gamma=0.1, n_epochs=2, participation=0.7)
    step = jax.jit(runtime.make_train_step(model, spec))
    batch = {"target": jnp.broadcast_to(
        jnp.arange(6, dtype=jnp.float32), (4, 6))}
    key = jax.random.PRNGKey(8)

    state_a = runtime.init_state(model, key, spec)
    for i in range(6):
        state_a, _ = step(state_a, batch, jax.random.fold_in(key, i))

    state_b = runtime.init_state(model, key, spec)
    for i in range(3):
        state_b, _ = step(state_b, batch, jax.random.fold_in(key, i))
    path = str(tmp_path / "rounds" / "step-000003")
    save_checkpoint(path, state_b, step=3, extra={"round": 3})
    like = runtime.init_state(model, key, spec)
    resumed = restore_checkpoint(
        find_latest_checkpoint(str(tmp_path / "rounds")), like)
    for i in range(3, 6):
        resumed, _ = step(resumed, batch, jax.random.fold_in(key, i))

    for la, lb in zip(jax.tree_util.tree_leaves((state_a.x, state_a.z)),
                      jax.tree_util.tree_leaves((resumed.x, resumed.z))):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
