"""Bounded-staleness async rounds: the async-engine contract.

The headline contract: ``async_mode="stale"`` with ``max_staleness=0``
is BITWISE identical to the synchronous engine per realization -- under
both state layouts, both engine backends, and the registry compressors
-- and any recorded arrival schedule replays bit-for-bit through the
in-jit model (the broker only ever chooses the rows).  On top of that:
staleness counter semantics (stragglers keep training, forced arrival
at the bound), the arrival-schedule privacy composition, participation
/ arrival-mask edge cases, and the construction-time numeric validation
the async fields ride in on (damping / staleness).
"""

import argparse

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.fedplt import FedPLT
from repro.core.problem import make_quadratic_problem
from repro.fed import async_engine, engine, runtime
from repro.fed.api import (CompressionSpec, FedSpec, PrivacySpec,
                           add_spec_args, build_trainer,
                           effective_privacy_report, privacy_report,
                           spec_from_args)
from repro.fed.broker import ArrivalSchedule, IncrementBroker, replay
from repro.fed.engine import RoundConfig, StalenessConfig

N_AGENTS = 6
ROUNDS = 10


@pytest.fixture(scope="module")
def quad():
    return make_quadratic_problem(n_agents=N_AGENTS, dim=8, seed=3)


def _dense_pair(quad, compression="none", **kw):
    base = dict(solver="gd", n_epochs=3, gamma=0.05, participation=0.6,
                damping=0.7, **kw,
                compression=CompressionSpec(name=compression,
                                            backend="xla"))
    sync = FedPLT(quad, FedSpec(**base))
    asy = FedPLT(quad, FedSpec(**base, async_mode="stale",
                               max_staleness=0))
    return sync, asy


# ---------------------------------------------------------------------------
# max_staleness = 0 == the synchronous engine, bit for bit
# ---------------------------------------------------------------------------

DENSE_CASES = [
    dict(state_layout=layout, engine_backend=backend, compression=comp)
    for layout in ("tree", "packed")
    for backend in ("xla", "pallas")
    for comp in ("none", "topk", "int8")
]


@pytest.mark.parametrize(
    "kw", DENSE_CASES,
    ids=[f"{k['state_layout']}-{k['engine_backend']}-{k['compression']}"
         for k in DENSE_CASES])
def test_k0_bitwise_equals_sync_dense(quad, kw):
    sync, asy = _dense_pair(quad, **kw)
    key = jax.random.PRNGKey(42)
    s_state, s_crit = sync.run(key, ROUNDS)
    a_state, a_crit, sched = asy.run_recorded(key, ROUNDS)
    np.testing.assert_array_equal(np.asarray(s_state.x),
                                  np.asarray(a_state.x))
    np.testing.assert_array_equal(np.asarray(s_state.z),
                                  np.asarray(a_state.z))
    if s_state.t is not None:
        np.testing.assert_array_equal(np.asarray(s_state.t),
                                      np.asarray(a_state.t))
    np.testing.assert_array_equal(np.asarray(s_crit), np.asarray(a_crit))
    # at K = 0 the arrival mask IS the participation draw: partial
    assert 0 < np.asarray(sched).sum() < ROUNDS * N_AGENTS


class QuadModel:
    def init(self, key):
        return {"x": jnp.zeros(8)}

    def loss_fn(self, params, batch, remat=False):
        x = params["x"]
        return 0.5 * x @ batch["Q"] @ x + batch["c"] @ x


@pytest.mark.parametrize("layout,backend,comp", [
    ("tree", "xla", "none"),
    ("tree", "pallas", "topk"),
    ("packed", "xla", "int8"),
    ("packed", "pallas", "none"),
])
def test_k0_bitwise_equals_sync_model(quad, layout, backend, comp):
    model, batch = QuadModel(), {"Q": quad.Q, "c": quad.c}
    base = dict(n_agents=N_AGENTS, gamma=0.05, n_epochs=2,
                participation=0.7, state_layout=layout,
                engine_backend=backend,
                compression=CompressionSpec(name=comp))
    key = jax.random.PRNGKey(0)
    states = {}
    for tag, extra in (("sync", {}),
                       ("async", dict(async_mode="stale",
                                      max_staleness=0))):
        spec = FedSpec(**base, **extra)
        step = jax.jit(runtime.make_train_step(model, spec))
        state = runtime.init_state(model, key, spec)
        for i in range(4):
            state, m = step(state, batch, jax.random.PRNGKey(7))
        states[tag] = state
    for leaf_s, leaf_a in zip(
            jax.tree_util.tree_leaves((states["sync"].x,
                                       states["sync"].z)),
            jax.tree_util.tree_leaves((states["async"].x,
                                       states["async"].z))):
        np.testing.assert_array_equal(np.asarray(leaf_s),
                                      np.asarray(leaf_a))


# ---------------------------------------------------------------------------
# Recorded schedules replay bit-for-bit
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("layout,backend", [("tree", "xla"),
                                            ("packed", "pallas")])
def test_recorded_schedule_replays_bitwise(quad, layout, backend):
    algo = FedPLT(quad, FedSpec(
        solver="gd", n_epochs=3, gamma=0.05,
        participation=0.4, damping=0.7, async_mode="stale",
        max_staleness=3, state_layout=layout, engine_backend=backend))
    key = jax.random.PRNGKey(11)
    state, crit, sched = algo.run_recorded(key, 20)
    async_engine.validate_schedule(np.asarray(sched), 3)
    r_state, r_crit = algo.replay(key, sched)
    np.testing.assert_array_equal(np.asarray(state.x),
                                  np.asarray(r_state.x))
    np.testing.assert_array_equal(np.asarray(state.z),
                                  np.asarray(r_state.z))
    np.testing.assert_array_equal(np.asarray(state.staleness),
                                  np.asarray(r_state.staleness))
    np.testing.assert_array_equal(np.asarray(crit), np.asarray(r_crit))


def test_broker_run_replays_bitwise(quad):
    algo = FedPLT(quad, FedSpec(
        solver="gd", n_epochs=2, gamma=0.05,
        damping=0.7, async_mode="stale", max_staleness=2))
    key = jax.random.PRNGKey(5)
    step = lambda s, u: algo.round_with_arrival(s, u)[0]  # noqa: E731
    # agent 0 is a 10x straggler: it must be carried by the staleness
    # bound, arriving roughly every K+1 rounds
    broker = IncrementBroker(
        N_AGENTS, max_staleness=2, grace=0.003,
        latency_fn=lambda a, r: 0.01 if a == 0 else 0.001)
    final, sched = broker.run(step, algo.init(key), 12)
    assert sched.n_rounds == 12 and sched.n_agents == N_AGENTS
    sched.validate()
    arr, _ = sched.effective_counts()
    assert arr[0] < arr[1]          # the straggler arrived less often
    r_state = replay(step, algo.init(key), sched)
    np.testing.assert_array_equal(np.asarray(final.x),
                                  np.asarray(r_state.x))
    np.testing.assert_array_equal(np.asarray(final.z),
                                  np.asarray(r_state.z))


def test_broker_k0_is_the_synchronous_barrier(quad):
    algo = FedPLT(quad, FedSpec(
        solver="gd", n_epochs=2, gamma=0.05,
        async_mode="stale", max_staleness=0))
    step = lambda s, u: algo.round_with_arrival(s, u)[0]  # noqa: E731
    broker = IncrementBroker(N_AGENTS, max_staleness=0,
                             latency_fn=lambda a, r: 0.001)
    _, sched = broker.run(step, algo.init(jax.random.PRNGKey(0)), 5)
    # blocking on every dispatched agent: everyone arrives every round
    np.testing.assert_array_equal(sched.arrivals,
                                  np.ones((5, N_AGENTS), np.float32))


def test_schedule_save_load_roundtrip(tmp_path):
    sched = ArrivalSchedule(
        arrivals=np.asarray([[1, 0], [1, 1], [1, 1]], np.float32),
        max_staleness=1)
    path = tmp_path / "sched.json"
    sched.save(path)
    loaded = ArrivalSchedule.load(path)
    np.testing.assert_array_equal(sched.arrivals, loaded.arrivals)
    assert loaded.max_staleness == 1


# ---------------------------------------------------------------------------
# Staleness semantics on the raw in-jit model
# ---------------------------------------------------------------------------

def _async_cfg(n_agents=3, max_staleness=2, **kw):
    return RoundConfig(
        n_agents=n_agents, participation=1.0,
        staleness=StalenessConfig(mode="stale",
                                  max_staleness=max_staleness), **kw)


def _null_solver(x, v, key):
    # "training" that just returns the reflected target: makes the
    # round's algebra hand-checkable
    return v, None


def test_staleness_counters_and_forced_arrival():
    cfg = _async_cfg()
    N, dim = 3, 4
    x = z = t = jnp.zeros((N, dim))
    y_tag = async_engine.init_y_tag(z)
    s = async_engine.init_staleness(N)
    key = jax.random.PRNGKey(0)
    # round 0: agent 0 misses, others arrive
    r = async_engine.async_round_step(
        cfg, x, z, t, y_tag, s, key, _null_solver,
        arrival=jnp.asarray([0.0, 1.0, 1.0]))
    np.testing.assert_array_equal(np.asarray(r.staleness), [1, 0, 0])
    np.testing.assert_array_equal(np.asarray(r.u), [0, 1, 1])
    # the straggler kept its local progress (x <- w) but its z is frozen
    np.testing.assert_array_equal(np.asarray(r.z[0]), np.asarray(z[0]))
    # round 1: agent 0 misses again -> staleness 2 == K
    r2 = async_engine.async_round_step(
        cfg, r.x, r.z, r.t, r.y_tag, r.staleness, r.next_key,
        _null_solver, arrival=jnp.asarray([0.0, 1.0, 1.0]))
    np.testing.assert_array_equal(np.asarray(r2.staleness), [2, 0, 0])
    # round 2: the bound forces agent 0 in even though the row says 0
    r3 = async_engine.async_round_step(
        cfg, r2.x, r2.z, r2.t, r2.y_tag, r2.staleness, r2.next_key,
        _null_solver, arrival=jnp.asarray([0.0, 1.0, 1.0]))
    assert float(r3.u[0]) == 1.0
    np.testing.assert_array_equal(np.asarray(r3.staleness), [0, 0, 0])


def test_stale_increment_is_tagged_with_pulled_coordinator_point():
    # agent 0 pulls y at round 0, arrives at round 2: its z-update must
    # use the ROUND-0 y (its y_tag), not the round-2 y
    cfg = _async_cfg(max_staleness=2, damping=0.5)
    N, dim = 3, 2
    key = jax.random.PRNGKey(1)
    x = z = t = jnp.asarray(np.random.default_rng(0).normal(
        size=(N, dim)).astype(np.float32))
    y_tag = async_engine.init_y_tag(z)
    s = async_engine.init_staleness(N)
    rows = [jnp.asarray([0.0, 1.0, 1.0]), jnp.asarray([0.0, 1.0, 1.0]),
            jnp.asarray([1.0, 1.0, 1.0])]
    y0 = None
    for row in rows:
        r = async_engine.async_round_step(cfg, x, z, t, y_tag, s, key,
                                          _null_solver, arrival=row)
        if y0 is None:
            y0 = np.asarray(r.y)          # round-0 coordinator point
            z0_agent0 = np.asarray(z[0])
        x, z, t, y_tag, s, key = r.x, r.z, r.t, r.y_tag, r.staleness, \
            r.next_key
    # the tag the arrival used was the round-0 y...
    w_stale = 2.0 * y0 - z0_agent0        # null solver: w = v_stale
    expected = z0_agent0 + 2.0 * 0.5 * (w_stale - y0)
    np.testing.assert_allclose(np.asarray(r.z[0]), expected, rtol=1e-6)


def test_effective_counts_and_validation():
    # N=2, K=2: agent 0 arrives at staleness 2 (carries 3 rounds),
    # agent 1 arrives every round
    sched = np.asarray([[0, 1], [0, 1], [1, 1], [1, 1]], np.float32)
    arr, rel = async_engine.effective_counts(sched, 2)
    np.testing.assert_array_equal(arr, [2, 4])
    np.testing.assert_array_equal(rel, [4, 4])   # 3 + 1 vs 1*4
    async_engine.validate_schedule(sched, 2)
    with pytest.raises(ValueError, match="violates max_staleness"):
        async_engine.validate_schedule(sched, 1)
    with pytest.raises(ValueError, match="n_rounds, n_agents"):
        async_engine.effective_counts(np.ones(3), 1)


# ---------------------------------------------------------------------------
# Stale-aware privacy composition
# ---------------------------------------------------------------------------

def test_effective_privacy_reflects_released_rounds():
    spec = FedSpec(n_agents=2, gamma=0.05, n_epochs=5, rho=1.0,
                   privacy=PrivacySpec(tau=0.5, clip=1.0),
                   async_mode="stale", max_staleness=3)
    # agent 0 arrives at rounds 1, 5, 9, 13, 17: its first arrival is
    # only 1 round stale (2 released rounds) and its last 2 rounds of
    # work are still in flight at the end -- 18 released rounds vs the
    # full 20 for agent 1 (a stale arrival carries s+1 rounds, so mere
    # infrequency does NOT shrink the composition; unreleased work does)
    sched = np.zeros((20, 2), np.float32)
    sched[:, 1] = 1.0
    sched[1::4, 0] = 1.0
    rep = effective_privacy_report(spec, sched, 100)
    assert rep.per_agent is not None and len(rep.per_agent) == 2
    a0, a1 = rep.per_agent
    assert a0.arrivals == 5 and a1.arrivals == 20
    assert a0.K == 18 < a1.K == 20
    assert a0.adp_eps < a1.adp_eps   # fewer released rounds, smaller eps
    # and both are bounded by the nominal synchronous composition
    nominal = privacy_report(spec, 20, 100)
    assert rep.adp_eps <= nominal.adp_eps + 1e-12


def test_build_per_agent_accepts_per_agent_round_counts():
    from repro.core.privacy import PrivacyReport

    rep = PrivacyReport.build_per_agent(
        sensitivities=[100.0, 100.0], mu=1.0, tau=0.5, qs=[100, 100],
        gammas=[0.05, 0.05], K=20, n_epochs_seq=[5, 5], delta=1e-5,
        Ks=[5, 20], arrivals=[5, 20])
    a0, a1 = rep.per_agent
    assert (a0.K, a0.arrivals, a1.K, a1.arrivals) == (5, 5, 20, 20)
    assert a0.adp_eps < a1.adp_eps
    assert rep.adp_eps == a1.adp_eps   # headline = worst agent


# ---------------------------------------------------------------------------
# participation_mask / arrival_mask edge cases
# ---------------------------------------------------------------------------

def test_participation_mask_degenerate_rates():
    p = (0.0, 1.0, 0.5, 1.0, 0.0, 0.5)
    cfg = RoundConfig(n_agents=6, participation=p)
    key = jax.random.PRNGKey(0)
    draws = np.stack([np.asarray(engine.participation_mask(
        jax.random.fold_in(key, i), cfg)) for i in range(64)])
    assert draws.shape == (64, 6)
    np.testing.assert_array_equal(draws[:, 0], 0.0)   # p=0: never
    np.testing.assert_array_equal(draws[:, 4], 0.0)
    np.testing.assert_array_equal(draws[:, 1], 1.0)   # p=1: always
    np.testing.assert_array_equal(draws[:, 3], 1.0)
    assert 0 < draws[:, 2].sum() < 64                 # p=0.5: both


def test_participation_vector_length_mismatch_raises_before_tracing():
    with pytest.raises(ValueError, match="6 entries for n_agents=4"):
        RoundConfig(n_agents=4,
                    participation=(0.5, 0.5, 0.5, 0.5, 0.5, 0.5))


def test_arrival_mask_forces_at_the_bound():
    cfg = _async_cfg(n_agents=4, max_staleness=2)
    s = jnp.asarray([0, 1, 2, 2], jnp.int32)
    u = async_engine.arrival_mask(jax.random.PRNGKey(0), cfg, s,
                                  arrival=jnp.zeros(4))
    np.testing.assert_array_equal(np.asarray(u), [0, 0, 1, 1])


# ---------------------------------------------------------------------------
# Construction-time validation: damping + staleness fields
# ---------------------------------------------------------------------------

def test_string_damping_raises_at_construction():
    with pytest.raises(ValueError, match="damping must be a number"):
        RoundConfig(n_agents=4, damping="0.5")


def test_zero_d_array_damping_and_rho_accepted():
    cfg = RoundConfig(n_agents=4, damping=np.float64(0.5),
                      rho=jnp.asarray(2.0))
    assert cfg.damping == 0.5 and isinstance(cfg.damping, float)
    assert cfg.rho == 2.0 and isinstance(cfg.rho, float)


def test_staleness_config_validation():
    with pytest.raises(ValueError, match="unknown async mode"):
        StalenessConfig(mode="eventually")
    with pytest.raises(ValueError, match="max_staleness must be >= 0"):
        StalenessConfig(mode="stale", max_staleness=-1)
    with pytest.raises(ValueError, match="must be an integer"):
        StalenessConfig(mode="stale", max_staleness="3")
    with pytest.raises(ValueError, match="must be an integer"):
        StalenessConfig(mode="stale", max_staleness=1.5)
    # 0-d arrays are fine (configs built from parsed / loaded values)
    cfg = StalenessConfig(mode="stale", max_staleness=np.int64(4))
    assert cfg.max_staleness == 4 and isinstance(cfg.max_staleness, int)
    assert cfg.enabled and not StalenessConfig().enabled


def test_round_config_rejects_non_config_staleness():
    with pytest.raises(ValueError, match="StalenessConfig"):
        RoundConfig(n_agents=4, staleness="stale")


def test_spec_validate_catches_bad_async_fields():
    with pytest.raises(ValueError, match="unknown async mode"):
        FedSpec(n_agents=4, async_mode="later").validate()
    with pytest.raises(ValueError, match="max_staleness"):
        FedSpec(n_agents=4, async_mode="stale",
                max_staleness=-2).validate()


def test_sync_round_rejects_arrival_override(quad):
    algo = FedPLT(quad, FedSpec(solver="gd", n_epochs=1, gamma=0.05))
    with pytest.raises(ValueError, match="require async_mode"):
        algo.round_with_arrival(algo.init(jax.random.PRNGKey(0)),
                                jnp.ones(N_AGENTS))


# ---------------------------------------------------------------------------
# Generated CLI
# ---------------------------------------------------------------------------

def test_async_cli_roundtrip(quad):
    spec = spec_from_args(["--async-mode", "stale",
                           "--max-staleness", "3",
                           "--participation", "0.5",
                           "--n-agents", str(N_AGENTS)])
    assert spec.async_mode == "stale" and spec.max_staleness == 3
    ecfg = build_trainer(quad, spec).algo._ecfg
    assert ecfg.staleness == StalenessConfig(mode="stale",
                                             max_staleness=3)
    # default stays synchronous
    assert spec_from_args([]).async_mode == "off"
    ap = argparse.ArgumentParser()
    add_spec_args(ap)
    with pytest.raises(SystemExit):   # argparse rejects unknown modes
        ap.parse_args(["--async-mode", "sometimes"])
