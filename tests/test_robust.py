"""Byzantine-robust aggregation suite (ISSUE 10).

Three tiers, mirroring the other kernel/engine contracts:

* KERNEL, bitwise: the robust_agg Pallas sort-and-trim kernel == the
  ref.py oracle across stats, trims, live masks, sort implementations,
  and block realizations (the parity contract).
* ENGINE, bitwise: ``aggregator="mean"`` -- and ``trimmed_mean`` at
  ``f = 0``, which IS the mean -- is a bitwise no-op vs the historical
  trajectories across state_layout x engine_backend x compressor (the
  8-combo assert), tree and packed robust trajectories agree bitwise
  on real columns, and a 1-device mesh reproduces the unsharded robust
  round bit-for-bit.
* BREAKDOWN, behavioral: under a persistent sign-flip attack on 25% of
  the agents the trimmed-mean trajectory stays within tolerance of the
  clean fixed point while the plain mean is steered several times
  further away; property tests pin permutation invariance and the
  honest-row envelope guarantee (``f < N/2``).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.fedplt import FedPLT
from repro.core.problem import make_quadratic_problem
from repro.core.solvers import SolverConfig
from repro.fed import async_engine
from repro.fed import compress as compress_lib
from repro.fed import engine, robust
from repro.fed.api import FedSpec, spec_from_args
from repro.fed.broker import IncrementBroker, replay
from repro.fed.faults import FaultPlan
from repro.fed.solvers import make_packed_local_solver
from repro.kernels.robust_agg import ops
from repro.kernels.robust_agg.ref import robust_aggregate_ref

multi_device = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs 8 devices (XLA_FLAGS=--xla_force_host_platform_"
           "device_count=8)")


def _stack(seed, n, m, scale=1.0):
    return scale * jax.random.normal(jax.random.PRNGKey(seed), (n, m))


def _mesh(agents=1, model=1):
    from jax.sharding import Mesh

    devs = np.asarray(jax.devices()[:agents * model]).reshape(
        agents, model)
    return Mesh(devs, ("agent", "model"))


# ---------------------------------------------------------------------------
# Kernel tier: pallas kernel vs ref oracle, bitwise
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,m", [(1, 5), (4, 128), (8, 300), (17, 64),
                                 (64, 129)])
@pytest.mark.parametrize("stat,trim", [("trimmed_mean", 0),
                                       ("trimmed_mean", 2),
                                       ("coord_median", 0)])
def test_kernel_matches_ref_bitwise(n, m, stat, trim):
    if 2 * trim >= n:
        pytest.skip("trim larger than the stack")
    x = _stack(n * m + trim, n, m)
    live = None
    if n >= 4:   # evict some rows; order stats must skip them
        live = np.ones(n, np.float32)
        live[:: max(n // 3, 1)] = 0.0
    want = jax.jit(robust_aggregate_ref,
                   static_argnames=("stat", "trim"))(x, live, stat=stat,
                                                     trim=trim)
    for sort_impl in ("xla", "bitonic"):
        for bc in (16, 256):
            got = ops.robust_aggregate(x, live, stat=stat, trim=trim,
                                       sort_impl=sort_impl,
                                       block_cols=bc)
            np.testing.assert_array_equal(
                np.asarray(got), np.asarray(want),
                err_msg=f"{stat} trim={trim} {sort_impl} bc={bc}")


def test_kernel_semantics_vs_numpy():
    """The sorted-selection arithmetic against a plain numpy oracle
    (allclose: numpy reduces in a different association)."""
    rng = np.random.default_rng(0)
    x = rng.normal(size=(9, 37)).astype(np.float32)
    live = np.ones(9, np.float32)
    live[[2, 5]] = 0.0
    rows = x[live == 1.0]
    got_tm = np.asarray(ops.robust_aggregate(x, live, stat="trimmed_mean",
                                             trim=2))
    want_tm = np.sort(rows, axis=0)[2:-2].mean(axis=0, keepdims=True)
    np.testing.assert_allclose(got_tm, want_tm, rtol=1e-6, atol=1e-7)
    got_md = np.asarray(ops.robust_aggregate(x, live,
                                             stat="coord_median"))
    want_md = np.median(rows, axis=0, keepdims=True)
    np.testing.assert_allclose(got_md, want_md, rtol=1e-6, atol=1e-7)


def test_kernel_rejects_bad_inputs():
    x = _stack(0, 4, 8)
    with pytest.raises(ValueError, match="unknown robust stat"):
        ops.robust_aggregate(x, stat="mode")
    with pytest.raises(ValueError, match=r"\(N, M\) buffers"):
        ops.robust_aggregate(jnp.zeros((4,)), stat="coord_median")
    with pytest.raises(ValueError, match="unknown robust stat"):
        robust_aggregate_ref(x, stat="mode")


# ---------------------------------------------------------------------------
# Registry + validation
# ---------------------------------------------------------------------------

def test_registry_contents_and_errors():
    assert set(robust.available_aggregators()) >= {
        "mean", "trimmed_mean", "coord_median", "norm_clip_mean"}
    with pytest.raises(ValueError, match="unknown aggregator"):
        robust.get_aggregator("geometric_median")
    with pytest.raises(ValueError, match="non-negative integer"):
        robust.validate_aggregator("trimmed_mean", 1.5)
    with pytest.raises(ValueError, match="2f < N"):
        robust.validate_aggregator("trimmed_mean", 2, n_agents=4)
    with pytest.raises(ValueError, match="clip radius"):
        robust.validate_aggregator("norm_clip_mean", 0.0)
    with pytest.raises(ValueError, match="clip radius"):
        robust.validate_aggregator("norm_clip_mean", float("inf"))
    assert robust.validate_aggregator("trimmed_mean", 2,
                                      n_agents=8) == 2.0
    assert robust.validate_aggregator("mean", 0.0) == 0.0


def test_spec_and_config_threading():
    spec = spec_from_args(["--aggregator", "trimmed_mean",
                           "--aggregator-param", "2",
                           "--n-agents", "8"]).validate()
    cfg = spec.round_config()
    assert cfg.aggregator == "trimmed_mean"
    assert cfg.aggregator_param == 2.0
    assert cfg.robust_aggregator == "trimmed_mean"
    with pytest.raises(ValueError, match="2f < N"):
        FedSpec(n_agents=4, aggregator="trimmed_mean",
                aggregator_param=2).validate()
    with pytest.raises(ValueError, match="unknown aggregator"):
        engine.RoundConfig(n_agents=4, aggregator="nope")
    # f = 0 IS the mean: the dispatch must resolve to the historical path
    assert engine.RoundConfig(
        n_agents=4, aggregator="trimmed_mean",
        aggregator_param=0.0).robust_aggregator is None
    assert engine.RoundConfig(n_agents=4).robust_aggregator is None


def test_mean_keeps_object_identity():
    """The mean path must return z_seen ITSELF (live=None): downstream
    lagged-path dispatch keys on ``z_seen is z``."""
    cfg = engine.RoundConfig(n_agents=4)
    z = {"a": _stack(0, 4, 8)}
    assert engine.robust_seen(cfg, z, None) is z
    cfg0 = engine.RoundConfig(n_agents=4, aggregator="trimmed_mean",
                              aggregator_param=0.0)
    assert engine.robust_seen(cfg0, z, None) is z


# ---------------------------------------------------------------------------
# Engine tier: the 8-combo bitwise no-op + robust layout parity
# ---------------------------------------------------------------------------

def _tree_state(n=8):
    key = jax.random.PRNGKey(3)
    return {"a": jax.random.normal(key, (n, 5)),
            "b": jax.random.normal(jax.random.fold_in(key, 1), (n, 3, 3))}


def _fgrad(w, k):
    return jax.tree_util.tree_map(lambda l: 0.1 * l, w)


_SCFG = SolverConfig(name="gd", n_epochs=2, step_size=0.1)


def _tree_solver():
    return engine.make_local_solver(_SCFG, _fgrad, 1.0, 0.1, 1.0)


def _packed_solver(meta):
    return make_packed_local_solver(_SCFG, _fgrad, 1.0, 0.1, 1.0,
                                    meta=meta)


def _run_rounds(cfg, state, solver, rounds=3, meta=None):
    x = z = t = state
    key = jax.random.PRNGKey(7)
    for _ in range(rounds):
        if meta is None:
            res = engine.round_step(cfg, x, z, t, key, solver)
        else:
            res = engine.packed_round_step(cfg, meta, x, z, t, key,
                                           solver)
        x, z, t, key = res.x, res.z, res.t, res.next_key
    return res


COMBOS = [(layout, backend, compression)
          for layout in ("tree", "packed")
          for backend in ("xla", "pallas")
          for compression in ("none", "topk")]


@pytest.mark.parametrize("layout,backend,compression", COMBOS)
def test_mean_is_bitwise_noop_8_combos(layout, backend, compression):
    """trimmed_mean(f=0) resolves to the mean dispatch, so its
    trajectories must equal the default config BIT FOR BIT on every
    layout x backend x compressor combo -- the robust layer leaves the
    historical graph untouched unless a real statistic is selected."""
    kw = dict(n_agents=8, engine_backend=backend, state_layout=layout,
              compression=compression, compress_ratio=0.5)
    tree = _tree_state()
    if layout == "packed":
        buf, meta = compress_lib.pack_leaves(tree)
        base = _run_rounds(engine.RoundConfig(**kw), buf,
                           _packed_solver(meta), meta=meta)
        rob = _run_rounds(
            engine.RoundConfig(aggregator="trimmed_mean",
                               aggregator_param=0.0, **kw),
            buf, _packed_solver(meta), meta=meta)
    else:
        base = _run_rounds(engine.RoundConfig(**kw), tree,
                           _tree_solver())
        rob = _run_rounds(
            engine.RoundConfig(aggregator="trimmed_mean",
                               aggregator_param=0.0, **kw),
            tree, _tree_solver())
    for a, b in zip(jax.tree_util.tree_leaves(base._asdict()),
                    jax.tree_util.tree_leaves(rob._asdict())):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("aggregator,param", [("trimmed_mean", 2),
                                              ("coord_median", 0),
                                              ("norm_clip_mean", 0.7)])
def test_robust_seen_tree_packed_aggregate_bitwise(aggregator, param):
    """The aggregated z_seen itself is BITWISE identical between the
    tree and packed entry points: both reduce per column through the
    same registry function on the same packed values."""
    tree = _tree_state()
    buf, meta = compress_lib.pack_leaves(tree)
    live = jnp.asarray([1, 1, 0, 1, 1, 1, 1, 1], jnp.float32)
    st = robust.robust_seen_tree(tree, live, name=aggregator,
                                 param=param, backend="xla")
    sp = robust.robust_seen_packed(buf, live, name=aggregator,
                                   param=param, meta=meta,
                                   backend="xla")
    zt = compress_lib.pack_leaves(st)[0]
    mask = np.zeros(meta.width, bool)
    for a, b in meta.segments:
        mask[a:b] = True
    np.testing.assert_array_equal(np.asarray(zt)[:, mask],
                                  np.asarray(sp)[:, mask])


@pytest.mark.parametrize("aggregator,param", [("trimmed_mean", 2),
                                              ("coord_median", 0),
                                              ("norm_clip_mean", 0.7)])
@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_robust_tree_packed_parity(aggregator, param, backend):
    """Tree- and packed-resident robust trajectories agree to f32
    rounding on the real (non-padding) columns, under both engine
    backends.  The aggregate is bitwise (previous test); the multi-round
    trajectories are only ulp-tight because the robust broadcast shifts
    XLA's fusion boundaries, and CPU instruction selection (FMA vs
    mul+add) may then differ between the two compiled layouts."""
    tree = _tree_state()
    buf, meta = compress_lib.pack_leaves(tree)
    kw = dict(n_agents=8, engine_backend=backend, aggregator=aggregator,
              aggregator_param=param)
    rt = _run_rounds(engine.RoundConfig(state_layout="tree", **kw),
                     tree, _tree_solver())
    rp = _run_rounds(engine.RoundConfig(state_layout="packed", **kw),
                     buf, _packed_solver(meta), meta=meta)
    mask = np.zeros(meta.width, bool)
    for a, b in meta.segments:
        mask[a:b] = True
    for field in ("x", "z", "t"):
        zt = compress_lib.pack_leaves(getattr(rt, field))[0]
        zp = getattr(rp, field)
        np.testing.assert_allclose(
            np.asarray(zt)[:, mask], np.asarray(zp)[:, mask],
            rtol=1e-6, atol=1e-7,
            err_msg=f"{field} {aggregator} {backend}")


@multi_device
@pytest.mark.parametrize("aggregator,param", [("trimmed_mean", 2),
                                              ("coord_median", 0)])
def test_robust_mesh_of_one_is_bitwise(aggregator, param):
    """A 1-device mesh runs the all-gather robust path, whose gather of
    one shard is the identity -- trajectories must equal the unsharded
    engine bit-for-bit (the degenerate-case contract)."""
    tree = _tree_state()
    buf, meta = compress_lib.pack_leaves(tree)
    kw = dict(n_agents=8, state_layout="packed", aggregator=aggregator,
              aggregator_param=param)
    base = _run_rounds(engine.RoundConfig(**kw), buf, _packed_solver(meta),
                       meta=meta)
    key = jax.random.PRNGKey(7)
    x = z = t = buf
    with _mesh(1, 1) as mesh:
        for _ in range(3):
            res = engine.packed_round_step(
                engine.RoundConfig(**kw), meta, x, z, t, key,
                _packed_solver(meta), mesh=mesh)
            x, z, t, key = res.x, res.z, res.t, res.next_key
    for field in ("x", "z", "t", "y"):
        np.testing.assert_array_equal(
            np.asarray(getattr(base, field)),
            np.asarray(getattr(res, field)), err_msg=field)


@multi_device
def test_robust_multi_device_mesh_close():
    """An 8-way agent mesh all-gathers real shards; the order statistic
    itself is deterministic, so trajectories match the unsharded run to
    f32 rounding (the downstream psum combine order is not bitwise)."""
    tree = _tree_state()
    buf, meta = compress_lib.pack_leaves(tree)
    kw = dict(n_agents=8, state_layout="packed",
              aggregator="trimmed_mean", aggregator_param=2)
    base = _run_rounds(engine.RoundConfig(**kw), buf, _packed_solver(meta),
                       meta=meta)
    key = jax.random.PRNGKey(7)
    x = z = t = buf
    with _mesh(8, 1) as mesh:
        for _ in range(3):
            res = engine.packed_round_step(
                engine.RoundConfig(agent_shards=8, **kw), meta, x, z, t,
                key, _packed_solver(meta), mesh=mesh)
            x, z, t, key = res.x, res.z, res.t, res.next_key
    for field in ("x", "z", "t", "y"):
        np.testing.assert_allclose(
            np.asarray(getattr(base, field)),
            np.asarray(getattr(res, field)), rtol=1e-5, atol=1e-6,
            err_msg=field)


def test_async_engine_takes_robust_aggregator():
    """The async round consumes the same robust z_seen transform, and
    at f=0 the trimmed dispatch resolves to the historical async round
    bitwise (same graph, not merely close)."""
    tree = _tree_state()
    stale = engine.StalenessConfig(mode="stale", max_staleness=2)
    y_tag = async_engine.init_y_tag(tree)
    s0 = async_engine.init_staleness(8)
    key = jax.random.PRNGKey(0)
    res = async_engine.async_round_step(
        engine.RoundConfig(n_agents=8, aggregator="coord_median",
                           staleness=stale),
        tree, tree, tree, y_tag, s0, key, _tree_solver())
    for l in jax.tree_util.tree_leaves(res.y):
        assert bool(jnp.isfinite(l).all())
    base = async_engine.async_round_step(
        engine.RoundConfig(n_agents=8, staleness=stale),
        tree, tree, tree, y_tag, s0, key, _tree_solver())
    trim0 = async_engine.async_round_step(
        engine.RoundConfig(n_agents=8, aggregator="trimmed_mean",
                           aggregator_param=0.0, staleness=stale),
        tree, tree, tree, y_tag, s0, key, _tree_solver())
    for a, b in zip(jax.tree_util.tree_leaves(base._asdict()),
                    jax.tree_util.tree_leaves(trim0._asdict())):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# Breakdown tier: the sign-flip attack (the acceptance scenario)
# ---------------------------------------------------------------------------

def _attack_run(aggregator, param, corrupt, rounds=60):
    quad = make_quadratic_problem(n_agents=8, dim=8, seed=3)
    algo = FedPLT(quad, FedSpec(
        solver="gd", n_epochs=2, gamma=0.05,
        damping=0.7, aggregator=aggregator, aggregator_param=param))
    s = algo.init(jax.random.PRNGKey(0))
    for _ in range(rounds):
        s, _ = algo.round_with_faults(s, None, corrupt, None)
    return np.asarray(s.y)


def test_sign_flip_attack_mean_diverges_trimmed_survives():
    """Sign-flip on 25% of the agents (2 of 8): finite and in-norm, so
    the guards cannot see it.  The plain mean is steered several times
    the clean scale away from the clean fixed point; trimmed_mean(f=2)
    stays within tolerance of it.  The acceptance scenario."""
    corrupt = np.zeros(8, np.float32)
    corrupt[:2] = -1.0                      # w -> -w for agents 0, 1
    corrupt = jnp.asarray(corrupt)
    y_clean = _attack_run("mean", 0.0, None)
    y_mean = _attack_run("mean", 0.0, corrupt)
    y_trim = _attack_run("trimmed_mean", 2, corrupt)
    scale = float(np.linalg.norm(y_clean))
    err_mean = float(np.linalg.norm(y_mean - y_clean))
    err_trim = float(np.linalg.norm(y_trim - y_clean))
    # trimmed-mean converges within tolerance of the clean run ...
    assert err_trim < 2.0 * scale, (err_trim, scale)
    # ... the mean does not (steered several times the clean scale) ...
    assert err_mean > 5.0 * scale, (err_mean, scale)
    # ... and the robust run is several times closer than the mean
    assert err_mean > 3.0 * err_trim, (err_mean, err_trim)


def test_byzantine_broker_end_to_end_with_replay():
    """FaultPlan byzantine events -> broker-realized (N, 2) rows ->
    robust survival, with the recording replaying bit-for-bit."""
    quad = make_quadratic_problem(n_agents=8, dim=8, seed=3)
    plan = FaultPlan.generate(5, 8, 40, n_byzantine=2,
                              byzantine_kind="sign_flip")
    assert plan.has_byzantine

    def build(aggregator, param):
        algo = FedPLT(quad, FedSpec(
            solver="gd", n_epochs=2, gamma=0.05,
            damping=0.7, async_mode="stale", max_staleness=0,
            aggregator=aggregator, aggregator_param=param))
        return algo, lambda s, u, c, l: algo.round_with_faults(
            s, u, c, l)[0]

    algo, step = build("trimmed_mean", 2)
    broker = IncrementBroker(8, max_staleness=0, seed=11)
    state0 = algo.init(jax.random.PRNGKey(0))
    s_rob, sched = broker.run(step, state0, n_rounds=40, faults=plan)
    rows = [broker.record.corrupt_row(r) for r in range(40)]
    assert all(r is not None and r.shape == (8, 2) for r in rows)

    # replay the recording: bit-for-bit
    s_replay = replay(step, state0, sched, record=broker.record)
    np.testing.assert_array_equal(np.asarray(s_rob.y),
                                  np.asarray(s_replay.y))

    # same attack through the plain mean: steered several times further
    algo_m, step_m = build("mean", 0.0)
    broker_m = IncrementBroker(8, max_staleness=0, seed=11)
    s_mean, _ = broker_m.run(step_m, algo_m.init(jax.random.PRNGKey(0)),
                             n_rounds=40, faults=plan)
    algo_c, step_c = build("mean", 0.0)
    broker_c = IncrementBroker(8, max_staleness=0, seed=11)
    s_clean, _ = broker_c.run(step_c,
                              algo_c.init(jax.random.PRNGKey(0)),
                              n_rounds=40)
    y_clean = np.asarray(s_clean.y)
    err_rob = np.linalg.norm(np.asarray(s_rob.y) - y_clean)
    err_mean = np.linalg.norm(np.asarray(s_mean.y) - y_clean)
    assert err_mean > 2.0 * err_rob, (err_mean, err_rob)


# ---------------------------------------------------------------------------
# Property tests (hypothesis; conftest ships a deterministic stub)
# ---------------------------------------------------------------------------

@settings(max_examples=12, deadline=None)
@given(seed=st.integers(min_value=0, max_value=10_000),
       n=st.sampled_from([4, 7, 8, 16]),
       stat=st.sampled_from(["trimmed_mean", "coord_median"]))
def test_order_stats_are_permutation_invariant(seed, n, stat):
    """Agent order cannot matter: the sort erases it EXACTLY (bitwise),
    live mask permuted along."""
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, 33)).astype(np.float32)
    live = (rng.random(n) > 0.25).astype(np.float32)
    if live.sum() == 0:
        live[0] = 1.0
    perm = rng.permutation(n)
    trim = 1 if (stat == "trimmed_mean" and n > 2) else 0
    a = robust_aggregate_ref(jnp.asarray(x), live, stat=stat, trim=trim)
    b = robust_aggregate_ref(jnp.asarray(x[perm]), live[perm],
                             stat=stat, trim=trim)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@settings(max_examples=12, deadline=None)
@given(seed=st.integers(min_value=0, max_value=10_000),
       n=st.sampled_from([4, 8, 16]))
def test_trimmed_f0_is_the_mean(seed, n):
    """trimmed_mean at f=0 averages every live row -- equal to the
    survivor mean to f32 rounding (BITWISE equality is guaranteed one
    level up: RoundConfig resolves f=0 to the exact mean dispatch,
    asserted in the 8-combo test)."""
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, 17)).astype(np.float32)
    a = np.asarray(robust.aggregate_rows(jnp.asarray(x), None,
                                         name="trimmed_mean", param=0.0))
    b = np.asarray(robust.aggregate_rows(jnp.asarray(x), None,
                                         name="mean", param=0.0))
    np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)


@settings(max_examples=12, deadline=None)
@given(seed=st.integers(min_value=0, max_value=10_000),
       n=st.sampled_from([5, 8, 16]),
       f=st.sampled_from([1, 2]),
       median=st.booleans())
def test_honest_envelope_breakdown_guarantee(seed, n, f, median):
    """With c corrupt rows, c <= f (trimmed) or c < N/2 (median), the
    aggregate of every column lies inside the honest rows' [min, max]
    envelope -- the breakdown guarantee that makes finite adversarial
    values harmless."""
    if 2 * f >= n:
        return
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, 21)).astype(np.float32)
    c = f if not median else max(1, (n - 1) // 2)
    corrupt_rows = rng.choice(n, size=c, replace=False)
    x[corrupt_rows] = rng.choice(
        [-1e6, 1e6, 3.0], size=(c, 21)).astype(np.float32)
    honest = np.delete(x, corrupt_rows, axis=0)
    lo = honest.min(axis=0) - 1e-4
    hi = honest.max(axis=0) + 1e-4
    if median:
        out = robust_aggregate_ref(jnp.asarray(x), None,
                                   stat="coord_median")
    else:
        out = robust_aggregate_ref(jnp.asarray(x), None,
                                   stat="trimmed_mean", trim=f)
    out = np.asarray(out)[0]
    assert np.all(out >= lo) and np.all(out <= hi), (
        out.min(), out.max(), lo.min(), hi.max())


@settings(max_examples=8, deadline=None)
@given(seed=st.integers(min_value=0, max_value=10_000),
       attack=st.booleans())
def test_norm_clip_stays_within_radius_of_center(seed, attack):
    """norm_clip_mean = center + mean of per-row residuals clipped to
    l2 norm <= radius, so the aggregate can never leave the radius-ball
    around the coordinate-median center -- no matter how wild the
    corrupt rows are (the clipping bound an adversary cannot beat)."""
    rng = np.random.default_rng(seed)
    n, m, radius = 8, 13, 0.5
    x = rng.normal(size=(n, m)).astype(np.float32)
    if attack:
        x[:3] = rng.choice([-1e6, 1e6], size=(3, m)).astype(np.float32)
    center = np.asarray(robust.aggregate_rows(
        jnp.asarray(x), None, name="coord_median", param=0.0))
    out = np.asarray(robust.aggregate_rows(
        jnp.asarray(x), None, name="norm_clip_mean", param=radius))
    assert np.linalg.norm(out - center) <= radius * (1.0 + 1e-5), \
        np.linalg.norm(out - center)
