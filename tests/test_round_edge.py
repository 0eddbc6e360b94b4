"""Fused round-edge kernel suite.

Parity contract (two tiers -- see the note in repro/fed/engine.py):

* KERNEL tier, bitwise: the fused kernels == the ref.py oracles on the
  same ``(N, M)`` buffer (whole prox table, lagged/exact exchange,
  NaN'd solver results, non-block-aligned widths), and the multi-block
  grid == the single-program realization.
* ENGINE tier, 1-ULP: ``engine_backend="pallas"`` trajectories equal
  ``"xla"`` to float32 rounding (dense + model scale, per-agent
  participation, heterogeneous groups, compressed rounds).  Exact
  bitwise equality across backends is NOT a stable property of
  XLA:CPU: the algebraic simplifier refolds the coordinator chain's
  constants per consumer, per surrounding program, and per array shape
  -- the unfused xla backend's own ``run()`` (scan-fused criterion)
  and ``step()`` already disagree bitwise at some shapes, so no single
  kernel formulation can match the xla path in every context.  In
  practice most full-round configurations DO agree bit-for-bit (the
  kernels mirror the unfused path's per-consumer chain duplication),
  but tests assert only what is guaranteed."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import prox as prox_lib
from repro.core.problem import make_logreg_problem
from repro.fed import engine
from repro.fed.api import (CompressionSpec, FedSpec, PrivacySpec,
                           build_trainer, spec_from_args)
from repro.fed.compress import pack_coord, pack_leaves, unpack_coord
from repro.kernels.round_edge import ops, ref

# the full make_prox table as (name, bound callable) -- every entry is
# elementwise, so every entry must take the fused kernel path
PROX_TABLE = [
    ("none", None),
    ("zero", prox_lib.prox_zero),
    ("l1", prox_lib.prox_l1),
    ("l2sq", prox_lib.prox_l2sq),
    ("weight_decay", prox_lib.make_prox("weight_decay", weight=0.1)),
    ("elastic_net", prox_lib.make_prox("elastic_net", l1=0.3, l2=0.7)),
    ("box", prox_lib.make_prox("box", lo=-0.3, hi=0.5)),
    ("linf_ball", prox_lib.make_prox("linf_ball", radius=0.4)),
]


def _assert_trees_equal(a, b):
    jax.tree_util.tree_map(
        lambda x, y: np.testing.assert_array_equal(np.asarray(x),
                                                   np.asarray(y)), a, b)


def _assert_trees_ulp_close(a, b):
    """Equality to float32 rounding (the cross-backend engine bar)."""
    jax.tree_util.tree_map(
        lambda x, y: np.testing.assert_allclose(
            np.asarray(x), np.asarray(y), rtol=1e-5, atol=1e-6), a, b)


def _stack(key, n, m, scale=1.0):
    return scale * jax.random.normal(key, (n, m))


# ---------------------------------------------------------------------------
# Kernels vs ref.py oracles (jit-vs-jit, static prox/rho -- the form the
# engine runs; see test_compress_kernels for why eager parity is not
# the bar)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,m", [(3, 7), (5, 300), (8, 128), (2, 1000),
                                 (32, 513)])
@pytest.mark.parametrize("pname,prox", PROX_TABLE,
                         ids=[p[0] for p in PROX_TABLE])
@pytest.mark.parametrize("lagged", [False, True])
def test_uplink_matches_ref(n, m, pname, prox, lagged):
    key = jax.random.PRNGKey(n * m)
    z = _stack(key, n, m)
    t = z + 0.1 * _stack(jax.random.fold_in(key, 1), n, m) if lagged \
        else None
    y, v = ops.round_uplink(z, t, prox=prox, rho_eff=0.25)
    ref_jit = jax.jit(ref.round_uplink_ref,
                      static_argnames=("prox", "rho_eff"))
    yr, vr = ref_jit(z, t, prox=prox, rho_eff=0.25)
    assert y.shape == (1, m) and v.shape == (n, m)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(yr))
    np.testing.assert_array_equal(np.asarray(v), np.asarray(vr))


@pytest.mark.parametrize("n,m", [(3, 7), (5, 300), (32, 1000)])
@pytest.mark.parametrize("lagged", [False, True])
def test_downlink_matches_ref(n, m, lagged):
    key = jax.random.PRNGKey(n + m)
    x = _stack(key, n, m)
    w = _stack(jax.random.fold_in(key, 1), n, m)
    z = _stack(jax.random.fold_in(key, 2), n, m)
    t = z + 0.1 * _stack(jax.random.fold_in(key, 3), n, m) if lagged \
        else None
    u = jax.random.bernoulli(jax.random.fold_in(key, 4), 0.5,
                             (n,)).astype(jnp.float32)
    prox = prox_lib.make_prox("weight_decay", weight=0.2)
    xn, zn = ops.round_downlink(x, w, z, u, t, prox=prox, rho_eff=0.2,
                                damping=0.65)
    ref_jit = jax.jit(ref.round_downlink_ref,
                      static_argnames=("prox", "rho_eff", "damping"))
    xr, zr = ref_jit(x, w, z, u, t, prox=prox, rho_eff=0.2, damping=0.65)
    np.testing.assert_array_equal(np.asarray(xn), np.asarray(xr))
    np.testing.assert_array_equal(np.asarray(zn), np.asarray(zr))


@pytest.mark.parametrize("block_cols", [128, 256])
def test_multi_block_grid_matches_single_program(block_cols):
    """An explicit column block smaller than the width tiles the grid;
    the tiling must not change a single bit vs the one-program default
    (the TPU-shaped realization vs the interpret default)."""
    key = jax.random.PRNGKey(0)
    n, m = 6, 900                    # pads to the block, grid > 1
    z = _stack(key, n, m)
    x = _stack(jax.random.fold_in(key, 1), n, m)
    w = _stack(jax.random.fold_in(key, 2), n, m)
    u = jnp.asarray([1.0, 0.0, 1.0, 1.0, 0.0, 1.0])
    prox = prox_lib.prox_l1
    y1, v1 = ops.round_uplink(z, prox=prox, rho_eff=0.3)
    y2, v2 = ops.round_uplink(z, prox=prox, rho_eff=0.3,
                              block_cols=block_cols)
    np.testing.assert_array_equal(np.asarray(y1), np.asarray(y2))
    np.testing.assert_array_equal(np.asarray(v1), np.asarray(v2))
    x1, z1 = ops.round_downlink(x, w, z, u, prox=prox, rho_eff=0.3)
    x2, z2 = ops.round_downlink(x, w, z, u, prox=prox, rho_eff=0.3,
                                block_cols=block_cols)
    np.testing.assert_array_equal(np.asarray(x1), np.asarray(x2))
    np.testing.assert_array_equal(np.asarray(z1), np.asarray(z2))


def test_direct_realization_matches_pallas_emulation():
    """Interpret mode's single-program grid runs the kernel body
    DIRECTLY (no emulator block copies); forcing the pallas_call
    emulator over the same single block must give identical bits --
    the two realizations of the same kernel."""
    key = jax.random.PRNGKey(5)
    n, m = 7, 384
    z = _stack(key, n, m)
    x = _stack(jax.random.fold_in(key, 1), n, m)
    w = _stack(jax.random.fold_in(key, 2), n, m)
    u = jax.random.bernoulli(jax.random.fold_in(key, 3), 0.5,
                             (n,)).astype(jnp.float32)
    prox = prox_lib.make_prox("elastic_net", l1=0.2, l2=0.4)
    kw = dict(prox=prox, rho_eff=0.2)
    y1, v1 = ops.round_uplink(z, **kw)
    y2, v2 = ops.round_uplink(z, emulate=True, **kw)
    np.testing.assert_array_equal(np.asarray(y1), np.asarray(y2))
    np.testing.assert_array_equal(np.asarray(v1), np.asarray(v2))
    x1, z1 = ops.round_downlink(x, w, z, u, damping=0.5, **kw)
    x2, z2 = ops.round_downlink(x, w, z, u, damping=0.5, emulate=True,
                                **kw)
    np.testing.assert_array_equal(np.asarray(x1), np.asarray(x2))
    np.testing.assert_array_equal(np.asarray(z1), np.asarray(z2))


def test_downlink_nan_safe():
    """A diverged (NaN/Inf) local solve must not leak into agents that
    sat the round out -- the where-select semantics of masked_mix."""
    n, m = 4, 70
    x = jnp.ones((n, m))
    z = 2.0 * jnp.ones((n, m))
    w = jnp.full((n, m), jnp.nan)
    u = jnp.asarray([0.0, 1.0, 0.0, 1.0])
    xn, zn = ops.round_downlink(x, w, z, u)
    assert np.isfinite(np.asarray(xn[0])).all()
    assert np.isfinite(np.asarray(zn[2])).all()
    np.testing.assert_array_equal(np.asarray(xn[0]), np.asarray(x[0]))
    np.testing.assert_array_equal(np.asarray(zn[2]), np.asarray(z[2]))
    assert np.isnan(np.asarray(xn[1])).all()


def test_float64_and_bad_shapes_rejected():
    with pytest.raises(ValueError, match=r"\(N, M\)"):
        ops.round_uplink(jnp.ones((4,)))
    with pytest.raises(ValueError, match="must match z"):
        from repro.kernels.round_edge.kernel import round_uplink_2d
        round_uplink_2d(jnp.ones((2, 128)), jnp.ones((3, 128)))


# ---------------------------------------------------------------------------
# Engine edges: packed pallas == per-leaf XLA on ragged pytrees
# ---------------------------------------------------------------------------

def _ragged_tree(n=6, seed=3):
    key = jax.random.PRNGKey(seed)
    shapes = {"emb": (n, 37, 5), "w": {"a": (n, 130), "b": (n, 3)},
              "bias": (n, 1)}
    return jax.tree_util.tree_map(
        lambda s: jax.random.normal(jax.random.fold_in(key, s[-1]), s),
        shapes, is_leaf=lambda s: isinstance(s, tuple))


@pytest.mark.parametrize("pname,prox", PROX_TABLE,
                         ids=[p[0] for p in PROX_TABLE])
@pytest.mark.parametrize("lagged", [False, True])
def test_engine_edges_on_pytrees(pname, prox, lagged):
    """coordinator_edge + agent_edge under engine_backend="pallas" ==
    the per-leaf XLA path on a ragged multi-leaf pytree: the
    coordinator output ``y`` bitwise, everything else to fp32 rounding
    (the xla path's reflection/z-update chains refold shape-dependently
    -- see the module docstring)."""
    n = 6
    z = _ragged_tree(n, seed=1)
    x = _ragged_tree(n, seed=2)
    w = _ragged_tree(n, seed=4)
    z_seen = (jax.tree_util.tree_map(lambda l: 1.01 * l, z)
              if lagged else None)
    u = jnp.asarray([1.0, 0.0, 1.0, 1.0, 0.0, 1.0])

    def edges(cfg, zs):
        zs = z if zs is None else zs
        y, v = engine.coordinator_edge(cfg, z, zs, prox)
        xn, zn = engine.agent_edge(cfg, u, w, x, z, y, zs, prox)
        return y, v, xn, zn

    outs = {}
    for backend in ("xla", "pallas"):
        cfg = engine.RoundConfig(n_agents=n, rho=1.3, damping=0.6,
                                 engine_backend=backend)
        outs[backend] = jax.jit(lambda zs: edges(cfg, zs))(z_seen)
    _assert_trees_ulp_close(outs["xla"], outs["pallas"])


def test_fusible_prox_gating():
    assert engine.fusible_prox(None)
    for _, prox in PROX_TABLE:
        if prox is not None:
            assert engine.fusible_prox(prox), prox
    assert not engine.fusible_prox(lambda y, rho: y * 0.5)


def test_custom_prox_falls_back_to_xla():
    """An untagged (possibly non-elementwise) prox must take the XLA
    path under backend="pallas" -- output equal to the XLA backend's."""
    def custom(y, rho):
        # deliberately non-elementwise: couples coordinates
        return y - rho * jnp.mean(y, keepdims=True)

    n = 4
    z = _ragged_tree(n, seed=7)
    outs = {}
    for backend in ("xla", "pallas"):
        cfg = engine.RoundConfig(n_agents=n, engine_backend=backend)
        outs[backend] = jax.jit(
            lambda: engine.coordinator_edge(cfg, z, z, custom))()
    _assert_trees_equal(outs["xla"], outs["pallas"])


def test_mixed_dtype_tree_falls_back():
    n = 4
    tree = {"a": jnp.ones((n, 40)), "b": jnp.ones((n, 24), jnp.bfloat16)}
    for backend in ("xla", "pallas"):
        cfg = engine.RoundConfig(n_agents=n, engine_backend=backend)
        y, v = engine.coordinator_edge(cfg, tree, tree, None)
    assert y["b"].dtype == jnp.bfloat16


def test_pack_coord_roundtrip():
    tree = _ragged_tree(5)
    buf, meta = pack_leaves(tree)
    y = jax.tree_util.tree_map(lambda l: jnp.mean(l, axis=0), tree)
    y_buf = pack_coord(y, meta)
    assert y_buf.shape == (1, meta.width)
    _assert_trees_equal(unpack_coord(y_buf, meta), y)
    back = unpack_coord(pack_leaves(
        jax.tree_util.tree_map(lambda l: l[None], y))[0], meta)
    _assert_trees_equal(back, y)
    with pytest.raises(ValueError, match="does not match"):
        pack_coord(jax.tree_util.tree_map(lambda l: l[..., None], y),
                   meta)


# ---------------------------------------------------------------------------
# RoundConfig validation (incl. the participation-string regression)
# ---------------------------------------------------------------------------

def test_round_config_rejects_unknown_engine_backend():
    with pytest.raises(ValueError, match="engine backend"):
        engine.RoundConfig(n_agents=2, engine_backend="nope")


def test_participation_rejects_strings():
    """participation="0.5" is a __len__-bearing sequence of characters;
    it must fail loudly, not tuple-ize into per-character draws."""
    with pytest.raises(ValueError, match="string"):
        engine.RoundConfig(n_agents=2, participation="0.5")
    with pytest.raises(ValueError, match="string"):
        engine.RoundConfig(n_agents=2, participation=b"0.5")


def test_participation_rejects_non_numeric_sequences():
    with pytest.raises(ValueError, match="numbers"):
        engine.RoundConfig(n_agents=2, participation=("0.5", "a"))
    with pytest.raises(ValueError, match="numbers"):
        engine.RoundConfig(n_agents=2, participation=(0.5, None))


def test_participation_accepts_numeric_sequences():
    cfg = engine.RoundConfig(n_agents=3,
                             participation=np.asarray([0.5, 1.0, 0.25]))
    assert cfg.participation == (0.5, 1.0, 0.25)
    with pytest.raises(ValueError, match="2 entries"):
        engine.RoundConfig(n_agents=3, participation=(0.5, 1.0))


def test_participation_accepts_0d_array_scalars():
    """ndarray types carry __len__ even at 0-d, so a numpy/jax scalar
    must be recognized as the scalar it is, not misdiagnosed as a
    malformed per-agent sequence."""
    for p in (np.float32(0.5), np.asarray(0.5), jnp.float32(0.5)):
        cfg = engine.RoundConfig(n_agents=3, participation=p)
        assert cfg.participation == 0.5
        assert isinstance(cfg.participation, float)


# ---------------------------------------------------------------------------
# The backend knob end to end
# ---------------------------------------------------------------------------

def test_spec_validates_engine_backend():
    with pytest.raises(ValueError, match="engine backend"):
        FedSpec(n_agents=2, engine_backend="nope").validate()


def test_cli_engine_backend_roundtrip():
    spec = spec_from_args(["--engine-backend", "pallas"])
    assert spec.engine_backend == "pallas"
    assert spec.validate().round_config().engine_backend == "pallas"


def test_backend_threads_to_dense_engine():
    prob = make_logreg_problem(n_agents=4, q=20, dim=10, seed=0)
    trainer = build_trainer(prob, FedSpec(engine_backend="pallas"))
    assert trainer.algo._ecfg.engine_backend == "pallas"


# ---------------------------------------------------------------------------
# Backend parity: full trajectories, round by round, to fp32 rounding
#
# Compared at ROUND granularity (the jitted step's RoundResult),
# iterated over a full trajectory.  The bar is 1-ULP-tight equality,
# not bitwise: XLA:CPU refolds the round body's constant chains per
# program context and per shape -- the xla backend's own run()
# (scan-fused criterion) and step() already disagree bitwise at some
# shapes -- so bitwise cross-backend equality is not a stable property
# of the platform (most configs do agree bit-for-bit in practice).
# ---------------------------------------------------------------------------

def _run_pair(prob, rounds=6, **kw):
    runs = []
    for backend in ("xla", "pallas"):
        spec = FedSpec(engine_backend=backend, **kw)
        trainer = build_trainer(prob, spec)
        state = trainer.init(jax.random.PRNGKey(0))
        crit = []
        for _ in range(rounds):
            state = trainer.step(state)
            crit.append(prob.criterion(state.x))
        t = state.t if state.t is not None else state.z
        runs.append((np.asarray(state.x), np.asarray(state.z),
                     np.asarray(t), np.asarray(state.y),
                     np.asarray(jnp.stack(crit))))
    return runs


@pytest.mark.parametrize("prox_h", ["zero", "l1", "l2sq", "elastic_net",
                                    "box", "linf_ball"])
def test_dense_trajectory_parity_prox_table(prox_h):
    prob = make_logreg_problem(n_agents=6, q=25, dim=16, seed=0)
    a, b = _run_pair(prob, rho=0.9, n_epochs=2, prox_h=prox_h)
    for x, y in zip(a, b):
        np.testing.assert_allclose(x, y, rtol=1e-5, atol=1e-6)


def test_dense_trajectory_parity_weight_decay():
    prob = make_logreg_problem(n_agents=6, q=25, dim=16, seed=0)
    a, b = _run_pair(prob, rho=0.8, n_epochs=2, weight_decay=0.1)
    for x, y in zip(a, b):
        np.testing.assert_allclose(x, y, rtol=1e-5, atol=1e-6)


def test_dense_trajectory_parity_participation_and_groups():
    """Per-agent participation vectors + heterogeneous SolverGroup
    partitions ride the fused edges bit-identically."""
    prob = make_logreg_problem(n_agents=6, q=25, dim=16, seed=0)
    a, b = _run_pair(
        prob, rho=1.0, n_epochs=2, damping=0.5,
        agent_groups="3*gd:participation=0.4,3*agd:n_epochs=1")
    for x, y in zip(a, b):
        np.testing.assert_allclose(x, y, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("name,cbackend", [
    ("topk", "xla"), ("int8", "pallas")])
def test_dense_trajectory_parity_compressed(name, cbackend):
    """Compressed rounds (incl. the packed pallas compress backend --
    both fused kernel suites in one round) match across engine
    backends."""
    prob = make_logreg_problem(n_agents=6, q=25, dim=16, seed=0)
    a, b = _run_pair(
        prob, rho=1.0, n_epochs=1, damping=0.7,
        compression=CompressionSpec(name, ratio=0.3, energy=0.9,
                                    backend=cbackend))
    for x, y in zip(a, b):
        np.testing.assert_allclose(x, y, rtol=1e-5, atol=1e-6)


def test_dense_trajectory_adaptive_compressed_converges_equally():
    """adaptive_topk's per-agent k_i comes from an energy-cumsum
    threshold: a 1-ULP backend difference in the increment can flip
    WHICH coordinate is transmitted (a discrete selection), after which
    states differ macroscopically -- so the parity bar for adaptive
    compression is equal convergence, not state equality.  (topk/int8
    at these seeds never sit on a selection boundary and stay
    ULP-close; see test_dense_trajectory_parity_compressed.)"""
    prob = make_logreg_problem(n_agents=6, q=25, dim=16, seed=0)
    a, b = _run_pair(
        prob, rounds=8, rho=1.0, n_epochs=1, damping=0.7,
        compression=CompressionSpec("adaptive_topk", ratio=0.3,
                                    energy=0.9, backend="pallas"))
    crit_a, crit_b = a[-1], b[-1]
    assert crit_a[-1] < 0.1 * crit_a[0] and crit_b[-1] < 0.1 * crit_b[0]
    np.testing.assert_allclose(np.log10(crit_a), np.log10(crit_b),
                               atol=0.1)


def test_dense_trajectory_parity_noisy():
    prob = make_logreg_problem(n_agents=4, q=25, dim=12, seed=0)
    a, b = _run_pair(prob, rho=1.0, n_epochs=2,
                     privacy=PrivacySpec(tau=0.05, clip=1.0))
    for x, y in zip(a, b):
        np.testing.assert_allclose(x, y, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("kw", [
    dict(n_epochs=1, weight_decay=0.05, participation=0.5, damping=0.5),
    dict(n_epochs=1, compression=CompressionSpec("topk", ratio=0.5)),
])
def test_model_trajectory_parity(kw):
    """The model-scale front end (ragged parameter pytree through
    fed/runtime.py) matches across engine backends to fp32 rounding."""
    from repro.configs import get_config
    from repro.configs.base import InputShape
    from repro.data.synthetic import make_batch_for
    from repro.models.model import build_model

    cfg = get_config("gemma2-2b").reduced(n_layers=1, d_model=64,
                                          vocab=128)
    model = build_model(cfg)
    shape = InputShape("t", 4, 4, "train")
    states = {}
    for backend in ("xla", "pallas"):
        spec = FedSpec(n_agents=2, gamma=0.1, engine_backend=backend,
                       **kw)
        trainer = build_trainer(model, spec)
        batch = make_batch_for(cfg, shape, n_agents=2)
        state = trainer.init(jax.random.PRNGKey(0))
        for i in range(2):
            state, _ = trainer.step(state, batch, jax.random.PRNGKey(i))
        states[backend] = state
    _assert_trees_ulp_close(states["xla"].x, states["pallas"].x)
    _assert_trees_ulp_close(states["xla"].z, states["pallas"].z)


def test_round_edge_launch_count():
    """On the TPU schedule (``interpret=False`` trace -- abstract eval
    only, safe on CPU) the fused round edges are exactly TWO pallas
    launches: one uplink, one downlink.  (The CPU default executes the
    same bodies directly when the grid is a single program, so the
    count is taken from the TPU-shaped trace.)"""
    n, m = 8, 4096
    z = jnp.zeros((n, m))
    u = jnp.zeros((n,))
    prox = prox_lib.prox_l1

    def count(jaxpr, name):
        total = 0
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == name:
                total += 1
            for v in eqn.params.values():
                for vv in (v if isinstance(v, (list, tuple)) else [v]):
                    inner = getattr(vv, "jaxpr", None)
                    if inner is not None:
                        total += count(inner, name)
                    elif hasattr(vv, "eqns"):
                        total += count(vv, name)
        return total

    def tpu_edges(x, w, z, u):
        _, v = ops.round_uplink(z, prox=prox, rho_eff=0.2,
                                interpret=False)
        xn, zn = ops.round_downlink(x, w, z, u, prox=prox, rho_eff=0.2,
                                    interpret=False)
        return v, xn, zn

    jaxpr = jax.make_jaxpr(tpu_edges)(z, z, z, u)
    assert count(jaxpr.jaxpr, "pallas_call") == 2
