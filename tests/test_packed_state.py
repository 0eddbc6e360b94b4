"""Packed-resident state layout: the engine layout contract.

Packed-resident ``(N, M_total)`` trajectories must be BITWISE identical
to the tree-resident path per realization -- across both engine
backends, both front ends, heterogeneous groups, per-agent
participation, every registry compressor, and the two solver-stream
fallbacks (noisy_gd / clipped runs).  On top of parity: the zero
concatenate/gather property of a packed round's state path
(``engine.count_primitives``), checkpoint save -> load -> resume
equality, the compress ``auto`` backend heuristic, and the single-leaf
pack fast path.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import InputShape
from repro.core.problem import make_logreg_problem
from repro.core.solvers import SolverConfig
from repro.data.synthetic import make_batch_for
from repro.fed import compress as compress_lib
from repro.fed import engine, runtime
from repro.fed.api import (CompressionSpec, FedSpec, PrivacySpec,
                           build_trainer, spec_from_args)
from repro.fed.compress import (pack_leaves, packed_meta, resolve_backend,
                                unpack_leaves)
from repro.fed.solvers import (PACKED_DIRECT_SOLVERS,
                               make_packed_local_solver)
from repro.models.model import build_model

# ---------------------------------------------------------------------------
# Dense front end: packed == tree, bit for bit
# ---------------------------------------------------------------------------

N_AGENTS = 6
ROUNDS = 4


@pytest.fixture(scope="module")
def logreg():
    return make_logreg_problem(n_agents=N_AGENTS, q=25, dim=16, seed=0)


def _dense_pair(problem, **kw):
    """(tree_state, packed_state, tree_crit, packed_crit) after ROUNDS."""
    out = []
    for layout in ("tree", "packed"):
        tr = build_trainer(problem, FedSpec(state_layout=layout, **kw))
        state, crit = tr.run(jax.random.PRNGKey(3), ROUNDS)
        out += [state, np.asarray(crit)]
    return out


DENSE_CASES = [
    dict(gamma=0.05, weight_decay=0.01, damping=0.7),
    dict(gamma=0.05, participation=0.6),
    dict(gamma=0.05, compression=CompressionSpec(name="topk", ratio=0.5)),
    dict(gamma=0.05, compression=CompressionSpec(name="int8")),
    dict(gamma=0.05,
         compression=CompressionSpec(name="adaptive_topk", energy=0.8)),
    dict(gamma=0.05, agent_groups="3*gd,3*agd:n_epochs=2"),
    dict(gamma=0.05, privacy=PrivacySpec(tau=0.05, clip=1.0)),
]


@pytest.mark.parametrize("backend", engine.ENGINE_BACKENDS)
@pytest.mark.parametrize("kw", DENSE_CASES,
                         ids=lambda kw: next(iter(
                             kw.get("compression").name.split()
                             if kw.get("compression") else
                             [k for k in kw if k != "gamma"] or ["plain"])))
def test_dense_packed_matches_tree_bitwise(logreg, backend, kw):
    s_tree, c_tree, s_packed, c_packed = _dense_pair(
        logreg, engine_backend=backend, **kw)
    # dense single-leaf state: the packed buffer IS the (N, n) array
    np.testing.assert_array_equal(np.asarray(s_tree.x),
                                  np.asarray(s_packed.x))
    np.testing.assert_array_equal(np.asarray(s_tree.z),
                                  np.asarray(s_packed.z))
    if s_tree.t is not None:
        np.testing.assert_array_equal(np.asarray(s_tree.t),
                                      np.asarray(s_packed.t))
    np.testing.assert_array_equal(c_tree, c_packed)


# ---------------------------------------------------------------------------
# Model-scale front end: packed == tree, bit for bit
# ---------------------------------------------------------------------------

SHAPE = InputShape("t", 4, 4, "train")


@pytest.fixture(scope="module")
def tiny_model():
    cfg = get_config("gemma2-2b").reduced(n_layers=1, d_model=64, vocab=128)
    return cfg, build_model(cfg)


def _model_run(model, cfg, spec, n_rounds=2, n_agents=2):
    step = jax.jit(runtime.make_train_step(model, spec))
    state = runtime.init_state(model, jax.random.PRNGKey(0), spec)
    batch = make_batch_for(cfg, SHAPE, n_agents=n_agents)
    losses = []
    for i in range(n_rounds):
        state, m = step(state, batch, jax.random.PRNGKey(7))
        losses.append(float(m["loss"]))
    return state, losses


def _as_flat(x, meta=None):
    if meta is not None:
        x = unpack_leaves(x, meta)
    return np.concatenate([np.asarray(l).reshape(l.shape[0], -1)
                           for l in jax.tree_util.tree_leaves(x)], axis=1)


MODEL_CASES = [
    ("xla", dict(weight_decay=0.01)),
    ("pallas", dict(weight_decay=0.01)),
    ("pallas", dict(compression=CompressionSpec(name="int8"))),
    ("pallas", dict(compression=CompressionSpec(name="adaptive_topk",
                                                energy=0.8))),
    ("xla", dict(compression=CompressionSpec(name="topk", ratio=0.5),
                 participation=0.7)),
    ("pallas", dict(agent_groups="1*gd,1*agd:n_epochs=1")),
    # solver-stream fallbacks: per-leaf DP noise / clip reductions
    ("xla", dict(privacy=PrivacySpec(tau=0.05, clip=1.0))),
]


@pytest.mark.parametrize("backend,kw", MODEL_CASES,
                         ids=[f"{b}-{next(iter(k))}" for b, k in MODEL_CASES])
def test_model_packed_matches_tree_bitwise(tiny_model, backend, kw):
    cfg, model = tiny_model
    base = dict(n_agents=2, n_epochs=2, gamma=0.1, engine_backend=backend)
    spec_t = FedSpec(state_layout="tree", **base, **kw)
    spec_p = FedSpec(state_layout="packed", **base, **kw)
    s_t, l_t = _model_run(model, cfg, spec_t)
    s_p, l_p = _model_run(model, cfg, spec_p)
    meta = runtime.packed_layout(model, spec_p)
    np.testing.assert_array_equal(_as_flat(s_t.x), _as_flat(s_p.x, meta))
    np.testing.assert_array_equal(_as_flat(s_t.z), _as_flat(s_p.z, meta))
    if s_t.t is not None:
        np.testing.assert_array_equal(_as_flat(s_t.t),
                                      _as_flat(s_p.t, meta))
    assert l_t == l_p
    # API boundary: consensus unpacks to the same deployable model
    cons_t = runtime.consensus_model(s_t)
    cons_p = runtime.consensus_model(s_p, meta=meta)
    for a, b in zip(jax.tree_util.tree_leaves(cons_t),
                    jax.tree_util.tree_leaves(cons_p)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_model_packed_state_is_one_buffer(tiny_model):
    cfg, model = tiny_model
    spec = FedSpec(n_agents=2, n_epochs=1, gamma=0.1, state_layout="packed")
    state = runtime.init_state(model, jax.random.PRNGKey(0), spec)
    meta = runtime.packed_layout(model, spec)
    assert isinstance(state.x, jnp.ndarray)
    assert state.x.shape == (2, meta.width)
    # the round keeps the state resident: output is the same single buffer
    step = jax.jit(runtime.make_train_step(model, spec))
    batch = make_batch_for(cfg, SHAPE, n_agents=2)
    state, _ = step(state, batch, jax.random.PRNGKey(1))
    assert state.x.shape == (2, meta.width)


# ---------------------------------------------------------------------------
# Checkpoint: save -> load -> resume == uninterrupted, bit for bit
# ---------------------------------------------------------------------------

def test_packed_checkpoint_roundtrip_and_resume(tiny_model, tmp_path):
    from repro.checkpoint.io import (checkpoint_extra, checkpoint_step,
                                     packed_layout_manifest,
                                     restore_checkpoint, save_checkpoint)

    cfg, model = tiny_model
    spec = FedSpec(n_agents=2, n_epochs=1, gamma=0.1, state_layout="packed",
                   compression=CompressionSpec(name="topk", ratio=0.5))
    meta = runtime.packed_layout(model, spec)
    step = jax.jit(runtime.make_train_step(model, spec))
    batch = make_batch_for(cfg, SHAPE, n_agents=2)

    state = runtime.init_state(model, jax.random.PRNGKey(0), spec)
    state, _ = step(state, batch, jax.random.PRNGKey(1))

    path = str(tmp_path / "ckpt")
    save_checkpoint(path, state, step=1,
                    extra=packed_layout_manifest(meta))
    like = jax.tree_util.tree_map(
        lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype), state)
    restored = restore_checkpoint(path, like)
    assert checkpoint_step(path) == 1

    # the manifest records the buffer geometry for restore validation
    extra = checkpoint_extra(path)
    assert extra["state_layout"] == "packed"
    assert extra["width"] == meta.width
    assert [tuple(s) for s in extra["segments"]] == list(meta.segments)

    # resume from the restored buffers == uninterrupted, bitwise
    s_cont, _ = step(state, batch, jax.random.PRNGKey(2))
    s_res, _ = step(restored, batch, jax.random.PRNGKey(2))
    np.testing.assert_array_equal(np.asarray(s_cont.x), np.asarray(s_res.x))
    np.testing.assert_array_equal(np.asarray(s_cont.z), np.asarray(s_res.z))
    np.testing.assert_array_equal(np.asarray(s_cont.t), np.asarray(s_res.t))


def test_checkpoint_extra_absent_is_none(tmp_path):
    from repro.checkpoint.io import checkpoint_extra, save_checkpoint

    path = str(tmp_path / "plain")
    save_checkpoint(path, {"a": jnp.zeros(3)}, step=0)
    assert checkpoint_extra(path) is None


# ---------------------------------------------------------------------------
# The zero-concatenate property: jaxpr op counts on the state path
# ---------------------------------------------------------------------------

def _ragged_tree(n=4):
    return {"a": jnp.ones((n, 3, 5)), "b": jnp.ones((n, 17)),
            "c": jnp.ones((n, 2, 2, 2))}


def _packed_round_jaxpr(backend, comp):
    tree = _ragged_tree()
    meta = packed_meta(tree)
    buf, _ = pack_leaves(tree)

    def fgrad(w, k):
        return jax.tree_util.tree_map(lambda l: 0.1 * l, w)

    scfg = SolverConfig(name="gd", n_epochs=2, step_size=0.1)
    spec = FedSpec(
        n_agents=4, engine_backend=backend, state_layout="packed",
        gamma=0.1, participation=0.9,
        # the per-segment XLA compressors: at this width "auto" would
        # take the compress kernel, whose in-VMEM body concatenates
        compression=(CompressionSpec(name=comp, ratio=0.5, backend="xla")
                     if comp != "none" else CompressionSpec()))
    ecfg = spec.round_config()
    solver = make_packed_local_solver(scfg, fgrad, spec.rho, 0.1, 1.0,
                                      meta=meta)
    return jax.make_jaxpr(
        lambda x, z, t, k: engine.packed_round_step(
            ecfg, meta, x, z, t, k, solver))(
        buf, buf, buf, jax.random.PRNGKey(0)).jaxpr


@pytest.mark.parametrize("backend", engine.ENGINE_BACKENDS)
@pytest.mark.parametrize("comp", ["none", "topk", "int8"])
def test_packed_round_state_path_has_zero_concatenates(backend, comp):
    """The layout contract's headline property: a packed round contains
    ZERO concatenate ops -- state never leaves the resident buffer.  The
    only layout traffic left is the gradient oracle's static
    update-slice chain (values, not state): 3 leaves uncompressed, +3
    for the compressed per-segment write-back under xla."""
    counts = engine.count_primitives(
        _packed_round_jaxpr(backend, comp),
        ["concatenate", "dynamic_update_slice"])
    assert counts["concatenate"] == 0
    assert counts["dynamic_update_slice"] <= 6


@pytest.mark.parametrize("comp", ["none", "int8"])
def test_packed_round_state_path_has_zero_gathers(comp):
    # topk excluded: rank_select's index arithmetic gathers *values*
    counts = engine.count_primitives(
        _packed_round_jaxpr("pallas", comp), ["gather"])
    assert counts["gather"] == 0


def test_packed_removes_per_edge_repacking():
    """Under the pallas backend the tree layout pays a pack/unpack
    update-slice chain at every round edge; the packed layout pays only
    the oracle's (one pack of the gradient tree)."""
    tree = _ragged_tree()

    def fgrad(w, k):
        return jax.tree_util.tree_map(lambda l: 0.1 * l, w)

    scfg = SolverConfig(name="gd", n_epochs=2, step_size=0.1)
    spec = FedSpec(n_agents=4, engine_backend="pallas", gamma=0.1)
    ecfg = spec.round_config()
    solver = engine.make_local_solver(scfg, fgrad, spec.rho, 0.1, 1.0)
    tree_jaxpr = jax.make_jaxpr(
        lambda x, z, t, k: engine.round_step(ecfg, x, z, t, k, solver))(
        tree, tree, tree, jax.random.PRNGKey(0)).jaxpr
    n_tree = engine.count_primitives(
        tree_jaxpr, ["dynamic_update_slice"])["dynamic_update_slice"]
    n_packed = engine.count_primitives(
        _packed_round_jaxpr("pallas", "none"),
        ["dynamic_update_slice"])["dynamic_update_slice"]
    n_leaves = len(tree)
    assert n_packed == n_leaves          # the oracle's single pack
    assert n_tree >= 3 * n_leaves        # per-edge repacking


def test_count_primitives_descends_into_subjaxprs():
    def f(x):
        def body(c, _):
            return jnp.concatenate([c, c])[:4], None
        return jax.lax.scan(body, x, None, length=2)[0]

    # the concatenate lives only in the scan body's sub-jaxpr: a
    # non-descending counter would report 0
    jaxpr = jax.make_jaxpr(f)(jnp.ones(4)).jaxpr
    assert engine.count_primitives(jaxpr, ["concatenate"]) == {
        "concatenate": 1}


# ---------------------------------------------------------------------------
# Compress backend "auto"
# ---------------------------------------------------------------------------

def _ccfg(name, backend="auto", ratio=0.5):
    spec = FedSpec(n_agents=4, gamma=0.1,
                   compression=CompressionSpec(name=name, ratio=ratio,
                                               backend=backend))
    return spec.round_config()


def test_auto_backend_dispatch():
    # explicit backends pass through untouched where the block fits
    assert resolve_backend(_ccfg("topk", "pallas")) == "pallas"
    assert resolve_backend(_ccfg("topk", "pallas"), m_total=1 << 14) \
        == "pallas"
    assert resolve_backend(_ccfg("topk", "xla"), m_total=1 << 30) == "xla"
    # auto: the kernel exactly where its whole-row block fits VMEM
    for name in ("topk", "adaptive_topk"):
        assert resolve_backend(_ccfg(name), m_total=1 << 14) == "pallas"
        assert resolve_backend(_ccfg(name), m_total=(1 << 14) + 1) == "xla"
    assert resolve_backend(_ccfg("int8"), m_total=1 << 16) == "pallas"
    assert resolve_backend(_ccfg("int8"), m_total=(1 << 16) + 128) == "xla"
    assert resolve_backend(_ccfg("int8"), m_total=355_000_000) == "xla"
    assert resolve_backend(_ccfg("int8")) == "xla"  # unknown width
    # compressors without a kernel never route to pallas
    assert resolve_backend(_ccfg("none")) == "xla"
    # an explicit kernel request beyond the block limit is refused
    for name in ("topk", "adaptive_topk", "int8"):
        with pytest.raises(ValueError, match="VMEM"):
            resolve_backend(_ccfg(name, "pallas"), m_total=1 << 20)


def test_auto_is_the_default_backend():
    assert CompressionSpec().backend == "auto"
    assert FedSpec(n_agents=2, gamma=0.1).validate()  # validates clean


@pytest.mark.parametrize("name", ["topk", "int8", "adaptive_topk"])
def test_auto_backend_is_bit_identical(name):
    """auto is a pure scheduling choice: both backends are bit-identical
    (PR 5 parity contract), so auto must match each of them."""
    key = jax.random.PRNGKey(0)
    dz = jax.random.normal(key, (4, 4096))
    # jit each, as the engine does (eager XLA codegen differs by a ULP
    # in the int8 scale on some shapes; see test_compress_kernels)
    outs = [jax.jit(lambda v, b=backend: compress_lib.compress_rows(
        v, _ccfg(name, b)))(dz) for backend in ("auto", "xla", "pallas")]
    np.testing.assert_array_equal(np.asarray(outs[0]), np.asarray(outs[1]))
    np.testing.assert_array_equal(np.asarray(outs[0]), np.asarray(outs[2]))


# ---------------------------------------------------------------------------
# pack_leaves fast path + PackedMeta
# ---------------------------------------------------------------------------

def test_single_leaf_pack_skips_padding_and_copies():
    x = jnp.arange(4 * 23, dtype=jnp.float32).reshape(4, 23)
    buf, meta = pack_leaves({"w": x})
    assert meta.width == 23                    # no lane alignment
    assert buf.shape == (4, 23)
    np.testing.assert_array_equal(np.asarray(buf), np.asarray(x))
    # zero-copy: no update-slice chain, no pad in the traced program
    jaxpr = jax.make_jaxpr(lambda t: pack_leaves(t)[0])({"w": x}).jaxpr
    counts = engine.count_primitives(
        jaxpr, ["dynamic_update_slice", "pad", "concatenate"])
    assert counts == {"dynamic_update_slice": 0, "pad": 0,
                      "concatenate": 0}
    # and the round trip is exact
    out = unpack_leaves(buf, meta)
    np.testing.assert_array_equal(np.asarray(out["w"]), np.asarray(x))


def test_multi_leaf_pack_still_lane_aligned():
    buf, meta = pack_leaves(_ragged_tree())
    assert meta.width % 128 == 0
    assert meta.width == buf.shape[1]
    assert meta.m_total == sum(b - a for a, b in meta.segments)


def test_packed_meta_is_static_and_hashable():
    meta1 = packed_meta(_ragged_tree())
    meta2 = packed_meta(jax.tree_util.tree_map(
        lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype), _ragged_tree()))
    assert meta1 == meta2                      # shapes only, no values
    assert {meta1: "jit-static"}[meta2] == "jit-static"


def test_unpack_leaves_row_slice():
    """Group buffers (row slices of the resident buffer) unpack with the
    same meta -- run_solvers' heterogeneous path depends on this."""
    tree = _ragged_tree(n=5)
    buf, meta = pack_leaves(tree)
    part = unpack_leaves(buf[1:3], meta)
    for k in tree:
        np.testing.assert_array_equal(np.asarray(part[k]),
                                      np.asarray(tree[k][1:3]))


def test_packed_direct_solver_registry():
    """gd/agd/sgd run on the buffer; noisy_gd and clipped runs must NOT
    (per-leaf noise folds / clip norms would change bit streams)."""
    assert set(PACKED_DIRECT_SOLVERS) == {"gd", "agd", "sgd"}
    assert "noisy_gd" not in PACKED_DIRECT_SOLVERS


# ---------------------------------------------------------------------------
# Spec / CLI / sharding plumbing
# ---------------------------------------------------------------------------

def test_state_layout_cli_roundtrip():
    spec = spec_from_args(["--state-layout", "packed",
                           "--compress-backend", "auto"])
    assert spec.state_layout == "packed"
    assert spec.compression.backend == "auto"
    assert spec_from_args([]).state_layout == "tree"


def test_state_layout_validated():
    with pytest.raises(ValueError, match="state layout"):
        FedSpec(n_agents=2, gamma=0.1, state_layout="bogus").validate()
    with pytest.raises(ValueError):
        engine.RoundConfig(n_agents=2, state_layout="bogus")


def test_fed_state_specs_packed():
    from jax.sharding import PartitionSpec as P

    from repro.fed.sharding import fed_state_specs

    stacked = jax.tree_util.tree_map(
        lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype), _ragged_tree())
    specs = fed_state_specs(stacked, agent_axis="data", fsdp_axis="model",
                            axis_sizes={"data": 2, "model": 2},
                            compressed=True, packed=True)
    # one buffer spec per state var: rows on the agent axis, columns on
    # the fsdp axis (width is lane-aligned, so 2 always divides)
    assert specs.x == P("data", "model")
    assert specs.z == specs.x and specs.t == specs.x
    assert specs.step == P()
    # non-divisible column axis falls back to replicated columns
    odd = {"w": jax.ShapeDtypeStruct((4, 23), jnp.float32)}
    specs_odd = fed_state_specs(odd, agent_axis="data", fsdp_axis="model",
                                axis_sizes={"data": 2, "model": 2},
                                packed=True)
    assert specs_odd.x == P("data", None)
