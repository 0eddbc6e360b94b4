"""Data pipeline, checkpointing, optimizers, sharding rules, HLO analyzer."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import restore_checkpoint, save_checkpoint
from repro.configs import ARCH_IDS, get_config
from repro.configs.base import SHAPES, InputShape
from repro.core.problem import dirichlet_partition
from repro.data.synthetic import make_batch_for, synthetic_lm_batch
from repro.fed import sharding
from repro.launch.hlo_analysis import analyze_text
from repro.models.model import build_model, input_specs, shape_supported
from repro.optim import adamw, apply_updates, momentum, sgd


# ---------------------------------------------------------------------------
# data
# ---------------------------------------------------------------------------

def test_synthetic_batch_shapes_and_range():
    b = synthetic_lm_batch(jax.random.PRNGKey(0), 100, 4, 16)
    assert b["tokens"].shape == (4, 16)
    assert int(b["tokens"].min()) >= 0 and int(b["tokens"].max()) < 100
    np.testing.assert_array_equal(b["labels"][:, :-1], b["tokens"][:, 1:])


def test_fed_batches_are_heterogeneous():
    cfg = get_config("gemma2-2b").reduced()
    shape = InputShape("t", 16, 8, "train")
    batch = make_batch_for(cfg, shape, n_agents=4)
    assert batch["tokens"].shape == (4, 2, 16)
    # different agents draw from skewed distributions
    assert not np.array_equal(batch["tokens"][0], batch["tokens"][3])


def test_dirichlet_partition_skews_labels():
    rng = np.random.default_rng(0)
    X = rng.normal(size=(1000, 3))
    y = rng.integers(0, 4, 1000)
    feats, labs = dirichlet_partition(X, y, n_agents=5, alpha=0.1, seed=1)
    assert feats.shape[0] == 5 and feats.shape[2] == 3
    # low alpha => at least one agent is label-skewed
    props = [np.mean(labs[i] == labs[i][0]) for i in range(5)]
    assert max(props) > 0.5


# ---------------------------------------------------------------------------
# checkpoint
# ---------------------------------------------------------------------------

def test_checkpoint_roundtrip(tmp_path):
    tree = {"a": jnp.arange(6.0).reshape(2, 3),
            "b": {"c": jnp.array([1, 2], jnp.int32)}}
    save_checkpoint(str(tmp_path / "ck"), tree, step=7)
    back = restore_checkpoint(str(tmp_path / "ck"), tree)
    np.testing.assert_allclose(back["a"], tree["a"])
    np.testing.assert_array_equal(back["b"]["c"], tree["b"]["c"])


def test_checkpoint_shape_mismatch_raises(tmp_path):
    tree = {"a": jnp.zeros((2, 3))}
    save_checkpoint(str(tmp_path / "ck"), tree)
    with pytest.raises(ValueError):
        restore_checkpoint(str(tmp_path / "ck"), {"a": jnp.zeros((3, 3))})


# ---------------------------------------------------------------------------
# optimizers
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("opt", [sgd(0.1), momentum(0.05), adamw(0.05)])
def test_optimizers_minimize_quadratic(opt):
    params = {"x": jnp.array([3.0, -2.0])}
    state = opt.init(params)
    for _ in range(300):
        grads = {"x": 2.0 * params["x"]}
        upd, state = opt.update(grads, state, params)
        params = apply_updates(params, upd)
    assert float(jnp.linalg.norm(params["x"])) < 1e-2


# ---------------------------------------------------------------------------
# sharding rules
# ---------------------------------------------------------------------------

AXES = {"data": 16, "model": 16}


def _check_tree(params, specs, reserve=0):
    flat_p = jax.tree_util.tree_leaves(params)
    flat_s = jax.tree_util.tree_leaves(
        specs, is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))
    assert len(flat_p) == len(flat_s)
    for leaf, spec in zip(flat_p, flat_s):
        assert len(spec) <= leaf.ndim
        for dim, axis in zip(leaf.shape, tuple(spec)):
            if axis is None:
                continue
            size = AXES[axis] if isinstance(axis, str) else \
                int(np.prod([AXES[a] for a in axis]))
            assert dim % size == 0, (leaf.shape, spec)


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_param_specs_divisible_all_archs(arch):
    cfg = get_config(arch)
    model = build_model(cfg)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    specs = sharding.param_specs(params, fsdp_axis="data",
                                 axis_sizes=AXES)
    _check_tree(params, specs)


@pytest.mark.parametrize("arch", ["gemma2-2b", "qwen2-moe-a2.7b"])
def test_cache_specs_divisible(arch):
    cfg = get_config(arch)
    for shape_id in ("decode_32k", "long_500k"):
        shape = SHAPES[shape_id]
        if not shape_supported(cfg, shape)[0]:
            continue
        from repro.models.model import cache_specs
        cache = cache_specs(cfg, shape)
        specs = sharding.cache_spec_tree(cache, AXES, data_axes=("data",))
        _check_tree(cache, specs)


def test_input_specs_cover_all_pairs():
    for arch in ARCH_IDS:
        cfg = get_config(arch)
        for shape in SHAPES.values():
            if not shape_supported(cfg, shape)[0]:
                continue
            specs = input_specs(cfg, shape)
            assert specs, (arch, shape.name)


# ---------------------------------------------------------------------------
# HLO analyzer
# ---------------------------------------------------------------------------

def test_hlo_analyzer_scan_trip_count():
    def body(x, _):
        return x @ x, None

    def f(x):
        y, _ = jax.lax.scan(body, x, None, length=8)
        return y.sum()

    txt = jax.jit(f).lower(jnp.ones((64, 64))).compile().as_text()
    c = analyze_text(txt)
    assert c.flops >= 2 * 64 ** 3 * 8  # trip-count multiplied
    assert c.flops < 2 * 64 ** 3 * 8 * 1.5


def test_hlo_analyzer_collectives():
    mesh = jax.make_mesh((1,), ("d",))
    from jax.sharding import PartitionSpec as P

    def f(x):
        return jax.lax.psum(x, "d")

    fn = jax.jit(
        jax.shard_map(f, mesh=mesh, in_specs=P("d"), out_specs=P()))
    txt = fn.lower(jnp.ones((8, 128))).compile().as_text()
    c = analyze_text(txt)
    # single-device all-reduce may be optimized away; just assert parse ok
    assert c.bytes >= 0
