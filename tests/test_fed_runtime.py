"""Large-scale Fed-PLT runtime: training works, DP noise flows, the
runtime round is semantically the paper's Algorithm 1 on pytrees."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import InputShape
from repro.data.synthetic import make_batch_for
from repro.fed import runtime
from repro.fed.api import CompressionSpec, FedSpec, build_trainer
from repro.models.model import build_model

SHAPE = InputShape("tiny", 32, 8, "train")


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("gemma2-2b").reduced()
    model = build_model(cfg)
    return cfg, model


def test_fed_training_reduces_loss(setup):
    cfg, model = setup
    fcfg = runtime.FedConfig(n_agents=4, n_epochs=2, gamma=0.1)
    state = runtime.init_state(model, jax.random.PRNGKey(0), fcfg)
    step = jax.jit(runtime.make_train_step(model, fcfg))
    batch = make_batch_for(cfg, SHAPE, n_agents=4)
    losses = []
    for i in range(6):
        state, m = step(state, batch, jax.random.PRNGKey(i))
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0]


def test_dp_noise_and_clipping_path(setup):
    cfg, model = setup
    fcfg = runtime.FedConfig(n_agents=2, n_epochs=2, tau=0.01, clip=1.0)
    state = runtime.init_state(model, jax.random.PRNGKey(0), fcfg)
    step = jax.jit(runtime.make_train_step(model, fcfg))
    batch = make_batch_for(cfg, SHAPE, n_agents=2)
    state, m = step(state, batch, jax.random.PRNGKey(0))
    assert np.isfinite(m["loss"])
    # agents received different noise: x_1 != x_2 even with same init/data
    diff = jax.tree_util.tree_reduce(
        lambda acc, x: acc + float(jnp.sum(jnp.abs(x[0] - x[1]))),
        state.x, 0.0)
    assert diff > 0


def test_inactive_agents_keep_state(setup):
    cfg, model = setup
    fcfg = runtime.FedConfig(n_agents=4, n_epochs=1,
                             participation=1e-7)  # nobody active
    state0 = runtime.init_state(model, jax.random.PRNGKey(0), fcfg)
    step = jax.jit(runtime.make_train_step(model, fcfg))
    batch = make_batch_for(cfg, SHAPE, n_agents=4)
    state1, m = step(state0, batch, jax.random.PRNGKey(3))
    same = jax.tree_util.tree_all(jax.tree_util.tree_map(
        lambda a, b: bool(jnp.array_equal(a, b)), state0.x, state1.x))
    assert same
    assert float(m["participation"]) == 0.0


def test_weight_decay_prox_shrinks(setup):
    cfg, model = setup
    fcfg = runtime.FedConfig(n_agents=2, weight_decay=0.1, rho=1.0)
    zbar = {"w": jnp.ones((3,))}
    y = runtime._coordinator_prox(zbar, fcfg)
    expect = 1.0 / (1.0 + 1.0 * 0.1 / 2)
    np.testing.assert_allclose(y["w"], expect, atol=1e-6)


def test_runtime_matches_core_fedplt_on_quadratic():
    """The pytree runtime round == the paper-faithful core round when the
    'model' is a bare quadratic loss (full participation, no noise)."""
    from repro.core.fedplt import FedPLT, FedPLTConfig
    from repro.core.problem import make_quadratic_problem
    from repro.core.solvers import SolverConfig

    prob = make_quadratic_problem(n_agents=3, dim=4, seed=0)

    class QuadModel:
        def init(self, key):
            return {"x": jnp.zeros(4)}

        def loss_fn(self, params, batch, remat=False):
            Q, c = batch["Q"], batch["c"]
            x = params["x"]
            return 0.5 * x @ Q @ x + c @ x

    gamma, rho, ne = 0.05, 1.0, 3
    fcfg = runtime.FedConfig(n_agents=3, rho=rho, gamma=gamma, n_epochs=ne)
    state = runtime.init_state(QuadModel(), jax.random.PRNGKey(0), fcfg)
    step = runtime.make_train_step(QuadModel(), fcfg)
    batch = {"Q": prob.Q, "c": prob.c}
    for i in range(50):
        state, _ = step(state, batch, jax.random.PRNGKey(i))

    core = FedPLT(prob, FedPLTConfig(
        rho=rho, solver=SolverConfig(name="gd", n_epochs=ne,
                                     step_size=gamma)))
    cstate, _ = core.run(jax.random.PRNGKey(0), 50)
    np.testing.assert_allclose(
        jnp.mean(state.x["x"], axis=0), core.x_bar(cstate), atol=1e-3)


# ---------------------------------------------------------------------------
# the trainer's round consumes (donates) the state it is given
# ---------------------------------------------------------------------------

LAYOUTS = {
    "tree": {},
    "tree-topk": dict(compression=CompressionSpec("topk")),
    "packed": dict(state_layout="packed"),
    "packed-topk": dict(state_layout="packed",
                        compression=CompressionSpec("topk")),
    "stale": dict(async_mode="stale", max_staleness=2, participation=0.5),
    "mesh": dict(mesh_shape="1x1"),
}


@pytest.fixture(scope="module")
def tiny():
    cfg = get_config("gemma2-2b").reduced(n_layers=1, d_model=64, vocab=128)
    batch = make_batch_for(cfg, InputShape("t", 4, 4, "train"), n_agents=2)
    return build_model(cfg), batch


def _tiny_trainer(tiny, layout):
    model, batch = tiny
    spec = FedSpec(n_agents=2, gamma=0.1, n_epochs=2, **LAYOUTS[layout])
    return build_trainer(model, spec), batch


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_init_buffers_are_distinct_and_step_consumes_them(tiny, layout):
    tr, batch = _tiny_trainer(tiny, layout)
    state = tr.init(jax.random.PRNGKey(0))
    leaves = jax.tree_util.tree_leaves(state)
    if "topk" in layout:
        assert state.t is not None
    ptrs = [leaf.unsafe_buffer_pointer() for leaf in leaves]
    assert len(set(ptrs)) == len(ptrs)
    new, m = tr.step(state, batch, jax.random.PRNGKey(1))
    assert all(leaf.is_deleted() for leaf in leaves)
    assert np.isfinite(float(m["loss"]))
    new, _ = tr.step(new, batch, jax.random.PRNGKey(2))
    assert int(new.step) == 2


@pytest.mark.parametrize("layout", ["tree", "packed-topk", "stale"])
def test_donated_rounds_match_undonated_bitwise(tiny, layout):
    tr, batch = _tiny_trainer(tiny, layout)
    plain = jax.jit(runtime.make_train_step(tr.model, tr.spec))
    key = jax.random.PRNGKey(0)
    donated, kept = tr.init(key), tr.init(key)
    for i in range(3):
        k = jax.random.PRNGKey(10 + i)
        donated, _ = tr.step(donated, batch, k)
        kept, _ = plain(kept, batch, k)
    for a, b in zip(jax.tree_util.tree_leaves((donated.x, donated.z)),
                    jax.tree_util.tree_leaves((kept.x, kept.z))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
