"""Unified round engine: the dense front end reproduces the pre-refactor
trajectory bit-for-bit; model-scale pytree rounds support compression,
agd, the fused kernel, and the DP accountant."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import InputShape
from repro.core.fedplt import FedPLT
from repro.core.problem import make_quadratic_problem
from repro.core.solvers import SolverConfig, clip_grad, local_train
from repro.data.synthetic import make_batch_for
from repro.fed import engine, runtime
from repro.fed.api import (CompressionSpec, FedSpec, PrivacySpec,
                           privacy_report)
from repro.models.model import build_model


# ---------------------------------------------------------------------------
# Pre-refactor reference: the historical core/fedplt.py round, inlined
# ---------------------------------------------------------------------------

def _reference_run(problem, rho, n_epochs, participation, n_rounds, key,
                   damping=1.0, compression="none", compress_ratio=0.25,
                   tau=0.0):
    """Verbatim re-implementation of the dense round as it existed before
    the engine refactor (gd / noisy_gd solvers, prox_h = 0)."""
    N = problem.n_agents
    mu = jnp.float32(problem.strong_convexity())
    L = jnp.float32(problem.smoothness())
    # same f32 arithmetic chain as the traced per-agent moduli
    gamma = 2.0 / ((L + 1.0 / rho) + (mu + 1.0 / rho))
    inv_rho = 1.0 / rho
    noise_scale = jnp.sqrt(2.0 * gamma) * tau
    data = (problem.Q, problem.c)

    def local_gd(data_i, x_i, v_i, key_i):
        def body(w, k):
            g = jax.grad(lambda xx: problem.local_loss(data_i, xx))(w)
            new = w - gamma * (g + inv_rho * (w - v_i))
            if tau > 0.0:
                _, k_noise = jax.random.split(k)
                new = new + noise_scale * jax.random.normal(k_noise,
                                                            w.shape)
            return new, None

        w, _ = jax.lax.scan(body, x_i, jax.random.split(key_i, n_epochs))
        return w

    def compress(dz):
        if compression == "topk":
            k = max(1, int(compress_ratio * dz.shape[-1]))

            def topk_row(row):
                thresh = jnp.sort(jnp.abs(row))[-k]
                return jnp.where(jnp.abs(row) >= thresh, row, 0.0)

            return jax.vmap(topk_row)(dz)
        if compression == "int8":
            scale = jnp.max(jnp.abs(dz), axis=-1, keepdims=True) / 127.0
            scale = jnp.maximum(scale, 1e-12)
            q = jnp.round(dz / scale).astype(jnp.int8)
            return q.astype(dz.dtype) * scale
        return dz

    compressed = compression != "none"

    def round_ref(state, _):
        x, z, t, key = state
        key, k_part, k_solve = jax.random.split(key, 3)
        z_seen = t if compressed else z
        y = jnp.mean(z_seen, axis=0)
        v = 2.0 * y[None, :] - z
        solver_keys = jax.random.split(k_solve, N)
        w = jax.vmap(local_gd)(data, x, v, solver_keys)
        u = jax.random.bernoulli(k_part, participation,
                                 (N,)).astype(w.dtype)[:, None]
        x_new = u * w + (1.0 - u) * x
        z_upd = z + 2.0 * damping * (w - y[None, :])
        z_new = u * z_upd + (1.0 - u) * z
        if compressed:
            t_new = t + u * compress(z_new - t)
        else:
            t_new = z_new
        return (x_new, z_new, t_new, key), (x_new, z_new)

    _, k_state = jax.random.split(key)
    x0 = jnp.zeros((N, problem.dim))
    (_, _, _, _), traj = jax.lax.scan(round_ref, (x0, x0, x0, k_state),
                                      None, length=n_rounds)
    return traj


@pytest.mark.parametrize("kw", [
    dict(participation=1.0),
    dict(participation=0.6),
    dict(participation=0.7, compression="topk", compress_ratio=0.5,
         damping=0.5),
    dict(compression="int8"),
    dict(tau=0.05),   # DP noisy GD: same PRNG noise stream
])
def test_dense_round_matches_pre_refactor_bit_for_bit(kw):
    """core/fedplt.py through the engine == the historical implementation,
    exactly (same PRNG consumption, same op order, same bits)."""
    prob = make_quadratic_problem(n_agents=6, dim=5, seed=3)
    rho, ne, rounds = 1.0, 4, 30
    tau = kw.pop("tau", 0.0)
    spec = FedSpec(
        rho=rho, solver="noisy_gd" if tau > 0 else "gd", n_epochs=ne,
        participation=kw.get("participation", 1.0),
        damping=kw.get("damping", 1.0), privacy=PrivacySpec(tau=tau),
        compression=CompressionSpec(
            name=kw.get("compression", "none"),
            ratio=kw.get("compress_ratio", 0.25), backend="xla"))
    algo = FedPLT(prob, spec)
    key = jax.random.PRNGKey(7)

    state = algo.init(key)

    def body(s, _):
        s = algo._round_impl(s)
        return s, (s.x, s.z)

    _, (xs, zs) = jax.lax.scan(body, state, None, length=rounds)

    ref_xs, ref_zs = _reference_run(
        prob, rho, ne, kw.get("participation", 1.0), rounds, key,
        damping=kw.get("damping", 1.0),
        compression=kw.get("compression", "none"),
        compress_ratio=kw.get("compress_ratio", 0.25), tau=tau)

    np.testing.assert_array_equal(np.asarray(xs), np.asarray(ref_xs))
    np.testing.assert_array_equal(np.asarray(zs), np.asarray(ref_zs))


# ---------------------------------------------------------------------------
# Engine pieces on pytrees
# ---------------------------------------------------------------------------

def test_compress_increment_topk_keeps_k_per_leaf():
    cfg = engine.RoundConfig(n_agents=2, compression="topk",
                             compress_ratio=0.25)
    dz = {"a": jnp.arange(1.0, 17.0).reshape(2, 2, 4),
          "b": jnp.ones((2, 3))}
    out = engine.compress_increment(dz, cfg)
    # per agent, per leaf: ceil/floor(0.25 * m) kept, top magnitudes
    assert int(jnp.sum(out["a"][0] != 0)) == 2
    np.testing.assert_allclose(out["a"][1].reshape(-1)[-2:],
                               dz["a"][1].reshape(-1)[-2:])


def test_masked_mix_isolates_nonfinite_inactive_agents():
    """A diverged (NaN) local solve on a NON-participating agent must not
    poison its preserved state (jnp.where, not u*new + (1-u)*old)."""
    u = jnp.array([1.0, 0.0])
    new = {"w": jnp.array([[1.0, 2.0], [jnp.nan, jnp.inf]])}
    old = {"w": jnp.array([[9.0, 9.0], [3.0, 4.0]])}
    out = engine.masked_mix(u, new, old)
    np.testing.assert_array_equal(out["w"][0], [1.0, 2.0])
    np.testing.assert_array_equal(out["w"][1], [3.0, 4.0])  # finite kept


def test_clip_grad_batched_is_per_agent():
    g = {"w": jnp.array([[3.0, 4.0], [0.3, 0.4]]),
         "b": jnp.zeros((2, 1))}
    out = clip_grad(g, 1.0, batched=True)
    # agent 0 has norm 5 -> scaled to 1; agent 1 has norm 0.5 -> untouched
    np.testing.assert_allclose(out["w"][0], [0.6, 0.8], atol=1e-6)
    np.testing.assert_allclose(out["w"][1], [0.3, 0.4], atol=1e-6)


def test_local_train_pytree_matches_array():
    """A pytree of two halves steps exactly like the concatenated array."""
    Q = jnp.diag(jnp.array([2.0, 1.0, 3.0, 0.5]))
    c = jnp.array([0.1, -0.2, 0.3, 0.4])
    v = jnp.array([1.0, 2.0, -1.0, 0.5])
    cfg = SolverConfig(name="gd", n_epochs=7, step_size=0.1)
    key = jax.random.PRNGKey(0)

    w_arr = local_train(lambda w, k: Q @ w + c, jnp.zeros(4), v, 1.0, cfg,
                        key, 0.5, 3.0)

    def fgrad_tree(w, k):
        full = jnp.concatenate([w["lo"], w["hi"]])
        g = Q @ full + c
        return {"lo": g[:2], "hi": g[2:]}

    w_tree = local_train(fgrad_tree, {"lo": jnp.zeros(2), "hi": jnp.zeros(2)},
                         {"lo": v[:2], "hi": v[2:]}, 1.0, cfg, key, 0.5, 3.0)
    np.testing.assert_allclose(
        jnp.concatenate([w_tree["lo"], w_tree["hi"]]), w_arr, atol=1e-7)


# ---------------------------------------------------------------------------
# Model scale: compression, agd, fused kernel, privacy
# ---------------------------------------------------------------------------

SHAPE = InputShape("tiny", 32, 8, "train")


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("gemma2-2b").reduced()
    model = build_model(cfg)
    return cfg, model


def _losses(cfg, model, spec, rounds=6):
    state = runtime.init_state(model, jax.random.PRNGKey(0), spec)
    step = jax.jit(runtime.make_train_step(model, spec))
    batch = make_batch_for(cfg, SHAPE, n_agents=spec.n_agents)
    out = []
    for i in range(rounds):
        state, m = step(state, batch, jax.random.PRNGKey(i))
        out.append(float(m["loss"]))
    return out, state


@pytest.mark.parametrize("comp", ["topk", "int8"])
def test_compressed_pytree_round_converges(setup, comp):
    """Model-scale smoke: a compressed z-exchange still trains."""
    cfg, model = setup
    spec = FedSpec(n_agents=2, n_epochs=2, gamma=0.1,
                   compression=CompressionSpec(name=comp, ratio=0.5,
                                               backend="xla"))
    losses, state = _losses(cfg, model, spec)
    assert losses[-1] < losses[0]
    assert state.t is not None  # coordinator copy materialized
    # t lags z (error feedback residual is nonzero under top-k)
    if comp == "topk":
        lag = jax.tree_util.tree_reduce(
            lambda acc, p: acc + float(jnp.sum(jnp.abs(p[0] - p[1]))),
            jax.tree_util.tree_map(lambda a, b: jnp.stack(
                [a[0].ravel()[:64], b[0].ravel()[:64]]), state.z, state.t),
            0.0)
        assert lag > 0


def test_agd_solver_at_model_scale(setup):
    cfg, model = setup
    spec = FedSpec(n_agents=2, n_epochs=3, gamma=0.05, solver="agd")
    losses, _ = _losses(cfg, model, spec)
    assert losses[-1] < losses[0]


def test_pallas_fused_step_matches_unfused(setup):
    cfg, model = setup
    base = FedSpec(n_agents=2, n_epochs=2, gamma=0.1)
    losses_ref, state_ref = _losses(cfg, model, base, rounds=2)
    fused = FedSpec(n_agents=2, n_epochs=2, gamma=0.1, use_pallas=True)
    losses_fused, state_fused = _losses(cfg, model, fused, rounds=2)
    np.testing.assert_allclose(losses_ref, losses_fused, rtol=1e-4)
    x_ref = jax.tree_util.tree_leaves(state_ref.x)[0]
    x_fused = jax.tree_util.tree_leaves(state_fused.x)[0]
    np.testing.assert_allclose(np.asarray(x_ref), np.asarray(x_fused),
                               atol=1e-5)


def test_privacy_report_threads_from_config():
    spec = FedSpec(n_agents=4, rho=1.0, gamma=0.05, n_epochs=3,
                   privacy=PrivacySpec(tau=0.1, clip=1.0))
    rep = privacy_report(spec, n_rounds=50, local_dataset_size=100)
    assert np.isfinite(rep.adp_eps) and rep.adp_eps > 0
    assert rep.adp_eps <= rep.eps_ceiling + 1e-9
    with pytest.raises(ValueError):
        privacy_report(FedSpec(n_agents=16, gamma=0.05), 10, 10)


def test_fed_state_specs_structure():
    from jax.sharding import PartitionSpec as P

    from repro.fed import sharding

    params = {"wq": jnp.zeros((4, 8, 16)), "norm": jnp.zeros((4, 8))}
    spec = sharding.fed_state_specs(params, fsdp_axis=None,
                                    agent_axis="data",
                                    axis_sizes={"data": 4},
                                    compressed=True)
    assert isinstance(spec, runtime.FedState)
    assert spec.x == spec.z == spec.t
    assert spec.step == P()
    assert spec.x["wq"][0] == "data"  # leading agent axis
