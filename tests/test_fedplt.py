"""Fed-PLT system behaviour: exact convergence, no client drift, partial
participation, composite problems, DP neighbourhood (paper Props. 1-4)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.fedplt import FedPLT, FedPLTConfig
from repro.core.problem import (make_logreg_problem,
                                make_quadratic_problem)
from repro.core.prox import prox_l1
from repro.core.solvers import SolverConfig


@pytest.fixture(scope="module")
def logreg():
    return make_logreg_problem(n_agents=20, q=50, dim=5, seed=0)


@pytest.fixture(scope="module")
def quad():
    return make_quadratic_problem(n_agents=8, dim=6, seed=1)


def run(problem, cfg, rounds=150, seed=0):
    algo = FedPLT(problem, cfg)
    state, crit = algo.run(jax.random.PRNGKey(seed), rounds)
    return algo, state, np.asarray(crit)


def test_exact_convergence_quadratic_closed_form(quad):
    cfg = FedPLTConfig(rho=1.0, solver=SolverConfig(name="gd", n_epochs=5))
    algo, state, crit = run(quad, cfg, 200)
    np.testing.assert_allclose(algo.x_bar(state), quad.solve(), atol=1e-4)


def test_exact_convergence_logreg(logreg):
    cfg = FedPLTConfig(rho=1.0, solver=SolverConfig(name="gd", n_epochs=5))
    algo, state, crit = run(logreg, cfg)
    assert crit[-1] < 1e-9
    np.testing.assert_allclose(algo.x_bar(state), logreg.solve(20000),
                               atol=1e-4)


def test_no_client_drift_large_ne(logreg):
    """Accuracy does not degrade as N_e grows (Sec. V-C2)."""
    for ne in (1, 5, 20):
        cfg = FedPLTConfig(rho=1.0,
                           solver=SolverConfig(name="gd", n_epochs=ne))
        _, _, crit = run(logreg, cfg, 200)
        assert crit[-1] < 1e-8, f"drift at N_e={ne}: {crit[-1]}"


def test_partial_participation_converges(logreg):
    cfg = FedPLTConfig(rho=1.0, participation=0.5,
                       solver=SolverConfig(name="gd", n_epochs=5))
    _, _, crit = run(logreg, cfg, 600)
    assert crit[-1] < 1e-8


def test_partial_participation_slower_than_full(logreg):
    """Table VI phenomenon: fewer active agents => slower convergence."""
    cfg_full = FedPLTConfig(rho=1.0, participation=1.0,
                            solver=SolverConfig(name="gd", n_epochs=5))
    cfg_half = FedPLTConfig(rho=1.0, participation=0.4,
                            solver=SolverConfig(name="gd", n_epochs=5))
    _, _, c_full = run(logreg, cfg_full, 120)
    _, _, c_half = run(logreg, cfg_half, 120)
    t_full = np.argmax(c_full < 1e-5) + 1
    t_half = np.argmax(c_half < 1e-5) + 1
    assert t_full < t_half


def test_accelerated_solver_converges(logreg):
    cfg = FedPLTConfig(rho=1.0,
                       solver=SolverConfig(name="agd", n_epochs=5))
    _, _, crit = run(logreg, cfg, 300)
    assert crit[-1] < 1e-8


def test_sgd_converges_to_neighbourhood(logreg):
    """Prop. 2: SGD converges to a variance-dependent neighbourhood that
    shrinks as the minibatch grows (nu smaller => tighter radius)."""
    tails = []
    for bs in (10, 45):
        cfg = FedPLTConfig(rho=1.0, batch_size=bs,
                           solver=SolverConfig(name="sgd", n_epochs=5))
        _, _, crit = run(logreg, cfg, 300)
        tails.append(np.mean(crit[-30:]))
    init = logreg.criterion(jnp.zeros((logreg.n_agents, logreg.dim)))
    assert tails[0] < 0.05 * float(init)   # converged to a neighbourhood
    assert tails[1] < tails[0]             # radius shrinks with variance


def test_noisy_gd_neighbourhood_scales_with_tau(logreg):
    errs = []
    for tau in (1e-4, 1e-2):
        cfg = FedPLTConfig(
            rho=1.0, solver=SolverConfig(name="noisy_gd", n_epochs=5,
                                         tau=tau))
        _, _, crit = run(logreg, cfg, 200)
        errs.append(np.mean(crit[-20:]))
    assert errs[0] < errs[1]  # Table VII: error grows with tau


def test_composite_l1_regularized(quad):
    """h = ||x||_1 at the coordinator: converges to the l1-regularized
    optimum (checked against proximal gradient oracle)."""
    cfg = FedPLTConfig(rho=0.5, prox_h="l1",
                       solver=SolverConfig(name="gd", n_epochs=10))
    algo, state, _ = run(quad, cfg, 400)
    # oracle: proximal gradient on F(x) = sum f_i + ||x||_1
    x = jnp.zeros(quad.dim)
    Lsum = quad.smoothness() * quad.n_agents
    for _ in range(20000):
        g = jnp.sum(quad.grads(jnp.broadcast_to(x, (quad.n_agents,
                                                    quad.dim))), axis=0)
        x = prox_l1(x - g / Lsum, 1.0 / Lsum)
    y_star = algo.prox_h(jnp.mean(state.z, axis=0),
                         cfg.rho / quad.n_agents)
    np.testing.assert_allclose(y_star, x, atol=2e-3)


def test_nonconvex_regularizer_runs():
    p = make_logreg_problem(n_agents=10, q=30, dim=4, nonconvex=True)
    cfg = FedPLTConfig(rho=1.0, L=5.0, mu=0.1,
                       solver=SolverConfig(name="gd", n_epochs=5,
                                           step_size=0.05))
    algo = FedPLT(p, cfg)
    _, crit = algo.run(jax.random.PRNGKey(0), 300)
    assert np.asarray(crit)[-1] < 1e-3  # converges in practice (Sec. VII)


def test_dp_init_draws_random_x0(logreg):
    cfg = FedPLTConfig(rho=1.0, dp_init=True,
                       solver=SolverConfig(name="noisy_gd", n_epochs=3,
                                           tau=0.1))
    algo = FedPLT(logreg, cfg)
    st = algo.init(jax.random.PRNGKey(0))
    assert float(jnp.std(st.x)) > 0.01


# ---------------------------------------------------------------------------
# the compiled solve == the eager scan it replaced, bit for bit
# ---------------------------------------------------------------------------

GD = SolverConfig(name="gd", n_epochs=3, step_size=0.05)
SOLVE_CASES = {
    "gd": dict(cfg=dict(participation=0.6)),
    "dp-init": dict(cfg=dict(dp_init=True, solver=SolverConfig(
        name="noisy_gd", n_epochs=3, step_size=0.05, tau=0.05))),
    "solver-groups": dict(groups=((3, GD), (5, SolverConfig(
        name="agd", n_epochs=2)))),
    "topk": dict(cfg=dict(compression="topk", compress_ratio=0.4,
                          damping=0.5)),
    "packed": dict(cfg=dict(state_layout="packed", participation=0.6)),
    "stale": dict(cfg=dict(async_mode="stale", max_staleness=2,
                           participation=0.5, damping=0.7)),
}


def _eager_solve(algo, key, n_rounds, schedule=None):
    """The solve as an eager ``lax.scan`` over ``_round_core`` from an
    op-by-op ``init``: ``(state, crit, sched)``, or ``(state, crit)``
    when a recorded ``schedule`` is replayed."""
    state = algo.init(key)
    if schedule is None:
        def body(s, _):
            s, u = algo._round_core(s)
            return s, (algo.problem.criterion(s.x), u)

        state, (crit, sched) = jax.lax.scan(body, state, None,
                                            length=n_rounds)
        return state, crit, sched

    def body(s, row):
        s, _ = algo._round_core(s, row)
        return s, algo.problem.criterion(s.x)

    return jax.lax.scan(body, state, jnp.asarray(schedule, jnp.float32))


def _assert_same_bits(got, want):
    got, want = jax.tree_util.tree_flatten(got), jax.tree_util.tree_flatten(
        want)
    assert got[1] == want[1]
    for a, b in zip(got[0], want[0]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("case", sorted(SOLVE_CASES))
def test_compiled_solve_matches_eager_scan_bitwise(case):
    spec = SOLVE_CASES[case]
    prob = make_logreg_problem(n_agents=8, q=20, dim=5, seed=2)
    cfg = FedPLTConfig(**{"solver": GD, **spec.get("cfg", {})})
    algo = FedPLT(prob, cfg, solver_groups=spec.get("groups"))
    key = jax.random.PRNGKey(7)
    got = algo.run_recorded(key, 6)
    _assert_same_bits(got, _eager_solve(algo, key, 6))
    # a second call of the length reuses the executable, not a result
    other = jax.random.PRNGKey(8)
    _assert_same_bits(algo.run_recorded(other, 6),
                      _eager_solve(algo, other, 6))
    if cfg.async_mode == "stale":
        sched = got[2]
        assert 0 < float(jnp.sum(sched)) < sched.size
        _assert_same_bits(algo.replay(key, sched),
                          _eager_solve(algo, key, 6, sched))
