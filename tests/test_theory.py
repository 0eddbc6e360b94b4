"""Theory module: contraction factors, S matrix, Lemma 7, Corollary 1."""

import jax
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import theory
from repro.core.fedplt import FedPLT
from repro.core.problem import make_quadratic_problem
from repro.core.solvers import SolverConfig
from repro.fed.api import FedSpec


def test_zeta_minimized_near_rho_star():
    """zeta(rho) is minimized at rho = 1/sqrt(mu L) (PRS theory)."""
    mu, L = 0.5, 8.0
    rho_star = 1.0 / np.sqrt(mu * L)
    z_star = theory.zeta_prs(rho_star, mu, L)
    for rho in (0.01, 0.1, 10.0, 100.0):
        assert theory.zeta_prs(rho, mu, L) >= z_star - 1e-12


@given(st.floats(0.05, 1.0), st.floats(1.5, 50.0), st.floats(0.05, 20.0))
@settings(max_examples=60, deadline=None)
def test_zeta_chi_in_unit_interval(mu, L, rho):
    assert 0.0 <= theory.zeta_prs(rho, mu, L) < 1.0
    gamma = 2.0 / (mu + L + 2.0 / rho)
    assert 0.0 <= theory.chi_gd(gamma, mu + 1 / rho, L + 1 / rho) < 1.0


def test_lemma7_stabilizer_finds_stable_params():
    for mu, L in [(0.5, 4.0), (0.01, 100.0), (1.0, 1.5)]:
        res = theory.stabilize(mu, L)
        assert res.spectral_radius < 1.0, (mu, L)


def test_sigma_increases_as_participation_drops():
    s = 0.9
    sig = [theory.sigma(p, p, s) for p in (1.0, 0.7, 0.4, 0.1)]
    assert all(a < b for a, b in zip(sig, sig[1:]))
    assert sig[0] == pytest.approx(s)


def test_s_norm_bounds_empirical_rate():
    """||S|| from Prop. 1 upper-bounds the empirical contraction rate of
    the full Fed-PLT operator on a quadratic problem."""
    prob = make_quadratic_problem(n_agents=6, dim=4, seed=3)
    mu, L = prob.strong_convexity(), prob.smoothness()
    rho, ne = 1.0, 5
    scfg = SolverConfig(name="gd", n_epochs=ne)
    s_norm = theory.s_norm(scfg, mu, L, rho)
    algo = FedPLT(prob, FedSpec(rho=rho, solver="gd", n_epochs=ne))
    state, crit = algo.run(jax.random.PRNGKey(0), 80)
    crit = np.asarray(crit)
    # empirical per-round criterion decay rate (criterion ~ dist^2)
    window = crit[10:60]
    emp_rate = np.exp(np.mean(np.diff(np.log(window + 1e-30)))) ** 0.5
    assert emp_rate <= s_norm + 0.05


def _stable_params(mu=0.5, L=4.0):
    res = theory.stabilize(mu, L)
    assert res.s_norm < 1.0
    return dict(mu=mu, L=L, rho=res.rho, gamma=res.gamma,
                n_epochs=res.n_epochs)


def test_corollary1_bound_monotone_in_tau():
    args = dict(K=100, dim=5, n_agents=10, r0=1.0, **_stable_params())
    b1 = theory.corollary1_bound(tau=1e-3, **args)
    b2 = theory.corollary1_bound(tau=1e-1, **args)
    assert b1 < b2 < float("inf")


def test_asymptotic_error_zero_noise():
    p = _stable_params()
    assert theory.asymptotic_error(p["mu"], p["L"], p["rho"], p["gamma"],
                                   p["n_epochs"], 0.0, 5, 10) == 0.0
