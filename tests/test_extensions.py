"""Extensions: Remark-1 uncoordinated solvers, compressed z-exchange,
Krasnosel'skii damping."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.fedplt import FedPLT
from repro.core.metrics import hitting_round
from repro.core.problem import LogRegProblem, make_logreg_problem
from repro.fed.api import CompressionSpec, FedSpec


def _comp(name, ratio=0.25):
    return CompressionSpec(name=name, ratio=ratio, backend="xla")


@pytest.fixture(scope="module")
def prob():
    return make_logreg_problem(n_agents=20, q=50, dim=20, seed=0)


@pytest.fixture(scope="module")
def hetero_prob():
    p0 = make_logreg_problem(n_agents=20, q=50, dim=5, seed=0)
    scales = jnp.linspace(0.3, 3.0, 20)[:, None, None]
    return LogRegProblem(A=p0.A * scales, b=p0.b, eps=0.5)


def _run(p, spec, rounds=500):
    _, crit = FedPLT(p, spec).run(jax.random.PRNGKey(0), rounds)
    return np.asarray(crit)


def test_uncoordinated_solvers_converge(hetero_prob):
    """Remark 1: per-agent step sizes from local moduli still converge
    exactly on heterogeneous agents."""
    crit = _run(hetero_prob, FedSpec(rho=1.0, uncoordinated=True), 300)
    assert crit[-1] < 1e-9


def test_per_agent_moduli_vary(hetero_prob):
    L_i = hetero_prob.per_agent_smoothness()
    assert float(jnp.max(L_i) / jnp.min(L_i)) > 5.0


@pytest.mark.parametrize("comp,kw,rounds", [
    ("int8", {}, 300),
    ("topk", {"ratio": 0.5}, 400),
    ("topk", {"ratio": 0.1}, 800),
])
def test_compressed_exchange_converges_exactly(prob, comp, kw, rounds):
    """Beyond-paper: lag-based error feedback keeps exact convergence
    under int8 and top-k (down to 10%) z compression."""
    crit = _run(prob, FedSpec(rho=1.0, compression=_comp(comp, **kw)),
                rounds)
    assert crit[-1] < 1e-9, crit[-1]


def test_compression_costs_rounds_not_accuracy(prob):
    hit_exact = hitting_round(_run(prob, FedSpec(rho=1.0), 300))
    hit_topk = hitting_round(_run(prob, FedSpec(
        rho=1.0, compression=_comp("topk", 0.1)), 800))
    assert hit_exact < hit_topk            # bandwidth traded for rounds
    assert hit_topk < 10 * hit_exact       # at sublinear cost


def test_damping_half_is_douglas_rachford(prob):
    """damping=1/2 (DRS) still converges exactly, slower than PRS."""
    crit = _run(prob, FedSpec(rho=1.0, damping=0.5), 400)
    assert crit[-1] < 1e-9


def test_compression_with_partial_participation(prob):
    spec = FedSpec(rho=1.0, compression=_comp("int8"), participation=0.6)
    crit = _run(prob, spec, 800)
    assert crit[-1] < 1e-8
