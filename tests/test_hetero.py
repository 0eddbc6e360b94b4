"""Per-agent heterogeneity: the solver registry, FedSpec.agent_groups
through both front ends, per-agent participation, and the per-agent
privacy table.

The safety contract that makes the feature cheap to adopt: a
*homogeneous* agent-group spec (one full-size group, knobs inherited)
is bit-identical to the ungrouped spec on every configuration class
(dense gd/agd, DP noise, compressed uplink, partial participation)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.fedplt import FedPLT
from repro.core.problem import make_quadratic_problem
from repro.core.solvers import SolverConfig
from repro.fed import runtime
from repro.fed.api import (AgentGroupSpec, CompressionSpec, FedSpec,
                           PrivacySpec, build_trainer, parse_agent_groups,
                           spec_from_args)
from repro.fed.solvers import (available_solvers, get_solver,
                               register_solver)


@pytest.fixture(scope="module")
def quad():
    return make_quadratic_problem(n_agents=5, dim=6, seed=3)


class QuadModel:
    def init(self, key):
        return {"x": jnp.zeros(6)}

    def loss_fn(self, params, batch, remat=False):
        x = params["x"]
        return 0.5 * x @ batch["Q"] @ x + batch["c"] @ x


def _quad_batch(quad):
    return {"Q": quad.Q, "c": quad.c}


# ---------------------------------------------------------------------------
# Solver registry
# ---------------------------------------------------------------------------

def test_solver_registry_has_builtins():
    for name in ("gd", "agd", "sgd", "noisy_gd"):
        assert name in available_solvers()


def test_unknown_solver_lists_registry():
    with pytest.raises(ValueError, match="unknown solver 'warp'.*"
                                         "registered:.*gd"):
        get_solver("warp")
    # ... and the same message surfaces from spec validation, both for
    # the top-level solver field and inside a group
    with pytest.raises(ValueError, match="registered:"):
        FedSpec(n_agents=2, gamma=0.1, agent_groups="2*warp").validate()


def test_registered_solver_usable_by_name(quad):
    """Extensibility proof mirroring the compressor registry: a solver
    registered here drives a group purely through FedSpec."""
    calls = []

    @register_solver("half_gd_test")
    def make_half_gd(scfg, fgrad, rho, mu, L, *, use_pallas, has_aux):
        from repro.core.solvers import local_train

        calls.append(1)
        half = SolverConfig(name="gd", n_epochs=scfg.n_epochs,
                            step_size=(scfg.step_size or 0.1) / 2.0)

        def solver(x, v, key):
            out = local_train(fgrad, x, v, rho, half, key, mu, L,
                              batched=True, has_aux=has_aux,
                              use_pallas=use_pallas)
            return out if has_aux else (out, None)

        return solver

    spec = FedSpec(n_agents=5, gamma=0.1, n_epochs=2,
                   agent_groups="3*gd,2*half_gd_test")
    trainer = build_trainer(QuadModel(), spec)
    state, hist = trainer.run(jax.random.PRNGKey(0), 5,
                              lambda i: _quad_batch(quad))
    assert calls, "registered solver factory was never dispatched"
    assert np.isfinite(hist[-1]["loss"])


def test_registered_solver_reaches_dense_front_end(quad):
    """A registry solver must also drive the dense (paper) path: the
    spec validates against the registry, so the trainer must dispatch
    through it rather than crash at trace time."""

    @register_solver("tiny_gd_test")
    def make_tiny_gd(scfg, fgrad, rho, mu, L, *, use_pallas, has_aux):
        from repro.core.solvers import local_train

        tiny = SolverConfig(name="gd", n_epochs=scfg.n_epochs,
                            step_size=0.05)

        def solver(x, v, key):
            out = local_train(fgrad, x, v, rho, tiny, key, mu, L,
                              batched=True, has_aux=has_aux,
                              use_pallas=use_pallas)
            return out if has_aux else (out, None)

        return solver

    spec = FedSpec(n_epochs=2, solver="tiny_gd_test")
    spec_g = FedSpec(n_epochs=2, agent_groups="3*gd,2*tiny_gd_test")
    for s in (spec, spec_g):
        state, crit = build_trainer(quad, s).run(jax.random.PRNGKey(0), 10)
        assert np.isfinite(np.asarray(crit)).all()


def test_custom_solver_with_tau_rejected():
    """Prop. 4 certifies NOISY local GD: a custom registered solver
    (which the accountant knows nothing about) must not silently earn a
    DP certificate just because tau was set."""
    register_solver("no_noise_test")(lambda *a, **k: None)
    with pytest.raises(ValueError, match="gd-type solver, not "
                                         "'no_noise_test'"):
        FedSpec(n_agents=2, gamma=0.1, solver="no_noise_test",
                privacy=PrivacySpec(tau=0.5)).validate()
    with pytest.raises(ValueError, match="gd-type solver"):
        FedSpec(n_agents=2, gamma=0.1,
                agent_groups="2*no_noise_test",
                privacy=PrivacySpec(tau=0.5)).validate()


def test_custom_solver_without_aux_trains_at_model_scale(quad):
    """A registry solver that returns aux=None (as the docstring
    permits) must not crash the model-scale loss metric: its agents
    drop out of the mean instead."""

    @register_solver("no_aux_gd_test")
    def make_no_aux_gd(scfg, fgrad, rho, mu, L, *, use_pallas, has_aux):
        from repro.core.solvers import local_train

        plain = SolverConfig(name="gd", n_epochs=scfg.n_epochs,
                             step_size=scfg.step_size)

        def solver(x, v, key):
            w = local_train(lambda w_, k: fgrad(w_, k)[0], x, v, rho,
                            plain, key, mu, L, batched=True)
            return w, None    # deliberately discards the loss trace

        return solver

    spec = FedSpec(n_agents=5, gamma=0.05, n_epochs=2,
                   agent_groups="3*gd,2*no_aux_gd_test")
    trainer = build_trainer(QuadModel(), spec)
    state, hist = trainer.run(jax.random.PRNGKey(0), 3,
                              lambda i: _quad_batch(quad))
    assert np.isfinite(hist[-1]["loss"])   # gd group still reports


def test_privacy_report_rejects_string_q(quad):
    trainer = build_trainer(quad, FedSpec(
        n_epochs=3, privacy=PrivacySpec(tau=0.1)))
    with pytest.raises(TypeError, match="not a string"):
        trainer.privacy_report(10, local_dataset_size="250")


def test_core_solvers_constant_matches_registry():
    """fedplt's dense fast path keys off CORE_SOLVERS; every core name
    must actually be registered (drift guard)."""
    from repro.fed.solvers import CORE_SOLVERS

    for name in CORE_SOLVERS:
        assert name in available_solvers()


def test_run_solvers_accepts_bare_solver_group():
    from repro.fed import engine

    x = {"w": jnp.arange(6.0).reshape(3, 2)}
    solver = lambda xs, vs, k: (jax.tree_util.tree_map(
        lambda l: l + 1.0, xs), None)
    w_bare, _ = engine.run_solvers(engine.SolverGroup(3, solver),
                                   x, x, jax.random.PRNGKey(0), 3)
    w_seq, _ = engine.run_solvers([engine.SolverGroup(3, solver)],
                                  x, x, jax.random.PRNGKey(0), 3)
    np.testing.assert_array_equal(np.asarray(w_bare["w"]),
                                  np.asarray(w_seq["w"]))


# ---------------------------------------------------------------------------
# Homogeneous agent_groups == the ungrouped spec, bit for bit (dense)
# ---------------------------------------------------------------------------

HOMOGENEOUS_CASES = [
    dict(),
    dict(solver="agd"),
    dict(privacy=PrivacySpec(tau=0.05)),
    dict(participation=0.6),
    dict(participation=0.7, damping=0.5,
         compression=CompressionSpec(name="topk", ratio=0.5)),
]


@pytest.mark.parametrize("spec_kw", HOMOGENEOUS_CASES,
                         ids=["gd", "agd", "dp", "partial", "topk"])
def test_single_homogeneous_group_bit_identical(quad, spec_kw):
    key = jax.random.PRNGKey(11)
    s_ref, c_ref = FedPLT(quad, FedSpec(rho=1.0, n_epochs=3,
                                        **spec_kw)).run(key, 25)
    spec = FedSpec(rho=1.0, n_epochs=3,
                   agent_groups=(AgentGroupSpec(size=quad.n_agents),),
                   **spec_kw)
    s_new, c_new = build_trainer(quad, spec).run(key, 25)
    np.testing.assert_array_equal(np.asarray(c_ref), np.asarray(c_new))
    np.testing.assert_array_equal(np.asarray(s_ref.x), np.asarray(s_new.x))
    np.testing.assert_array_equal(np.asarray(s_ref.z), np.asarray(s_new.z))


def test_multi_group_homogeneous_matches_legacy_closely(quad):
    """Two groups with identical knobs are the same algorithm; only the
    batched-slice op scheduling may differ, so allclose not bit-equal."""
    key = jax.random.PRNGKey(11)
    _, c_ref = FedPLT(quad, FedSpec(rho=1.0, n_epochs=3)).run(key, 20)
    _, c_new = build_trainer(
        quad, FedSpec(n_epochs=3, agent_groups="2,3")).run(key, 20)
    np.testing.assert_allclose(np.asarray(c_new), np.asarray(c_ref),
                               rtol=2e-3, atol=1e-9)


def test_single_homogeneous_group_bit_identical_model_scale(quad):
    """Model path: grouped spec with one inheriting group == ungrouped
    spec, same bits (the engine's single-group pass-through)."""
    batch = _quad_batch(quad)

    def run(spec):
        state = runtime.init_state(QuadModel(), jax.random.PRNGKey(0),
                                   spec)
        step = jax.jit(runtime.make_train_step(QuadModel(), spec))
        losses = []
        for i in range(4):
            state, m = step(state, batch, jax.random.PRNGKey(i))
            losses.append(float(m["loss"]))
        return losses, state

    base = dict(n_agents=5, gamma=0.05, n_epochs=3,
                privacy=PrivacySpec(tau=0.05, clip=1.0))
    l_ref, s_ref = run(FedSpec(**base))
    l_grp, s_grp = run(FedSpec(**base, agent_groups="5"))
    assert l_ref == l_grp
    np.testing.assert_array_equal(np.asarray(s_ref.x["x"]),
                                  np.asarray(s_grp.x["x"]))
    np.testing.assert_array_equal(np.asarray(s_ref.z["x"]),
                                  np.asarray(s_grp.z["x"]))


# ---------------------------------------------------------------------------
# Mixed groups end to end
# ---------------------------------------------------------------------------

def test_mixed_gd_agd_groups_dense_converges(quad):
    spec = FedSpec(n_epochs=3, agent_groups="3*gd,2*agd:n_epochs=2")
    state, crit = build_trainer(quad, spec).run(jax.random.PRNGKey(0), 40)
    crit = np.asarray(crit)
    assert np.isfinite(crit).all()
    assert crit[-1] < crit[0] * 1e-3  # still solves the problem


def test_mixed_groups_model_scale_build_trainer(quad):
    """Acceptance: a mixed gd/agd two-group spec runs end-to-end through
    build_trainer with per-group epochs/step sizes, and the consensus
    model still reaches the quadratic optimum."""
    spec = FedSpec(n_agents=5, gamma=0.05, n_epochs=3,
                   agent_groups="3*gd,2*agd:n_epochs=2:gamma=0.04")
    trainer = build_trainer(QuadModel(), spec)
    state, hist = trainer.run(jax.random.PRNGKey(0), 40,
                              lambda i: _quad_batch(quad))
    err = float(jnp.linalg.norm(trainer.consensus(state)["x"]
                                - quad.solve()))
    assert err < 1e-3
    assert np.isfinite(hist[-1]["loss"])


def test_per_group_participation_draws_per_agent(quad):
    """A (nearly-)zero-participation group freezes its agents' states
    while the p=1 group keeps moving."""
    spec = FedSpec(n_epochs=2,
                   agent_groups="2*gd:participation=1e-6,3*gd")
    trainer = build_trainer(quad, spec)
    state, _ = trainer.run(jax.random.PRNGKey(0), 10)
    x = np.asarray(state.x)
    assert np.abs(x[:2]).max() == 0.0      # init was zeros; never active
    assert np.abs(x[2:]).min() > 0.0


def test_engine_rejects_mismatched_group_sizes(quad):
    from repro.fed import engine

    cfg = engine.RoundConfig(n_agents=4)
    x = jnp.zeros((4, 2))
    dummy = engine.SolverGroup(3, lambda x, v, k: (x, None))
    with pytest.raises(ValueError, match="cover 3 agents"):
        engine.round_step(cfg, x, x, x, jax.random.PRNGKey(0), [dummy])


def test_round_config_participation_vector_length_checked():
    from repro.fed import engine

    with pytest.raises(ValueError, match="2 entries for n_agents=3"):
        engine.RoundConfig(n_agents=3, participation=(0.5, 1.0))


# ---------------------------------------------------------------------------
# Spec validation + CLI
# ---------------------------------------------------------------------------

def test_group_sizes_must_partition_agent_axis():
    with pytest.raises(ValueError, match="sizes sum to 3.*n_agents=4"):
        FedSpec(n_agents=4, gamma=0.1, agent_groups="2*gd,1*agd").validate()


def test_group_knobs_validated():
    with pytest.raises(ValueError, match=r"n_epochs.*\(agent group 1\)"):
        FedSpec(n_agents=4, gamma=0.1,
                agent_groups="2*gd,2*gd:n_epochs=0").validate()
    with pytest.raises(ValueError, match=r"participation.*group 0"):
        FedSpec(n_agents=2, gamma=0.1,
                agent_groups="2*gd:participation=1.5").validate()
    with pytest.raises(ValueError, match="agd momentum needs L > mu"):
        FedSpec(n_agents=2, agent_groups="2*agd:gamma=2.0").validate()
    with pytest.raises(ValueError, match="gd-type solver, not 'agd'"):
        FedSpec(n_agents=2, gamma=0.1, agent_groups="2*agd",
                privacy=PrivacySpec(tau=0.1)).validate()


def test_parse_agent_groups_grammar():
    assert parse_agent_groups("2*gd,1*agd:n_epochs=2:gamma=0.5") == (
        AgentGroupSpec(size=2, solver="gd"),
        AgentGroupSpec(size=1, solver="agd", n_epochs=2, gamma=0.5))
    assert parse_agent_groups("3") == (AgentGroupSpec(size=3),)
    with pytest.raises(ValueError, match="integer size"):
        parse_agent_groups("gd*2")
    with pytest.raises(ValueError, match="unknown agent-group option"):
        parse_agent_groups("2*gd:epochs=3")


def test_agent_groups_cli_roundtrip(quad):
    spec = spec_from_args(["--n-agents", "5", "--gamma", "0.05",
                           "--agent-groups", "3*gd,2*agd:n_epochs=1"])
    assert spec.agent_groups == (
        AgentGroupSpec(size=3, solver="gd"),
        AgentGroupSpec(size=2, solver="agd", n_epochs=1))
    spec.validate()
    # the parsed spec drives a real heterogeneous fed train step
    step = jax.jit(runtime.make_train_step(QuadModel(), spec))
    state = runtime.init_state(QuadModel(), jax.random.PRNGKey(0), spec)
    state, m = step(state, _quad_batch(quad), jax.random.PRNGKey(0))
    assert np.isfinite(m["loss"])


# ---------------------------------------------------------------------------
# Per-agent privacy accounting
# ---------------------------------------------------------------------------

def test_per_agent_eps_monotone_in_q(quad):
    """Prop. 4: eps_i shrinks as the local dataset grows (unclipped
    sensitivity convention, where the bound scales as 1/q_i^2)."""
    trainer = build_trainer(quad, FedSpec(
        n_epochs=3, privacy=PrivacySpec(tau=0.1)))
    qs = [10, 20, 40, 80, 160]
    rep = trainer.privacy_report(50, local_dataset_size=qs)
    eps = [a.adp_eps for a in rep.per_agent]
    assert all(a > b for a, b in zip(eps, eps[1:]))
    assert rep.adp_eps == max(eps)
    assert [a.q for a in rep.per_agent] == qs


def test_grouped_spec_reports_per_agent_table(quad):
    """A heterogeneous spec yields a per-agent table even with one
    scalar q: eps_i varies with each group's epoch count, and the
    headline eps is the max."""
    spec = FedSpec(n_agents=5, gamma=0.05, rho=1.0,
                   privacy=PrivacySpec(tau=0.1, clip=1.0),
                   agent_groups="3*gd:n_epochs=1,2*gd:n_epochs=50")
    trainer = build_trainer(QuadModel(), spec)
    rep = trainer.privacy_report(20, local_dataset_size=100)
    assert len(rep.per_agent) == 5
    eps = [a.adp_eps for a in rep.per_agent]
    # more local epochs -> closer to the ceiling -> strictly more eps
    assert eps[4] > eps[0]
    assert rep.adp_eps == pytest.approx(max(eps))
    # ... but never above the K*Ne->inf ceiling (the paper's headline)
    for a in rep.per_agent:
        assert a.adp_eps <= a.eps_ceiling + 1e-9


def test_homogeneous_scalar_report_unchanged(quad):
    """No groups + scalar q keeps the historical scalar report (no
    per-agent table materialized)."""
    trainer = build_trainer(quad, FedSpec(
        n_epochs=5, privacy=PrivacySpec(tau=0.05, clip=1.0)))
    rep = trainer.privacy_report(30, local_dataset_size=100)
    assert rep.per_agent is None
    assert np.isfinite(rep.adp_eps) and rep.adp_eps > 0


def test_per_agent_q_length_mismatch_raises(quad):
    trainer = build_trainer(quad, FedSpec(
        n_epochs=3, privacy=PrivacySpec(tau=0.1)))
    with pytest.raises(ValueError, match="3 entries for n_agents=5"):
        trainer.privacy_report(10, local_dataset_size=[10, 20, 30])
