"""The Fed-PLT front door: FedSpec validation, the spec's round knobs
reaching the engine on both paths, the generated CLI, and the compressor
registry (including the per-agent adaptive compressor at model scale)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.problem import make_quadratic_problem
from repro.core.prox import make_prox
from repro.fed import engine, runtime
from repro.fed.api import (CompressionSpec, FedSpec, PrivacySpec,
                           add_spec_args, build_trainer, privacy_report,
                           spec_from_args)
from repro.fed.compress import (available_compressors, get_compressor,
                                register_compressor)


@pytest.fixture(scope="module")
def quad():
    return make_quadratic_problem(n_agents=5, dim=6, seed=3)


class QuadModel:
    """Minimal model-path object: a bare quadratic loss."""

    def init(self, key):
        return {"x": jnp.zeros(6)}

    def loss_fn(self, params, batch, remat=False):
        x = params["x"]
        return 0.5 * x @ batch["Q"] @ x + batch["c"] @ x


def _quad_batch(quad):
    return {"Q": quad.Q, "c": quad.c}


# ---------------------------------------------------------------------------
# Dense path
# ---------------------------------------------------------------------------

def test_dense_state_t_materialized_only_when_compressed(quad):
    uncompressed = build_trainer(quad, FedSpec(rho=1.0))
    assert uncompressed.init(jax.random.PRNGKey(0)).t is None
    compressed = build_trainer(quad, FedSpec(
        rho=1.0, compression=CompressionSpec(name="topk")))
    assert compressed.init(jax.random.PRNGKey(0)).t is not None
    # ... and running uncompressed still works (scan carries the None)
    state, crit = uncompressed.run(jax.random.PRNGKey(0), 5)
    assert state.t is None and np.isfinite(np.asarray(crit)).all()


def test_dense_trainer_consensus_and_report(quad):
    spec = FedSpec(rho=1.0, n_epochs=5,
                   privacy=PrivacySpec(tau=0.05, clip=1.0))
    trainer = build_trainer(quad, spec)
    state, _ = trainer.run(jax.random.PRNGKey(0), 30)
    np.testing.assert_allclose(trainer.consensus(state),
                               jnp.mean(state.x, axis=0))
    rep = trainer.privacy_report(30, local_dataset_size=100)
    assert np.isfinite(rep.adp_eps) and rep.adp_eps > 0


def test_weight_decay_prox_shared_registry():
    """The model path's weight decay is the core/prox.py registry entry:
    one ProxH convention for both fronts."""
    y = jnp.array([2.0, -4.0])
    # reciprocal-multiply form (not division): see prox_l2sq's docstring
    np.testing.assert_array_equal(
        make_prox("weight_decay", weight=0.3)(y, 0.5),
        y * (1.0 / (1.0 + 0.3 * 0.5)))
    spec = FedSpec(n_agents=2, gamma=0.05, weight_decay=0.3, rho=1.0)
    np.testing.assert_array_equal(
        engine.coordinator_prox({"w": jnp.stack([y, y])},
                                spec.round_config(),
                                spec.resolve_prox_h())["w"],
        y * (1.0 / (1.0 + 0.3 * (1.0 / 2))))


# ---------------------------------------------------------------------------
# Validation: one home, messages survive the dedup
# ---------------------------------------------------------------------------

def test_clip_validation_raised_once_from_spec():
    with pytest.raises(ValueError, match="clip must be positive"):
        FedSpec(n_agents=2, gamma=0.1,
                privacy=PrivacySpec(clip=0.0)).validate()
    # ... and fails fast at the runtime and the accountant
    with pytest.raises(ValueError, match="clip must be positive"):
        runtime.make_train_step(QuadModel(), FedSpec(
            n_agents=2, gamma=0.05, privacy=PrivacySpec(clip=0.0)))
    with pytest.raises(ValueError, match="clip must be positive"):
        privacy_report(FedSpec(n_agents=2, gamma=0.05,
                               privacy=PrivacySpec(tau=0.1, clip=-1.0)),
                       10, 10)


def test_agd_moduli_validation_raised_once_from_spec():
    # gamma=2, rho=1 derives L = 1/2 - 1 < 0 <= mu
    with pytest.raises(ValueError, match="agd momentum needs L > mu"):
        FedSpec(n_agents=2, solver="agd", gamma=2.0).validate()
    with pytest.raises(ValueError, match="agd momentum needs L > mu"):
        runtime.make_train_step(
            QuadModel(), FedSpec(n_agents=2, solver="agd", gamma=2.0))
    with pytest.raises(ValueError, match="agd momentum needs L > mu"):
        FedSpec(n_agents=2, solver="agd", mu=2.0, L=1.0).validate()


def test_agd_with_dp_noise_rejected():
    with pytest.raises(ValueError, match="gd-type solver, not 'agd'"):
        FedSpec(n_agents=2, solver="agd",
                privacy=PrivacySpec(tau=0.1)).validate()


def test_privacy_report_requires_tau():
    with pytest.raises(ValueError, match="requires tau > 0"):
        privacy_report(FedSpec(n_agents=2, gamma=0.05), 10, 10)


def test_unknown_compressor_lists_registry():
    with pytest.raises(ValueError, match="registered:.*topk"):
        FedSpec(n_agents=2,
                compression=CompressionSpec(name="nope")).validate()


def test_unknown_prox_lists_registry():
    with pytest.raises(ValueError, match="unknown prox.*registered:"):
        FedSpec(n_agents=2, prox_h="nope").validate()


def test_compress_energy_threads_to_dense_engine(quad):
    """CompressionSpec.energy must reach the dense round engine, not
    silently reset to the default."""
    spec = FedSpec(rho=1.0, compression=CompressionSpec(
        name="adaptive_topk", ratio=0.25, energy=0.5))
    trainer = build_trainer(quad, spec)
    assert trainer.algo._ecfg.compress_energy == 0.5


@pytest.mark.parametrize("kw,want", [
    (dict(compression=CompressionSpec(name="adaptive_topk", energy=0.5)),
     dict(compress_energy=0.5)),
    (dict(compression=CompressionSpec(name="topk", backend="pallas")),
     dict(compress_backend="pallas")),
    (dict(engine_backend="pallas"), dict(engine_backend="pallas")),
    (dict(guard_increments=True, guard_norm_bound=9.0),
     dict(guard_increments=True, guard_norm_bound=9.0)),
    (dict(aggregator="trimmed_mean", aggregator_param=1.0),
     dict(aggregator="trimmed_mean", aggregator_param=1.0)),
    (dict(state_layout="packed"), dict(state_layout="packed")),
    (dict(async_mode="stale", max_staleness=2),
     dict(staleness=engine.StalenessConfig(mode="stale",
                                           max_staleness=2))),
], ids=["compress-energy", "compress-backend", "engine-backend", "guard",
        "aggregator", "state-layout", "async"])
def test_round_knob_reaches_dense_engine(quad, kw, want):
    """Each round knob goes from the spec to the dense engine through
    ``FedSpec.round_config`` alone."""
    spec = FedSpec(rho=1.0, **kw)
    trainer = build_trainer(quad, spec)
    ecfg = trainer.algo._ecfg
    assert ecfg == dataclasses.replace(
        spec, n_agents=quad.n_agents).round_config()
    for name, value in want.items():
        assert getattr(ecfg, name) == value


@pytest.mark.parametrize("path", ["dense", "model"])
def test_zero_participation_agent_keeps_its_state(quad, path):
    """A per-agent participation tuple on the spec: the agent with rate
    0 never arrives, so its (x, z) stay at the init over rounds while the
    others move."""
    part = (1.0, 1.0, 0.0, 1.0, 1.0)
    if path == "dense":
        trainer = build_trainer(quad, FedSpec(rho=1.0, n_epochs=2,
                                              gamma=0.05,
                                              participation=part))
        state = trainer.init(jax.random.PRNGKey(0))
        x0, z0 = np.asarray(state.x), np.asarray(state.z)
        for _ in range(4):
            state = trainer.step(state)
        x, z = np.asarray(state.x), np.asarray(state.z)
    else:
        trainer = build_trainer(QuadModel(), FedSpec(
            n_agents=5, n_epochs=2, gamma=0.05, participation=part))
        state = trainer.init(jax.random.PRNGKey(0))
        x0 = np.asarray(state.x["x"])
        z0 = np.asarray(state.z["x"])
        for i in range(4):
            state, _ = trainer.step(state, _quad_batch(quad),
                                    jax.random.PRNGKey(i))
        x, z = np.asarray(state.x["x"]), np.asarray(state.z["x"])
    np.testing.assert_array_equal(x[2], x0[2])
    np.testing.assert_array_equal(z[2], z0[2])
    moved = [k for k in range(5) if k != 2]
    assert not np.array_equal(x[moved], x0[moved])
    assert not np.array_equal(z[moved], z0[moved])


@pytest.mark.parametrize("part,match", [
    ((1.0, 0.5), "2 entries for n_agents=3"),
    ((1.0, 1.5, 0.5), r"must be in \[0, 1\]"),
    ((1.0, -0.1, 0.5), r"must be in \[0, 1\]"),
], ids=["wrong-length", "above-one", "negative"])
def test_per_agent_participation_validated(part, match):
    with pytest.raises(ValueError, match=match):
        FedSpec(n_agents=3, gamma=0.05, participation=part).validate()


@pytest.mark.parametrize("part", [
    [1.0, 0.0, 0.5],
    np.array([1.0, 0.0, 0.5]),
    jnp.array([1.0, 0.0, 0.5]),
], ids=["list", "numpy", "jax"])
def test_per_agent_participation_sequence_is_a_tuple(part):
    spec = FedSpec(n_agents=3, gamma=0.05, participation=part).validate()
    assert spec.participation == (1.0, 0.0, 0.5)
    assert spec.round_config().participation == (1.0, 0.0, 0.5)


def test_scalar_participation_string_rejected():
    with pytest.raises(ValueError, match=r"must be in \(0, 1\]"):
        FedSpec(n_agents=3, gamma=0.05, participation="0.5").validate()


def test_dense_trainer_rejects_agent_count_mismatch(quad):
    with pytest.raises(ValueError, match="spec.n_agents="):
        build_trainer(quad, FedSpec(n_agents=quad.n_agents + 1,
                                    gamma=0.05))


# ---------------------------------------------------------------------------
# Generated CLI round-trip
# ---------------------------------------------------------------------------

def test_spec_from_args_roundtrip(quad):
    spec = spec_from_args([
        "--n-agents", "5", "--rho", "0.5", "--gamma", "0.1",
        "--n-epochs", "2", "--participation", "0.8", "--tau", "0.01",
        "--clip", "1.0", "--weight-decay", "0.2",
        "--compression", "topk", "--compress-ratio", "0.5"])
    assert spec == FedSpec(
        n_agents=5, rho=0.5, gamma=0.1, n_epochs=2, participation=0.8,
        weight_decay=0.2, privacy=PrivacySpec(tau=0.01, clip=1.0),
        compression=CompressionSpec(name="topk", ratio=0.5))
    # the parsed spec drives a real fed train step
    spec.validate()
    step = jax.jit(runtime.make_train_step(QuadModel(), spec))
    state = runtime.init_state(QuadModel(), jax.random.PRNGKey(0), spec)
    state, m = step(state, _quad_batch(quad), jax.random.PRNGKey(0))
    assert np.isfinite(m["loss"])
    assert state.t is not None   # compressed exchange materializes t


def test_cli_defaults_match_spec_field_defaults():
    """A no-flag CLI run and FedSpec() must denote the SAME training
    trajectory: every exposed field whose dataclass default is concrete
    must generate a flag with exactly that default (the n_epochs 5-vs-3
    drift trained silently different models).  None-defaulted fields
    (n_agents, gamma) are the one sanctioned exception: the CLI has to
    pick a concrete value where the spec derives one."""
    from repro.fed import api

    classes = {"spec": FedSpec, "privacy": PrivacySpec,
               "compression": CompressionSpec}
    for owner, name, flag, _, kwargs in api._cli_entries():
        fields = {f.name: f for f in dataclasses.fields(classes[owner])}
        default = fields[name].default
        if default is None or default is dataclasses.MISSING:
            continue
        assert kwargs["default"] == default, (
            f"{flag} defaults to {kwargs['default']!r} but "
            f"{classes[owner].__name__}.{name} defaults to {default!r}")
    # the drift this guards against, end to end:
    assert spec_from_args([]).n_epochs == FedSpec().n_epochs


def test_cli_agd_with_tau_fails_fast():
    spec = spec_from_args(["--tau", "0.3", "--solver", "agd"])
    with pytest.raises(ValueError, match="gd-type solver, not 'agd'"):
        spec.validate()


def test_cli_flags_track_registered_compressors():
    """A compressor registered at runtime is immediately a legal
    --compression choice: the CLI is generated, not hand-mirrored."""
    import argparse

    @register_compressor("cli_probe_compressor")
    def probe(dz, cfg):   # pragma: no cover - never called
        return dz

    ap = argparse.ArgumentParser()
    add_spec_args(ap)
    spec = spec_from_args(
        ap.parse_args(["--compression", "cli_probe_compressor"]))
    assert spec.compression.name == "cli_probe_compressor"


# ---------------------------------------------------------------------------
# Compressor registry + the per-agent adaptive compressor
# ---------------------------------------------------------------------------

def test_registry_has_builtins():
    names = available_compressors()
    for expected in ("none", "topk", "int8", "adaptive_topk"):
        assert expected in names


def test_registered_compressor_usable_by_name(quad):
    """Extensibility proof at the dense front end: a compressor
    registered here runs through FedSpec without engine changes."""
    calls = []

    @register_compressor("mean_sign_test")
    def mean_sign(dz, cfg):
        calls.append(1)
        scale = jnp.mean(jnp.abs(dz), axis=-1, keepdims=True)
        return jnp.sign(dz) * scale

    trainer = build_trainer(quad, FedSpec(
        rho=1.0, damping=0.5,
        compression=CompressionSpec(name="mean_sign_test")))
    state, crit = trainer.run(jax.random.PRNGKey(0), 10)
    assert calls, "registered compressor was never dispatched"
    assert np.isfinite(np.asarray(crit)).all()
    assert state.t is not None


def test_topk_transmits_exactly_k_on_ties():
    """Magnitude ties must not inflate the uplink: a threshold-select
    keeps EVERY tied coordinate (an all-constant increment would
    transmit all m entries at a k/m bandwidth budget)."""
    cfg = type("C", (), {"compress_ratio": 0.25,
                         "compress_energy": 0.95})()
    m = 16
    k = int(0.25 * m)
    rows = jnp.stack([jnp.ones(m),                 # all-tied constants
                      jnp.zeros(m),                # all-zero increment
                      -3.0 * jnp.ones(m)])         # tied negatives
    out = get_compressor("topk")(rows, cfg)
    kept = np.asarray((out != 0).sum(axis=-1))
    assert kept[0] == k
    assert kept[1] == 0                             # zeros transmit zeros
    assert kept[2] == k
    # surviving entries are the original values, untouched
    np.testing.assert_array_equal(np.asarray(out[0][out[0] != 0]),
                                  np.ones(k))


def test_adaptive_topk_transmits_exactly_k_on_ties():
    """Same tie discipline for the adaptive compressor: an all-constant
    row at a 0.5 energy target needs ceil(m/2) coordinates -- the old
    threshold-select transmitted all m of them."""
    cfg = type("C", (), {"compress_ratio": 1.0 / 16.0,
                         "compress_energy": 0.5})()
    m = 16
    out = get_compressor("adaptive_topk")(jnp.stack([jnp.ones(m)]), cfg)
    kept = int(np.asarray((out != 0).sum(axis=-1))[0])
    assert kept == 8   # smallest prefix with >= 50% energy, not m


def test_topk_no_ties_keeps_top_magnitudes():
    cfg = type("C", (), {"compress_ratio": 0.5, "compress_energy": 0.95})()
    row = jnp.array([[0.1, -5.0, 2.0, 0.01, 3.0, -0.2]])
    out = get_compressor("topk")(row, cfg)
    np.testing.assert_array_equal(
        np.asarray(out[0]), [0.0, -5.0, 2.0, 0.0, 3.0, 0.0])


def test_adaptive_topk_ratio_is_per_agent():
    """A concentrated increment keeps fewer coordinates than a diffuse
    one -- the ratio adapts per agent instead of one global k."""
    cfg = type("C", (), {"compress_ratio": 1.0 / 16.0,
                         "compress_energy": 0.9})()
    concentrated = jnp.zeros(64).at[7].set(10.0).at[40].set(5.0)
    diffuse = jnp.ones(64)
    out = get_compressor("adaptive_topk")(
        jnp.stack([concentrated, diffuse]), cfg)
    kept = (out != 0).sum(axis=-1)
    assert int(kept[0]) <= 4            # hot coords only
    assert int(kept[1]) >= 32           # diffuse energy needs many
    # transmitted values are the original entries (no rescaling)
    np.testing.assert_array_equal(out[0][7], concentrated[7])


def test_adaptive_topk_at_model_scale_through_fedspec(quad):
    """Acceptance: the per-agent heterogeneous scenario the redesign
    enables -- an adaptive-ratio compressor from the registry, driven at
    model scale purely through FedSpec."""
    spec = FedSpec(n_agents=5, gamma=0.05, n_epochs=3, damping=0.5,
                   compression=CompressionSpec(name="adaptive_topk",
                                               ratio=0.25, energy=0.95))
    trainer = build_trainer(QuadModel(), spec)
    batch = _quad_batch(quad)
    state, _ = trainer.run(jax.random.PRNGKey(0), 50, lambda i: batch)
    # the consensus model reaches the closed-form optimum of sum_i f_i
    # despite every uplink being adaptively sparsified (error feedback)
    err = float(jnp.linalg.norm(trainer.consensus(state)["x"]
                                - quad.solve()))
    assert err < 1e-3
    assert state.t is not None
    # error feedback: the coordinator copy lags z under sparsification
    lag = jax.tree_util.tree_reduce(
        lambda a, l: a + float(jnp.sum(jnp.abs(l))),
        jax.tree_util.tree_map(lambda z, t: z - t, state.z, state.t), 0.0)
    assert lag > 0


def test_model_trainer_requires_resolved_spec():
    with pytest.raises(ValueError, match="n_agents"):
        build_trainer(QuadModel(), FedSpec(gamma=0.1))
    with pytest.raises(ValueError, match="gamma"):
        build_trainer(QuadModel(), FedSpec(n_agents=2))
    with pytest.raises(TypeError, match="cannot build a trainer"):
        build_trainer(object(), FedSpec(n_agents=2, gamma=0.1))


def test_spec_is_hashable_and_frozen():
    spec = FedSpec(n_agents=2)
    hash(spec)
    with pytest.raises(dataclasses.FrozenInstanceError):
        spec.rho = 2.0
