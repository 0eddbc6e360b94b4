"""Fused uplink-compression kernel suite: bit-exactness vs the ref.py
oracles AND the registry XLA compressors (tie-heavy / ragged /
non-block-aligned inputs, interpret mode), packed-path == per-leaf-path
identity, compressor invariants across the whole registry, and the
backend knob end to end."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.problem import make_logreg_problem
from repro.fed.api import CompressionSpec, FedSpec, build_trainer, spec_from_args
from repro.fed.compress import (PALLAS_COMPRESSORS, available_compressors,
                                compress_increment, compress_rows,
                                get_compressor, pack_leaves, unpack_leaves)
from repro.fed.engine import RoundConfig
from repro.kernels.compress import ops, ref


# tie-heavy / non-aligned row battery: every case is (N, m) plus a
# mutation planting adversarial structure
def _tie_heavy(n, m, seed=0):
    x = jax.random.normal(jax.random.PRNGKey(seed), (n, m))
    x = x.at[0].set(1.0)                   # all-tied row
    x = x.at[1 % n].set(0.0)               # all-zero row
    x = x.at[2 % n, ::3].set(-2.5)         # repeated magnitude, mixed sign
    return x


def _cfg(name, ratio=0.25, energy=0.9, backend="xla"):
    return RoundConfig(n_agents=1, compression=name,
                       compress_ratio=ratio, compress_energy=energy,
                       compress_backend=backend)


# ---------------------------------------------------------------------------
# Kernel vs ref.py vs registry XLA compressors (bit-exact)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,m", [(3, 7), (5, 300), (8, 128), (2, 1000),
                                 (11, 33)])
@pytest.mark.parametrize("mode", ["topk", "adaptive_topk"])
def test_rank_select_matches_ref_and_registry(n, m, mode):
    x = _tie_heavy(n, m, seed=m)
    out = ops.rank_select(x, mode=mode, ratio=0.25, energy=0.9)
    oracle = ref.rank_select_ref(x, mode=mode, ratio=0.25, energy=0.9)
    registry = get_compressor(mode)(x, _cfg(mode))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(oracle))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(registry))


@pytest.mark.parametrize("n,m", [(3, 7), (5, 300), (2, 1000)])
def test_int8_matches_ref_and_registry(n, m):
    """Bit-exact under jit on both sides -- the engine always runs the
    compressors jitted, and eager XLA compiles the dequant scale's
    division one ULP differently on some shapes (fusion-dependent
    codegen), so jit-vs-eager is not the parity that matters."""
    x = _tie_heavy(n, m, seed=m)
    out = ops.int8_quantize(x)
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(jax.jit(ref.int8_ref)(x)))
    registry = jax.jit(lambda v: get_compressor("int8")(v, _cfg("int8")))
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(registry(x)))


@pytest.mark.parametrize("segments", [
    ((0, 20), (20, 277), (277, 300)),      # ragged, non-block-aligned
    ((0, 3), (3, 4), (4, 300)),            # tiny segments
    ((0, 150), (160, 300)),                # gap (padding columns)
])
@pytest.mark.parametrize("mode", ["topk", "adaptive_topk"])
def test_segmented_rank_select_matches_ref(segments, mode):
    x = _tie_heavy(5, 300)
    out = ops.rank_select(x, segments=segments, mode=mode, ratio=0.25,
                          energy=0.9)
    oracle = ref.rank_select_ref(x, segments, mode=mode, ratio=0.25,
                                 energy=0.9)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(oracle))


def test_segmented_int8_matches_ref():
    x = _tie_heavy(5, 300)
    segments = ((0, 20), (20, 277), (277, 300))
    oracle = jax.jit(lambda v: ref.int8_ref(v, segments))(x)
    np.testing.assert_array_equal(
        np.asarray(ops.int8_quantize(x, segments=segments)),
        np.asarray(oracle))


def test_segment_ranks_match_ref():
    x = _tie_heavy(4, 96)
    segments = ((0, 40), (40, 96))
    got = ops.segment_ranks(x, segments=segments)
    oracle = ref.segment_ranks_ref(x, segments)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(oracle))


@pytest.mark.parametrize("n,m,segments", [
    (3, 64, None), (4, 37, ((0, 10), (12, 37))), (2, 128, ((0, 128),)),
    (9, 5, None),
])
def test_bitonic_sort_impl_matches_xla(n, m, segments):
    """The explicit compare-exchange network (the Mosaic-lowerable form)
    realizes the identical permutation as the in-kernel lax.sort: the
    composite key is unique, so both equal the stable order."""
    x = _tie_heavy(n, m, seed=n * m)
    covered = ((0, m),) if segments is None else segments
    a = ops.segment_ranks(x, segments=segments, sort_impl="xla")
    b = ops.segment_ranks(x, segments=segments, sort_impl="bitonic")
    for s0, s1 in covered:
        np.testing.assert_array_equal(np.asarray(a[:, s0:s1]),
                                      np.asarray(b[:, s0:s1]))
    for mode in ("topk", "adaptive_topk"):
        sa = ops.rank_select(x, segments=segments, mode=mode, ratio=0.3,
                             energy=0.8, sort_impl="xla")
        sb = ops.rank_select(x, segments=segments, mode=mode, ratio=0.3,
                             energy=0.8, sort_impl="bitonic")
        np.testing.assert_array_equal(np.asarray(sa), np.asarray(sb))


def test_bf16_rank_select_matches_registry():
    x = _tie_heavy(4, 200).astype(jnp.bfloat16)
    for mode in ("topk", "adaptive_topk"):
        out = ops.rank_select(x, mode=mode, ratio=0.25, energy=0.9)
        registry = get_compressor(mode)(x, _cfg(mode))
        assert out.dtype == jnp.bfloat16
        np.testing.assert_array_equal(np.asarray(out, np.float32),
                                      np.asarray(registry, np.float32))


# ---------------------------------------------------------------------------
# Leaf packing + the packed pallas path == the per-leaf XLA path
# ---------------------------------------------------------------------------

def _ragged_tree(n=5, seed=3):
    key = jax.random.PRNGKey(seed)
    shapes = {"emb": (n, 37, 5), "w": {"a": (n, 130), "b": (n, 3)},
              "bias": (n, 1)}
    return jax.tree_util.tree_map(
        lambda s: jax.random.normal(jax.random.fold_in(key, s[-1]), s),
        shapes, is_leaf=lambda s: isinstance(s, tuple))


def test_pack_unpack_roundtrip():
    tree = _ragged_tree()
    buf, meta = pack_leaves(tree)
    assert buf.shape[1] % 128 == 0         # lane-aligned packed width
    back = unpack_leaves(buf, meta)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a),
                                                   np.asarray(b)),
        tree, back)


@pytest.mark.parametrize("name", sorted(PALLAS_COMPRESSORS))
def test_packed_path_bit_identical_to_per_leaf(name):
    """One packed kernel launch == the historical per-leaf registry
    dispatch, bitwise, on a ragged multi-leaf pytree (incl. an all-tied
    leaf)."""
    tree = _ragged_tree()
    tree["w"]["a"] = jnp.ones_like(tree["w"]["a"])   # all-tied leaf
    # jit both, as the engine does (eager XLA codegen differs by a ULP
    # in the int8 scale on some shapes; see test_int8_matches_* above)
    per_leaf = jax.jit(
        lambda t: compress_increment(t, _cfg(name, backend="xla")))(tree)
    packed = jax.jit(
        lambda t: compress_increment(t, _cfg(name, backend="pallas")))(tree)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a),
                                                   np.asarray(b)),
        per_leaf, packed)


def test_packed_path_under_jit():
    tree = _ragged_tree()
    cfg = _cfg("adaptive_topk", backend="pallas")
    eager = compress_increment(tree, cfg)
    jitted = jax.jit(lambda t: compress_increment(t, cfg))(tree)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a),
                                                   np.asarray(b)),
        eager, jitted)


def test_non_accelerated_compressor_falls_back():
    """backend="pallas" with a compressor that has no kernel silently
    uses the per-leaf XLA path (documented fallback)."""
    tree = _ragged_tree()
    out_x = compress_increment(tree, _cfg("none", backend="xla"))
    out_p = compress_increment(tree, _cfg("none", backend="pallas"))
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a),
                                                   np.asarray(b)),
        out_x, out_p)


# ---------------------------------------------------------------------------
# Compressor invariants across the whole registry, both backends
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", sorted(available_compressors()))
@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_registry_preserves_shape_and_dtype(name, backend):
    x = _tie_heavy(6, 97)
    out = compress_rows(x, _cfg(name, backend=backend))
    assert out.shape == x.shape
    assert out.dtype == x.dtype


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_exact_k_on_all_tied_rows(backend):
    """Adversarial all-tied input: EXACTLY k values survive per row --
    the tie discipline a threshold select would blow (it would transmit
    the whole row)."""
    m = 64
    x = jnp.ones((4, m))
    out = compress_rows(x, _cfg("topk", ratio=0.25, backend=backend))
    np.testing.assert_array_equal(
        np.asarray(jnp.sum(out != 0.0, axis=-1)), np.full(4, m // 4))
    out = compress_rows(
        x, _cfg("adaptive_topk", ratio=1.0 / 16.0, energy=0.5,
                backend=backend))
    # flat spectrum: the smallest prefix holding >= 50% energy is m/2
    np.testing.assert_array_equal(
        np.asarray(jnp.sum(out != 0.0, axis=-1)), np.full(4, m // 2))


# ---------------------------------------------------------------------------
# The backend knob end to end
# ---------------------------------------------------------------------------

def test_round_config_rejects_unknown_backend():
    with pytest.raises(ValueError, match="backend"):
        RoundConfig(n_agents=2, compress_backend="nope")


def test_spec_validates_backend():
    with pytest.raises(ValueError, match="backend"):
        FedSpec(n_agents=2, compression=CompressionSpec(
            backend="nope")).validate()


def test_cli_backend_roundtrip():
    spec = spec_from_args(["--compression", "adaptive_topk",
                           "--compress-backend", "pallas"])
    assert spec.compression.backend == "pallas"
    assert spec.validate().round_config().compress_backend == "pallas"


@pytest.mark.parametrize("name", sorted(PALLAS_COMPRESSORS))
def test_dense_trainer_backend_bit_identity(name):
    """Full Fed-PLT trajectories are bit-identical under either
    backend: the fused kernels change the schedule, not the numbers."""
    prob = make_logreg_problem(n_agents=6, q=30, dim=20, seed=0)
    runs = {}
    for backend in ("xla", "pallas"):
        spec = FedSpec(rho=1.0, n_epochs=2, compression=CompressionSpec(
            name=name, ratio=0.3, energy=0.9, backend=backend))
        state, crit = build_trainer(prob, spec).run(
            jax.random.PRNGKey(0), 6)
        runs[backend] = (np.asarray(state.x), np.asarray(state.z),
                         np.asarray(state.t), np.asarray(crit))
    for a, b in zip(runs["xla"], runs["pallas"]):
        np.testing.assert_array_equal(a, b)


def test_backend_threads_to_dense_engine():
    prob = make_logreg_problem(n_agents=4, q=20, dim=10, seed=0)
    spec = FedSpec(rho=1.0, compression=CompressionSpec(
        name="topk", backend="pallas"))
    trainer = build_trainer(prob, spec)
    assert trainer.algo._ecfg.compress_backend == "pallas"
    # legacy shim round-trips the knob too
    from repro.core.fedplt import FedPLTConfig
    cfg = FedPLTConfig(compression="topk", compress_backend="pallas")
    assert cfg.to_spec().compression.backend == "pallas"


def test_mixed_dtype_tree_falls_back_per_leaf():
    n = 4
    tree = {"a": jnp.ones((n, 40)),
            "b": jnp.ones((n, 24), jnp.bfloat16)}
    out = compress_increment(tree, _cfg("topk", backend="pallas"))
    per_leaf = compress_increment(tree, _cfg("topk", backend="xla"))
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(
            np.asarray(a, np.float32), np.asarray(b, np.float32)),
        out, per_leaf)
    assert out["b"].dtype == jnp.bfloat16


def test_compress_bench_perf_payload(monkeypatch):
    """The --json emitter's per-case payload stays machine-readable and
    structural: run the kernel sweep on one tiny case and check the
    committed-baseline schema -- shapes, whether a kernel serves the
    width, and bit-identity with the XLA path; no wall time."""
    from benchmarks import compress_bench as cb

    # the engine-scale case the acceptance tracks is in the real sweep
    assert "engine_gemma2r" in {c[0] for c in cb._KERNEL_CASES}
    monkeypatch.setattr(cb, "_KERNEL_CASES", (("tiny", 2, (64, 30)),))
    rows, payload = cb._kernels()
    assert len(rows) == len(payload) == len(PALLAS_COMPRESSORS)
    assert {p["compressor"] for p in payload} == set(PALLAS_COMPRESSORS)
    for p in payload:
        assert p["kind"] == "kernel" and p["case"] == "tiny"
        assert p["m_total"] == 94 and p["n_leaves"] == 2
        assert p["kernel"] is True and p["bitwise_equal"] is True
        assert not any(k.startswith("ms_") or "speedup" in k for k in p)
