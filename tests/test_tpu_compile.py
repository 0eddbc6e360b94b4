"""The main path's Pallas kernels compile for a TPU v5e at real widths.

Nothing here runs on a chip: each test compiles for a v5e that is
described, not attached, and checks that the compiled program holds the
Mosaic kernel (``tpu_custom_call``).  A kernel the chip's compiler
refuses (a primitive Mosaic cannot lower, a block over the VMEM limit)
fails here, in seconds, instead of on the chip.

The topology is described inside a fixture, never at import: only one
process may load the TPU compiler library, and a test worker that
described it while collecting would keep the others from it.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.fed import telemetry
from repro.fed.compress import packed_meta
from repro.kernels.compress import ops as compress_ops
from repro.kernels.fedplt_update.ops import fedplt_update
from repro.kernels.robust_agg.ops import robust_aggregate
from repro.kernels.round_edge import ops as edge_ops


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 -- any failure means "no TPU lib"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep these out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compiled_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def _phase_b_width():
    """chip_smoke phase b's packed width: phi4-mini-3.8b at published
    widths, 2 layers, a quarter of the vocabulary, 2 agents."""
    from repro.configs import get_config
    from repro.models.model import build_model

    full = get_config("phi4-mini-3.8b")
    cfg = dataclasses.replace(full, n_layers=2, vocab=full.vocab // 4)
    shapes = jax.eval_shape(build_model(cfg).init, jax.random.PRNGKey(0))
    stacked = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct((2,) + s.shape, s.dtype), shapes)
    return packed_meta(stacked).width


@pytest.mark.parametrize("edge", ["uplink", "downlink"])
def test_round_edges_compile_at_model_width(one_chip, edge):
    width = _phase_b_width()
    assert width > 300_000_000
    buf = jax.ShapeDtypeStruct((2, width), jnp.bfloat16, sharding=one_chip)
    if edge == "uplink":
        text = _compiled_text(
            lambda z: edge_ops.round_uplink(z, interpret=False), buf)
    else:
        u = jax.ShapeDtypeStruct((2,), jnp.float32, sharding=one_chip)
        text = _compiled_text(
            lambda x, w, z, u: edge_ops.round_downlink(
                x, w, z, u, interpret=False), buf, buf, buf, u)
    assert "tpu_custom_call" in text


def test_fedplt_update_compiles_on_a_model_leaf(one_chip):
    leaf = jax.ShapeDtypeStruct((3072, 8192), jnp.bfloat16,
                                sharding=one_chip)
    text = _compiled_text(
        lambda w, g, v: fedplt_update(w, g, v, gamma=0.5, inv_rho=1.0,
                                      interpret=False), leaf, leaf, leaf)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("kernel,width", [("int8", 1 << 16),
                                          ("segment_ranks", 1 << 14)])
def test_compress_kernels_compile_at_widest_auto_width(one_chip, kernel,
                                                       width):
    """The widest block ``auto`` hands each compress kernel compiles;
    one column more and ``auto`` takes XLA instead."""
    name = "int8" if kernel == "int8" else "topk"
    assert compress_ops.fits_vmem(name, width)
    assert not compress_ops.fits_vmem(name, width + 128)
    buf = jax.ShapeDtypeStruct((8, width), jnp.float32, sharding=one_chip)
    if kernel == "int8":
        fn = lambda x: compress_ops.int8_quantize(x, interpret=False)  # noqa: E731
    else:
        fn = lambda x: compress_ops.segment_ranks(x, interpret=False)  # noqa: E731
    assert "tpu_custom_call" in _compiled_text(fn, buf)


def test_robust_trimmed_mean_compiles(one_chip):
    buf = jax.ShapeDtypeStruct((8, 65536), jnp.float32, sharding=one_chip)
    text = _compiled_text(
        lambda x: robust_aggregate(x, stat="trimmed_mean", trim=1,
                                   interpret=False), buf)
    assert "tpu_custom_call" in text


def test_sharded_uplink_compiles_on_four_chips(topo):
    mesh = Mesh(np.asarray(topo.devices).reshape(4, 1), ("agent", "model"))
    buf = jax.ShapeDtypeStruct((4, 1 << 20), jnp.bfloat16,
                               sharding=NamedSharding(mesh, P("agent")))
    text = _compiled_text(
        lambda z: edge_ops.round_uplink_sharded(
            z, mesh=mesh, n_total=4, interpret=False), buf)
    assert "tpu_custom_call" in text
    assert "all-reduce" in text



def test_local_heavy_round_aliases_its_state_without_remat(one_chip):
    """The model round at the phi4mini.local-heavy shapes (published
    widths, 2 layers, vocabulary 25,008, float32 state, 2 agents x 4 x
    512 tokens, N_e 5): the new state aliases the donated one, so the
    program fits the chip's 15.75 GiB without XLA cloning a single op
    to recompute it (undonated, the round compiles with 16 clones)."""
    from repro.configs import get_config
    from repro.fed.api import FedSpec, build_trainer
    from repro.models.model import build_model

    full = get_config("phi4-mini-3.8b")
    cfg = dataclasses.replace(full, n_layers=2, vocab=25008,
                              dtype="float32")
    tr = build_trainer(build_model(cfg),
                       FedSpec(n_agents=2, n_epochs=5, gamma=0.05))
    place = lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,  # noqa: E731
                                           sharding=one_chip)
    state = jax.tree_util.tree_map(
        place, jax.eval_shape(tr.init, jax.random.PRNGKey(0)))
    tokens = jax.ShapeDtypeStruct((2, 4, 512), jnp.int32, sharding=one_chip)
    key = place(jax.eval_shape(lambda: jax.random.PRNGKey(0)))
    mem = telemetry.compiled_memory(
        tr.lower(state, {"tokens": tokens, "labels": tokens},
                 key).compile())
    # the state's bytes as the chip lays them out (a scalar takes 512)
    state_bytes = jax.jit(lambda s: s).lower(state).compile() \
        .memory_analysis().argument_size_in_bytes
    assert state_bytes > 4 * 2**30
    assert mem["remat_clones"] == 0
    assert mem["alias_bytes"] == state_bytes
    assert mem["peak_bytes"] < 15.75 * 2**30
