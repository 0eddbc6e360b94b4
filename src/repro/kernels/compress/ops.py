"""Public fused uplink-compression ops.

Jitted wrappers over the :mod:`repro.kernels.compress.kernel` Pallas
kernels: pad the agent axis to the row block, dispatch, slice back.
``segments`` is the static tuple of ``(start, stop)`` column ranges (one
per packed pytree leaf; ``None`` means the whole buffer is one segment,
the per-leaf case).  Columns outside every segment are padding and come
back zero.

``interpret`` resolves via :data:`repro.kernels.ON_TPU` like the other
kernel suites; ``sort_impl`` defaults to the in-kernel ``lax.sort`` when
interpreting (this CPU container) and to the explicit bitonic network on
TPU, where ``lax.sort`` has no Mosaic lowering -- both produce the same
permutation (unique composite keys), asserted in the kernel tests.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import ON_TPU
from repro.kernels.compress.kernel import (BLOCK_AGENTS, _pow2_pad, int8_2d,
                                           rank_select_2d, segment_ranks_2d)

# Scoped VMEM a Mosaic kernel may use by default on a TPU v5e.
VMEM_LIMIT_BYTES = 16 * 2**20

# VMEM bytes each element of a kernel's (rows, width) block keeps live,
# keyed by compressor: input and output blocks (double-buffered) plus
# in-kernel temporaries -- for the rank select (topk, adaptive_topk, and
# the sort segment_ranks shares) the sort keys over the power-of-two
# padded row.  Calibrated against the v5e compiler: the widest block
# each allows compiles (tests/test_tpu_compile.py), and at the next
# power of two the compiler runs out of VMEM.
_VMEM_BYTES_PER_ELEMENT = {"int8": 32, "topk": 128, "adaptive_topk": 128}

_SUBLANES = 8      # a block's rows occupy at least one (8, 128) tile


def block_vmem_bytes(compressor, width):
    """Estimated VMEM working set of one grid program of
    ``compressor``'s kernel on a buffer ``width`` columns wide.  A block
    holds ``min(8, N)`` whole rows, padded to the 8-row tile, so the
    estimate does not depend on the agent count."""
    cols = (-(-width // 128) * 128 if compressor == "int8"
            else _pow2_pad(width)[0])
    return _SUBLANES * cols * _VMEM_BYTES_PER_ELEMENT[compressor]


def fits_vmem(compressor, width):
    """Whether ``compressor``'s whole-row kernel block fits the chip's
    fast memory at ``width`` columns (a packed model row never does)."""
    return block_vmem_bytes(compressor, width) <= VMEM_LIMIT_BYTES


def _resolve(x, segments, interpret, sort_impl):
    if x.ndim != 2:
        raise ValueError(f"compression ops take (N, M) buffers, got "
                         f"shape {x.shape}")
    if x.dtype == jnp.float64:
        raise ValueError("float64 buffers are not supported (the sort "
                         "key is the float32 magnitude bit pattern)")
    if interpret is None:
        interpret = not ON_TPU
    if sort_impl is None:
        sort_impl = "xla" if interpret else "bitonic"
    if segments is None:
        segments = ((0, x.shape[1]),)
    return tuple(tuple(s) for s in segments), interpret, sort_impl


def _pad_rows(x, block_agents):
    n = x.shape[0]
    bm = min(block_agents, n)
    pad = -n % bm
    if pad:
        x = jnp.concatenate(
            [x, jnp.zeros((pad, x.shape[1]), x.dtype)], axis=0)
    return x, n


@partial(jax.jit, static_argnames=("segments", "mode", "ratio", "energy",
                                   "interpret", "sort_impl",
                                   "block_agents"))
def rank_select(x, *, segments=None, mode="topk", ratio=0.25,
                energy=0.95, interpret=None, sort_impl=None,
                block_agents=BLOCK_AGENTS):
    """Fused magnitude-rank top-k selection.

    ``mode="topk"`` keeps the static ``max(1, int(ratio * m))`` largest-
    magnitude entries per (agent, segment); ``mode="adaptive_topk"``
    keeps the smallest per-agent k_i capturing an ``energy`` fraction of
    the segment's l2 energy (floored at the static k).  Ties break by
    position -- exactly k entries survive -- matching the registry
    compressors bit-for-bit.
    """
    segments, interpret, sort_impl = _resolve(x, segments, interpret,
                                              sort_impl)
    xp, n = _pad_rows(x, block_agents)
    out = rank_select_2d(xp, segments=segments, mode=mode, ratio=ratio,
                         energy=energy, sort_impl=sort_impl,
                         block_agents=block_agents, interpret=interpret)
    return out[:n]


@partial(jax.jit, static_argnames=("segments", "interpret", "sort_impl",
                                   "block_agents"))
def segment_ranks(x, *, segments=None, interpret=None, sort_impl=None,
                  block_agents=BLOCK_AGENTS):
    """Stable descending-|x| rank of every entry within its segment.

    An introspection/test surface (the compressors use the counting
    form and never need the rank array); the permutation is inverted by
    a second sort, so it lowers wherever the compressors do."""
    segments, interpret, sort_impl = _resolve(x, segments, interpret,
                                              sort_impl)
    xp, n = _pad_rows(x, block_agents)
    out = segment_ranks_2d(xp, segments=segments, sort_impl=sort_impl,
                           block_agents=block_agents, interpret=interpret)
    return out[:n]


@partial(jax.jit, static_argnames=("segments", "interpret",
                                   "block_agents"))
def int8_quantize(x, *, segments=None, interpret=None,
                  block_agents=BLOCK_AGENTS):
    """Fused symmetric int8 quantize-dequantize, one scale per
    (agent, segment)."""
    segments, interpret, _ = _resolve(x, segments, interpret, "xla")
    xp, n = _pad_rows(x, block_agents)
    out = int8_2d(xp, segments=segments, block_agents=block_agents,
                  interpret=interpret)
    return out[:n]
