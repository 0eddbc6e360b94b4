"""Pure-jnp oracles for the fused uplink-compression kernels.

Independent implementations (per-segment ``lax.top_k`` / double-sort /
plain quantize), mirroring the :mod:`repro.fed.compress` registry
compressors applied segment-by-segment -- the kernels must bit-match
these on tie-heavy, ragged, and non-block-aligned inputs.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.compress.kernel import prefix_sum


def _segments_of(x, segments):
    return (((0, x.shape[1]),) if segments is None
            else tuple((int(a), int(b)) for a, b in segments))


def segment_ranks_ref(x, segments=None):
    """Stable descending-|x| ranks within each segment (int32)."""
    out = jnp.zeros(x.shape, jnp.int32)
    for s0, s1 in _segments_of(x, segments):
        order = jnp.argsort(-jnp.abs(x[:, s0:s1]), axis=-1, stable=True)
        m = s1 - s0
        rank = jnp.zeros((x.shape[0], m), jnp.int32).at[
            jnp.arange(x.shape[0])[:, None], order].set(
            jnp.arange(m, dtype=jnp.int32)[None, :])
        out = out.at[:, s0:s1].set(rank)
    return out


def rank_select_ref(x, segments=None, mode="topk", ratio=0.25,
                    energy=0.95):
    """Per-segment exact-k magnitude selection (ties by position)."""
    out = jnp.zeros_like(x)
    for s0, s1 in _segments_of(x, segments):
        seg = x[:, s0:s1]
        m = s1 - s0
        k_floor = max(1, int(ratio * m))
        if mode == "topk":
            def topk_row(row):
                _, idx = jax.lax.top_k(jnp.abs(row), k_floor)
                return jnp.zeros_like(row).at[idx].set(row[idx])

            res = jax.vmap(topk_row)(seg)
        elif mode == "adaptive_topk":
            def adaptive_row(row):
                e = jnp.square(jnp.abs(row))
                desc = jnp.sort(e)[::-1]
                cum = prefix_sum(desc)
                total = jnp.maximum(cum[-1], 1e-30)
                k = jnp.sum(cum < energy * total) + 1
                k = jnp.clip(k, k_floor, m)
                order = jnp.argsort(-jnp.abs(row))
                rank = jnp.zeros(m, jnp.int32).at[order].set(
                    jnp.arange(m, dtype=jnp.int32))
                return jnp.where(rank < k, row, 0.0)

            res = jax.vmap(adaptive_row)(seg)
        else:
            raise ValueError(f"unknown rank-select mode {mode!r}")
        out = out.at[:, s0:s1].set(res)
    return out


def int8_ref(x, segments=None):
    """Per-(agent, segment) symmetric int8 quantize-dequantize."""
    out = jnp.zeros_like(x)
    for s0, s1 in _segments_of(x, segments):
        seg = x[:, s0:s1]
        scale = jnp.max(jnp.abs(seg), axis=-1, keepdims=True) / 127.0
        scale = jnp.maximum(scale, 1e-12)
        q = jnp.round(seg / scale).astype(jnp.int8)
        out = out.at[:, s0:s1].set(q.astype(x.dtype) * scale)
    return out
