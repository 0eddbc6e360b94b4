"""Pluggable per-agent uplink compressors for the Fed-PLT z-exchange.

A compressor maps the flattened per-leaf increment ``dz`` of shape
``(N, m)`` (one row per agent) to the values actually transmitted; the
round engine (:mod:`repro.fed.engine`) advances the coordinator's lagged
copy ``t`` by exactly what was transmitted, so the never-transmitted
residual is the error-feedback memory.  Top-k / int8 scales are per
agent per leaf -- what an actual uplink would quantize.

New compressors plug in through :func:`register_compressor`::

    @register_compressor("sign")
    def compress_sign(dz, cfg):
        scale = jnp.mean(jnp.abs(dz), axis=-1, keepdims=True)
        return jnp.sign(dz) * scale

and are immediately reachable by name from ``FedSpec`` and the train
CLI generated from it -- the engine
dispatches through this registry, never through hard-coded branches.

The registered function receives the :class:`repro.fed.engine.RoundConfig`
(duck-typed: it only reads ``compress_ratio`` / ``compress_energy``) and
must preserve shape and dtype.

Backends: the registry functions are the XLA reference path.  With
``cfg.compress_backend == "pallas"`` the accelerated compressors
(:data:`PALLAS_COMPRESSORS`) instead run the fused
:mod:`repro.kernels.compress` kernels, and ``compress_increment`` packs
ALL pytree leaves into one ``(N, M_total)`` buffer
(:func:`pack_leaves`) so the whole round's uplink is ONE kernel launch
with segment-aware per-(agent, leaf) scales -- bit-identical to the
per-leaf XLA path (asserted in tests).  Compressors without a kernel
(custom registry entries, ``none``) fall back to the per-leaf XLA path
under either backend.

``"auto"`` (the :class:`repro.fed.api.CompressionSpec` default) takes
the fused kernel exactly where its whole-row block fits the chip's fast
memory (:func:`resolve_backend`) -- the dense paper problems, never a
packed model row -- and the registry path elsewhere.  Both backends are
bit-identical, so auto-dispatch is a pure scheduling choice --
trajectories do not depend on it.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.fed import telemetry
from repro.kernels.compress.kernel import prefix_sum

# (dz_rows (N, m), round_cfg) -> transmitted rows (N, m)
CompressFn = Callable[[jnp.ndarray, Any], jnp.ndarray]

_REGISTRY: Dict[str, CompressFn] = {}

COMPRESS_BACKENDS = ("auto", "xla", "pallas")
# registry names with a fused kernel implementation
PALLAS_COMPRESSORS = frozenset({"topk", "adaptive_topk", "int8"})

# column alignment of the packed buffer (TPU lane width)
_LANE = 128


def register_compressor(name: str) -> Callable[[CompressFn], CompressFn]:
    """Decorator registering a per-agent row compressor under ``name``."""

    def deco(fn: CompressFn) -> CompressFn:
        _REGISTRY[name] = fn
        return fn

    return deco


def get_compressor(name: str) -> CompressFn:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown compressor {name!r}; registered: "
            f"{', '.join(available_compressors())}") from None


def available_compressors() -> list[str]:
    return sorted(_REGISTRY)


def _backend_of(cfg) -> str:
    backend = getattr(cfg, "compress_backend", "xla")
    if backend not in COMPRESS_BACKENDS:
        raise ValueError(f"unknown compress backend {backend!r}; known: "
                         f"{', '.join(COMPRESS_BACKENDS)}")
    return backend


def resolve_backend(cfg, m_total=None) -> str:
    """Resolve ``cfg.compress_backend`` to a concrete ``"xla"`` /
    ``"pallas"`` for a buffer ``m_total`` columns wide.

    The kernels hold whole ``(rows, m_total)`` rows in one block, so they
    exist only where that block fits the chip's fast memory
    (:func:`repro.kernels.compress.ops.fits_vmem`).  ``"auto"`` takes the
    kernel exactly there, and XLA elsewhere or when the width is
    unknown; an explicit ``"pallas"`` beyond it raises instead of
    falling back.  Both backends are bit-identical, so this is purely a
    scheduling decision.  Compressors without a kernel always resolve
    to the registry path.
    """
    from repro.kernels.compress.ops import (VMEM_LIMIT_BYTES,
                                            block_vmem_bytes, fits_vmem)

    backend = _backend_of(cfg)
    name = cfg.compression
    if name not in PALLAS_COMPRESSORS or backend == "xla":
        return "xla" if backend == "auto" else backend
    fits = m_total is not None and fits_vmem(name, m_total)
    if backend == "pallas" and m_total is not None and not fits:
        raise ValueError(
            f"compress backend 'pallas': the {name} kernel keeps whole "
            f"{m_total}-column rows in one block, about "
            f"{block_vmem_bytes(name, m_total) / 2**20:.0f} MiB of VMEM "
            f"(limit {VMEM_LIMIT_BYTES / 2**20:.0f} MiB); use backend "
            f"'auto' or 'xla'")
    if backend == "auto":
        return "pallas" if fits else "xla"
    return backend


def _use_pallas(cfg, m_total=None) -> bool:
    return (resolve_backend(cfg, m_total) == "pallas"
            and cfg.compression in PALLAS_COMPRESSORS)


def _pallas_rows(dz: jnp.ndarray, cfg, segments=None) -> jnp.ndarray:
    """The fused-kernel compressor on an (N, m) buffer (optionally with
    per-leaf column segments)."""
    from repro.kernels.compress import ops

    name = cfg.compression
    if name == "int8":
        return ops.int8_quantize(dz, segments=segments)
    return ops.rank_select(dz, segments=segments, mode=name,
                           ratio=cfg.compress_ratio,
                           energy=cfg.compress_energy)


def compress_rows(dz: jnp.ndarray, cfg) -> jnp.ndarray:
    """Dispatch the configured compressor on a flattened (N, m) increment."""
    if _use_pallas(cfg, dz.shape[1]):
        return _pallas_rows(dz, cfg)
    return get_compressor(cfg.compression)(dz, cfg)


# ---------------------------------------------------------------------------
# Leaf packing: the whole pytree as one (N, M_total) buffer
# ---------------------------------------------------------------------------

class PackedMeta(NamedTuple):
    """Static layout of a packed agent-stacked pytree: everything needed
    to invert :func:`pack_leaves` and to hand the kernels their static
    per-leaf column segments.  Hashable (tuples + a treedef), so it can
    ride through ``jit`` closures and static arguments unchanged -- the
    packed-resident engine keeps ONE meta for the whole run."""

    treedef: Any
    shapes: Tuple[Tuple[int, ...], ...]      # per-leaf (N, ...) shapes
    segments: Tuple[Tuple[int, int], ...]    # per-leaf (start, stop) cols
    width: int                               # padded column count

    @property
    def m_total(self) -> int:
        """Data columns (excluding lane padding) -- the auto-dispatch
        shape signal."""
        return self.segments[-1][1]


def packed_meta(tree: Any) -> PackedMeta:
    """The :class:`PackedMeta` that :func:`pack_leaves` would record for
    ``tree`` -- pure shape arithmetic, so ``tree`` may hold
    ``ShapeDtypeStruct`` leaves (e.g. from ``jax.eval_shape``): the
    packed-resident front ends derive their static layout without ever
    materializing a tree-form state."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    if not leaves:
        raise ValueError("packed_meta: empty pytree")
    n = leaves[0].shape[0]
    dtype = jnp.result_type(leaves[0])
    for l in leaves:
        if l.shape[0] != n or jnp.result_type(l) != dtype:
            raise ValueError(
                "pack_leaves needs a uniform agent axis and dtype, got "
                f"{[(tuple(x.shape), str(jnp.result_type(x))) for x in leaves]}")
    segments, start = [], 0
    for l in leaves:
        m = 1
        for d in l.shape[1:]:
            m *= d
        segments.append((start, start + m))
        start += m
    # single leaf: the flattened leaf IS the buffer, no lane padding --
    # the kernel wrappers pad to their block internally, and skipping
    # the pad keeps the dense (N, n) front end's packed form identical
    # to its tree form (zero-copy residency)
    width = start if len(leaves) == 1 else -(-start // _LANE) * _LANE
    return PackedMeta(treedef=treedef,
                      shapes=tuple(tuple(l.shape) for l in leaves),
                      segments=tuple(segments), width=width)


def pack_leaves(tree: Any) -> Tuple[jnp.ndarray, PackedMeta]:
    """Flatten every ``(N, ...)`` leaf and concatenate along columns into
    one ``(N, M_total)`` buffer (padded to the TPU lane width), recording
    per-leaf segment offsets.  All leaves must share the agent axis and
    dtype (the uplink buffer is one wire format).

    Fast path: a single-leaf tree (the dense front end) skips the copy
    chain entirely -- the flattened leaf is returned as the buffer, a
    pure reshape (and the identity for an already-2D array)."""
    meta = packed_meta(tree)
    leaves = jax.tree_util.tree_leaves(tree)
    n = leaves[0].shape[0]
    flat = [l.reshape(n, -1) for l in leaves]
    if len(flat) == 1:
        return flat[0], meta
    # write each leaf into a preallocated buffer: XLA:CPU compiles
    # a many-operand concatenate as a chain of whole-buffer copies
    # (O(leaves x M_total) traffic -- ~20x slower at a 200-leaf
    # engine-scale tree), while consecutive dynamic_update_slice
    # ops alias in place under jit
    if _lane_rows(meta, meta.width):
        rows = jnp.zeros((n, meta.width // _LANE, _LANE), leaves[0].dtype)
        for f, (s0, _) in zip(flat, meta.segments):
            rows = jax.lax.dynamic_update_slice(
                rows, f.reshape(n, -1, _LANE), (0, s0 // _LANE, 0))
        return (jax.lax.optimization_barrier(rows).reshape(n, meta.width),
                meta)
    buf = jnp.zeros((n, meta.width), leaves[0].dtype)
    for f, (s0, _) in zip(flat, meta.segments):
        buf = jax.lax.dynamic_update_slice(buf, f, (0, s0))
    return buf, meta


def _lane_rows(meta: PackedMeta, width: int) -> bool:
    """Whether every leaf starts and ends on a 128-column boundary, so
    that :func:`pack_leaves` / :func:`unpack_leaves` move whole rows of
    a ``(n, width/128, 128)`` view, held apart from the ``(n, width)``
    buffer by a barrier.

    On a TPU the ``(n, width)`` buffer tiles the agent axis into its
    minor tile.  Reshaped straight into (or out of) model-shaped leaves,
    XLA keeps that layout through the model and lowers the vmapped
    matmuls over the agent axis as dilated convolutions whose compile
    time grows with the weights: a 2-layer phi4-mini-width round with a
    50k-row vocabulary compiled for a v5e in ~500 s instead of the tree
    layout's ~15 s.  The row view puts the agent axis major."""
    return width % _LANE == 0 and all(
        s % _LANE == 0 for seg in meta.segments for s in seg)


def unpack_leaves(buf: jnp.ndarray, meta: PackedMeta) -> Any:
    """Invert :func:`pack_leaves` (padding columns are dropped).

    The agent count is taken from ``buf``, not ``meta``, so a row-sliced
    buffer (a heterogeneous solver group's agents) unpacks with the same
    meta."""
    n = buf.shape[0]
    if _lane_rows(meta, buf.shape[1]):
        rows = jax.lax.optimization_barrier(
            buf.reshape(n, buf.shape[1] // _LANE, _LANE))
        leaves = [rows[:, s0 // _LANE:s1 // _LANE].reshape((n,) + shape[1:])
                  for (s0, s1), shape in zip(meta.segments, meta.shapes)]
    else:
        leaves = [buf[:, s0:s1].reshape((n,) + shape[1:])
                  for (s0, s1), shape in zip(meta.segments, meta.shapes)]
    return jax.tree_util.tree_unflatten(meta.treedef, leaves)


def pack_coord(tree: Any, meta: PackedMeta) -> jnp.ndarray:
    """Pack a COORDINATOR pytree (the agent-axis-free ``y``, leaves
    shaped like the agent leaves minus the leading axis) into a
    ``(1, width)`` buffer aligned with ``meta``'s column segments --
    the form the fused round-edge kernels stream ``y`` in."""
    leaves = jax.tree_util.tree_leaves(tree)
    if len(leaves) != len(meta.shapes):
        raise ValueError(f"coordinator tree has {len(leaves)} leaves, "
                         f"meta has {len(meta.shapes)}")
    flat = []
    for leaf, shape in zip(leaves, meta.shapes):
        if tuple(leaf.shape) != tuple(shape[1:]):
            raise ValueError(f"coordinator leaf {tuple(leaf.shape)} does "
                             f"not match agent leaf {tuple(shape)}")
        flat.append(leaf.reshape(1, -1))
    if len(flat) == 1 and meta.width == flat[0].shape[1]:
        return flat[0]
    buf = jnp.zeros((1, meta.width), flat[0].dtype)
    for f, (s0, _) in zip(flat, meta.segments):
        buf = jax.lax.dynamic_update_slice(buf, f, (0, s0))
    return buf


def unpack_coord(buf: jnp.ndarray, meta: PackedMeta) -> Any:
    """Invert :func:`pack_coord`: a ``(1, width)`` coordinator buffer
    back to the agent-axis-free pytree."""
    leaves = [buf[:, s0:s1].reshape(shape[1:])
              for (s0, s1), shape in zip(meta.segments, meta.shapes)]
    return jax.tree_util.tree_unflatten(meta.treedef, leaves)


def _tree_m_total(leaves) -> int:
    total = 0
    for l in leaves:
        m = 1
        for d in l.shape[1:]:
            m *= d
        total += m
    return total


@telemetry.scope("fedplt.compress")
def compress_increment(dz: Any, cfg) -> Any:
    """Apply the configured compressor to a stacked increment pytree
    (top-k / int8 scales are per agent per leaf, which is what an actual
    uplink would quantize).

    XLA backend: leaf-wise, each leaf flattened to (N, m) -- one sort
    launch per leaf.  Pallas backend (accelerated compressors only):
    leaves are packed into one (N, M_total) buffer and the fused
    segment-aware kernel runs ONCE per round; bit-identical output."""
    leaves = jax.tree_util.tree_leaves(dz)
    if _use_pallas(cfg, _tree_m_total(leaves)):
        uniform = len({(l.shape[0], jnp.result_type(l)) for l in leaves}) == 1
        if uniform:
            buf, meta = pack_leaves(dz)
            return unpack_leaves(_pallas_rows(buf, cfg, meta.segments),
                                 meta)
        # mixed-dtype trees have no single wire format: per-leaf kernels
        return jax.tree_util.tree_map(
            lambda l: _pallas_rows(l.reshape(l.shape[0], -1),
                                   cfg).reshape(l.shape), dz)
    fn = get_compressor(cfg.compression)

    def leaf(l):
        return fn(l.reshape(l.shape[0], -1), cfg).reshape(l.shape)

    return jax.tree_util.tree_map(leaf, dz)


@telemetry.scope("fedplt.compress")
def compress_increment_packed(dz_buf: jnp.ndarray, meta: PackedMeta,
                              cfg) -> jnp.ndarray:
    """The configured compressor on a RESIDENT packed ``(N, width)``
    increment -- the packed-resident engine's uplink: no pack/unpack at
    all.

    Pallas-resolved backends run the fused segment-aware kernel directly
    on the buffer.  The XLA path runs the registry function per column
    segment (each segment is exactly one flattened leaf, so scales stay
    per (agent, leaf) and the output is bit-identical to the tree path)
    and writes the results into a zero buffer -- out-of-segment padding
    columns therefore come back zero under BOTH backends (the kernels
    zero them too), which keeps the coordinator copy ``t``'s padding
    static across rounds."""
    if _use_pallas(cfg, meta.m_total):
        return _pallas_rows(dz_buf, cfg, meta.segments)
    fn = get_compressor(cfg.compression)
    if len(meta.segments) == 1 and meta.width == meta.m_total:
        return fn(dz_buf, cfg)     # single leaf: the buffer IS the leaf
    out = jnp.zeros_like(dz_buf)
    for s0, s1 in meta.segments:
        out = jax.lax.dynamic_update_slice(
            out, fn(jax.lax.slice_in_dim(dz_buf, s0, s1, axis=1), cfg),
            (0, s0))
    return out


# ---------------------------------------------------------------------------
# Built-in compressors
# ---------------------------------------------------------------------------

@register_compressor("none")
def compress_none(dz: jnp.ndarray, cfg) -> jnp.ndarray:
    """Exact exchange: transmit the full-precision increment."""
    del cfg
    return dz


@register_compressor("topk")
def compress_topk(dz: jnp.ndarray, cfg) -> jnp.ndarray:
    """Keep the ``compress_ratio`` fraction of largest-magnitude entries
    per agent (same k for every agent).

    Exactly k entries survive: selection is by top-k *index* (ties
    broken by position), not by thresholding ``|row| >= |row|_(k)`` --
    a threshold transmits every tied coordinate (an all-constant row
    would transmit ALL of them), silently blowing the bandwidth budget
    the ratio promises."""
    k = max(1, int(cfg.compress_ratio * dz.shape[-1]))

    def topk_row(row):
        _, idx = jax.lax.top_k(jnp.abs(row), k)
        return jnp.zeros_like(row).at[idx].set(row[idx])

    return jax.vmap(topk_row)(dz)


@register_compressor("int8")
def compress_int8(dz: jnp.ndarray, cfg) -> jnp.ndarray:
    """Symmetric per-agent int8 quantization (scale = max|dz| / 127)."""
    del cfg
    scale = jnp.max(jnp.abs(dz), axis=-1, keepdims=True) / 127.0
    scale = jnp.maximum(scale, 1e-12)
    q = jnp.round(dz / scale).astype(jnp.int8)
    return q.astype(dz.dtype) * scale


@register_compressor("adaptive_topk")
def compress_adaptive_topk(dz: jnp.ndarray, cfg) -> jnp.ndarray:
    """Per-agent ADAPTIVE-ratio top-k (ROADMAP follow-up): each agent
    keeps the smallest k_i whose top coordinates capture a
    ``compress_energy`` fraction of its increment's l2 energy, floored at
    ``compress_ratio * m``.  Agents with concentrated increments (a few
    hot coordinates -- e.g. embedding rows they actually touched)
    transmit far fewer values than agents with diffuse updates, instead
    of everyone paying one global worst-case k."""
    m = dz.shape[-1]
    k_floor = max(1, int(cfg.compress_ratio * m))

    def row_fn(row):
        energy = jnp.square(jnp.abs(row))
        desc = jnp.sort(energy)[::-1]
        cum = prefix_sum(desc)
        total = jnp.maximum(cum[-1], 1e-30)
        # smallest prefix capturing the energy target, never below the floor
        k = jnp.sum(cum < cfg.compress_energy * total) + 1
        k = jnp.clip(k, k_floor, m)
        # exactly-k selection by magnitude *rank* (stable argsort breaks
        # ties by position); k is traced here, so jax.lax.top_k (static
        # k only) is not an option and thresholding would transmit every
        # tied coordinate
        order = jnp.argsort(-jnp.abs(row))
        rank = jnp.zeros(m, jnp.int32).at[order].set(
            jnp.arange(m, dtype=jnp.int32))
        return jnp.where(rank < k, row, 0.0)

    return jax.vmap(row_fn)(dz)
