"""Fused uplink-compression kernels for the Fed-PLT z-exchange.

Two kernels over an agent-stacked ``(N, M)`` buffer whose columns are
partitioned into static *segments* (one segment per pytree leaf in the
packed path; a single segment in the per-leaf path):

  rank-select  -- ONE sort-equivalent pass per row computes the stable
                  descending-magnitude *rank* of every entry within its
                  segment, then keeps entries with ``rank < k``.  Ranks
                  (not a threshold) are required for exact-k semantics
                  on magnitude ties, and the same ranks serve both
                  ``topk`` (static per-segment k) and ``adaptive_topk``
                  (traced per-agent k_i from the energy cumsum of the
                  already-sorted magnitudes -- the XLA baseline's second
                  per-row sort disappears).
  int8         -- fused symmetric quantize-dequantize with one scale
                  per (agent, segment), i.e. per agent per leaf.

The select kernel always uses the COUNTING form of the rank select --
``rank < k`` rewritten as "strictly above the k-th magnitude, plus the
first k - #above positional ties", which needs only the SORTED
magnitudes, never a permutation: no dynamic gather or scatter anywhere
in the kernel (the Mosaic/TPU constraint).  Only how the sorted
magnitudes are obtained differs, and both give the IDENTICAL mask
(asserted bit-for-bit in tests):

  ``sort_impl="xla"``     -- one single-operand in-kernel ``lax.sort``
                             of the magnitude keys per segment;
                             executes under ``interpret=True`` (this
                             CPU container), where it is ~6x cheaper
                             than a stable key-value sort.
  ``sort_impl="bitonic"`` -- one compare-exchange network over the
                             whole padded buffer keyed by
                             (segment, -|x| bits), built from shuffles
                             and selects (the form a Mosaic/TPU
                             lowering needs, where ``lax.sort`` is
                             unavailable); O(M log^2 M).

(:func:`segment_ranks_2d` additionally materializes the int32 ranks by
inverting the sort permutation with a second sort -- an
introspection/test surface.)

Prefix sums (the adaptive energy cumsum, the tie prefix) go through
:func:`prefix_sum`, whose association is fixed by this module; the XLA
compressors call the same function, so kernel and registry agree
bitwise on every backend.

All segment metadata (ids, starts, per-segment k) is static -- derived
from the packed treedef at trace time -- so it is baked into the kernel
as constants; only values and the adaptive k_i are traced.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_AGENTS = 8   # rows per grid program (the agent axis is small)

_I32_MAX = np.int32(np.iinfo(np.int32).max)


# ---------------------------------------------------------------------------
# Static segment metadata
# ---------------------------------------------------------------------------

def _check_segments(segments, width):
    segs = tuple((int(a), int(b)) for a, b in segments)
    prev = 0
    for s0, s1 in segs:
        if not 0 <= s0 < s1 <= width:
            raise ValueError(f"segment ({s0}, {s1}) out of range for "
                             f"width {width}")
        if s0 < prev:
            raise ValueError(f"segments must be sorted and disjoint, got "
                             f"{segs}")
        prev = s1
    return segs


def _column_intervals(segments, width):
    """Segments plus the uncovered gaps (padding), in column order.

    Every column belongs to exactly one contiguous interval; because the
    intervals are contiguous AND the sort's primary key is the interval
    id in column order, interval ``l`` occupies exactly the global
    sorted positions ``[start_l, stop_l)`` -- which is what turns one
    global sort into per-segment ranks by a constant subtraction.
    """
    intervals, cursor = [], 0
    for s0, s1 in segments:
        if cursor < s0:
            intervals.append((cursor, s0, False))
        intervals.append((s0, s1, True))
        cursor = s1
    if cursor < width:
        intervals.append((cursor, width, False))
    return intervals


def _segment_constants(segments, width):
    """(seg_id, seg_start) int32 column vectors, shape ``(1, width)``.

    Derived from the static segment tuple at trace time and handed to
    the kernels as (tiny) extra inputs -- Pallas kernels cannot capture
    array constants."""
    seg_id = np.empty((1, width), np.int32)
    seg_start = np.empty((1, width), np.int32)
    for i, (s0, s1, _) in enumerate(_column_intervals(segments, width)):
        seg_id[0, s0:s1] = i
        seg_start[0, s0:s1] = s0
    return seg_id, seg_start


# ---------------------------------------------------------------------------
# The one sort pass: stable descending-magnitude ranks within segments
# ---------------------------------------------------------------------------

def _magnitude_key(x):
    """int32 key monotone in |x| (IEEE bits of the non-negative |x|)."""
    mag = jnp.abs(x).astype(jnp.float32)
    return jax.lax.bitcast_convert_type(mag, jnp.int32)


def _lex_lt(a, b):
    """Strict lexicographic ``a < b`` over tuples of int32 arrays."""
    lt = jnp.zeros(a[0].shape, jnp.bool_)
    eq = jnp.ones(a[0].shape, jnp.bool_)
    for ai, bi in zip(a, b):
        lt = lt | (eq & (ai < bi))
        eq = eq & (ai == bi)
    return lt


def _pow2_pad(width):
    """(next power of two, columns to pad) for the bitonic network."""
    pow2 = 1 << max(1, (width - 1).bit_length())
    return pow2, pow2 - width


def _pad_cols(a, pad, fill):
    """Append ``pad`` columns of scalar ``fill`` to a (bm, n) int32
    array.  Padding must sort LAST: callers fill the primary key with
    ``_I32_MAX`` (a segment id beyond every real one)."""
    if not pad:
        return a
    return jnp.concatenate(
        [a, jnp.full((a.shape[0], pad), fill, jnp.int32)], axis=1)


def _lane_rolls(a, s):
    """``(a[..., i - s], a[..., i + s])`` along the last axis, cyclic.

    Two lane rotations; which one holds ``a[i - s]`` is read off a
    rotated lane iota rather than assumed, so the result does not depend
    on the rotate direction convention of the lowering."""
    axis = a.ndim - 1
    n = a.shape[axis]
    idx = jax.lax.broadcasted_iota(jnp.int32, a.shape, axis)
    fwd, bwd = pltpu.roll(a, s, axis), pltpu.roll(a, n - s, axis)
    is_minus = pltpu.roll(idx, s, axis) == (idx - s) % n
    return jnp.where(is_minus, fwd, bwd), jnp.where(is_minus, bwd, fwd)


def _xor_shuffle(a, j):
    """``a[..., i ^ j]`` for a power-of-two stride ``j``: XOR with j
    flips exactly one index bit, so the partner is ``i - j`` where that
    bit is set and ``i + j`` where it is not -- two lane rotations and a
    select (no gather: Pallas kernels cannot capture index constants,
    and Mosaic lowers neither a general gather nor the ``rev`` a
    reshape-and-flip would need)."""
    minus, plus = _lane_rolls(a, j)
    idx = jax.lax.broadcasted_iota(jnp.int32, a.shape, a.ndim - 1)
    return jnp.where((idx & j) != 0, minus, plus)


def prefix_sum(v, shift_right=None):
    """Inclusive prefix sum along the last axis, Hillis-Steele order.

    The association is fixed by this function (``log2 n`` passes of
    ``v + v[i - s]``), not by a backend's ``cumsum`` lowering, so the
    kernels (lane rotations, which Mosaic lowers; it has no ``cumsum``)
    and the XLA compressors (zero-filled slices) that both call it add
    the same operands in the same order and agree bitwise.
    ``shift_right(v, s)`` returns ``v[..., i - s]`` with zeros for
    ``i < s``; the default builds it from slices."""
    if shift_right is None:
        shift_right = _slice_shift_right
    s = 1
    while s < v.shape[-1]:
        v = v + shift_right(v, s)
        s *= 2
    return v


def _slice_shift_right(v, s):
    pad = jnp.zeros(v.shape[:-1] + (s,), v.dtype)
    return jnp.concatenate([pad, v[..., :-s]], axis=-1)


def _lane_shift_right(v, s):
    idx = jax.lax.broadcasted_iota(jnp.int32, v.shape, v.ndim - 1)
    return jnp.where(idx >= s, _lane_rolls(v, s)[0], 0).astype(v.dtype)


def _bitonic_sort(arrs):
    """Ascending bitonic sort along the last axis (power-of-two length).

    ``arrs`` is a tuple of int32 arrays compared lexicographically; the
    key must be unique per element (we always include the position), so
    the network realizes exactly the stable order.  Compare-exchange
    partners and directions come from in-kernel iotas and lane
    rotations -- the Mosaic-lowerable form.
    """
    n = arrs[0].shape[-1]
    if n & (n - 1):
        raise ValueError(f"bitonic sort needs a power-of-two length, "
                         f"got {n}")
    idx = jax.lax.broadcasted_iota(jnp.int32, arrs[0].shape,
                                   arrs[0].ndim - 1)

    def stage(k, j, arrs):
        parrs = tuple(_xor_shuffle(a, j) for a in arrs)
        ascending = (idx & k) == 0
        is_left = (idx & j) == 0        # i < i ^ j  <=>  bit j unset
        want_min = ascending == is_left
        # ``where(want_min, ~lt, lt)`` as an xor: Mosaic has no select
        # over boolean vectors
        take_partner = _lex_lt(arrs, parrs) ^ want_min
        return tuple(jnp.where(take_partner, pa, a)
                     for a, pa in zip(arrs, parrs))

    if n <= 128:
        # within one lane tile: unrolled, with static rotations (Mosaic
        # does not lower a rotation by a traced amount of a partial tile)
        for p in range(1, n.bit_length()):
            for q in range(p):
                arrs = stage(1 << p, (1 << (p - 1)) >> q, arrs)
        return arrs

    # wider rows run the stages in loops with a traced block size and
    # stride: unrolled, the log^2 n stages make the kernel program (and
    # its Mosaic compile time) grow as n log^2 n
    def merge(p, arrs):
        k = jnp.left_shift(1, p)
        return jax.lax.fori_loop(
            0, p, lambda q, a: stage(k, jnp.right_shift(k // 2, q), a), arrs)

    return jax.lax.fori_loop(1, n.bit_length(), merge, arrs)


def _segment_ranks(x, seg_id, seg_start, sort_impl):
    """(rank_within_segment, sorted_mag) for one ``(bm, M)`` block.

    One sort of the composite key (segment id, -|x| bits, position):
    stable descending-magnitude order within every segment at once.
    ``sorted_mag[:, start:stop]`` are segment ``(start, stop)``'s
    magnitudes in descending order (dtype of ``x``), so the adaptive
    energy cumsum needs no second sort.  ``seg_id`` / ``seg_start`` are
    the ``(1, width)`` column metadata rows from
    :func:`_segment_constants`.
    """
    bm, width = x.shape
    seg = jnp.broadcast_to(seg_id, x.shape)
    neg_mag = -_magnitude_key(x)
    pos = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)

    if sort_impl == "xla":
        _, neg_mag_s, pos_s = jax.lax.sort(
            (seg, neg_mag, pos), dimension=1, num_keys=2, is_stable=True)
    elif sort_impl == "bitonic":
        _, pad = _pow2_pad(width)
        seg_p = _pad_cols(seg, pad, _I32_MAX)
        neg_p = _pad_cols(neg_mag, pad, 0)
        pos_p = pos
        if pad:     # distinct positions for the padding columns too
            pos_p = jnp.concatenate(
                [pos, width + jax.lax.broadcasted_iota(
                    jnp.int32, (bm, pad), 1)], axis=1)
        _, neg_mag_s, pos_p_sorted = _bitonic_sort((seg_p, neg_p, pos_p))
        neg_mag_s = neg_mag_s[:, :width]
        pos_s = pos_p_sorted[:, :width]
    else:
        raise ValueError(f"unknown sort_impl {sort_impl!r} "
                         f"(known: 'xla', 'bitonic')")

    # invert the permutation (global sorted position of every column) by
    # sorting the sorted positions back into column order -- a second
    # sort, not a scatter, which Mosaic does not lower -- then subtract
    # the (static) segment start -> rank within segment
    order = jax.lax.broadcasted_iota(jnp.int32, pos_s.shape, 1)
    if sort_impl == "xla":
        _, rank = jax.lax.sort((pos_s, order), dimension=1, num_keys=1)
    else:
        _, rank = _bitonic_sort((pos_p_sorted, _pad_cols(order, pad, 0)))
        rank = rank[:, :width]
    rank = rank - seg_start
    # recover |x| in sorted order from the key bits (exact for f32/bf16)
    sorted_mag = jax.lax.bitcast_convert_type(
        -neg_mag_s, jnp.float32).astype(x.dtype)
    return rank, sorted_mag


# ---------------------------------------------------------------------------
# Kernel bodies
# ---------------------------------------------------------------------------

def _seg_k(ratio, m):
    """The XLA compressors' k: ``max(1, int(ratio * m))`` (static)."""
    return max(1, int(ratio * m))


def _select_k(sorted_mag, mode, ratio, energy, m):
    """The per-(agent, segment) keep-count from the descending
    magnitudes: static for ``topk``; for ``adaptive_topk`` the traced
    k_i from the energy cumsum of the ALREADY-SORTED magnitudes -- the
    second sort of the XLA baseline is gone.  Arithmetic mirrors the
    registry compressor op-for-op so the traced k_i is bit-identical."""
    if mode == "topk":
        return _seg_k(ratio, m)            # static, same for every agent
    cum = prefix_sum(jnp.square(sorted_mag), _lane_shift_right)
    total = jnp.maximum(cum[:, -1:], 1e-30)
    k = jnp.sum(cum < energy * total, axis=-1, keepdims=True) + 1
    return jnp.clip(k, _seg_k(ratio, m), m)


def _rank_select_kernel(x_ref, seg_ref, out_ref, *, segments, mode,
                        ratio, energy, sort_impl):
    """The COUNTING form of the rank select: from the per-segment
    descending magnitudes, the mask ``rank < k`` is equivalently "every
    entry STRICTLY above the k-th magnitude, plus the first
    ``k - #above`` entries TIED with it in position order" -- exactly
    the stable-rank tie discipline, with NO permutation inversion.  The
    TPU-shaped bitonic branch uses no dynamic gather/scatter anywhere
    (the Mosaic constraint); the interpret/CPU branch uses whatever
    XLA:CPU runs fastest (``top_k`` partial selection for static k, the
    counting mask after one single-operand sort for the traced adaptive
    k_i).  Every realization produces the bit-identical mask (asserted
    in tests)."""
    x = x_ref[...]
    bm, width = x.shape

    sorted_neg_full = None
    if sort_impl == "bitonic":
        # one compare-exchange network over the whole padded buffer
        # keyed by (segment, -|x| bits): ascending segment ids are the
        # column order, so segment l's descending magnitudes land
        # exactly in its own columns [s0, s1)
        seg = jnp.broadcast_to(seg_ref[...], x.shape)
        neg = -_magnitude_key(x)
        _, pad = _pow2_pad(width)
        _, sorted_neg_full = _bitonic_sort(
            (_pad_cols(seg, pad, _I32_MAX), _pad_cols(neg, pad, 0)))
    elif sort_impl != "xla":
        raise ValueError(f"unknown sort_impl {sort_impl!r} "
                         f"(known: 'xla', 'bitonic')")

    # per-interval VALUES, not masks, are joined: Mosaic does not
    # concatenate boolean vectors at offsets off the lane tile
    outs = []
    rows = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    for s0, s1, real in _column_intervals(segments, width):
        m = s1 - s0
        if not real:                       # padding: transmit nothing
            outs.append(jnp.zeros((bm, m), x.dtype))
            continue
        if sorted_neg_full is None and mode == "topk":
            # static k on CPU: top_k is a partial selection, cheaper
            # than any full sort (ties break by lowest index -- the
            # same discipline as the stable ranks)
            k = _seg_k(ratio, m)
            _, idx = jax.lax.top_k(jnp.abs(x[:, s0:s1]), k)
            mask = jnp.zeros((bm, m), jnp.bool_).at[
                rows[:, :k], idx].set(True)
            outs.append(jnp.where(mask, x[:, s0:s1], 0.0).astype(x.dtype))
            continue
        mag_key = _magnitude_key(x[:, s0:s1])
        if sorted_neg_full is not None:
            neg_s = sorted_neg_full[:, s0:s1]
        else:
            # one single-operand sort per segment: ~6x cheaper than a
            # stable key-value sort on XLA:CPU
            neg_s = jax.lax.sort(-mag_key, dimension=1, is_stable=False)
        sorted_mag = jax.lax.bitcast_convert_type(
            -neg_s, jnp.float32).astype(x.dtype)
        k = _select_k(sorted_mag, mode, ratio, energy, m)
        if mode == "topk":                 # static k: static slice
            kth = -neg_s[:, k - 1:k]       # k-th largest |x| key
        else:                              # traced per-agent k_i: a
            # masked reduction, not a gather (Mosaic-lowerable)
            pos = jax.lax.broadcasted_iota(jnp.int32, (bm, m), 1)
            kth = -jnp.sum(jnp.where(pos == k - 1, neg_s, 0),
                           axis=-1, keepdims=True)
        above = mag_key > kth
        tie = mag_key == kth
        n_above = jnp.sum(above, axis=-1, keepdims=True)
        tie_prefix = prefix_sum(tie.astype(jnp.int32), _lane_shift_right)
        mask = above | (tie & (tie_prefix <= k - n_above))
        outs.append(jnp.where(mask, x[:, s0:s1], 0.0).astype(x.dtype))
    out = outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=1)
    out_ref[...] = out.astype(out_ref.dtype)


def _segment_ranks_kernel(x_ref, seg_ref, start_ref, rank_ref, *,
                          sort_impl):
    rank, _ = _segment_ranks(x_ref[...], seg_ref[...], start_ref[...],
                             sort_impl)
    rank_ref[...] = rank


def _round_half_even(v):
    """``jnp.round`` (ties to even) from ``floor``, in f32: Mosaic has no
    bf16 ``roundeven``.  Exact for every input ``jnp.round`` sees here:
    the widening cast, ``v - floor(v)`` and the halving are all exact,
    so the result equals ``jnp.round(v)`` bit for bit."""
    v = v.astype(jnp.float32)
    r = jnp.floor(v)
    frac = v - r
    odd = r - 2.0 * jnp.floor(0.5 * r)
    up = (frac > 0.5) | ((frac == 0.5) & (odd == 1.0))
    return r + up.astype(jnp.float32)


def _int8_kernel(x_ref, out_ref, *, segments):
    """Fused symmetric int8 quantize-dequantize, one scale per
    (agent, segment) -- arithmetic mirrors the registry ``int8``
    compressor op-for-op per segment."""
    x = x_ref[...]
    width = x.shape[1]
    outs = []
    for s0, s1, real in _column_intervals(segments, width):
        if not real:
            outs.append(jnp.zeros((x.shape[0], s1 - s0), x.dtype))
            continue
        sl = x[:, s0:s1]
        scale = jnp.max(jnp.abs(sl), axis=-1, keepdims=True) / 127.0
        scale = jnp.maximum(scale, 1e-12)
        q = _round_half_even(sl / scale).astype(jnp.int8)
        outs.append(q.astype(x.dtype) * scale)
    out = outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=1)
    out_ref[...] = out.astype(out_ref.dtype)


# ---------------------------------------------------------------------------
# pallas_call wrappers (2-D, rows padded to the block by ops.py)
# ---------------------------------------------------------------------------

def _row_blocked_call(kernel, x, out_dtype, block_agents, interpret,
                      meta_arrays=()):
    n, width = x.shape
    bm = min(block_agents, n)
    if n % bm:
        raise ValueError(f"row count {n} not a multiple of the agent "
                         f"block {bm} (ops.py pads)")
    spec = pl.BlockSpec((bm, width), lambda i: (i, 0))
    meta_spec = pl.BlockSpec((1, width), lambda i: (0, 0))
    return pl.pallas_call(
        kernel,
        grid=(n // bm,),
        in_specs=[spec] + [meta_spec] * len(meta_arrays),
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(x.shape, out_dtype),
        interpret=interpret,
    )(x, *(jnp.asarray(a) for a in meta_arrays))


def rank_select_2d(x, *, segments, mode, ratio, energy, sort_impl,
                   block_agents=BLOCK_AGENTS, interpret=True):
    """Fused rank-select compressor on an ``(N, M)`` buffer."""
    if mode not in ("topk", "adaptive_topk"):
        raise ValueError(f"unknown rank-select mode {mode!r}")
    segments = _check_segments(segments, x.shape[1])
    seg_id, _ = _segment_constants(segments, x.shape[1])
    kernel = functools.partial(_rank_select_kernel, segments=segments,
                               mode=mode, ratio=ratio, energy=energy,
                               sort_impl=sort_impl)
    return _row_blocked_call(kernel, x, x.dtype, block_agents, interpret,
                             (seg_id,))


def segment_ranks_2d(x, *, segments, sort_impl,
                     block_agents=BLOCK_AGENTS, interpret=True):
    """Stable descending-|x| ranks within each segment (int32)."""
    segments = _check_segments(segments, x.shape[1])
    seg_id, seg_start = _segment_constants(segments, x.shape[1])
    kernel = functools.partial(_segment_ranks_kernel, sort_impl=sort_impl)
    return _row_blocked_call(kernel, x, jnp.int32, block_agents,
                             interpret, (seg_id, seg_start))


def int8_2d(x, *, segments, block_agents=BLOCK_AGENTS, interpret=True):
    """Fused per-(agent, segment) int8 quantize-dequantize."""
    segments = _check_segments(segments, x.shape[1])
    kernel = functools.partial(_int8_kernel, segments=segments)
    return _row_blocked_call(kernel, x, x.dtype, block_agents, interpret)
