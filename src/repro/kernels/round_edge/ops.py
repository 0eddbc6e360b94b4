"""Public fused round-edge ops.

Jitted wrappers over the :mod:`repro.kernels.round_edge.kernel` Pallas
kernels: pad the column axis to the block, dispatch, slice back.  The
prox callable is a STATIC argument (it is traced into the kernel body),
so only the :func:`repro.core.prox.make_prox` table's elementwise
functions belong here -- the engine gates on their ``elementwise`` tag
and sends anything else down the XLA path.

``interpret`` resolves via :data:`repro.kernels.ON_TPU` like the other
kernel suites.  Padding columns are zeros; their outputs are sliced off
before returning, so a prox whose fixed point is nonzero at 0 (e.g. a
box with ``lo > 0``) cannot leak padding into real columns.

MESH-AWARE REALIZATIONS.  :func:`round_uplink_sharded` /
:func:`round_downlink_sharded` are the same two edges with the agent
axis behind ``shard_map`` on an ``(agent, model)`` mesh: each shard
reduces its local rows in-VMEM (:func:`round_uplink_partial`), ONE
``psum`` of the ``(1, M)`` partials crosses devices, and the chain
finishes (``/ N`` -> prox -> reflection) on coordinator-sized arrays --
``zbar`` never hits HBM at agent-stack size, sharded or not.  The
downlink consumes the replicated ``y`` with purely local per-row work
(:func:`round_downlink_presummed`), so a sharded round still launches
exactly TWO fused edge kernels per shard.  On a 1-device mesh the
results are bit-identical to the unsharded ops (asserted in tests): the
1-device mesh is the degenerate case of the one code path, not a
separate engine.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.kernels import ON_TPU
from repro.kernels.round_edge.kernel import (BLOCK_COLS,
                                             round_downlink_2d,
                                             round_downlink_presummed_2d,
                                             round_uplink_2d,
                                             round_uplink_partial_2d)


def _resolve(x, interpret):
    if x.ndim != 2:
        raise ValueError(f"round-edge ops take (N, M) buffers, got "
                         f"shape {x.shape}")
    return (not ON_TPU) if interpret is None else interpret


def _block_cols(m, block_cols, interpret):
    """Interpret mode defaults to ONE program spanning the whole width:
    the column block is a TPU VMEM-tiling concern, and the interpret
    emulator's per-program loop overhead would otherwise dominate the
    very traffic the fusion removes.  An explicit ``block_cols`` always
    wins (the multi-block grid is exercised in tests)."""
    if interpret and block_cols == BLOCK_COLS:
        return max(block_cols, m)
    return block_cols


def _pad_cols(x, block_cols):
    m = x.shape[1]
    bc = min(block_cols, m)
    pad = -m % bc
    if pad:
        x = jnp.concatenate(
            [x, jnp.zeros((x.shape[0], pad), x.dtype)], axis=1)
    return x, m


@partial(jax.jit, static_argnames=("prox", "rho_eff", "interpret",
                                   "block_cols", "emulate"))
def round_uplink(z, t=None, *, prox=None, rho_eff=1.0, interpret=None,
                 block_cols=BLOCK_COLS, emulate=False):
    """Fused ``y = prox(mean_i z_i, rho_eff)``, ``v = 2 y - z``.

    ``t`` (optional) is the coordinator's lagged copy of ``z`` under a
    compressed exchange: the mean/prox run over ``t``, the reflection
    over ``z``.  Returns ``(y, v)`` with ``y`` of shape ``(1, M)``.
    """
    interpret = _resolve(z, interpret)
    block_cols = _block_cols(z.shape[1], block_cols, interpret)
    zp, m = _pad_cols(z, block_cols)
    tp = None if t is None else _pad_cols(t, block_cols)[0]
    y, v = round_uplink_2d(zp, tp, prox_fn=prox, rho_eff=rho_eff,
                           block_cols=block_cols, interpret=interpret,
                           emulate=emulate)
    return y[:, :m], v[:, :m]


@partial(jax.jit, static_argnames=("prox", "rho_eff", "damping",
                                   "interpret", "block_cols", "emulate"))
def round_downlink(x, w, z, u, t=None, *, prox=None, rho_eff=1.0,
                   damping=1.0, interpret=None, block_cols=BLOCK_COLS,
                   emulate=False):
    """Fused ``z + 2*damping*(w - prox(mean z_seen, rho_eff))`` +
    participation selects of x and z.  ``u`` is the ``(N,)``
    participation draw (nonzero = active); ``t`` the lagged coordinator
    copy under a compressed exchange (None = exact; the coordinator
    chain is recomputed in-kernel either way -- see the kernel
    docstrings for why it is not an input).  Returns
    ``(x_new, z_new)``.
    """
    interpret = _resolve(x, interpret)
    block_cols = _block_cols(x.shape[1], block_cols, interpret)
    xp, m = _pad_cols(x, block_cols)
    wp, _ = _pad_cols(w, block_cols)
    zp, _ = _pad_cols(z, block_cols)
    tp = None if t is None else _pad_cols(t, block_cols)[0]
    x_new, z_new = round_downlink_2d(
        xp, wp, zp, tp, u=u.reshape(-1, 1), prox_fn=prox,
        rho_eff=rho_eff, damping=damping, block_cols=block_cols,
        interpret=interpret, emulate=emulate)
    return x_new[:, :m], z_new[:, :m]


@partial(jax.jit, static_argnames=("interpret", "block_cols", "emulate"))
def round_uplink_partial(z, *, interpret=None, block_cols=BLOCK_COLS,
                         emulate=False):
    """Local half of the sharded uplink: the ``(1, M)`` column sums of
    one shard's rows (one kernel launch; the psum happens outside)."""
    interpret = _resolve(z, interpret)
    block_cols = _block_cols(z.shape[1], block_cols, interpret)
    zp, m = _pad_cols(z, block_cols)
    s = round_uplink_partial_2d(zp, block_cols=block_cols,
                                interpret=interpret, emulate=emulate)
    return s[:, :m]


@partial(jax.jit, static_argnames=("damping", "interpret", "block_cols",
                                   "emulate"))
def round_downlink_presummed(x, w, z, y, u, *, damping=1.0,
                             interpret=None, block_cols=BLOCK_COLS,
                             emulate=False):
    """Sharded downlink: fused z-update + participation selects of one
    shard's rows, consuming the replicated coordinator point ``y``
    (shape ``(1, M)``) instead of recomputing the chain in-kernel."""
    interpret = _resolve(x, interpret)
    block_cols = _block_cols(x.shape[1], block_cols, interpret)
    xp, m = _pad_cols(x, block_cols)
    wp, _ = _pad_cols(w, block_cols)
    zp, _ = _pad_cols(z, block_cols)
    yp, _ = _pad_cols(y, block_cols)
    x_new, z_new = round_downlink_presummed_2d(
        xp, wp, zp, yp, u=u.reshape(-1, 1), damping=damping,
        block_cols=block_cols, interpret=interpret, emulate=emulate)
    return x_new[:, :m], z_new[:, :m]


def round_uplink_sharded(z, t=None, *, mesh, n_total, prox=None,
                         rho_eff=1.0, row_axis="agent", col_axis=None,
                         interpret=None, block_cols=BLOCK_COLS,
                         emulate=False):
    """Mesh-aware fused uplink: ``shard_map`` over ``mesh``'s agent
    axis, one partial-sum kernel launch per shard, one ``(1, M)`` psum,
    then ``y = prox(psum / n_total)`` and ``v = 2 y - z_local``.

    ``n_total`` is the GLOBAL agent count (the local row extent is
    ``n_total / shards``).  ``col_axis`` additionally shards columns
    (the caller guarantees divisibility).  Returns ``(y, v)`` with
    ``y`` replicated across the agent axis.
    """
    def _body(z_l, t_l=None):
        seen = z_l if t_l is None else t_l
        part = round_uplink_partial(seen, interpret=interpret,
                                    block_cols=block_cols,
                                    emulate=emulate)
        zbar = jax.lax.psum(part, row_axis) / n_total
        y = zbar if prox is None else prox(zbar, rho_eff)
        return y, 2.0 * y - z_l

    spec = P(row_axis, col_axis)
    in_specs = (spec,) if t is None else (spec, spec)
    f = shard_map(_body, mesh=mesh, in_specs=in_specs,
                  out_specs=(P(None, col_axis), spec), check_vma=False)
    return f(z) if t is None else f(z, t)


def round_downlink_sharded(x, w, z, y, u, *, mesh, damping=1.0,
                           row_axis="agent", col_axis=None,
                           interpret=None, block_cols=BLOCK_COLS,
                           emulate=False):
    """Mesh-aware fused downlink: one presummed-downlink kernel launch
    per shard, purely local (the replicated ``y`` carries the only
    cross-shard information).  ``u`` is the global ``(N,)``
    participation draw, sharded with the rows.  Returns
    ``(x_new, z_new)``.
    """
    def _body(x_l, w_l, z_l, y_l, u_l):
        return round_downlink_presummed(x_l, w_l, z_l, y_l, u_l,
                                        damping=damping,
                                        interpret=interpret,
                                        block_cols=block_cols,
                                        emulate=emulate)

    spec = P(row_axis, col_axis)
    f = shard_map(_body, mesh=mesh,
                  in_specs=(spec, spec, spec, P(None, col_axis),
                            P(row_axis)),
                  out_specs=(spec, spec), check_vma=False)
    return f(x, w, z, y, u.reshape(-1))
