"""Public fused Fed-PLT update op: arbitrary-shape leaves + pytrees."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import ON_TPU
from repro.kernels.fedplt_update.kernel import (BLOCK_M, BLOCK_N,
                                                fedplt_update_2d)


def _pad_to_2d(x):
    flat = x.reshape(-1)
    n = flat.shape[0]
    cols = BLOCK_N if n >= BLOCK_N else n
    rows = -(-n // cols)
    if rows > BLOCK_M and rows % BLOCK_M:
        rows += BLOCK_M - rows % BLOCK_M   # row-tile alignment
    pad = rows * cols - n
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), x.dtype)])
    return flat.reshape(rows, cols), n


@partial(jax.jit, static_argnames=("gamma", "inv_rho", "interpret"))
def fedplt_update(w, g, v, t=None, *, gamma: float, inv_rho: float,
                  interpret: bool | None = None):
    """Fused ``w - gamma (g + inv_rho (w - v)) [+ t]`` for one leaf."""
    if interpret is None:
        interpret = not ON_TPU
    if w.ndim == 2 and w.shape[1] % BLOCK_N == 0 and (
            w.shape[0] <= BLOCK_M or w.shape[0] % BLOCK_M == 0):
        # already tileable, e.g. the packed (N, width) state buffer:
        # flattening it would relayout the whole buffer on a TPU, where
        # its agent axis sits in the minor tile
        return fedplt_update_2d(
            w, g.astype(w.dtype), v.astype(w.dtype),
            None if t is None else t.astype(w.dtype), gamma=gamma,
            inv_rho=inv_rho, interpret=interpret)
    w2, n = _pad_to_2d(w)
    g2, _ = _pad_to_2d(g.astype(w.dtype))
    v2, _ = _pad_to_2d(v.astype(w.dtype))
    t2 = None
    if t is not None:
        t2, _ = _pad_to_2d(t.astype(w.dtype))
    out = fedplt_update_2d(w2, g2, v2, t2, gamma=gamma, inv_rho=inv_rho,
                           interpret=interpret)
    return out.reshape(-1)[:n].reshape(w.shape)


def fedplt_update_tree(w_tree, g_tree, v_tree, *, gamma: float,
                       inv_rho: float, interpret: bool | None = None):
    """Apply the fused update leaf-wise across a parameter pytree."""
    return jax.tree_util.tree_map(
        lambda w, g, v: fedplt_update(w, g, v, gamma=gamma,
                                      inv_rho=inv_rho,
                                      interpret=interpret),
        w_tree, g_tree, v_tree)
