"""How the program's numbers are held against the reference's.

A gap of norms is the gap between the program's norm of a leaf and the
reference's, not the norm of their difference, as a share of the
reference's norm of that leaf or of the median leaf, whichever is
larger (some leaves barely move).  Leaves whose reference norm is under
a thousandth of the median leaf's are left out: they move by round-off
alone.
"""

from __future__ import annotations

import math
import statistics

import jax

NEGLIGIBLE = 1e-3


def _norms(a, b):
    import jax.numpy as jnp

    return jax.tree_util.tree_map(
        lambda x, y: jnp.sqrt(jnp.sum(jnp.square(
            x.astype(jnp.float32) - y.astype(jnp.float32)))), a, b)


_norms_jit = jax.jit(_norms)


def leaf_norms(a, b) -> dict:
    """``{leaf path: ||a - b||}`` over two trees of one structure, in
    float32, one jitted call."""
    out = jax.device_get(_norms_jit(a, b))
    return {jax.tree_util.keystr(p): float(v)
            for p, v in jax.tree_util.tree_leaves_with_path(out)}


def stacked_norms(per_agent: list) -> dict:
    """Norms of agent-stacked leaves from each agent's leaf norms."""
    return {k: math.sqrt(sum(n[k] ** 2 for n in per_agent))
            for k in per_agent[0]}


def leaf_gaps(prog: dict, ref: dict) -> dict:
    """Each counted leaf's gap of norms (see the module docstring)."""
    if set(prog) != set(ref):
        raise ValueError(f"leaves differ: {sorted(set(prog) ^ set(ref))}")
    med = statistics.median(ref.values())
    return {k: (abs(prog[k] - r) / max(r, med)
                if math.isfinite(prog[k]) else math.inf)
            for k, r in ref.items() if r >= NEGLIGIBLE * med}


def norm_gap(prog: dict, ref: dict) -> float:
    """Worst leaf's gap of norms."""
    return max(leaf_gaps(prog, ref).values())


def rel_gap(prog, ref) -> float:
    """Largest ``|p - r| / |r|`` over paired sequences (inf where the
    program's number is not finite)."""
    gaps = [abs(p - r) / abs(r) for p, r in zip(prog, ref, strict=True)]
    return max(g if math.isfinite(g) else math.inf for g in gaps)
