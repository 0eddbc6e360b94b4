"""Byzantine-robust coordinator aggregation (ROADMAP fault-tolerance leg).

The Fed-PLT coordinator step is ``y = prox_{rho h/N}(mean_i z_i)`` -- a
mean with BREAKDOWN POINT ZERO: the in-jit increment guards quarantine
non-finite or over-norm rows, but one adversarial agent submitting a
finite, in-norm-bound, sign-flipped increment still steers the
consensus arbitrarily.  This module supplies the missing layer: a
registry of robust aggregators that replace the plain agent mean at the
uplink, selected by ``RoundConfig.aggregator`` / ``FedSpec.aggregator``.

Registry (mirrors :func:`repro.fed.compress.register_compressor` /
:func:`repro.fed.solvers.register_solver`): an aggregator is
``fn(z, live, *, param, colmask=None, model_axis=None) -> (1, M)``
over the agent-stacked ``(N, M)`` buffer.  ``live`` is the broker's 0/1
eviction row (None = everyone live): dead rows are EXCLUDED from the
order statistics, matching the survivor-mean semantics of
:func:`repro.fed.engine.survivor_mean_input`.  ``colmask`` marks real
(non-lane-padding) columns for aggregators whose arithmetic couples
columns (``norm_clip_mean`` row norms); per-column order statistics
ignore it.  ``model_axis`` is the mesh axis name to ``psum`` row-norm
partials over when the column axis is itself sharded.

Built-ins:

* ``mean`` -- the bitwise-identical default.  The engine never routes
  it through this module: :func:`repro.fed.engine.robust_seen` resolves
  ``"mean"`` (and ``trimmed_mean`` with ``f = 0``) to the historical
  :func:`survivor_mean_input` path, so clean configurations keep the
  exact pre-robustness graph.
* ``trimmed_mean`` -- drop the ``f = int(param)`` smallest and largest
  live values per column, average the rest.  Tolerates ``f`` byzantine
  agents (breakdown ``f < N/2`` enforced at validation).
* ``coord_median`` -- per-column median of the live values
  (``trimmed_mean`` at maximal trim; breakdown 1/2).
* ``norm_clip_mean`` -- centered clipping: rows are recentered at the
  coordinate-wise median, clipped to l2 radius ``param``, and averaged.
  Bounds any single agent's pull by ``param / n_live`` while keeping
  full mean efficiency for in-radius honest rows.

HOW THE ENGINE CONSUMES THE AGGREGATE: the robust statistic is folded
in as a ``z_seen`` INPUT TRANSFORM -- the ``(1, M)`` aggregate is
broadcast back to ``(N, M)`` and handed to the unchanged round edges,
whose fixed mean-over-N of N identical rows reproduces the aggregate
(to f32 rounding; exactly when N is a power of two).  One transform
point therefore composes with every layout x backend x compressor x
mesh combination and with the fused downlink, which recomputes the
coordinator chain from the SAME broadcast buffer -- no kernel learns a
second code path.  The reflection ``v = 2 y - z`` still reads the
original ``z``.

MESH CONTRACT extension: order statistics need the FULL agent column,
so the sharded packed path all-gathers the per-shard row blocks on the
``agent`` axis before aggregating -- ``(N/shards, M_local)`` rows move
per device per round, versus the mean's single ``(1, M)`` psum.  That
cost is the price of a breakdown point (documented in ROADMAP);
``mean`` keeps the single-psum uplink untouched.  A 1-device mesh is
bitwise identical to the unsharded path (the gather of one shard is
the identity).

Backends: ``trimmed_mean`` and ``coord_median`` have a Pallas
column-wise sort-and-trim kernel (:mod:`repro.kernels.robust_agg`)
used under ``engine_backend="pallas"``; the XLA oracle
(:func:`repro.kernels.robust_agg.ref.robust_aggregate_ref`) is
BITWISE-identical (parity contract, asserted in tests), so backends
never fork trajectories at the aggregate.  ``norm_clip_mean`` is
XLA-only (its clip is a dense row-wise rescale, already one fused
elementwise chain).

Robust aggregation interacts with privacy accounting in one direction
only: it can SAVE a run from a poisoned consensus, but it never
refunds epsilon -- DP guarantees come from the local noise mechanism
(Prop. 4) and are unaffected by how the coordinator combines the
submitted increments.
"""

from __future__ import annotations

import math
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.fed import compress as compress_lib
from repro.fed import telemetry
from repro.kernels.robust_agg.ref import robust_aggregate_ref

tree_map = jax.tree_util.tree_map

# Aggregators with a Pallas sort-and-trim kernel (others always run the
# XLA registry implementation, whatever the engine backend)
PALLAS_AGGREGATORS = frozenset({"trimmed_mean", "coord_median"})

# fn(z, live, *, param, colmask=None, model_axis=None) -> (1, M)
Aggregator = Callable[..., jnp.ndarray]

_AGGREGATORS: Dict[str, Aggregator] = {}


def register_aggregator(name: str):
    """Register an aggregator under ``name`` (decorator), making it
    reachable from every front end via ``FedSpec.aggregator``."""

    def deco(fn: Aggregator) -> Aggregator:
        _AGGREGATORS[name] = fn
        return fn

    return deco


def get_aggregator(name: str) -> Aggregator:
    try:
        return _AGGREGATORS[name]
    except KeyError:
        raise ValueError(
            f"unknown aggregator {name!r}; registered: "
            f"{', '.join(sorted(_AGGREGATORS))}") from None


def available_aggregators():
    return sorted(_AGGREGATORS)


def validate_aggregator(name: str, param, n_agents: Optional[int] = None
                        ) -> float:
    """Construction-time screening of an (aggregator, param) pair;
    returns the normalized float param.  One home for the rules, called
    by ``FedSpec.validate()`` and ``RoundConfig.__post_init__`` alike:

    * ``trimmed_mean``: ``param`` is the trim count ``f`` -- a
      non-negative integer with ``2 f < n_agents`` (something must
      survive the trim; ``f`` is also the byzantine tolerance).
    * ``norm_clip_mean``: ``param`` is the clip radius -- finite, > 0.
    * ``mean`` / ``coord_median``: no parameter (``param`` ignored).
    """
    get_aggregator(name)   # fail fast on unknown names
    try:
        p = float(param)
    except (TypeError, ValueError):
        raise ValueError(
            f"aggregator_param must be a number, got {param!r}") from None
    if name == "trimmed_mean":
        if not (math.isfinite(p) and p >= 0 and p == int(p)):
            raise ValueError(
                f"trimmed_mean takes a non-negative integer trim count "
                f"f as aggregator_param, got {param!r}")
        if n_agents is not None and 2 * int(p) >= n_agents:
            raise ValueError(
                f"trimmed_mean with f={int(p)} trims 2f={2 * int(p)} of "
                f"n_agents={n_agents} rows: need 2f < N so at least one "
                f"row survives the trim")
    elif name == "norm_clip_mean":
        if not (math.isfinite(p) and p > 0):
            raise ValueError(
                f"norm_clip_mean takes a finite positive clip radius as "
                f"aggregator_param, got {param!r}")
    return p


# ---------------------------------------------------------------------------
# Shared pieces
# ---------------------------------------------------------------------------

def _live_row(live, n: int) -> jnp.ndarray:
    """Canonical ``(1, N)`` float 0/1 live row (None = all live)."""
    if live is None:
        return jnp.ones((1, n), jnp.float32)
    return jnp.asarray(live, jnp.float32).reshape(1, n)


def _mean_live(rows: jnp.ndarray, lv: jnp.ndarray) -> jnp.ndarray:
    """Mean over live rows -> ``(1, M)`` (``lv`` is ``(1, N)``)."""
    n_live = jnp.maximum(jnp.sum(lv), 1.0)
    return jnp.sum(rows * lv.T, axis=0, keepdims=True) / n_live


# ---------------------------------------------------------------------------
# Built-in aggregators
# ---------------------------------------------------------------------------

@register_aggregator("mean")
def _mean(z, live, *, param, colmask=None, model_axis=None):
    """Survivor mean -- the registry form of the engine default (the
    engine itself short-circuits to :func:`survivor_mean_input`)."""
    return _mean_live(z, _live_row(live, z.shape[0]))


@register_aggregator("trimmed_mean")
def _trimmed_mean(z, live, *, param, colmask=None, model_axis=None):
    return robust_aggregate_ref(z, live, stat="trimmed_mean",
                                trim=int(param))


@register_aggregator("coord_median")
def _coord_median(z, live, *, param, colmask=None, model_axis=None):
    return robust_aggregate_ref(z, live, stat="coord_median")


@register_aggregator("norm_clip_mean")
def _norm_clip_mean(z, live, *, param, colmask=None, model_axis=None):
    """Centered clipping: recenter at the coordinate-wise median, clip
    each live row's residual to l2 radius ``param``, average.  The
    residual norm is taken over REAL columns only (``colmask``): lane
    padding may have drifted in the resident packed layout, and must
    not perturb real-column results (layout parity)."""
    lv = _live_row(live, z.shape[0])
    center = robust_aggregate_ref(z, live, stat="coord_median")
    r = z - center
    if colmask is not None:
        r = r * colmask.astype(r.dtype)
    partial = jnp.sum(jnp.square(r.astype(jnp.float32)), axis=1,
                      keepdims=True)
    if model_axis is not None:
        partial = jax.lax.psum(partial, model_axis)
    norms = jnp.sqrt(partial)
    scale = jnp.minimum(1.0, param / jnp.maximum(norms, 1e-12))
    return center + _mean_live(r * scale.astype(r.dtype), lv)


# ---------------------------------------------------------------------------
# Dispatch: one (N, M) buffer -> (1, M) aggregate
# ---------------------------------------------------------------------------

def aggregate_rows(z: jnp.ndarray, live, *, name: str, param: float,
                   colmask=None, backend: str = "xla",
                   model_axis: Optional[str] = None) -> jnp.ndarray:
    """Aggregate the agent-stacked ``(N, M)`` buffer to ``(1, M)``.

    ``backend="pallas"`` routes :data:`PALLAS_AGGREGATORS` through the
    :mod:`repro.kernels.robust_agg` sort-and-trim kernel (bitwise equal
    to the registry oracle -- parity contract); everything else, and
    every aggregator without a kernel, runs the registry entry."""
    if backend == "pallas" and name in PALLAS_AGGREGATORS \
            and model_axis is None:
        from repro.kernels.robust_agg import ops as robust_ops

        return robust_ops.robust_aggregate(
            z, live, stat=name,
            trim=int(param) if name == "trimmed_mean" else 0)
    return get_aggregator(name)(z, live, param=param, colmask=colmask,
                                model_axis=model_axis)


def _segment_colmask(meta) -> Optional[np.ndarray]:
    """``(1, width)`` bool mask of real (in-segment) columns, or None
    when the packing has no lane padding."""
    mask = np.zeros((1, meta.width), bool)
    for a, b in meta.segments:
        mask[0, a:b] = True
    return None if mask.all() else mask


# ---------------------------------------------------------------------------
# Engine entry points: the z_seen input transforms
# ---------------------------------------------------------------------------

@telemetry.scope("fedplt.aggregate")
def robust_seen_packed(z_seen: jnp.ndarray, live, *, name: str,
                       param: float, meta, backend: str,
                       mesh=None, col_axis: Optional[str] = None
                       ) -> jnp.ndarray:
    """Robust ``z_seen`` transform on the resident packed buffer:
    aggregate the live rows, broadcast back to ``(N, width)``.

    With a ``mesh`` the transform runs under ``shard_map``: each agent
    shard all-gathers the full agent column (the mesh-contract cost of
    an order statistic), aggregates locally via the XLA oracle (bitwise
    equal to the kernel -- parity contract), and writes its own row
    block of the broadcast.  ``col_axis`` names the mesh axis sharding
    the column dimension (None = replicated columns)."""
    n, width = z_seen.shape
    lv = _live_row(live, n)
    colmask = _segment_colmask(meta)
    if mesh is None:
        agg = aggregate_rows(z_seen, lv, name=name, param=param,
                             colmask=None if colmask is None
                             else jnp.asarray(colmask),
                             backend=backend)
        return jnp.broadcast_to(agg, z_seen.shape)

    cmask = np.ones((1, width), bool) if colmask is None else colmask

    def body(z_l, lv_l, cm_l):
        z_full = jax.lax.all_gather(z_l, "agent", axis=0, tiled=True)
        agg = aggregate_rows(z_full, lv_l, name=name, param=param,
                             colmask=cm_l, backend="xla",
                             model_axis=col_axis)
        return jnp.broadcast_to(agg, z_l.shape)

    spec = P("agent", col_axis)
    f = shard_map(body, mesh=mesh,
                  in_specs=(spec, P(), P(None, col_axis)),
                  out_specs=spec, check_vma=False)
    return f(z_seen, lv, jnp.asarray(cmask))


@telemetry.scope("fedplt.aggregate")
def robust_seen_tree(z_seen, live, *, name: str, param: float,
                     backend: str, mesh=None):
    """Robust ``z_seen`` transform on agent-stacked pytrees: pack the
    leaves (fresh pack -- padding columns are exact zeros), aggregate,
    broadcast, unpack.  Real-column arithmetic is identical to the
    packed-resident path, so tree and packed trajectories stay
    bitwise-aligned per realization (layout contract).  With a ``mesh``
    the packed buffer takes the same ``shard_map`` all-gather path as
    the packed layout (a Pallas kernel cannot be partitioned by GSPMD)."""
    from repro.fed.engine import _mesh_col_axis

    buf, meta = compress_lib.pack_leaves(z_seen)
    col = None if mesh is None else _mesh_col_axis(mesh, buf.shape[1])
    out = robust_seen_packed(buf, live, name=name, param=param,
                             meta=meta, backend=backend, mesh=mesh,
                             col_axis=col)
    return compress_lib.unpack_leaves(out, meta)
