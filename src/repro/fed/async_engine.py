"""Bounded-staleness async rounds: the deterministic in-jit model.

The engine's :func:`repro.fed.engine.round_step` is bulk-synchronous:
every agent trains against THIS round's reflection and the coordinator
averages whoever the participation draw selected.  Production
coordinators are not synchronous -- agents return increments late, and
the coordinator applies them as they arrive.  This module generalizes
the round to that regime while staying a deterministic pure function
inside jit, so async behavior is replayable and testable bit-for-bit
(the host-side realization of *when* arrivals happen lives in
:mod:`repro.fed.broker`; this module owns all the numerics).

THE STALENESS CONTRACT
======================

Two per-agent state variables ride next to ``(x, z, t)``:

* ``y_tag`` -- the coordinator point agent i's current local work was
  computed against (the ``y`` it "pulled"; leaves carry the agent axis).
* ``staleness`` -- ``(N,)`` int32: how many rounds old that work is.
  ``0`` means the agent starts fresh work this round.

One async round (:func:`async_round_step`):

1. Coordinator edge exactly as the synchronous engine: ``y_r`` and the
   fresh reflection ``v_r`` from the same
   :func:`~repro.fed.engine.coordinator_edge` (both backends, both
   layouts -- the fused uplink kernel path is unchanged).
2. Training target: fresh agents (``staleness == 0``) take ``v_r`` and
   record ``y_tag <- y_r``; stale agents keep training against their
   stale reflection ``2 * y_tag - z`` (``z_i`` is unchanged while an
   agent is stale, so this reproduces the reflection it originally
   pulled).  Every agent runs the local solver warm-started at its
   current ``x`` -- a stale agent therefore accumulates MORE local
   epochs against the same proximal target, the paper's central lever.
3. Arrival mask: the Bernoulli participation draw (same key slot as the
   synchronous round), OR-ed with the hard bound -- an agent whose work
   is ``max_staleness`` rounds old is FORCED to arrive.  A recorded
   schedule may be substituted for the draw (``arrival=``), which is
   how :mod:`repro.fed.broker` replays realized schedules bit-for-bit.
4. Arrived agents: the synchronous downlink edge applies
   ``z += 2*damping*(w - y)`` and the selects of ``(x, z)`` with the
   arrival mask streamed exactly like the participation mask (the fused
   downlink kernel path is unchanged); arrived agents whose work was
   STALE are then corrected to use their tagged coordinator point:
   ``z_i <- z_i + 2*damping*(w_i - y_tag_i)`` -- the increment is
   applied against the round it was computed in, not the current one.
5. Non-arrived agents below the bound keep their local progress
   (``x <- w``) and age (``staleness += 1``).  At ``max_staleness = 0``
   no stale work may exist, so a miss discards the round's local work
   -- which is EXACTLY the synchronous engine's inactive-agent
   semantics.

PARITY CONTRACT: with ``max_staleness = 0`` the async round is BITWISE
identical to :func:`repro.fed.engine.round_step` /
:func:`~repro.fed.engine.packed_round_step` per realization, under both
state layouts, both engine backends, and every registry compressor: the
key is split the same 3 ways, the arrival draw is the participation
draw from the same key slot (the forcing term is identically zero when
``staleness`` is identically zero), and every staleness select reduces
to an elementwise pass-through of the synchronous values (asserted in
``tests/test_async_engine.py``).

Privacy: staleness changes the *composition*, not the mechanism -- an
agent that arrived ``a_i`` times released ``(s+1)`` rounds of local
epochs per arrival (work discarded at the bound was never transmitted
and charges nothing).  :func:`effective_counts` derives those per-agent
effective round counts from a recorded arrival schedule;
``repro.fed.api.effective_privacy_report`` feeds them to the per-agent
Prop. 4 accountant.
"""

from __future__ import annotations

from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.fed import engine
from repro.fed.engine import (ASYNC_MODES, ProxH,  # noqa: F401  (re-export)
                              RoundConfig, SolverAssignment,
                              StalenessConfig)

tree_map = jax.tree_util.tree_map


class AsyncRoundResult(NamedTuple):
    """:class:`repro.fed.engine.RoundResult` plus the staleness carry."""

    x: Any               # pytree / buffer, agent axis leading
    z: Any
    t: Any               # coordinator's copy (== z when uncompressed)
    y: Any               # coordinator model of THIS round
    y_tag: Any           # per-agent pulled coordinator point (agent axis)
    staleness: jnp.ndarray   # (N,) int32 age of each agent's work
    next_key: jax.Array
    u: jnp.ndarray       # (N,) realized arrival mask of this round
    aux: Any


# ---------------------------------------------------------------------------
# State initialization
# ---------------------------------------------------------------------------

def init_staleness(n_agents: int) -> jnp.ndarray:
    """Round-0 counters: every agent starts fresh."""
    return jnp.zeros((n_agents,), jnp.int32)


def init_y_tag(z: Any) -> Any:
    """Round-0 tags: zeros shaped like the agent-stacked state.  The
    value is never read -- a fresh agent (staleness 0) overwrites its
    tag with this round's ``y`` before anything consumes it."""
    return tree_map(jnp.zeros_like, z)


# ---------------------------------------------------------------------------
# Round pieces
# ---------------------------------------------------------------------------

def _vec(mask: jnp.ndarray, leaf: jnp.ndarray) -> jnp.ndarray:
    """Reshape an (N,) mask for broadcast against an agent-axis leaf."""
    return mask.reshape((-1,) + (1,) * (leaf.ndim - 1))


def _select(mask: jnp.ndarray, new: Any, old: Any) -> Any:
    """``jnp.where`` on trees with an (N,) bool mask (NaN-safe select,
    same semantics as :func:`repro.fed.engine.masked_mix`)."""
    return tree_map(lambda nl, ol: jnp.where(_vec(mask, nl), nl, ol),
                    new, old)


def forced_arrivals(staleness: jnp.ndarray, max_staleness: int) \
        -> jnp.ndarray:
    """The hard bound: an agent holding work ``max_staleness`` rounds
    old must arrive.  Fresh agents (staleness 0) are never forced --
    at K = 0 a miss discards instead (the synchronous semantics), so
    the forcing term is identically zero there and the arrival mask is
    the participation draw bit-for-bit."""
    return (staleness >= max_staleness) & (staleness > 0)


def arrival_mask(key: jax.Array, cfg: RoundConfig,
                 staleness: jnp.ndarray,
                 arrival: Optional[jnp.ndarray] = None,
                 live=None) -> jnp.ndarray:
    """The round's realized (N,) float arrival mask: the Bernoulli
    participation draw (or an externally realized schedule row --
    broker runs and replays) OR-ed with the forced arrivals.  An
    eviction ``live`` row zeroes dead agents AFTER the forcing term --
    an evicted agent neither draws nor is forced in."""
    if arrival is None:
        draw = engine.participation_mask(key, cfg)
    else:
        draw = jnp.asarray(arrival, jnp.float32).reshape(-1)
    forced = forced_arrivals(staleness, cfg.staleness.max_staleness)
    return engine.live_mask_rows(
        jnp.maximum(draw, forced.astype(jnp.float32)), live)


def _advance_staleness(staleness: jnp.ndarray, u: jnp.ndarray,
                       max_staleness: int, live=None) -> jnp.ndarray:
    """Arrivals reset to 0; pending work below the bound ages by one;
    a miss AT the bound (only reachable at K = 0, where the bound
    forces every stale agent in) stays -- its work was discarded.
    Evicted agents (``live`` row 0) are pinned at 0: their pending work
    is abandoned, and a later rejoin starts them fresh."""
    aged = jnp.where(staleness < max_staleness, staleness + 1, staleness)
    out = jnp.where(u != 0, jnp.zeros_like(staleness), aged)
    if live is not None:
        out = jnp.where(jnp.asarray(live).reshape(-1) != 0, out,
                        jnp.zeros_like(out))
    return out


# ---------------------------------------------------------------------------
# One async round, tree layout
# ---------------------------------------------------------------------------

def async_round_step(cfg: RoundConfig, x: Any, z: Any, t: Any,
                     y_tag: Any, staleness: jnp.ndarray, key: jax.Array,
                     local_solver: SolverAssignment,
                     prox_h: ProxH = None,
                     arrival: Optional[jnp.ndarray] = None,
                     mesh=None, corrupt=None, live=None) -> AsyncRoundResult:
    """One bounded-staleness round on agent-stacked pytrees (module
    contract above).  Mirrors :func:`repro.fed.engine.round_step`'s key
    schedule and edge formulas exactly; ``arrival`` optionally replaces
    the Bernoulli draw with a realized schedule row (broker replay).
    With a ``mesh`` the edges run under ``shard_map`` and every async
    carrier (``y_tag``, ``staleness``, the arrival rows) shards on the
    agent axis with the state; the staleness selects between the edges
    are per-row elementwise, so GSPMD shards them transparently (mesh
    contract in :mod:`repro.fed.engine`).

    ``corrupt`` / ``live`` are broker-realized fault rows (see
    :func:`repro.fed.engine.round_step`): corrupted increments are
    screened by the guard into non-arrivals AND excluded from the keep
    branch (poisoned local progress is discarded, not carried); evicted
    agents leave the coordinator mean, the arrival draw, and the keep
    branch until a rejoin."""
    if mesh is not None:
        engine.validate_mesh(cfg, mesh, local_solver)
    key, k_part, k_solve = jax.random.split(key, 3)

    # -- coordinator edge: identical to the synchronous round (with the
    # survivor rescale when agents were evicted, and the robust
    # aggregate when one is configured) ---------------------------------
    z_seen = t if cfg.compressed else z
    z_seen = engine.robust_seen(cfg, z_seen, live, mesh=mesh)
    y, v_fresh = engine.coordinator_edge(cfg, z, z_seen, prox_h, mesh)

    # -- training targets: fresh agents pull this round's reflection,
    # stale agents reproduce the one they pulled (z_i unchanged while
    # stale, so 2*y_tag - z IS that reflection) -------------------------
    fresh = staleness == 0
    v_stale = tree_map(lambda ytl, zl: 2.0 * ytl - zl, y_tag, z)
    v_eff = _select(fresh, v_fresh, v_stale)
    y_tag_new = tree_map(
        lambda yl, ytl: jnp.where(_vec(fresh, ytl), yl[None], ytl),
        y, y_tag)

    # -- every agent trains, warm-started at its current x --------------
    w, aux = engine.run_solvers(local_solver, x, v_eff, k_solve,
                                cfg.n_agents)
    w = engine.apply_corruption(w, corrupt)

    # -- arrivals: the participation draw + the hard staleness bound,
    # screened by the increment guard (a corrupt row is a non-arrival) --
    u = arrival_mask(k_part, cfg, staleness, arrival, live)
    u, ok = engine.increment_guard(cfg, w, u)

    # -- synchronous downlink edge with the arrival mask streamed like
    # the participation mask (fused kernel path unchanged) --------------
    x_upd, z_upd = engine.agent_edge(cfg, u, w, x, z, y, z_seen, prox_h,
                                     mesh)

    # -- stale arrivals: the increment is tagged with the coordinator
    # point it was computed against, not this round's -------------------
    arrived = u != 0
    stale_arrival = arrived & (~fresh)
    z_tagged = tree_map(
        lambda zl, wl, ytl: zl + 2.0 * cfg.damping * (wl - ytl),
        z, w, y_tag)
    z_new = _select(stale_arrival, z_tagged, z_upd)

    # -- stragglers below the bound keep their local progress; a
    # quarantined (corrupt) or evicted agent must NOT -- keeping a
    # poisoned w would carry the corruption into the next round ---------
    keep = (~arrived) & (staleness < cfg.staleness.max_staleness)
    if live is not None:
        keep = keep & (jnp.asarray(live).reshape(-1) != 0)
    if ok is not None:
        keep = keep & ok
    x_new = _select(keep, w, x_upd)

    s_new = _advance_staleness(staleness, u, cfg.staleness.max_staleness,
                               live)

    # -- compressed uplink: only arrived increments are transmitted -----
    t_new = engine.transmit(cfg, z_new, t, u) if cfg.compressed else z_new

    return AsyncRoundResult(x=x_new, z=z_new, t=t_new, y=y,
                            y_tag=y_tag_new, staleness=s_new,
                            next_key=key, u=u, aux=aux)


# ---------------------------------------------------------------------------
# One async round, packed-resident layout
# ---------------------------------------------------------------------------

def packed_async_round_step(cfg: RoundConfig, meta, x: jnp.ndarray,
                            z: jnp.ndarray, t: jnp.ndarray,
                            y_tag: jnp.ndarray, staleness: jnp.ndarray,
                            key: jax.Array,
                            local_solver: SolverAssignment,
                            prox_h: ProxH = None,
                            arrival: Optional[jnp.ndarray] = None,
                            mesh=None, corrupt=None,
                            live=None) -> AsyncRoundResult:
    """:func:`async_round_step` on the RESIDENT ``(N, width)`` buffers
    (engine layout contract): ``y_tag`` is an ``(N, width)`` buffer and
    ``y`` comes back ``(1, width)``.  Same arithmetic per column, so
    packed async trajectories are bitwise identical to the tree path
    per realization, exactly like the synchronous engine.  ``mesh``
    shards the edges and every async carrier on the agent axis (mesh
    contract in :mod:`repro.fed.engine`)."""
    if mesh is not None:
        engine.validate_mesh(cfg, mesh, local_solver)
    key, k_part, k_solve = jax.random.split(key, 3)

    z_seen = t if cfg.compressed else z
    z_seen = engine.robust_seen(cfg, z_seen, live, meta, mesh)
    y, v_fresh = engine.coordinator_edge_packed(cfg, z, z_seen, meta,
                                                prox_h, mesh)

    fresh_col = (staleness == 0).reshape(-1, 1)
    v_eff = jnp.where(fresh_col, v_fresh, 2.0 * y_tag - z)
    y_tag_new = jnp.where(fresh_col, y, y_tag)   # (1, w) broadcasts

    w, aux = engine.run_solvers(local_solver, x, v_eff, k_solve,
                                cfg.n_agents)
    w = engine.apply_corruption(w, corrupt)

    u = arrival_mask(k_part, cfg, staleness, arrival, live)
    u, ok = engine.increment_guard(cfg, w, u, meta)

    x_upd, z_upd = engine.agent_edge_packed(cfg, u, w, x, z, y, z_seen,
                                            prox_h, mesh)

    arrived = u != 0
    stale_arrival = (arrived & ~fresh_col.reshape(-1)).reshape(-1, 1)
    z_tagged = z + 2.0 * cfg.damping * (w - y_tag)
    z_new = jnp.where(stale_arrival, z_tagged, z_upd)

    keep = (~arrived) & (staleness < cfg.staleness.max_staleness)
    if live is not None:
        keep = keep & (jnp.asarray(live).reshape(-1) != 0)
    if ok is not None:
        keep = keep & ok
    x_new = jnp.where(keep.reshape(-1, 1), w, x_upd)

    s_new = _advance_staleness(staleness, u, cfg.staleness.max_staleness,
                               live)

    t_new = (engine.transmit(cfg, z_new, t, u, meta) if cfg.compressed
             else z_new)

    return AsyncRoundResult(x=x_new, z=z_new, t=t_new, y=y,
                            y_tag=y_tag_new, staleness=s_new,
                            next_key=key, u=u, aux=aux)


# ---------------------------------------------------------------------------
# Schedule analysis: the staleness semantics replayed on the host, for
# privacy composition (and broker-schedule validation)
# ---------------------------------------------------------------------------

def effective_counts(schedule, max_staleness: int, live=None) \
        -> Tuple[np.ndarray, np.ndarray]:
    """Per-agent effective composition of a realized arrival schedule.

    ``schedule`` is the ``(R, N)`` 0/1 arrival record (one row per
    round, e.g. stacked ``AsyncRoundResult.u``).  Returns
    ``(arrivals, released_rounds)`` int64 ``(N,)`` vectors:

    * ``arrivals[i]`` -- how many increments agent i released (its
      effective participation count);
    * ``released_rounds[i]`` -- how many ROUNDS of local training those
      increments carried (an increment ``s`` rounds stale carries
      ``s + 1`` rounds of epochs).  Work discarded at the K = 0 bound
      was never transmitted and charges nothing -- DP composes over
      released information only.

    This replays :func:`_advance_staleness` on the host, so the counts
    agree with what the in-jit model realized.  ``live`` (an optional
    ``(R, N)`` 0/1 liveness matrix from a faulty run's ``FaultRecord``)
    pins evicted agents' counters at 0 the same way the in-jit model
    does; released-round charges from BEFORE an eviction are kept --
    that information left the agent, so DP must still pay for it."""
    sched = np.asarray(schedule)
    if sched.ndim != 2:
        raise ValueError(f"schedule must be (n_rounds, n_agents), got "
                         f"shape {sched.shape}")
    lv = _check_live(live, sched.shape)
    r_rounds, n = sched.shape
    s = np.zeros(n, np.int64)
    arrivals = np.zeros(n, np.int64)
    released = np.zeros(n, np.int64)
    for r in range(r_rounds):
        u = sched[r] != 0
        if lv is not None:
            u = u & (lv[r] != 0)
        arrivals += u
        released += np.where(u, s + 1, 0)
        s = np.where(u, 0,
                     np.where(s < max_staleness, s + 1, s))
        if lv is not None:
            s = np.where(lv[r] != 0, s, 0)
    return arrivals, released


def _check_live(live, shape) -> Optional[np.ndarray]:
    if live is None:
        return None
    lv = np.asarray(live)
    if lv.shape != tuple(shape):
        raise ValueError(f"live matrix shape {lv.shape} does not match "
                         f"schedule shape {tuple(shape)}")
    return lv


def validate_schedule(schedule, max_staleness: int, live=None) -> None:
    """Raise ValueError when a schedule violates the hard bound: an
    agent may never hold work more than ``max_staleness`` rounds old
    when increments are pending (the in-jit model would force such an
    arrival; a recorded schedule claiming otherwise is corrupt).  With
    a ``live`` matrix (faulty runs), evicted agents are exempt from the
    bound while dead -- their pending work was abandoned, not held --
    but an arrival from a dead agent is itself a violation."""
    sched = np.asarray(schedule)
    if sched.ndim != 2:
        raise ValueError(f"schedule must be (n_rounds, n_agents), got "
                         f"shape {sched.shape}")
    lv = _check_live(live, sched.shape)
    n = sched.shape[1]
    s = np.zeros(n, np.int64)
    for r, row in enumerate(sched):
        u = row != 0
        alive = np.ones(n, bool) if lv is None else (lv[r] != 0)
        ghost = u & ~alive
        if ghost.any():
            raise ValueError(
                f"schedule is inconsistent with the live matrix: agents "
                f"{np.nonzero(ghost)[0].tolist()} arrive in round {r} "
                f"while evicted")
        over = (~u) & (s >= max_staleness) & (s > 0) & alive
        if over.any():
            raise ValueError(
                f"schedule violates max_staleness={max_staleness}: "
                f"agents {np.nonzero(over)[0].tolist()} miss round {r} "
                f"while holding work {int(s[over].max())} rounds old")
        s = np.where(u, 0, np.where(s < max_staleness, s + 1, s))
        s = np.where(alive, s, 0)
