"""The single Fed-PLT round engine: Algorithm 1 on agent-stacked pytrees.

Every leaf of the state pytrees carries a leading agent axis ``(N, ...)``;
a dense ``(N, n)`` array (the convex experiments in :mod:`repro.core`) is
just the single-leaf case, a stacked model parameter pytree
(:mod:`repro.fed.runtime`) the general one.  One round:

  coordinator:  y = prox_{rho h / N}( mean_i z_i )            (Lemma 6)
  agents i active (u_i ~ Ber(p_i)):
      v_i   = 2 y - z_i                                       (reflection)
      x_i   <- N_e epochs of the local solver on
               d_i(w) = f_i(w) + ||w - v_i||^2/(2 rho),  warm start x_i
      z_i   <- z_i + 2 * damping * (x_i - y)
  agents inactive: state unchanged.

The local solver is pluggable (:data:`LocalSolver`, built by name from
the :mod:`repro.fed.solvers` registry): adapters supply the gradient
oracle / per-agent vmap; the *round topology* -- coordinator prox,
reflection, participation masking, and the compressed z-exchange --
lives only here, so ``core/fedplt.py`` and ``fed/runtime.py`` cannot
diverge again.  Agents need not be uniform: ``round_step`` accepts a
partition of the agent axis into :class:`SolverGroup` slices (each with
its own solver/epochs/step size, see :func:`run_solvers`) and
``participation`` may be a per-agent vector -- the paper's "agents
choose their local training solver" and per-agent Prop. 4 accounting,
at engine level.

Compressed uplink (beyond-paper): agents transmit the compressed
increment ``C(z_new - t)`` and the coordinator's copy ``t`` advances by
exactly what was transmitted.  ``t`` therefore lags ``z`` by the
never-transmitted residual, which *is* error feedback (an explicit error
memory would double-count the residual and diverge).

Round-edge backends: ``RoundConfig.engine_backend`` selects how the
round's memory-bound coordinator edges execute -- ``"xla"`` (default)
is the historical per-leaf ``tree_map`` path; ``"pallas"`` packs the
agent stack into one ``(N, M_total)`` buffer and runs the two fused
:mod:`repro.kernels.round_edge` kernels (mean + prox + reflection;
z-update + participation selects), collapsing the coordinator edge to
TWO launches.  Parity contract: the kernels are bit-identical to the
per-leaf edge formulas as materialized values (asserted against the
ref oracles across the whole prox table), and cross-backend
trajectories agree to float32 rounding.  Exact bitwise equality of
whole jitted rounds is NOT promised: XLA refolds the coordinator
chain's constants per consumer/program/shape -- the xla backend's own
``run()`` and ``step()`` already differ bitwise at some shapes -- so
the kernels mirror the unfused path's typical compilation (chain
duplication per consumer, pinned prox scales in ``core/prox.py``),
which makes most full-round configurations agree bit-for-bit in
practice.

State layouts -- the LAYOUT CONTRACT (ROADMAP item 1):
``RoundConfig.state_layout`` selects the round-to-round representation
of the federated state ``(x, z, t)``:

* ``"tree"`` (default): agent-stacked pytrees, the historical layout.
  Every packed-backend feature (fused edges, packed compress) pays a
  ``pack_leaves``/``unpack_leaves`` round-trip per use.
* ``"packed"``: ONE resident ``(N, M_total)`` buffer per state
  variable plus one static :class:`repro.fed.compress.PackedMeta`,
  packed once at ``init``.  Every round-to-round transition -- both
  fused round-edge kernels, the compressed z-exchange, participation
  selects, the Krasnosel'skii update, and (for gd/agd/sgd) the local
  solver itself -- runs directly on the buffer
  (:func:`packed_round_step`); the tree form is reconstructed only at
  the API boundary (consensus, metrics, checkpointing) and inside the
  gradient oracle (``unpack -> fgrad -> pack``, traced into the same
  jit).  A packed pallas round therefore contains ZERO concatenate /
  gather ops on the state path (asserted in tests via
  :func:`count_primitives`); the remaining layout traffic is the
  oracle's static slice/update-slice chain, which touches gradient
  values, not state.

  Parity: packed-resident trajectories are BITWISE identical to the
  tree-resident path per realization, under both engine backends and
  every registry compressor (asserted in tests).  The packed edges
  compute the same per-column arithmetic the per-leaf path computes
  (columns are independent; the agent-axis mean reduces in the same
  order), the PRNG key schedule is unchanged, and the two
  solver-stream exceptions fall back to unpack-around-the-solver
  rather than forking bits: ``noisy_gd`` (its per-leaf noise draws
  fold the key per leaf -- a single buffer would change the DP noise
  stream) and clipped runs (the clip norm reduces per leaf before
  summing -- one buffer would reorder the reduction).

  Padding columns (multi-leaf trees are lane-aligned) are dead state:
  they start at zero, may drift under an elementwise prox whose fixed
  point at 0 is nonzero, and are never unpacked; the compress paths
  zero out-of-segment columns under both backends, so the coordinator
  copy ``t``'s padding never advances.  ``jnp.where`` masking keeps
  them NaN-safe.

SHARDED ROUNDS -- the MESH CONTRACT (ROADMAP item 2): passing a
``mesh`` (an ``(agent, model)`` :class:`jax.sharding.Mesh`) to
:func:`round_step` / :func:`packed_round_step` (and the async variants)
runs the round's EDGES under ``shard_map``, with each device owning a
contiguous ``n_agents / agent_shards`` row block of every per-agent
carrier -- state buffers/leaves, the participation draw, and (async)
the staleness counters, ``y_tag``, and arrival rows all shard together
on the agent axis.  The uplink's agent mean becomes: an in-VMEM local
row reduce per shard (one fused kernel launch under the pallas
backend), ONE ``(1, width)`` cross-device ``psum`` of the partials,
then ``/ N -> prox -> reflection`` at coordinator size -- ``zbar``
still never materializes at agent-stack size.  The downlink consumes
the replicated coordinator point with purely local per-row work (the
second launch), so a sharded pallas round still runs exactly TWO fused
edge launches PER SHARD.  Everything between the edges (local solvers,
compression, masks, the key schedule) is row-wise or
coordinator-sized and runs under GSPMD unchanged, which is what keeps
the parity contract: a 1-DEVICE MESH IS BITWISE-IDENTICAL to the
unsharded engine on every layout x backend x compressor combo
(asserted in tests) -- the degenerate case of one code path, not a
separate engine -- while multi-device trajectories agree with
single-device to fp32 rounding only (cross-device psum reduction order
is not bitwise-stable, measured at one ulp in practice).
Solver groups must land shard-aligned (group boundaries at multiples
of the shard row block) or the round step raises before tracing;
a non-elementwise custom prox falls back to the unsharded edge formula
(GSPMD still shards the arithmetic, there is just no per-shard kernel).
MESH CONTRACT extension (robust aggregation): an order-statistic
aggregator (``RoundConfig.aggregator`` != "mean") needs the FULL agent
column, so the packed sharded uplink is preceded by an all-gather of
the per-shard row blocks on the agent axis
(:func:`repro.fed.robust.robust_seen_packed`) -- ``(N/shards, width)``
rows move per device per round, the documented price of a nonzero
breakdown point.  ``mean`` keeps the single-psum uplink untouched, and
a 1-device mesh remains bitwise identical to the unsharded engine
(the gather of one shard is the identity).  Tree-layout robust rounds
under a mesh compute the aggregate globally (GSPMD inserts the
collectives) before the sharded edges run.

NAMED SCOPES: each phase of the round carries a ``jax.named_scope``
(:func:`repro.fed.telemetry.scope`) on the shared function every path
calls -- ``fedplt.uplink`` (:func:`coordinator_edge`,
:func:`coordinator_edge_packed`), ``fedplt.aggregate``
(:func:`robust_seen`), ``fedplt.local_solver`` (:func:`run_solvers`),
``fedplt.downlink`` (:func:`agent_edge`, :func:`agent_edge_packed`,
:func:`participation_mask`) and ``fedplt.compress`` (:func:`transmit`).
A scope names ops in their metadata only, so trajectories keep their
bits; :func:`repro.fed.telemetry.op_scopes` maps a compiled round's ops
back to these phases.
"""

from __future__ import annotations

import dataclasses
from typing import (Any, Callable, Dict, NamedTuple, Optional, Sequence,
                    Tuple, Union)

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.fed import compress as compress_lib
from repro.fed import telemetry
from repro.fed.compress import compress_increment, get_compressor

tree_map = jax.tree_util.tree_map

# round-edge execution backends: "xla" = per-leaf tree_map ops;
# "pallas" = the fused repro.kernels.round_edge kernels on the packed
# (N, M_total) buffer -- ONE launch per edge (parity contract above)
ENGINE_BACKENDS = ("xla", "pallas")

# round-to-round state representations (layout contract above):
# "tree" = agent-stacked pytrees; "packed" = one resident (N, M_total)
# buffer per state variable + a static PackedMeta
ENGINE_LAYOUTS = ("tree", "packed")

# round synchrony modes: "off" = the bulk-synchronous round above;
# "stale" = the bounded-staleness async model (arrival mask + per-agent
# staleness counters; semantics in repro.fed.async_engine)
ASYNC_MODES = ("off", "stale")


def _numeric_scalar(name: str, value):
    """Normalize a config scalar to ``float`` with a clear construction
    error: strings (which ``float()`` would happily parse -- hiding the
    type bug until deep inside jit) and non-numerics raise ValueError;
    0-d numpy/jax arrays are accepted and unwrapped."""
    if isinstance(value, (str, bytes)):
        raise ValueError(
            f"{name} must be a number, got the string {value!r}")
    if getattr(value, "ndim", None) == 0:   # 0-d numpy/jax scalar
        return float(value)
    try:
        return float(value)
    except (TypeError, ValueError):
        raise ValueError(
            f"{name} must be a number, got {value!r}") from None


def _int_scalar(name: str, value) -> int:
    """Like :func:`_numeric_scalar` but for integer knobs: accepts ints
    and 0-d integer arrays, rejects strings, floats with a fractional
    part, and non-numerics -- at construction, not inside jit."""
    if isinstance(value, (str, bytes)):
        raise ValueError(
            f"{name} must be an integer, got the string {value!r}")
    if isinstance(value, bool):
        raise ValueError(f"{name} must be an integer, got {value!r}")
    if getattr(value, "ndim", None) == 0:
        value = value.item()
    try:
        as_int = int(value)
    except (TypeError, ValueError):
        raise ValueError(
            f"{name} must be an integer, got {value!r}") from None
    if as_int != value:
        raise ValueError(f"{name} must be an integer, got {value!r}")
    return as_int


@dataclasses.dataclass(frozen=True)
class StalenessConfig:
    """Bounded-staleness async-round knobs (ROADMAP item 3).

    ``mode="stale"`` turns the round's Bernoulli participation draw into
    an *arrival* draw: agents that arrive submit their increment (tagged
    with the coordinator point it was computed against) and pull a fresh
    reflection next round; agents that do not arrive KEEP TRAINING
    against their stale reflection, aging a per-agent staleness counter.
    ``max_staleness`` is the hard bound K: an agent holding work K
    rounds old is forced to arrive.  K = 0 permits no stale work at all
    -- a miss discards the round's local work, which is exactly the
    synchronous engine (bitwise per realization; contract in
    :mod:`repro.fed.async_engine`).
    """

    mode: str = "off"            # "off" | "stale"
    max_staleness: int = 0       # K: forced arrival at staleness K

    def __post_init__(self):
        if self.mode not in ASYNC_MODES:
            raise ValueError(
                f"unknown async mode {self.mode!r}; "
                f"known: {', '.join(ASYNC_MODES)}")
        k = _int_scalar("max_staleness", self.max_staleness)
        if k < 0:
            raise ValueError(f"max_staleness must be >= 0, got {k}")
        object.__setattr__(self, "max_staleness", k)

    @property
    def enabled(self) -> bool:
        return self.mode != "off"

# (x_stack, v_stack, key) -> (w_stack, aux); aux may be None.  The solver
# must be warm-started at x_stack (Section V-C1) -- the engine passes the
# previous local states as the first argument.
LocalSolver = Callable[[Any, Any, jax.Array], Tuple[Any, Any]]


class SolverGroup(NamedTuple):
    """A contiguous slice of the agent axis running its own local solver.

    ``round_step`` accepts a sequence of groups instead of one
    :data:`LocalSolver`: the stacked pytrees are partitioned along the
    agent axis (group g owns agents ``[sum(sizes[:g]), sum(sizes[:g+1]))``),
    each group's solver runs on its slice (vmapped within the group by
    whoever built it), and the results are re-stitched by concatenation.
    A single group is dispatched exactly like a bare solver (same key,
    no slicing), so a homogeneous "grouped" round is bit-identical to
    the historical path.
    """

    size: int
    solver: LocalSolver


# A round's solver assignment: one solver for every agent, or a
# partition of the agent axis into heterogeneous groups.
SolverAssignment = Union[LocalSolver, Sequence[SolverGroup]]

# Leaf-wise proximal operator of the coordinator regularizer h:
# (zbar, rho_eff) -> y, applied to the agent-mean tree with
# rho_eff = rho / N (Lemma 6).  None means h = 0 (identity).
ProxH = Optional[Callable[[Any, float], Any]]


@dataclasses.dataclass(frozen=True)
class RoundConfig:
    """Round-topology knobs shared by every Fed-PLT front end."""

    n_agents: int
    rho: float = 1.0
    # p: one scalar shared by every agent, or an (n_agents,)-tuple of
    # per-agent probabilities (Prop. 4 / heterogeneous deployments)
    participation: Union[float, Tuple[float, ...]] = 1.0
    # Krasnosel'skii relaxation: z <- z + 2*damping*(x - y).  damping = 1
    # is the paper's PRS; damping = 1/2 is Douglas-Rachford -- needed to
    # stabilize aggressively compressed exchanges.
    damping: float = 1.0
    # compressor name in the repro.fed.compress registry
    # (none | topk | int8 | adaptive_topk | anything registered)
    compression: str = "none"
    compress_ratio: float = 0.25      # top-k fraction kept (floor for adaptive)
    compress_energy: float = 0.95     # adaptive_topk per-agent energy target
    # "xla" = per-leaf registry compressors; "pallas" = packed agent-axis
    # buffer through the fused repro.kernels.compress kernels (one launch
    # per round, bit-identical output; non-accelerated compressors fall
    # back to the per-leaf path)
    compress_backend: str = "xla"
    # "xla" = per-leaf tree_map round edges; "pallas" = the fused
    # repro.kernels.round_edge kernels on the packed buffer (coordinator
    # prox + reflect in one launch, z-update + participation selects in
    # another; parity contract in the module docstring.  Non-elementwise
    # custom proxes and mixed-dtype trees fall back per edge)
    engine_backend: str = "xla"
    # "tree" = agent-stacked pytrees round to round; "packed" = one
    # resident (N, M_total) buffer per state variable (layout contract
    # in the module docstring; front ends dispatch on this to
    # packed_round_step and convert at the API boundary only)
    state_layout: str = "tree"
    # bounded-staleness async rounds: mode "off" keeps this config a
    # synchronous round; "stale" generalizes the participation draw to
    # an arrival mask with per-agent staleness counters (front ends
    # dispatch to repro.fed.async_engine when enabled)
    staleness: StalenessConfig = dataclasses.field(
        default_factory=StalenessConfig)
    # number of contiguous row blocks the agent axis is sharded into
    # when a mesh is passed to the round step (mesh contract in the
    # module docstring); 1 = unsharded.  Every shard owns
    # n_agents/agent_shards agents, so N must divide evenly
    agent_shards: int = 1
    # in-jit increment guards (fault tolerance): when enabled, each
    # agent row of the local-solve result is screened at the uplink --
    # a non-finite row (NaN/Inf), or one whose l2 norm exceeds
    # guard_norm_bound, is converted into a NON-ARRIVAL (u_i -> 0, the
    # quarantine row), so one corrupt increment cannot poison the
    # consensus mean.  With every row clean the guard multiplies u by
    # an all-ones mask: trajectories are bitwise unchanged
    guard_increments: bool = False
    guard_norm_bound: float = float("inf")   # inf = finiteness-only screen
    # coordinator aggregator (repro.fed.robust registry): "mean" keeps
    # the historical uplink bitwise; "trimmed_mean" (param = trim count
    # f), "coord_median", and "norm_clip_mean" (param = clip radius)
    # replace the agent mean with a robust statistic of the live rows
    # -- finite, guard-evading byzantine increments bounded by the
    # aggregator's breakdown point instead of steering the consensus
    aggregator: str = "mean"
    aggregator_param: float = 0.0

    def __post_init__(self):
        get_compressor(self.compression)  # fail fast on unknown names
        from repro.fed import robust as robust_lib
        object.__setattr__(
            self, "aggregator_param",
            robust_lib.validate_aggregator(
                self.aggregator, self.aggregator_param, self.n_agents))
        if self.compress_backend not in compress_lib.COMPRESS_BACKENDS:
            raise ValueError(
                f"unknown compress backend {self.compress_backend!r}; "
                f"known: {', '.join(compress_lib.COMPRESS_BACKENDS)}")
        if self.engine_backend not in ENGINE_BACKENDS:
            raise ValueError(
                f"unknown engine backend {self.engine_backend!r}; "
                f"known: {', '.join(ENGINE_BACKENDS)}")
        if self.state_layout not in ENGINE_LAYOUTS:
            raise ValueError(
                f"unknown state layout {self.state_layout!r}; "
                f"known: {', '.join(ENGINE_LAYOUTS)}")
        # damping gets the same construction-time screening as
        # participation below: a string "0.5" parses as a valid float,
        # so without this it would only blow up (or worse, silently
        # trace) deep inside the jitted round
        object.__setattr__(self, "damping",
                           _numeric_scalar("damping", self.damping))
        object.__setattr__(self, "rho", _numeric_scalar("rho", self.rho))
        shards = _int_scalar("agent_shards", self.agent_shards)
        if shards < 1:
            raise ValueError(f"agent_shards must be >= 1, got {shards}")
        object.__setattr__(self, "agent_shards", shards)
        if self.n_agents % shards:
            raise ValueError(
                f"n_agents={self.n_agents} is not divisible by "
                f"agent_shards={shards}: every shard owns an equal "
                f"contiguous row block of the agent axis -- choose "
                f"n_agents a multiple of the shard count (or reduce "
                f"agent_shards)")
        object.__setattr__(self, "guard_increments",
                           bool(self.guard_increments))
        bound = _numeric_scalar("guard_norm_bound", self.guard_norm_bound)
        if not bound > 0.0:   # rejects 0, negatives, and NaN
            raise ValueError(
                f"guard_norm_bound must be > 0 (inf disables the norm "
                f"screen), got {bound}")
        object.__setattr__(self, "guard_norm_bound", bound)
        if self.staleness is None:
            object.__setattr__(self, "staleness", StalenessConfig())
        elif not isinstance(self.staleness, StalenessConfig):
            raise ValueError(
                f"staleness must be a StalenessConfig, got "
                f"{self.staleness!r}")
        p = self.participation
        if isinstance(p, (str, bytes)):
            # a string is a __len__-bearing sequence of characters:
            # without this guard participation="0.5" would silently
            # tuple-ize into per-character draws (or crash later)
            raise ValueError(
                f"participation must be a probability or a per-agent "
                f"sequence of probabilities, got the string {p!r}")
        if getattr(p, "ndim", None) == 0:
            # a 0-d numpy/jax scalar: ndarray types carry __len__ (it
            # raises when called), so without this it would be
            # misdiagnosed as a malformed per-agent sequence
            object.__setattr__(self, "participation", float(p))
        elif isinstance(p, (list, tuple)) or hasattr(p, "__len__"):
            try:
                p = tuple(float(x) for x in p)
            except (TypeError, ValueError):
                raise ValueError(
                    f"per-agent participation must contain numbers, "
                    f"got {self.participation!r}") from None
            object.__setattr__(self, "participation", p)
            if len(p) != self.n_agents:
                raise ValueError(
                    f"per-agent participation has {len(p)} entries for "
                    f"n_agents={self.n_agents}")

    @property
    def compressed(self) -> bool:
        return self.compression != "none"

    @property
    def robust_aggregator(self) -> Optional[str]:
        """The aggregator name when the uplink is actually robust, else
        None: ``"mean"`` -- and ``"trimmed_mean"`` at ``f = 0``, which
        IS the mean -- resolve to the historical
        :func:`survivor_mean_input` path, keeping clean configurations
        bitwise identical to the pre-robustness engine."""
        if self.aggregator == "mean":
            return None
        if (self.aggregator == "trimmed_mean"
                and int(self.aggregator_param) == 0):
            return None
        return self.aggregator


class RoundResult(NamedTuple):
    x: Any               # pytree, leaves (N, ...)
    z: Any               # pytree, leaves (N, ...)
    t: Any               # coordinator's copy of z (== z when uncompressed)
    y: Any               # pytree, coordinator model (no agent axis)
    next_key: jax.Array  # carried PRNG state
    u: jnp.ndarray       # (N,) participation draw of this round
    aux: Any             # whatever the local solver returned


# ---------------------------------------------------------------------------
# Round pieces
# ---------------------------------------------------------------------------

def agent_mean(z: Any) -> Any:
    """Mean over the leading agent axis, leaf-wise."""
    return tree_map(lambda zl: jnp.mean(zl, axis=0), z)


def coordinator_prox(z: Any, cfg: RoundConfig, prox_h: ProxH = None) -> Any:
    """``y = prox_{rho h / N}(mean_i z_i)`` on pytrees (Lemma 6)."""
    zbar = agent_mean(z)
    if prox_h is None:
        return zbar
    rho_eff = cfg.rho / cfg.n_agents
    return tree_map(lambda zl: prox_h(zl, rho_eff), zbar)


def reflect(y: Any, z: Any) -> Any:
    """``v = 2 y - z`` with y broadcast across the agent axis."""
    return tree_map(lambda yl, zl: 2.0 * yl[None] - zl, y, z)


@telemetry.scope("fedplt.downlink")
def participation_mask(key: jax.Array, cfg: RoundConfig) -> jnp.ndarray:
    """One Bernoulli(p_i) draw per agent, as a float (N,) vector.

    Scalar ``cfg.participation`` reproduces the historical uniform draw
    bit-for-bit; an ``(N,)`` tuple draws each agent at its own rate from
    the same key (one uniform per agent either way)."""
    p = cfg.participation
    if isinstance(p, tuple):
        p = jnp.asarray(p, jnp.float32)
    return jax.random.bernoulli(
        key, p, (cfg.n_agents,)).astype(jnp.float32)


def masked_mix(u: jnp.ndarray, new: Any, old: Any) -> Any:
    """Select ``new`` where the agent participated, ``old`` otherwise,
    leaf-wise.  ``jnp.where`` (not ``u*new + (1-u)*old``) so a diverged
    local solve (NaN/Inf) cannot leak into agents that sat the round
    out; for finite values the two are bit-identical with u in {0, 1}."""
    mask = u != 0

    def mix(nl, ol):
        return jnp.where(mask.reshape((-1,) + (1,) * (nl.ndim - 1)),
                         nl, ol)

    return tree_map(mix, new, old)


# ---------------------------------------------------------------------------
# Fault tolerance: corruption injection, in-jit increment guards, and the
# survivor mean (live masks).  All three are BITWISE NO-OPS when disabled
# (corrupt=None / guards off / live=None) -- the fault-free graph is the
# historical graph, which is what keeps clean trajectories replayable
# against recordings made before this layer existed.
# ---------------------------------------------------------------------------

def apply_corruption(w: Any, corrupt) -> Any:
    """Inject a recorded corruption row into the solver output.

    ``corrupt`` is the broker-realized corruption, in one of two forms:

    * an ``(N,)`` row (the historical encoding): agent ``i``'s row of
      every leaf is multiplied by ``corrupt[i]`` wherever the entry is
      non-zero-or-NaN (NaN multipliers poison the row to NaN, Inf to
      Inf, a huge finite value trips the norm guard); zero entries
      leave the row untouched.
    * an ``(N, 2)`` ``[mult, add]`` pair per agent (the byzantine
      encoding): flagged rows -- any row whose pair is not ``(0, 0)``
      -- become ``w * mult + add``, which expresses the guard-evading
      attacks (``sign_flip`` = ``(-1, 0)``, ``scale(v)`` = ``(v, 0)``,
      ``drift(v)`` = ``(1, v)``) as well as every legacy multiplicative
      corruption (``(v, 0)``).

    ``None`` returns ``w`` unchanged.  This is the numerics half of a
    ``FaultPlan`` corruption event: the broker only RECORDS the rows
    (timing side), the jitted round applies them here, so replaying the
    rows reproduces the corruption bit-for-bit.  Plans without
    byzantine events keep realizing the ``(N,)`` form, so their
    recordings replay on the exact historical graph."""
    if corrupt is None:
        return w
    c = jnp.asarray(corrupt, jnp.float32)
    if c.ndim == 2:
        mult, add = c[:, 0], c[:, 1]
        # NaN != 0 is True: NaN entries flag the row (poison semantics)
        flagged = (mult != 0.0) | (add != 0.0)

        def poison(l):
            shape = (-1,) + (1,) * (l.ndim - 1)
            return jnp.where(
                flagged.reshape(shape),
                l * mult.astype(l.dtype).reshape(shape)
                + add.astype(l.dtype).reshape(shape), l)

        return tree_map(poison, w)
    c = c.reshape(-1)
    flagged = c != 0.0        # NaN != 0 is True: NaN rows are flagged

    def poison(l):
        shape = (-1,) + (1,) * (l.ndim - 1)
        return jnp.where(flagged.reshape(shape),
                         l * c.astype(l.dtype).reshape(shape), l)

    return tree_map(poison, w)


def _row_sq_norms(w: Any, meta=None) -> jnp.ndarray:
    """Per-agent squared l2 norm over the non-agent axes, in float32.
    For a resident packed buffer pass ``meta``: lane-padding columns are
    zeroed BEFORE squaring (NaN * 0 is NaN -- masking after the square
    would let drifted padding state trip the guard)."""
    leaves = jax.tree_util.tree_leaves(w)
    if meta is not None and len(leaves) == 1:
        buf = leaves[0]
        mask = np.zeros((buf.shape[-1],), bool)
        for a, b in meta.segments:
            mask[a:b] = True
        vals = buf if mask.all() else jnp.where(
            jnp.asarray(mask)[None, :], buf, 0.0)
        return jnp.sum(jnp.square(vals.astype(jnp.float32)), axis=1)
    total = None
    for l in leaves:
        sq = jnp.sum(jnp.square(l.astype(jnp.float32)),
                     axis=tuple(range(1, l.ndim)))
        total = sq if total is None else total + sq
    return total


def increment_guard(cfg: RoundConfig, w: Any, u: jnp.ndarray, meta=None
                    ) -> Tuple[jnp.ndarray, Optional[jnp.ndarray]]:
    """The in-jit uplink screen: returns ``(u_guarded, ok)`` where
    ``ok`` is the per-agent ``(N,)`` bool clean mask (``None`` when
    guards are off).  A corrupt row -- non-finite, or l2 norm above
    ``cfg.guard_norm_bound`` -- becomes a NON-ARRIVAL: ``u_i -> 0``,
    exactly as if the agent had not arrived, and the NaN-safe
    ``jnp.where`` selects downstream keep the poison out of
    ``(x, z, t)``.  With every row clean ``u * ok`` multiplies by ones,
    so guarded clean rounds are bitwise identical to unguarded ones."""
    if not cfg.guard_increments:
        return u, None
    sq = _row_sq_norms(w, meta)
    ok = jnp.isfinite(sq)
    if np.isfinite(cfg.guard_norm_bound):
        ok = ok & (sq <= jnp.float32(cfg.guard_norm_bound) ** 2)
    return u * ok.astype(u.dtype), ok


def survivor_mean_input(cfg: RoundConfig, z_seen: Any, live) -> Any:
    """Fold an eviction ``live`` row into the coordinator's input so the
    engine's fixed mean-over-N becomes the mean over SURVIVORS:
    ``z * live * (N / n_live)`` sums to ``sum_live(z)`` and the edges
    divide by N downstream, i.e. ``mean_live(z)``; dead rows contribute
    exact zeros.  Premultiplying here -- rather than teaching every
    uplink a second mask -- is what makes survivor averaging work on
    every layout x backend x mesh combo without touching a kernel: the
    scaled buffer is simply not ``z``, so the lagged ``z_seen`` path
    engages everywhere (including the fused downlink, which recomputes
    the coordinator chain from the SAME scaled input).  ``live=None``
    returns ``z_seen`` unchanged -- the historical graph."""
    if live is None:
        return z_seen
    lv = jnp.asarray(live, jnp.float32).reshape(-1)
    scale = lv * (cfg.n_agents / jnp.sum(lv))
    return tree_map(
        lambda l: l * scale.astype(l.dtype).reshape(
            (-1,) + (1,) * (l.ndim - 1)),
        z_seen)


@telemetry.scope("fedplt.aggregate")
def robust_seen(cfg: RoundConfig, z_seen: Any, live, meta=None,
                mesh=None) -> Any:
    """The uplink's aggregation input transform -- THE one place the
    coordinator's reduction is shaped.  ``aggregator="mean"`` (and
    ``trimmed_mean`` at ``f = 0``) calls :func:`survivor_mean_input`
    exactly: clean configurations keep the historical graph bitwise
    (including the ``z_seen is z`` object-identity the lagged-path
    dispatch keys on).  A robust aggregator computes its ``(1, M)``
    statistic over the LIVE rows and broadcasts it back across the
    agent axis, so the unchanged edges' fixed mean-over-N reproduces
    the robust ``y`` -- one transform, every layout x backend x
    compressor x mesh combo (rationale in :mod:`repro.fed.robust`).

    ``meta`` marks the packed form (``z_seen`` a resident ``(N, width)``
    buffer); without it ``z_seen`` is an agent-stacked pytree."""
    name = cfg.robust_aggregator
    if name is None:
        return survivor_mean_input(cfg, z_seen, live)
    from repro.fed import robust as robust_lib

    if meta is None:
        return robust_lib.robust_seen_tree(
            z_seen, live, name=name, param=cfg.aggregator_param,
            backend=cfg.engine_backend, mesh=mesh)
    col = None if mesh is None else _mesh_col_axis(mesh, z_seen.shape[1])
    return robust_lib.robust_seen_packed(
        z_seen, live, name=name, param=cfg.aggregator_param,
        meta=meta, backend=cfg.engine_backend, mesh=mesh, col_axis=col)


def live_mask_rows(u: jnp.ndarray, live) -> jnp.ndarray:
    """Zero the arrival/participation row of evicted agents (``live``
    an ``(N,)`` 0/1 row; None = everyone live, returned unchanged)."""
    if live is None:
        return u
    return u * jnp.asarray(live, u.dtype).reshape(-1)


# ---------------------------------------------------------------------------
# Round edges: the coordinator-side memory-bound passes, with a fused
# packed-buffer backend
# ---------------------------------------------------------------------------

def fusible_prox(prox_h: ProxH) -> bool:
    """Whether ``prox_h`` may be traced into the fused uplink kernel:
    h = 0, or a :func:`repro.core.prox.make_prox` table entry (every one
    is elementwise and carries the ``elementwise`` tag).  Untagged
    custom callables take the XLA path."""
    return prox_h is None or getattr(prox_h, "elementwise", False)


def _uniform_stack(*trees) -> bool:
    """True when every leaf of every tree shares one (agent count,
    dtype) -- the precondition for packing them into one buffer (the
    same rule :func:`repro.fed.compress.compress_increment` uses)."""
    leaves = [l for t in trees for l in jax.tree_util.tree_leaves(t)]
    return len({(l.shape[0], jnp.result_type(l)) for l in leaves}) == 1


# ---------------------------------------------------------------------------
# Mesh plumbing (the mesh contract in the module docstring)
# ---------------------------------------------------------------------------

def mesh_agent_shards(mesh) -> int:
    """The extent of ``mesh``'s agent axis (1 when ``mesh`` is None)."""
    if mesh is None:
        return 1
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    if "agent" not in sizes:
        raise ValueError(
            f"sharded rounds need a mesh with an 'agent' axis, got "
            f"axes {tuple(mesh.axis_names)}")
    return int(sizes["agent"])


def _mesh_col_axis(mesh, width: int) -> Optional[str]:
    """The mesh axis that additionally shards the packed column axis:
    ``"model"`` when the mesh has one whose extent divides the buffer
    width, else None (columns replicated within each agent shard)."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    m = int(sizes.get("model", 0))
    return "model" if m > 1 and width % m == 0 else None


def validate_mesh(cfg: RoundConfig, mesh,
                  local_solver: SolverAssignment = None) -> None:
    """Trace-time screening of a sharded round: the mesh's agent axis
    must evenly partition the agent axis, agree with
    ``cfg.agent_shards`` when that was pinned, and every solver-group
    boundary must land on a shard boundary (group slicing happens on
    the host; a group straddling shards would silently gather rows
    across devices every round)."""
    shards = mesh_agent_shards(mesh)
    if cfg.n_agents % shards:
        raise ValueError(
            f"n_agents={cfg.n_agents} is not divisible by the mesh's "
            f"agent axis ({shards} shards): every shard owns an equal "
            f"contiguous row block -- choose n_agents a multiple of "
            f"the shard count or shrink the mesh")
    if cfg.agent_shards > 1 and cfg.agent_shards != shards:
        raise ValueError(
            f"RoundConfig.agent_shards={cfg.agent_shards} but the mesh "
            f"has {shards} agent shards: drop one of the two or make "
            f"them agree")
    if (shards > 1 and local_solver is not None
            and not callable(local_solver)
            and not isinstance(local_solver, SolverGroup)):
        rows = cfg.n_agents // shards
        start = 0
        for g_idx, grp in enumerate(tuple(local_solver)[:-1]):
            start += grp.size
            if start % rows:
                raise ValueError(
                    f"solver group {g_idx} ends at agent {start}, "
                    f"inside an agent shard: with {shards} shards of "
                    f"{rows} agents each, group boundaries must be "
                    f"multiples of {rows} -- resize the groups or "
                    f"change the shard count")


def _row_specs(tree):
    """Per-leaf ``P('agent', None, ...)`` specs for agent-stacked
    pytrees (rank-matched, columns replicated)."""
    return tree_map(
        lambda l: P(*(("agent",) + (None,) * (l.ndim - 1))), tree)


def _rep_specs(tree):
    """Per-leaf fully-replicated specs (coordinator pytrees carry no
    agent axis)."""
    return tree_map(lambda l: P(*((None,) * l.ndim)), tree)


def _uplink_sharded_xla(cfg: RoundConfig, z: jnp.ndarray,
                        z_seen: jnp.ndarray, prox_h: ProxH, mesh,
                        col: Optional[str]) \
        -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Sharded packed uplink, xla backend: local column sums per shard,
    one psum of the ``(1, width)`` partials, then the coordinator-sized
    chain and the local reflection -- the same formulation the fused
    sharded kernel realizes (bitwise on a 1-device mesh: ``div(psum(
    sum), N)`` == ``div(sum, N)`` and the reflection reads the shared
    ``y``, exactly like the unsharded xla edge)."""
    n = cfg.n_agents
    rho_eff = cfg.rho / cfg.n_agents
    lagged = z_seen is not z

    def body(z_l, *rest):
        seen = rest[0] if rest else z_l
        part = jnp.sum(seen, axis=0, keepdims=True)
        zbar = jax.lax.psum(part, "agent") / n
        y = zbar if prox_h is None else prox_h(zbar, rho_eff)
        return y, 2.0 * y - z_l

    spec = P("agent", col)
    f = shard_map(body, mesh=mesh,
                  in_specs=(spec, spec) if lagged else (spec,),
                  out_specs=(P(None, col), spec), check_vma=False)
    return f(z, z_seen) if lagged else f(z)


def _downlink_sharded_xla(cfg: RoundConfig, u: jnp.ndarray,
                          w: jnp.ndarray, x: jnp.ndarray,
                          z: jnp.ndarray, y: jnp.ndarray, mesh,
                          col: Optional[str]) \
        -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Sharded packed downlink, xla backend: purely local per-row work
    consuming the replicated coordinator point (op-for-op the unsharded
    xla edge, which already consumes ``y``)."""
    def body(u_l, w_l, x_l, z_l, y_l):
        mask = (u_l != 0).reshape(-1, 1)
        x_new = jnp.where(mask, w_l, x_l)
        z_upd = z_l + 2.0 * cfg.damping * (w_l - y_l)
        return x_new, jnp.where(mask, z_upd, z_l)

    spec = P("agent", col)
    f = shard_map(body, mesh=mesh,
                  in_specs=(P("agent"), spec, spec, spec, P(None, col)),
                  out_specs=(spec, spec), check_vma=False)
    return f(u.reshape(-1), w, x, z, y)


def _tree_uplink_sharded(cfg: RoundConfig, z: Any, z_seen: Any,
                         prox_h: ProxH, mesh) -> Tuple[Any, Any]:
    """Sharded uplink on agent-stacked pytrees: per-leaf local sums,
    one psum per leaf, chain at coordinator size.  The ``y`` leaves are
    COMPLETE after the agent-axis reduction, so ANY per-leaf prox --
    including non-elementwise customs the packed paths must refuse --
    is applied here unchanged."""
    n = cfg.n_agents
    rho_eff = cfg.rho / cfg.n_agents
    lagged = z_seen is not z

    def body(z_t, *rest):
        seen = rest[0] if rest else z_t
        zbar = tree_map(
            lambda sl: jax.lax.psum(jnp.sum(sl, axis=0), "agent") / n,
            seen)
        y = (zbar if prox_h is None
             else tree_map(lambda l: prox_h(l, rho_eff), zbar))
        v = tree_map(lambda yl, zl: 2.0 * yl[None] - zl, y, z_t)
        return y, v

    rows = _row_specs(z)
    y_specs = tree_map(lambda l: P(*((None,) * (l.ndim - 1))), z)
    f = shard_map(body, mesh=mesh,
                  in_specs=(rows, _row_specs(z_seen)) if lagged
                  else (rows,),
                  out_specs=(y_specs, rows), check_vma=False)
    return f(z, z_seen) if lagged else f(z)


def _tree_downlink_sharded(cfg: RoundConfig, u: jnp.ndarray, w: Any,
                           x: Any, z: Any, y: Any,
                           mesh) -> Tuple[Any, Any]:
    """Sharded downlink on agent-stacked pytrees: the Krasnosel'skii
    update + NaN-safe participation selects per row block, consuming
    the replicated coordinator tree."""
    def body(u_l, w_t, x_t, z_t, y_t):
        mask = u_l != 0

        def mix(nl, ol):
            return jnp.where(
                mask.reshape((-1,) + (1,) * (nl.ndim - 1)), nl, ol)

        x_new = tree_map(mix, w_t, x_t)
        z_upd = tree_map(
            lambda zl, wl, yl: zl + 2.0 * cfg.damping * (wl - yl[None]),
            z_t, w_t, y_t)
        return x_new, tree_map(mix, z_upd, z_t)

    f = shard_map(body, mesh=mesh,
                  in_specs=(P("agent"), _row_specs(w), _row_specs(x),
                            _row_specs(z), _rep_specs(y)),
                  out_specs=(_row_specs(x), _row_specs(z)),
                  check_vma=False)
    return f(u.reshape(-1), w, x, z, y)


@telemetry.scope("fedplt.uplink")
def coordinator_edge(cfg: RoundConfig, z: Any, z_seen: Any,
                     prox_h: ProxH = None, mesh=None) -> Tuple[Any, Any]:
    """The round's uplink edge: ``y = prox_{rho h/N}(mean_i z_seen_i)``
    and the reflection ``v = 2 y - z`` (``z_seen`` is the coordinator's
    lagged copy ``t`` under a compressed exchange, ``z`` itself
    otherwise).

    Under ``cfg.engine_backend == "pallas"`` (uniform stack, fusible
    prox) the leaves are packed into one ``(N, M_total)`` buffer and the
    agent-axis mean-reduce, the elementwise prox, and the reflected
    broadcast run as ONE :mod:`repro.kernels.round_edge` launch --
    ``zbar`` never materializes in HBM (parity contract: module
    docstring).  With a ``mesh`` the same edge runs under ``shard_map``
    (mesh contract: module docstring)."""
    if (cfg.engine_backend == "pallas" and fusible_prox(prox_h)
            and _uniform_stack(z, z_seen)):
        from repro.kernels.round_edge import ops as edge_ops

        buf_z, meta = compress_lib.pack_leaves(z)
        buf_t = (None if z_seen is z
                 else compress_lib.pack_leaves(z_seen)[0])
        if mesh is not None:
            y_buf, v_buf = edge_ops.round_uplink_sharded(
                buf_z, buf_t, mesh=mesh, n_total=cfg.n_agents,
                prox=prox_h, rho_eff=cfg.rho / cfg.n_agents,
                col_axis=_mesh_col_axis(mesh, buf_z.shape[1]))
        else:
            y_buf, v_buf = edge_ops.round_uplink(
                buf_z, buf_t, prox=prox_h,
                rho_eff=cfg.rho / cfg.n_agents)
        return (compress_lib.unpack_coord(y_buf, meta),
                compress_lib.unpack_leaves(v_buf, meta))
    if mesh is not None:
        return _tree_uplink_sharded(cfg, z, z_seen, prox_h, mesh)
    y = coordinator_prox(z_seen, cfg, prox_h)
    return y, reflect(y, z)


@telemetry.scope("fedplt.downlink")
def agent_edge(cfg: RoundConfig, u: jnp.ndarray, w: Any, x: Any, z: Any,
               y: Any, z_seen: Any = None,
               prox_h: ProxH = None, mesh=None) -> Tuple[Any, Any]:
    """The round's downlink edge: the Krasnosel'skii update
    ``z + 2*damping*(w - y)`` and the participation selects of both
    state variables (``x`` from the solver result ``w``, ``z`` from the
    update), returning ``(x_new, z_new)``.

    Under ``cfg.engine_backend == "pallas"`` (uniform stack, fusible
    prox) both updates run as ONE fused :mod:`repro.kernels.round_edge`
    launch on the packed buffer, the mask streamed as an ``(N,)``
    vector -- ``jnp.where`` semantics preserved, so a diverged (NaN)
    local solve still cannot leak into agents that sat the round out.
    The kernel recomputes the coordinator chain from ``z_seen`` (the
    same source :func:`coordinator_edge` read) instead of consuming
    ``y``: the unfused path never materializes ``y`` between the prox
    and the z-update, and parity wants the compiler handed the same
    expression (see the kernel docstrings; contract in the module
    docstring).
    """
    if z_seen is None:
        z_seen = z
    if (cfg.engine_backend == "pallas" and fusible_prox(prox_h)
            and _uniform_stack(x, w, z, z_seen)):
        from repro.kernels.round_edge import ops as edge_ops

        x_buf, meta = compress_lib.pack_leaves(x)
        w_buf = compress_lib.pack_leaves(w)[0]
        z_buf = compress_lib.pack_leaves(z)[0]
        if mesh is not None:
            y_buf = compress_lib.pack_coord(y, meta)
            xb, zb = edge_ops.round_downlink_sharded(
                x_buf, w_buf, z_buf, y_buf, u, mesh=mesh,
                damping=cfg.damping,
                col_axis=_mesh_col_axis(mesh, x_buf.shape[1]))
        else:
            t_buf = (None if z_seen is z
                     else compress_lib.pack_leaves(z_seen)[0])
            xb, zb = edge_ops.round_downlink(
                x_buf, w_buf, z_buf, u, t_buf, prox=prox_h,
                rho_eff=cfg.rho / cfg.n_agents, damping=cfg.damping)
        return (compress_lib.unpack_leaves(xb, meta),
                compress_lib.unpack_leaves(zb, meta))
    if mesh is not None:
        return _tree_downlink_sharded(cfg, u, w, x, z, y, mesh)
    x_new = masked_mix(u, w, x)
    z_upd = tree_map(
        lambda zl, wl, yl: zl + 2.0 * cfg.damping * (wl - yl[None]),
        z, w, y)
    return x_new, masked_mix(u, z_upd, z)


# ---------------------------------------------------------------------------
# Packed-resident round edges: the same arithmetic on the resident
# (N, M_total) buffer -- no pack/unpack anywhere (layout contract in the
# module docstring)
# ---------------------------------------------------------------------------

@telemetry.scope("fedplt.uplink")
def coordinator_edge_packed(cfg: RoundConfig, z: jnp.ndarray,
                            z_seen: jnp.ndarray, meta,
                            prox_h: ProxH = None, mesh=None) \
        -> Tuple[jnp.ndarray, jnp.ndarray]:
    """:func:`coordinator_edge` on resident ``(N, width)`` buffers:
    returns ``(y, v)`` with ``y`` the ``(1, width)`` coordinator buffer.

    The pallas backend hands the buffers straight to the fused kernel
    (the tree path's pack step vanishes); the xla backend computes the
    identical per-column arithmetic with whole-buffer ops.  A
    non-elementwise custom prox is the one case that must see the tree:
    it is applied through ``unpack_coord``/``pack_coord`` on the
    ``(1, width)`` mean -- coordinator-sized traffic, not agent-stack
    traffic."""
    rho_eff = cfg.rho / cfg.n_agents
    if mesh is not None and fusible_prox(prox_h):
        col = _mesh_col_axis(mesh, z.shape[1])
        if cfg.engine_backend == "pallas":
            from repro.kernels.round_edge import ops as edge_ops

            return edge_ops.round_uplink_sharded(
                z, None if z_seen is z else z_seen, mesh=mesh,
                n_total=cfg.n_agents, prox=prox_h, rho_eff=rho_eff,
                col_axis=col)
        return _uplink_sharded_xla(cfg, z, z_seen, prox_h, mesh, col)
    # a non-elementwise custom prox under a mesh falls through to the
    # unsharded formula: the prox sees the coordinator-sized tree and
    # GSPMD shards the agent-stack arithmetic (mesh contract)
    if cfg.engine_backend == "pallas" and fusible_prox(prox_h):
        from repro.kernels.round_edge import ops as edge_ops

        return edge_ops.round_uplink(
            z, None if z_seen is z else z_seen, prox=prox_h,
            rho_eff=rho_eff)
    zbar = jnp.mean(z_seen, axis=0, keepdims=True)
    if prox_h is None:
        y = zbar
    elif getattr(prox_h, "elementwise", False):
        y = prox_h(zbar, rho_eff)
    else:
        y = compress_lib.pack_coord(
            tree_map(lambda l: prox_h(l, rho_eff),
                     compress_lib.unpack_coord(zbar, meta)), meta)
    return y, 2.0 * y - z


@telemetry.scope("fedplt.downlink")
def agent_edge_packed(cfg: RoundConfig, u: jnp.ndarray, w: jnp.ndarray,
                      x: jnp.ndarray, z: jnp.ndarray, y: jnp.ndarray,
                      z_seen: jnp.ndarray,
                      prox_h: ProxH = None, mesh=None) \
        -> Tuple[jnp.ndarray, jnp.ndarray]:
    """:func:`agent_edge` on resident ``(N, width)`` buffers (``y`` is
    the ``(1, width)`` coordinator buffer): Krasnosel'skii update +
    participation selects, ``jnp.where`` semantics preserved so a
    diverged (NaN) local solve cannot leak into inactive agents."""
    if mesh is not None and fusible_prox(prox_h):
        col = _mesh_col_axis(mesh, z.shape[1])
        if cfg.engine_backend == "pallas":
            from repro.kernels.round_edge import ops as edge_ops

            return edge_ops.round_downlink_sharded(
                x, w, z, y, u, mesh=mesh, damping=cfg.damping,
                col_axis=col)
        return _downlink_sharded_xla(cfg, u, w, x, z, y, mesh, col)
    if cfg.engine_backend == "pallas" and fusible_prox(prox_h):
        from repro.kernels.round_edge import ops as edge_ops

        return edge_ops.round_downlink(
            x, w, z, u, None if z_seen is z else z_seen, prox=prox_h,
            rho_eff=cfg.rho / cfg.n_agents, damping=cfg.damping)
    mask = (u != 0).reshape(-1, 1)
    x_new = jnp.where(mask, w, x)
    z_upd = z + 2.0 * cfg.damping * (w - y)
    return x_new, jnp.where(mask, z_upd, z)


def packed_round_step(cfg: RoundConfig, meta, x: jnp.ndarray,
                      z: jnp.ndarray, t: jnp.ndarray, key: jax.Array,
                      local_solver: SolverAssignment,
                      prox_h: ProxH = None, mesh=None,
                      corrupt=None, live=None) -> RoundResult:
    """One Fed-PLT round on the RESIDENT packed state: ``x``/``z``/``t``
    are ``(N, width)`` buffers laid out by ``meta`` (a static
    :class:`repro.fed.compress.PackedMeta`), and the returned
    :class:`RoundResult` carries buffers too (``y`` is ``(1, width)``).

    Mirrors :func:`round_step` exactly -- same 3-way key split, same
    edge formulas, same compressed-uplink ``t + u * q`` -- so packed
    and tree trajectories are bitwise identical per realization
    (asserted in tests).  ``local_solver`` must consume buffers: build
    it with :func:`repro.fed.solvers.make_packed_local_solver` (or wrap
    a tree solver with :func:`repro.fed.solvers.wrap_packed_solver`).
    :func:`run_solvers` works unchanged -- a buffer is a pytree, group
    slicing is row slicing.

    ``corrupt`` / ``live`` are broker-realized fault rows (see
    :func:`round_step`); ``None`` for both keeps the historical graph.
    """
    if mesh is not None:
        validate_mesh(cfg, mesh, local_solver)
    key, k_part, k_solve = jax.random.split(key, 3)

    z_seen = t if cfg.compressed else z
    z_seen = robust_seen(cfg, z_seen, live, meta, mesh)
    y, v = coordinator_edge_packed(cfg, z, z_seen, meta, prox_h, mesh)

    w, aux = run_solvers(local_solver, x, v, k_solve, cfg.n_agents)
    w = apply_corruption(w, corrupt)

    u = live_mask_rows(participation_mask(k_part, cfg), live)
    u, _ok = increment_guard(cfg, w, u, meta)
    x_new, z_new = agent_edge_packed(cfg, u, w, x, z, y, z_seen, prox_h,
                                     mesh)

    t_new = transmit(cfg, z_new, t, u, meta) if cfg.compressed else z_new

    return RoundResult(x=x_new, z=z_new, t=t_new, y=y, next_key=key,
                       u=u, aux=aux)


def count_primitives(jaxpr, names: Sequence[str]) -> Dict[str, int]:
    """Occurrences of each primitive in ``jaxpr`` (a ``ClosedJaxpr``'s
    ``.jaxpr`` or any inner jaxpr), descending into sub-jaxprs (scan /
    cond / pjit bodies).  The layout contract's measurement tool: tests,
    the engine benchmark, and the CI smoke all assert the packed pallas
    round's state path through it (zero ``concatenate`` / ``gather``)."""
    counts = {n: 0 for n in names}
    _count_into(jaxpr, counts)
    return counts


def _count_into(jaxpr, counts) -> None:
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in counts:
            counts[eqn.primitive.name] += 1
        for v in eqn.params.values():
            for vv in (v if isinstance(v, (list, tuple)) else [v]):
                inner = getattr(vv, "jaxpr", None)
                if inner is not None:
                    _count_into(inner, counts)
                elif hasattr(vv, "eqns"):
                    _count_into(vv, counts)


# ---------------------------------------------------------------------------
# Heterogeneous agent groups
# ---------------------------------------------------------------------------

def _slice_agents(tree: Any, start: int, stop: int) -> Any:
    return tree_map(lambda l: l[start:stop], tree)


@telemetry.scope("fedplt.local_solver")
def run_solvers(local_solver: SolverAssignment, x: Any, v: Any,
                key: jax.Array, n_agents: int) -> Tuple[Any, Any]:
    """Dispatch the round's solver assignment on the reflected states.

    A bare :data:`LocalSolver` (or a single :class:`SolverGroup`) is
    called on the full stack with ``key`` unchanged -- bit-identical to
    the historical homogeneous path.  Multiple groups partition the
    agent axis contiguously: group ``g`` solves its slice under
    ``fold_in(key, g)`` and the per-group results are re-stitched by
    concatenation.  ``aux`` is the solver's aux unchanged when
    homogeneous, else the tuple of per-group auxes (None when every
    group returned None) -- per-group epoch counts may differ, so the
    engine cannot stack them.
    """
    if isinstance(local_solver, SolverGroup):   # bare group, not a seq
        local_solver = (local_solver,)
    if callable(local_solver):
        return local_solver(x, v, key)
    groups = tuple(local_solver)
    sizes = [g.size for g in groups]
    if sum(sizes) != n_agents:
        raise ValueError(f"solver groups cover {sum(sizes)} agents, "
                         f"round has n_agents={n_agents}")
    if len(groups) == 1:
        return groups[0].solver(x, v, key)
    ws, auxs = [], []
    start = 0
    for g_idx, grp in enumerate(groups):
        stop = start + grp.size
        w_g, aux_g = grp.solver(_slice_agents(x, start, stop),
                                _slice_agents(v, start, stop),
                                jax.random.fold_in(key, g_idx))
        ws.append(w_g)
        auxs.append(aux_g)
        start = stop
    w = tree_map(lambda *ls: jnp.concatenate(ls, axis=0), *ws)
    aux = None if all(a is None for a in auxs) else tuple(auxs)
    return w, aux


# ---------------------------------------------------------------------------
# Compressed z-exchange: the compressor itself lives in the
# repro.fed.compress registry; `compress_increment` is re-exported above
# so front ends keep one import site.
# ---------------------------------------------------------------------------

@telemetry.scope("fedplt.compress")
def transmit(cfg: RoundConfig, z_new: Any, t: Any, u: jnp.ndarray,
             meta=None) -> Any:
    """The compressed uplink: agents send ``q = C(z_new - t)`` and the
    coordinator's copy advances by what was sent, ``t + u * q``.  ``meta``
    marks the packed form (resident ``(N, width)`` buffers).

    Arithmetic (``u*q``) masking, not ``jnp.where``: an inactive agent's
    increment is computed from its own finite old state, so there is no
    NaN hazard here, and the historical ``t + u*q`` lets XLA contract the
    int8 dequant-multiply + add into one FMA -- keeping compressed
    trajectories bit-identical to pre-refactor."""
    if meta is None:
        q = compress_increment(tree_map(jnp.subtract, z_new, t), cfg)
    else:
        q = compress_lib.compress_increment_packed(z_new - t, meta, cfg)
    return tree_map(
        lambda tl, ql: tl + u.astype(ql.dtype).reshape(
            (-1,) + (1,) * (ql.ndim - 1)) * ql,
        t, q)


# ---------------------------------------------------------------------------
# One round
# ---------------------------------------------------------------------------

def round_step(cfg: RoundConfig, x: Any, z: Any, t: Any, key: jax.Array,
               local_solver: SolverAssignment,
               prox_h: ProxH = None, mesh=None,
               corrupt=None, live=None) -> RoundResult:
    """One Fed-PLT round on agent-stacked pytrees.

    ``t`` is the coordinator's copy of ``z`` (pass ``z`` itself when the
    exchange is uncompressed).  Consumes ``key`` exactly like the
    historical implementations: split 3 ways (carry, participation,
    solver).  ``local_solver`` is one solver for every agent or a
    sequence of :class:`SolverGroup` partitioning the agent axis (see
    :func:`run_solvers`).

    ``corrupt`` / ``live`` are broker-realized fault rows: ``corrupt``
    multiplies flagged agents' solver output (:func:`apply_corruption`,
    screened by :func:`increment_guard` when enabled), ``live`` drops
    evicted agents from both the participation draw and the coordinator
    mean (:func:`survivor_mean_input`).  ``None`` for both keeps the
    historical graph bitwise.
    """
    if mesh is not None:
        validate_mesh(cfg, mesh, local_solver)
    key, k_part, k_solve = jax.random.split(key, 3)

    # -- coordinator edge: prox of the mean of the *transmitted* copies
    # when the exchange is compressed (t_i), else the exact z_i (Lemma
    # 6), fused with the reflection; evictions rescale the input so the
    # mean runs over survivors only, and a robust aggregator replaces
    # the mean with its statistic of the live rows --------------------
    z_seen = t if cfg.compressed else z
    z_seen = robust_seen(cfg, z_seen, live, mesh=mesh)
    y, v = coordinator_edge(cfg, z, z_seen, prox_h, mesh)

    # -- agents: warm-started local training on the reflected states ----
    w, aux = run_solvers(local_solver, x, v, k_solve, cfg.n_agents)
    w = apply_corruption(w, corrupt)

    # -- agent edge: Krasnosel'skii z-update + partial participation ----
    u = live_mask_rows(participation_mask(k_part, cfg), live)
    u, _ok = increment_guard(cfg, w, u)
    x_new, z_new = agent_edge(cfg, u, w, x, z, y, z_seen, prox_h, mesh)

    # -- compressed uplink: t advances by the transmitted increment ------
    t_new = transmit(cfg, z_new, t, u) if cfg.compressed else z_new

    return RoundResult(x=x_new, z=z_new, t=t_new, y=y, next_key=key, u=u,
                       aux=aux)


# ---------------------------------------------------------------------------
# Default local solver: core/solvers.py generalized to stacked pytrees
# ---------------------------------------------------------------------------

def make_local_solver(solver_cfg, fgrad, rho: float, mu: float = 0.0,
                      L: float = 0.0, *, use_pallas: bool = False,
                      has_aux: bool = False) -> LocalSolver:
    """Build a :data:`LocalSolver` from a stacked gradient oracle.

    ``fgrad(w_stack, key)`` returns the per-agent gradient pytree (leaves
    (N, ...)); with ``has_aux`` it returns ``(grads, aux)``.  Solver
    choice, step size, DP noise, and per-agent clipping all come from
    ``solver_cfg`` (a :class:`repro.core.solvers.SolverConfig`);
    dispatch goes through the :mod:`repro.fed.solvers` registry, so a
    solver registered there is reachable by name from every front end.
    The fused ``fedplt_update`` Pallas kernel is used for the inner step
    when ``use_pallas`` and the step size is static.
    """
    from repro.fed.solvers import make_local_solver as _make

    return _make(solver_cfg, fgrad, rho, mu, L, use_pallas=use_pallas,
                 has_aux=has_aux)
