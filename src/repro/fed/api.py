"""One front door for Fed-PLT: ``FedSpec`` + ``build_trainer``.

The historical configs (``FedPLTConfig`` for the dense paper
experiments, ``FedConfig`` for model scale, plus the engine's
``RoundConfig`` and the solvers' ``SolverConfig``) redeclared
overlapping knobs and validated them in three different places.
``FedSpec`` is the single composable spec:

    round topology   -- n_agents / rho / participation / damping
    local solver     -- solver / n_epochs / gamma / (mu, L)
    privacy          -- :class:`PrivacySpec` (tau, clip, delta, dp_init)
    uplink           -- :class:`CompressionSpec` (registry name + knobs)
    coordinator h    -- prox_h registry name (+ weight_decay shorthand)

with ONE :meth:`FedSpec.validate` owning every cross-field check, and
:func:`build_trainer` dispatching to either front end behind one handle:

    >>> spec = FedSpec(n_agents=4, gamma=0.1, n_epochs=3)
    >>> trainer = build_trainer(problem_or_model, spec)
    >>> state, history = trainer.run(jax.random.PRNGKey(0), 100)

Both legacy configs now expose ``.to_spec()`` and stay bit-compatible:
``build_trainer(problem, cfg.to_spec())`` reproduces
``FedPLT(problem, cfg)`` trajectories exactly.

The CLI in :mod:`repro.launch.train` is *generated* from the spec's
dataclass fields (:func:`add_spec_args` / :func:`spec_from_args`), so a
new knob added here -- or a new compressor registered in
:mod:`repro.fed.compress` -- shows up as a flag without touching the
driver.
"""

from __future__ import annotations

import argparse
import dataclasses
from typing import Any, Optional, Sequence, Union

import jax

from repro.core import prox as prox_lib
from repro.core.solvers import SolverConfig
from repro.fed import engine, telemetry
from repro.fed.compress import (COMPRESS_BACKENDS, available_compressors,
                                get_compressor)
from repro.fed.robust import available_aggregators, validate_aggregator
from repro.fed.solvers import get_solver


def _upgrade_solver(name: str, tau: float) -> str:
    """tau > 0 turns the gd-type solvers into DP noisy GD.

    Any other solver -- agd, or a custom registry entry -- is REJECTED
    under tau > 0: the Prop. 4 accountant certifies noisy local GD
    specifically, and a solver that injects no noise must never receive
    an (eps, delta) certificate just because tau was set."""
    if tau > 0.0:
        if name in ("gd", "sgd"):
            return "noisy_gd"
        if name != "noisy_gd":
            raise ValueError("DP noise (tau > 0) requires a gd-type "
                             f"solver, not {name!r}")
    return name


def _cli(flag=None, help="", arg_type=None, choices=None, default=None,
         expose=True):
    """Field metadata driving the generated argparse flags.

    ``default`` overrides the dataclass default on the CLI only (the CLI
    must pick concrete values where the spec allows None/derived).
    """
    return {"cli": {"flag": flag, "help": help, "type": arg_type,
                    "choices": choices, "default": default,
                    "expose": expose}}


# ---------------------------------------------------------------------------
# Component specs
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class PrivacySpec:
    """DP knobs (paper Section VI)."""

    tau: float = dataclasses.field(default=0.0, metadata=_cli(
        help="DP noise std (tau > 0 turns gd-type solvers into noisy GD)"))
    clip: Optional[float] = dataclasses.field(default=None, metadata=_cli(
        arg_type=float,
        help="per-agent gradient clip threshold C (DP sensitivity)"))
    delta: float = dataclasses.field(default=1e-5, metadata=_cli(
        help="ADP delta for the privacy report"))
    dp_init: bool = dataclasses.field(default=False, metadata=_cli(
        expose=False))   # x0 ~ N(0, 2 tau^2/mu I) (Prop. 4, dense path)


@dataclasses.dataclass(frozen=True)
class CompressionSpec:
    """z-uplink compression; ``name`` is a :mod:`repro.fed.compress`
    registry entry, so registered compressors are reachable by name from
    every front end (and the generated CLI) without engine changes."""

    name: str = dataclasses.field(default="none", metadata=_cli(
        flag="--compression", help="z-uplink compressor (registry name)"))
    ratio: float = dataclasses.field(default=0.25, metadata=_cli(
        flag="--compress-ratio",
        help="top-k fraction kept (floor for adaptive_topk)"))
    energy: float = dataclasses.field(default=0.95, metadata=_cli(
        flag="--compress-energy",
        help="adaptive_topk per-agent energy target"))
    # "pallas": pack all leaves into one (N, M_total) buffer and run the
    # fused repro.kernels.compress kernels once per round (bit-identical
    # to the per-leaf "xla" path; compressors without a kernel fall
    # back).  "auto" (default) picks per case from the committed
    # benchmark heuristics (repro.fed.compress.resolve_backend) -- a
    # pure scheduling choice, since both backends are bit-identical.
    backend: str = dataclasses.field(default="auto", metadata=_cli(
        flag="--compress-backend", choices=["auto", "xla", "pallas"],
        help="uplink compressor backend (auto picks per case; pallas = "
             "fused packed kernels)"))


@dataclasses.dataclass(frozen=True)
class AgentGroupSpec:
    """One contiguous group of agents with its own local-training recipe.

    ``None`` fields inherit the top-level :class:`FedSpec` value, so a
    group only states what makes it *different*.  Groups partition the
    agent axis in order: the first group owns agents ``[0, size)``, the
    next ``[size, size + size')``, and so on; the engine runs each
    group's registered solver on its slice and re-stitches the stacked
    pytree (:func:`repro.fed.engine.run_solvers`).
    """

    size: int
    solver: Optional[str] = None         # repro.fed.solvers registry name
    n_epochs: Optional[int] = None       # N_e of this group
    gamma: Optional[float] = None        # local step size of this group
    participation: Optional[float] = None  # Bernoulli p of this group


def parse_agent_groups(text: str) -> tuple[AgentGroupSpec, ...]:
    """Parse the CLI grammar for ``--agent-groups``.

    Comma-separated groups, each ``SIZE[*SOLVER][:key=value]...`` with
    keys ``n_epochs`` / ``gamma`` / ``participation``; omitted pieces
    inherit the top-level spec.  Examples::

        2*gd,2*agd
        3*gd:participation=0.5,1*agd:n_epochs=1:gamma=0.02
    """
    groups = []
    for part in text.split(","):
        part = part.strip()
        if not part:
            raise ValueError(f"empty agent group in {text!r}")
        head, *opts = part.split(":")
        if "*" in head:
            size_s, solver = head.split("*", 1)
            solver = solver.strip() or None
        else:
            size_s, solver = head, None
        try:
            size = int(size_s)
        except ValueError:
            raise ValueError(
                f"agent group {part!r} must start with an integer size "
                f"(grammar: SIZE[*SOLVER][:key=value]...)") from None
        kw = {}
        for opt in opts:
            k, sep, val = opt.partition("=")
            k = k.strip()
            if not sep or k not in ("n_epochs", "gamma", "participation"):
                raise ValueError(
                    f"unknown agent-group option {opt!r} in {part!r} "
                    f"(known: n_epochs=, gamma=, participation=)")
            kw[k] = int(val) if k == "n_epochs" else float(val)
        groups.append(AgentGroupSpec(size=size, solver=solver, **kw))
    return tuple(groups)


# ---------------------------------------------------------------------------
# The spec
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class FedSpec:
    """Composable Fed-PLT specification -- the one front-door config."""

    # -- round topology --------------------------------------------------
    n_agents: Optional[int] = dataclasses.field(default=None, metadata=_cli(
        arg_type=int, default=4,
        help="number of agents (dense path: taken from the problem)"))
    rho: float = dataclasses.field(default=1.0, metadata=_cli(
        help="proximal penalty rho of Algorithm 1"))
    participation: float = dataclasses.field(default=1.0, metadata=_cli(
        help="per-agent Bernoulli participation probability p"))
    damping: float = dataclasses.field(default=1.0, metadata=_cli(
        help="Krasnosel'skii relaxation (1 = PRS, 0.5 = Douglas-Rachford)"))
    # -- local solver ----------------------------------------------------
    solver: str = dataclasses.field(default="gd", metadata=_cli(
        choices=["gd", "agd", "sgd"],
        help="local solver (tau > 0 upgrades gd-type to noisy_gd)"))
    # NOTE: the generated CLI default must equal the field default (one
    # FedSpec() regardless of the front end) -- asserted in tests.
    n_epochs: int = dataclasses.field(default=5, metadata=_cli(
        help="local epochs N_e per round"))
    gamma: Optional[float] = dataclasses.field(default=None, metadata=_cli(
        arg_type=float, default=0.05,
        help="local step size (None: optimal 2/(L_d + mu_d) from moduli; "
             "required at model scale)"))
    mu: Optional[float] = dataclasses.field(default=None,
                                            metadata=_cli(expose=False))
    L: Optional[float] = dataclasses.field(default=None,
                                           metadata=_cli(expose=False))
    batch_size: Optional[int] = dataclasses.field(
        default=None, metadata=_cli(expose=False))  # dense sgd minibatch
    uncoordinated: bool = dataclasses.field(
        default=False, metadata=_cli(expose=False))  # Remark 1 (dense)
    # -- heterogeneous agent groups -------------------------------------
    # None = every agent runs the top-level solver/n_epochs/gamma/
    # participation (the historical homogeneous path, bit-identical).
    # A tuple of AgentGroupSpec partitions the agent axis into groups,
    # each with its own registered solver and knobs.
    agent_groups: Optional[tuple[AgentGroupSpec, ...]] = dataclasses.field(
        default=None, metadata=_cli(
            arg_type=parse_agent_groups,
            help="heterogeneous agent groups, e.g. "
                 "'2*gd,2*agd:n_epochs=1:gamma=0.02' (sizes must sum to "
                 "n-agents; omitted knobs inherit the top-level spec)"))
    # -- coordinator regularizer h --------------------------------------
    prox_h: str = dataclasses.field(default="zero",
                                    metadata=_cli(expose=False))
    weight_decay: float = dataclasses.field(default=0.0, metadata=_cli(
        help="coordinator l2 regularizer h (prox_h='weight_decay')"))
    # -- composed specs --------------------------------------------------
    privacy: PrivacySpec = dataclasses.field(default_factory=PrivacySpec)
    compression: CompressionSpec = dataclasses.field(
        default_factory=CompressionSpec)
    # -- execution -------------------------------------------------------
    use_pallas: bool = dataclasses.field(default=False, metadata=_cli(
        flag="--use-pallas-update",
        help="fused fedplt_update kernel for the local step"))
    # "pallas": run the round's coordinator edges (prox + reflect;
    # z-update + participation selects) as the two fused
    # repro.kernels.round_edge launches on the packed (N, M_total)
    # buffer (fp32-rounding-identical to the per-leaf "xla" path --
    # parity contract in repro.fed.engine; custom non-elementwise
    # proxes and mixed-dtype trees fall back per edge)
    engine_backend: str = dataclasses.field(default="xla", metadata=_cli(
        flag="--engine-backend", choices=["xla", "pallas"],
        help="round-edge backend (pallas = fused packed kernels)"))
    # "packed": carry the federated state (x, z, t) as one resident
    # (N, M_total) buffer per variable across rounds -- packed once at
    # init, unpacked only at the API boundary (consensus / metrics /
    # checkpoints).  Bitwise-identical trajectories to "tree" per
    # realization (layout contract in repro.fed.engine).
    state_layout: str = dataclasses.field(default="tree", metadata=_cli(
        flag="--state-layout", choices=["tree", "packed"],
        help="round-to-round state representation (packed = one "
             "resident agent-axis buffer, zero per-round pack/unpack)"))
    # "stale": bounded-staleness async rounds -- the participation draw
    # becomes an ARRIVAL draw, non-arrived agents keep training against
    # their stale reflection, and an agent is forced to arrive when its
    # work is max_staleness rounds old.  max_staleness=0 reproduces the
    # synchronous engine bitwise per realization (contract in
    # repro.fed.async_engine).
    async_mode: str = dataclasses.field(default="off", metadata=_cli(
        flag="--async-mode", choices=["off", "stale"],
        help="async round mode (stale = bounded-staleness arrivals; "
             "off = bulk-synchronous rounds)"))
    max_staleness: int = dataclasses.field(default=0, metadata=_cli(
        flag="--max-staleness", arg_type=int,
        help="staleness bound K: an agent holding K-round-old work is "
             "forced to arrive (0 = synchronous semantics)"))
    # in-jit increment guards (fault tolerance): screen every agent's
    # uplink row for non-finite values (and, when guard_norm_bound is
    # finite, for norm above the bound) and convert a failing row into a
    # NON-ARRIVAL this round -- a bitwise no-op when every row is clean.
    guard_increments: bool = dataclasses.field(default=False, metadata=_cli(
        flag="--guard-increments",
        help="screen agent increments in-jit: a non-finite (or "
             "over-norm) uplink row becomes a non-arrival this round"))
    guard_norm_bound: float = dataclasses.field(
        default=float("inf"), metadata=_cli(
            flag="--guard-norm-bound", arg_type=float,
            help="l2 norm bound for --guard-increments (inf = "
                 "finiteness-only screen)"))
    # coordinator aggregation (repro.fed.robust registry): "mean" keeps
    # the historical uplink bitwise; trimmed_mean / coord_median /
    # norm_clip_mean replace it with a robust statistic of the live
    # rows, bounding what finite guard-evading byzantine increments
    # can do to the consensus
    aggregator: str = dataclasses.field(default="mean", metadata=_cli(
        flag="--aggregator",
        help="coordinator aggregator (repro.fed.robust registry name; "
             "mean = the historical uplink)"))
    aggregator_param: float = dataclasses.field(
        default=0.0, metadata=_cli(
            flag="--aggregator-param", arg_type=float,
            help="aggregator parameter: trim count f for trimmed_mean, "
                 "clip radius for norm_clip_mean"))
    # sharded rounds (engine mesh contract): shard the agent axis of
    # every per-agent carrier across this many devices.  1 = unsharded;
    # a 1-device mesh reproduces the unsharded trajectory bitwise.
    agent_shards: int = dataclasses.field(default=1, metadata=_cli(
        flag="--agent-shards", arg_type=int,
        help="shard the round's agent axis across this many devices "
             "(n-agents must divide evenly; 1 = unsharded)"))
    # explicit (agent, model) mesh extents as "AxM", e.g. "8x1"; None
    # derives (agent_shards, 1).  The model axis additionally shards
    # the packed buffer's columns when it divides the width.
    mesh_shape: Optional[str] = dataclasses.field(default=None, metadata=_cli(
        flag="--mesh-shape", arg_type=str,
        help="explicit AGENTSxMODEL device mesh, e.g. '8x1' "
             "(default: agent-shards x 1)"))

    def __post_init__(self):
        groups = self.agent_groups
        if groups is not None:
            if isinstance(groups, str):
                groups = parse_agent_groups(groups)
            object.__setattr__(self, "agent_groups", tuple(groups))

    # ------------------------------------------------------------------
    # Resolution
    # ------------------------------------------------------------------
    def solver_name(self) -> str:
        """tau > 0 turns the gd-type solvers into DP noisy GD."""
        return _upgrade_solver(self.solver, self.privacy.tau)

    def solver_config(self) -> SolverConfig:
        return SolverConfig(name=self.solver_name(),
                            n_epochs=self.n_epochs, step_size=self.gamma,
                            tau=self.privacy.tau, clip=self.privacy.clip)

    def resolved_groups(self) -> Optional[tuple[AgentGroupSpec, ...]]:
        """``agent_groups`` with every None field filled from the
        top-level spec (None when the spec is homogeneous)."""
        if self.agent_groups is None:
            return None
        return tuple(AgentGroupSpec(
            size=g.size,
            solver=g.solver if g.solver is not None else self.solver,
            n_epochs=(g.n_epochs if g.n_epochs is not None
                      else self.n_epochs),
            gamma=g.gamma if g.gamma is not None else self.gamma,
            participation=(g.participation if g.participation is not None
                           else self.participation))
            for g in self.agent_groups)

    def group_solver_configs(self) -> Optional[tuple[SolverConfig, ...]]:
        """Per-group :class:`SolverConfig` (tau>0 upgrades gd-type
        groups to noisy GD, exactly like the homogeneous path)."""
        groups = self.resolved_groups()
        if groups is None:
            return None
        return tuple(SolverConfig(
            name=_upgrade_solver(g.solver, self.privacy.tau),
            n_epochs=g.n_epochs, step_size=g.gamma,
            tau=self.privacy.tau, clip=self.privacy.clip)
            for g in groups)

    def participation_schedule(self) -> Union[float, tuple[float, ...]]:
        """Engine participation: the scalar p, or the per-agent (N,)
        tuple expanded from the groups when any group deviates."""
        groups = self.resolved_groups()
        if groups is None or all(
                g.participation == self.participation for g in groups):
            return self.participation
        out: list[float] = []
        for g in groups:
            out.extend([float(g.participation)] * g.size)
        return tuple(out)

    def round_config(self) -> engine.RoundConfig:
        if self.n_agents is None:
            raise ValueError("FedSpec.n_agents is unresolved (the dense "
                             "path fills it from the problem; set it "
                             "explicitly at model scale)")
        return engine.RoundConfig(
            n_agents=self.n_agents, rho=self.rho,
            participation=self.participation_schedule(),
            damping=self.damping,
            compression=self.compression.name,
            compress_ratio=self.compression.ratio,
            compress_energy=self.compression.energy,
            compress_backend=self.compression.backend,
            engine_backend=self.engine_backend,
            state_layout=self.state_layout,
            staleness=self.staleness_config(),
            agent_shards=self.resolved_agent_shards(),
            guard_increments=self.guard_increments,
            guard_norm_bound=self.guard_norm_bound,
            aggregator=self.aggregator,
            aggregator_param=self.aggregator_param)

    def staleness_config(self) -> engine.StalenessConfig:
        """The engine :class:`repro.fed.engine.StalenessConfig` this
        spec denotes (validates mode / bound on construction)."""
        return engine.StalenessConfig(mode=self.async_mode,
                                      max_staleness=self.max_staleness)

    def mesh_axes(self) -> Optional[tuple[int, int]]:
        """The ``(agent, model)`` mesh extents this spec denotes, or
        None when the run is unsharded.  ``mesh_shape`` wins when set
        (and must agree with a non-default ``agent_shards``)."""
        if self.mesh_shape is None:
            if self.agent_shards == 1:
                return None
            return (self.agent_shards, 1)
        parts = self.mesh_shape.lower().split("x")
        if len(parts) != 2:
            raise ValueError(
                f"mesh_shape must be 'AGENTSxMODEL' (e.g. '8x1'), got "
                f"{self.mesh_shape!r}")
        try:
            a, m = (int(p) for p in parts)
        except ValueError:
            raise ValueError(
                f"mesh_shape extents must be integers, got "
                f"{self.mesh_shape!r}") from None
        if a < 1 or m < 1:
            raise ValueError(f"mesh_shape extents must be >= 1, got "
                             f"{self.mesh_shape!r}")
        if self.agent_shards != 1 and self.agent_shards != a:
            raise ValueError(
                f"agent_shards={self.agent_shards} disagrees with "
                f"mesh_shape={self.mesh_shape!r} (agent extent {a}); "
                f"set one, or make them agree")
        return (a, m)

    def resolved_agent_shards(self) -> int:
        """The agent-axis device count the engine must validate against
        (1 when unsharded)."""
        axes = self.mesh_axes()
        return 1 if axes is None else axes[0]

    def build_mesh(self):
        """The ``jax.sharding.Mesh`` this spec denotes, or None when
        unsharded.  Raises with the host-device escape hatch named when
        the platform has too few devices."""
        axes = self.mesh_axes()
        if axes is None:
            return None
        import numpy as np
        from jax.sharding import Mesh

        a, m = axes
        devices = jax.devices()
        if len(devices) < a * m:
            raise ValueError(
                f"mesh of {a}x{m} needs {a * m} devices, but only "
                f"{len(devices)} are visible -- on CPU set "
                f"XLA_FLAGS=--xla_force_host_platform_device_count="
                f"{a * m} before importing jax")
        return Mesh(np.asarray(devices[:a * m]).reshape(a, m),
                    ("agent", "model"))

    def moduli_for(self, gamma: Optional[float]) \
            -> tuple[float, Optional[float]]:
        """(mu, L) of the local f_i given a group's step size.  Explicit
        values win; with ``gamma`` set (model scale) an unknown L is
        derived as 1/gamma - 1/rho so that agd's 1/L_d step equals
        gamma; with neither (dense path) L stays None and the problem's
        own moduli are used."""
        mu = self.mu if self.mu is not None else 0.0
        if self.L is not None:
            return mu, self.L
        if gamma is None:
            return mu, None
        return mu, 1.0 / gamma - 1.0 / self.rho

    def moduli(self) -> tuple[float, Optional[float]]:
        """(mu, L) of the local f_i for momentum resolution (top-level
        gamma; see :meth:`moduli_for`)."""
        return self.moduli_for(self.gamma)

    def resolve_prox_h(self) -> engine.ProxH:
        """Engine ProxH of the coordinator regularizer h; None when h = 0.
        Every name -- including the model path's weight decay -- comes
        from the one :func:`repro.core.prox.make_prox` registry."""
        if self.weight_decay != 0.0:
            return prox_lib.make_prox("weight_decay",
                                      weight=self.weight_decay)
        if self.prox_h == "zero":
            return None
        return prox_lib.make_prox(self.prox_h)

    # ------------------------------------------------------------------
    # Validation: the single home of every cross-field check
    # ------------------------------------------------------------------
    def validate(self) -> "FedSpec":
        """Raise ValueError on any inconsistent combination; returns self
        so call sites can chain ``spec.validate()``."""
        if self.n_agents is not None and self.n_agents < 1:
            raise ValueError("n_agents must be >= 1")
        if self.rho <= 0.0:
            raise ValueError("rho must be positive")
        if not 0.0 < self.participation <= 1.0:
            raise ValueError("participation must be in (0, 1]")
        if not 0.0 < self.damping <= 1.0:
            raise ValueError("damping must be in (0, 1]")
        if self.n_epochs < 1:
            raise ValueError("n_epochs must be >= 1")
        if self.gamma is not None and self.gamma <= 0.0:
            raise ValueError("gamma must be positive")
        p = self.privacy
        if p.tau < 0.0:
            raise ValueError("tau must be >= 0")
        if p.clip is not None and p.clip <= 0.0:
            raise ValueError("clip must be positive (clip=0 zeroes every "
                             "gradient; use None to disable clipping)")
        if not 0.0 < p.delta < 1.0:
            raise ValueError("delta must be in (0, 1)")
        name = self.solver_name()   # raises for agd + tau > 0
        get_solver(name)            # unknown-solver registry error
        get_compressor(self.compression.name)  # unknown-compressor error
        if not 0.0 < self.compression.ratio <= 1.0:
            raise ValueError("compress ratio must be in (0, 1]")
        if not 0.0 < self.compression.energy <= 1.0:
            raise ValueError("compress energy must be in (0, 1]")
        if self.compression.backend not in COMPRESS_BACKENDS:
            raise ValueError(
                f"unknown compress backend {self.compression.backend!r}; "
                f"known: {', '.join(COMPRESS_BACKENDS)}")
        if self.engine_backend not in engine.ENGINE_BACKENDS:
            raise ValueError(
                f"unknown engine backend {self.engine_backend!r}; "
                f"known: {', '.join(engine.ENGINE_BACKENDS)}")
        if self.state_layout not in engine.ENGINE_LAYOUTS:
            raise ValueError(
                f"unknown state layout {self.state_layout!r}; "
                f"known: {', '.join(engine.ENGINE_LAYOUTS)}")
        self.staleness_config()     # bad mode / bound -> ValueError
        if not self.guard_norm_bound > 0.0:   # also rejects NaN
            raise ValueError("guard_norm_bound must be positive (use "
                             "inf for a finiteness-only screen)")
        validate_aggregator(self.aggregator, self.aggregator_param,
                            self.n_agents)
        if self.weight_decay < 0.0:
            raise ValueError("weight_decay must be >= 0")
        if self.weight_decay != 0.0 and self.prox_h not in (
                "zero", "weight_decay"):
            raise ValueError("weight_decay and a non-trivial prox_h are "
                             "mutually exclusive (one coordinator h)")
        self.resolve_prox_h()       # unknown prox name -> KeyError
        if name == "agd":
            self._check_agd_moduli(self.gamma)
        self._validate_groups()
        self._validate_mesh()
        return self

    def _check_agd_moduli(self, gamma: Optional[float],
                          where: str = "") -> None:
        mu, L = self.moduli_for(gamma)
        if L is not None and L <= mu:
            if self.L is not None:
                raise ValueError(f"agd momentum needs L > mu (got "
                                 f"L={L:.4g}, mu={mu:.4g}){where}")
            raise ValueError(
                f"agd momentum needs L > mu; derived L={L:.4g} from "
                f"gamma={gamma} (needs gamma < rho/(1 + mu*rho) "
                f"= {self.rho / (1.0 + mu * self.rho):.4g}) -- pass "
                f"an explicit L in the spec{where}")

    def _validate_groups(self) -> None:
        groups = self.resolved_groups()
        if groups is None:
            return
        if not groups:
            raise ValueError("agent_groups must have at least one group "
                             "(use None for the homogeneous path)")
        for i, g in enumerate(groups):
            where = f" (agent group {i})"
            if g.size < 1:
                raise ValueError(f"agent group sizes must be >= 1, got "
                                 f"{g.size}{where}")
            gname = _upgrade_solver(g.solver, self.privacy.tau)
            get_solver(gname)   # unknown-solver registry error
            if g.n_epochs < 1:
                raise ValueError(f"n_epochs must be >= 1{where}")
            if g.gamma is not None and g.gamma <= 0.0:
                raise ValueError(f"gamma must be positive{where}")
            if not 0.0 < g.participation <= 1.0:
                raise ValueError(
                    f"participation must be in (0, 1]{where}")
            if gname == "agd":
                self._check_agd_moduli(g.gamma, where)
        total = sum(g.size for g in groups)
        if self.n_agents is not None and total != self.n_agents:
            raise ValueError(
                f"agent_groups sizes sum to {total}, but "
                f"n_agents={self.n_agents} -- groups must partition the "
                f"agent axis")

    def _validate_mesh(self) -> None:
        if self.agent_shards < 1:
            raise ValueError(f"agent_shards must be >= 1, got "
                             f"{self.agent_shards}")
        shards = self.resolved_agent_shards()  # parses/checks mesh_shape
        if shards == 1:
            return
        if self.n_agents is not None and self.n_agents % shards != 0:
            raise ValueError(
                f"n_agents={self.n_agents} is not divisible by "
                f"agent_shards={shards} -- every device must own the "
                f"same number of agent rows (pad n_agents or change the "
                f"shard count)")
        groups = self.resolved_groups()
        if groups is not None and self.n_agents is not None:
            rows = self.n_agents // shards
            edge = 0
            for i, g in enumerate(groups[:-1]):
                edge += g.size
                if edge % rows != 0:
                    raise ValueError(
                        f"agent group {i} ends at row {edge}, which is "
                        f"not a multiple of the shard size {rows} "
                        f"(n_agents={self.n_agents} / agent_shards="
                        f"{shards}) -- a solver group may not straddle "
                        f"a device boundary; re-cut the groups or "
                        f"change the shard count")

    # ------------------------------------------------------------------
    # Legacy-config bridge (kept bit-compatible)
    # ------------------------------------------------------------------
    def to_dense_config(self):
        """The :class:`repro.core.fedplt.FedPLTConfig` this spec denotes
        (inverse of ``FedPLTConfig.to_spec``, used by the dense trainer
        so trajectories stay bit-identical to the legacy front end)."""
        from repro.core.fedplt import FedPLTConfig

        return FedPLTConfig(
            rho=self.rho,
            solver=self.solver_config(),
            participation=self.participation,
            prox_h=self.prox_h,
            batch_size=self.batch_size,
            mu=self.mu, L=self.L,
            dp_init=self.privacy.dp_init,
            uncoordinated=self.uncoordinated,
            compression=self.compression.name,
            compress_ratio=self.compression.ratio,
            compress_energy=self.compression.energy,
            compress_backend=self.compression.backend,
            engine_backend=self.engine_backend,
            state_layout=self.state_layout,
            damping=self.damping,
            async_mode=self.async_mode,
            max_staleness=self.max_staleness,
            guard_increments=self.guard_increments,
            guard_norm_bound=self.guard_norm_bound,
            aggregator=self.aggregator,
            aggregator_param=self.aggregator_param)


def as_spec(cfg: Any) -> FedSpec:
    """Normalize a FedSpec / FedPLTConfig / FedConfig to a FedSpec."""
    if isinstance(cfg, FedSpec):
        return cfg
    to_spec = getattr(cfg, "to_spec", None)
    if to_spec is None:
        raise TypeError(f"cannot interpret {type(cfg).__name__} as a "
                        f"FedSpec (no .to_spec())")
    return to_spec()


# ---------------------------------------------------------------------------
# Privacy accounting from the spec
# ---------------------------------------------------------------------------

def _resolve_gamma(spec: "FedSpec", gamma: Optional[float]) -> float:
    """A concrete step size for the accountant: the configured gamma, or
    the optimal 2/(L_d + mu_d) derived from explicit moduli."""
    if gamma is not None:
        return gamma
    m, L = spec.moduli()
    if L is None:
        raise ValueError("privacy_report needs gamma (or explicit "
                         "moduli to derive it)")
    return spec.solver_config().resolve_step_size(
        m + 1.0 / spec.rho, L + 1.0 / spec.rho)


def privacy_report(spec: Any, n_rounds: int,
                   local_dataset_size: Union[int, Sequence[int]],
                   delta: Optional[float] = None, *,
                   mu: Optional[float] = None):
    """Position a DP run on the paper's (eps, delta) map (Prop. 4 +
    Lemma 5 via :mod:`repro.core.privacy`).

    Proposition 4 is a PER-AGENT statement: eps_i depends on agent i's
    dataset size q_i and local epoch count.  ``local_dataset_size`` may
    therefore be one int (every agent) or a per-agent sequence; with
    per-agent sizes or a heterogeneous ``spec.agent_groups`` the report
    carries the full per-agent (eps_i, delta) table
    (``report.per_agent``) and its headline ``adp_eps`` is the max over
    agents -- the budget the deployment as a whole must honor.  A
    homogeneous spec with one scalar q returns the historical scalar
    report unchanged.

    ``mu`` is the strong-convexity modulus the accountant charges
    against: the caller's problem modulus on the dense path, and by
    default the curvature the algorithm optimizes against at model scale
    (the proximal term gives d_i strong convexity >= weight_decay +
    1/rho, valid even for nonconvex local losses).

    Sensitivity convention: ``core.privacy`` expects the paper's
    Assumption-3 L (a PER-SAMPLE gradient bound; the bound divides by
    q^2).  The runtime clips the per-agent MEAN gradient at C, so
    swapping one of q samples can move the clipped gradient by up to 2C
    -- the per-sample-equivalent bound is L = C * q_i.  An unclipped run
    assumes per-sample bound L = 1.0 and a loud caveat is on the caller.
    """
    from repro.core.privacy import PrivacyReport

    spec = as_spec(spec).validate()
    p = spec.privacy
    if p.tau <= 0.0:
        raise ValueError("privacy_report requires tau > 0")
    mu_eff = mu if mu is not None else spec.weight_decay + 1.0 / spec.rho
    if mu_eff <= 0.0:
        raise ValueError("privacy accounting requires a strongly convex "
                         "local objective (mu > 0)")
    delta_eff = delta if delta is not None else p.delta
    groups = spec.resolved_groups()

    if isinstance(local_dataset_size, (str, bytes)):
        raise TypeError("local_dataset_size must be an int or a "
                        "sequence of per-agent ints, not a string")
    try:                     # a per-agent sequence of q_i?
        qs = [int(q) for q in local_dataset_size]
    except TypeError:        # scalar (python or numpy int): every agent
        qs = None

    if groups is None and qs is None:
        # homogeneous spec, one q: the historical scalar report
        gamma = _resolve_gamma(spec, spec.gamma)
        sensitivity = (p.clip * local_dataset_size
                       if p.clip is not None else 1.0)
        return PrivacyReport.build(
            sensitivity=sensitivity, mu=mu_eff, tau=p.tau,
            q=local_dataset_size, gamma=gamma, K=n_rounds,
            n_epochs=spec.n_epochs, delta=delta_eff)

    # per-agent accounting: expand groups / q_i to one row per agent
    qs, gammas, epochs, sensitivities = _per_agent_inputs(spec, qs,
                                                          local_dataset_size)
    return PrivacyReport.build_per_agent(
        sensitivities=sensitivities, mu=mu_eff, tau=p.tau, qs=qs,
        gammas=gammas, K=n_rounds, n_epochs_seq=epochs, delta=delta_eff)


def _per_agent_inputs(spec: "FedSpec", qs, local_dataset_size):
    """Expand a validated spec + dataset size(s) to one accounting row
    per agent: ``(qs, gammas, epochs, sensitivities)``, each length N."""
    if spec.n_agents is None:
        raise ValueError("per-agent privacy_report needs a resolved "
                         "n_agents")
    N = spec.n_agents
    if qs is None:
        qs = [int(local_dataset_size)] * N
    if len(qs) != N:
        raise ValueError(f"local_dataset_size has {len(qs)} entries for "
                         f"n_agents={N}")
    groups = spec.resolved_groups()
    if groups is None:
        gammas = [_resolve_gamma(spec, spec.gamma)] * N
        epochs = [spec.n_epochs] * N
    else:
        gammas, epochs = [], []
        for g in groups:
            gammas.extend([_resolve_gamma(spec, g.gamma)] * g.size)
            epochs.extend([g.n_epochs] * g.size)
    clip = spec.privacy.clip
    sensitivities = [clip * q if clip is not None else 1.0 for q in qs]
    return qs, gammas, epochs, sensitivities


def effective_privacy_report(spec: Any, schedule,
                             local_dataset_size: Union[int, Sequence[int]],
                             delta: Optional[float] = None, *,
                             mu: Optional[float] = None):
    """Per-agent privacy report under a REALIZED async arrival schedule.

    ``schedule`` is the ``(n_rounds, n_agents)`` 0/1 arrival record of a
    bounded-staleness run (stacked per-round arrival masks -- a broker's
    ``ArrivalSchedule.arrivals`` or the stacked ``u`` of the in-jit
    model).  Staleness changes the DP *composition*, not the mechanism:
    agent i released ``arrivals_i`` increments carrying
    ``released_rounds_i`` rounds of local epochs (an increment ``s``
    rounds stale carries ``s + 1`` rounds; work discarded at the bound
    was never transmitted and charges nothing).  The report therefore
    composes agent i over ``K_i = released_rounds_i`` effective rounds
    instead of the nominal round count -- always the per-agent table,
    even for a homogeneous spec, because realized schedules are
    per-agent by nature.
    """
    from repro.core.privacy import PrivacyReport
    from repro.fed.async_engine import effective_counts

    spec = as_spec(spec).validate()
    p = spec.privacy
    if p.tau <= 0.0:
        raise ValueError("effective_privacy_report requires tau > 0")
    mu_eff = mu if mu is not None else spec.weight_decay + 1.0 / spec.rho
    if mu_eff <= 0.0:
        raise ValueError("privacy accounting requires a strongly convex "
                         "local objective (mu > 0)")
    delta_eff = delta if delta is not None else p.delta

    if isinstance(local_dataset_size, (str, bytes)):
        raise TypeError("local_dataset_size must be an int or a "
                        "sequence of per-agent ints, not a string")
    try:
        qs = [int(q) for q in local_dataset_size]
    except TypeError:
        qs = None
    qs, gammas, epochs, sensitivities = _per_agent_inputs(spec, qs,
                                                          local_dataset_size)
    import numpy as _np
    sched = _np.asarray(schedule)
    if sched.ndim != 2 or sched.shape[1] != spec.n_agents:
        raise ValueError(f"schedule must be (n_rounds, n_agents="
                         f"{spec.n_agents}), got shape {sched.shape}")
    arrivals, released = effective_counts(sched, spec.max_staleness)
    return PrivacyReport.build_per_agent(
        sensitivities=sensitivities, mu=mu_eff, tau=p.tau, qs=qs,
        gammas=gammas, K=int(sched.shape[0]), n_epochs_seq=epochs,
        delta=delta_eff, Ks=[int(k) for k in released],
        arrivals=[int(a) for a in arrivals])


# ---------------------------------------------------------------------------
# The trainer handle
# ---------------------------------------------------------------------------

class FedTrainer:
    """Uniform handle over both Fed-PLT front ends.

    ``init / step / run / consensus / privacy_report`` mean the same
    thing on the dense paper problems and at model scale; only ``step``
    / ``run`` arity differs (model-scale rounds consume a batch).
    """

    spec: FedSpec

    def init(self, key: jax.Array):
        raise NotImplementedError

    def step(self, state, *args):
        raise NotImplementedError

    def run(self, key: jax.Array, n_rounds: int, *args):
        raise NotImplementedError

    def consensus(self, state):
        raise NotImplementedError

    def privacy_report(self, n_rounds: int,
                       local_dataset_size=None,
                       delta: Optional[float] = None):
        raise NotImplementedError


class DenseTrainer(FedTrainer):
    """:class:`repro.core.fedplt.FedPLT` behind the FedTrainer handle --
    trajectories are bit-identical to the legacy front end."""

    def __init__(self, problem, spec: FedSpec):
        if spec.n_agents not in (None, problem.n_agents):
            raise ValueError(f"spec.n_agents={spec.n_agents} != "
                             f"problem.n_agents={problem.n_agents}")
        self.spec = dataclasses.replace(spec, n_agents=problem.n_agents)
        # the spec with the problem's actual curvature filled in --
        # validation and privacy accounting both need the real moduli
        self._resolved = dataclasses.replace(
            self.spec,
            mu=spec.mu if spec.mu is not None
            else float(problem.strong_convexity()),
            L=spec.L if spec.L is not None
            else float(problem.smoothness())).validate()
        from repro.core.fedplt import FedPLT

        prox_override = (self.spec.resolve_prox_h()
                         if self.spec.weight_decay != 0.0 else None)
        groups = self._resolved.resolved_groups()
        solver_groups = None
        if groups is not None:
            solver_groups = tuple(
                (g.size, scfg) for g, scfg in zip(
                    groups, self._resolved.group_solver_configs()))
        part = self._resolved.participation_schedule()
        self.problem = problem
        self.algo = FedPLT(problem, self.spec.to_dense_config(),
                           prox_h=prox_override,
                           solver_groups=solver_groups,
                           participation=part if isinstance(part, tuple)
                           else None,
                           mesh=self._resolved.build_mesh())

    def init(self, key: jax.Array):
        with telemetry.span("fedplt.init"):
            return self.algo.init(key)

    def step(self, state):
        """One Fed-PLT round (jitted)."""
        with telemetry.span("fedplt.step", step=True):
            return self.algo.round(state)

    def run(self, key: jax.Array, n_rounds: int):
        """Run from a fresh init; returns (state, criterion_history)."""
        with telemetry.span("fedplt.run", rounds=n_rounds):
            return self.algo.run(key, n_rounds)

    def run_recorded(self, key: jax.Array, n_rounds: int):
        """:meth:`run` that also returns the realized ``(n_rounds, N)``
        arrival schedule (feed it to :meth:`effective_privacy_report`
        or :meth:`replay`)."""
        with telemetry.span("fedplt.run", rounds=n_rounds):
            return self.algo.run_recorded(key, n_rounds)

    def replay(self, key: jax.Array, schedule):
        """Re-run a recorded arrival schedule through the in-jit async
        model (bit-identical to the run that recorded it)."""
        return self.algo.replay(key, schedule)

    def round_with_faults(self, state, arrival=None, corrupt=None,
                          live=None):
        """One round under broker-supplied fault overrides: ``arrival``
        (N,) 0/1 row, ``corrupt`` (N,) per-agent corruption multipliers
        (0 = clean), ``live`` (N,) 0/1 survivor mask.  All None
        reproduces :meth:`step` bitwise."""
        return self.algo.round_with_faults(state, arrival, corrupt, live)

    def consensus(self, state):
        return self.algo.x_bar(state)

    def privacy_report(self, n_rounds: int,
                       local_dataset_size=None,
                       delta: Optional[float] = None):
        """``local_dataset_size`` may be one int or a per-agent sequence
        of q_i (defaults to the problem's uniform q)."""
        q = (local_dataset_size if local_dataset_size is not None
             else self.problem.q)
        return privacy_report(self._resolved, n_rounds, q, delta,
                              mu=self.algo.mu if self.algo.mu > 0
                              else None)

    def effective_privacy_report(self, schedule,
                                 local_dataset_size=None,
                                 delta: Optional[float] = None):
        """Per-agent report under a realized async arrival schedule
        (see :func:`repro.fed.api.effective_privacy_report`)."""
        q = (local_dataset_size if local_dataset_size is not None
             else self.problem.q)
        return effective_privacy_report(
            self._resolved, schedule, q, delta,
            mu=self.algo.mu if self.algo.mu > 0 else None)


class ModelTrainer(FedTrainer):
    """:mod:`repro.fed.runtime` behind the FedTrainer handle."""

    def __init__(self, model, spec: FedSpec, use_remat: bool = True):
        if spec.n_agents is None:
            raise ValueError("FedSpec.n_agents is required at model scale")
        if spec.gamma is None:
            raise ValueError("FedSpec.gamma is required at model scale "
                             "(the local moduli are unknown)")
        from repro.fed import runtime

        self.spec = spec.validate()
        self.model = model
        self._runtime = runtime
        # packed layout: the one static buffer meta of the run, needed
        # for the API-boundary unpack (consensus / checkpoint targets)
        self.packed_meta = (runtime.packed_layout(model, self.spec)
                            if self.spec.state_layout == "packed"
                            else None)
        # sharded rounds: the (agent, model) mesh of the run; the round
        # engine wraps the edges in shard_map on it and init places the
        # state by repro.fed.sharding.fed_state_specs (the one placement
        # source, shared with the dry-run compiler)
        self.mesh = self.spec.build_mesh()
        # the state is donated: each new leaf may take the old one's
        # buffer, so a round holds one state, not two, and XLA need not
        # recompute the solver's forward pass to fit in memory
        self._step = jax.jit(
            runtime.make_train_step(model, spec, use_remat=use_remat),
            donate_argnums=0)

    def _state_shardings(self):
        from repro.fed import sharding

        axes = dict(zip(self.mesh.axis_names, self.mesh.devices.shape))
        agent_axis, fsdp_axis = sharding.fed_axes(axes)
        shapes = jax.eval_shape(self.model.init, jax.random.PRNGKey(0))
        stacked = jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(
                (self.spec.n_agents,) + s.shape, s.dtype), shapes)
        specs = sharding.fed_state_specs(
            stacked, fsdp_axis=fsdp_axis, agent_axis=agent_axis,
            axis_sizes=axes,
            compressed=self.spec.compression.name != "none",
            packed=self.spec.state_layout == "packed",
            stale=self.spec.staleness_config().enabled)
        return sharding.shardings(self.mesh, specs)

    def init(self, key: jax.Array):
        with telemetry.span("fedplt.init"):
            state = self._runtime.init_state(self.model, key, self.spec)
            if self.mesh is None:
                return state
            return jax.device_put(state, self._state_shardings())

    def step(self, state, batch, key: jax.Array, arrival=None,
             corrupt=None, live=None):
        """One jitted Fed-PLT round on an agent-stacked batch.
        ``arrival`` (async mode) replaces the arrival draw with a
        recorded (N,) 0/1 schedule row -- broker numerics / replay.
        ``corrupt`` / ``live`` are the broker's fault overrides (see
        :mod:`repro.fed.broker`): per-agent corruption multipliers and
        the survivor mask after evictions.

        ``state`` is consumed: its buffers are donated to the round and
        read as deleted afterwards.  Rebind it to the returned state; a
        caller that needs the old one copies it first
        (``jax.tree_util.tree_map(jnp.copy, state)``)."""
        with telemetry.span("fedplt.step", step=True):
            return self._step(state, batch, key, arrival, corrupt, live)

    def lower(self, state, batch, key: jax.Array, arrival=None,
              corrupt=None, live=None) -> jax.stages.Lowered:
        """The round :meth:`step` runs, lowered for these arguments
        (arrays or ``jax.ShapeDtypeStruct``); ``.compile().as_text()``
        is the HLO that :func:`repro.fed.telemetry.op_scopes` reads."""
        return self._step.lower(state, batch, key, arrival, corrupt, live)

    def run(self, key: jax.Array, n_rounds: int, batches):
        """Run from a fresh init.  ``batches`` is either a callable
        ``i -> batch`` or an iterable of per-round batches; returns
        ``(state, metrics_history)``.  Scalar metrics come back as
        floats; vector metrics (the async mode's per-agent ``arrivals``
        row) as numpy arrays."""
        import numpy as np

        with telemetry.span("fedplt.run", rounds=n_rounds):
            state = self.init(key)
            if callable(batches):
                get = batches
            else:
                it = iter(batches)
                get = lambda i: next(it)  # noqa: E731
            history = []
            for i in range(n_rounds):
                state, m = self.step(state, get(i),
                                     jax.random.fold_in(key, i))
                history.append({
                    k: float(v) if getattr(v, "ndim", 0) == 0
                    else np.asarray(v)
                    for k, v in m.items()})
            return state, history

    def consensus(self, state):
        return self._runtime.consensus_model(state, meta=self.packed_meta)

    def privacy_report(self, n_rounds: int,
                       local_dataset_size=None,
                       delta: Optional[float] = None):
        """``local_dataset_size`` may be one int or a per-agent sequence
        of q_i."""
        if local_dataset_size is None:
            raise ValueError("model-scale privacy_report needs the local "
                             "dataset size q_i")
        return privacy_report(self.spec, n_rounds, local_dataset_size,
                              delta)


def build_trainer(problem_or_model, spec: Any) -> FedTrainer:
    """The front door: a unified trainer over both Fed-PLT paths.

    Dense convex problems (``local_loss`` + ``n_agents``; see
    :mod:`repro.core.problem`) get the paper-faithful ``FedPLT`` engine
    front end; model objects (``init`` + ``loss_fn``; see
    :mod:`repro.models.model`) get the model-scale runtime.  ``spec``
    may be a :class:`FedSpec` or any legacy config with ``.to_spec()``.
    """
    spec = as_spec(spec)
    with telemetry.span("fedplt.build"):
        if hasattr(problem_or_model, "local_loss") and \
                hasattr(problem_or_model, "n_agents"):
            return DenseTrainer(problem_or_model, spec)
        if hasattr(problem_or_model, "loss_fn") and \
                hasattr(problem_or_model, "init"):
            return ModelTrainer(problem_or_model, spec)
    raise TypeError(
        f"cannot build a trainer for {type(problem_or_model).__name__}: "
        f"expected a dense problem (local_loss/n_agents) or a model "
        f"(init/loss_fn)")


# ---------------------------------------------------------------------------
# CLI generation: argparse flags derived from the spec fields
# ---------------------------------------------------------------------------

def _cli_entries():
    """(owner, field, flag, dest, argparse-kwargs) for every exposed
    spec field, derived from the dataclass metadata -- the CLI cannot
    drift from the spec because it is generated from it."""
    out = []
    for owner in ("spec", "privacy", "compression"):
        cls = {"spec": FedSpec, "privacy": PrivacySpec,
               "compression": CompressionSpec}[owner]
        for f in dataclasses.fields(cls):
            if dataclasses.is_dataclass(f.type) or f.name in (
                    "privacy", "compression"):
                continue
            meta = f.metadata.get("cli")
            if meta is None or not meta["expose"]:
                continue
            flag = meta["flag"] or "--" + f.name.replace("_", "-")
            dest = flag.lstrip("-").replace("-", "_")
            default = (meta["default"] if meta["default"] is not None
                       else f.default)
            kwargs = dict(default=default, help=meta["help"])
            if f.type in ("bool", bool):
                kwargs["action"] = "store_true"
            else:
                kwargs["type"] = meta["type"] or type(default)
                if meta["choices"]:
                    kwargs["choices"] = meta["choices"]
            if f.name == "name" and owner == "compression":
                kwargs["choices"] = available_compressors()
            if f.name == "aggregator" and owner == "spec":
                kwargs["choices"] = available_aggregators()
            out.append((owner, f.name, flag, dest, kwargs))
    return out


def add_spec_args(ap: argparse.ArgumentParser) -> argparse.ArgumentParser:
    """Add one flag per exposed :class:`FedSpec` field (fed mode)."""
    for _, _, flag, _, kwargs in _cli_entries():
        ap.add_argument(flag, **kwargs)
    return ap


def spec_from_args(args) -> FedSpec:
    """Build a :class:`FedSpec` from parsed args (or an argv list).

    Accepts either the ``argparse.Namespace`` of a parser that went
    through :func:`add_spec_args`, or a raw argv list, e.g.
    ``spec_from_args(["--tau", "0.1", "--solver", "gd"])``.
    """
    if not isinstance(args, argparse.Namespace):
        ap = argparse.ArgumentParser(prog="fedspec")
        add_spec_args(ap)
        args = ap.parse_args(list(args))
    buckets = {"spec": {}, "privacy": {}, "compression": {}}
    for owner, name, _, dest, _ in _cli_entries():
        buckets[owner][name] = getattr(args, dest)
    return FedSpec(privacy=PrivacySpec(**buckets["privacy"]),
                   compression=CompressionSpec(**buckets["compression"]),
                   **buckets["spec"])
