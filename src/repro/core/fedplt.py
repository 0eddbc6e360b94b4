"""Fed-PLT -- Algorithm 1 of the paper, vectorized over agents.

This is the paper-faithful *dense* front end: local states are a single
``(N, n)`` array, i.e. the single-leaf case of the unified round engine
in :mod:`repro.fed.engine`, which owns the round topology (coordinator
prox -> reflection -> warm-started local solver -> Bernoulli
participation -> optional compressed z-exchange).  This class only
supplies the per-agent gradient oracles, curvature moduli, and the
``lax.scan`` training loop that records the paper's convergence
criterion.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core.solvers import SolverConfig, local_train
from repro.fed import engine


class FedPLTState(NamedTuple):
    x: jnp.ndarray      # (N, n) local models
    z: jnp.ndarray      # (N, n) auxiliary (PRS) variables
    y: jnp.ndarray      # (n,)  coordinator model (last broadcast)
    key: jax.Array
    k: jnp.ndarray      # round counter
    # coordinator's copy of each z_i; lags z by the never-transmitted
    # residual when the exchange is compressed.  None when uncompressed:
    # the coordinator then sees z exactly and a separate copy would just
    # double z-memory.
    t: Optional[jnp.ndarray] = None
    # bounded-staleness async rounds only (None when synchronous):
    # per-agent pulled coordinator point and staleness counters (the
    # carry of repro.fed.async_engine.async_round_step)
    y_tag: Optional[jnp.ndarray] = None     # (N, n)
    staleness: Optional[jnp.ndarray] = None  # (N,) int32


def _jit_data_as_args(fn, static_argnums=()):
    """``jax.jit(fn, static_argnums=...)`` whose executable takes the
    arrays ``fn`` closes over (the problem's data, the curvature moduli)
    as arguments, as an eager ``lax.scan`` does.  Embedded as constants,
    XLA folds them into the program: on the chip that moved the solve's
    last bits and lengthened set-up by about a third.  One executable per
    static value and argument shape; it caches programs, never results."""
    compiled = {}

    def call(*args):
        dynamic = [a for i, a in enumerate(args) if i not in static_argnums]
        flat, tree = jax.tree_util.tree_flatten(dynamic)
        sig = (tuple(args[i] for i in static_argnums), tree,
               tuple(map(jax.typeof, flat)))
        if sig not in compiled:
            closed, out = jax.make_jaxpr(fn, static_argnums=static_argnums,
                                         return_shape=True)(*args)
            run = jax.jit(functools.partial(jax.core.eval_jaxpr,
                                            closed.jaxpr))
            compiled[sig] = (run, closed.consts,
                             jax.tree_util.tree_structure(out))
        run, consts, out_tree = compiled[sig]
        return jax.tree_util.tree_unflatten(out_tree, run(consts, *flat))

    return call


class FedPLT:
    """Paper-faithful Fed-PLT on a vectorized federated problem.

    ``spec`` is a :class:`repro.fed.api.FedSpec`; its ``n_agents`` is
    filled from the problem, and ``mu`` / ``L`` left None are the
    problem's own moduli.  ``spec.agent_groups`` partitions the agent
    axis into heterogeneous groups, each running its own solver/epochs/
    step on its contiguous slice (the paper's "agents choose their local
    solver"); None reproduces the homogeneous trajectory.  A per-agent
    ``spec.participation`` tuple gives each agent its own Bernoulli rate.

    ``mesh`` (an ``(agent, model)`` :class:`jax.sharding.Mesh`, e.g.
    from :meth:`repro.fed.api.FedSpec.build_mesh`) shards the agent
    axis of every round across the mesh per the engine's mesh contract;
    a 1-device mesh reproduces the unsharded trajectory bitwise."""

    def __init__(self, problem, spec, mesh=None):
        if spec.n_agents not in (None, problem.n_agents):
            raise ValueError(f"spec.n_agents={spec.n_agents} != "
                             f"problem.n_agents={problem.n_agents}")
        spec = dataclasses.replace(spec, n_agents=problem.n_agents)
        self.problem = problem
        self.spec = spec
        self.mesh = mesh
        # the problem's moduli stay arrays: as Python floats they would
        # be folded into the compiled solve as constants
        self.mu = spec.mu if spec.mu is not None else problem.strong_convexity()
        self.L = spec.L if spec.L is not None else problem.smoothness()
        if self.mu <= 0:  # nonconvex / merely-convex: fall back to 1/rho curvature
            self.mu = 0.0
        if spec.uncoordinated and hasattr(problem, "per_agent_smoothness"):
            self.mu_i = problem.per_agent_strong_convexity()
            self.L_i = problem.per_agent_smoothness()
        else:
            N = problem.n_agents
            self.mu_i = jnp.full((N,), self.mu)
            self.L_i = jnp.full((N,), self.L)
        self.prox_h = spec.resolve_prox_h()
        self._ecfg = spec.round_config()
        # packed layout: the dense state is single-leaf, so its resident
        # (N, n) buffer IS the stacked array (pack_leaves fast path, no
        # lane padding) -- the meta is pure shape arithmetic and the
        # historical solvers consume the buffer unchanged
        self._meta = None
        if spec.state_layout == "packed":
            from repro.fed import compress as compress_lib
            self._meta = compress_lib.packed_meta(jax.ShapeDtypeStruct(
                (problem.n_agents, problem.dim), jnp.float32))
        groups = spec.resolved_groups()
        if groups is None:
            # the homogeneous path is the single full-size group; a
            # [0:N] slice is a no-op, so this is bit-identical to the
            # historical dedicated path
            self._solvers = self._make_group_solver(
                0, problem.n_agents, spec.solver_config())
        else:
            self._solvers, start = [], 0
            for g, scfg in zip(groups, spec.group_solver_configs()):
                self._solvers.append(engine.SolverGroup(
                    g.size, self._make_group_solver(start, g.size, scfg)))
                start += g.size
            self._solvers = tuple(self._solvers)
        self._round = jax.jit(self._round_impl)
        self._round_arrival = jax.jit(self._round_core)
        # the whole solve (fresh init, scan over rounds, criterion) as one
        # executable per length: an eager scan traces a new body on every
        # call, so it misses the dispatch cache and compiles each time
        self._solve = _jit_data_as_args(self._solve_impl, static_argnums=(1,))
        self._replay = _jit_data_as_args(self._replay_impl)

    # ------------------------------------------------------------------
    def init(self, key: jax.Array) -> FedPLTState:
        N, n = self.problem.n_agents, self.problem.dim
        k_init, k_state = jax.random.split(key)
        tau = self.spec.privacy.tau
        if self.spec.privacy.dp_init and tau > 0 and self.mu > 0:
            std = jnp.sqrt(2.0 * tau ** 2 / self.mu)
            # the barrier keeps XLA from folding std into the draw's own
            # constants when init is compiled, so x0 has the same bits
            # compiled as op by op
            x0 = std * jax.lax.optimization_barrier(
                jax.random.normal(k_init, (N, n)))
        else:
            x0 = jnp.zeros((N, n))
        # t (the coordinator's copy) is only materialized when the
        # exchange is compressed; uncompressed it would double z-memory
        stale = self._ecfg.staleness.enabled
        return FedPLTState(x=x0, z=x0, y=jnp.zeros(n), key=k_state,
                           k=jnp.zeros((), jnp.int32),
                           t=x0 if self._ecfg.compressed else None,
                           y_tag=jnp.zeros((N, n)) if stale else None,
                           staleness=(jnp.zeros((N,), jnp.int32)
                                      if stale else None))

    # ------------------------------------------------------------------
    def _fgrad(self, data, w, key, scfg):
        """Per-agent gradient oracle (full or minibatch)."""
        batch_size = self.spec.batch_size
        if scfg.name == "sgd" and batch_size is not None:
            q = data[0].shape[0]
            idx = jax.random.randint(key, (batch_size,), 0, q)
            return self.problem.minibatch_grad(data, w, idx)
        return jax.grad(lambda xx: self.problem.local_loss(data, xx))(w)

    def _agent_data(self):
        # Problems expose stacked per-agent arrays; assemble the leaf tuple.
        if hasattr(self.problem, "A"):
            return (self.problem.A, self.problem.b)
        return (self.problem.Q, self.problem.c)

    # ------------------------------------------------------------------
    def _make_group_solver(self, start: int, size: int,
                           scfg: SolverConfig):
        """Engine LocalSolver for agents ``[start, start+size)`` running
        their own ``scfg``.

        Core solvers keep the historical per-agent vmap + key split over
        ``local_train`` with (possibly per-agent, Remark 1) curvature
        moduli -- restricted to the group's slice of the data and
        moduli, so the single full-size group IS the homogeneous path,
        bit for bit.  Any other name is a :mod:`repro.fed.solvers`
        registry entry and is built through its factory on a stacked
        gradient oracle (the same batched contract the model path uses),
        so registered custom solvers are reachable from the dense front
        end too."""
        stop = start + size
        from repro.fed import solvers as solver_registry

        if scfg.name not in solver_registry.CORE_SOLVERS:

            def fgrad_stacked(w_stack, key):
                data_g = tuple(a[start:stop] for a in self._agent_data())
                keys = jax.random.split(key, size)
                return jax.vmap(
                    lambda d, w, k: self._fgrad(d, w, k, scfg))(
                        data_g, w_stack, keys)

            return solver_registry.make_local_solver(
                scfg, fgrad_stacked, self.spec.rho, self.mu, self.L)

        def solver(x_g, v_g, k_solve):
            solver_keys = jax.random.split(k_solve, size)
            data_g = tuple(a[start:stop] for a in self._agent_data())

            def one_agent(data_i, x_i, v_i, key_i, mu_i, L_i):
                fgrad = lambda w, k: self._fgrad(data_i, w, k, scfg)
                return local_train(fgrad, x_i, v_i, self.spec.rho, scfg,
                                   key_i, mu_i, L_i)

            w = jax.vmap(one_agent)(data_g, x_g, v_g, solver_keys,
                                    self.mu_i[start:stop],
                                    self.L_i[start:stop])
            return w, None

        return solver

    def _round_core(self, state: FedPLTState, arrival=None,
                    corrupt=None, live=None):
        """One round; returns ``(next_state, u)`` with ``u`` the round's
        realized (N,) participation / arrival mask.  ``arrival``
        (async mode only) substitutes a recorded schedule row for the
        Bernoulli draw -- the broker replay path.  ``corrupt`` / ``live``
        are broker-realized fault rows (corruption injection / eviction
        masks; see :func:`repro.fed.engine.round_step`) and work in both
        synchrony modes."""
        compressed = self._ecfg.compressed
        t = state.t if compressed else state.z
        if self._ecfg.staleness.enabled:
            from repro.fed import async_engine

            step = (async_engine.packed_async_round_step
                    if self._meta is not None
                    else async_engine.async_round_step)
            extra = (self._meta,) if self._meta is not None else ()
            res = step(self._ecfg, *extra, state.x, state.z, t,
                       state.y_tag, state.staleness, state.key,
                       self._solvers, prox_h=self.prox_h,
                       arrival=arrival, mesh=self.mesh,
                       corrupt=corrupt, live=live)
            y = res.y.reshape(-1) if self._meta is not None else res.y
            return FedPLTState(x=res.x, z=res.z, y=y, key=res.next_key,
                               k=state.k + 1,
                               t=res.t if compressed else None,
                               y_tag=res.y_tag,
                               staleness=res.staleness), res.u
        if arrival is not None:
            raise ValueError("arrival schedules require async_mode="
                             "'stale' (synchronous rounds draw "
                             "participation internally)")
        if self._meta is not None:
            res = engine.packed_round_step(
                self._ecfg, self._meta, state.x, state.z, t, state.key,
                self._solvers, prox_h=self.prox_h, mesh=self.mesh,
                corrupt=corrupt, live=live)
            y = res.y.reshape(-1)   # (1, n) coordinator buffer -> (n,)
        else:
            res = engine.round_step(self._ecfg, state.x, state.z, t,
                                    state.key, self._solvers,
                                    prox_h=self.prox_h, mesh=self.mesh,
                                    corrupt=corrupt, live=live)
            y = res.y
        return FedPLTState(x=res.x, z=res.z, y=y, key=res.next_key,
                           k=state.k + 1,
                           t=res.t if compressed else None), res.u

    def _round_impl(self, state: FedPLTState) -> FedPLTState:
        return self._round_core(state)[0]

    # ------------------------------------------------------------------
    def round(self, state: FedPLTState) -> FedPLTState:
        return self._round(state)

    def round_with_arrival(self, state: FedPLTState, arrival=None):
        """One jitted round returning ``(next_state, u)``; ``arrival``
        optionally replaces the arrival draw with a recorded (N,) 0/1
        row (async mode) -- the broker's numerics entry point."""
        return self._round_arrival(state, arrival)

    def round_with_faults(self, state: FedPLTState, arrival=None,
                          corrupt=None, live=None):
        """One jitted round returning ``(next_state, u)`` with the full
        broker override set: ``arrival`` (recorded schedule row, async
        mode), ``corrupt`` (per-agent corruption multipliers applied to
        the solver output) and ``live`` (0/1 eviction mask; the
        coordinator averages over survivors).  The fault-capable broker
        entry point -- e.g.
        ``lambda s, u, c, l: algo.round_with_faults(s, u, c, l)[0]``.
        All-None reproduces :meth:`round_with_arrival` bitwise."""
        return self._round_arrival(state, arrival, corrupt, live)

    def run(self, key: jax.Array, n_rounds: int):
        """Run ``n_rounds`` rounds; returns (final_state, criterion_history).

        criterion_history[k] = || sum_i grad f_i(x_bar_k) ||^2 *after* round k.
        """
        state, crit, _ = self.run_recorded(key, n_rounds)
        return state, crit

    def run_recorded(self, key: jax.Array, n_rounds: int):
        """:meth:`run` that also returns the realized ``(n_rounds, N)``
        arrival schedule (the stacked per-round masks -- feed it to
        :func:`repro.fed.api.effective_privacy_report` or replay it with
        :meth:`replay`)."""
        return self._solve(key, n_rounds)

    def _solve_impl(self, key, n_rounds):
        def body(s, _):
            s, u = self._round_core(s)
            return s, (self.problem.criterion(s.x), u)

        state, (crit, sched) = jax.lax.scan(body, self.init(key), None,
                                            length=n_rounds)
        return state, crit, sched

    def replay(self, key: jax.Array, schedule):
        """Re-run a recorded ``(n_rounds, N)`` arrival schedule through
        the in-jit async model; returns (final_state, criterion_history)
        bit-identical to the run that recorded it (same init key)."""
        if not self._ecfg.staleness.enabled:
            raise ValueError("replay requires async_mode='stale'")
        return self._replay(key, jnp.asarray(schedule, jnp.float32))

    def _replay_impl(self, key, schedule):
        def body(s, row):
            s, _ = self._round_core(s, row)
            return s, self.problem.criterion(s.x)

        return jax.lax.scan(body, self.init(key), schedule)

    # convenience -------------------------------------------------------
    def x_bar(self, state: FedPLTState) -> jnp.ndarray:
        return jnp.mean(state.x, axis=0)
