"""Fed-PLT at model scale: the paper's Algorithm 1 on parameter pytrees.

The paper's agents become mesh slices: per-agent states (x_i, z_i) are the
model parameter pytree stacked on a leading agent axis (sharded over
'data' on a single pod, over 'pod' across pods).  One jitted
``train_step`` is one Fed-PLT round, delegated to the unified round
engine (:mod:`repro.fed.engine`):

  1. coordinator:  y = prox_h( mean_A z )        -- ONE agent-axis
     all-reduce per round (vs one per step for FedAvg-style DP training:
     this is the paper's communication saving, mapped to the inter-slice
     link);
  2. N_e local epochs of the chosen solver (gd / agd / sgd / noisy_gd,
     :mod:`repro.core.solvers` generalized to pytrees) -- no agent-axis
     collectives inside; the fused update is the fedplt_update Pallas
     kernel on TPU;
  3. masked participation update of (x, z), optionally with topk/int8
     increment compression of the z uplink (lag-based error feedback).

The gradient grad f_i is computed on the agent's local batch, vmapped over
the agent axis; within an agent, activations shard over 'model' (+'data'
in multi-pod fed mode).

Every entry point takes a :class:`repro.fed.api.FedSpec`;
:func:`make_train_step` validates it and takes the round's engine config
from ``spec.round_config()``.
"""

from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.fed import engine
from repro.fed.api import FedSpec
from repro.models.model import Model


class FedState(NamedTuple):
    """Per-agent federated state.

    Tree layout (default): ``x``/``z``/``t`` are parameter pytrees with
    leaves ``(A, ...)``.  Packed layout (``spec.state_layout ==
    "packed"``, engine layout contract): each is ONE resident
    ``(A, width)`` buffer laid out by the static
    :func:`packed_layout` meta -- packed once in :func:`init_state`,
    unpacked only at the API boundary (:func:`consensus_model`,
    checkpoint restore targets)."""

    x: Any              # pytree, leaves (A, ...) -- or (A, width) buffer
    z: Any              # pytree, leaves (A, ...) -- or (A, width) buffer
    step: jnp.ndarray
    # coordinator's copy of z -- only materialized when the z-exchange is
    # compressed (None otherwise: at model scale t doubles state memory)
    t: Any = None
    # bounded-staleness async rounds only (None when synchronous): the
    # per-agent pulled coordinator point and staleness counters carried
    # by repro.fed.async_engine
    y_tag: Any = None
    staleness: Any = None   # (A,) int32


def packed_layout(model: Model, spec: FedSpec):
    """The static :class:`repro.fed.compress.PackedMeta` of a model's
    agent-stacked state -- pure shape arithmetic over
    ``jax.eval_shape(model.init)``, so no parameters are materialized.
    One meta serves the whole run (init, every round, the API
    boundary)."""
    from repro.fed import compress as compress_lib

    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    stacked = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct((spec.n_agents,) + s.shape,
                                       s.dtype), shapes)
    return compress_lib.packed_meta(stacked)


def init_state(model: Model, key: jax.Array, spec: FedSpec) -> FedState:
    """The agent-stacked starting state of a run.

    Under the packed layout the broadcast parameter stack is packed
    ONCE here -- the round loop never packs again.  ``x``, ``z`` and
    ``t`` start equal but hold buffers of their own: the trainer
    donates the state to its round, and one buffer cannot be donated
    twice."""
    params = model.init(key)
    stacked = jax.tree_util.tree_map(
        lambda p: jnp.broadcast_to(p, (spec.n_agents,) + p.shape), params)
    if spec.state_layout == "packed":
        from repro.fed.compress import pack_leaves

        stacked = pack_leaves(stacked)[0]
    copy = lambda: jax.tree_util.tree_map(jnp.copy, stacked)  # noqa: E731
    t = copy() if spec.compression.name != "none" else None
    stale = spec.staleness_config().enabled
    return FedState(x=stacked, z=copy(), step=jnp.zeros((), jnp.int32),
                    t=t,
                    y_tag=(jax.tree_util.tree_map(jnp.zeros_like, stacked)
                           if stale else None),
                    staleness=(jnp.zeros((spec.n_agents,), jnp.int32)
                               if stale else None))


def make_train_step(model: Model, spec: FedSpec):
    """Returns ``step(state, batch, key) -> (state, metrics)``.

    ``batch`` leaves carry a leading agent axis: tokens (A, b, S), etc.
    """
    spec = spec.validate()            # the ONE validation site
    scfg = spec.solver_config()
    ecfg = spec.round_config()
    mesh = spec.build_mesh()          # None = unsharded rounds
    prox_h = spec.resolve_prox_h()
    mu, L = spec.moduli()
    groups = spec.resolved_groups()
    group_cfgs = spec.group_solver_configs()
    # packed layout: one static meta; solvers are built on the resident
    # buffer (gd/agd/sgd run directly on it, the gradient oracle
    # unpacking inside the jit -- see repro.fed.solvers)
    meta = (packed_layout(model, spec)
            if spec.state_layout == "packed" else None)
    if meta is not None:
        from repro.fed.solvers import make_packed_local_solver

        def make_solver(cfg_s, fgrad, mu_s, L_s):
            return make_packed_local_solver(
                cfg_s, fgrad, spec.rho, mu_s, L_s, meta=meta,
                use_pallas=spec.use_pallas, has_aux=True)
    else:
        def make_solver(cfg_s, fgrad, mu_s, L_s):
            return engine.make_local_solver(
                cfg_s, fgrad, spec.rho, mu_s, L_s,
                use_pallas=spec.use_pallas, has_aux=True)

    def per_agent_loss(params_i, batch_i):
        return model.loss_fn(params_i, batch=batch_i, remat=True)

    grad_fn = jax.value_and_grad(per_agent_loss)

    def train_step(state: FedState, batch, key: jax.Array, arrival=None,
                   corrupt=None, live=None):
        rkey = jax.random.fold_in(key, state.step)

        def fgrad_for(batch_slice):
            def fgrad(w, k):
                del k  # the local batch is fixed within a round
                losses, g = jax.vmap(grad_fn)(w, batch_slice)
                return g, losses
            return fgrad

        if groups is None:
            local_solver = make_solver(scfg, fgrad_for(batch), mu, L)
        else:
            # heterogeneous groups: each contiguous agent slice gets its
            # own registered solver over its slice of the batch, with
            # moduli derived from the group's own step size
            local_solver, start = [], 0
            for g, gscfg in zip(groups, group_cfgs):
                stop = start + g.size
                batch_g = jax.tree_util.tree_map(
                    lambda b, lo=start, hi=stop: b[lo:hi], batch)
                mu_g, L_g = spec.moduli_for(gscfg.step_size)
                local_solver.append(engine.SolverGroup(
                    g.size, make_solver(gscfg, fgrad_for(batch_g),
                                        mu_g, L_g)))
                start = stop
            local_solver = tuple(local_solver)

        t = state.t if ecfg.compressed else state.z
        if ecfg.staleness.enabled:
            from repro.fed import async_engine

            if meta is not None:
                res = async_engine.packed_async_round_step(
                    ecfg, meta, state.x, state.z, t, state.y_tag,
                    state.staleness, rkey, local_solver, prox_h=prox_h,
                    arrival=arrival, mesh=mesh, corrupt=corrupt,
                    live=live)
            else:
                res = async_engine.async_round_step(
                    ecfg, state.x, state.z, t, state.y_tag,
                    state.staleness, rkey, local_solver, prox_h=prox_h,
                    arrival=arrival, mesh=mesh, corrupt=corrupt,
                    live=live)
        elif arrival is not None:
            raise ValueError("arrival schedules require async_mode="
                             "'stale' (synchronous rounds draw "
                             "participation internally)")
        elif meta is not None:
            res = engine.packed_round_step(ecfg, meta, state.x, state.z,
                                           t, rkey, local_solver,
                                           prox_h=prox_h, mesh=mesh,
                                           corrupt=corrupt, live=live)
        else:
            res = engine.round_step(ecfg, state.x, state.z, t, rkey,
                                    local_solver, prox_h=prox_h,
                                    mesh=mesh, corrupt=corrupt,
                                    live=live)

        # aux is the (N_e, A) per-epoch loss stack when homogeneous, a
        # tuple of per-group (N_e_g, size_g) stacks when grouped (epoch
        # counts may differ per group).  A custom registry solver may
        # return aux=None -- its agents drop out of the metric (NaN when
        # nobody reports) rather than crashing the round.
        if groups is None or len(local_solver) == 1:
            lasts = [] if res.aux is None else [res.aux[-1]]
        else:
            lasts = [a[-1] for a in (res.aux or ()) if a is not None]
        loss = (jnp.mean(jnp.concatenate(lasts)) if lasts
                else jnp.asarray(jnp.nan, jnp.float32))
        metrics = {
            "loss": loss,
            "participation": jnp.mean(res.u.astype(jnp.float32)),
        }
        if ecfg.staleness.enabled:
            # the realized (A,) arrival row -- stack over rounds to get
            # the schedule effective_privacy_report composes over
            metrics["arrivals"] = res.u
            metrics["staleness"] = jnp.mean(
                res.staleness.astype(jnp.float32))
            new_state = FedState(x=res.x, z=res.z, step=state.step + 1,
                                 t=res.t if ecfg.compressed else None,
                                 y_tag=res.y_tag,
                                 staleness=res.staleness)
        else:
            new_state = FedState(x=res.x, z=res.z, step=state.step + 1,
                                 t=res.t if ecfg.compressed else None)
        return new_state, metrics

    return train_step


def consensus_model(state: FedState, meta=None):
    """The deployable model: the coordinator average of the agent states.

    ``meta`` is required for a packed-layout state (the API-boundary
    unpack of the layout contract); the tree layout ignores it."""
    x = state.x
    if meta is not None:
        from repro.fed.compress import unpack_leaves

        x = unpack_leaves(x, meta)
    return jax.tree_util.tree_map(lambda l: jnp.mean(l, axis=0), x)
