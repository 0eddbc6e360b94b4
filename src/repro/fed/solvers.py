"""Pluggable per-agent local-solver registry for the Fed-PLT engine.

The paper's flexibility claim -- "agents have the flexibility to choose
from various local training solvers" -- is a *per-agent* statement, so
solver dispatch mirrors the uplink-compressor registry
(:func:`repro.fed.compress.register_compressor`): a name maps to a
*factory* that builds an :data:`repro.fed.engine.LocalSolver` from a
:class:`repro.core.solvers.SolverConfig` plus the gradient oracle, and
``FedSpec`` and the train CLI generated from it reach registered
solvers by name.  Heterogeneous deployments assign a
different registered solver (and epochs / step size) to each agent
group; see ``FedSpec.agent_groups``.

New solvers plug in through :func:`register_solver`::

    @register_solver("signum")
    def make_signum(scfg, fgrad, rho, mu, L, *, use_pallas, has_aux):
        def solver(x, v, key):
            ...  # n_epochs sign-GD steps on d_i, warm-started at x
            return w, aux  # aux = the oracle's aux when has_aux,
        return solver      #       else None

The factory receives ``(scfg, fgrad, rho, mu, L)`` and keyword-only
``use_pallas`` / ``has_aux``; the returned solver must be warm-started
at its first argument and respect the engine's ``(x, v, key) ->
(w, aux)`` contract (leaves carry a leading agent axis).  With
``has_aux`` the oracle returns ``(grad, aux)`` and the solver should
return the stacked per-epoch aux (the model runtime reads it as the
per-agent loss trace); returning ``aux=None`` instead is tolerated --
the run still trains, the solver's agents just drop out of the loss
metric.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Tuple

# (x_stack, v_stack, key) -> (w_stack, aux) -- see repro.fed.engine
LocalSolver = Callable[[Any, Any, Any], Tuple[Any, Any]]
# (solver_cfg, fgrad, rho, mu, L, *, use_pallas, has_aux) -> LocalSolver
SolverFactory = Callable[..., LocalSolver]

_REGISTRY: Dict[str, SolverFactory] = {}


def register_solver(name: str) -> Callable[[SolverFactory], SolverFactory]:
    """Decorator registering a local-solver factory under ``name``."""

    def deco(fn: SolverFactory) -> SolverFactory:
        _REGISTRY[name] = fn
        return fn

    return deco


def get_solver(name: str) -> SolverFactory:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown solver {name!r}; registered: "
            f"{', '.join(available_solvers())}") from None


def available_solvers() -> list[str]:
    return sorted(_REGISTRY)


def make_local_solver(solver_cfg, fgrad, rho: float, mu: float = 0.0,
                      L: float = 0.0, *, use_pallas: bool = False,
                      has_aux: bool = False) -> LocalSolver:
    """Build the :data:`LocalSolver` registered under ``solver_cfg.name``.

    ``fgrad(w_stack, key)`` returns the per-agent gradient pytree (leaves
    ``(N, ...)``); with ``has_aux`` it returns ``(grads, aux)``.
    """
    factory = get_solver(solver_cfg.name)
    return factory(solver_cfg, fgrad, rho, mu, L, use_pallas=use_pallas,
                   has_aux=has_aux)


# ---------------------------------------------------------------------------
# Built-in solvers: the paper's gd / agd / sgd / noisy_gd, all served by
# core/solvers.local_train (which dispatches internally on scfg.name)
# ---------------------------------------------------------------------------

# The names served by core/solvers.local_train.  The dense front end
# (core/fedplt.py) keeps its historical per-agent vmap for exactly
# these; anything else registered here gets the stacked-oracle factory
# path.  One constant, imported there -- the lists must not drift.
CORE_SOLVERS = ("gd", "agd", "sgd", "noisy_gd")

# Core solvers whose update is purely elementwise on the state: under
# the packed layout they run core/solvers.local_train DIRECTLY on the
# resident (N, width) buffer, bit-identical per column to the per-leaf
# tree path.  noisy_gd is excluded -- its per-leaf noise folds the key
# per leaf, so a single buffer would change the DP noise stream -- and
# clipped runs are excluded at call time (the clip norm reduces per
# leaf before summing across the tree; one buffer would reorder it).
PACKED_DIRECT_SOLVERS = ("gd", "agd", "sgd")


def _core_local_train(scfg, fgrad, rho, mu, L, *, use_pallas, has_aux):
    from repro.core.solvers import local_train

    def solver(x, v, key):
        out = local_train(fgrad, x, v, rho, scfg, key, mu, L,
                          batched=True, has_aux=has_aux,
                          use_pallas=use_pallas)
        if has_aux:
            return out
        return out, None

    return solver


for _name in CORE_SOLVERS:
    register_solver(_name)(_core_local_train)
del _name


# ---------------------------------------------------------------------------
# Packed-layout adapters (repro.fed.engine layout contract): solvers for
# the resident (N, width) buffer
# ---------------------------------------------------------------------------

def wrap_packed_solver(solver: LocalSolver, meta) -> LocalSolver:
    """Adapt a tree-form :data:`LocalSolver` to the packed layout:
    unpack the resident buffers, run the solver on the tree, pack the
    result -- all inside the round's jit.  The exact-bits fallback for
    solvers whose internals depend on the leaf decomposition."""
    from repro.fed.compress import pack_leaves, unpack_leaves

    def packed(x_buf, v_buf, key):
        w, aux = solver(unpack_leaves(x_buf, meta),
                        unpack_leaves(v_buf, meta), key)
        return pack_leaves(w)[0], aux

    return packed


def make_packed_local_solver(solver_cfg, fgrad, rho: float,
                             mu: float = 0.0, L: float = 0.0, *, meta,
                             use_pallas: bool = False,
                             has_aux: bool = False) -> LocalSolver:
    """Build a :data:`LocalSolver` operating on the resident packed
    buffer (``meta`` is its static :class:`~repro.fed.compress.PackedMeta`).

    :data:`PACKED_DIRECT_SOLVERS` with no clipping run
    ``core/solvers.local_train`` directly on the ``(N, width)`` buffer
    -- the update is elementwise, so every column computes exactly what
    the per-leaf path computes -- with the gradient oracle wrapped as
    unpack-inside-jit (``unpack_leaves -> fgrad -> pack_leaves``): the
    state path itself carries zero pack/unpack; the only remaining
    layout traffic is the oracle's slice/update-slice chain on gradient
    values.  ``noisy_gd`` and clipped configurations instead fall back
    to :func:`wrap_packed_solver` around the registered tree solver,
    preserving their exact PRNG/reduction streams (see
    :data:`PACKED_DIRECT_SOLVERS`)."""
    from repro.fed.compress import pack_leaves, unpack_leaves

    direct = (solver_cfg.name in PACKED_DIRECT_SOLVERS
              and solver_cfg.clip is None)
    if not direct:
        return wrap_packed_solver(
            make_local_solver(solver_cfg, fgrad, rho, mu, L,
                              use_pallas=use_pallas, has_aux=has_aux),
            meta)

    from repro.core.solvers import local_train

    def fgrad_buf(w_buf, key):
        out = fgrad(unpack_leaves(w_buf, meta), key)
        if has_aux:
            g, aux = out
            return pack_leaves(g)[0], aux
        return pack_leaves(out)[0]

    def solver(x_buf, v_buf, key):
        out = local_train(fgrad_buf, x_buf, v_buf, rho, solver_cfg, key,
                          mu, L, batched=True, has_aux=has_aux,
                          use_pallas=use_pallas)
        if has_aux:
            return out
        return out, None

    return solver
