"""Round-engine structure: what one round launches, counted from its
trace -- no wall time (device times come from the chip benchmark under
``bench/``; CPU and interpret-mode times are not evidence of speed).

* **Round structure**: state-path op counts of one round --
  concatenate / gather / dynamic_update_slice per (layout x backend)
  at engine scale with an elementwise oracle, so the counts measure the
  STATE path, not the model's forward/backward.  The committed baseline
  asserts the packed rounds contain zero concatenates and that the
  packed pallas round's update-slice count collapses to the oracle's
  single pack.
* **Sharded structure**: fused edge launches per shard of the
  mesh-sharded packed round (exactly two: the partial-sum uplink and
  the presummed downlink; the psum is a collective, not a launch).
* **Round edges**: the coordinator edge (prox + reflect; z-update +
  participation selects) at ENGINE SCALE -- N >= 32 agents on a ragged
  multi-leaf tree: jaxpr ops of the per-leaf XLA edge vs pallas_call
  launches of the fused edge (two).

``run`` returns ``(rows, payload)``: CSV rows plus the JSON-able dict
``benchmarks.run --json`` writes (committed baseline:
``BENCH_engine.json``).
"""

import jax
import jax.numpy as jnp

from repro.core import prox as prox_lib
from repro.fed import engine
from repro.kernels.round_edge import ops as edge_ops

# engine-scale round-edge case: agents x ragged transformer-like leaves
EDGE_N_AGENTS = 64
EDGE_WIDTHS = (1024, 256, 256, 64, 512, 512, 64, 16) * 25   # 200 leaves


def _count_prims(jaxpr, name):
    return engine.count_primitives(jaxpr, [name])[name]


def _round_structure():
    """State-path op counts of one full round per (layout x backend).

    Uses the engine-scale ragged tree with an ELEMENTWISE gradient
    oracle, so concatenate / gather / dynamic_update_slice counts
    measure the state path only (a real model's forward/backward adds
    its own value-path ops, identical across layouts).  The packed
    rows' zero concatenate count is the layout contract's headline
    property; the CI engine smoke asserts it from the committed JSON.
    """
    from repro.core.solvers import SolverConfig
    from repro.fed import compress as compress_lib
    from repro.fed.solvers import make_packed_local_solver

    n = 8
    tree = {f"l{i}": jnp.ones((n, w))
            for i, w in enumerate(EDGE_WIDTHS[:16])}
    meta = compress_lib.packed_meta(tree)
    buf, _ = compress_lib.pack_leaves(tree)

    def fgrad(w, k):
        return jax.tree_util.tree_map(lambda l: 0.1 * l, w)

    scfg = SolverConfig(name="gd", n_epochs=2, step_size=0.1)
    rows, payload = [], []
    for layout in ("tree", "packed"):
        for backend in ("xla", "pallas"):
            cfg = engine.RoundConfig(n_agents=n, rho=1.0, damping=0.5,
                                     participation=0.9,
                                     engine_backend=backend,
                                     state_layout=layout)
            if layout == "packed":
                solver = make_packed_local_solver(
                    scfg, fgrad, cfg.rho, 0.1, 1.0, meta=meta)
                jaxpr = jax.make_jaxpr(
                    lambda x, z, t, k: engine.packed_round_step(
                        cfg, meta, x, z, t, k, solver))(
                    buf, buf, buf, jax.random.PRNGKey(0)).jaxpr
            else:
                solver = engine.make_local_solver(scfg, fgrad, cfg.rho,
                                                  0.1, 1.0)
                jaxpr = jax.make_jaxpr(
                    lambda x, z, t, k: engine.round_step(
                        cfg, x, z, t, k, solver))(
                    tree, tree, tree, jax.random.PRNGKey(0)).jaxpr
            counts = engine.count_primitives(
                jaxpr, ["concatenate", "gather", "dynamic_update_slice"])
            rows.append(
                f"engine,structure:{layout}_{backend},"
                f"concat={counts['concatenate']},"
                f"gather={counts['gather']},"
                f"dus={counts['dynamic_update_slice']}")
            payload.append(dict(
                kind="round_structure", layout=layout, backend=backend,
                concatenate=counts["concatenate"],
                gather=counts["gather"],
                dynamic_update_slice=counts["dynamic_update_slice"],
                n_agents=n, n_leaves=len(tree)))
    return rows, payload


def _sharded():
    """Fused edge launches per shard of the mesh-sharded packed round
    (ROADMAP item 2), counted on the TPU-shaped (interpret=False) trace
    of the sharded edges -- the partial-sum uplink + presummed
    downlink.  The CI sharded smoke asserts exactly TWO."""
    import numpy as np
    from jax.sharding import Mesh

    mesh1 = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1),
                 ("agent", "model"))
    zt = jnp.zeros((8, 1024))

    def tpu_sharded_edges(x_, w_, z_, u_):
        y, v = edge_ops.round_uplink_sharded(z_, mesh=mesh1, n_total=8,
                                             rho_eff=0.125,
                                             interpret=False)
        xn, zn = edge_ops.round_downlink_sharded(x_, w_, z_, y, u_,
                                                 mesh=mesh1, damping=0.5,
                                                 interpret=False)
        return v, xn, zn

    launches = _count_prims(
        jax.make_jaxpr(tpu_sharded_edges)(zt, zt, zt,
                                          jnp.zeros((8,))).jaxpr,
        "pallas_call")
    return ([f"engine,sharded:structure,launches_per_shard={launches}"],
            [dict(kind="sharded_structure", launches_per_shard=launches)])


def _edge_trees():
    key = jax.random.PRNGKey(0)
    tree = {f"l{i}": jax.random.normal(jax.random.fold_in(key, i),
                                       (EDGE_N_AGENTS, w))
            for i, w in enumerate(EDGE_WIDTHS)}
    x = tree
    w = {k: 0.9 * v for k, v in tree.items()}
    z = {k: 1.1 * v for k, v in tree.items()}
    u = jax.random.bernoulli(key, 0.7,
                             (EDGE_N_AGENTS,)).astype(jnp.float32)
    return x, w, z, u


def _round_edge():
    """Per-leaf XLA edge jaxpr ops vs fused pallas launches (module
    docstring).  Launch counts come from the TPU-shaped
    (interpret=False) trace -- abstract eval only, safe on CPU."""
    prox = prox_lib.make_prox("weight_decay", weight=0.1)
    x, w, z, u = _edge_trees()
    m_total = int(sum(EDGE_WIDTHS))
    shape_s = f"N={EDGE_N_AGENTS};m={m_total};leaves={len(EDGE_WIDTHS)}"
    width = -(-m_total // 128) * 128
    zt = jnp.zeros((EDGE_N_AGENTS, width))
    ut = jnp.zeros((EDGE_N_AGENTS,))

    def tpu_edges(x_, w_, z_, u_):
        _, v = edge_ops.round_uplink(z_, prox=prox,
                                     rho_eff=1.0 / EDGE_N_AGENTS,
                                     interpret=False)
        xn, zn = edge_ops.round_downlink(x_, w_, z_, u_, prox=prox,
                                         rho_eff=1.0 / EDGE_N_AGENTS,
                                         damping=0.5, interpret=False)
        return v, xn, zn

    fused_launches = _count_prims(
        jax.make_jaxpr(tpu_edges)(zt, zt, zt, ut).jaxpr, "pallas_call")
    cfg = engine.RoundConfig(n_agents=EDGE_N_AGENTS, rho=1.0, damping=0.5)

    def xla_edges(x_, w_, z_, u_):
        y, v = engine.coordinator_edge(cfg, z_, z_, prox)
        xn, zn = engine.agent_edge(cfg, u_, w_, x_, z_, y, z_, prox)
        return v, xn, zn

    n_ops = len(jax.make_jaxpr(xla_edges)(x, w, z, u).jaxpr.eqns)
    # distinct labels: "launches=" is the TPU-schedule pallas_call count
    # (a 0 here is a regression, never substituted), "ops=" the
    # per-leaf path's jaxpr equation count
    rows = [f"engine,edge:xla,ops={n_ops},{shape_s}",
            f"engine,edge:pallas,launches={fused_launches},{shape_s}"]
    shape = dict(n_agents=EDGE_N_AGENTS, m_total=m_total,
                 n_leaves=len(EDGE_WIDTHS))
    payload = [dict(kind="edge", backend="xla", jaxpr_ops=n_ops, **shape),
               dict(kind="edge", backend="pallas",
                    pallas_launches=fused_launches, **shape)]
    return rows, payload


def run(quick=True):
    rows, payload = [], []
    for part in (_round_structure, _sharded, _round_edge):
        r, p = part()
        rows += r
        payload += p
    return rows, {"cases": payload, "quick": bool(quick)}


if __name__ == "__main__":
    print("\n".join(run()[0]))
