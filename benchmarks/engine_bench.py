"""Unified round-engine benchmark: fused vs unfused local epochs,
compressed vs uncompressed rounds, and the fused round-edge kernels.

Part 1 (rounds): times one jitted Fed-PLT round of a reduced
transformer through ``fed/runtime.py`` (i.e. through
``fed/engine.py``) for:

  * baseline           -- gd local epochs, exact z-exchange
  * pallas_fused       -- fedplt_update fused local step (NOTE: interpret
                          mode on this CPU container, so the fused number
                          is a correctness path, not TPU performance)
  * topk50 / int8      -- compressed z uplink (adds the per-agent
                          compressor to the round's critical path; the
                          quantity bought is uplink bytes, reported as
                          the compression ratio column)
  * pallas_edges       -- the fused round-edge backend end to end
  * packed_xla/pallas  -- the packed-resident state layout (engine
                          layout contract): (x, z, t) stay one
                          (N, M_total) buffer across rounds, so the
                          round pays ZERO pack/unpack traffic on the
                          state path (asserted by the structure rows
                          below and the CI smoke)

Part 1b (round structure): state-path op counts of one round --
concatenate / gather / dynamic_update_slice per (layout x backend) at
engine scale with an elementwise oracle, so the counts measure the
STATE path, not the model's forward/backward.  The committed baseline
asserts the packed rounds contain zero concatenates and that the
packed pallas round's update-slice count collapses to the oracle's
single pack.

Part 2 (round edges): the coordinator edge (prox + reflect; z-update +
participation selects) at ENGINE SCALE -- N >= 32 agents on a ragged
multi-leaf tree -- measured three ways:

  * per-backend edge wall time through ``engine.coordinator_edge`` /
    ``engine.agent_edge`` (the shipped paths; on this CPU container the
    packed path pays pack/unpack concatenation and interpret-emulation
    overhead that a TPU does not, so treat these as correctness-path
    numbers, like the other interpret-mode rows);
  * STRUCTURE: jaxpr ops of the XLA edge vs pallas_call launches of the
    fused edge -- the committed baseline asserts the coordinator edge
    collapses to TWO kernel launches;
  * LAUNCH-GRANULAR speedup: the edge arithmetic executed as one
    jitted launch per op per leaf (the xla backend's own granularity --
    the HBM round-trips + dispatches an unfused schedule pays between
    launches) vs the two fused kernels -- a real measurement of what
    the fusion removes, CPU-measurable because each jitted call is a
    genuine executable with genuine memory round-trips.  A second
    bracket (per-op launches on the already-packed buffer) isolates
    how much of the win is packing vs fusing.

``run`` returns ``(rows, payload)``: CSV rows plus the JSON-able dict
``benchmarks.run --json`` writes (committed baseline:
``BENCH_engine.json``), so future PRs can regress per-case wall times,
launch counts, and the launch-granular speedup.
"""

import time

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.configs.base import InputShape
from repro.core import prox as prox_lib
from repro.data.synthetic import make_batch_for
from repro.fed import engine
from repro.fed.api import CompressionSpec, FedSpec, build_trainer
from repro.kernels.round_edge import ops as edge_ops

# engine-scale round-edge case: agents x ragged transformer-like leaves
EDGE_N_AGENTS = 64
EDGE_WIDTHS = (1024, 256, 256, 64, 512, 512, 64, 16) * 25   # 200 leaves


def _best_ms(fn, args, iters, reps=3):
    out = fn(*args)
    jax.block_until_ready(out)
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / iters * 1e3)
    return best


def _count_prims(jaxpr, name):
    return engine.count_primitives(jaxpr, [name])[name]


def _bench_round(cfg, model, spec, iters):
    trainer = build_trainer(model, spec)
    state = trainer.init(jax.random.PRNGKey(0))
    shape = InputShape("bench", 32, 8, "train")
    batch = make_batch_for(cfg, shape, n_agents=spec.n_agents)
    key = jax.random.PRNGKey(1)
    state, _ = trainer.step(state, batch, key)  # compile + warm-up
    jax.block_until_ready(state.x)
    t0 = time.perf_counter()
    for i in range(iters):
        state, m = trainer.step(state, batch, jax.random.fold_in(key, i))
    jax.block_until_ready(state.x)
    return (time.perf_counter() - t0) / iters * 1e3  # ms


def _rounds(quick):
    iters = 3 if quick else 10
    cfg = get_config("gemma2-2b").reduced()
    from repro.models.model import build_model
    model = build_model(cfg)
    base = dict(n_agents=2, n_epochs=2, gamma=0.1)

    cases = [
        ("baseline", dict(), 1.0),
        ("pallas_fused", dict(use_pallas=True), 1.0),
        # compress backends pinned to "xla": the kernels' whole-row
        # blocks do not fit VMEM at model width (fed/compress.py)
        ("topk50", dict(compression=CompressionSpec(
            "topk", 0.5, backend="xla")), 2.0),
        ("topk25", dict(compression=CompressionSpec(
            "topk", 0.25, backend="xla")), 4.0),
        ("int8", dict(compression=CompressionSpec(
            "int8", backend="xla")), 4.0),
        ("adaptive", dict(compression=CompressionSpec(
            "adaptive_topk", ratio=0.25, energy=0.9,
            backend="xla")), 4.0),
        # heterogeneous groups: half the agents run AGD, half run one
        # cheap GD epoch -- measures the sequential group-dispatch cost
        ("hetero_gd_agd", dict(
            agent_groups="1*agd,1*gd:n_epochs=1"), 1.0),
        # fused round-edge backend end to end (weight decay exercises
        # the in-kernel prox)
        ("pallas_edges", dict(engine_backend="pallas",
                              weight_decay=0.01), 1.0),
        # packed-resident state layout: same rounds with (x, z, t) kept
        # as one (N, M_total) buffer -- packed_pallas is pallas_edges
        # minus every per-edge pack/unpack copy
        ("packed_xla", dict(state_layout="packed"), 1.0),
        ("packed_pallas", dict(state_layout="packed",
                               engine_backend="pallas",
                               weight_decay=0.01), 1.0),
    ]
    rows, payload = [], []
    ms0 = None
    for name, kw, uplink in cases:
        spec = FedSpec(**base, **kw)
        ms = _bench_round(cfg, model, spec, iters)
        if ms0 is None:
            ms0 = ms
        rows.append(f"engine,{name},{ms:.1f},{ms / ms0:.2f}x,"
                    f"uplink/{uplink:.0f}")
        payload.append(dict(kind="round", case=name, ms_per_round=ms,
                            rel_to_baseline=ms / ms0,
                            uplink_ratio=uplink))
    return rows, payload


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


def _async_rounds(quick):
    """Async (bounded-staleness) rounds vs the synchronous round at
    engine scale: N=64 agents on the packed layout with an elementwise
    oracle, staleness bounds 0 / 2 / 8.  The async round adds only
    per-agent select/counter arithmetic on top of the synchronous edges
    (the arrival mask streams through the same downlink path as the
    participation mask), so these rows bound the steady-state cost of
    the staleness machinery itself -- the broker's wall-clock win from
    not blocking on stragglers is a host-side property benchmarks on
    synthetic latencies would only restate."""
    from repro.core.solvers import SolverConfig
    from repro.fed import async_engine
    from repro.fed import compress as compress_lib
    from repro.fed.solvers import make_packed_local_solver

    iters = 5 if quick else 20
    n = EDGE_N_AGENTS
    tree = {f"l{i}": jnp.ones((n, w))
            for i, w in enumerate(EDGE_WIDTHS[:16])}
    meta = compress_lib.packed_meta(tree)
    buf, _ = compress_lib.pack_leaves(tree)

    def fgrad(w, k):
        return jax.tree_util.tree_map(lambda l: 0.1 * l, w)

    cfg0 = engine.RoundConfig(n_agents=n, participation=0.7,
                              damping=0.5, state_layout="packed")
    scfg = SolverConfig(name="gd", n_epochs=2, step_size=0.1)
    solver = make_packed_local_solver(scfg, fgrad, cfg0.rho, 0.1, 1.0,
                                      meta=meta)
    key = jax.random.PRNGKey(0)
    m_total = int(meta.m_total)
    shape_s = f"N={n};m={m_total};leaves={len(tree)}"
    rows, payload = [], []

    sync_f = jax.jit(lambda x, z, t, k: engine.packed_round_step(
        cfg0, meta, x, z, t, k, solver))
    ms0 = _best_ms(sync_f, (buf, buf, buf, key), iters)
    rows.append(f"engine,async:sync_ref,{ms0:.2f},1.00x,{shape_s}")
    payload.append(dict(kind="async_round", case="sync_ref",
                        max_staleness=None, ms_per_round=ms0,
                        rel_to_sync=1.0, n_agents=n, m_total=m_total))

    staleness0 = async_engine.init_staleness(n)
    y_tag0 = jnp.zeros_like(buf)
    for K in (0, 2, 8):
        cfg = engine.RoundConfig(
            n_agents=n, participation=0.7, damping=0.5,
            state_layout="packed",
            staleness=engine.StalenessConfig(mode="stale",
                                             max_staleness=K))
        f = jax.jit(lambda x, z, t, yt, st, k, cfg=cfg:
                    async_engine.packed_async_round_step(
                        cfg, meta, x, z, t, yt, st, k, solver))
        ms = _best_ms(f, (buf, buf, buf, y_tag0, staleness0, key),
                      iters)
        rows.append(f"engine,async:stale_K{K},{ms:.2f},"
                    f"{ms / ms0:.2f}x,{shape_s}")
        payload.append(dict(kind="async_round", case=f"stale_K{K}",
                            max_staleness=K, ms_per_round=ms,
                            rel_to_sync=ms / ms0, n_agents=n,
                            m_total=m_total))
    return rows, payload


def _sharded(quick):
    """Weak scaling of the mesh-sharded packed round (ROADMAP item 2).

    One engine-scale packed round (elementwise oracle, pallas edges)
    per (devices, N) point at a fixed 512 agents PER SHARD: N=512 on 1
    device up to N=4096 on 8, plus the N=64 single-device baseline.
    Points needing more devices than are visible are skipped (the
    committed rows come from an
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` run).  On
    this single-core CPU container the host devices time-share one
    core, so ms/round GROWS with N here -- the weak-scaling flatness
    claim is about real multi-chip meshes; these rows pin the
    correctness path and the per-shard launch structure (exactly TWO
    fused edge launches per shard, asserted by the CI sharded smoke
    from the ``launches_per_shard`` field)."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.core.solvers import SolverConfig
    from repro.fed import compress as compress_lib
    from repro.fed.solvers import make_packed_local_solver

    iters = 2 if quick else 8
    widths = EDGE_WIDTHS[:16]

    def fgrad(w, k):
        return jax.tree_util.tree_map(lambda l: 0.1 * l, w)

    scfg = SolverConfig(name="gd", n_epochs=2, step_size=0.1)
    n_dev = len(jax.devices())
    rows, payload = [], []

    # per-shard launch structure: TPU-shaped (interpret=False) trace of
    # the sharded edges -- the partial-sum uplink + presummed downlink
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
    rows.append(f"engine,sharded:structure,launches_per_shard={launches}")
    payload.append(dict(kind="sharded_structure",
                        launches_per_shard=launches))

    cases = [(64, 1)] + [(512 * d, d) for d in (1, 2, 4, 8)]
    ms0 = None
    for n, d in cases:
        name = f"n{n}_d{d}"
        if d > n_dev:
            rows.append(f"engine,sharded:{name},skipped,needs {d} devices")
            continue
        mesh = Mesh(np.asarray(jax.devices()[:d]).reshape(d, 1),
                    ("agent", "model"))
        tree = {f"l{i}": jnp.ones((n, w)) for i, w in enumerate(widths)}
        meta = compress_lib.packed_meta(tree)
        buf = jax.device_put(
            compress_lib.pack_leaves(tree)[0],
            NamedSharding(mesh, P("agent", None)))
        del tree
        solver = make_packed_local_solver(scfg, fgrad, 1.0, 0.1, 1.0,
                                          meta=meta)
        cfg = engine.RoundConfig(n_agents=n, participation=0.9,
                                 damping=0.5, state_layout="packed",
                                 engine_backend="pallas", agent_shards=d)
        f = jax.jit(lambda x, z, t, k, cfg=cfg, meta=meta,
                    solver=solver, mesh=mesh:
                    engine.packed_round_step(cfg, meta, x, z, t, k,
                                             solver, mesh=mesh))
        ms = _best_ms(f, (buf, buf, buf, jax.random.PRNGKey(0)), iters,
                      reps=2)
        if ms0 is None:
            ms0 = ms
        rows.append(f"engine,sharded:{name},{ms:.2f},{ms / ms0:.2f}x,"
                    f"N={n};devices={d};m={int(meta.m_total)}")
        payload.append(dict(kind="sharded_round", case=name, n_agents=n,
                            devices=d, ms_per_round=ms,
                            rel_to_first=ms / ms0,
                            per_shard_rows=n // d,
                            launches_per_shard=launches,
                            m_total=int(meta.m_total)))
    return rows, payload


def _robust_agg(quick):
    """Robust-aggregation uplink statistics (byzantine-robust PR).

    One jitted aggregate over an (N, width) z stack per (stat, backend,
    N) point: the plain survivor mean (the historical reduce, the
    baseline row), trimmed_mean(f=2) and coord_median through the XLA
    registry path and through the robust_agg sort kernel (interpret
    mode on this CPU container -- a correctness path, not TPU
    performance, like every other interpret-mode row here).  The
    quantity bought is the robustness statistic itself; the cost is the
    per-column sort replacing the single row reduce, so the ratio
    column reports each stat against the mean at the same N."""
    from repro.fed import robust
    from repro.kernels.robust_agg import ops as robust_ops

    iters = 2 if quick else 8
    width = 2048 if quick else 8192
    rows, payload = [], []
    key = jax.random.PRNGKey(0)

    def registry(name, param):
        return jax.jit(lambda v: robust.aggregate_rows(
            v, None, name=name, param=param, backend="xla"))

    for n in (64, 256, 1024):
        x = jax.random.normal(jax.random.fold_in(key, n), (n, width))
        cases = [
            ("mean", "xla", registry("mean", 0.0)),
            ("trimmed_mean_f2", "xla", registry("trimmed_mean", 2.0)),
            ("trimmed_mean_f2", "pallas",
             jax.jit(lambda v: robust_ops.robust_aggregate(
                 v, stat="trimmed_mean", trim=2))),
            ("coord_median", "xla", registry("coord_median", 0.0)),
            ("coord_median", "pallas",
             jax.jit(lambda v: robust_ops.robust_aggregate(
                 v, stat="coord_median"))),
        ]
        ms_mean = None
        for stat, backend, f in cases:
            ms = _best_ms(f, (x,), iters, reps=2)
            if ms_mean is None:
                ms_mean = ms
            name = f"{stat}_{backend}_n{n}"
            rows.append(f"engine,robust_agg:{name},{ms:.3f},"
                        f"{ms / ms_mean:.2f}x,N={n};m={width}")
            payload.append(dict(kind="robust_agg", case=name, stat=stat,
                                backend=backend, n_agents=n,
                                width=width, ms_per_agg=ms,
                                rel_to_mean=ms / ms_mean))
    return rows, payload


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


def _edges(backend, prox):
    cfg = engine.RoundConfig(n_agents=EDGE_N_AGENTS, rho=1.0,
                             damping=0.5, engine_backend=backend)

    def f(x, w, z, u):
        y, v = engine.coordinator_edge(cfg, z, z, prox)
        xn, zn = engine.agent_edge(cfg, u, w, x, z, y, z, prox)
        return v, xn, zn

    return f


def _round_edge(quick):
    iters = 5 if quick else 20
    prox = prox_lib.make_prox("weight_decay", weight=0.1)
    x, w, z, u = _edge_trees()
    m_total = int(sum(EDGE_WIDTHS))
    shape_s = f"N={EDGE_N_AGENTS};m={m_total};leaves={len(EDGE_WIDTHS)}"
    rows, payload = [], []

    # -- per-backend edge wall time + structure -------------------------
    # launch counts come from the TPU-shaped (interpret=False) trace --
    # abstract eval only, safe on CPU; the CPU default executes the same
    # kernel bodies directly when the grid is one program
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

    ms = {}
    for backend in ("xla", "pallas"):
        f = _edges(backend, prox)
        ms[backend] = _best_ms(jax.jit(f), (x, w, z, u), iters)
        n_ops = len(jax.make_jaxpr(f)(x, w, z, u).jaxpr.eqns)
        launches = fused_launches if backend == "pallas" else 0
        # distinct labels: "launches=" is the TPU-schedule pallas_call
        # count (a 0 here is a regression, never substituted), "ops="
        # the per-leaf path's jaxpr equation count
        detail = (f"launches={launches}" if backend == "pallas"
                  else f"ops={n_ops}")
        rows.append(f"engine,edge:{backend},{ms[backend]:.2f},"
                    f"{detail},{shape_s}")
        payload.append(dict(
            kind="edge", backend=backend, ms_per_edge_pair=ms[backend],
            pallas_launches=launches, jaxpr_ops=n_ops,
            n_agents=EDGE_N_AGENTS, m_total=m_total,
            n_leaves=len(EDGE_WIDTHS)))

    # -- packed-resident edges: the same fused kernels with the state
    # ALREADY resident in one (N, width) buffer (the packed layout's
    # round-to-round steady state) -- what the tree-layout pallas row
    # pays on top of this is pure pack/unpack traffic
    from repro.fed import compress as compress_lib

    meta = compress_lib.packed_meta(z)
    xb = compress_lib.pack_leaves(x)[0]
    wb = compress_lib.pack_leaves(w)[0]
    zb = compress_lib.pack_leaves(z)[0]
    pcfg = engine.RoundConfig(n_agents=EDGE_N_AGENTS, rho=1.0,
                              damping=0.5, engine_backend="pallas",
                              state_layout="packed")

    def packed_edges(x_, w_, z_, u_):
        y, v = engine.coordinator_edge_packed(pcfg, z_, z_, meta, prox)
        xn, zn = engine.agent_edge_packed(pcfg, u_, w_, x_, z_, y, z_,
                                          prox)
        return v, xn, zn

    ms_packed_res = _best_ms(jax.jit(packed_edges), (xb, wb, zb, u),
                             iters)
    rows.append(f"engine,edge:packed_pallas,{ms_packed_res:.2f},"
                f"launches={fused_launches},{shape_s}")
    payload.append(dict(
        kind="edge", backend="packed_pallas",
        ms_per_edge_pair=ms_packed_res,
        pallas_launches=fused_launches, jaxpr_ops=None,
        n_agents=EDGE_N_AGENTS, m_total=m_total,
        n_leaves=len(EDGE_WIDTHS)))

    # -- launch-granular: the unfused schedule (one jitted executable
    # per op = one launch + HBM round-trip each) vs the two fused
    # kernels.  Two unfused brackets: per-leaf per-op launches (the xla
    # backend's own granularity -- ~7 launches x n_leaves) and per-op
    # launches on the already-packed buffer (the launch floor an
    # unfused schedule could reach with packing but no fusion).
    key = jax.random.PRNGKey(1)
    zb = jax.random.normal(key, (EDGE_N_AGENTS, width))
    xb, wb = 0.9 * zb, 1.1 * zb
    rho_eff, damping = 1.0 / EDGE_N_AGENTS, 0.5

    mean_f = jax.jit(lambda z: jnp.mean(z, axis=0))
    prox_f = jax.jit(lambda zb_: prox(zb_, rho_eff))
    refl_f = jax.jit(lambda y, z: 2.0 * y[None] - z)
    zupd_f = jax.jit(lambda z, w_, y: z + 2.0 * damping * (w_ - y[None]))
    sel_f = jax.jit(lambda u_, a, b: jnp.where(
        (u_ != 0).reshape(-1, 1), a, b))

    def unfused_ops(x_, w_, z_, u_):
        zbar = mean_f(z_)
        y = prox_f(zbar)
        v = refl_f(y, z_)
        zu = zupd_f(z_, w_, y)
        return v, sel_f(u_, w_, x_), sel_f(u_, zu, z_)

    def unfused_per_leaf(x_, w_, z_, u_):
        return [unfused_ops(x_[k], w_[k], z_[k], u_) for k in z_]

    def fused(x_, w_, z_, u_):
        _, v = edge_ops.round_uplink(z_, prox=prox, rho_eff=rho_eff)
        xn, zn = edge_ops.round_downlink(x_, w_, z_, u_, prox=prox,
                                         rho_eff=rho_eff,
                                         damping=damping)
        return v, xn, zn

    ms_leaf = _best_ms(unfused_per_leaf, (x, w, z, u), iters)
    ms_packed = _best_ms(unfused_ops, (xb, wb, zb, u), iters)
    ms_fused = _best_ms(fused, (xb, wb, zb, u), iters)
    speedup = ms_leaf / ms_fused
    rows.append(f"engine,edge:launch_granular,{ms_fused:.2f},"
                f"{speedup:.2f}x,{shape_s}")
    payload.append(dict(
        kind="edge_launch",
        ms_unfused_per_leaf_launches=ms_leaf,
        ms_unfused_packed_launches=ms_packed,
        ms_fused_kernels=ms_fused, speedup=speedup,
        unfused_launches=7 * len(EDGE_WIDTHS), fused_launches=2,
        n_agents=EDGE_N_AGENTS, m_total=m_total,
        n_leaves=len(EDGE_WIDTHS)))
    return rows, payload


def run(quick=True):
    round_rows, round_payload = _rounds(quick)
    struct_rows, struct_payload = _round_structure()
    async_rows, async_payload = _async_rounds(quick)
    sharded_rows, sharded_payload = _sharded(quick)
    robust_rows, robust_payload = _robust_agg(quick)
    edge_rows, edge_payload = _round_edge(quick)
    payload = {"cases": (round_payload + struct_payload + async_payload
                         + sharded_payload + robust_payload
                         + edge_payload),
               "quick": bool(quick)}
    return (round_rows + struct_rows + async_rows + sharded_rows
            + robust_rows + edge_rows, payload)


if __name__ == "__main__":
    print("\n".join(run()[0]))
