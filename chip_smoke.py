#!/usr/bin/env python3
"""Start-up check of the Fed-PLT system on a TPU, through the front door.

Run from the root of the repository:

    python3 chip_smoke.py                # phases a-d on one chip
    python3 chip_smoke.py --four-chips   # only the sharded rounds, 2x2 host

Phases (one process, one chip):

  a. the paper's dense path: ``configs/fedplt_logreg.py`` ``CONFIG``
     through ``build_trainer``; the criterion must fall.
  b. the model path: phi4-mini-3.8b at its published widths, cut in depth
     and vocabulary (printed below), ``FedSpec`` -> ``build_trainer`` ->
     ``fed/engine.py`` with xla edges; the loss must be finite and fall.
  c. the same round on the packed state layout with the Pallas kernels
     (fused round edges, fused local update); the compiled round must
     contain ``tpu_custom_call`` and its round-1 consensus must agree
     with phase b's.
  d. one round compressed with ``int8`` under ``backend="auto"`` (at
     this width auto takes the XLA compressor: a kernel row would not
     fit VMEM) and one with ``aggregator="trimmed_mean"``, f=0, on the
     pallas edges (f=0 is the mean, which the engine keeps as the mean
     path); both must agree with phase b's round-1 consensus.  Then the
     compress and robust-aggregation kernels, which no model round here
     reaches, are checked against their ``ref.py`` oracles on the chip.

``--four-chips`` runs N=4 agents sharded over 4 chips (packed layout,
pallas edges, ``mean`` then ``trimmed_mean`` f=1, two rounds each) and
compares each with the same spec on a 1-device mesh.  Both hold four agents' state,
so the model is cut further (printed).

Each phase prints its compile seconds, every round's time (to
``block_until_ready``), the loss, and the device's peak bytes in use so
far.  Any failure raises and exits non-zero.  The last line of standard
output is one JSON object naming the device; it is printed only when
every phase passed.  The script refuses to run unless JAX's first
device is a TPU.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

ARCH = "phi4-mini-3.8b"
N_LAYERS = 2
# a quarter of the published 200,064 rows: one chip's share when the
# vocabulary is split over the 4 chips of a v5e 2x2 host.  The whole
# vocabulary does not fit one chip beside two agent replicas of the state
VOCAB_SHARE = 4
SEQ_LEN = 512
SEQS_PER_AGENT = 4
N_AGENTS = 2
N_EPOCHS = 2
GAMMA = 0.5
MODEL_ROUNDS = 3
DENSE_ROUNDS = 300
SEED = 0

# Phases c and d against phase b: the state is bf16, and the engine's
# parity contract promises agreement across backends only to rounding
# (the fused update and the fused edges round the same f32 arithmetic
# in their own order).  After one round of N_EPOCHS steps a handful of
# bf16 ulps is expected; allow 4 ulps at the top of each leaf's range:
# |a - b| <= 2**-5 * max|b|.
CONSENSUS_RTOL = 2.0 ** -5


def _fail(msg: str) -> None:
    print(f"chip_smoke: {msg}", file=sys.stderr)
    raise SystemExit(1)


def _peak_bytes(device) -> int:
    return int(device.memory_stats()["peak_bytes_in_use"])


def model_config():
    from repro.configs import get_config

    full = get_config(ARCH)
    cfg = dataclasses.replace(full, n_layers=N_LAYERS,
                              vocab=full.vocab // VOCAB_SHARE)
    print(f"model: {ARCH} at published widths (d_model {cfg.d_model}, "
          f"{cfg.n_heads} query / {cfg.n_kv_heads} KV heads of "
          f"{cfg.resolved_head_dim}, d_ff {cfg.d_ff}, {cfg.activation}, "
          f"{cfg.dtype})")
    print(f"  cut: depth {full.n_layers} -> {cfg.n_layers} layers "
          f"(a whole round's compile and memory stay within one chip)")
    print(f"  cut: vocabulary {full.vocab} -> {cfg.vocab} rows (this "
          f"chip's share of a {VOCAB_SHARE}-way vocabulary split; the "
          f"full table with two agent replicas exceeds 16 GB)")
    return cfg


def model_batches(cfg, n_agents, n_rounds, key):
    from repro.configs.base import InputShape
    from repro.data.synthetic import make_batch_for

    import jax

    shape = InputShape("smoke", SEQ_LEN, SEQS_PER_AGENT * n_agents,
                       "train")
    out = [make_batch_for(cfg, shape, jax.random.fold_in(key, i),
                          n_agents=n_agents) for i in range(n_rounds)]
    return jax.block_until_ready(out)


def run_model_phase(name, model, spec, batches, key, device,
                    expect_kernels=False):
    """Compile ``spec``'s round once, run ``len(batches)`` rounds.

    Returns ``(losses, round1_consensus)``.  The round is the
    trainer's own jitted step (``build_trainer``), compiled ahead of time
    so that compile time and round times are reported apart."""
    import jax
    import numpy as np

    from repro.fed.api import build_trainer

    trainer = build_trainer(model, spec)
    state = trainer.init(key)
    t0 = time.perf_counter()
    compiled = trainer._step.lower(state, batches[0], key, None, None,
                                   None).compile()
    compile_s = time.perf_counter() - t0
    kernels = "tpu_custom_call" in compiled.as_text()
    print(f"[{name}] compile {compile_s:.2f} s, tpu_custom_call in the "
          f"compiled round: {kernels}")
    if expect_kernels and not kernels:
        raise AssertionError(f"[{name}] the compiled round holds no "
                             f"Pallas kernel (tpu_custom_call)")
    losses, first = [], None
    for i, batch in enumerate(batches):
        t0 = time.perf_counter()
        state, metrics = compiled(state, batch, jax.random.fold_in(key, i),
                                  None, None, None)
        jax.block_until_ready((state, metrics))
        dt = time.perf_counter() - t0
        loss = float(metrics["loss"])
        losses.append(loss)
        print(f"[{name}] round {i + 1}: {dt:.3f} s, loss {loss:.5f}, "
              f"peak {_peak_bytes(device) / 2**30:.2f} GiB")
        if i == 0:     # kept on the host: the device needs its memory
            first = jax.device_get(trainer.consensus(state))
    if not np.all(np.isfinite(losses)):
        raise AssertionError(f"[{name}] non-finite loss: {losses}")
    return losses, first


def check_falls(name, losses):
    if not losses[-1] < losses[0]:
        raise AssertionError(f"[{name}] loss did not fall: {losses}")


def compare_consensus(name, got, ref, rtol=CONSENSUS_RTOL):
    """Every leaf within ``rtol`` of its own largest magnitude."""
    import jax
    import numpy as np

    worst = 0.0
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree_util.tree_leaves(ref)):
        a = np.asarray(a, np.float32)
        b = np.asarray(b, np.float32)
        scale = max(float(np.max(np.abs(b))), 1e-30)
        rel = float(np.max(np.abs(a - b))) / scale
        worst = max(worst, rel)
        if not rel <= rtol:
            raise AssertionError(
                f"[{name}] consensus leaf {jax.tree_util.keystr(path)} "
                f"differs by {rel:.3e} of its scale (limit {rtol:.3e})")
    print(f"[{name}] round-1 consensus agrees: worst leaf difference "
          f"{worst:.3e} of its scale (limit {rtol:.3e})")


def phase_dense(device):
    import jax
    import numpy as np

    from repro.configs.fedplt_logreg import CONFIG
    from repro.core.problem import make_logreg_problem
    from repro.fed.api import FedSpec, build_trainer

    prob = make_logreg_problem(n_agents=CONFIG.n_agents, q=CONFIG.q,
                               dim=CONFIG.dim, eps=CONFIG.eps,
                               nonconvex=CONFIG.nonconvex,
                               seed=CONFIG.seed)
    trainer = build_trainer(prob, FedSpec(rho=CONFIG.rho,
                                          n_epochs=CONFIG.n_epochs))
    key = jax.random.PRNGKey(CONFIG.seed)
    times = []
    for _ in range(2):      # the first run compiles, the second does not
        t0 = time.perf_counter()
        _, crit = trainer.run(key, DENSE_ROUNDS)
        crit = np.asarray(jax.block_until_ready(crit))
        times.append(time.perf_counter() - t0)
    print(f"[a dense] N={CONFIG.n_agents} n={CONFIG.dim} q={CONFIG.q}, "
          f"{DENSE_ROUNDS} rounds: first run {times[0]:.2f} s (compiles), "
          f"second {times[1]:.3f} s ({times[1] / DENSE_ROUNDS * 1e3:.3f} "
          f"ms/round); compile ~{times[0] - times[1]:.2f} s")
    print(f"[a dense] criterion {crit[0]:.6e} -> {crit[-1]:.6e}, "
          f"peak {_peak_bytes(device) / 2**30:.2f} GiB")
    if not (np.all(np.isfinite(crit)) and crit[-1] < 1e-3 * crit[0]):
        raise AssertionError(f"[a dense] criterion did not fall three "
                             f"decades: {crit[0]} -> {crit[-1]}")


def phases_model(device):
    import jax

    from repro.fed.api import CompressionSpec, FedSpec
    from repro.models.model import build_model

    cfg = model_config()
    model = build_model(cfg)
    key = jax.random.PRNGKey(SEED)
    batches = model_batches(cfg, N_AGENTS, MODEL_ROUNDS, key)
    base = FedSpec(n_agents=N_AGENTS, gamma=GAMMA, n_epochs=N_EPOCHS)

    losses, ref = run_model_phase("b xla", model, base, batches, key,
                                  device)
    check_falls("b xla", losses)

    # gd with a static step keeps the fused local update (a traced step
    # or agd would drop it); the model's leaves share one dtype, so the
    # packed layout applies and neither fused edge falls back
    kern = dataclasses.replace(base, state_layout="packed",
                               engine_backend="pallas", use_pallas=True)
    losses, got = run_model_phase("c packed pallas", model, kern, batches,
                                  key, device, expect_kernels=True)
    check_falls("c packed pallas", losses)
    compare_consensus("c packed pallas", got, ref)
    del got

    int8 = dataclasses.replace(base, compression=CompressionSpec("int8"))
    _, got = run_model_phase("d int8 auto", model, int8, batches[:1], key,
                             device)
    compare_consensus("d int8 auto", got, ref)
    del got

    trim = dataclasses.replace(base, engine_backend="pallas",
                               aggregator="trimmed_mean",
                               aggregator_param=0.0)
    _, got = run_model_phase("d trimmed_mean f=0", model, trim,
                             batches[:1], key, device, expect_kernels=True)
    compare_consensus("d trimmed_mean f=0", got, ref)
    del got, ref
    phase_kernels()


def phase_kernels():
    """The compress and robust-aggregation kernels against their
    oracles, run on the chip (``interpret`` resolves to Mosaic here)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.compress import ops as compress_ops
    from repro.kernels.compress import ref as compress_ref
    from repro.kernels.robust_agg import ops as robust_ops
    from repro.kernels.robust_agg import ref as robust_ref

    x = jax.random.normal(jax.random.PRNGKey(SEED), (8, 4096), jnp.float32)
    x = x.at[0].set(1.0).at[1, ::3].set(-2.5)      # magnitude ties
    segments = ((0, 1000), (1000, 4096))
    checks = [
        ("segment_ranks",
         lambda: compress_ops.segment_ranks(x, segments=segments),
         lambda: compress_ref.segment_ranks_ref(x, segments), 0.0),
        ("rank_select topk",
         lambda: compress_ops.rank_select(x, segments=segments, ratio=0.1),
         lambda: compress_ref.rank_select_ref(x, segments, ratio=0.1), 0.0),
        ("robust trimmed_mean f=1",
         lambda: robust_ops.robust_aggregate(x, stat="trimmed_mean",
                                             trim=1),
         lambda: robust_ref.robust_aggregate_ref(x, stat="trimmed_mean",
                                                 trim=1), 0.0),
        # one quantum: the kernel and XLA may round x / scale apart
        ("int8", lambda: compress_ops.int8_quantize(x, segments=segments),
         lambda: jax.jit(compress_ref.int8_ref, static_argnums=1)(
             x, segments), float(jnp.max(jnp.abs(x))) / 127),
    ]
    for name, kernel, oracle, atol in checks:
        t0 = time.perf_counter()
        got = np.asarray(jax.block_until_ready(kernel()))
        dt = time.perf_counter() - t0
        want = np.asarray(oracle())
        err = float(np.max(np.abs(got.astype(np.float64)
                                  - want.astype(np.float64))))
        print(f"[d kernel {name}] (8, 4096): {dt:.2f} s with compile, "
              f"max difference from ref.py {err:.3e} (limit {atol:.3e})")
        if not err <= atol:
            raise AssertionError(f"[d kernel {name}] differs from its "
                                 f"oracle by {err}")


def phase_four_chips(device):
    import jax

    from repro.fed.api import FedSpec
    from repro.models.model import build_model

    full_cfg = model_config()
    # the 1-device comparison holds all four agents' state on one chip:
    # at 1 layer and a quarter of the vocabulary its round needs ~21 GiB
    # (compiled for v5e), so one layer and a sixteenth of the rows
    cfg = dataclasses.replace(full_cfg, n_layers=1,
                              vocab=full_cfg.vocab // 4)
    print(f"  cut: for four agents, depth -> {cfg.n_layers} layer and "
          f"vocabulary -> {cfg.vocab} rows (the 1-device mesh holds all "
          f"four agents' state on one 16 GB chip)")
    model = build_model(cfg)
    key = jax.random.PRNGKey(SEED)
    batches = model_batches(cfg, 4, 2, key)
    for agg, f in (("mean", 0.0), ("trimmed_mean", 1.0)):
        spec = FedSpec(n_agents=4, gamma=GAMMA, n_epochs=N_EPOCHS,
                       state_layout="packed", engine_backend="pallas",
                       aggregator=agg, aggregator_param=f)
        name = f"4 chips {agg} f={int(f)}"
        _, ref = run_model_phase(
            f"{name}, 1-device mesh", model,
            dataclasses.replace(spec, mesh_shape="1x1"), batches, key,
            device, expect_kernels=True)
        _, got = run_model_phase(
            f"{name}, agent_shards=4", model,
            dataclasses.replace(spec, agent_shards=4), batches, key,
            device, expect_kernels=True)
        compare_consensus(name, got, ref)
        del ref, got


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded rounds on a 4-chip host")
    args = ap.parse_args(argv)
    if not (SRC / "repro").is_dir():
        _fail(f"no repro package under {SRC}: run this script from a "
              f"checkout of the repository")
    sys.path.insert(0, str(SRC))

    from repro.launch.cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}")

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        _fail(f"needs a TPU, but JAX's first device is platform "
              f"{dev.platform!r} ({dev.device_kind}); no result")
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        _fail(f"needs {want} TPU devices, found {len(devices)}")
    from repro import kernels

    if not kernels.ON_TPU:
        _fail("repro.kernels.ON_TPU is false on a TPU: the kernels would "
              "run in interpret mode")
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}, "
          f"jax {jax.__version__}")

    t0 = time.perf_counter()
    if args.four_chips:
        phase_four_chips(dev)
    else:
        phase_dense(dev)
        phases_model(dev)
    print(f"all phases passed in {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
