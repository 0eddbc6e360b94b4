"""Registry- and backend-driven uplink-compression sweep.

Two parts (this harness absorbed the PR-1-era ``compression_bench``):

* **Convergence**: every compressor registered in
  :mod:`repro.fed.compress` runs the paper's dim-20 logreg problem
  through the :class:`repro.fed.api.FedSpec` front door --
  rounds-to-threshold, final criterion, measured keep fraction, and the
  relative uplink bytes the compressor buys (keep * value bits vs 32-bit
  exact exchange).

* **Perf**: ``compress_increment`` wall time, backend x compressor x
  shape -- per-leaf XLA registry path vs the packed
  :mod:`repro.kernels.compress` Pallas path (interpret mode on this CPU
  container), including the engine-scale ragged pytree (the reduced
  gemma2-2b leaf layout ``engine_bench`` rounds flatten).  The
  ``speedup`` column is XLA time / Pallas time for the same case.

``run`` returns ``(rows, payload)``: CSV rows plus the JSON-able dict
``benchmarks.run --json`` writes (committed baseline:
``BENCH_compress.json``), so future PRs can regress against per-case
wall times and speedups.

Rows::

  compress_bench,conv:<name>,<rounds-to-threshold>,<final criterion>,
      keep=..;uplink=..;ms=..
  compress_bench,perf:<case>:<name>:<backend>,<ms/call>,<speedup vs
      xla>,N=..;m=..;leaves=..
"""

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.metrics import hitting_round
from repro.core.problem import make_logreg_problem
from repro.fed.api import CompressionSpec, FedSpec, build_trainer
from repro.fed.compress import (PALLAS_COMPRESSORS, available_compressors,
                                compress_increment, get_compressor)
from repro.fed.engine import RoundConfig
from repro.kernels.compress.ops import fits_vmem

# bits per transmitted value on the wire (topk adds ~log2(m) index bits,
# folded into the measured keep fraction's 32-bit values below)
_VALUE_BITS = {"int8": 8}

# leaf widths of the reduced gemma2-2b parameter tree -- the exact
# ragged pytree one engine_bench round compresses (engine-scale case)
_GEMMA2R_LEAVES = (131072, 256, 65536, 65536, 65536, 65536, 256, 256,
                   262144, 131072, 65536, 65536, 65536, 65536, 256, 256,
                   262144, 131072)

# perf sweep: (case name, n_agents, per-leaf widths)
_PERF_CASES = (
    ("dense100x256", 100, (256,)),
    ("wide8x65536", 8, (65536,)),
    ("engine_gemma2r", 2, _GEMMA2R_LEAVES),
)


def _convergence(quick):
    rows, payload = [], []
    prob = make_logreg_problem(n_agents=100, q=250, dim=20, seed=0)
    rounds = 600 if quick else 1000
    # measured keep fraction on a fixed probe increment: the sparsity an
    # actual uplink would exploit (int8 keeps everything but sends 8
    # bits; the keep column tracks sparsity only)
    probe = jax.random.normal(jax.random.PRNGKey(1),
                              (prob.n_agents, 256))
    k_exact = None
    names = available_compressors()
    # the exact exchange runs first: it is the rounds-to-threshold
    # baseline the rel_uplink column normalizes against
    names = ["none"] + [n for n in names if n != "none"]
    for name in names:
        comp = CompressionSpec(name=name, ratio=0.25, energy=0.9)
        trainer = build_trainer(
            prob, FedSpec(rho=1.0, n_epochs=5, compression=comp))
        t0 = time.perf_counter()
        _, crit = trainer.run(jax.random.PRNGKey(0), rounds)
        crit = np.asarray(crit)          # blocks on the scan
        ms = (time.perf_counter() - t0) / rounds * 1e3
        k = hitting_round(crit)
        rc = trainer.spec.round_config()
        kept = float(jnp.mean(get_compressor(name)(probe, rc) != 0.0))
        if name == "none":
            k_exact = k
        bits = _VALUE_BITS.get(name, 32.0 * kept)
        uplink = (k * bits / (k_exact * 32.0)
                  if k is not None and k_exact else None)
        up_s = f"{uplink:.2f}" if uplink is not None else "-"
        rows.append(f"compress_bench,conv:{name},{k if k else '-'},"
                    f"{crit[-1]:.3e},keep={kept:.2f};"
                    f"uplink={up_s};ms={ms:.2f}")
        payload.append(dict(kind="convergence", compressor=name,
                            rounds_to_threshold=k,
                            final_criterion=float(crit[-1]),
                            keep_fraction=kept, rel_uplink=uplink,
                            ms_per_round=ms))
    return rows, payload


def _time_compress(tree, cfg, iters):
    f = jax.jit(lambda t: compress_increment(t, cfg))
    out = f(tree)
    jax.block_until_ready(out)           # compile + warm-up
    t0 = time.perf_counter()
    for _ in range(iters):
        out = f(tree)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e3


def _perf(quick):
    rows, payload = [], []
    iters = 3 if quick else 10
    key = jax.random.PRNGKey(0)
    for case, n_agents, widths in _PERF_CASES:
        tree = {f"l{i}": jax.random.normal(jax.random.fold_in(key, i),
                                           (n_agents, w))
                for i, w in enumerate(widths)}
        m_total = int(sum(widths))
        for name in sorted(PALLAS_COMPRESSORS):
            if not fits_vmem(name, m_total):
                continue       # no kernel at this width: nothing to compare
            ms = {}
            for backend in ("xla", "pallas"):
                cfg = RoundConfig(
                    n_agents=n_agents, compression=name,
                    compress_ratio=0.25, compress_energy=0.9,
                    compress_backend=backend)
                ms[backend] = _time_compress(tree, cfg, iters)
            speedup = ms["xla"] / ms["pallas"]
            for backend in ("xla", "pallas"):
                rel = speedup if backend == "pallas" else 1.0
                rows.append(
                    f"compress_bench,perf:{case}:{name}:{backend},"
                    f"{ms[backend]:.2f},{rel:.2f}x,"
                    f"N={n_agents};m={m_total};leaves={len(widths)}")
                payload.append(dict(
                    kind="perf", case=case, compressor=name,
                    backend=backend, n_agents=n_agents,
                    m_total=m_total, n_leaves=len(widths),
                    ms_per_call=ms[backend], speedup_vs_xla=rel))
    return rows, payload


def run(quick=True):
    conv_rows, conv_payload = _convergence(quick)
    perf_rows, perf_payload = _perf(quick)
    payload = {"cases": conv_payload + perf_payload,
               "quick": bool(quick)}
    return conv_rows + perf_rows, payload


if __name__ == "__main__":
    print("\n".join(run()[0]))
