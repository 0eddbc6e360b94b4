"""Registry- and backend-driven uplink-compression sweep.

Two parts (this harness absorbed the PR-1-era ``compression_bench``):

* **Convergence**: every compressor registered in
  :mod:`repro.fed.compress` runs the paper's dim-20 logreg problem
  through the :class:`repro.fed.api.FedSpec` front door --
  rounds-to-threshold, final criterion, measured keep fraction, and the
  relative uplink bytes the compressor buys (keep * value bits vs 32-bit
  exact exchange).

* **Kernels**: for each compressor with a fused
  :mod:`repro.kernels.compress` kernel and each shape (including the
  engine-scale ragged pytree of the reduced gemma2-2b leaf layout),
  whether the kernel's whole-row block fits (``fits_vmem``, the rule of
  the ``auto`` backend) and, where it does, whether its output is
  bit-identical to the per-leaf XLA registry path.

No wall time: device times come from the chip benchmark under
``bench/``; CPU and interpret-mode times are not evidence of speed.
``run`` returns ``(rows, payload)``: CSV rows plus the JSON-able dict
``benchmarks.run --json`` writes (committed baseline:
``BENCH_compress.json``).

Rows::

  compress_bench,conv:<name>,<rounds-to-threshold>,<final criterion>,
      keep=..;uplink=..
  compress_bench,kernel:<case>:<name>,kernel=<0|1>,bitwise=<0|1|->,
      N=..;m=..;leaves=..
"""

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

# kernel sweep: (case name, n_agents, per-leaf widths)
_KERNEL_CASES = (
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
        _, crit = trainer.run(jax.random.PRNGKey(0), rounds)
        crit = np.asarray(crit)
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
                    f"uplink={up_s}")
        payload.append(dict(kind="convergence", compressor=name,
                            rounds_to_threshold=k,
                            final_criterion=float(crit[-1]),
                            keep_fraction=kept, rel_uplink=uplink))
    return rows, payload


def _kernels():
    rows, payload = [], []
    key = jax.random.PRNGKey(0)
    for case, n_agents, widths in _KERNEL_CASES:
        tree = {f"l{i}": jax.random.normal(jax.random.fold_in(key, i),
                                           (n_agents, w))
                for i, w in enumerate(widths)}
        m_total = int(sum(widths))
        for name in sorted(PALLAS_COMPRESSORS):
            kernel = fits_vmem(name, m_total)
            bitwise = None
            if kernel:
                out = []
                for backend in ("xla", "pallas"):
                    cfg = RoundConfig(
                        n_agents=n_agents, compression=name,
                        compress_ratio=0.25, compress_energy=0.9,
                        compress_backend=backend)
                    out.append(jax.jit(
                        lambda t, cfg=cfg: compress_increment(t, cfg))(tree))
                bitwise = all(
                    np.array_equal(a, b) for a, b in zip(
                        jax.tree_util.tree_leaves(out[0]),
                        jax.tree_util.tree_leaves(out[1])))
            bit_s = "-" if bitwise is None else int(bitwise)
            rows.append(
                f"compress_bench,kernel:{case}:{name},kernel={int(kernel)},"
                f"bitwise={bit_s},"
                f"N={n_agents};m={m_total};leaves={len(widths)}")
            payload.append(dict(
                kind="kernel", case=case, compressor=name,
                n_agents=n_agents, m_total=m_total, n_leaves=len(widths),
                kernel=bool(kernel), bitwise_equal=bitwise))
    return rows, payload


def run(quick=True):
    conv_rows, conv_payload = _convergence(quick)
    kernel_rows, kernel_payload = _kernels()
    payload = {"cases": conv_payload + kernel_payload,
               "quick": bool(quick)}
    return conv_rows + kernel_rows, payload


if __name__ == "__main__":
    print("\n".join(run()[0]))
