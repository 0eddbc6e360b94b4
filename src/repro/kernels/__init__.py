"""Pallas TPU kernels for the framework's compute hot spots.

Each kernel ships as <name>/kernel.py (pl.pallas_call + BlockSpec),
<name>/ops.py (jit'd public wrapper with interpret fallback) and
<name>/ref.py (pure-jnp oracle used by the allclose test sweeps).

  fedplt_update   -- the paper's fused local training step (elementwise,
                     3 reads 1 write, optional DP noise) -- the deployed
                     algorithm's per-parameter hot loop.
  compress        -- fused uplink-compression kernels (per-segment
                     magnitude-rank select for topk/adaptive_topk, int8
                     quantize-dequantize) over the packed agent-axis
                     buffer of repro.fed.compress.pack_leaves.
  round_edge      -- the Fed-PLT round's coordinator edges, fused over
                     the same packed buffer: agent-axis mean + prox_h +
                     reflection in one launch (uplink), Krasnosel'skii
                     z-update + participation selects in another
                     (downlink) -- repro.fed.engine's "pallas" backend.
  flash_attention -- blockwise online-softmax attention with GQA,
                     sliding window and logit softcap (model hot spot).
  lru_scan        -- chunked diagonal linear recurrence (RG-LRU / mamba
                     time mixing) with sequential cross-chunk carry.

On the CPU (the test suite) kernels run with interpret=True; on a TPU
the default ``interpret=None`` resolves to Mosaic via ``ON_TPU``, which
is read once, at import.  ``chip_smoke.py`` fails if it is false on a
TPU, and checks that its compiled rounds hold ``tpu_custom_call``.
"""

import jax

ON_TPU = jax.default_backend() == "tpu"
