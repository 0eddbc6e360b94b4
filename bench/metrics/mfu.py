"""Model FLOP utilization of the round step: the forward and backward
FLOPs the window's rounds require (``bench/flops.py``, counted from
shapes, recomputation excluded) over the window on the host clock times
the chip's bf16 peak (``bench/peaks.py``)."""


def read(r):
    if not r.get("flops_per_unit") or not r["units"]:
        return None
    from bench.peaks import peaks_for

    peak = peaks_for(r["device_kind"]).bf16_flops
    return 100.0 * r["flops_per_unit"] * r["units"] / (r["window_s"] * peak)
