"""Share of the traced window of the model cells in which no operation
ran on the device (profiler trace: 1 - union of device-op intervals /
window, the window running from the first to the last harness span)."""


def read(r):
    t = r.get("trace")
    if t is None or "round.dispatch" not in r["spans"].count:
        return None
    return 100.0 * t.idle_share
