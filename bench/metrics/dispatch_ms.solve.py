"""Host time to hand one solve to the device: the harness span
``solve.dispatch`` around ``trainer.run`` (init, tracing and dispatch
of the round scan), summed over the window and divided by the solves.
The readback that follows waits for the device, so it is not counted
here."""


def read(r):
    n = r["spans"].count.get("solve.dispatch")
    if not n:
        return None
    return 1e3 * r["spans"].total_s["solve.dispatch"] / n
