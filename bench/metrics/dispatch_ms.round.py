"""Host time to hand one round to the device: the harness span
``round.dispatch`` around ``trainer.step``, summed over the window and
divided by the rounds.  The loss readback that follows waits for the
device, so it is not counted here."""


def read(r):
    n = r["spans"].count.get("round.dispatch")
    if not n:
        return None
    return 1e3 * r["spans"].total_s["round.dispatch"] / n
