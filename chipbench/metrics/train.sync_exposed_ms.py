"""Gradient sync left exposed: the device time of the exchanges between
chips during which no other operation ran, per training step, in
milliseconds (mean over the chips), from the trace.  Nothing to read on
one chip, where no exchange runs."""


def read(ctx):
    tr, c = ctx["trace"], ctx["counters"]
    if tr is None or not c.get("train_steps") or c.get("chips", 1) < 2:
        return None
    return 1e3 * tr.exposed_collective_s / c["train_steps"]
