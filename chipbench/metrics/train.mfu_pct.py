"""Whole-step share of the chips' peak: the least time of one training
step (``flops.train_step_flops`` / ``train_step_bytes``, no
recomputation) times the steps run, over the window's wall time."""

from chipbench import flops


def read(ctx):
    c = ctx["counters"]
    if not c.get("train_steps"):
        return None
    s = flops.LMShape(**c["shape"])
    least = flops.least_time(
        flops.train_step_flops(s, c["batch_per_chip"], c["seq"]),
        flops.train_step_bytes(s), ctx["peak"])
    return 100.0 * least * c["train_steps"] / c["window_s"]
