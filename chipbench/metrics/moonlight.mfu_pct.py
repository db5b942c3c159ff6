"""Whole-step share of the chip's peak of the DeepSeek-V3-style train
step: its least time (``flops_mla.train_step_flops`` /
``train_step_bytes``, no recomputation; the routed experts count the
pairs their held experts keep, read off the last step's load counter)
times the steps run, over the window's wall time."""

from chipbench import flops, flops_mla


def read(ctx):
    c = ctx["counters"]
    if not c.get("train_steps") or "load" not in c:
        return None
    s = flops_mla.MLAShape(**c["shape"])
    first, held = c["held"]
    rows = flops_mla.kept_rows(c["load"], first, held, c["capacity"])
    least = flops.least_time(
        flops_mla.train_step_flops(s, c["batch_per_chip"], c["seq"], rows),
        flops_mla.train_step_bytes(s), ctx["peak"])
    return 100.0 * least * c["train_steps"] / c["window_s"]
