"""Host time spent waiting for a point's result, in milliseconds per
point, from the program's span ``repro.fabric.readback``: the device
program still running, then the copy of the per-rank times back."""

from chipbench.fabric_spans import stage_ms

STAGES = ("readback",)


def read(ctx):
    return stage_ms(ctx, STAGES)
