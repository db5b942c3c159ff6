"""Host time of building a point's result from the per-rank times, in
milliseconds per point, from the program's span
``repro.fabric.result``."""

from chipbench.fabric_spans import stage_ms

STAGES = ("result",)


def read(ctx):
    return stage_ms(ctx, STAGES)
