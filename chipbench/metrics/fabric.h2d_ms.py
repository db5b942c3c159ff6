"""Host time of handing a point to the device, in milliseconds per
point, from the program's spans: the operands' copy to the device
(``repro.fabric.h2d``) and the program's launch (``launch``)."""

from chipbench.fabric_spans import stage_ms

STAGES = ("h2d", "launch")


def read(ctx):
    return stage_ms(ctx, STAGES)
