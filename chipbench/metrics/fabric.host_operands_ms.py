"""Host work that turns a merged point into the device program's
operands, in milliseconds per point, from the program's spans: the
finish probes (``repro.fabric.finish_spec``) and the operand assembly
with its layouts, cost columns and bucket indices (``operands``)."""

from chipbench.fabric_spans import stage_ms

STAGES = ("finish_spec", "operands")


def read(ctx):
    return stage_ms(ctx, STAGES)
