"""Host assembly of a point, in milliseconds per point, from the
program's spans: the stencil's flow and message columns
(``repro.fabric.prepare``), the merge's sort (``merge``) and the column
gathers into merge order (``permute``)."""

from chipbench.fabric_spans import stage_ms

STAGES = ("prepare", "merge", "permute")


def read(ctx):
    return stage_ms(ctx, STAGES)
