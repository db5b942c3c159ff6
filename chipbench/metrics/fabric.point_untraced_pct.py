"""Share of the points' time (the benchmark's ``chipbench.point`` spans)
that no stage span of the program (``fabric_spans.STAGES``) covers.

A guard on the spans' coverage, not a layer's speed: it rises when a
change moves work out of the stages.  Its numerator (the grid call's
own bookkeeping) hardly moves, so it also rises when the stages get
faster."""

from chipbench.fabric_spans import STAGES, points, stage_s


def read(ctx):
    found = points(ctx)
    if found is None:
        return None
    _, sp = found
    point_s = sp.s.get("chipbench.point", 0.0)
    if point_s <= 0.0:
        return None
    return 100.0 * (point_s - stage_s(sp, STAGES)) / point_s
