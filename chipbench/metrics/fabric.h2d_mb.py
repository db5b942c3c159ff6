"""Operand bytes copied to the device per point, in MB (1e6 bytes):
the program's count ``bytes`` on its ``repro.fabric.h2d`` spans."""

from chipbench.fabric_spans import PREFIX, points


def read(ctx):
    found = points(ctx)
    if found is None:
        return None
    n, sp = found
    return sp.counts.get(PREFIX + "h2d", {}).get("bytes", 0) / n / 1e6
