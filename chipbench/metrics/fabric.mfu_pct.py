"""Whole-point share of the chip's peak: the least time of the device
memory traffic a point cannot avoid (``flops.fabric_point_bytes``) over
the measured wall of the points, host work included."""


def read(ctx):
    c = ctx["counters"]
    if not c.get("points"):
        return None
    least = c["point_bytes"] / ctx["peak"]["hbm_bytes_per_s"]
    return 100.0 * least / sum(c["point_walls_s"])
