"""Device time of the ``fabric_queue_scan`` Pallas kernels per point, in
microseconds, from the trace.  (Their operands sit in on-chip memory,
so a share of the HBM roofline would overstate them; see PERF.md.)"""


def read(ctx):
    tr, c = ctx["trace"], ctx["counters"]
    if tr is None or not c.get("points"):
        return None
    kernel_s = tr.ops_named("fabric_queue_scan")
    if kernel_s <= 0.0:
        return None
    return 1e6 * kernel_s / c["points"]
