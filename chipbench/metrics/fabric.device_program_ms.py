"""Device time of a point's jitted program (the stage gathers, the scan
kernels and the finish reductions), in milliseconds per point, from the
trace."""


def read(ctx):
    tr, c = ctx["trace"], ctx["counters"]
    if tr is None or not c.get("points"):
        return None
    prog_s = tr.module_mean_s("jit_run")
    if prog_s is None:
        return None
    n = sum(k for m, k in tr.module_n.items() if "jit_run" in m)
    return 1e3 * prog_s * n / c["points"]
