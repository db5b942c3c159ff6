"""Share of the traced window in which no operation ran on the chip."""


def read(ctx):
    tr = ctx["trace"]
    if tr is None or not ctx["counters"].get("points"):
        return None
    return 100.0 * (1.0 - tr.busy_s / tr.window_s)
