"""Share of the last step's routed (token, slot) pairs that the router
sent to the experts held here (before capacity), from the program's load
counter: an eighth where routing is even over an 8-chip group."""


def read(ctx):
    c = ctx["counters"]
    if not c.get("train_steps") or "load" not in c:
        return None
    first, held = c["held"]
    total = sum(sum(row) for row in c["load"])
    mine = sum(sum(row[first:first + held]) for row in c["load"])
    return 100.0 * mine / total if total else None
