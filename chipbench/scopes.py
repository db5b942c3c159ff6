"""Device time by model scope: the trace's op times joined to the
compiled step's ``repro.*`` scopes.

The driver hands the readers ``counters["scopes"]``, the compiled
step's map from HLO instruction name to the innermost ``repro.*`` scope
of its ``op_name`` metadata; the trace names each device op by its
instruction (``"<instruction> <opcode>"`` in ``TraceSummary.op_s``).
"""

from __future__ import annotations

from typing import Optional


def seconds(ctx, *scopes: str) -> Optional[float]:
    """Device seconds per chip of the ops whose scope is one of
    ``scopes``; None without a trace or a map."""
    tr, c = ctx["trace"], ctx["counters"]
    if tr is None or not c.get("train_steps") or "scopes" not in c:
        return None
    names = c["scopes"]
    tot = 0.0
    for key, s in tr.op_s.items():
        scope = names.get(key.split(" ")[0])
        if scope is not None and scope in scopes:
            tot += s
    return tot / max(tr.n_devices, 1)


def ms_per_step(ctx, *scopes: str) -> Optional[float]:
    s = seconds(ctx, *scopes)
    return None if s is None else 1e3 * s / ctx["counters"]["train_steps"]


def unscoped_share(ctx) -> Optional[float]:
    """Share of the device op time whose instruction has no scope."""
    tr, c = ctx["trace"], ctx["counters"]
    if tr is None or not c.get("train_steps") or "scopes" not in c:
        return None
    names = c["scopes"]
    total = sum(tr.op_s.values())
    none = sum(s for key, s in tr.op_s.items()
               if key.split(" ")[0] not in names)
    return none / total if total else None
