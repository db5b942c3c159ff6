"""The fabric grid call's stage spans, as the ``fabric.*`` span metrics
read them.

Each stage is one program span directly inside ``repro.fabric.grid``
(``simulator.simulate_stencil_grid``); the metrics are per point, and
read only when the window holds one grid span per point (so a program
without the spans reports none).

``python3 -m chipbench.fabric_spans <trace dir or .xplane.pb>`` prints
every ``repro.fabric.*`` span and ``chipbench.point`` of a traced run
of the fabric cell in milliseconds per point: the split of a point.
"""

from __future__ import annotations

import json
import sys
from typing import Optional

from chipbench import spans as _spans

PREFIX = "repro.fabric."
GRID = PREFIX + "grid"
STAGES = ("prepare", "merge", "permute", "finish_spec", "operands", "h2d",
          "launch", "readback", "result")


def points(ctx) -> Optional[tuple]:
    """``(points, spans)`` of the fabric window when its trace holds one
    ``repro.fabric.grid`` span for each point; None otherwise (no trace,
    or a program without the spans)."""
    n = ctx["counters"].get("points")
    if not n:
        return None
    sp = _spans.of_summary(ctx["trace"])
    if sp is None or sp.n.get(GRID) != n:
        return None
    return n, sp


def stage_s(sp: _spans.Spans, stages) -> float:
    """Seconds inside the named ``repro.fabric.*`` stage spans, all told."""
    return sum(sp.s.get(PREFIX + st, 0.0) for st in stages)


def stage_ms(ctx, stages) -> Optional[float]:
    """Milliseconds per point inside the named stage spans; None where
    :func:`points` finds none."""
    found = points(ctx)
    if found is None:
        return None
    n, sp = found
    return 1e3 * stage_s(sp, stages) / n


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        print("usage: python3 -m chipbench.fabric_spans <trace dir or "
              ".xplane.pb>", file=sys.stderr)
        return 2
    sp = _spans.read(argv[0])
    n = sp.n.get(GRID) if sp is not None else None
    if not n:
        print(f"no {GRID} span in the window of {argv[0]}", file=sys.stderr)
        return 1
    ms = {name: 1e3 * s / n for name, s in sorted(sp.s.items())
          if name.startswith(PREFIX) or name == "chipbench.point"}
    print(json.dumps({"points": n, "ms_per_point": ms}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
