"""Host spans of a recorded trace, per name, inside its window.

The host plane of a trace holds the benchmark's spans (``chipbench.*``)
and the program's (``repro.*``, written by ``repro.runtime.spans``),
one line per host thread, on the clock the device planes share.  A
per-layer reader gets the run's :class:`~chipbench.trace.TraceSummary`;
:func:`of_summary` finds the ``.xplane.pb`` it was reduced from (under
``harness.TRACE_DIR``, the one whose ``chipbench.window`` lasts the
summary's ``window_s``) and reduces its spans, once per file.
"""

from __future__ import annotations

import math
from collections import defaultdict
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional, Tuple

from chipbench import harness
from chipbench.trace import WINDOW_SPAN, find_xplane

PREFIXES = ("chipbench.", "repro.")


@dataclass
class Spans:
    window_s: float
    s: Dict[str, float] = field(default_factory=dict)  # seconds inside
    #                                                     the window
    n: Dict[str, int] = field(default_factory=dict)    # spans overlapping
    #                                                     the window
    counts: Dict[str, Dict[str, float]] = field(default_factory=dict)
    #                                        numeric args, summed


def host_spans(pd) -> List[Tuple[int, int, str, dict]]:
    """``(start_ns, end_ns, name, args)`` of every benchmark and program
    span on the host planes, in plane, line and event order."""
    return [(e.start_ns, e.start_ns + e.duration_ns, e.name, dict(e.stats))
            for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith(PREFIXES)]


def reduce_spans(spans, w0: float, w1: float) -> Spans:
    """Per name, over the spans that overlap the window ``[w0, w1]``:
    seconds inside it, how many, and each numeric arg summed."""
    secs: Dict[str, float] = defaultdict(float)
    num: Dict[str, int] = defaultdict(int)
    counts: Dict[str, Dict[str, float]] = defaultdict(
        lambda: defaultdict(float))
    for a, b, name, args in spans:
        if not (a < w1 and b > w0):
            continue
        num[name] += 1
        secs[name] += (min(b, w1) - max(a, w0)) * 1e-9
        for k, v in args.items():
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                counts[name][k] += v
    return Spans(window_s=(w1 - w0) * 1e-9, s=dict(secs), n=dict(num),
                 counts={k: dict(v) for k, v in counts.items()})


_READ: Dict[tuple, Optional[Spans]] = {}


def read(path: Path) -> Optional[Spans]:
    """The spans of one trace file inside its first ``chipbench.window``
    (as :func:`chipbench.trace.reduce_trace` takes it); None when it has
    no window."""
    from jax.profiler import ProfileData

    path = Path(path)
    if path.is_dir():
        path = find_xplane(path)
    st = path.stat()
    key = (str(path), st.st_mtime_ns, st.st_size)
    if key not in _READ:
        spans = host_spans(ProfileData.from_file(str(path)))
        windows = [(a, b) for a, b, name, _ in spans if name == WINDOW_SPAN]
        _READ[key] = reduce_spans(spans, *windows[0]) if windows else None
    return _READ[key]


def of_summary(summary) -> Optional[Spans]:
    """The spans of the trace ``summary`` was reduced from: the newest
    trace under ``harness.TRACE_DIR`` whose window lasts as long.  None
    when there is no summary or no such trace."""
    if summary is None:
        return None
    files = sorted(Path(harness.TRACE_DIR).glob("**/*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime_ns, reverse=True)
    for path in files:
        spans = read(path)
        if spans is not None and math.isclose(
                spans.window_s, summary.window_s, rel_tol=1e-12):
            return spans
    return None

