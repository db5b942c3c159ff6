"""Reduction of a profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read.

Planes named ``/device:TPU:<n>`` are the chips; their ``XLA Ops`` line
holds one event per device operation, and ``XLA Modules`` one per
executed program.  The host plane carries the benchmark's own spans
(``chipbench.*``, written by ``jax.profiler.TraceAnnotation``); the one
named ``chipbench.window`` bounds the traced window.  Times are in
nanoseconds on the profiler's common clock.
"""

from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional, Tuple

WINDOW_SPAN = "chipbench.window"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# "%fusion.3 = f32[...] fusion(...), kind=..." -> ("%fusion.3", "fusion")
_INSTR = re.compile(r"^(%[\w.\-]+) = .*?\b([a-z][\w\-]*)\(")
# ops whose span holds other ops' events: busy, but not a cost of their own
CONTAINERS = ("while", "conditional", "call")
# exchanges between chips (synchronous ops, or the wait of an async pair)
COLLECTIVE = re.compile(r"all-reduce|reduce-scatter|all-gather|"
                        r"collective-permute|all-to-all")


@dataclass
class TraceSummary:
    window_s: float
    n_devices: int
    busy_s: float                       # mean over devices
    op_s: Dict[str, float]              # "<instruction> <opcode>" ->
    #                                     seconds, summed over devices
    module_s: Dict[str, float]          # program name -> seconds, device 0
    module_n: Dict[str, int]            # program name -> executions, dev 0
    exposed_collective_s: float = 0.0   # mean over devices: collective
    #                                     ops while no other op ran
    idle_gaps: List[Tuple[str, float]] = field(default_factory=list)

    def ops_named(self, substr: str) -> float:
        """Seconds of every op whose instruction name holds ``substr``,
        per device."""
        tot = sum(s for n, s in self.op_s.items()
                  if substr in n.split(" ")[0])
        return tot / max(self.n_devices, 1)

    def module_mean_s(self, substr: str) -> Optional[float]:
        """Mean device seconds of one execution of the program(s) whose
        name holds ``substr``; None when none ran in the window."""
        n = sum(c for m, c in self.module_n.items() if substr in m)
        if n == 0:
            return None
        return sum(s for m, s in self.module_s.items() if substr in m) / n

    def breakdown(self, k: int = 10) -> dict:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:k]
        return {"device_ops": [[n, s / max(self.n_devices, 1)]
                               for n, s in ops],
                "idle_gaps": [[n, s] for n, s in self.idle_gaps[:k]]}


def op_key(name: str) -> str:
    """``"<instruction> <opcode>"`` of an op event's HLO text."""
    m = _INSTR.match(name)
    return f"{m.group(1)} {m.group(2)}" if m else name[:80]


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def _minus(a: List[Tuple[float, float]], b: List[Tuple[float, float]]
           ) -> float:
    """Length of the union ``a`` outside the union ``b`` (both sorted
    and disjoint)."""
    total, j = 0.0, 0
    for lo, hi in a:
        while j < len(b) and b[j][1] <= lo:
            j += 1
        cur, k = lo, j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                total += b[k][0] - cur
            cur = max(cur, b[k][1])
            k += 1
        total += max(0.0, hi - cur)
    return total


def find_xplane(trace_dir: Path) -> Path:
    files = sorted(Path(trace_dir).glob("**/*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def reduce_trace(path: Path, gaps: int = 10) -> TraceSummary:
    """Summarise one trace file.  ``path`` is the ``.xplane.pb`` or a
    directory holding one."""
    from jax.profiler import ProfileData

    path = Path(path)
    if path.is_dir():
        path = find_xplane(path)
    pd = ProfileData.from_file(str(path))
    host_spans: List[Tuple[float, float, str]] = []
    devices: Dict[int, object] = {}
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            devices[int(m.group(1))] = plane
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("chipbench."):
                        host_spans.append((e.start_ns,
                                           e.start_ns + e.duration_ns,
                                           e.name))
    windows = [(a, b) for a, b, n in host_spans if n == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"no {WINDOW_SPAN!r} span in {path}")
    w0, w1 = windows[0]
    if not devices:
        raise ValueError(f"no /device:TPU plane in {path}")

    op_s: Dict[str, float] = defaultdict(float)
    module_s: Dict[str, float] = defaultdict(float)
    module_n: Dict[str, int] = defaultdict(int)
    busy_total = exposed_total = 0.0
    first_busy: List[Tuple[float, float]] = []
    for i, dev in enumerate(sorted(devices)):
        iv, coll, comp = [], [], []
        for line in devices[dev].lines:
            if line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for e in line.events:
                a = max(e.start_ns, w0)
                b = min(e.start_ns + e.duration_ns, w1)
                if b <= a:
                    continue
                if line.name == OPS_LINE:
                    iv.append((a, b))
                    key = op_key(e.name)
                    if key.split(" ")[-1] in CONTAINERS:
                        continue
                    op_s[key] += (b - a) * 1e-9
                    (coll if COLLECTIVE.search(key) else comp).append((a, b))
                elif i == 0:
                    module_s[e.name] += (b - a) * 1e-9
                    module_n[e.name] += 1
        merged = _union(iv)
        busy_total += sum(b - a for a, b in merged) * 1e-9
        exposed_total += _minus(_union(coll), _union(comp)) * 1e-9
        if i == 0:
            first_busy = merged
    summary = TraceSummary(
        window_s=(w1 - w0) * 1e-9, n_devices=len(devices),
        busy_s=busy_total / len(devices), op_s=dict(op_s),
        module_s=dict(module_s), module_n=dict(module_n),
        exposed_collective_s=exposed_total / len(devices))
    summary.idle_gaps = _idle_gaps(first_busy, w0, w1, host_spans, gaps)
    return summary


def _idle_gaps(busy, w0, w1, host_spans, k):
    """The ``k`` longest idle stretches of one device inside the window,
    each named by the innermost benchmark span on the host around its
    middle (``host:idle`` when none)."""
    edges = [w0] + [x for iv in busy for x in iv] + [w1]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    inner = [s for s in host_spans if s[2] != WINDOW_SPAN]
    out = []
    for a, b in gaps[:k]:
        mid = (a + b) / 2
        around = [s for s in inner if s[0] <= mid <= s[1]]
        name = min(around, key=lambda s: s[1] - s[0])[2] if around \
            else "host:idle"
        out.append((name, (b - a) * 1e-9))
    return out
