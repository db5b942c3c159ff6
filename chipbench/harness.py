"""What every driver shares: files found by name, the device, the window.

A driver (``drivers/<kind>.py``) exposes ``run(cell) -> Outcome``; it
builds the system under test from the cell's configuration and traffic,
warms it up, measures one :class:`Window`, and checks what the window
produced against the configuration's plain reference.
"""

from __future__ import annotations

import importlib
import importlib.util
import json
import math
import shutil
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Dict, List, Optional

PKG = Path(__file__).resolve().parent
ROOT = PKG.parent
TRACE_DIR = ROOT / ".chipbench" / "trace"

# JAX monitoring events that mean "something was traced or compiled"
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/core/compile/jaxpr_trace_duration")


def load_json(path: Path) -> dict:
    return json.loads(Path(path).read_text())


def load_benchmark(root: Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


def load_module(path: Path):
    """Import a file by path (file names follow benchmark names, which
    may hold ``-`` and ``.``)."""
    path = Path(path)
    name = "chipbench_file_" + "".join(
        c if c.isalnum() else "_" for c in str(path.relative_to(ROOT)))
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def driver(kind: str):
    return importlib.import_module(f"chipbench.drivers.{kind}")


def metric_reader(name: str):
    return load_module(PKG / "metrics" / f"{name}.py")


def reference(config: dict):
    """The configuration's plain reference, beside its file."""
    return load_module(PKG / "configs" / config["reference"])


def peaks(device_kind: str) -> dict:
    table = load_json(PKG / "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


@dataclass
class Cell:
    """One workload, resolved: its entry, configuration and traffic."""
    name: str
    chips: int
    config: dict
    traffic: dict
    seed: int
    seconds: float
    trace: bool
    t_process: float              # perf_counter at process start
    devices: list = field(default_factory=list)


def resolve(bench: dict, workload: str) -> tuple:
    """(workload entry, configuration dict, traffic dict)."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; one of "
                       f"{sorted(cells)}")
    w = cells[workload]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = load_json(ROOT / cfg_entry["file"])
    traffic = load_json(PKG / "traffic" / f"{w['traffic']}.json")
    return w, config, traffic


# ---------------------------------------------------------------------------
# Compilations inside the window
# ---------------------------------------------------------------------------

_COMPILES = [0]
_LISTENING = [False]


def _listen() -> None:
    if _LISTENING[0]:
        return
    from jax._src import monitoring

    def on_event(name, _secs, **_kw):
        if name in COMPILE_EVENTS:
            _COMPILES[0] += 1

    monitoring.register_event_duration_secs_listener(on_event)
    _LISTENING[0] = True


def compile_count() -> int:
    _listen()
    return _COMPILES[0]


# ---------------------------------------------------------------------------
# The measured window (and its trace)
# ---------------------------------------------------------------------------

class Window:
    """The timed span: counts compilations in it and, with ``trace``,
    records a profiler trace of it under ``TRACE_DIR/<cell>``.

    ``running()`` is True until ``seconds`` have passed since the
    window opened; drivers finish the unit in flight and close it.
    """

    def __init__(self, cell_name: str, seconds: float, trace: bool):
        self.seconds = seconds
        self.trace = trace
        self.dir = TRACE_DIR / cell_name
        self.compiles = 0
        self.t0 = self.t1 = 0.0
        self._annot = None

    def __enter__(self) -> "Window":
        import jax
        c0 = compile_count()
        if self.trace:
            shutil.rmtree(self.dir, ignore_errors=True)
            self.dir.mkdir(parents=True)
            # device ops and the benchmark's spans: all the reducer reads
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(str(self.dir), profiler_options=opts)
        self._annot = jax.profiler.TraceAnnotation("chipbench.window")
        self._annot.__enter__()
        self._c0 = c0
        self.t0 = time.perf_counter()
        return self

    def running(self) -> bool:
        return time.perf_counter() - self.t0 < self.seconds

    def elapsed(self) -> float:
        return time.perf_counter() - self.t0

    def __exit__(self, *exc) -> None:
        import jax
        self.t1 = time.perf_counter()
        self._annot.__exit__(*exc)
        if self.trace:
            jax.profiler.stop_trace()
        self.compiles = compile_count() - self._c0

    @property
    def wall_s(self) -> float:
        return self.t1 - self.t0


def span(name: str):
    """A host span on the profiler's clock (free when not tracing)."""
    import jax
    return jax.profiler.TraceAnnotation("chipbench." + name)


# ---------------------------------------------------------------------------
# What a driver hands back
# ---------------------------------------------------------------------------

@dataclass
class Check:
    """One number compared: correct when ``value <= limit``."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


@dataclass
class Outcome:
    e2e: Dict[str, float]                 # end-to-end metrics of the cell
    setup_s: float
    attempted: int
    failed: int
    checks: List[Check]
    counters: Dict[str, Any]              # read by the per-layer readers
    memory_peak_bytes: int
    window: Optional[Window] = None


def memory_peak(devices) -> int:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def percentile(values, q: float) -> float:
    """Nearest-rank percentile (q in 0..100) of all values."""
    v = sorted(values)
    if not v:
        return float("nan")
    k = max(0, math.ceil(q / 100.0 * len(v)) - 1)
    return float(v[k])


def sample_indices(seed: int, n: int, k: int, always=()) -> List[int]:
    """``k`` of ``range(n)`` drawn from ``seed``, plus ``always``."""
    import numpy as np
    rng = np.random.default_rng([seed, 0x5A3])
    pick = set(int(i) for i in always if 0 <= i < n)
    rest = [i for i in range(n) if i not in pick]
    extra = max(0, min(k - len(pick), len(rest)))
    pick.update(int(i) for i in rng.choice(rest, extra, replace=False))
    return sorted(pick)
