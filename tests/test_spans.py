"""Host spans of the fabric's grid path (:mod:`repro.runtime.spans`).

A profiler trace of one grid point holds each host stage once, nested in
``repro.fabric.grid`` on the calling thread, with its counts as event
args.  With no profiler a span is a plain context; where JAX was never
imported (the NumPy engines) it is a shared no-op and imports nothing.
The fabric's jitted program keeps its name, which the benchmark's trace
reduction looks for (``jit_run``).
"""

import glob
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from repro.core import simulator as sim  # noqa: E402
from repro.runtime import spans  # noqa: E402

REPO = Path(__file__).resolve().parent.parent

# the stages directly inside ``repro.fabric.grid``, in the order they run
SHARED = ["prepare", "merge", "permute"]
PALLAS = SHARED + ["finish_spec", "operands", "h2d", "launch", "readback",
                   "result"]
OPERANDS = ["layouts", "cost_columns", "stage_ops", "finish_layout"]


def _point(scale: float) -> dict:
    """A 4x4x4 torus halo point; ``scale`` makes its ready table (and so
    every memo key) its own."""
    ready = np.cumsum(np.full((2, 4), 1e-6 * scale), axis=1)
    return dict(approach="part", dims=(4, 4, 4), periodic=True, theta=4,
                n_threads=2, local_shape=(16, 16, 16), bytes_per_cell=8.0,
                halo_width=1, ready=ready, n_vcis=2, aggr_bytes=0.0)


def _traced_point(engine: str, scale: float, tmp_path):
    """Program spans of one grid point (its own ``scale``) traced on the
    CPU after a warm-up point of the same shapes: ``[(name, start, end,
    args)]`` and the point's result."""
    sim.simulate_stencil_grid([_point(1.0)], engine=engine)
    jax.profiler.start_trace(str(tmp_path))
    try:
        res = sim.simulate_stencil_grid([_point(scale)], engine=engine)[0]
    finally:
        jax.profiler.stop_trace()
    from jax.profiler import ProfileData
    path = sorted(glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True))
    got = []
    for plane in ProfileData.from_file(path[-1]).planes:
        for line in plane.lines:
            got += [(e.name[len(spans.PREFIX):], e.start_ns,
                     e.start_ns + e.duration_ns, dict(e.stats))
                    for e in line.events
                    if e.name.startswith(spans.PREFIX + "fabric.")]
    return got, res


def _inside(inner, outer) -> bool:
    return outer[1] <= inner[1] and inner[2] <= outer[2]


@pytest.mark.parametrize("engine,scale", [("jax", 1.5), ("pallas", 1.75)])
def test_grid_point_trace_holds_every_stage_span(engine, scale, tmp_path):
    got, res = _traced_point(engine, scale, tmp_path)
    by = {}
    for s in got:
        by.setdefault(s[0], []).append(s)
    stages = PALLAS if engine == "pallas" else SHARED + ["result"]
    inner = OPERANDS if engine == "pallas" else []
    assert sorted(by) == sorted(["fabric.grid"]
                                + ["fabric." + s for s in stages + inner])
    assert all(len(v) == 1 for v in by.values()), by
    grid = by["fabric.grid"][0]
    seq = [by["fabric." + s][0] for s in stages]
    assert all(_inside(s, grid) for s in seq)
    # the stages run one after the other
    assert all(a[2] <= b[1] for a, b in zip(seq, seq[1:]))
    if engine == "pallas":
        ops = by["fabric.operands"][0]
        assert all(_inside(by["fabric." + s][0], ops) for s in inner)
        # float32 release times and costs of every message, at least
        assert by["fabric.h2d"][0][3]["bytes"] > 4 * 4 * res.n_messages


def test_fabric_program_module_is_jit_run(monkeypatch):
    from repro.core import fabric_pallas as fp
    real = fp._build_call
    lowered = []

    def spy(meta):
        fn = real(meta)

        def call(*ops):
            lowered.append(fn.lower(*ops).as_text())
            return fn(*ops)
        return call

    monkeypatch.setattr(fp, "_build_call", spy)
    res = sim.simulate_stencil_grid([_point(2.0)], engine="pallas")[0]
    assert res is not None and lowered
    assert all("module @jit_run" in text for text in lowered)


def test_span_without_a_profiler_is_a_plain_context():
    with spans.span("test.stage", bytes=8) as s:
        s.set_metadata(bytes=16)
    assert not jax.profiler.TraceAnnotation.is_enabled()


def _python(code: str) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(REPO / "src"))
    return subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=300)


def test_span_without_jax_is_a_shared_no_op_and_imports_none():
    proc = _python(
        "import sys\n"
        "from repro.runtime import spans\n"
        "a = spans.span('x', bytes=1)\n"
        "with a as s:\n"
        "    s.set_metadata(bytes=2)\n"
        "assert a is spans.span('y')\n"
        "assert 'jax' not in sys.modules, 'jax imported'\n")
    assert proc.returncode == 0, proc.stderr


@pytest.mark.parametrize("engine", ["vector", "reference"])
def test_numpy_engines_import_no_jax(engine):
    proc = _python(
        "import sys\n"
        "from repro.core import simulator as sim\n"
        f"r = sim.simulate_stencil('part', dims=(4, 4, 4), theta=4,\n"
        f"    n_threads=2, local_shape=(8, 8, 8), n_vcis=2,\n"
        f"    engine={engine!r})\n"
        "assert r.n_messages > 0\n"
        "assert 'jax' not in sys.modules, 'jax imported'\n")
    assert proc.returncode == 0, proc.stderr
