"""Shared engine-differential harness: one driver table, every engine.

The engine suites (``test_engine_diff.py``, ``test_engine_pallas.py``
and the serving/faults diff classes) grew
near-identical copies of the approach lists, the randomized ready-table
builder, the forced-scan cutoff switching and the per-driver result
comparison loops.  This module is the single copy: a :data:`DRIVERS`
table maps each scenario driver to how it runs on one engine and which
result fields the engines must agree on **exactly** (the bit-for-bit
contract — arrays via ``np.array_equal``, scalars via ``==``), and
:func:`assert_engines_agree` is the one differential loop.

A new driver — like the plan-IR executor — registers one
:class:`DriverCase` row and gets all-engine differential coverage from
the same table instead of another copy-pasted suite.
"""

import contextlib
from dataclasses import dataclass
from typing import Callable, Tuple

import numpy as np

from repro.core import fabric as fb
from repro.core import plan_ir as pir
from repro.core import simulator as sim

APPROACHES = sorted(sim.APPROACHES)
PIPELINED = ("part", "part_old", "pt2pt_single", "pt2pt_many")

# Relative tolerance of the compiled engine's float32 mode (x64 off):
# single-precision rounding over a few thousand serial queue updates
# stays well inside 1e-4 relative.
F32_RTOL = 1e-4


def ready(n_threads, theta, seed):
    """The randomized ready table every suite draws from its seed axis
    (``None``: the driver's default table)."""
    if seed is None:
        return None
    rng = np.random.default_rng(seed)
    return rng.uniform(0.0, 25e-6, size=(n_threads, theta))


def grid_items(points):
    """Assemble GridItems + FinishSpecs for the low-level grid entry
    points, the way ``simulate_stencil_grid`` does internally."""
    from repro.core import fabric_pallas as fp
    items, fins = [], []
    for p in points:
        prep = sim._prepare_stencil(**p)
        order = sim._merge_order(prep.cols["t_ready"], prep.memo_key)
        c = prep.cols
        items.append(fp.GridItem(
            t_ready=c["t_ready"][order], nbytes=c["nbytes"][order],
            vci=c["vci"][order], thread=c["thread"][order],
            put=c["put"][order], am_copy=c["am_copy"][order],
            src=c["src"][order], dst=c["dst"][order],
            cfg=prep.cfg, n_vcis=prep.n_vcis, n_ranks=prep.n_ranks,
            key=prep.memo_key, order=order, plan_key=prep.plan_key))
        fins.append(sim._pallas_finish_spec(prep))
    return items, fins


@contextlib.contextmanager
def forced_scans():
    """Route every batch through the staged scans / fused kernels,
    however narrow, so small scenarios exercise the batched paths the
    adaptive cutoffs would route to the scalar fallback.  Module-global
    cutoffs are restored on exit; hypothesis tests use this directly
    (function-scoped fixtures don't reset per example)."""
    cutoff, par = fb.SCALAR_BATCH_CUTOFF, fb.MIN_GROUP_PARALLELISM
    fb.SCALAR_BATCH_CUTOFF = fb.MIN_GROUP_PARALLELISM = 0
    try:
        yield
    finally:
        fb.SCALAR_BATCH_CUTOFF, fb.MIN_GROUP_PARALLELISM = cutoff, par


@dataclass(frozen=True)
class DriverCase:
    """One driver-table row: how to run a scenario on one engine, and
    the result fields every engine must reproduce exactly."""
    run: Callable           # (approach, engine, **kw) -> result object
    fields: Tuple[str, ...]


def _ir_run(approach, engine, *, module, faults=None):
    """The IR executor as a table driver: the module (usually built by
    ``plan_ir.raise_*`` — possibly pass-rewritten) carries the scenario;
    ``approach`` rides in the module and is ignored here."""
    return pir.execute(module, engine=engine, faults=faults)


DRIVERS = {
    "oneshot": DriverCase(
        lambda ap, engine, **kw: sim.simulate(ap, engine=engine, **kw),
        ("n_messages", "time_s", "tts_s")),
    "steady": DriverCase(
        lambda ap, engine, **kw: sim.simulate_steady_state(
            ap, engine=engine, **kw),
        ("iter_times_s", "setup_s", "tts_s", "n_messages")),
    "halo": DriverCase(
        lambda ap, engine, **kw: sim.simulate_halo(
            ap, engine=engine, **kw),
        ("rank_tts_s", "n_messages", "time_s", "tts_s")),
    "stencil": DriverCase(
        lambda ap, engine, **kw: sim.simulate_stencil(
            ap, engine=engine, **kw),
        ("rank_tts_s", "sent_per_rank", "face_bytes", "n_messages",
         "time_s", "tts_s")),
    "imbalance": DriverCase(
        lambda ap, engine, **kw: sim.simulate_imbalance(
            ap, engine=engine, **kw),
        ("rank_tts_s", "mean_delay_s", "n_messages", "time_s", "tts_s")),
    "serving": DriverCase(
        lambda ap, engine, **kw: sim.simulate_serving(
            ap, engine=engine, **kw),
        ("latency_s", "tts_s", "n_messages", "n_waves")),
    "faulty": DriverCase(
        lambda ap, engine, **kw: sim.simulate_faulty(
            ap, engine=engine, **kw),
        ("rank_tts_s", "tts_s", "n_retransmits", "retrans_bytes",
         "rounds", "n_messages")),
    "ir": DriverCase(
        _ir_run,
        ("rank_tts_s", "tts_s", "time_s", "n_messages", "n_wire",
         "n_flows", "n_retransmits", "retrans_bytes", "rounds")),
}


def assert_results_equal(a, b, fields, context=""):
    """Exact equality on ``fields`` of two result objects — arrays
    compared elementwise, everything else with ``==``."""
    for f in fields:
        va, vb = getattr(a, f), getattr(b, f)
        if isinstance(va, np.ndarray) or isinstance(vb, np.ndarray):
            ok = np.array_equal(va, vb)
        else:
            ok = va == vb
        assert ok, f"{context}{f}: {va!r} != {vb!r}"


def assert_results_close(a, b, rtol=F32_RTOL):
    """The compiled engine's float32 contract: structural counters stay
    exact, times within ``rtol`` — ``time_s`` subtracts compute from
    tts, so its tolerance is anchored to the tts magnitude, not its own
    (possibly tiny) value."""
    assert a.n_messages == b.n_messages
    assert abs(a.tts_s - b.tts_s) <= rtol * abs(b.tts_s)
    assert abs(a.time_s - b.time_s) <= rtol * abs(b.tts_s)


def assert_engines_agree(driver, approach, *,
                         engines=("vector", "reference"), forced=False,
                         **kw):
    """Run one scenario on each engine and require exact agreement on
    the driver's comparison fields; returns the first engine's result.

    ``forced`` pushes every non-reference engine through the staged
    scans / fused kernels regardless of batch width (the reference
    oracle has no batched path to force).  The compiled engine needs
    x64 for exact equality — callers wrap in ``compat.x64_mode(True)``.
    """
    case = DRIVERS[driver]
    results = []
    for engine in engines:
        if forced and engine != "reference":
            with forced_scans():
                results.append(case.run(approach, engine, **kw))
        else:
            results.append(case.run(approach, engine, **kw))
    base = results[0]
    for engine, r in zip(engines[1:], results[1:]):
        assert_results_equal(
            base, r, case.fields,
            context=f"[{driver}/{approach}] {engines[0]} vs {engine}: ")
    return base
