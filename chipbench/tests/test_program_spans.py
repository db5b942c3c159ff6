"""The host-span reduction (``chipbench/spans.py``) and the fabric's
span metrics (``chipbench/fabric_spans.py``): clipping to the window,
summed counts, finding the trace a summary came from, silence on a
trace without program spans, and a traced CPU run of the fabric driver
whose stages make up the points' wall.  The existing readers' values on
the recorded four-chip trace are pinned beside them."""

from __future__ import annotations

import dataclasses
import json
import shutil
from pathlib import Path

import pytest

from chipbench import fabric_spans, harness, spans, trace

RECORDED = Path(__file__).parent / "data" / "dp4_small.xplane.pb"
NEW = ["fabric.host_assembly_ms", "fabric.host_operands_ms",
       "fabric.h2d_ms", "fabric.readback_ms", "fabric.host_result_ms",
       "fabric.h2d_mb", "fabric.point_untraced_pct"]
EXISTING = {  # the readers' values on the recorded trace
    "fabric.mfu_pct": 6.105006105006105,
    "fabric.scan_kernel_us": None,
    "fabric.device_program_ms": None,
    "fabric.device_idle_pct": 99.92851749153357,
    "train.mfu_pct": 0.3250460236060236,
    "train.device_idle_pct": 99.92851749153357,
    "train.sync_exposed_ms": 0.01689175,
}


def test_reduce_spans_clips_counts_and_sums_args():
    # the window [5, 125] cuts the point's first 5 ns
    # and the second grid span's last 5
    sp = spans.reduce_spans(
        [(0, 100, "chipbench.point", {}),
         (10, 90, "repro.fabric.grid", {}),
         (50, 80, "repro.fabric.h2d", {"bytes": 7, "tag": "x",
                                       "flag": True}),
         (120, 130, "repro.fabric.grid", {}),
         (200, 210, "repro.fabric.h2d", {"bytes": 100}),
         (20, 30, "repro.fabric.h2d", {"bytes": 5})], 5, 125)
    ns = 1e-9
    assert sp.window_s == pytest.approx(120 * ns)
    assert sp.s["chipbench.point"] == pytest.approx(95 * ns)
    assert sp.s["repro.fabric.grid"] == pytest.approx((80 + 5) * ns)
    assert sp.s["repro.fabric.h2d"] == pytest.approx((30 + 10) * ns)
    # the span wholly outside the window counts nowhere
    assert sp.n == {"chipbench.point": 1, "repro.fabric.grid": 2,
                    "repro.fabric.h2d": 2}
    # numbers only: strings and flags are not counts
    assert sp.counts["repro.fabric.h2d"] == {"bytes": 12}
    assert "chipbench.point" not in sp.counts


def test_of_summary_finds_the_trace_it_was_reduced_from(tmp_path,
                                                        monkeypatch):
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path)
    assert spans.of_summary(trace.reduce_trace(RECORDED)) is None
    (tmp_path / "cell").mkdir()
    shutil.copy(RECORDED, tmp_path / "cell" / "a.xplane.pb")
    summary = trace.reduce_trace(RECORDED)
    got = spans.of_summary(summary)
    assert got is not None and got.window_s == summary.window_s
    assert got.n["chipbench.window"] == 1
    assert not any(n.startswith("repro.") for n in got.n)
    # another window's summary, or none, finds nothing
    other = dataclasses.replace(summary, window_s=summary.window_s * 1.01)
    assert spans.of_summary(other) is None
    assert spans.of_summary(None) is None


def _recorded_ctx(kind: str) -> dict:
    from chipbench import flops
    from chipbench.tests import small
    counters = {
        "fabric": {"points": 3, "point_walls_s": [0.01, 0.02, 0.03],
                   "point_bytes": 3e9},
        "train": {"train_steps": 3, "chips": 4, "batch_per_chip": 2,
                  "seq": 32, "window_s": 0.09,
                  "shape": flops.LMShape.from_config(
                      small.granite()).__dict__}}[kind]
    return {"trace": trace.reduce_trace(RECORDED), "counters": counters,
            "peak": harness.peaks("TPU v5 lite"), "chips": 4}


@pytest.mark.parametrize("name", sorted(EXISTING))
def test_existing_readers_read_as_before(name):
    got = harness.metric_reader(name).read(_recorded_ctx(name.split(".")[0]))
    want = EXISTING[name]
    assert got == (None if want is None else pytest.approx(want, rel=1e-12))


@pytest.mark.parametrize("name", NEW)
def test_span_readers_read_nothing_without_program_spans(name, monkeypatch):
    # the trace is found, but holds no grid span (a program without
    # spans): no value, and no error
    monkeypatch.setattr(harness, "TRACE_DIR", RECORDED.parent)
    ctx = _recorded_ctx("fabric")
    assert spans.of_summary(ctx["trace"]) is not None
    assert harness.metric_reader(name).read(ctx) is None
    assert harness.metric_reader(name).read(dict(ctx, trace=None)) is None


def test_stage_metrics_cover_every_stage_once():
    seen = [st for name in NEW[:5]
            for st in harness.metric_reader(name).STAGES]
    assert sorted(seen) == sorted(fabric_spans.STAGES)


def test_span_readers_on_a_traced_cpu_run(tmp_path, monkeypatch, capsys):
    """The fabric driver's traced window on the CPU: the seven readers
    find their spans, and the stages with the untraced share make up the
    points' wall.  (Times here are the CPU's, a check of the reduction
    only.)"""
    from chipbench.tests import small
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path)
    cell = dataclasses.replace(
        small.cell(small.torus(), small.traffic("part-noise"), 1.0),
        trace=True)
    out = harness.driver("stencil_grid").run(cell)
    assert small.correct(out), out.checks
    sp = spans.read(out.window.dir)
    # the CPU trace has no chip plane for reduce_trace: a summary that
    # carries the window alone stands in for it
    ctx = {"trace": trace.TraceSummary(
        window_s=sp.window_s, n_devices=1, busy_s=0.0, op_s={},
        module_s={}, module_n={}), "counters": out.counters}
    got = {m: harness.metric_reader(m).read(ctx) for m in NEW}
    assert all(v is not None and v >= 0 for v in got.values()), got
    points = out.counters["points"]
    assert sp.n[fabric_spans.GRID] == points
    wall_ms = 1e3 * sum(out.counters["point_walls_s"]) / points
    point_ms = 1e3 * sp.s["chipbench.point"] / points
    stages_ms = sum(got[m] for m in NEW[:5])
    untraced_ms = got["fabric.point_untraced_pct"] / 100 * point_ms
    assert stages_ms + untraced_ms == pytest.approx(wall_ms, rel=0.03)
    assert got["fabric.h2d_mb"] > 0
    # the split of a point, as the command line prints it
    assert fabric_spans.main([str(out.window.dir)]) == 0
    split = json.loads(capsys.readouterr().out)
    assert split["points"] == points
    assert split["ms_per_point"]["chipbench.point"] == pytest.approx(
        point_ms)
    assert sum(split["ms_per_point"][fabric_spans.PREFIX + st]
               for st in fabric_spans.STAGES) == pytest.approx(stages_ms)
