"""The trace reduction: busy time as a union of device intervals inside
the window, exposed collective time, idle gaps named by the host span
around them, and a refusal of traces without a chip.  The recorded
trace is of four v5e chips (``record_trace.py``)."""

from __future__ import annotations

from pathlib import Path

import pytest

from chipbench import trace
from chipbench.tests import record_trace

RECORDED = Path(__file__).parent / "data" / "dp4_small.xplane.pb"


def test_union_merges_overlaps():
    iv = [(5, 7), (0, 2), (1, 3), (7, 9), (10, 11)]
    assert trace._union(iv) == [(0, 3), (5, 9), (10, 11)]


def test_minus_counts_what_lies_outside():
    a = [(0, 10), (20, 30)]
    b = [(2, 4), (8, 22), (25, 26)]
    assert trace._minus(a, b) == 2 + 4 + 3 + 4
    assert trace._minus(a, []) == 20
    assert trace._minus(a, [(-5, 40)]) == 0


def test_recorded_trace_of_four_chips():
    s = trace.reduce_trace(RECORDED)
    assert s.n_devices == 4
    # no op holds another here, so busy time is the ops' time
    assert s.busy_s == pytest.approx(sum(s.op_s.values()) / 4, rel=1e-9)
    assert 0 < s.busy_s < s.window_s
    # each exchange ran in a program of its own: all of it is exposed
    coll = sum(v for k, v in s.op_s.items() if k.endswith(" all-reduce"))
    assert coll > 0
    assert s.exposed_collective_s == pytest.approx(coll / 4, rel=1e-9)
    # the device clock of this recording runs ~0.9 ms behind the host's,
    # so the first step falls before the window opens: the other steps
    # each ran the matmul and the exchange program once
    assert sorted(s.module_n.values()) == [record_trace.STEPS - 1] * 2
    # the chips idle through each host sleep, and the gap says so
    longest = s.idle_gaps[:record_trace.STEPS - 1]
    assert [n for n, _ in longest] == ["chipbench.sleep"] * len(longest)
    assert all(g >= record_trace.SLEEP_S for _, g in longest)


def test_idle_gaps_are_named_by_the_innermost_host_span():
    busy = [(10, 20), (50, 60)]
    spans = [(0, 100, trace.WINDOW_SPAN), (0, 100, "chipbench.point"),
             (25, 45, "chipbench.draw")]
    gaps = trace._idle_gaps(busy, 0, 100, spans, 10)
    assert [g[0] for g in gaps] == ["chipbench.point",  # 60..100
                                    "chipbench.draw",   # 20..50
                                    "chipbench.point"]  # 0..10
    assert [g[1] for g in gaps] == pytest.approx([40e-9, 30e-9, 10e-9])


def test_a_trace_without_a_chip_is_refused(tmp_path):
    import jax
    import jax.numpy as jnp
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
        jnp.ones((8, 8)).sum().block_until_ready()
    jax.profiler.stop_trace()
    with pytest.raises(ValueError, match="no /device:TPU plane"):
        trace.reduce_trace(tmp_path)
