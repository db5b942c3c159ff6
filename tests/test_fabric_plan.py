"""The pallas engine's operand plan (``fabric_pallas._build_plan``).

A plan holds what an exchange's structure fixes — each scan stage's
groups, depth buckets and slots, the finish groupings — and nothing a
ready table moves; every call composes its own merge order into it.  So
a plan built on one point and composed with another gives exactly the
operands a fresh build gives on the latter, and the engine's results
stay bit-for-bit the reference fabric's under x64.  The plan is kept per
structure (``plan_stats()``), apart from the memos that could answer a
point (``memo_stats()``).
"""

import dataclasses

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from _engines import grid_items  # noqa: E402
from repro import compat  # noqa: E402
from repro.core import fabric_pallas as fp  # noqa: E402
from repro.core import simulator as sim  # noqa: E402


def _ready(n_threads: int, seed: int, theta: int = 4) -> np.ndarray:
    rng = np.random.default_rng(seed)
    return np.cumsum(rng.uniform(0.0, 5e-6, size=(n_threads, theta)),
                     axis=1)


# zero increments: partitions (and threads) ready at the same instant,
# so the merge breaks ties by flow-major position
TIES = np.cumsum([[1e-6, 0.0, 0.0, 2e-6], [1e-6, 0.0, 1e-6, 0.0]], axis=1)


def _pt(ready, **kw) -> dict:
    p = dict(approach="part", dims=(4, 4, 4), periodic=True, theta=4,
             n_threads=2, local_shape=(16, 16, 16), bytes_per_cell=8.0,
             halo_width=1, n_vcis=2, aggr_bytes=0.0, ready=ready)
    p.update(kw)
    return p


# open grids: ranks on a face have fewer neighbours and, with per-dimension
# aggregation, flows of different lengths, so stages have jagged depths;
# on the 4-D grid one stage has more than MAX_EXACT_DEPTHS of them
OPEN3 = dict(dims=(4, 3, 2), periodic=False, local_shape=(64, 16, 4),
             aggr_bytes=4096.0)
OPEN4 = dict(dims=(3, 3, 3, 3), periodic=False, local_shape=(32, 16, 8, 4),
             aggr_bytes=8192.0)

# point lists A (the plan's) and B (composed with A's plan)
CASES = {
    "torus_4x4x4": ([_pt(_ready(2, 1))], [_pt(_ready(2, 2))]),
    "open_3d_jagged": ([_pt(_ready(2, 3), **OPEN3)],
                       [_pt(_ready(2, 4), **OPEN3)]),
    "open_4d_pow2_classes": ([_pt(_ready(2, 5), **OPEN4)],
                             [_pt(_ready(2, 6), **OPEN4)]),
    "one_thread": ([_pt(_ready(1, 7), n_threads=1)],
                   [_pt(_ready(1, 8), n_threads=1)]),
    "ready_ties": ([_pt(_ready(2, 9))], [_pt(TIES)]),
    "two_item_batch": ([_pt(_ready(2, 10)), _pt(_ready(2, 11))],
                       [_pt(_ready(2, 12)), _pt(TIES)]),
}


def _assert_same(got, want):
    """Two ``_assemble`` results hold the same structure and arrays
    (dtype, shape and every element); ``plan_reused`` aside."""
    (core_g, dyn_g, st_g, aux_g), (core_w, dyn_w, st_w, aux_w) = got, want
    assert core_g == core_w
    assert len(dyn_g) == len(dyn_w) and len(st_g) == len(st_w)
    for g, w in zip(dyn_g + st_g, dyn_w + st_w):
        assert g.dtype == w.dtype and g.shape == w.shape
        assert np.array_equal(g, w)
    assert aux_g.keys() == aux_w.keys()
    for k in aux_g.keys() - {"plan_reused"}:
        assert np.array_equal(np.asarray(aux_g[k]), np.asarray(aux_w[k])), k


@pytest.mark.parametrize("case", sorted(CASES))
def test_plan_is_independent_of_the_ready_table(case):
    a_pts, b_pts = CASES[case]
    with compat.x64_mode(True):
        items_a, fins_a = grid_items(a_pts)
        items, fins = grid_items(b_pts)
        assert all(f is not None for f in fins_a + fins)
        for mode_fins_a, mode_fins in ((fins_a, fins), (None, None)):
            fp.clear_memos()
            fp._assemble(items_a, mode_fins_a)
            got = fp._assemble(items, mode_fins)
            fp.clear_memos()
            want = fp._assemble(items, mode_fins)
            assert got[3]["plan_reused"] and not want[3]["plan_reused"]
            _assert_same(got, want)

        # both modes through A's plans, against the reference fabric
        fp.clear_memos()
        fp.transmit_grid_finish(items_a, fins_a)
        fp.transmit_grid(items_a)
        rank_tts = fp.transmit_grid_finish(items, fins)
        arrs = fp.transmit_grid(items)
        assert fp.plan_stats() == {"builds": 2, "reuses": 2}
        for p, it, tts, arr in zip(b_pts, items, rank_tts, arrs):
            want = sim.simulate_stencil(**p, engine="reference").rank_tts_s
            assert np.array_equal(tts, want)
            arrivals = np.empty_like(arr)
            arrivals[it.order] = arr
            res = sim._finish_prepared(sim._prepare_stencil(**p), arrivals)
            assert np.array_equal(res.rank_tts_s, want)


def test_plan_builds_once_per_topology():
    fp.clear_memos()
    n = 3
    for k in range(n):
        sim.simulate_stencil_grid([_pt(_ready(2, 20 + k))], engine="pallas")
    assert fp.plan_stats() == {"builds": 1, "reuses": n - 1}
    sim.simulate_stencil_grid([_pt(_ready(2, 30), dims=(4, 4, 2))],
                              engine="pallas")
    assert fp.plan_stats() == {"builds": 2, "reuses": n - 1}
    # an item without a plan key builds for the call and keeps nothing
    items, fins = grid_items([_pt(_ready(2, 31))])
    items = [dataclasses.replace(it, plan_key=None) for it in items]
    for _ in range(2):
        assert not fp._assemble(items, fins)[3]["plan_reused"]
    assert fp.plan_stats() == {"builds": 4, "reuses": n - 1}
    # a plan answers no point: it is not among the memos
    assert sorted(fp.memo_stats()) == ["arrivals", "grid_ops"]
