"""Differential suite for the compiled fabric engine (``engine="pallas"``).

The three grouped queue scans (VCI banks, NIC serialization, wire
links) run as one fused Pallas program, so every driver and approach is
diffed against the vectorized engine — and therefore the scalar
``ReferenceFabric`` — under both precision modes:

* ``JAX_ENABLE_X64``: bit-for-bit, no tolerance.  The kernel consumes
  host-precomputed float64 cost columns built with the exact operation
  order of the scalar engine, so the in-kernel recurrence
  ``t = max(r, t_prev) + c`` is the only arithmetic left to match.
* float32: tolerance-gated (~1e-4 relative); structural counters stay
  exact.

Driver invocation and comparison fields come from the shared table in
``tests/_engines.py``.  On CPU CI the kernel runs in interpret mode
(the shared ``REPRO_PALLAS_INTERPRET`` resolver in
:mod:`repro.kernels.runtime`), which executes the same program through
XLA — the differential guarantees carry to compiled TPU runs because
the operand protocol and program are identical; their v5e compile
is pinned by ``tests/test_tpu_compile.py``.  Lane and depth tiling of
the scan kernels is diffed against the single-tile layout.  The 4096-
and 32768-rank smoke tiers must finish within budget and reproduce the
committed baseline; the full XXL grid is ``slow``-marked.
"""

import json
import pathlib
import time

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from _engines import (APPROACHES, F32_RTOL, PIPELINED,  # noqa: E402
                      assert_engines_agree, assert_results_close,
                      forced_scans as forced, grid_items, ready)
from repro import compat  # noqa: E402
from repro.core import fabric as fb  # noqa: E402
from repro.core import fabric_pallas as fp  # noqa: E402
from repro.core import perfmodel as pm  # noqa: E402
from repro.core import simulator as sim  # noqa: E402
from repro.kernels import runtime as rt  # noqa: E402

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # env without hypothesis: deterministic fallback
    from _hypo import given, settings, st

PV = ("pallas", "vector")


class TestX64BitForBit:
    """Under x64 the fused kernel equals the NumPy engines exactly."""

    @pytest.mark.parametrize("ap", APPROACHES)
    def test_stencil_all_approaches(self, ap):
        with compat.x64_mode(True):
            for dims, n, theta, vcis, seed in (
                    ((2, 2), 1, 2, 1, 0), ((2, 2, 2), 2, 4, 2, 1)):
                assert_engines_agree(
                    "stencil", ap, engines=PV, forced=True, dims=dims,
                    theta=theta, n_threads=n, n_vcis=vcis,
                    local_shape=(24, 8, 4)[:len(dims)],
                    ready=ready(n, theta, seed))

    @pytest.mark.parametrize("ap", APPROACHES)
    def test_stencil_deployment_shapes(self, ap):
        """A non-periodic torus with a two-deep halo and aggregation:
        boundary ranks send fewer faces, and the faces' sizes and the
        aggregation threshold set every flow's message count."""
        with compat.x64_mode(True):
            assert_engines_agree(
                "stencil", ap, engines=PV, forced=True, dims=(3, 2, 2),
                periodic=False, theta=4, n_threads=2, n_vcis=2,
                local_shape=(24, 8, 4), halo_width=2, aggr_bytes=4096.0,
                ready=ready(2, 4, 17))

    @pytest.mark.parametrize("ap", APPROACHES)
    def test_halo_all_approaches(self, ap):
        with compat.x64_mode(True):
            assert_engines_agree(
                "halo", ap, engines=PV, forced=True, n_ranks=4, theta=4,
                part_bytes=4096, n_threads=2, n_vcis=2,
                ready=ready(2, 4, 3))

    @pytest.mark.parametrize("ap", APPROACHES)
    def test_oneshot_and_steady(self, ap):
        """Warm-state drivers: the steady-state loop re-enters the
        kernel with carried VCI/NIC/wire busy-until vectors."""
        with compat.x64_mode(True):
            kw = dict(n_threads=2, theta=4, part_bytes=2048, n_vcis=2,
                      ready=ready(2, 4, 5))
            assert_engines_agree("oneshot", ap, engines=PV, forced=True,
                                 **kw)
            assert_engines_agree("steady", ap, engines=PV, forced=True,
                                 n_iters=3, **kw)

    @pytest.mark.parametrize("ap", PIPELINED[:2])
    def test_imbalance(self, ap):
        with compat.x64_mode(True):
            assert_engines_agree(
                "imbalance", ap, engines=PV, forced=True, n_ranks=4,
                workload=pm.WORKLOADS["stencil"], theta=2,
                part_bytes=1 << 18, n_threads=2, n_vcis=2, seed=7)

    @given(ap=st.sampled_from(PIPELINED),
           dims=st.sampled_from([(3, 2), (2, 2, 2)]),
           theta=st.sampled_from([2, 4]), seed=st.integers(0, 2))
    @settings(max_examples=10, deadline=None)
    def test_stencil_randomized(self, ap, dims, theta, seed):
        """Randomized scenarios through the fused kernel (forced on)."""
        with compat.x64_mode(True):
            assert_engines_agree(
                "stencil", ap, engines=PV, forced=True, dims=dims,
                theta=theta, n_threads=2, n_vcis=2,
                local_shape=(24, 8, 4)[:len(dims)],
                ready=ready(2, theta, seed))

    def test_wide_batch_takes_kernel_unforced(self):
        """A 512-rank torus engages the fused kernel through the normal
        adaptive routing (no forcing) and still matches exactly."""
        with compat.x64_mode(True):
            assert_engines_agree(
                "stencil", "part", engines=PV, dims=(8, 8, 8), theta=4,
                n_threads=2, n_vcis=2, local_shape=(64, 64, 64))

    def test_narrow_batch_takes_scalar_fallback(self, monkeypatch):
        """Below the adaptive cutoffs PallasFabric must not launch a
        kernel: with kernel construction sabotaged, a tiny scenario
        still completes (via the inherited scalar path) and matches."""
        def _boom(_meta):
            raise AssertionError("kernel launched for a narrow batch")
        monkeypatch.setattr(fp, "_build_call", _boom)
        with compat.x64_mode(True):
            assert_engines_agree(
                "oneshot", "part", engines=PV, n_threads=1, theta=2,
                part_bytes=64, n_vcis=1, ready=ready(1, 2, 9))


class TestFloat32Tolerance:
    """Without x64 the engine is tolerance-gated, counters stay exact."""

    @pytest.mark.parametrize("ap", APPROACHES)
    def test_stencil(self, ap):
        kw = dict(dims=(2, 2, 2), theta=4, n_threads=2, n_vcis=2,
                  local_shape=(24, 8, 4), ready=ready(2, 4, 11))
        with compat.x64_mode(False), forced():
            rp = sim.simulate_stencil(ap, engine="pallas", **kw)
        rv = sim.simulate_stencil(ap, engine="vector", **kw)
        assert rp.sent_per_rank == rv.sent_per_rank
        np.testing.assert_allclose(rp.rank_tts_s, rv.rank_tts_s,
                                   rtol=F32_RTOL)
        assert_results_close(rp, rv)

    @pytest.mark.parametrize("ap", APPROACHES)
    def test_halo(self, ap):
        kw = dict(n_ranks=4, theta=4, part_bytes=4096, n_threads=2,
                  n_vcis=2, ready=ready(2, 4, 3))
        with compat.x64_mode(False), forced():
            rp = sim.simulate_halo(ap, engine="pallas", **kw)
        rv = sim.simulate_halo(ap, engine="vector", **kw)
        np.testing.assert_allclose(rp.rank_tts_s, rv.rank_tts_s,
                                   rtol=F32_RTOL)
        assert_results_close(rp, rv)

    def test_x64_guard_reports_mode(self):
        with compat.x64_mode(True):
            assert fp.x64_enabled()
        with compat.x64_mode(False):
            assert not fp.x64_enabled()


class TestGridPath:
    """The fused whole-grid path vs the per-point engines."""

    POINTS = [dict(approach=ap, dims=d, theta=4, n_threads=2, n_vcis=2,
                   local_shape=(64, 64, 64), bytes_per_cell=8.0)
              for ap in ("pt2pt_single", "part", "pt2pt_many")
              for d in ((2, 2, 2), (3, 2, 2))]

    def test_grid_matches_per_point_x64(self):
        with compat.x64_mode(True):
            results = sim.simulate_stencil_grid(self.POINTS,
                                                engine="pallas")
            for p, r in zip(self.POINTS, results):
                rv = sim.simulate_stencil(engine="vector", **p)
                assert r is not None
                assert r.rank_tts_s == rv.rank_tts_s
                assert r.sent_per_rank == rv.sent_per_rank
                assert r.face_bytes == rv.face_bytes
                assert r.n_messages == rv.n_messages
                assert r.time_s == rv.time_s and r.tts_s == rv.tts_s

    @pytest.mark.parametrize("dims", [(2, 2, 2), (3, 2, 2), (4, 4, 2)],
                             ids=lambda d: "x".join(map(str, d)))
    @pytest.mark.parametrize("ap", PIPELINED)
    def test_grid_point_matches_vector_x64(self, ap, dims):
        """One point a call, with its own ready table, as the fabric
        cell sends them: the grid path's finish equals the per-point
        vector engine."""
        p = dict(self.POINTS[0], approach=ap, dims=dims,
                 ready=ready(2, 4, sum(dims)))
        with compat.x64_mode(True):
            r = sim.simulate_stencil_grid([p], engine="pallas")[0]
            rv = sim.simulate_stencil(engine="vector", **p)
        assert r is not None
        assert r.rank_tts_s == rv.rank_tts_s
        assert r.sent_per_rank == rv.sent_per_rank
        assert r.n_messages == rv.n_messages
        assert r.time_s == rv.time_s and r.tts_s == rv.tts_s

    def test_dependent_traffic_falls_back_to_none(self):
        with compat.x64_mode(True):
            pts = [dict(self.POINTS[0], approach="rma_many_passive")]
            assert sim.simulate_stencil_grid(pts, engine="pallas") \
                == [None]

    @pytest.mark.parametrize("ap", PIPELINED)
    def test_arrivals_mode_matches_vector(self, ap):
        """The in-kernel arrivals output (the non-affine-finish escape
        hatch) equals a cold vector fabric's arrivals bit-for-bit."""
        pts = [dict(self.POINTS[0], approach=ap, dims=d)
               for d in ((2, 2, 2), (3, 2, 2))]
        with compat.x64_mode(True):
            items, _ = grid_items(pts)
            got = fp.transmit_grid(items)
        for it, g in zip(items, got):
            vec = fb.Fabric(it.cfg, it.n_vcis, n_ranks=it.n_ranks)
            want = vec.transmit_arrays(it.t_ready, it.nbytes, it.vci,
                                       it.thread, it.put, it.am_copy,
                                       it.src, it.dst)
            assert np.array_equal(np.asarray(g), want)

    def test_tiled_grid_matches_single_tile(self, monkeypatch):
        """Tiny VMEM and depth budgets split every scan bucket into
        several lane tiles and carry the recurrence across depth tiles;
        rank finish times and arrivals stay bit-identical to the
        default tiling."""
        pts = [dict(self.POINTS[0], approach=ap, dims=(8, 8, 4))
               for ap in ("pt2pt_single", "part")]
        with compat.x64_mode(True):
            items, fins = grid_items(pts)
            fp.clear_memos()
            wide = fp.transmit_grid_finish(items, fins)
            wide_arr = fp.transmit_grid(items)
            monkeypatch.setattr(fp, "VMEM_BLOCK_BUDGET", 1)
            monkeypatch.setattr(fp, "MAX_TILE_DEPTH", 3)
            assert fp._tiles(7, 3000) == (3, 9, 8, 24)
            fp.clear_memos()
            tiled = fp.transmit_grid_finish(items, fins)
            tiled_arr = fp.transmit_grid(items)
            fp.clear_memos()
            for a, b in zip(wide + wide_arr, tiled + tiled_arr):
                assert np.array_equal(a, b)

    def test_run_records_batched(self):
        """The experiments layer's batched pallas records equal the
        per-point runner's (exact under x64, tolerance in f32)."""
        from repro.experiments.engine import (run_records_batched,
                                              run_stencil)
        batched = run_records_batched("stencil", self.POINTS,
                                      engine="pallas")
        assert batched is not None and all(m is not None for m in batched)
        for p, metrics in zip(self.POINTS, batched):
            ref = run_stencil(p, engine="vector")
            assert metrics["n_messages"] == ref["n_messages"]
            assert metrics["time_us"] == pytest.approx(
                ref["time_us"], rel=10 * F32_RTOL, abs=1e-9)

    def test_process_pool_refused_for_device_engines(self):
        """A worker forked after this process touched the device could
        not reach it: jobs > 1 is for the NumPy engines only."""
        from repro.experiments.engine import run_records
        with pytest.raises(ValueError, match="jobs=2"):
            run_records("stencil", self.POINTS, jobs=2, engine="pallas")

    def test_batched_path_declines_other_runners(self):
        from repro.experiments.engine import run_records_batched
        assert run_records_batched("halo", [], engine="pallas") is None
        assert run_records_batched("stencil", [], engine="vector") is None


# the XLA stage-scan engine's name, retired with its engine
RETIRED = "jax"


@pytest.mark.parametrize("entry", ["simulate_stencil",
                                   "simulate_stencil_grid", "run_records"])
def test_retired_engine_is_refused(entry):
    """The retired engine's name is refused at every entry."""
    from repro.experiments.engine import run_records
    p = dict(TestGridPath.POINTS[0])
    calls = {
        "simulate_stencil": lambda: sim.simulate_stencil(engine=RETIRED,
                                                         **p),
        "simulate_stencil_grid": lambda: sim.simulate_stencil_grid(
            [p], engine=RETIRED),
        "run_records": lambda: run_records("stencil", [p], engine=RETIRED),
    }
    with pytest.raises(ValueError, match=f"unknown (grid )?engine "
                                         f"'{RETIRED}'"):
        calls[entry]()


class TestMemos:
    """The host-side memos of the warm driver path and the plan key."""

    COLS = dict(src=np.array([0, 1, 0, 2, 1, 0]),
                dst=np.array([1, 2, 2, 0, 0, 1]),
                vci=np.array([0, 1, 1, 0, 0, 1]))

    def test_raw_layouts_reuse_by_key(self):
        fp.clear_memos()
        a = fp._raw_layouts(**self.COLS, n_vcis=2, n_ranks=3, key=("k",))
        assert fp._raw_layouts(**self.COLS, n_vcis=2, n_ranks=3,
                               key=("k",)) is a
        st = fp.layout_memo_stats()
        assert (st["hits"], st["misses"], st["size"]) == (1, 1, 1)
        fresh = fp._raw_layouts(**self.COLS, n_vcis=2, n_ranks=3, key=None)
        assert fresh is not a
        for la, lf in zip(a, fresh):
            assert all(np.array_equal(x, y) for x, y in zip(la, lf))
        assert fp.layout_memo_stats()["size"] == 1

    def test_plan_key_ignores_the_ready_table(self):
        p = dict(TestGridPath.POINTS[1])
        (a, b), _ = grid_items([dict(p, ready=ready(2, 4, 1)),
                                dict(p, ready=ready(2, 4, 2))])
        assert a.plan_key == b.plan_key
        assert a.key != b.key
        assert not np.array_equal(a.t_ready, b.t_ready)

    def test_clear_merge_memo_clears_layout_memo(self):
        fp._raw_layouts(**self.COLS, n_vcis=2, n_ranks=3, key=("c",))
        assert fp.layout_memo_stats()["size"] >= 1
        sim.clear_merge_memo()
        st = fp.layout_memo_stats()
        assert (st["size"], st["hits"], st["misses"]) == (0, 0, 0)


class TestInterpretResolver:
    """The shared lazy REPRO_PALLAS_INTERPRET resolver (satellite of
    the fused kernel: one switch for kernels/ops.py and the fabric)."""

    def test_force_interpret_round_trip(self):
        base = rt.interpret_mode()
        with rt.force_interpret(True):
            assert rt.interpret_mode() is True
            with rt.force_interpret(False):
                assert rt.interpret_mode() is False
            assert rt.interpret_mode() is True
        assert rt.interpret_mode() is base

    def test_default_follows_backend(self, monkeypatch):
        """Unset, the interpreter runs only on the CPU backend; the
        environment variable overrides either way."""
        monkeypatch.delenv("REPRO_PALLAS_INTERPRET", raising=False)
        assert rt.interpret_mode() is True  # this suite runs on the CPU
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert rt.interpret_mode() is False
        monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
        assert rt.interpret_mode() is True
        monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
        monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
        assert rt.interpret_mode() is False

    def test_x64_off_the_cpu_raises(self, monkeypatch):
        """Mosaic has no float64: under x64 on an accelerator backend the
        engine refuses at construction instead of running interpreted or
        silently in float32."""
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        with compat.x64_mode(True):
            with pytest.raises(RuntimeError, match="float64"):
                fp.PallasFabric(fb.DEFAULT_NET, 2, n_ranks=4)
        with compat.x64_mode(False):
            fp.PallasFabric(fb.DEFAULT_NET, 2, n_ranks=4)

    def test_kernel_matches_across_modes(self, forced_scans):
        """Interpret on/off must not change results (on CPU both
        resolve to the interpreted XLA path; on accelerators this
        diffs the compiled kernel against interpret)."""
        with compat.x64_mode(True):
            kw = dict(dims=(2, 2, 2), theta=4, n_threads=2, n_vcis=2,
                      local_shape=(24, 8, 4), ready=ready(2, 4, 13))
            with rt.force_interpret(True):
                fp.clear_memos()
                ri = sim.simulate_stencil("part", engine="pallas", **kw)
            fp.clear_memos()
            rv = sim.simulate_stencil("part", engine="vector", **kw)
            assert ri.rank_tts_s == rv.rank_tts_s
            assert ri.n_messages == rv.n_messages
            assert ri.time_s == rv.time_s and ri.tts_s == rv.tts_s


class TestWeakScalingXL:
    """Acceptance: the 4096-rank tier is tractable in tier-1."""

    def test_4096_rank_smoke_under_budget(self):
        from repro.experiments import SPECS, compare_to_baseline, run_spec
        spec = SPECS["weak_scaling_xl"]
        t0 = time.perf_counter()
        results = run_spec(spec, mode="smoke", engine="pallas")
        wall = time.perf_counter() - t0
        assert wall < 30.0, f"4096-rank smoke tier took {wall:.1f}s"
        assert any("dims=16x16x16" in k for k in results)
        baseline = json.loads(
            (pathlib.Path(__file__).resolve().parent.parent /
             "BENCH_scenarios.json").read_text())
        violations = compare_to_baseline(
            baseline, {"weak_scaling_xl": results})
        assert not violations, "\n".join(violations)


class TestWeakScalingXXL:
    """Acceptance: the 32768-rank tier is tractable in tier-1."""

    def test_32k_rank_smoke_under_budget(self):
        from repro.experiments import SPECS, compare_to_baseline, run_spec
        spec = SPECS["weak_scaling_xxl"]
        t0 = time.perf_counter()
        results = run_spec(spec, mode="smoke", engine="pallas")
        wall = time.perf_counter() - t0
        assert wall < 60.0, f"32768-rank smoke tier took {wall:.1f}s"
        assert any("dims=32x32x32" in k for k in results)
        baseline = json.loads(
            (pathlib.Path(__file__).resolve().parent.parent /
             "BENCH_scenarios.json").read_text())
        violations = compare_to_baseline(
            baseline, {"weak_scaling_xxl": results})
        assert not violations, "\n".join(violations)

    @pytest.mark.slow
    def test_32k_rank_full_grid_matches_jax(self):
        """Full XXL grid (12 records, ~6.3M wire messages) under x64:
        every record bit-identical to the committed baseline."""
        from repro.experiments import SPECS, run_spec
        from repro.experiments.engine import _CACHE
        spec = SPECS["weak_scaling_xxl"]
        with compat.x64_mode(True):
            _CACHE.clear()
            rp = run_spec(spec, mode="full", engine="pallas")
        want = json.loads(
            (pathlib.Path(__file__).resolve().parent.parent /
             "BENCH_scenarios.json").read_text()
        )["specs"]["weak_scaling_xxl"]["records"]
        assert set(rp) == set(want) and len(rp) == 12
        for key in rp:
            for metric, val in rp[key].items():
                assert val == want[key][metric], (key, metric)


class TestWarmWaves:
    """The warm-state path: admission waves through one live fabric,
    each starting while the previous still holds VCIs, NICs and wires —
    the kernels' init vectors carry that state in and out."""

    @pytest.mark.parametrize("n_ranks,per_rank,n_vcis",
                             [(512, 16, 4), (1024, 8, 2), (2048, 4, 1)])
    def test_waves_match_vector_bitwise(self, n_ranks, per_rank, n_vcis):
        from chip_smoke import wave_traffic
        cols = wave_traffic(seed=3, n_ranks=n_ranks, per_rank=per_rank)
        with compat.x64_mode(True):
            pal = fp.PallasFabric(fb.DEFAULT_NET, n_vcis, n_ranks=n_ranks)
            vec = fb.Fabric(fb.DEFAULT_NET, n_vcis, n_ranks=n_ranks)
            calls = fp._build_call.cache_info()
            shift = 0.0
            for _ in range(3):
                wave = dict(cols, t_ready=cols["t_ready"] + shift)
                ap, av = pal.advance(**wave), vec.advance(**wave)
                assert np.array_equal(ap, av)
                shift = 0.5 * float(av.max())
            after = fp._build_call.cache_info()
            assert after.hits + after.misses == calls.hits + calls.misses + 3
        assert pal.nic_free == vec.nic_free
        assert pal.vci_free == vec.vci_free
        assert pal.vci_last_thread == vec.vci_last_thread
        assert pal.wire_free == vec.wire_free
