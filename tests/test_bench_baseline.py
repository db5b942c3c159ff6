"""Golden-baseline regression suite: the committed ``BENCH_scenarios.json``
must stay reproducible by the sweep engine within its recorded
tolerances.

Tier-1 keeps this cheap: structural checks plus a 2-point smoke per spec
(first and last smoke-grid records).  The full-grid re-run is marked
``slow`` (CI runs the smoke diff separately via ``benchmarks.sweep
--smoke --check``).  Regenerate the baseline after an intentional
calibration change with ``python -m benchmarks.sweep --update
BENCH_scenarios.json``.
"""

import json
import pathlib

import pytest

from repro.experiments import (BASELINE_VERSION, SPECS, compare_to_baseline,
                               contention_crossover, load_disk_cache,
                               record_key, run_spec, run_specs,
                               save_disk_cache)
from repro.experiments import engine as engine_mod

BASELINE_PATH = pathlib.Path(__file__).resolve().parent.parent / \
    "BENCH_scenarios.json"


@pytest.fixture(scope="module")
def baseline():
    assert BASELINE_PATH.exists(), (
        "BENCH_scenarios.json missing; regenerate with"
        " python -m benchmarks.sweep --update BENCH_scenarios.json")
    return json.loads(BASELINE_PATH.read_text())


class TestBaselineDocument:
    def test_version_and_spec_coverage(self, baseline):
        assert baseline["version"] == BASELINE_VERSION
        assert set(baseline["specs"]) == set(SPECS)

    def test_full_grid_keys_match_baseline(self, baseline):
        """Every current full-grid point has a record and vice versa —
        spec edits must come with a baseline regeneration."""
        for name, spec in SPECS.items():
            want = {record_key(p) for p in spec.points("full")}
            have = set(baseline["specs"][name]["records"])
            assert have == want, f"{name}: baseline records out of date"

    def test_smoke_grids_are_subsets_of_full(self):
        for name, spec in SPECS.items():
            full = {record_key(p) for p in spec.points("full")}
            smoke = {record_key(p) for p in spec.points("smoke")}
            assert smoke <= full, f"{name}: smoke point not in full grid"
            assert smoke, f"{name}: empty smoke grid"

    def test_message_counts_are_exact(self, baseline):
        for name, bspec in baseline["specs"].items():
            assert bspec["tolerances"].get("n_messages") == 0.0, name


class TestTwoPointSmoke:
    """Tier-1: re-run each spec's (tiny) smoke grid — the whole grid is
    needed so derived gain metrics have their baseline-approach partner —
    and diff two records per spec against the committed baseline."""

    @pytest.mark.parametrize("name", sorted(SPECS))
    def test_spec_reproduces_baseline(self, name, baseline):
        spec = SPECS[name]
        results = run_spec(spec, mode="smoke")
        keys = sorted(record_key(p) for p in spec.points("smoke"))
        picked = {keys[0], keys[-1]}
        subset = {k: m for k, m in results.items() if k in picked}
        violations = compare_to_baseline(baseline, {name: subset})
        assert not violations, "\n".join(violations)


class TestContentionCrossover:
    """Acceptance: the Fig-5/Fig-6 crossover — part/many collapse vs
    single on one VCI and recover with 32 VCIs."""

    def test_smoke_reproduces_crossover(self):
        ratios = contention_crossover(
            {"fig6_vci": run_spec(SPECS["fig6_vci"], mode="smoke")})
        for ap in ("part", "pt2pt_many"):
            assert ratios[ap]["slowdown_at_1_vcis"] > 10.0
        assert ratios["pt2pt_many"]["slowdown_at_32_vcis"] < 1.5
        assert ratios["part"]["slowdown_at_32_vcis"] < 6.0
        # the crossover itself: VCIs recover an order of magnitude
        for ap in ("part", "pt2pt_many"):
            assert (ratios[ap]["slowdown_at_1_vcis"]
                    / ratios[ap]["slowdown_at_32_vcis"]) > 10.0

    def test_stencil_smoke_has_8_ranks_and_spread_faces(self):
        results = run_spec(SPECS["stencil3d"], mode="smoke")
        for key, metrics in results.items():
            assert "dims=2x2x2" in key
            assert metrics["face_bytes_max"] / metrics["face_bytes_min"] \
                >= 10.0


class TestSweepCliPartialUpdate:
    """`--update` with `--specs` must merge into the existing baseline,
    not rewrite it with only the selected specs' records."""

    @staticmethod
    def _sweep(*argv):
        import os
        import subprocess
        import sys
        root = BASELINE_PATH.parent
        env = dict(os.environ)
        env["PYTHONPATH"] = "src" + os.pathsep + env.get("PYTHONPATH", "")
        return subprocess.run(
            [sys.executable, "-m", "benchmarks.sweep", *argv],
            cwd=root, env=env, capture_output=True, text=True)

    def test_partial_update_keeps_other_specs(self, tmp_path):
        import shutil
        path = tmp_path / "baseline.json"
        shutil.copyfile(BASELINE_PATH, path)
        proc = self._sweep("--specs", "fig7_aggregation",
                           "--update", str(path))
        assert proc.returncode == 0, proc.stderr
        doc = json.loads(path.read_text())
        assert set(doc["specs"]) == set(SPECS)

    def test_partial_update_refuses_without_existing_baseline(self, tmp_path):
        proc = self._sweep("--specs", "fig7_aggregation",
                           "--update", str(tmp_path / "missing.json"))
        assert proc.returncode == 2
        assert "full --update" in proc.stderr
        assert not (tmp_path / "missing.json").exists()


class TestDiskCache:
    """The opt-in persistent run cache: a second process re-runs nothing."""

    def test_round_trip_seeds_process_cache(self, tmp_path):
        path = tmp_path / "cache.json"
        spec = SPECS["fig7_aggregation"]
        run_spec(spec, mode="smoke")
        before = dict(engine_mod._CACHE)
        save_disk_cache(str(path))
        engine_mod._CACHE.clear()
        try:
            assert load_disk_cache(str(path)) == len(before)
            assert engine_mod._CACHE == before
            # a fully-seeded cache means run_spec recomputes nothing:
            # poison one of the spec's own records and watch it flow
            # through untouched
            key = record_key(spec.points("smoke")[0])
            engine_mod._CACHE[(spec.runner, key, "vector")]["time_us"] = -1.0
            results = run_spec(spec, mode="smoke")
            assert results[key]["time_us"] == -1.0
        finally:
            # never leak poisoned records into later tests
            engine_mod._CACHE.clear()

    def test_save_is_atomic_crash_mid_write(self, tmp_path, monkeypatch):
        """A crash mid-save must leave the previous cache file intact
        (the document is written to a temp file and os.replace-d), so
        concurrent `sweep --jobs N --cache` runs can never truncate or
        corrupt each other's cache."""
        path = tmp_path / "cache.json"
        run_spec(SPECS["fig7_aggregation"], mode="smoke")
        written = save_disk_cache(str(path))
        assert written > 0
        before = path.read_text()

        def crash_mid_write(doc, f, **kw):
            f.write('{"baseline_version":')  # partial bytes, then die
            raise RuntimeError("simulated crash mid-write")

        monkeypatch.setattr(engine_mod.json, "dump", crash_mid_write)
        with pytest.raises(RuntimeError, match="mid-write"):
            save_disk_cache(str(path))
        monkeypatch.undo()
        assert path.read_text() == before  # old file byte-identical
        assert list(tmp_path.glob("*.tmp")) == []  # temp file cleaned up
        engine_mod._CACHE.clear()
        try:
            assert load_disk_cache(str(path)) == written
        finally:
            engine_mod._CACHE.clear()

    def test_malformed_cache_file_is_ignored_wholesale(self, tmp_path):
        bad = tmp_path / "bad.json"
        bad.write_text(json.dumps({
            "baseline_version": BASELINE_VERSION,
            "records": {"vector": {"oneshot": {
                "a": {"time_us": 1.0},
                "b": {"time_us": "not a number"}}}}}))
        snapshot = dict(engine_mod._CACHE)
        assert load_disk_cache(str(bad)) == 0
        assert engine_mod._CACHE == snapshot  # no partial seeding

    def test_version_mismatch_invalidates(self, tmp_path):
        path = tmp_path / "cache.json"
        path.write_text(json.dumps({"baseline_version": -1, "records": {
            "vector": {"oneshot": {"k": {"time_us": 1.0}}}}}))
        assert load_disk_cache(str(path)) == 0

    def test_unreadable_file_is_empty(self, tmp_path):
        assert load_disk_cache(str(tmp_path / "missing.json")) == 0
        bad = tmp_path / "bad.json"
        bad.write_text("{not json")
        assert load_disk_cache(str(bad)) == 0

    def test_cli_cache_flag(self, tmp_path):
        path = tmp_path / "cache.json"
        p1 = TestSweepCliPartialUpdate._sweep(
            "--smoke", "--specs", "fig7_aggregation", "--cache", str(path))
        assert p1.returncode == 0, p1.stderr
        assert path.exists()
        p2 = TestSweepCliPartialUpdate._sweep(
            "--smoke", "--specs", "fig7_aggregation", "--cache", str(path),
            "--check", str(BASELINE_PATH))
        assert p2.returncode == 0, p2.stderr
        assert "loaded" in p2.stderr and "cached records" in p2.stderr


class TestEngineThroughputBench:
    """--bench-engine: the committed BENCH_engine.json and its CI gate."""

    BENCH_PATH = BASELINE_PATH.parent / "BENCH_engine.json"

    def test_committed_document_shape(self):
        from benchmarks.sweep import (BENCH_ENGINES,
                                      BENCH_EXCLUDED_RUNNERS,
                                      BENCH_SPEC_ENGINES)
        doc = json.loads(self.BENCH_PATH.read_text())
        cells = {(e["spec"], e["engine"]) for e in doc["entries"]}
        for name, spec in SPECS.items():
            if spec.runner in BENCH_EXCLUDED_RUNNERS:
                assert (name, "vector") not in cells, (
                    f"{name} is bench-excluded; regenerate"
                    " BENCH_engine.json")
                continue
            allowed = BENCH_SPEC_ENGINES.get(name, BENCH_ENGINES)
            for engine in BENCH_ENGINES:
                if engine not in allowed:
                    assert (name, engine) not in cells, (
                        f"{name}/{engine} is engine-restricted;"
                        " regenerate BENCH_engine.json")
                    continue
                assert (name, engine) in cells, (name, engine)
        assert doc.get("jax_enable_x64") is True, (
            "committed BENCH_engine.json must be measured under"
            " JAX_ENABLE_X64=1 (the CI pallas gate's precision mode)")
        speedup = doc["totals"]["speedup_vector_vs_reference"]
        assert speedup >= 5.0, (
            f"vectorized engine only {speedup:.1f}x faster than the scalar"
            " oracle on the full grids; regenerate BENCH_engine.json via"
            " JAX_ENABLE_X64=1 python -m benchmarks.sweep --bench-engine"
            " --full --bench-out BENCH_engine.json")

    @staticmethod
    def _doc(vector_eps, reference_eps, events=50000):
        return {"entries": [
            {"spec": "s", "engine": "vector", "mode": "full",
             "events": events, "events_per_sec": vector_eps},
            {"spec": "s", "engine": "reference", "mode": "full",
             "events": events, "events_per_sec": reference_eps}]}

    def test_regression_check_is_relative_to_reference(self):
        """The gate compares the same-machine vector/reference ratio, so
        uniformly slower hardware never trips it."""
        from benchmarks.sweep import check_bench_regression
        ref = self._doc(1e6, 1e5)                      # committed: 10x
        assert check_bench_regression(self._doc(6e5, 1e5), ref) == []  # 6x
        assert check_bench_regression(self._doc(5e5, 5e4), ref) == []  # 2x-
        #                              slower machine, same 10x ratio ^
        slow = self._doc(4e5, 1e5)                     # 4x: >2x ratio drop
        assert len(check_bench_regression(slow, ref)) == 1
        tiny = self._doc(1e6, 1e5, events=10)          # noise floor
        assert check_bench_regression(slow, tiny) == []


@pytest.mark.slow
class TestFullGrid:
    def test_full_grid_reproduces_baseline(self, baseline):
        results = run_specs(list(SPECS.values()), mode="full")
        violations = compare_to_baseline(baseline, results)
        assert not violations, "\n".join(violations)

    def test_full_grid_reference_engine_matches_too(self, baseline):
        """The scalar oracle reproduces the same committed records."""
        results = run_specs(list(SPECS.values()), mode="full",
                            engine="reference")
        violations = compare_to_baseline(baseline, results)
        assert not violations, "\n".join(violations)
