"""Sweep CLI: run the declarative experiment specs, emit/check baselines.

  python -m benchmarks.sweep --smoke                  # reduced grids (CI)
  python -m benchmarks.sweep --full --jobs 4          # full grids, 4 procs
  python -m benchmarks.sweep --smoke --check BENCH_scenarios.json
  python -m benchmarks.sweep --update BENCH_scenarios.json   # regenerate
  python -m benchmarks.sweep --full --engine reference       # scalar oracle
  python -m benchmarks.sweep --full --engine pallas  # compiled engine
  python -m benchmarks.sweep --full --cache .sweep_cache.json  # reuse runs
  python -m benchmarks.sweep --bench-engine --smoke \\
      --bench-engines vector,reference \\
      --bench-check BENCH_engine.json                 # throughput gate (CI)
  JAX_ENABLE_X64=1 python -m benchmarks.sweep --bench-engine --smoke \\
      --bench-engines vector,pallas \\
      --bench-check BENCH_engine.json                 # pallas gate (CI)
  JAX_ENABLE_X64=1 python -m benchmarks.sweep --bench-engine --full \\
      --bench-out BENCH_engine.json   # regenerate throughput (x64: the
      #                                 pallas cells must match the CI
      #                                 gate's precision mode)
  python -m benchmarks.sweep --profile --specs weak_scaling  # cProfile top-N

``--check`` diffs the fresh results against a committed golden baseline
and exits non-zero on any out-of-tolerance metric; ``--update`` runs the
full grids and rewrites the baseline document.  ``--out`` dumps the raw
results as JSON (CI uploads it as an artifact).  ``--engine`` selects the
fabric implementation (vectorized by default; ``reference`` is the scalar
oracle) — both must reproduce the same baseline.  ``--cache`` names an
opt-in persistent JSON run cache (keyed by engine + runner + record key +
baseline version), so repeated ``--check`` runs after unrelated edits
re-run nothing.

``--bench-engine`` measures engine throughput instead of checking
records (it cannot be combined with the record-checking flags): per spec
and per engine (``--bench-engines`` restricts the set) it reports wall
time and events/sec (wire messages simulated per second of engine wall
time) and writes the document to ``--bench-out`` when given.
``--bench-check`` gates against a committed ``BENCH_engine.json``: the
compared quantities are the per-spec speedups of each ``BENCH_PAIRS``
engine pair (vector-vs-reference and pallas-vs-vector) —
both engines of a pair are measured in the same run on the same
machine, so the ratio is hardware-independent — and a >2x relative
slowdown fails; only pairs whose engines were both measured in this run
are gated.  ``BENCH_SPEC_ENGINES`` restricts scalar-intractable grids
(the 32k-rank XXL sweep) to the compiled engine.  The Fig-5/Fig-6
contention crossover (part/many ~ single at 32 VCIs, >> single at 1 VCI)
is printed whenever the fig6 spec ran.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

from repro.experiments import (SPECS, compare_to_baseline,
                               contention_crossover, load_disk_cache,
                               make_baseline, run_spec, run_specs,
                               save_disk_cache)
from repro.experiments import engine as _engine_mod
from repro.runtime.compile_cache import enable_compile_cache

BENCH_ENGINES = ("vector", "reference", "pallas")
BENCH_VERSION = 1
# Engine pairs whose same-job throughput ratio the regression gate
# tracks: (numerator, denominator).  Both engines of a pair run in the
# same process on the same machine, so the ratio is hardware-independent.
BENCH_PAIRS = (("vector", "reference"), ("pallas", "vector"))
# Specs whose grids are tractable only on a subset of the engines: the
# 32k-rank XXL sweep takes minutes per record on the scalar/NumPy
# engines, so its bench cells are measured on the compiled engine
# only.  Pair speedups are summed over the specs where BOTH engines of
# the pair have cells, so a skipped cell narrows a pair's coverage
# instead of skewing its ratio.
BENCH_SPEC_ENGINES = {"weak_scaling_xxl": ("pallas",)}
# Runners excluded from --bench-engine: the autotune runner re-simulates
# a whole candidate grid of mostly tiny (scalar-path) scenarios per
# record, so its wall time measures planner overhead, not fabric
# throughput — including it would dilute the vector/reference ratio the
# regression gate tracks.  The serving runner's wall time is likewise
# dominated by the Python-side admission loop (per-wave intent building
# and heap scheduling), not the fabric scans; the fault-injection
# runners (retransmission rounds, re-agreement epochs, faulty+clean
# serving pairs) are orchestration-bound the same way, and the IR
# runner's time goes to pass-pipeline guard simulations, not one scan.
BENCH_EXCLUDED_RUNNERS = ("autotune", "serving", "faulty", "membership",
                          "servingfaults", "ir", "recovery")
# Grids below this many simulated wire messages finish in a handful of
# milliseconds, where the vector/reference ratio is timer noise (and the
# adaptive routing sends them down the scalar path anyway, pinning the
# true ratio near 1x) — the regression gate only considers specs wide
# enough for the staged scans to matter.
BENCH_MIN_EVENTS = 5000
BENCH_REGRESSION_FACTOR = 2.0


def _parse_args(argv):
    ap = argparse.ArgumentParser(
        prog="python -m benchmarks.sweep", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--list", action="store_true",
                    help="print every registered spec with its runner and"
                         " one-line description, then exit")
    ap.add_argument("--smoke", action="store_true",
                    help="run the reduced smoke grids (default)")
    ap.add_argument("--full", action="store_true",
                    help="run the full grids")
    ap.add_argument("--specs", default="",
                    help="comma-separated spec names (default: all)")
    ap.add_argument("--jobs", type=int, default=1,
                    help="process-pool width for scenario runs")
    ap.add_argument("--engine", default="vector",
                    choices=("vector", "reference", "pallas"),
                    help="fabric engine (vector = batched NumPy,"
                         " reference = scalar oracle, pallas ="
                         " fused single-kernel pipeline)")
    ap.add_argument("--cache", default="",
                    help="persistent JSON run cache: load before running,"
                         " save after (opt-in)")
    ap.add_argument("--out", default="",
                    help="write raw results JSON to this path")
    ap.add_argument("--check", default="",
                    help="baseline JSON to diff against (exit 1 on drift)")
    ap.add_argument("--update", default="",
                    help="run full grids and (re)write this baseline JSON")
    ap.add_argument("--bench-engine", action="store_true",
                    help="measure engine throughput (events/sec + wall time"
                         " per spec and engine) instead of records")
    ap.add_argument("--bench-engines", default=",".join(BENCH_ENGINES),
                    help="comma-separated engines to measure with"
                         " --bench-engine (CI steps restrict this so the"
                         " vector/reference and pallas/vector gates"
                         " each measure only their own pair)")
    ap.add_argument("--bench-out", default="",
                    help="write the throughput document to this path"
                         " (omit to measure/check without writing)")
    ap.add_argument("--bench-check", default="",
                    help="committed BENCH_engine.json to gate against"
                         " (exit 1 on >2x events/sec regression)")
    ap.add_argument("--profile", action="store_true",
                    help="run the selected specs under cProfile and print"
                         " the hottest functions")
    ap.add_argument("--profile-top", type=int, default=20,
                    help="rows of cProfile output with --profile")
    return ap.parse_args(argv)


def _select_specs(args):
    if args.specs:
        names = [n.strip() for n in args.specs.split(",") if n.strip()]
        unknown = [n for n in names if n not in SPECS]
        if unknown:
            print(f"unknown specs {unknown}; have {sorted(SPECS)}",
                  file=sys.stderr)
            return None
        return [SPECS[n] for n in names]
    return list(SPECS.values())


def _bench_entry(spec, mode: str, engine: str, repeats: int = 3) -> dict:
    """Measure one (spec, engine, mode) cell: wall time + events/sec.

    Best of ``repeats`` uncached runs — scheduler noise only ever slows
    a run down, so the minimum is the stable estimator the 2x regression
    gate needs.
    """
    wall = float("inf")
    for _ in range(repeats):
        _engine_mod._CACHE.clear()  # measure real runs, not cache hits
        t0 = time.perf_counter()
        records = run_spec(spec, mode=mode, engine=engine)
        wall = min(wall, time.perf_counter() - t0)
    events = sum(m.get("n_messages", 0.0) for m in records.values())
    return {
        "spec": spec.name, "engine": engine, "mode": mode,
        "records": len(records), "events": int(events),
        "wall_s": wall,
        "events_per_sec": events / wall if wall > 0 else 0.0,
    }


def run_bench_engine(specs, mode: str,
                     engines=BENCH_ENGINES) -> dict:
    """Throughput document: every (spec, engine) cell.

    Smoke runs measure the smoke grids only (the CI gate); full runs
    measure both modes so the committed document carries reference
    entries for either kind of later check.  Totals (and the printed
    speedups) are over the full-grid entries when present.
    """
    modes = ("smoke",) if mode == "smoke" else ("smoke", "full")
    entries = []
    for m in modes:
        for engine in engines:
            for spec in specs:
                allowed = BENCH_SPEC_ENGINES.get(spec.name, BENCH_ENGINES)
                if engine not in allowed:
                    print(f"# bench {spec.name:18s} {engine:9s} {m:5s} "
                          f"   skipped (engines: {', '.join(allowed)})")
                    continue
                e = _bench_entry(spec, m, engine)
                entries.append(e)
                print(f"# bench {e['spec']:18s} {engine:9s} {m:5s} "
                      f"{e['wall_s'] * 1e3:9.1f} ms  {e['events']:8d} events"
                      f"  {e['events_per_sec'] / 1e3:9.1f} kev/s")
    totals = {}
    total_mode = modes[-1]
    cells = {(e["spec"], e["engine"]): e for e in entries
             if e["mode"] == total_mode}
    for engine in engines:
        es = [e for e in entries
              if e["engine"] == engine and e["mode"] == total_mode]
        totals[engine] = {"wall_s": sum(e["wall_s"] for e in es),
                          "events": sum(e["events"] for e in es)}
    for num, den in BENCH_PAIRS:
        # sum over the specs both engines of the pair measured, so a
        # BENCH_SPEC_ENGINES skip narrows coverage without skewing the
        # ratio (per-engine totals above may span different spec sets)
        common = [s.name for s in specs
                  if (s.name, num) in cells and (s.name, den) in cells]
        num_wall = sum(cells[(s, num)]["wall_s"] for s in common)
        den_wall = sum(cells[(s, den)]["wall_s"] for s in common)
        if not common or num_wall <= 0:
            continue
        speedup = den_wall / num_wall
        totals[f"speedup_{num}_vs_{den}"] = speedup
        print(f"# bench total ({total_mode}, {len(common)} specs): {den}"
              f" {den_wall:.3f}s vs {num}"
              f" {num_wall:.3f}s ({speedup:.1f}x)")
    _engine_mod._CACHE.clear()  # leave no half-measured state behind
    doc = {"version": BENCH_VERSION, "mode": mode, "entries": entries,
           "totals": totals}
    if "pallas" in engines:
        # record the precision mode: pallas float64 vs float32
        # throughput differs, so a gate should compare like against like
        # (the committed document and the CI compiled-engine gate both
        # run under JAX_ENABLE_X64=1)
        from repro.compat import x64_enabled
        doc["jax_enable_x64"] = x64_enabled()
    return doc


def _speedup_by_spec(doc: dict, mode: str, num: str = "vector",
                     den: str = "reference") -> dict:
    """Per-spec ``num``-vs-``den`` events/sec ratio for one mode."""
    cells = {(e["spec"], e["engine"]): e for e in doc.get("entries", [])
             if e.get("mode") == mode}
    out = {}
    for (spec, engine), e in cells.items():
        ref = cells.get((spec, den))
        if engine != num or ref is None \
                or min(e["events"], ref["events"]) < BENCH_MIN_EVENTS \
                or ref["events_per_sec"] <= 0:
            continue
        out[spec] = e["events_per_sec"] / ref["events_per_sec"]
    return out


def check_bench_regression(doc: dict, ref: dict) -> list:
    """>2x regressions of any engine pair's per-spec speedup.

    Both documents carry each spec's throughput for the engines of a
    :data:`BENCH_PAIRS` pair measured on the same machine in the same
    run, so the compared quantity — the pair's events-per-second
    ratio — is hardware-independent: a slower CI runner slows both
    engines alike, while an engine code regression shows up directly.
    A pair is only gated when the fresh document measured both of its
    engines (CI's vector/reference and pallas/vector steps each restrict
    ``--bench-engines`` to their own pair); specs under
    ``BENCH_MIN_EVENTS`` events are timer noise and exempt.
    """
    violations = []
    for num, den in BENCH_PAIRS:
        for mode in ("smoke", "full"):
            measured = _speedup_by_spec(doc, mode, num, den)
            committed = _speedup_by_spec(ref, mode, num, den)
            for spec, want in committed.items():
                have = measured.get(spec)
                if have is not None \
                        and have * BENCH_REGRESSION_FACTOR < want:
                    violations.append(
                        f"{spec}/{mode}: {num} engine {have:.2f}x the"
                        f" {den} engine vs committed {want:.2f}x"
                        f" (>{BENCH_REGRESSION_FACTOR}x relative slowdown)")
    return violations


def list_specs(specs) -> None:
    """One line per spec: name, runner, grid sizes, description."""
    for spec in specs:
        n_full = len(spec.points("full"))
        n_smoke = len(spec.points("smoke"))
        print(f"{spec.name:18s} {spec.runner:9s} "
              f"{n_full:4d} records ({n_smoke} smoke)  {spec.note}")


def main(argv=None) -> int:
    args = _parse_args(argv)
    mode = "full" if (args.full or args.update) else "smoke"
    specs = _select_specs(args)
    if specs is None:
        return 2
    enable_compile_cache()

    if args.list:
        list_specs(specs)
        return 0

    if args.bench_engine:
        clash = [f for f in ("update", "check", "out", "cache", "profile")
                 if getattr(args, f)]
        if clash:
            print("--bench-engine measures throughput only; it cannot be"
                  f" combined with {', '.join('--' + f for f in clash)}",
                  file=sys.stderr)
            return 2
        engines = tuple(e.strip() for e in args.bench_engines.split(",")
                        if e.strip())
        unknown = [e for e in engines if e not in BENCH_ENGINES]
        if unknown:
            print(f"unknown --bench-engines {unknown};"
                  f" have {list(BENCH_ENGINES)}", file=sys.stderr)
            return 2
        skipped = [s.name for s in specs
                   if s.runner in BENCH_EXCLUDED_RUNNERS]
        if skipped:
            print(f"# bench excludes {', '.join(skipped)} (runner wall time"
                  " measures orchestration overhead, not fabric throughput)",
                  file=sys.stderr)
        specs = [s for s in specs if s.runner not in BENCH_EXCLUDED_RUNNERS]
        doc = run_bench_engine(specs, mode, engines)
        if args.bench_check:
            try:
                with open(args.bench_check) as f:
                    ref = json.load(f)
            except (FileNotFoundError, json.JSONDecodeError) as e:
                print(f"# cannot read bench baseline {args.bench_check}:"
                      f" {e}", file=sys.stderr)
                return 2
            violations = check_bench_regression(doc, ref)
            if violations:
                print(f"# ENGINE THROUGHPUT REGRESSION"
                      f" ({len(violations)} violations):", file=sys.stderr)
                for v in violations:
                    print(f"#   {v}", file=sys.stderr)
                return 1
            print("# engine throughput check passed")
        if args.bench_out:  # never overwrite a committed doc implicitly
            with open(args.bench_out, "w") as f:
                json.dump(doc, f, indent=2, sort_keys=True)
                f.write("\n")
            print(f"# throughput document written to {args.bench_out}",
                  file=sys.stderr)
        return 0

    if args.cache:
        n = load_disk_cache(args.cache)
        if n:
            print(f"# loaded {n} cached records from {args.cache}",
                  file=sys.stderr)

    profiler = None
    if args.profile:
        import cProfile
        from repro.core import simulator as _sim
        _sim.clear_merge_memo()
        profiler = cProfile.Profile()
        t_cold = time.perf_counter()
        profiler.enable()
    results = run_specs(specs, mode=mode, jobs=args.jobs,
                        engine=args.engine)
    if profiler is not None:
        t_cold = time.perf_counter() - t_cold
        # second pass: the record cache is cleared so every scenario
        # really re-runs, but the hoisted merge-sort / stage-layout
        # memos are warm — the wall delta is what the memoization buys
        # repeated evaluations (benchmark repeats, steady re-runs)
        _engine_mod._CACHE.clear()
        t_warm = time.perf_counter()
        run_specs(specs, mode=mode, jobs=args.jobs, engine=args.engine)
        t_warm = time.perf_counter() - t_warm
        import pstats
        profiler.disable()
        stats = pstats.Stats(profiler, stream=sys.stderr)
        stats.strip_dirs().sort_stats("cumulative")
        print(f"# cProfile, top {args.profile_top} by cumulative time"
              " (both passes):", file=sys.stderr)
        stats.print_stats(args.profile_top)
        st = _sim.merge_memo_stats()
        print(f"# merge-layout memo: pass 1 (cold) {t_cold:.3f}s ->"
              f" pass 2 (warm) {t_warm:.3f}s;"
              f" {st['hits']} hits, {st['misses']} misses,"
              f" {st['evictions']} evictions,"
              f" {st['messages_saved']} message re-sorts avoided",
              file=sys.stderr)
        if args.engine == "pallas":
            from repro.core import fabric_pallas as _fp
            gst = _sim.grid_memo_stats()
            lst = _fp.layout_memo_stats()
            print(f"# grid-point memo: {gst['hits']} hits,"
                  f" {gst['misses']} misses, {gst['evictions']} evictions;"
                  f" stage-layout memo: {lst['hits']} hits,"
                  f" {lst['misses']} misses, {lst['evictions']} evictions",
                  file=sys.stderr)
            for name, ps in sorted(_fp.memo_stats().items()):
                print(f"# pallas {name} memo: {ps['hits']} hits,"
                      f" {ps['misses']} misses, {ps['evictions']}"
                      f" evictions ({ps['size']}/{ps['cap']} resident)",
                      file=sys.stderr)
    for name, recs in results.items():
        print(f"# {name}: {len(recs)} records ({mode}, {args.engine})")

    cross = contention_crossover(results)
    for ap, ratios in cross.items():
        detail = ", ".join(f"{k}={v:.2f}x" for k, v in ratios.items())
        print(f"# crossover {ap} vs pt2pt_single: {detail}")

    if args.cache:
        save_disk_cache(args.cache)
        print(f"# run cache saved to {args.cache}", file=sys.stderr)

    if args.out:
        with open(args.out, "w") as f:
            json.dump({"mode": mode, "engine": args.engine,
                       "results": results}, f, indent=2, sort_keys=True)
        print(f"# results written to {args.out}", file=sys.stderr)

    if args.update:
        doc = make_baseline(specs, results)
        if args.specs:
            # Partial update: keep the unselected specs' records by merging
            # into the existing document instead of overwriting it.
            try:
                with open(args.update) as f:
                    old = json.load(f)
            except (FileNotFoundError, json.JSONDecodeError):
                old = None
            if old is None or old.get("version") != doc["version"]:
                print("--update with --specs needs an existing baseline of"
                      " the same version to merge into; run a full --update"
                      " first", file=sys.stderr)
                return 2
            doc["specs"] = {**old["specs"], **doc["specs"]}
        with open(args.update, "w") as f:
            json.dump(doc, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"# baseline written to {args.update}", file=sys.stderr)

    if args.check:
        try:
            with open(args.check) as f:
                doc = json.load(f)
        except (FileNotFoundError, json.JSONDecodeError) as e:
            print(f"# cannot read baseline {args.check}: {e}",
                  file=sys.stderr)
            return 2
        violations = compare_to_baseline(doc, results)
        if violations:
            print(f"# BASELINE DRIFT ({len(violations)} violations):",
                  file=sys.stderr)
            for v in violations:
                print(f"#   {v}", file=sys.stderr)
            return 1
        n = sum(len(r) for r in results.values())
        print(f"# baseline check passed: {n} records within tolerance")
    return 0


if __name__ == "__main__":
    sys.exit(main())
