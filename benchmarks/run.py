"""Benchmark harness entry point — one module per paper table/figure,
plus the post-paper scenario drivers (steady-state, halo, N-D stencil,
load imbalance, open-loop serving).

Prints ``name,us_per_call,derived`` CSV.  Simulator-based figures and
scenarios run in milliseconds; ``--fast`` skips everything that reads or
spawns outside the simulator (the jax_earlybird 8-device subprocess and
the roofline_report artifact scan).  ``--seed N`` threads a seed to the
imbalance scenario so JSON output is reproducible run-to-run.

``--json [PATH]`` additionally writes the scenario results (steady-state,
halo, stencil, imbalance, serving sweeps) as a JSON document (default:
benchmark_results.json).  Grid sweeps with golden-baseline checking live
in ``benchmarks.sweep``.
"""

import json
import sys

from . import (fig4_latency, fig5_congestion, fig6_vci, fig7_aggregation,
               fig8_earlybird, jax_earlybird, roofline_report, scen_faults,
               scen_halo, scen_imbalance, scen_serving, scen_steady,
               scen_stencil, tableA_delayrate)
from .common import emit
from repro.runtime.compile_cache import enable_compile_cache

SCENARIOS = (scen_steady, scen_halo, scen_stencil, scen_imbalance,
             scen_serving, scen_faults)


def _json_path(argv) -> str:
    if "--json" not in argv:
        return ""
    i = argv.index("--json")
    if i + 1 < len(argv) and not argv[i + 1].startswith("-"):
        return argv[i + 1]
    return "benchmark_results.json"


def _seed(argv) -> int:
    if "--seed" not in argv:
        return 0
    i = argv.index("--seed")
    try:
        seed = int(argv[i + 1])
        if seed < 0:
            raise ValueError
    except (IndexError, ValueError):
        raise SystemExit("--seed needs a non-negative integer value")
    return seed


def _scenario_kw(mod, seed: int) -> dict:
    return {"seed": seed} if mod is scen_imbalance else {}


def main() -> None:
    fast = "--fast" in sys.argv
    enable_compile_cache()
    seed = _seed(sys.argv)
    emit([], header=True)
    for mod in (tableA_delayrate, fig4_latency, fig5_congestion, fig6_vci,
                fig7_aggregation, fig8_earlybird, *SCENARIOS):
        emit(mod.rows(**_scenario_kw(mod, seed)))
    path = _json_path(sys.argv)
    if path:
        doc = {mod.__name__.split(".")[-1]:
               mod.results(**_scenario_kw(mod, seed))
               for mod in SCENARIOS}
        with open(path, "w") as f:
            json.dump(doc, f, indent=2)
        print(f"# scenario JSON written to {path}", file=sys.stderr)
    if not fast:
        emit(jax_earlybird.rows())
        emit(roofline_report.rows())
        emit(roofline_report.rows("multi"))


if __name__ == '__main__':
    main()
