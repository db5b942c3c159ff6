"""Run one cell of BENCHMARK.json on the chips of this machine.

    python -m chipbench.run --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

Prints, as the last line of standard output, one JSON object: whether
the window's output matched the plain reference (``correct``), the units
attempted and failed, the metrics (end-to-end ones with ``--trace 0``,
the cell's per-layer ones with ``--trace 1``), the device, and under
``checks`` (last) each number compared with its limit.  The same checks
end standard error.  Exits non-zero with no result when JAX finds no
TPU, fewer chips than the cell asks for, or no program beside the
benchmark (``src/``).
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

from chipbench import harness  # noqa: E402


class NoChip(RuntimeError):
    pass


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def chips(n: int) -> list:
    """The first ``n`` TPU chips; raises :class:`NoChip` otherwise.  A
    measurement never falls back to the CPU."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX's default backend is {devs[0].platform!r}")
    if len(devs) < n:
        raise NoChip(f"the cell needs {n} chips, JAX sees {len(devs)}")
    from repro.kernels import runtime as rt
    if rt.interpret_mode():
        raise NoChip("the Pallas interpreter is on (REPRO_PALLAS_INTERPRET)")
    return devs[:n]


def per_layer(bench: dict, workload: str, outcome, trace_summary,
              device_kind: str, n_chips: int) -> dict:
    """The cell's per-layer metrics whose readers found something."""
    ctx = {"trace": trace_summary, "counters": outcome.counters,
           "peak": harness.peaks(device_kind), "chips": n_chips}
    out = {}
    for m in bench["per_layer"]:
        if workload not in m.get("workloads", [workload]):
            continue
        value = harness.metric_reader(m["name"]).read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def end_to_end(bench: dict, workload: str, outcome) -> dict:
    out = {}
    for m in bench["end_to_end"]:
        if workload not in m.get("workloads", [workload]):
            continue
        value = outcome.setup_s if m["name"] == "setup_s" \
            else outcome.e2e[m["name"]]
        out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def result(bench, cell, outcome, devices) -> dict:
    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices),
              "memory_peak_bytes": outcome.memory_peak_bytes}
    res = {"correct": all(c.ok for c in outcome.checks),
           "attempted": outcome.attempted, "failed": outcome.failed}
    if cell.trace:
        from chipbench.trace import reduce_trace
        summary = reduce_trace(outcome.window.dir)
        device.update(busy_s=summary.busy_s, window_s=summary.window_s)
        res["metrics"] = per_layer(bench, cell.name, outcome, summary,
                                   d0.device_kind, len(devices))
        res["device"] = device
        res["breakdown"] = summary.breakdown()
    else:
        res["metrics"] = end_to_end(bench, cell.name, outcome)
        res["device"] = device
    res["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                     for c in outcome.checks}
    return res


def main(argv=None) -> int:
    args = parse_args(argv)
    src = harness.ROOT / "src"
    if not (src / "repro").is_dir():
        print(f"no program to measure: {src}/repro is missing",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(src))
    bench = harness.load_benchmark()
    entry, config, traffic = harness.resolve(bench, args.workload)

    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    try:
        devices = chips(entry["chips"])
    except NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 3
    cell = harness.Cell(name=entry["name"], chips=entry["chips"],
                        config=config, traffic=traffic, seed=args.seed,
                        seconds=args.seconds, trace=bool(args.trace),
                        t_process=T_PROCESS, devices=devices)
    outcome = harness.driver(traffic["kind"]).run(cell)
    res = result(bench, cell, outcome, devices)
    for c in outcome.checks:
        print(f"check {c.name}: {c.value!r} (limit {c.limit!r}) "
              f"{'ok' if c.ok else 'FAILED'}", file=sys.stderr)
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
