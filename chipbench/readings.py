"""Readings that the limits of ``correct`` are set from.

    python -m chipbench.readings --workload <name> --seeds 1,2,... \
        --control-seeds 7,8,9 [--fault-seeds 7,8,9] [--seconds 3]

In one process, for each ``--seeds`` seed, a short run of the cell
prints the numbers its checks compare (the program's readings); for
each ``--control-seeds`` seed, the control's readings: the reference
put in the program's place in the nearest precision below the one the
configuration states — bfloat16 for the fabric's float32 and for
training's default-precision float32; for each ``--fault-seeds`` seed,
the readings of each fault planted in the reference that the cell can
have.  The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

from chipbench import harness


def control_fabric(cell: harness.Cell) -> dict:
    import ml_dtypes
    from chipbench.drivers import stencil_grid as sg
    c, tr = cell.config, cell.traffic
    ref = harness.reference(c)
    part = ref.face_bytes(c)[0] / (c["n_threads"] * c["theta"])
    ready = sg.ready_table(tr["noise"], c["n_threads"], c["theta"], part,
                           np.random.default_rng([cell.seed, 1]))
    low = ref.simulate(c, ready, dtype=ml_dtypes.bfloat16)

    class Result:  # the control in the program's place
        rank_tts_s = low["rank_tts_s"]
        time_s = low["time_s"]
        n_messages = low["n_messages"]

    checks = sg.compare(c, ref, [ready], [Result], cell.seed, tr)
    return {k.name: k.value for k in checks}


_WANT: dict = {}  # seed -> the reference's readings, shared by the
#                   control and the faults of that seed


def control_train(cell: harness.Cell, share: float = 1.0) -> dict:
    """The bfloat16 control; with ``share`` < 1, instead a fault planted
    in the reference: the loss's mean over that leading share of the
    tokens (a half: half of the batch left out; one chip's rows: the
    exchange between chips left out, as the first chip sees it)."""
    import jax
    from chipbench.drivers import train as drv
    c, tr = cell.config, cell.traffic
    ref = harness.reference(c)
    rows = [drv.packed_rows(cell.seed, i, tr["batch_per_chip"] * cell.chips,
                            tr["seq_len"], c["vocab_size"],
                            tr["data"]["mean_doc_len"], tr["data"]["eos_id"])
            for i in range(tr["checked_steps"])]
    batches = [(r[:, :-1], r[:, 1:]) for r in rows]
    key = jax.random.PRNGKey(cell.seed)
    if cell.seed not in _WANT:
        _WANT[cell.seed] = ref.train_readings(c, key, batches,
                                              devices=cell.devices)
    want = _WANT[cell.seed]
    if share < 1.0:
        low = ref.train_readings(c, key, batches, share=share,
                                 devices=cell.devices)
    else:
        low = ref.train_readings(c, key, batches, dtype="bfloat16",
                                 precision="default", devices=cell.devices)
    checks = drv.compare(low["losses"], low["first_grad"], low["change"],
                         want, tr["limits"])
    return {k.name: k.value for k in checks}


CONTROLS = {"stencil_grid": control_fabric, "train": control_train}


def faults(cell: harness.Cell) -> dict:
    """The planted faults a cell can have, by name."""
    if cell.traffic["kind"] != "train":
        return {}
    out = {"half_batch": lambda: control_train(cell, share=0.5)}
    if cell.chips > 1:
        out["no_exchange"] = lambda: control_train(cell,
                                                   share=1.0 / cell.chips)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--fault-seeds", default="",
                    help="seeds for the planted faults (training cells)")
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(harness.ROOT / "src"))
    from chipbench import run
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    bench = harness.load_benchmark()
    entry, config, traffic = harness.resolve(bench, args.workload)
    devices = run.chips(entry["chips"])

    def cell(seed):
        return harness.Cell(name=entry["name"], chips=entry["chips"],
                            config=config, traffic=traffic, seed=seed,
                            seconds=args.seconds, trace=False,
                            t_process=time.perf_counter(), devices=devices)

    def emit(who, seed, t0, values):
        print(json.dumps({"who": who, "seed": seed, "readings": values,
                          "seconds": time.perf_counter() - t0}), flush=True)

    kind = traffic["kind"]
    for s in [int(x) for x in args.seeds.split(",") if x]:
        t0 = time.perf_counter()
        out = harness.driver(kind).run(cell(s))
        emit("program", s, t0, {k.name: k.value for k in out.checks})
    for s in [int(x) for x in args.control_seeds.split(",") if x]:
        t0 = time.perf_counter()
        emit("control", s, t0, CONTROLS[kind](cell(s)))
    for s in [int(x) for x in args.fault_seeds.split(",") if x]:
        for name, read in faults(cell(s)).items():
            t0 = time.perf_counter()
            emit(name, s, t0, read())
    return 0


if __name__ == "__main__":
    sys.exit(main())
