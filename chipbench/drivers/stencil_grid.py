"""Driver of fabric cells: stencil grid points through the Pallas engine.

Each timed unit is one point — one ``simulate_stencil_grid([point],
engine="pallas")`` call: host assembly of every flow's messages, the merge,
the device program, and the per-rank results back on the host.  Each
point gets a fresh shared ``(n_threads, theta)`` ready table drawn from
the seed with the Appendix-A compute-noise model, so no memo keyed by
the point's parameters can answer it, while every point keeps the same
shapes (nothing recompiles).

Traffic keys: ``noise`` (the model's ``ai``, ``ci``,
``eps``, ``delta``, ``freq_hz``), ``check_points`` (points compared
with the reference, drawn from the seed, the last one always among
them) and ``limits``.
"""

from __future__ import annotations

import gc
import math
import sys
import time

import numpy as np

from chipbench import flops, harness
from chipbench.harness import Check, Outcome, span

# The fabric's device path: the Pallas queue-scan kernels.
ENGINE = "pallas"


def ready_table(noise: dict, n_threads: int, theta: int, part_bytes: float,
                rng: np.random.Generator) -> np.ndarray:
    """Appendix-A compute noise: per-partition compute ``mu * S *
    N(1, sigma)`` clipped at 0, accumulated along each thread, with
    ``mu = (ai / ci) / (8 F)`` seconds per byte and ``sigma = (eps +
    delta) / 2``."""
    mu = (noise["ai"] / noise["ci"]) / (8.0 * noise["freq_hz"])
    sigma = (noise["eps"] + noise["delta"]) / 2.0
    per = mu * part_bytes * rng.normal(1.0, sigma, size=(n_threads, theta))
    return np.maximum(per, 0.0).cumsum(axis=1)


def point_kwargs(c: dict, ready: np.ndarray) -> dict:
    from repro.core.fabric import NetConfig
    return dict(approach=c["approach"], dims=tuple(c["dims"]),
                periodic=c["periodic"], theta=c["theta"],
                n_threads=c["n_threads"], local_shape=tuple(c["local_shape"]),
                bytes_per_cell=c["bytes_per_cell"],
                halo_width=c["halo_width"], ready=ready,
                n_vcis=c["n_vcis"], aggr_bytes=c["aggr_bytes"],
                cfg=NetConfig(**c["net"]))


def memo_hits() -> dict:
    """Hits of every memo that could answer a point from an earlier one."""
    from repro.core import simulator as sim
    hits = {"merge": sim.merge_memo_stats()["hits"],
            "grid": sim.grid_memo_stats()["hits"]}
    fp = sys.modules.get("repro.core.fabric_pallas")
    if fp is not None:
        for k, v in fp.memo_stats().items():
            hits["pallas_" + k] = v["hits"]
    return hits


def run(cell: harness.Cell) -> Outcome:
    from repro.core import simulator as sim

    c, tr = cell.config, cell.traffic
    ref = harness.reference(c)
    n_part = c["n_threads"] * c["theta"]
    part_bytes = ref.face_bytes(c)[0] / n_part
    rng = np.random.default_rng([cell.seed, 1])

    def draw():
        return ready_table(tr["noise"], c["n_threads"], c["theta"],
                           part_bytes, rng)

    def one_point(ready):
        res = sim.simulate_stencil_grid([point_kwargs(c, ready)],
                                        engine=ENGINE)[0]
        if res is None:
            raise RuntimeError("the grid path refused the point")
        return res

    # set-up: one point from its own stream compiles and warms the path
    with span("warmup"):
        one_point(ready_table(tr["noise"], c["n_threads"], c["theta"],
                              part_bytes, np.random.default_rng(
                                  [cell.seed, 0])))
    hits0 = memo_hits()
    setup_s = time.perf_counter() - cell.t_process

    tables, results, walls = [], [], []
    gc2 = gc.get_stats()[2]["collections"]
    with harness.Window(cell.name, cell.seconds, cell.trace) as win:
        while win.running():
            with span("draw"):
                ready = draw()
            t0 = time.perf_counter()
            with span("point"):
                res = one_point(ready)
            walls.append(time.perf_counter() - t0)
            tables.append(ready)
            results.append(res)
    hits = {k: v - hits0.get(k, 0) for k, v in memo_hits().items()}
    q = np.quantile(walls, [0.0, 0.25, 0.5, 0.75, 1.0])
    slow = sorted(range(len(walls)), key=lambda i: -walls[i])[:3]
    print(f"info point walls (s): min/q1/median/q3/max {q.tolist()}; "
          f"slowest (point, s) {[(i, walls[i]) for i in slow]}; full "
          f"garbage collections in the window "
          f"{gc.get_stats()[2]['collections'] - gc2}", file=sys.stderr)
    mem = harness.memory_peak(cell.devices)

    n_msgs = [r.n_messages for r in results]
    n_ranks = math.prod(c["dims"])
    checks = [Check("window_compiles", float(win.compiles), 0.0),
              Check("memo_hits", float(sum(hits.values())), 0.0)]
    checks += compare(c, ref, tables, results, cell.seed, tr)
    return Outcome(
        e2e={"fabric_msgs_per_s": sum(n_msgs) / sum(walls)},
        setup_s=setup_s, attempted=len(results), failed=0, checks=checks,
        counters={"points": len(results), "messages": sum(n_msgs),
                  "n_ranks": n_ranks, "point_walls_s": walls,
                  "point_bytes": sum(flops.fabric_point_bytes(n, n_ranks)
                                     for n in n_msgs),
                  "memo_hits": hits},
        memory_peak_bytes=mem, window=win)


def compare(c, ref, tables, results, seed, tr):
    """The sampled points against the reference: the worst relative
    gap of any rank's completion time, of ``time_s``, and the message
    count (exact)."""
    pick = harness.sample_indices(seed, len(results), tr["check_points"],
                                  always=(len(results) - 1,))
    rank_err = time_err = msg_diff = 0.0
    for i in pick:
        want = ref.simulate(c, tables[i])
        got = np.asarray(results[i].rank_tts_s, dtype=np.float64)
        w = want["rank_tts_s"]
        rank_err = max(rank_err, float(np.max(np.abs(got - w)
                                              / np.abs(w))))
        time_err = max(time_err, abs(results[i].time_s - want["time_s"])
                       / abs(want["time_s"]))
        msg_diff = max(msg_diff, abs(results[i].n_messages
                                     - want["n_messages"]))
    lim = tr["limits"]
    return [Check("rank_tts_rel_err", rank_err, lim["rank_tts_rel_err"]),
            Check("time_rel_err", time_err, lim["time_rel_err"]),
            Check("n_messages_diff", float(msg_diff), 0.0)]
