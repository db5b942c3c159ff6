"""Early-bird gradient sync demo — the paper's technique on a JAX mesh.

Runs the same training step under the three §2.3-style strategies:
  bulk        ~ Pt2Pt single  (all comm after backward, one fused stream)
  per_leaf    ~ Pt2Pt many    (one collective per parameter, no aggregation)
  partitioned ~ MPI-4.0 partitioned (per-layer, aggregated, in-backward)

and reports, per mode: program-level all-reduce count, per-device
all-reduce bytes (loop-corrected), whether reductions sit INSIDE the
backward scan (the early-bird placement), and CPU wall time.

NOTE: sets XLA_FLAGS before importing jax — run as a script, 8 fake devices.
"""

import os

os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")

import re
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs import get_smoke_config
from repro.core.earlybird import SyncConfig, value_and_synced_grad
from repro.launch import hlo_analysis
from repro.launch.mesh import make_mesh
from repro.models import lm
from repro.compat import shard_map


def main():
    mesh = make_mesh((jax.device_count(),), ("data",))
    cfg = get_smoke_config("llama3.2-1b").replace(
        n_layers=12, d_model=128, d_ff=512, vocab=2048)
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    key = jax.random.PRNGKey(1)
    batch = {"tokens": jax.random.randint(key, (16, 256), 0, cfg.vocab),
             "labels": jax.random.randint(jax.random.PRNGKey(2), (16, 256),
                                          0, cfg.vocab)}

    print(f"{'mode':>12} {'AR (program)':>13} {'AR (compiled)':>14} "
          f"{'AR MiB/dev':>11} {'in-loop?':>9} {'wall ms':>8}")
    for mode in ("bulk", "per_leaf", "partitioned"):
        sync = SyncConfig(mode=mode, axes=("data",), aggr_bytes=64 << 10)
        vg = value_and_synced_grad(
            lambda p, bt, param_hooks=None: lm.loss_fn(cfg, p, bt,
                                                      param_hooks=param_hooks),
            sync)
        step = jax.jit(shard_map(
            lambda p, bt: vg(p, bt), mesh=mesh,
            in_specs=(P(), {"tokens": P("data", None),
                            "labels": P("data", None)}),
            out_specs=(P(), P()), check_vma=False, axis_names={"data"}))
        lowered = step.lower(params, batch)
        pre_ar = len(re.findall(r"stablehlo\.all_reduce", lowered.as_text()))
        compiled = lowered.compile()
        stats = hlo_analysis.analyze_hlo(compiled.as_text())
        comps, _ = hlo_analysis._split_computations(compiled.as_text())
        in_loop = hlo_bodies_have_ar(comps)
        loss, grads = step(params, batch)   # warmup/compile
        jax.block_until_ready(loss)
        t0 = time.perf_counter()
        for _ in range(3):
            loss, grads = step(params, batch)
        jax.block_until_ready(loss)
        dt = (time.perf_counter() - t0) / 3
        print(f"{mode:>12} {pre_ar:>13} "
              f"{stats.counts.get('all-reduce', 0):>14} "
              f"{stats.bytes_.get('all-reduce', 0) / 2**20:>11.1f} "
              f"{str(in_loop):>9} {dt * 1e3:>8.1f}")
    print("\nProgram-level AR counts show the three §2.3 strategies: bulk packs"
          "\neverything (2 ops), per_leaf pays one op per parameter (12),"
          "\npartitioned buckets per layer (10).  On this CPU-toy scale XLA"
          "\nunrolls the 12-layer scan and its combiner merges the compiled ops"
          "\n— the same aggregation the paper implements by hand in MPICH.  At"
          "\nproduction scale (42-layer scans, see the dry-run artifacts) the"
          "\nloop survives and only the partitioned mode keeps its reductions"
          "\ninside the backward loop body, where they overlap compute.")


def hlo_bodies_have_ar(comps):
    for txt in comps.values():
        for m in re.finditer(r"while\([^)]*\), condition=[%\w.\-]+, "
                             r"body=([%\w.\-]+)", txt):
            if "all-reduce" in "\n".join(comps.get(m.group(1), [])):
                return True
    return False


if __name__ == "__main__":
    main()
