"""JAX-side early-bird benchmark: gradient-sync modes on an 8-device mesh.

Spawns a CPU-only subprocess with 8 fake host devices (the benchmark
process itself keeps whatever device it has) and reports, per sync mode:
  * pre-optimization all-reduce count (program structure),
  * per-device all-reduce bytes from the compiled HLO (loop-corrected),
  * predicted DP-sync time on the v5e ICI from those bytes,
  * CPU wall time per step (structure check, not a TPU number).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

from .common import emit

_CHILD = r"""
import json, os, re, time
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.compat import shard_map
from repro.core.earlybird import SyncConfig, value_and_synced_grad
from repro.configs import get_smoke_config
from repro.models import lm
from repro.launch import hlo_analysis
from repro.launch.mesh import make_mesh

mesh = make_mesh((8,), ("data",))
cfg = get_smoke_config("llama3.2-1b").replace(n_layers=8, d_model=128,
                                              d_ff=512, vocab=2048)
params = lm.init_params(cfg, jax.random.PRNGKey(0))
key = jax.random.PRNGKey(1)
batch = {"tokens": jax.random.randint(key, (16, 128), 0, cfg.vocab),
         "labels": jax.random.randint(jax.random.PRNGKey(2), (16, 128), 0,
                                      cfg.vocab)}
out = {}
for mode in ("bulk", "per_leaf", "partitioned"):
    sync = SyncConfig(mode=mode, axes=("data",), aggr_bytes=1 << 16)
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
    pre = lowered.as_text()
    compiled = lowered.compile()
    stats = hlo_analysis.analyze_hlo(compiled.as_text())
    loss, grads = step(params, batch)   # warmup
    jax.block_until_ready(loss)
    t0 = time.perf_counter()
    for _ in range(3):
        loss, grads = step(params, batch)
    jax.block_until_ready(loss)
    dt = (time.perf_counter() - t0) / 3
    out[mode] = {
        "pre_opt_all_reduce": len(re.findall(r"stablehlo\.all_reduce", pre)),
        "ar_bytes_per_dev": stats.bytes_.get("all-reduce", 0),
        "wall_s": dt,
    }
print("RESULT " + json.dumps(out))
"""


def rows():
    env = os.environ.copy()
    # a structure check on 8 host devices: the child never needs (and so
    # never contends for) an accelerator the parent may hold
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    repo = Path(__file__).resolve().parent.parent
    env["PYTHONPATH"] = f"{repo / 'src'}{os.pathsep}" + \
        env.get("PYTHONPATH", "")
    r = subprocess.run([sys.executable, "-c", _CHILD], env=env,
                       capture_output=True, text=True, timeout=900)
    line = next((l for l in r.stdout.splitlines() if l.startswith("RESULT ")),
                None)
    if r.returncode != 0 or line is None:
        raise RuntimeError(
            f"jax_earlybird child failed (exit {r.returncode}):\n"
            f"{(r.stderr or r.stdout)[-4000:]}")
    data = json.loads(line[len("RESULT "):])
    out = []
    for mode, d in data.items():
        sync_us = d["ar_bytes_per_dev"] / 50e9 * 1e6  # v5e ICI
        out.append((f"jax_earlybird/{mode}/wall", d["wall_s"] * 1e6,
                    f"pre_opt_ar={d['pre_opt_all_reduce']},"
                    f"ar_bytes={d['ar_bytes_per_dev']},"
                    f"pred_ici_us={sync_us:.1f}"))
    return out


def main():
    emit(rows())


if __name__ == "__main__":
    main()
