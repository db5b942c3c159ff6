import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    # The CPU backend's all-reduce-promotion pass crashes on bf16
    # all-reduces (it exists because the CPU *runtime* cannot reduce
    # 16-bit types).  The dry-run only compiles — never executes — so we
    # disable it to keep the true bf16 wire bytes in the analyzed HLO.
    "--xla_disable_hlo_passes=all-reduce-promotion")

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The two lines above MUST stay first: jax locks the device count on first
init, and the production meshes need 512 placeholder host devices.

For each cell this produces a JSON artifact with:
  * compiled.memory_analysis()  — per-device bytes (proves it fits 16 GB),
  * compiled.cost_analysis()    — per-device HLO FLOPs / bytes accessed,
  * parsed collective traffic   — per-device bytes by collective type,
    loop-multiplied (launch.hlo_analysis),
  * roofline terms (v5e: 197 TFLOP/s bf16, 819 GB/s HBM, ~50 GB/s/link),
  * MODEL_FLOPS = 6·N·D (train) / 2·N·D (inference) and the useful-compute
    ratio MODEL_FLOPS / HLO_FLOPs.

Usage:
  python -m repro.launch.dryrun --arch llama3.2-1b --shape train_4k
  python -m repro.launch.dryrun --all --mesh single
  python -m repro.launch.dryrun --all --mesh multi   # 2-pod, 512 chips
"""

import argparse
import json
import time
import traceback
from pathlib import Path

import jax
import numpy as np

from repro.configs import ARCH_IDS, get_config
from repro.configs.shapes import SHAPES, cells
from repro.launch import hlo_analysis
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import (StepConfig, make_decode_step,
                                make_prefill_step, make_train_step)
from repro.models import lm

PEAK_FLOPS = 197e12      # bf16 per chip
HBM_BW = 819e9           # B/s per chip
LINK_BW = 50e9           # B/s per ICI link


def lower_cell(arch_id: str, shape_name: str, *, multi_pod: bool,
               scfg: StepConfig):
    """Lower one cell; returns (lowered, n_chips, cfg, shape)."""
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    cfg = get_config(arch_id)
    # a biased router's serving steps take the trained selection bias
    bias = ({"route_bias": jax.eval_shape(
        lambda: lm.init_route_state(cfg)["bias"])}
        if cfg.moe is not None and cfg.moe.biased else {})
    with jax.set_mesh(mesh):
        if shape.kind == "train":
            step_fn, state_structs, batch_structs, _ = make_train_step(
                cfg, mesh, scfg, seq_len=shape.seq_len,
                global_batch=shape.global_batch)
            lowered = jax.jit(step_fn, donate_argnums=0).lower(
                state_structs, batch_structs)
        elif shape.kind == "prefill":
            step_fn, p_structs, b_structs, c_structs = make_prefill_step(
                cfg, mesh, scfg, seq_len=shape.seq_len,
                global_batch=shape.global_batch)
            lowered = jax.jit(step_fn, donate_argnums=2).lower(
                p_structs, b_structs, c_structs, **bias)
        elif shape.kind == "decode":
            (step_fn, p_structs, c_structs, t_structs, pos_struct,
             extra) = make_decode_step(cfg, mesh, scfg,
                                       seq_len=shape.seq_len,
                                       global_batch=shape.global_batch)
            args = [p_structs, c_structs, t_structs, pos_struct]
            kw = dict(bias)
            if extra:
                kw["embeds"] = extra["embeds"]
            lowered = jax.jit(step_fn, donate_argnums=1).lower(*args, **kw)
        else:
            raise ValueError(shape.kind)
    n_chips = 512 if multi_pod else 256
    return lowered, n_chips, cfg, shape


def analyze_cell(arch_id: str, shape_name: str, *, multi_pod: bool,
                 scfg: StepConfig) -> dict:
    t0 = time.time()
    lowered, n_chips, cfg, shape = lower_cell(
        arch_id, shape_name, multi_pod=multi_pod, scfg=scfg)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    # cost_analysis counts while bodies ONCE — useless for scanned layers.
    # hlo_analysis re-derives dot FLOPs / HBM traffic / collective bytes
    # with loop trip-count multipliers (see launch/hlo_analysis.py).
    stats = hlo_analysis.analyze_hlo(compiled.as_text())
    flops_dev = float(stats.dot_flops)
    bytes_dev = float(stats.hbm_bytes_min)  # production-traffic estimate
    bytes_dev_ub = float(stats.hbm_bytes)   # op-level upper bound
    coll = stats

    # roofline terms (seconds); all statistics are PER DEVICE in the
    # partitioned module, so divide by per-chip rates directly.
    compute_s = flops_dev / PEAK_FLOPS
    memory_s = bytes_dev / HBM_BW
    collective_s = coll.total_bytes / LINK_BW
    terms = {"compute_s": compute_s, "memory_s": memory_s,
             "collective_s": collective_s}
    dominant = max(terms, key=terms.get)

    # XLA-CPU legalizes bf16 dots by upcasting operands to f32; when a
    # bf16 input buffer (e.g. the KV cache) re-appears as a same-shape f32
    # temp, that copy is a CPU-compile artifact absent on TPU (native bf16
    # MXU).  Report a corrected estimate alongside the raw number.
    hlo_txt = compiled.as_text()
    artifact_bytes = 0
    import re as _re
    seen_shapes = set()
    for m_ in _re.finditer(r"bf16\[([\d,]+)\][^=]*parameter\(", hlo_txt):
        dims = m_.group(1)
        if dims in seen_shapes:
            continue
        seen_shapes.add(dims)
        n_el = 1
        for d in dims.split(","):
            n_el *= int(d)
        if n_el * 2 < (64 << 20):
            continue  # only large input buffers (KV caches, weights)
        n_copies = len(set(_re.findall(
            rf"(%[\w.\-]+) = f32\[{dims}\]", hlo_txt)))
        # at most the k & v copies per shape; archs that legitimately
        # compute in f32 (SSD) would otherwise be over-corrected
        artifact_bytes += min(n_copies, 2) * n_el * 4
    _pre_total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                  + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    artifact_bytes = min(artifact_bytes, int(0.6 * _pre_total))

    n_active = cfg.active_param_count()
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode"
                                   else 1)
    model_flops = (6 if shape.kind == "train" else 2) * n_active * tokens
    model_flops_dev = model_flops / n_chips
    hbm_gib = 16.0
    mem_total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                 + mem.temp_size_in_bytes - mem.alias_size_in_bytes)

    return {
        "arch": arch_id, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_chips": n_chips,
        "sync_mode": scfg.sync_mode, "aggr_bytes": scfg.aggr_bytes,
        "seq_parallel": scfg.seq_parallel,
        "comm_dtype": scfg.comm_dtype,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "total_per_device_bytes": int(mem_total),
            "total_per_device_gib": round(mem_total / (1 << 30), 3),
            "cpu_bf16_upcast_artifact_gib":
                round(artifact_bytes / (1 << 30), 3),
            "tpu_estimate_gib":
                round((mem_total - artifact_bytes) / (1 << 30), 3),
            "fits_16gib": bool((mem_total - artifact_bytes) / (1 << 30)
                               <= hbm_gib),
        },
        "cost": {"flops_per_device": flops_dev,
                 "bytes_per_device": bytes_dev,
                 "bytes_per_device_upper_bound": bytes_dev_ub,
                 "xla_cost_flops_no_loop_mult": float(cost.get("flops", 0)),
                 "xla_cost_bytes_no_loop_mult":
                     float(cost.get("bytes accessed", 0))},
        "collectives": {k: v for k, v in coll.to_dict().items()
                        if k not in ("dot_flops", "hbm_bytes",
                                     "hbm_bytes_min")},
        "roofline": {
            **{k: float(v) for k, v in terms.items()},
            "dominant": dominant,
            "model_flops_global": float(model_flops),
            "model_flops_per_device": float(model_flops_dev),
            "useful_compute_ratio": float(model_flops_dev / flops_dev)
            if flops_dev else None,
        },
    }


def run(args) -> int:
    out_dir = Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)
    scfg = StepConfig(sync_mode=args.sync, aggr_bytes=args.aggr_bytes,
                      comm_dtype=args.comm_dtype or None,
                      seq_parallel=not args.no_seq_parallel,
                      ce_gather_targets=args.ce_gather,
                      flash_decode=args.flash_decode,
                      moe_chunk=args.moe_chunk,
                      capacity_factor=args.capacity_factor)
    if args.all:
        todo = [(a, s.name) for a in ARCH_IDS for s in cells(a)]
    else:
        todo = [(args.arch, args.shape)]
    failures = 0
    for arch_id, shape_name in todo:
        multi = args.mesh == "multi"
        tag = f"{arch_id}__{shape_name}__{args.mesh}"
        variant = args.suffix or (args.sync if args.sync != "partitioned"
                                  else "")
        if variant:
            tag += f"__{variant}"
        path = out_dir / f"{tag}.json"
        if path.exists() and not args.force:
            print(f"[skip] {tag} (exists)")
            continue
        print(f"[dryrun] {tag} ...", flush=True)
        try:
            rec = analyze_cell(arch_id, shape_name, multi_pod=multi,
                               scfg=scfg)
            path.write_text(json.dumps(rec, indent=1))
            r = rec["roofline"]
            print(f"  ok: compile={rec['compile_s']}s "
                  f"mem={rec['memory']['total_per_device_gib']}GiB "
                  f"compute={r['compute_s']:.4f}s "
                  f"memory={r['memory_s']:.4f}s "
                  f"collective={r['collective_s']:.4f}s "
                  f"dominant={r['dominant']}", flush=True)
        except Exception as e:
            failures += 1
            print(f"  FAILED: {type(e).__name__}: {e}", flush=True)
            (out_dir / f"{tag}.error.txt").write_text(traceback.format_exc())
    return failures


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=tuple(SHAPES))
    ap.add_argument("--mesh", choices=("single", "multi"), default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--sync", default="partitioned",
                    choices=("bulk", "per_leaf", "partitioned"))
    ap.add_argument("--aggr-bytes", type=int, default=4 << 20)
    ap.add_argument("--comm-dtype", default="")
    ap.add_argument("--no-seq-parallel", action="store_true")
    ap.add_argument("--ce-gather", action="store_true",
                    help="naive take_along_axis CE targets (baseline)")
    ap.add_argument("--flash-decode", action="store_true",
                    help="partitioned-KV decode attention (optimized)")
    ap.add_argument("--moe-chunk", type=int, default=0)
    ap.add_argument("--capacity-factor", type=float, default=0.0)
    ap.add_argument("--suffix", default="",
                    help="artifact tag suffix for perf iterations")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--out", default="artifacts/dryrun")
    args = ap.parse_args()
    if not args.all and (not args.arch or not args.shape):
        ap.error("--arch/--shape or --all required")
    raise SystemExit(1 if run(args) else 0)


if __name__ == "__main__":
    main()
