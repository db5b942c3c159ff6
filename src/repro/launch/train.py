"""End-to-end training driver (runnable on CPU; same code path as TPU).

Wires every substrate together: mesh planning (elastic), synthetic data
pipeline, the partitioned gradient-sync engine, AdamW/ZeRO-1, async
checkpointing, preemption-safe loop, straggler monitor.

  PYTHONPATH=src python -m repro.launch.train --arch llama3.2-1b --smoke \
      --steps 50 --global-batch 4 --seq-len 128

``--resume`` continues from the latest checkpoint (exact, because the
data pipeline is stateless in the step index).
"""

from __future__ import annotations

import argparse
import time
from pathlib import Path

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.ckpt.checkpoint import AsyncCheckpointer, latest_step, restore
from repro.configs import ARCH_IDS, get_config, get_smoke_config
from repro.data import pipeline
from repro.launch.mesh import model_size
from repro.launch.steps import StepConfig, make_train_step
from repro.models import lm
from repro.optim.adamw import AdamWConfig, init_opt_state
from repro.runtime import elastic
from repro.runtime.compile_cache import enable_compile_cache
from repro.runtime.fault_tolerance import (Heartbeat, LoopReport,
                                           StragglerMonitor,
                                           run_training_loop)


def build_state(cfg, mesh, scfg):
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    psh = jax.tree.map(lambda s: NamedSharding(mesh, s), lm.param_specs(cfg),
                       is_leaf=lambda x: isinstance(x, P))
    params = jax.tree.map(jax.device_put, params, psh)
    opt = init_opt_state(params, AdamWConfig())
    state = {"params": params, "opt": opt}
    if cfg.moe is not None and cfg.moe.biased:
        state["router"] = lm.init_route_state(cfg)
    return state


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b", choices=ARCH_IDS)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config (CPU-friendly)")
    ap.add_argument("--scale", type=float, default=1.0,
                    help="width multiplier on the smoke config")
    ap.add_argument("--layers", type=int, default=0)
    ap.add_argument("--d-model", type=int, default=0)
    ap.add_argument("--d-ff", type=int, default=0)
    ap.add_argument("--vocab", type=int, default=0)
    ap.add_argument("--heads", type=int, default=0)
    ap.add_argument("--kv", type=int, default=0)
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--global-batch", type=int, default=4)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--sync", default="partitioned",
                    choices=("bulk", "per_leaf", "partitioned"))
    ap.add_argument("--aggr-bytes", type=int, default=1 << 20)
    ap.add_argument("--ckpt-dir", default="artifacts/train_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--param-dtype", default="float32")
    ap.add_argument("--peak-lr", type=float, default=3e-4)
    ap.add_argument("--log-every", type=int, default=10)
    return ap.parse_args(argv)


def model_config(args):
    """The run's model config: registered (or smoke) config + overrides."""
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if args.scale != 1.0:
        cfg = cfg.replace(d_model=int(cfg.d_model * args.scale),
                          d_ff=int(cfg.d_ff * args.scale))
    over = {k: v for k, v in [("n_layers", args.layers),
                              ("d_model", args.d_model),
                              ("d_ff", args.d_ff), ("vocab", args.vocab),
                              ("n_heads", args.heads), ("n_kv", args.kv)]
            if v}
    if over:
        cfg = cfg.replace(**over, head_dim=0)
    return cfg.replace(param_dtype=args.param_dtype)


def step_config(args, plan) -> StepConfig:
    return StepConfig(sync_mode=args.sync, aggr_bytes=args.aggr_bytes,
                      param_dtype=args.param_dtype, peak_lr=args.peak_lr,
                      warmup_steps=max(args.steps // 10, 1),
                      total_steps=args.steps, seq_parallel=plan.model > 1)


def put_batch(batch, batch_structs):
    """Place a host batch with the step's batch shardings (split over the
    data axes), not on the default device alone."""
    return {k: jax.device_put(v, batch_structs[k].sharding)
            for k, v in batch.items()}


def run(args) -> LoopReport:
    """Train ``args.steps`` steps; returns the loop's report."""
    enable_compile_cache()
    cfg = model_config(args)
    plan = elastic.plan_mesh(len(jax.devices()), args.tp)
    mesh = elastic.build_mesh(plan)
    print(f"mesh: data={plan.data} model={plan.model} "
          f"(devices={plan.n_devices})")

    scfg = step_config(args, plan)
    with jax.set_mesh(mesh):
        step_fn, _, batch_structs, _ = make_train_step(
            cfg, mesh, scfg, seq_len=args.seq_len,
            global_batch=args.global_batch)
        jit_step = jax.jit(step_fn, donate_argnums=0)

        state = build_state(cfg.with_tp(model_size(mesh)), mesh, scfg)
        start = 0
        ckpt_dir = Path(args.ckpt_dir) / cfg.name.replace("/", "_")
        if args.resume and latest_step(ckpt_dir) is not None:
            start, state = restore(ckpt_dir, state)
            print(f"resumed from step {start}")

        stream = pipeline.for_model(cfg, args.seq_len, args.global_batch)
        n_params = cfg.param_count()
        print(f"arch={cfg.name} params={n_params/1e6:.1f}M "
              f"tokens/step={args.global_batch * args.seq_len}")

        losses = []

        def on_loss(step, loss):
            losses.append(loss)
            if step % args.log_every == 0:
                print(f"step {step:5d} loss {loss:.4f}", flush=True)

        def get_batch(step):
            return put_batch(stream.batch(step), batch_structs)

        checkpointer = AsyncCheckpointer(ckpt_dir)
        t0 = time.time()
        with Heartbeat(ckpt_dir / "heartbeat.json") as hb:
            report = run_training_loop(
                step_fn=jit_step, state=state, start_step=start,
                num_steps=args.steps, checkpoint_every=args.ckpt_every,
                checkpointer=checkpointer, get_batch=get_batch,
                on_loss=on_loss, straggler=StragglerMonitor(), heartbeat=hb)
        dt = time.time() - t0
        tok_s = report.steps_run * args.global_batch * args.seq_len / dt
        print(f"done: {report.steps_run} steps in {dt:.1f}s "
              f"({tok_s:.0f} tok/s, {dt/max(report.steps_run,1):.2f}s/step); "
              f"loss {losses[0]:.3f} -> {losses[-1]:.3f}; "
              f"final ckpt step {report.final_step}")
        if report.straggler_steps:
            print(f"stragglers at {report.straggler_steps}")
    return report


def main(argv=None):
    run(parse_args(argv))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
