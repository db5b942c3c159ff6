"""Quickstart: train a small LM end-to-end on CPU with the full stack
(data pipeline -> partitioned gradient sync -> AdamW/ZeRO-1 -> async
checkpointing -> fault-tolerant loop).

    PYTHONPATH=src python examples/quickstart.py

This is the same code path the production launcher uses; scale knobs and
the mesh come from the CLI there (repro.launch.train).
"""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

import jax
import jax.numpy as jnp

from repro.configs import get_smoke_config
from repro.data import pipeline
from repro.launch.steps import StepConfig, make_train_step
from repro.launch.train import build_state
from repro.runtime import elastic


def main():
    arch = sys.argv[1] if len(sys.argv) > 1 else "llama3.2-1b"
    cfg = get_smoke_config(arch).replace(param_dtype="float32")
    plan = elastic.plan_mesh(len(jax.devices()), 1)
    mesh = elastic.build_mesh(plan)

    scfg = StepConfig(sync_mode="partitioned", aggr_bytes=1 << 20,
                      param_dtype="float32", peak_lr=1e-3,
                      warmup_steps=5, total_steps=60)
    seq_len, batch = 128, 4
    with jax.set_mesh(mesh):
        step_fn, *_ = make_train_step(cfg, mesh, scfg, seq_len=seq_len,
                                      global_batch=batch)
        step = jax.jit(step_fn, donate_argnums=0)
        state = build_state(cfg, mesh, scfg)
        stream = pipeline.for_model(cfg, seq_len, batch)
        print(f"training {cfg.name}: {cfg.param_count()/1e6:.2f}M params, "
              f"{batch * seq_len} tokens/step")
        first = None
        for i in range(60):
            batch_np = {k: jnp.asarray(v) for k, v in stream.batch(i).items()}
            state, loss = step(state, batch_np)
            if first is None:
                first = float(loss)
            if i % 10 == 0:
                print(f"  step {i:3d}  loss {float(loss):.4f}")
        print(f"loss: {first:.4f} -> {float(loss):.4f} "
              f"({'improved' if float(loss) < first else 'check lr'})")


if __name__ == "__main__":
    main()
