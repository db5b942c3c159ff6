"""Mini dry-run: the launch layer (steps + analysis) on an 8-device mesh.

Lowers and compiles train/prefill/decode steps for a reduced config on a
(2 data x 4 model) mesh — the same code path the production 512-chip
dry-run uses — and sanity-checks the HLO analyzer outputs.
"""
import os

assert "xla_force_host_platform_device_count" in os.environ.get("XLA_FLAGS", "")

import jax
import jax.numpy as jnp

from repro.configs import get_smoke_config
from repro.launch import hlo_analysis
from repro.launch.mesh import make_mesh
from repro.launch.steps import (StepConfig, make_decode_step,
                                make_prefill_step, make_train_step)

mesh = make_mesh((2, 4), ("data", "model"))
scfg = StepConfig(param_dtype="float32")  # CPU compile, no bf16 passes

for arch in ("llama3.2-1b", "granite-moe-3b-a800m", "mamba2-780m"):
    cfg = get_smoke_config(arch)
    with jax.set_mesh(mesh):
        # train
        step_fn, state_structs, batch_structs, _ = make_train_step(
            cfg, mesh, scfg, seq_len=64, global_batch=4)
        compiled = jax.jit(step_fn, donate_argnums=0).lower(
            state_structs, batch_structs).compile()
        stats = hlo_analysis.analyze_hlo(compiled.as_text())
        assert stats.counts.get("all-reduce", 0) > 0, arch
        assert stats.dot_flops > 0 and stats.hbm_bytes_min > 0
        mem = compiled.memory_analysis()
        assert mem.temp_size_in_bytes > 0
        print(f"{arch} train ok: AR={stats.counts['all-reduce']} "
              f"flops={stats.dot_flops:.2e}")

        # prefill
        pf, ps, bs, cs = make_prefill_step(cfg, mesh, scfg, seq_len=64,
                                           global_batch=4)
        jax.jit(pf, donate_argnums=2).lower(ps, bs, cs).compile()
        print(f"{arch} prefill ok")

        # decode (+ flash-decode variant for attention archs)
        for flash in (False, True):
            if flash and cfg.mixer == "mamba":
                continue
            scfg2 = StepConfig(param_dtype="float32", flash_decode=flash)
            out = make_decode_step(cfg, mesh, scfg2, seq_len=64,
                                   global_batch=4)
            df, pstr, cstr, tstr, posstr, extra = out
            kw = {"embeds": extra["embeds"]} if extra else {}
            jax.jit(df, donate_argnums=1).lower(
                pstr, cstr, tstr, posstr, **kw).compile()
            print(f"{arch} decode ok (flash={flash})")

print("ALL-OK")
