"""Chip smoke test: the system's main path, once, on a TPU.

    python chip_smoke.py             # one chip: device, fabric, train, serve
    python chip_smoke.py --chips 4   # four chips: partitioned vs bulk
                                     # gradient sync on a data=4 mesh

Everything runs in this one process: a chip belongs to one process, and
a child started after this one touched JAX could not reach it.  Each
phase prints one JSON line; the last line is

    {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}

and nothing else.  Any failed check raises, so the script exits
non-zero and never prints that line: without a TPU, with the Pallas
interpreter on, or outside the repository (``src/`` must sit next to
this file).

Phases (one chip):

* fabric — the ``weak_scaling_xxl`` smoke points (a 32x32x32-rank
  periodic torus, ~1.6M wire messages per partitioned record) on the
  compiled pallas engine, checked against
  ``BENCH_scenarios.json`` by the sweep's ``--check`` comparison (2%
  relative, message counts exact); then two warm admission waves
  through one live pallas fabric against the NumPy engine, and the
  steady-state driver on the pallas engine against the NumPy engine.
* train — ``granite-moe-3b-a800m`` at its published widths, depth cut
  to 4 layers (554M parameters; weights, gradients and AdamW state at
  16 B per parameter fill ~8.9 GB of the chip's 16 GB), a few steps of
  ``repro.launch.train`` with partitioned gradient sync.
* serve — the same model in bfloat16: one prefill and incremental
  decode through ``make_prefill_step`` / ``make_decode_step``; decoding
  the prompt through the KV cache must reproduce the prefill logits.

Weights are random, drawn from fixed seeds.
"""

from __future__ import annotations

import argparse
import json
import math
import shutil
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent
sys.path.insert(0, str(REPO / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.runtime.compile_cache import enable_compile_cache  # noqa: E402

ARCH = "granite-moe-3b-a800m"
MODEL_ARGS = ("--arch", ARCH, "--layers", "4")
TRAIN_ARGS = ("--steps", "4", "--global-batch", "8", "--seq-len", "512")
FABRIC_SPEC = "weak_scaling_xxl"
CKPT_DIR = REPO / "artifacts" / "chip_smoke"
# Prefill vs incremental decode, both in bfloat16: the two paths round
# K/V, attention sums and the residual stream at different points, and
# bfloat16 keeps 8 significant bits (one rounding step is 2^-8 = 0.4%
# of a value).  A handful of such steps through 4 layers leaves the
# logits within 1-2% of the logit range; a wrong cache slot, position or
# mask moves them by O(1).  So the bound is 3% of max |prefill logit|.
SERVE_REL_TOL = 3e-2
# Partitioned vs bulk gradient sync do identical arithmetic and differ
# only in which buckets each all-reduce carries and where XLA fuses;
# float32 rounding from that shows as ~1e-6 relative, a missing or
# doubled reduction as O(1).  On a TPU the default precision of a
# float32 matmul rounds its operands to bfloat16, and the two programs
# round at different points (4.5e-3 relative apart on v5e), so the
# gradients are compared at "highest" matmul precision.  Training
# losses keep the default precision: 4 steps stay within the bound.
SYNC_REL_TOL = 1e-3


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise RuntimeError(msg)


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def phase_device(chips: int) -> dict:
    from repro.kernels import runtime as rt
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    check(dev["platform"] == "tpu",
          f"no TPU: JAX's default backend is {dev['platform']!r}")
    check(not rt.interpret_mode(),
          "Pallas interpret mode is on for the TPU (REPRO_PALLAS_INTERPRET)")
    check(dev["count"] >= chips,
          f"{chips} chips requested, JAX sees {dev['count']}")
    emit("device", **dev, interpret=rt.interpret_mode(),
         compile_cache=enable_compile_cache())
    return dev


def _max_rel(results: dict, ref: dict, metric: str) -> float:
    return max(abs(m[metric] - ref[k][metric]) / abs(ref[k][metric])
               for k, m in results.items())


def phase_fabric(spec_name: str = FABRIC_SPEC) -> None:
    from repro.core import fabric as fb
    from repro.core import fabric_pallas as fp
    from repro.core import simulator as sim
    from repro.experiments import SPECS, compare_to_baseline, run_spec
    from repro.experiments import engine as xeng
    from repro.kernels import runtime as rt

    # the kernel program lowers to Mosaic, not to the interpreter's HLO
    probe = fp._scan_call(8, 8, 8, 8, "float32", rt.interpret_mode())
    x = jnp.zeros((8, 8, fp.LANES), jnp.float32)
    lowered = jax.jit(probe).lower(x, x, x[0]).as_text()
    check("tpu_custom_call" in lowered, "pallas scan kernel not compiled")

    baseline = json.loads((REPO / "BENCH_scenarios.json").read_text())
    ref = baseline["specs"][spec_name]["records"]
    spec = SPECS[spec_name]
    walls = []
    for _ in range(2):  # cold (compiles), then warm
        xeng._CACHE.clear()
        t0 = time.perf_counter()
        results = run_spec(spec, mode="smoke", engine="pallas")
        walls.append(time.perf_counter() - t0)
    violations = compare_to_baseline(baseline, {spec_name: results})
    check(not violations, f"{spec_name} on pallas: " + "; ".join(violations))
    emit("fabric", spec=spec_name, engine="pallas", records=len(results),
         events=int(sum(m["n_messages"] for m in results.values())),
         max_rel_err_time_us=_max_rel(results, ref, "time_us"),
         first_run_s=walls[0], warm_run_s=walls[1],
         baseline_violations=len(violations))

    # Warm state: two admission waves through one live fabric (the
    # online ``advance`` entry point), the second starting while the
    # first still holds VCIs, NICs and wires.  The single-flow
    # steady-state driver never batches (one sender is one serial
    # chain), so it cannot reach the kernels; these waves do.
    cfg = fb.DEFAULT_NET
    cols = wave_traffic(seed=0, n_ranks=4096, per_rank=64)
    n_ranks = int(cols["src"].max()) + 1
    pal = fp.PallasFabric(cfg, 4, n_ranks=n_ranks)
    vec = fb.Fabric(cfg, 4, n_ranks=n_ranks)
    calls = fp._build_call.cache_info()
    before = calls.hits + calls.misses
    errs, shift = [], 0.0
    for _ in range(2):
        wave = dict(cols, t_ready=cols["t_ready"] + shift)
        ap, av = pal.advance(**wave), vec.advance(**wave)
        errs.append(float(np.abs(ap - av).max() / np.abs(av).max()))
        shift = 0.5 * float(av.max())
    calls = fp._build_call.cache_info()
    check(calls.hits + calls.misses >= before + 2,
          "warm waves never reached the pallas kernels")
    check(max(errs) <= 1e-4, f"warm waves pallas vs vector: {errs}")
    emit("fabric_warm", engine="pallas", vs="vector", waves=2,
         messages_per_wave=len(cols["t_ready"]), max_rel_err=max(errs))

    # The steady-state driver on the pallas engine: its bit-identical
    # scalar route, which every single-flow batch takes.
    kw = dict(n_iters=8, n_threads=4, theta=64, part_bytes=4096.0,
              n_vcis=2)
    ss_p = sim.simulate_steady_state("part", engine="pallas", **kw)
    ss_v = sim.simulate_steady_state("part", engine="vector", **kw)
    check(ss_p.iter_times_s == ss_v.iter_times_s
          and ss_p.n_messages == ss_v.n_messages,
          "steady state: pallas engine differs from vector")
    emit("fabric_steady", engine="pallas", vs="vector", iters=kw["n_iters"],
         messages=ss_p.n_messages, amortized_s=ss_p.amortized_s,
         identical=True)


def wave_traffic(seed: int, n_ranks: int, per_rank: int) -> dict:
    """One admission wave of ``advance`` columns, drawn from ``seed``:
    every rank sends ``per_rank`` messages to ranks 1, 16 and 256 away
    (mod ``n_ranks``), sizes spanning the eager, bcopy and rendezvous
    protocols, rows in processing (``t_ready``) order."""
    rng = np.random.default_rng(seed)
    n = n_ranks * per_rank
    src = rng.permutation(np.repeat(np.arange(n_ranks), per_rank))
    hop = rng.choice(np.array([1, -1, 16, -16, 256, -256]), n)
    return dict(t_ready=np.sort(rng.uniform(0.0, 20e-6, n)),
                nbytes=rng.choice(np.array([512.0, 4096.0, 65536.0]), n),
                vci=rng.integers(0, 4, n), thread=rng.integers(0, 2, n),
                put=rng.random(n) < 0.25, am_copy=np.zeros(n, dtype=bool),
                src=src, dst=(src + hop) % n_ranks)


def _train_args(model_args, sync: str, extra=()):
    from repro.launch import train
    return train.parse_args([*model_args, *TRAIN_ARGS, "--sync", sync,
                             "--ckpt-dir", str(CKPT_DIR),
                             "--ckpt-every", "0", "--log-every", "1",
                             *extra])


def phase_train(model_args=MODEL_ARGS) -> None:
    from repro.launch import train
    args = _train_args(model_args, "partitioned")
    t0 = time.perf_counter()
    report = train.run(args)
    wall = time.perf_counter() - t0
    shutil.rmtree(CKPT_DIR, ignore_errors=True)
    check(report.steps_run == args.steps, f"ran {report.steps_run} steps")
    check(all(math.isfinite(v) for v in report.losses),
          f"non-finite loss: {report.losses}")
    cfg = train.model_config(args)
    emit("train", arch=cfg.name, layers=cfg.n_layers, d_model=cfg.d_model,
         params=cfg.param_count(), sync=args.sync,
         tokens_per_step=args.global_batch * args.seq_len,
         losses=report.losses, first_step_s=report.step_s[0],
         later_step_s=report.step_s[1:], wall_s=wall)


def phase_serve(model_args=MODEL_ARGS, batch: int = 4, prompt: int = 64,
                gen: int = 8) -> None:
    from repro.launch import train
    from repro.launch.steps import (StepConfig, make_decode_step,
                                    make_prefill_step)
    from repro.models import lm
    from repro.runtime import elastic

    cfg = train.model_config(train.parse_args(list(model_args)))
    # dropless routing (capacity = every token): prefill and decode
    # then route each token identically, as serving requires
    scfg = StepConfig(capacity_factor=cfg.moe.n_experts / cfg.moe.top_k
                      if cfg.moe else 0.0)
    mesh = elastic.build_mesh(elastic.plan_mesh(1, 1))
    with jax.set_mesh(mesh):
        pf, *_ = make_prefill_step(cfg, mesh, scfg, seq_len=prompt,
                                   global_batch=batch)
        df, *_ = make_decode_step(cfg, mesh, scfg, seq_len=prompt + gen,
                                  global_batch=batch)
        pcfg = cfg.with_tp(1).replace(param_dtype=scfg.param_dtype)
        params = lm.init_params(pcfg, jax.random.PRNGKey(0))
        tokens = jax.random.randint(jax.random.PRNGKey(1),
                                    (batch, prompt), 1, cfg.vocab)

        def empty_cache():
            return lm.init_cache(pcfg, batch, prompt + gen,
                                 jnp.dtype(scfg.cache_dtype))

        prefill = jax.jit(pf, donate_argnums=2)
        decode = jax.jit(df, donate_argnums=1)
        t0 = time.perf_counter()
        logits_p, cache = prefill(params, {"tokens": tokens}, empty_cache())
        logits_p.block_until_ready()
        prefill_s = time.perf_counter() - t0

        inc = empty_cache()
        times = []
        for t in range(prompt):
            t0 = time.perf_counter()
            logits_i, inc = decode(params, inc, tokens[:, t], jnp.int32(t))
            logits_i.block_until_ready()
            times.append(time.perf_counter() - t0)
        lp, li = np.asarray(logits_p), np.asarray(logits_i)
        check(np.isfinite(lp).all() and np.isfinite(li).all(),
              "non-finite logits")
        scale = float(np.abs(lp).max())
        err = float(np.abs(lp - li).max())
        check(err <= SERVE_REL_TOL * scale,
              f"prefill vs decode: max|dlogit| {err} > "
              f"{SERVE_REL_TOL} x {scale}")

        tok = jnp.argmax(logits_p[:, :cfg.vocab], axis=-1).astype(jnp.int32)
        out = []
        for t in range(prompt, prompt + gen):
            out.append(np.asarray(tok))
            logits, cache = decode(params, cache, tok, jnp.int32(t))
            tok = jnp.argmax(logits[:, :cfg.vocab], axis=-1).astype(jnp.int32)
        check(bool(jnp.isfinite(logits).all()), "non-finite decode logits")
    emit("serve", arch=cfg.name, layers=cfg.n_layers, dtype=scfg.param_dtype,
         batch=batch, prompt=prompt, generated=len(out),
         max_abs_dlogit=err, max_abs_logit=scale,
         rel_err=err / scale, rel_tol=SERVE_REL_TOL,
         agree_argmax=float((lp.argmax(-1) == li.argmax(-1)).mean()),
         first_prefill_s=prefill_s, first_decode_s=times[0],
         median_decode_s=float(np.median(times[1:])))


def phase_sync4(model_args=MODEL_ARGS, chips: int = 4) -> None:
    """Data-parallel training on a data=4 mesh, partitioned vs bulk."""
    from repro.data import pipeline
    from repro.launch import hlo_analysis, train
    from repro.launch.steps import make_train_step
    from repro.runtime import elastic

    out = {}
    for sync in ("partitioned", "bulk"):
        args = _train_args(model_args, sync)
        report = train.run(args)
        shutil.rmtree(CKPT_DIR, ignore_errors=True)
        check(all(math.isfinite(v) for v in report.losses),
              f"{sync}: non-finite loss {report.losses}")
        cfg = train.model_config(args)
        plan = elastic.plan_mesh(chips, 1)
        mesh = elastic.build_mesh(plan)
        scfg = train.step_config(args, plan)
        with jax.set_mesh(mesh):
            _, _, batch_structs, grad_fn = make_train_step(
                cfg, mesh, scfg, seq_len=args.seq_len,
                global_batch=args.global_batch)
            params = train.build_state(cfg.with_tp(1), mesh, scfg)["params"]
            stream = pipeline.for_model(cfg, args.seq_len, args.global_batch)
            batch = train.put_batch(stream.batch(0), batch_structs)
            with jax.default_matmul_precision("highest"):
                compiled = jax.jit(grad_fn).lower(params, batch).compile()
            loss, grads = compiled(params, batch)
            n_ar = hlo_analysis.analyze_hlo(
                compiled.as_text()).counts.get("all-reduce", 0)
            check(n_ar > 0, f"{sync}: no all-reduce in the gradient step")
            leaves = jax.tree.leaves(params)
            spans = {len(x.sharding.device_set) for x in leaves}
            tok = batch["tokens"]
            shard_rows = sorted({s.data.shape[0]
                                 for s in tok.addressable_shards})
            check(spans == {chips}, f"params span {spans} devices")
            check(len(tok.sharding.device_set) == chips
                  and shard_rows == [args.global_batch // chips],
                  f"batch not split over {chips} devices: {shard_rows}")
            out[sync] = dict(losses=report.losses, loss0=float(loss),
                             grads=jax.tree_util.tree_leaves_with_path(
                                 jax.tree.map(np.asarray, grads)),
                             all_reduces=n_ar, step_s=report.step_s,
                             param_devices=sorted(spans),
                             batch_rows_per_device=shard_rows)
        del params, grads, batch

    a, b = out["partitioned"], out["bulk"]
    loss_err = max(abs(x - y) / abs(y) for x, y in zip(a["losses"],
                                                       b["losses"]))
    grad_err, worst = max(
        (float(np.abs(x - y).max()) / max(float(np.abs(y).max()), 1e-30),
         jax.tree_util.keystr(path))
        for (path, x), (_, y) in zip(a["grads"], b["grads"]))
    check(loss_err <= SYNC_REL_TOL, f"losses disagree: {loss_err}")
    check(grad_err <= SYNC_REL_TOL,
          f"gradients disagree: {grad_err} at {worst}")
    emit("sync4", arch=ARCH, chips=chips,
         **{f"{m}_{k}": v for m, d in out.items() for k, v in d.items()
            if k != "grads"},
         max_rel_loss_diff=loss_err, max_rel_grad_diff=grad_err,
         worst_grad_leaf=worst, rel_tol=SYNC_REL_TOL)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the data-parallel sync comparison")
    args = ap.parse_args(argv)
    dev = phase_device(args.chips)
    if args.chips == 4:
        phase_sync4()
    else:
        phase_fabric()
        phase_train()
        phase_serve()
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
