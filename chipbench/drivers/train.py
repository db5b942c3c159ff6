"""Driver of training cells: the program's jitted train step, window-fed.

Set-up builds one object — the compiled step (``make_train_step`` under
``jax.jit``, state donated) with its state, the weights made from the
seed in one jitted call — and drives it through its first steps with
the window's own call and feed (``pipeline.for_model`` batches placed by
``put_batch``).  Those steps are what the reference follows.  The window
then runs the same object on: each step's tokens count once its loss is
on the device; two steps may be in flight.  Checkpointing is off.

Traffic keys: ``seq_len``, ``batch_per_chip``, ``sync`` (gradient sync
mode), ``data`` (``mean_doc_len``, ``eos_id``), ``checked_steps`` and
``limits``.
"""

from __future__ import annotations

import collections
import sys
import time

import numpy as np

from chipbench import flops, harness
from chipbench.drivers import lm_program
from chipbench.harness import Check, Outcome, span


def packed_rows(seed: int, step: int, batch: int, seq: int, vocab: int,
                mean_doc_len: int, eos_id: int) -> np.ndarray:
    """(batch, seq + 1) token rows: documents of geometric length
    (mean ``mean_doc_len``) with ids uniform in [1, vocab), joined by
    ``eos_id``; row r of step s from a Philox stream keyed on the seed
    and counter (s, r)."""
    out = np.empty((batch, seq + 1), np.int32)
    for r in range(batch):
        rng = np.random.Generator(np.random.Philox(
            key=seed, counter=[step, r, 0, 0]))
        pos = 0
        while pos < seq + 1:
            n = min(1 + rng.geometric(1.0 / mean_doc_len), seq + 1 - pos)
            out[r, pos:pos + n] = rng.integers(1, vocab, size=n,
                                               dtype=np.int32)
            pos += n
            if pos < seq + 1:
                out[r, pos] = eos_id
                pos += 1
    return out


def leaf_gaps(prog: np.ndarray, ref: np.ndarray, ref_grad: np.ndarray):
    """Worst leaf's gap between two per-leaf norms, against the larger of
    the reference leaf's norm and the median leaf's, and that leaf's
    index.  Leaves whose reference gradient is under a thousandth of the
    median leaf's are left out: they move by round-off alone."""
    keep = ref_grad >= 1e-3 * np.median(ref_grad)
    floor = np.median(ref[keep])
    gap = np.where(keep, np.abs(prog - ref) / np.maximum(ref, floor), 0.0)
    return float(np.max(gap)), int(np.argmax(gap))


def run(cell: harness.Cell) -> Outcome:
    import jax
    import jax.numpy as jnp
    from repro.data import pipeline
    from repro.launch import steps as S
    from repro.launch.train import put_batch
    from repro.optim.adamw import AdamWConfig

    c, tr = cell.config, cell.traffic
    tc = c["train"]
    ref = harness.reference(c)
    opt = tc["optimizer"]
    n = cell.chips
    seq, bpc = tr["seq_len"], tr["batch_per_chip"]
    gb = bpc * n
    cfg = lm_program.model_config(c, tc["param_dtype"],
                                  tc["capacity_factor"])
    mesh = lm_program.mesh_for(n)
    scfg = S.StepConfig(
        sync_mode=tr["sync"], param_dtype=tc["param_dtype"],
        peak_lr=opt["peak_lr"], warmup_steps=opt["warmup_steps"],
        total_steps=opt["total_steps"], seq_parallel=False,
        capacity_factor=tc["capacity_factor"],
        adam=AdamWConfig(b1=opt["b1"], b2=opt["b2"], eps=opt["eps"],
                         weight_decay=opt["weight_decay"],
                         clip_norm=opt["clip_norm"]))
    key = jax.random.PRNGKey(cell.seed)
    data = tr["data"]
    n_check = tr["checked_steps"]

    with jax.set_mesh(mesh):
        step_fn, state_structs, batch_structs, _ = S.make_train_step(
            cfg, mesh, scfg, seq_len=seq, global_batch=gb)
        jit_step = jax.jit(step_fn, donate_argnums=0)
        shardings = jax.tree.map(lambda s: s.sharding, state_structs)
        pdt = {"*": tc["param_dtype"]}

        def init_state(k):
            p = ref.init_params(c, k, pdt)
            z = jax.tree.map(jnp.zeros_like, p)
            return {"params": p,
                    "opt": {"step": jnp.zeros((), jnp.int32), "m": z,
                            "v": jax.tree.map(jnp.zeros_like, p)}}

        lm_program.check_tree(jax.eval_shape(init_state, key),
                              state_structs, "train state")
        with span("init"):
            state = jax.jit(init_state, out_shardings=shardings)(key)
        stream = pipeline.for_model(cfg, seq, gb, seed=cell.seed)

        def batch(i):
            return put_batch(stream.batch(i), batch_structs)

        # set-up: the first steps through the window's own call and feed
        losses, first_grad = [], None
        norms = jax.jit(ref.leaf_norms)
        for i in range(n_check):
            state, loss = jit_step(state, batch(i))
            losses.append(float(loss))
            if i == 0:
                first_grad = np.asarray(norms(state["opt"]["m"])) \
                    / (1.0 - opt["b1"])
        change = np.asarray(jax.jit(lambda p, k: ref.leaf_norms(jax.tree.map(
            lambda a, b: a - b, p, ref.init_params(c, k, pdt))))(
                state["params"], key))
        setup_s = time.perf_counter() - cell.t_process

        steps = 0
        inflight = collections.deque()
        with harness.Window(cell.name, cell.seconds, cell.trace) as win:
            i = n_check
            while win.running():
                with span("batch"):
                    b = batch(i)
                with span("step"):
                    state, loss = jit_step(state, b)
                inflight.append(loss)
                if len(inflight) > 2:
                    with span("wait"):
                        inflight.popleft().block_until_ready()
                i += 1
                steps += 1
            with span("wait"):
                for x in inflight:
                    x.block_until_ready()
        finite = bool(np.isfinite(float(loss)))
        mem = harness.memory_peak(cell.devices)
        del state, b, loss, inflight

    # the reference, once the program's state is freed
    rows = [packed_rows(cell.seed, i, gb, seq, c["vocab_size"],
                        data["mean_doc_len"], data["eos_id"])
            for i in range(n_check)]
    fed = [stream.batch(i) for i in range(n_check)]
    mismatch = sum(int(np.sum(np.any(r[:, :-1] != f["tokens"], axis=1)
                              | np.any(r[:, 1:] != f["labels"], axis=1)))
                   for r, f in zip(rows, fed))
    want = ref.train_readings(c, key, [(r[:, :-1], r[:, 1:]) for r in rows],
                              devices=cell.devices)
    checks = compare(losses, first_grad, change, want, tr["limits"])
    checks.append(Check("data_rows_mismatch", float(mismatch), 0.0))
    checks.append(Check("window_compiles", float(win.compiles), 0.0))
    checks.append(Check("loss_not_finite", 0.0 if finite else 1.0, 0.0))
    tokens = steps * gb * seq
    return Outcome(
        e2e={"train_tokens_per_s": tokens / win.wall_s},
        setup_s=setup_s, attempted=steps, failed=0, checks=checks,
        counters={"train_steps": steps, "window_s": win.wall_s,
                  "seq": seq, "batch_per_chip": bpc, "chips": n,
                  "shape": flops.LMShape.from_config(c).__dict__},
        memory_peak_bytes=mem, window=win)


def compare(losses, first_grad, change, want, lim) -> list:
    """The set-up steps against the reference's: the worst step's loss
    gap and the worst leaf's gap in the parameters' change.  The first
    gradient's worst-leaf gap is printed, not compared: on the chip it
    has no reading that separates it from lower precision (PERF.md)."""
    loss_gap = max(abs(a - b) / abs(b) for a, b in zip(losses,
                                                       want["losses"]))
    grad_gap, gi = leaf_gaps(first_grad, want["first_grad"],
                             want["first_grad"])
    change_gap, ci = leaf_gaps(change, want["change"], want["first_grad"])
    print(f"info first-gradient gap {grad_gap!r} (worst leaf #{gi} of "
          f"{len(change)}); change gap worst leaf #{ci}; losses {losses} "
          f"vs {want['losses']}", file=sys.stderr)
    return [Check("loss_rel_gap", loss_gap, lim["loss_rel_gap"]),
            Check("update_norm_gap", change_gap, lim["update_norm_gap"])]
