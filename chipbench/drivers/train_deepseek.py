"""Driver of DeepSeek-V3-style training cells (``model_type``
``deepseek_v3``: latent attention, sigmoid routing with a selection bias,
shared experts, leading dense layers, an expert share).

As the ``train`` driver: set-up compiles the program's jitted train step
(``make_train_step``, state donated), makes its state from the seed with
the reference's layout, and drives it through its first steps with the
window's own call and feed; the window runs the same compiled step on,
two steps in flight.  The state adds the routing part (``router``: the
selection bias and the last step's routed pairs per expert).

``correct`` compares the set-up steps with the plain reference: the loss
and the parameters' change (``train.compare``) and the routing bias after
them (``route_bias_gap``).  The counters hand the readers the last step's
per-expert load and the compiled step's map from HLO instruction names to
the model's ``repro.*`` scopes (from its ``op_name`` metadata), which
joins the trace's device ops to the layers.

Traffic keys as the ``train`` driver's; ``limits`` adds
``route_bias_gap``.  ``python -m chipbench.readings_deepseek`` takes the
readings its limits are set from.
"""

from __future__ import annotations

import collections
import dataclasses
import re
import sys
import time
from typing import Dict

import numpy as np

from chipbench import flops_mla, harness
from chipbench.drivers import lm_program
from chipbench.drivers.train import compare as compare_train
from chipbench.drivers.train import packed_rows
from chipbench.harness import Check, Outcome, span

# "%fusion.3 = ... metadata={op_name="jit(step_fn)/.../repro.mla/dot" ...}"
_INSTR = re.compile(r"^\s*(?:ROOT )?(%[\w.\-]+) = .*?"
                    r"metadata=\{[^}]*?op_name=\"([^\"]*)\"")
_SCOPE = re.compile(r"repro\.[A-Za-z_][\w.]*")


def model_config(c: dict, param_dtype: str, capacity_factor: float):
    """The program's ``ModelConfig`` of a ``deepseek_v3`` configuration
    file (its keys as run)."""
    from repro.configs import get_config
    from repro.models.lm import MLAConfig
    base = get_config(c["arch"])
    share = c["expert_share"]
    moe = dataclasses.replace(
        base.moe, n_experts=share["router_experts"],
        top_k=c["num_experts_per_tok"], d_expert=c["moe_intermediate_size"],
        capacity_factor=capacity_factor,
        dispatch_chunk=c["moe_dispatch_chunk"],
        min_capacity=c["moe_min_capacity"], score=c["scoring_func"],
        routed_scale=c["routed_scaling_factor"],
        n_shared=c["n_shared_experts"], held=share["held"],
        first=share["first"], bias_rate=c["routing_bias_rate"],
        balance_weight=c["seq_aux_weight"] if c["seq_aux"] else 0.0)
    mla = MLAConfig(q_lora=c["q_lora_rank"], kv_lora=c["kv_lora_rank"],
                    qk_nope=c["qk_nope_head_dim"],
                    qk_rope=c["qk_rope_head_dim"], v_dim=c["v_head_dim"])
    cfg = base.replace(
        n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"], n_kv=c["num_key_value_heads"],
        head_dim=0, d_ff=c["intermediate_size"], vocab=c["vocab_size"],
        rope_theta=float(c["rope_theta"]), norm_eps=c["rms_norm_eps"],
        first_dense=c["first_k_dense_replace"],
        tie_embeddings=c["tie_word_embeddings"],
        qkv_bias=c["attention_bias"], mla=mla, moe=moe,
        param_dtype=param_dtype)
    as_stated(cfg, c)
    return cfg


def as_stated(cfg, c: dict) -> None:
    """Raise where the program departs from the configuration file."""
    want = {
        "mixer": "attn", "window_pattern": "global", "attn_softcap": None,
        "final_softcap": None, "post_norm": False,
        "zero_centered_norm": False, "emb_scale": False,
        "frontend": "tokens", "mamba": None, "mrope_sections": None,
        "q_scale": None, "qkv_bias": False,
    }
    bad = {k: getattr(cfg, k) for k, v in want.items()
           if getattr(cfg, k) != v}
    # what the program runs of DeepSeek-V3's routing and stack
    stated = {"model_type": "deepseek_v3", "hidden_act": "silu",
              "topk_method": "noaux_tc", "scoring_func": "sigmoid",
              "norm_topk_prob": True, "n_group": 1, "topk_group": 1,
              "moe_layer_freq": 1, "num_nextn_predict_layers": 0}
    bad.update({k: c[k] for k, v in stated.items() if c[k] != v})
    if c["num_key_value_heads"] != c["num_attention_heads"]:
        bad["num_key_value_heads"] = c["num_key_value_heads"]
    if c["n_routed_experts"] != c["expert_share"]["held"]:
        bad["n_routed_experts"] = c["n_routed_experts"]
    if bad:
        raise ValueError(f"{c['arch']}: the program would not run the "
                         f"configuration as stated: {bad}")


def scope_map(hlo_text: str) -> Dict[str, str]:
    """Instruction name -> innermost ``repro.*`` scope in its ``op_name``
    metadata, for every instruction of a compiled module that has one.
    A fusion carries the metadata of the instruction it was built
    around; backward and recomputed ops keep the scope in their path."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m:
            scopes = _SCOPE.findall(m.group(2))
            if scopes:
                out[m.group(1)] = scopes[-1]
    return out


def batches(cell: harness.Cell) -> list:
    """The checked steps' (tokens, labels) as the generator makes them."""
    c, tr = cell.config, cell.traffic
    rows = [packed_rows(cell.seed, i, tr["batch_per_chip"] * cell.chips,
                        tr["seq_len"], c["vocab_size"],
                        tr["data"]["mean_doc_len"], tr["data"]["eos_id"])
            for i in range(tr["checked_steps"])]
    return [(r[:, :-1], r[:, 1:]) for r in rows]


def compare(prog: dict, want: dict, lim: dict) -> list:
    """The set-up steps against the reference's: ``train.compare``'s
    checks, and the routing bias after the steps: the summed gap over
    the bias entries, over the reference's summed change of them."""
    checks = compare_train(prog["losses"], prog["first_grad"],
                           prog["change"], want, lim)
    moved = np.abs(want["bias"] - want["bias_start"]).sum()
    gap = np.abs(prog["bias"] - want["bias"]).sum() / max(float(moved),
                                                          1e-30)
    checks.append(Check("route_bias_gap", float(gap), lim["route_bias_gap"]))
    return checks


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
    cfg = model_config(c, tc["param_dtype"], tc["capacity_factor"])
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
    n_check = tr["checked_steps"]

    with jax.set_mesh(mesh):
        step_fn, state_structs, batch_structs, _ = S.make_train_step(
            cfg, mesh, scfg, seq_len=seq, global_batch=gb)
        step = jax.jit(step_fn, donate_argnums=0).lower(
            state_structs, batch_structs).compile()
        scopes = scope_map(step.as_text())
        shardings = jax.tree.map(lambda s: s.sharding, state_structs)
        pdt = {"*": tc["param_dtype"]}

        def init_state(k):
            p = ref.init_params(c, k, pdt)
            z = jax.tree.map(jnp.zeros_like, p)
            return {"params": p,
                    "opt": {"step": jnp.zeros((), jnp.int32), "m": z,
                            "v": jax.tree.map(jnp.zeros_like, p)},
                    "router": ref.init_route_state(c, k)}

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
            state, loss = step(state, batch(i))
            losses.append(float(loss))
            if i == 0:
                first_grad = np.asarray(norms(state["opt"]["m"])) \
                    / (1.0 - opt["b1"])
        change = np.asarray(jax.jit(lambda p, k: ref.leaf_norms(jax.tree.map(
            lambda a, b: a - b, p, ref.init_params(c, k, pdt))))(
                state["params"], key))
        bias = np.asarray(state["router"]["bias"])
        setup_s = time.perf_counter() - cell.t_process

        steps = 0
        inflight = collections.deque()
        done = []  # host clock as each step's loss is seen ready
        with harness.Window(cell.name, cell.seconds, cell.trace) as win:
            i = n_check
            while win.running():
                with span("batch"):
                    b = batch(i)
                with span("step"):
                    state, loss = step(state, b)
                inflight.append(loss)
                if len(inflight) > 2:
                    with span("wait"):
                        inflight.popleft().block_until_ready()
                    done.append(time.perf_counter())
                i += 1
                steps += 1
            with span("wait"):
                for x in inflight:
                    x.block_until_ready()
                    done.append(time.perf_counter())
        gaps = np.diff([win.t0] + done)
        print(f"info window: {steps} steps in {win.wall_s!r} s; steps seen "
              f"done every {float(np.median(gaps))!r} s (median), longest "
              f"{float(gaps.max())!r} s before step {int(gaps.argmax())}",
              file=sys.stderr)
        finite = bool(np.isfinite(float(loss)))
        load = np.asarray(state["router"]["load"])
        mem = harness.memory_peak(cell.devices)
        del state, b, loss, inflight, step

    # the reference, once the program's state is freed
    checked = batches(cell)
    fed = [stream.batch(i) for i in range(n_check)]
    mismatch = sum(int(np.sum(np.any(t != f["tokens"], axis=1)
                              | np.any(lab != f["labels"], axis=1)))
                   for (t, lab), f in zip(checked, fed))
    want = ref.train_readings(c, key, checked, devices=cell.devices)
    checks = compare({"losses": losses, "first_grad": first_grad,
                      "change": change, "bias": bias}, want, tr["limits"])
    checks.append(Check("data_rows_mismatch", float(mismatch), 0.0))
    checks.append(Check("window_compiles", float(win.compiles), 0.0))
    checks.append(Check("loss_not_finite", 0.0 if finite else 1.0, 0.0))
    share = c["expert_share"]
    cap = cfg.moe.capacity(min(cfg.moe.dispatch_chunk, gb * seq))
    return Outcome(
        e2e={"train_tokens_per_s": steps * gb * seq / win.wall_s},
        setup_s=setup_s, attempted=steps, failed=0, checks=checks,
        counters={"train_steps": steps, "window_s": win.wall_s,
                  "seq": seq, "batch_per_chip": bpc, "chips": n,
                  "shape": flops_mla.MLAShape.from_config(c).__dict__,
                  "load": load.tolist(), "held": [share["first"],
                                                  share["held"]],
                  "capacity": cap, "scopes": scopes},
        memory_peak_bytes=mem, window=win)


# ---------------------------------------------------------------------------
# Readings for the limits (``python -m chipbench.readings_deepseek``)
# ---------------------------------------------------------------------------

_WANT: Dict[int, dict] = {}  # seed -> the reference's sound readings


def control(cell: harness.Cell, fault=None) -> dict:
    """The bfloat16 control's readings: the reference in the program's
    place one precision step down; with ``fault``, instead the sound
    precision with that fault planted in the reference."""
    import jax
    c = cell.config
    ref = harness.reference(c)
    checked = batches(cell)
    key = jax.random.PRNGKey(cell.seed)
    if cell.seed not in _WANT:
        _WANT[cell.seed] = ref.train_readings(c, key, checked,
                                              devices=cell.devices)
    if fault is None:
        low = ref.train_readings(c, key, checked, dtype="bfloat16",
                                 precision="default", devices=cell.devices)
    else:
        low = ref.train_readings(c, key, checked, fault=fault,
                                 devices=cell.devices)
    checks = compare(low, _WANT[cell.seed], cell.traffic["limits"])
    print(f"info {fault or 'control'} seed {cell.seed}: "
          f"{ {k.name: k.value for k in checks} }", file=sys.stderr)
    return {k.name: k.value for k in checks}


def faults(cell: harness.Cell) -> dict:
    """The planted faults of these cells, by name."""
    ref = harness.reference(cell.config)
    return {f: (lambda f=f: control(cell, f)) for f in ref.FAULTS}
