"""The program's Moonlight-16B-A3B path (``deepseek_v3``: MLA without a
query low-rank, a leading dense layer, sigmoid routing with a selection
bias, shared experts, an expert share) against the plain reference
``chipbench/configs/moonlight_ref.py`` on seeded weights, at a small size
on the CPU.

Both sides compute in float32 on the CPU, where a float32 matmul is
exact float32: what differs is the order of the additions (the program
folds the rope key into one concatenated attention dot, runs experts
over capacity buffers, chunks the cross entropy), so values agree to a
few float32 ulps of their scale, amplified by the depth.  Routing is
compared exactly: with random weights no two scores tie.
"""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import harness
from chipbench.drivers import lm_program
from chipbench.drivers.train_deepseek import model_config, scope_map
from chipbench.tests import small_moonlight
from repro.launch import steps as S
from repro.models import attention, lm
from repro.models import moe as moe_mod

REF = harness.load_module(harness.PKG / "configs" / "moonlight_ref.py")
B, T = 2, 32
AMPLE = 8.0    # capacity factor with no drops at this size
F32 = {"*": "float32"}


def _setup(cf=AMPLE, **over):
    c = small_moonlight.config(**over)
    cfg = model_config(c, "float32", cf)
    p = REF.init_params(c, jax.random.PRNGKey(3), F32)
    m = REF.dims(c)
    bias = 0.02 * jax.random.normal(jax.random.PRNGKey(4), (m["L"], m["E"]))
    tok = jax.random.randint(jax.random.PRNGKey(5), (B, T + 1), 1,
                             c["vocab_size"])
    return c, cfg, p, bias, tok[:, :-1], tok[:, 1:]


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def _ref_logits(c, p, tok, bias):
    return jax.jit(lambda p, tok, bias: REF.logits(c, p, REF.hidden(
        c, p, tok, bias, capacity_factor=AMPLE)[0]))(p, tok, bias)


def test_logits_match_the_reference():
    c, cfg, p, bias, tok, _ = _setup()
    got = jax.jit(lambda p, tok, bias: lm.forward(
        cfg, p, {"tokens": tok}, route_bias=bias)[0] @ p["head"])(
            p, tok, bias)
    want = _ref_logits(c, p, tok, bias)
    # float32 reassociation over 3 layers: ~1e-6 of the logits' scale
    assert _rel(got, want) < 2e-5
    assert float(jnp.max(jnp.abs(got - want))) < 1e-4 * float(
        jnp.max(jnp.abs(want)))


def test_biased_router_refuses_a_forward_pass_without_its_bias():
    """Serving or evaluating a trained model with a zero bias would pick
    other experts than training did, so a pass without it raises, in
    the serving steps too."""
    c, cfg, p, bias, tok, lab = _setup()
    with pytest.raises(ValueError, match="route_bias"):
        lm.forward(cfg, p, {"tokens": tok})
    with pytest.raises(ValueError, match="route_bias"):
        lm.loss_fn(cfg, p, {"tokens": tok, "labels": lab})
    with pytest.raises(ValueError, match="route_bias"):
        lm.prefill(cfg, p, {"tokens": tok})


def test_loss_gradients_and_loads_match_the_reference():
    c, cfg, p, bias, tok, lab = _setup()
    (loss, stats), g = jax.jit(jax.value_and_grad(
        lambda q: lm.loss_fn(cfg, q, {"tokens": tok, "labels": lab},
                             route_bias=bias, with_stats=True),
        has_aux=True))(p)
    (rloss, rload), rg = jax.jit(jax.value_and_grad(
        lambda q: REF.loss(c, q, tok, lab, bias, capacity_factor=AMPLE),
        has_aux=True))(p)
    # the loss holds the balance term; float32 sums of 64 tokens
    assert abs(float(loss) - float(rloss)) < 1e-5 * abs(float(rloss))
    np.testing.assert_array_equal(np.asarray(stats["load"]),
                                  np.asarray(rload))
    assert float(jnp.sum(stats["balance"])) > 0
    # every leaf's gradient: float32 reassociation through the backward
    # of 3 layers and the chunked cross entropy
    gaps = jax.tree.map(_rel, g, rg)
    worst = max(jax.tree.leaves(gaps))
    assert worst < 1e-4, gaps


def _state(cfg, c, p, step):
    opt = jax.tree.map(jnp.zeros_like, p)
    return {"params": p,
            "opt": {"step": jnp.int32(step), "m": opt,
                    "v": jax.tree.map(jnp.zeros_like, p)},
            "router": REF.init_route_state(c)}


def test_one_train_step_with_bias_update_and_balance_loss():
    """The program's step (AdamW, the aux-loss-free bias update, the
    kept load) against the reference's, from optimizer step 5 so that
    the warmup's learning rate is not 0."""
    c, cfg, p, _, tok, lab = _setup(cf=1.25)
    opt = c["train"]["optimizer"]
    mesh = lm_program.mesh_for(1)
    scfg = S.StepConfig(param_dtype="float32", seq_parallel=False,
                        capacity_factor=1.25, peak_lr=opt["peak_lr"],
                        warmup_steps=opt["warmup_steps"],
                        total_steps=opt["total_steps"])
    with jax.set_mesh(mesh):
        step_fn, ss, _, _ = S.make_train_step(cfg, mesh, scfg, seq_len=T,
                                              global_batch=B)
        lm_program.check_tree(_state(cfg, c, p, 5), ss, "train state")
        new, loss = jax.jit(step_fn)(_state(cfg, c, p, 5),
                                     {"tokens": tok, "labels": lab})
    bias0 = REF.init_route_state(c)["bias"]
    (rloss, load), g = jax.jit(jax.value_and_grad(
        lambda q: REF.loss(c, q, tok, lab, bias0, capacity_factor=1.25),
        has_aux=True))(p)
    z = jax.tree.map(jnp.zeros_like, p)
    rp, _, rm, _ = jax.jit(lambda p, g, z: REF.adamw(opt, p, g, z, z, 5))(
        p, g, z)
    assert abs(float(loss) - float(rloss)) < 1e-5 * abs(float(rloss))
    np.testing.assert_array_equal(np.asarray(new["router"]["load"]),
                                  np.asarray(load))
    np.testing.assert_array_equal(np.asarray(new["router"]["bias"]),
                                  np.asarray(REF.bias_step(c, bias0, load)))
    # the clipped gradient in Adam's first moment: float32 reassociation
    gaps = jax.tree.map(_rel, new["opt"]["m"], rm)
    assert max(jax.tree.leaves(gaps)) < 1e-4, gaps
    # the parameters: Adam's first step moves each by lr * g / (|g| +
    # eps); where |g| is near eps (1e-8) the quotient carries the
    # gradient's float32 cancellation error, up to a few % of lr
    lr = opt["peak_lr"] * 5 / opt["warmup_steps"]
    worst = max(jax.tree.leaves(jax.tree.map(
        lambda a, b: float(jnp.max(jnp.abs(a - b))), new["params"], rp)))
    assert worst < 0.1 * lr
    assert int(new["opt"]["step"]) == 6


def test_prefill_then_decode_match_the_full_forward():
    """Prefill of the first T-2 tokens, then two decode steps through the
    MLA latent cache (dense prefix included), against the reference's
    logits at those positions from one full forward pass."""
    c, cfg, p, bias, tok, _ = _setup()
    want = _ref_logits(c, p, tok, bias)
    n = T - 2
    cache = lm.init_cache(cfg, B, T)
    assert set(cache) == {"ckv", "kr", "prefix"}
    logits, cache = jax.jit(lambda p, tok, cache, bias: lm.prefill(
        cfg, p, {"tokens": tok}, cache=cache, route_bias=bias))(
            p, tok[:, :n], cache, bias)
    got = [logits]
    decode = jax.jit(lambda p, cache, t, i, bias: lm.decode_step(
        cfg, p, cache, t, i, route_bias=bias))
    for i in range(n, T - 1):
        logits, cache = decode(p, cache, tok[:, i], jnp.int32(i), bias)
        got.append(logits)
    scale = float(jnp.max(jnp.abs(want)))
    for j, g in enumerate(got):
        # float32: the cache path sums the same terms in another order
        assert float(jnp.max(jnp.abs(g - want[:, n - 1 + j]))) \
            < 1e-4 * scale


def test_expert_shares_add_up_to_the_uncut_layer():
    """The parts that 4 shares of 4 experts give, with the shared experts
    counted once, add up to the uncut layer's output, in the program and
    against the reference's uncut layer; capacity as run (1.25), which
    drops the same pairs in every share."""
    c, cfg, p, bias, tok, _ = _setup(cf=1.25)
    m = REF.dims(c)
    x = 0.5 * jax.random.normal(jax.random.PRNGKey(7), (B, T, m["d"]))
    full = jax.tree.map(lambda a: a[0], p["layers"]["moe"])
    keys = jax.random.split(jax.random.PRNGKey(8), 3)
    # the uncut layer's 16 experts: the held 4 in place, 12 more drawn
    for name, k in zip(("w_gate", "w_up", "w_down"), keys):
        w = full[name]
        more = jax.random.normal(k, (m["E"] - m["held"], *w.shape[1:])) \
            / np.sqrt(w.shape[1])
        full[name] = jnp.concatenate([more[:m["first"]], w,
                                      more[m["first"]:]])
    uncut_mo = dataclasses.replace(cfg.moe, held=0, first=0)
    layer = jax.jit(lambda p, x, bias, mo: moe_mod.routed_moe_fwd(
        p, x, mo=mo, bias=bias)[0], static_argnums=3)
    uncut = layer(full, x, bias[0], uncut_mo)
    shared = moe_mod.swiglu(full["shared"], x.reshape(-1, m["d"])
                            ).reshape(x.shape)
    parts = 0.0
    for first in range(0, m["E"], m["held"]):
        mo = dataclasses.replace(cfg.moe, first=first)
        mine = {**full, **{n: full[n][first:first + m["held"]]
                           for n in ("w_gate", "w_up", "w_down")}}
        parts = parts + layer(mine, x, bias[0], mo) - shared
    parts = parts + shared
    scale = float(jnp.max(jnp.abs(uncut)))
    assert float(jnp.max(jnp.abs(parts - uncut))) < 1e-5 * scale
    cu = dict(c, expert_share=dict(c["expert_share"], first=0,
                                   held=m["E"]))
    want = jax.jit(lambda p, x, b: REF._moe(cu, p, x, b, 1.25, None)[0])(
        full, x, bias[0])
    assert float(jnp.max(jnp.abs(uncut - want))) < 1e-5 * scale


@pytest.mark.parametrize("fault", REF.FAULTS)
def test_each_planted_fault_changes_the_loss(fault):
    """Each fault the benchmark plants in the reference is visible in its
    loss at this size (and the bias fault needs a bias to ignore)."""
    c, _, p, bias, tok, lab = _setup(cf=1.25)
    loss = jax.jit(lambda p, bias, fault: REF.loss(
        c, p, tok, lab, bias, capacity_factor=1.25, fault=fault)[0],
        static_argnums=2)
    sound, bad = loss(p, bias, None), loss(p, bias, fault)
    assert abs(float(bad) - float(sound)) > 1e-4 * abs(float(sound))


def test_attention_backward_stays_in_its_scope():
    """Every instruction of the compiled train step that holds a score
    tile of a query chunk against its key prefix, (q_chunk, j q_chunk)
    for every j up to Sk / q_chunk, carries the ``repro.mla`` scope, by
    the join the benchmark's scope split makes (``scope_map``), so that
    every chunk of the recompute backward is counted in
    ``moonlight.mla_ms``.  Parameters and tuple elements are names of
    values, not device ops, and so is a scalar constant's splat inside a
    fusion: it is an operand of the fusion's elementwise ops, which take
    their scope from their own metadata, while the splat keeps the
    constant's, the scope of the remat'd layer around it."""
    t, qc = 80, 20   # sizes no other tensor of the small model has
    c = small_moonlight.config()
    cfg = model_config(c, "float32", 1.25).replace(q_chunk=qc)
    opt = c["train"]["optimizer"]
    mesh = lm_program.mesh_for(1)
    scfg = S.StepConfig(param_dtype="float32", seq_parallel=False,
                        capacity_factor=1.25, peak_lr=opt["peak_lr"],
                        warmup_steps=opt["warmup_steps"],
                        total_steps=opt["total_steps"])
    before = attention.recompute_stats()["chunked_vjp"]
    with jax.set_mesh(mesh):
        step_fn, ss, bs, _ = S.make_train_step(cfg, mesh, scfg, seq_len=t,
                                               global_batch=B)
        text = jax.jit(step_fn).lower(ss, bs).compile().as_text()
    assert attention.recompute_stats()["chunked_vjp"] > before
    scopes = scope_map(text)
    tiles = {w: re.compile(
        rf"\b(?:f32|bf16)\[[\d,]*\b(?:{qc},{w}|{w},{qc})\]")
        for w in range(qc, t + 1, qc)}   # each chunk's key prefix
    instr = re.compile(r"^\s*(?:ROOT )?(%[\w.\-]+) = (\S+) ([a-z][\w\-]*)\(")
    seen, outside, fused = dict.fromkeys(tiles, 0), [], False
    for line in text.splitlines():
        if not line.startswith(" "):   # a computation's header or end
            fused = line.startswith("%fused_computation")
        m = instr.match(line)
        if not m or m.group(3) in ("parameter", "get-tuple-element") or (
                fused and m.group(3) == "broadcast"
                and "broadcast(%constant" in line):
            continue
        hit = [w for w, tile in tiles.items() if tile.search(m.group(2))]
        for w in hit:
            seen[w] += 1
        if hit and scopes.get(m.group(1)) != "repro.mla":
            outside.append(line.strip()[:200])
    assert all(seen.values()), seen
    assert not outside, outside
