"""Plain float32 reference of a DeepSeek-V3-style decoder (Moonlight-16B-A3B,
HF ``model_type`` ``deepseek_v3``), written from DeepSeek-V3's description.

Per layer: RMSNorm -> multi-head latent attention -> residual -> RMSNorm
-> FFN -> residual; final RMSNorm; logits = h @ head (untied).  Every
RMSNorm uses ``rms_norm_eps``.

* Attention (``q_lora_rank`` null): q = x W_q, per head ``qk_nope`` +
  ``qk_rope`` dims; the latent c = RMSNorm(x W_dkv) (``kv_lora_rank``);
  k_nope = c W_uk and v = c W_uv per head; one rope key x W_kr shared by
  every head.  score = (q_nope . k_nope + rope(q_pe) . rope(k_pe)) /
  sqrt(qk_nope + qk_rope), causal softmax, out = (probs v) W_o.  Rotary
  convention: rotate-half over the rope dims (``rope_theta``).  DeepSeek's
  checkpoints pair those dims interleaved; with random weights that is
  one fixed permutation of the rope columns of W_q and W_kr, and the
  program uses rotate-half too.
* The first ``first_k_dense_replace`` layers: a SwiGLU of
  ``intermediate_size``.
* Every later layer: ``noaux_tc`` routing.  s = sigmoid(x W_r) over all
  ``expert_share.router_experts`` experts; the top ``num_experts_per_tok``
  of s + bias are picked (the bias picks, never weighs); their s are
  renormalised to sum 1 and scaled by ``routed_scaling_factor``.  With a
  capacity factor an expert takes at most ``ceil(chunk * k / E *
  factor)`` (at least ``moe_min_capacity``) of a dispatch chunk's
  (token, slot) pairs, in token-major order, and drops the rest.  The
  layer computes the held experts' part alone (``expert_share``: ``held``
  experts from ``first``; every held expert computes every token and the
  router's weights select), plus ``n_shared_experts`` shared experts (one
  SwiGLU of ``n_shared_experts * moe_intermediate_size``).
* Training: loss = mean next-token cross entropy + sum over MoE layers of
  the sequence-wise balance loss, ``seq_aux_weight`` x the mean over
  sequences of sum_i f_i P_i (f_i = E / (k S) x the sequence's picks of
  expert i, a constant; P_i = the sequence's mean of s_i / sum_j s_j).
  AdamW as the granite reference's; after each step each MoE layer's
  bias moves by ``routing_bias_rate`` x sign(mean load - load), the load
  being the step's picks per expert (before capacity).

All sizes are read from the configuration file, which states them as
run.  The parameter layout is the one the benchmark hands the program.
"""

from __future__ import annotations

import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import harness

# the optimizer, learning rate and norms are the granite reference's
_GRANITE = harness.load_module(harness.PKG / "configs" / "granite_moe_ref.py")
adamw = _GRANITE.adamw
leaf_norms = _GRANITE.leaf_norms

# Attention takes this many queries at a time (memory only).
QUERY_BLOCK = 1024

# Faults the benchmark plants here (``python -m chipbench.readings``).
FAULTS = ("no_shared", "bias_ignored", "dense_routed", "half_batch")


def dims(c: dict) -> dict:
    share = c["expert_share"]
    return dict(d=c["hidden_size"], H=c["num_attention_heads"],
                nope=c["qk_nope_head_dim"], rope=c["qk_rope_head_dim"],
                v=c["v_head_dim"], r=c["kv_lora_rank"],
                f=c["intermediate_size"], fe=c["moe_intermediate_size"],
                E=share["router_experts"], held=share["held"],
                first=share["first"], k=c["num_experts_per_tok"],
                shared=c["n_shared_experts"], V=c["vocab_size"],
                nd=c["first_k_dense_replace"],
                L=c["num_hidden_layers"] - c["first_k_dense_replace"])


def param_shapes(c: dict) -> Dict:
    """Leaf shapes, and each leaf's fan-in (0: an RMSNorm scale; -1: the
    embedding, scaled by the hidden size)."""
    m = dims(c)
    d, H, r, nd, L = m["d"], m["H"], m["r"], m["nd"], m["L"]
    qk = m["nope"] + m["rope"]

    def attn(n):
        return {"w_q": ((n, d, H, qk), d), "w_dkv": ((n, d, r), d),
                "norm_kv": ((n, r), 0), "w_uk": ((n, r, H, m["nope"]), r),
                "w_uv": ((n, r, H, m["v"]), r),
                "w_kr": ((n, d, m["rope"]), d),
                "wo": ((n, H, m["v"], d), H * m["v"])}

    def swiglu(n, f):
        return {"w_gate": ((n, d, f), d), "w_up": ((n, d, f), d),
                "w_down": ((n, f, d), f)}

    fs = m["shared"] * m["fe"]
    moe = {"router": ((L, d, m["E"]), d),
           "w_gate": ((L, m["held"], d, m["fe"]), d),
           "w_up": ((L, m["held"], d, m["fe"]), d),
           "w_down": ((L, m["held"], m["fe"], d), m["fe"])}
    if fs:
        moe["shared"] = swiglu(L, fs)
    tree = {"embed": ((m["V"], d), -1), "final_norm": ((d,), 0),
            "head": ((d, m["V"]), d),
            "layers": {"ln1": ((L, d), 0), "attn": attn(L),
                       "ln2": ((L, d), 0), "moe": moe}}
    if nd:
        tree["prefix"] = {"ln1": ((nd, d), 0), "attn": attn(nd),
                          "ln2": ((nd, d), 0), "mlp": swiglu(nd, m["f"])}
    return tree


def _is_spec(x):
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple)


def init_params(c: dict, key, dtypes: Dict[str, str]) -> Dict:
    """Weights drawn from ``key`` as the granite reference draws them:
    normal / sqrt(fan-in) (the embedding: / sqrt(d)), RMSNorm scales 1.
    ``dtypes`` maps a leaf name to its type (``"*"`` for the rest)."""
    d = c["hidden_size"]
    leaves, tree = jax.tree_util.tree_flatten_with_path(param_shapes(c),
                                                        is_leaf=_is_spec)
    out = []
    for i, (path, (shape, fan_in)) in enumerate(leaves):
        name = jax.tree_util.keystr(path).split("'")[-2]
        dt = jnp.dtype(dtypes.get(name, dtypes["*"]))
        if fan_in == 0:
            out.append(jnp.ones(shape, dt))
            continue
        scale = 1.0 / math.sqrt(d if fan_in < 0 else fan_in)
        w = jax.random.normal(jax.random.fold_in(key, i), shape,
                              jnp.float32) * scale
        out.append(w.astype(dt))
    return jax.tree_util.tree_unflatten(tree, out)


def init_route_state(c: dict, key=None) -> Dict:
    """The routing state a run starts from: with ``key``, a bias drawn
    normal with standard deviation ``routing_bias_init_std`` (a bias
    that training has moved); without, zeros."""
    m = dims(c)
    z = jnp.zeros((m["L"], m["E"]), jnp.float32)
    if key is None:
        return {"bias": z, "load": z}
    bias = jax.random.normal(jax.random.fold_in(key, 1 << 16), z.shape,
                             jnp.float32) * c["routing_bias_init_std"]
    return {"bias": bias, "load": z}


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _rms(x, w, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * w.astype(jnp.float32)).astype(x.dtype)


def _rope(x, theta):
    """Rotate-half rotary positions over the last dim of (B, S, ..., D)."""
    s, half = x.shape[1], x.shape[-1] // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv
    ang = ang.reshape((s,) + (1,) * (x.ndim - 3) + (half,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           -1).astype(x.dtype)


def _attention(c, a, x):
    m = dims(c)
    s = x.shape[1]
    eps = c["rms_norm_eps"]
    q = jnp.einsum("bsd,dhk->bshk", x, a["w_q"])
    q_nope, q_pe = q[..., :m["nope"]], _rope(q[..., m["nope"]:],
                                             c["rope_theta"])
    lat = _rms(jnp.einsum("bsd,dr->bsr", x, a["w_dkv"]), a["norm_kv"], eps)
    k_pe = _rope(jnp.einsum("bsd,dk->bsk", x, a["w_kr"]), c["rope_theta"])
    k_nope = jnp.einsum("bsr,rhk->bshk", lat, a["w_uk"])
    v = jnp.einsum("bsr,rhk->bshk", lat, a["w_uv"])
    scale = 1.0 / math.sqrt(m["nope"] + m["rope"])

    @jax.checkpoint
    def rows(_, blk):
        """One block of queries (from ``first``) against every key,
        causally masked."""
        qn, qp, first = blk
        scores = (jnp.einsum("bqhk,bshk->bhqs", qn, k_nope)
                  + jnp.einsum("bqhk,bsk->bhqs", qp, k_pe)
                  ).astype(jnp.float32) * scale
        qi = first + jnp.arange(qn.shape[1])[:, None]
        scores = jnp.where(jnp.arange(s)[None, :] <= qi, scores, -jnp.inf)
        probs = jax.nn.softmax(scores, -1).astype(x.dtype)
        return None, jnp.einsum("bhqs,bshk->bqhk", probs, v)

    blk = QUERY_BLOCK if s % QUERY_BLOCK == 0 else s
    n = s // blk

    def blocks(t):
        return jnp.moveaxis(t.reshape(t.shape[0], n, blk, *t.shape[2:]), 1, 0)

    _, out = jax.lax.scan(rows, None, (blocks(q_nope), blocks(q_pe),
                                       jnp.arange(n) * blk))
    out = jnp.moveaxis(out, 0, 1).reshape(x.shape[0], s, m["H"], m["v"])
    return jnp.einsum("bqhk,hkd->bqd", out, a["wo"])


def _swiglu(p, x):
    return jnp.einsum("...f,fd->...d",
                      jax.nn.silu(jnp.einsum("...d,df->...f", x, p["w_gate"]))
                      * jnp.einsum("...d,df->...f", x, p["w_up"]),
                      p["w_down"])


def _capacity(c, t, capacity_factor):
    m = dims(c)
    chunk = min(c["moe_dispatch_chunk"], t)
    if t % chunk:
        chunk = t
    cap = max(c["moe_min_capacity"],
              math.ceil(chunk * m["k"] / m["E"] * capacity_factor))
    return chunk, cap


def _route(c, xt, router, bias, capacity_factor, use_bias=True):
    """(combine weights (T, E), picks per token and expert (T, E) as 0/1,
    scores (T, E))."""
    m = dims(c)
    t, E, k = xt.shape[0], m["E"], m["k"]
    logits = jnp.einsum("td,de->te", xt, router.astype(xt.dtype)
                        ).astype(jnp.float32)
    s = jax.nn.sigmoid(logits)
    _, top_i = jax.lax.top_k(s + bias if use_bias else s, k)
    g = jnp.take_along_axis(s, top_i, -1)
    g = g / (jnp.sum(g, -1, keepdims=True) + 1e-20) \
        * c["routed_scaling_factor"]
    onehot = jax.nn.one_hot(top_i, E, dtype=jnp.int32)        # (T, k, E)
    if capacity_factor:
        chunk, cap = _capacity(c, t, capacity_factor)
        flat = onehot.reshape(t // chunk, chunk * k, E)
        seen = (jnp.cumsum(flat, axis=1) - flat).reshape(t, k, E)
        pos = jnp.sum(seen * onehot, -1)
        g = jnp.where(pos < cap, g, 0.0)
    w = jnp.zeros((t, E), jnp.float32).at[jnp.arange(t)[:, None],
                                           top_i].add(g)
    return w, jnp.sum(onehot, 1).astype(jnp.float32), s


def _experts(c, mo, xt, w):
    """The held experts' part: each computes every token, weighted."""
    m = dims(c)
    wh = w[:, m["first"]:m["first"] + m["held"]].astype(xt.dtype)
    gate = jnp.einsum("td,edf->tef", xt, mo["w_gate"])
    up = jnp.einsum("td,edf->tef", xt, mo["w_up"])
    hid = jax.nn.silu(gate) * up * wh[:, :, None]
    return jnp.einsum("tef,efd->td", hid, mo["w_down"])


def _balance(c, picks, s, b, sq):
    m = dims(c)
    s = s.reshape(b, sq, -1)
    p_i = jnp.mean(s / jnp.sum(s, -1, keepdims=True), axis=1)
    f_i = jax.lax.stop_gradient(jnp.sum(picks.reshape(b, sq, -1), axis=1)) \
        * (m["E"] / (m["k"] * sq))
    return c["seq_aux_weight"] * jnp.mean(jnp.sum(f_i * p_i, -1))


def _moe(c, mo, x, bias, capacity_factor, fault):
    b, sq, d = x.shape
    xt = x.reshape(b * sq, d)
    w, picks, s = _route(c, xt, mo["router"], bias, capacity_factor,
                         use_bias=fault != "bias_ignored")
    out = _experts(c, mo, xt, w)
    if "shared" in mo and fault != "no_shared":
        out = out + _swiglu(mo["shared"], xt)
    return (out.reshape(b, sq, d), jnp.sum(picks, 0),
            _balance(c, picks, s, b, sq))


def _dense_routed(c, p, mlp, x, capacity_factor):
    """The planted fault ``dense_routed``: the dense layer run as a routed
    layer whose held experts are its own weights cut into blocks of the
    expert width, routed by the first MoE layer's router (no bias, no
    shared experts)."""
    m = dims(c)
    n = m["f"] // m["fe"]
    if n != m["held"]:
        raise ValueError("dense_routed needs a dense width of held experts")
    b, sq, d = x.shape
    mo = {"w_gate": mlp["w_gate"].reshape(d, n, m["fe"]).transpose(1, 0, 2),
          "w_up": mlp["w_up"].reshape(d, n, m["fe"]).transpose(1, 0, 2),
          "w_down": mlp["w_down"].reshape(n, m["fe"], d)}
    xt = x.reshape(b * sq, d)
    w, _, _ = _route(c, xt, p["layers"]["moe"]["router"][0],
                     jnp.zeros((m["E"],)), capacity_factor)
    return _experts(c, mo, xt, w).reshape(b, sq, d)


def hidden(c: dict, p: Dict, tokens, bias, *, capacity_factor=None,
           fault: Optional[str] = None):
    """(final-normed hidden states (B, S, d), each MoE layer's picks per
    expert (L, E), the summed balance loss) of a token batch; ``bias``:
    (L, E).  The MoE layers run one at a time (a scan), each under
    ``jax.checkpoint``, so that the reference fits beside its optimizer
    state."""
    eps = c["rms_norm_eps"]
    m = dims(c)
    x = p["embed"][tokens]

    @jax.checkpoint
    def dense(x, lp):
        x = x + _attention(c, lp["attn"], _rms(x, lp["ln1"], eps))
        h = _rms(x, lp["ln2"], eps)
        f = _dense_routed(c, p, lp["mlp"], h, capacity_factor) \
            if fault == "dense_routed" else _swiglu(lp["mlp"], h)
        return x + f

    @jax.checkpoint
    def routed(x, layer):
        lp, lb = layer
        x = x + _attention(c, lp["attn"], _rms(x, lp["ln1"], eps))
        f, load, bal = _moe(c, lp["moe"], _rms(x, lp["ln2"], eps), lb,
                            capacity_factor, fault)
        return x + f, (load, bal)

    for i in range(m["nd"]):
        x = dense(x, jax.tree.map(lambda a: a[i], p["prefix"]))
    x, (loads, bal) = jax.lax.scan(routed, x, (p["layers"], bias))
    return _rms(x, p["final_norm"], eps), loads, jnp.sum(bal)


def logits(c: dict, p: Dict, h):
    return jnp.einsum("bsd,dv->bsv", h, p["head"]).astype(jnp.float32)


def loss(c: dict, p: Dict, tokens, labels, bias, *, capacity_factor=None,
         fault: Optional[str] = None):
    """(mean next-token cross entropy + balance loss, picks (L, E)).  The
    fault ``half_batch`` takes the mean over the leading half of the
    positions only."""
    h, loads, bal = hidden(c, p, tokens, bias,
                           capacity_factor=capacity_factor, fault=fault)
    lg = logits(c, p, h)
    lse = jax.nn.logsumexp(lg, -1)
    tgt = jnp.take_along_axis(lg, labels[..., None], -1)[..., 0]
    ce = (lse - tgt).reshape(-1)
    if fault == "half_batch":
        ce = ce[:ce.size // 2]
    return jnp.mean(ce) + bal, loads


def bias_step(c: dict, bias, loads):
    """The aux-loss-free update after a step with ``loads`` (L, E)."""
    mean = jnp.mean(loads, -1, keepdims=True)
    return bias + c["routing_bias_rate"] * jnp.sign(mean - loads)


# ---------------------------------------------------------------------------
# Training
# ---------------------------------------------------------------------------


def train_readings(c: dict, key, batches, *, dtype="float32",
                   precision="highest", fault: Optional[str] = None,
                   devices=None) -> dict:
    """The first steps of training from the seed's weights and routing
    bias on ``batches`` (host arrays of tokens and labels): each step's
    loss, the per-leaf norms of the first gradient as the optimizer
    applies it (after clipping) and of the parameters' change over all
    steps, the routing bias before and after them and the last step's
    picks per expert.
    ``dtype`` and ``precision`` are the arithmetic's (the control takes a
    lower one); ``fault`` plants one of :data:`FAULTS`."""
    tc = c["train"]
    opt = tc["optimizer"]
    cf = tc["capacity_factor"]
    dts = {"*": dtype}
    mesh = jax.sharding.Mesh(np.asarray(devices or jax.devices()[:1]),
                             ("rows",))
    whole = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    rows = jax.sharding.NamedSharding(mesh,
                                      jax.sharding.PartitionSpec("rows"))

    def step_fn(p, m, v, bias, step, tokens, labels):
        (lval, loads), g = jax.value_and_grad(
            lambda q: loss(c, q, tokens, labels, bias, capacity_factor=cf,
                           fault=fault), has_aux=True)(p)
        p, g, m, v = adamw(opt, p, g, m, v, step)
        return p, m, v, bias_step(c, bias, loads), loads, lval, \
            leaf_norms(g)

    with jax.default_matmul_precision(precision):
        init = jax.jit(lambda k: init_params(c, k, dts),
                       out_shardings=whole)
        step = jax.jit(step_fn, donate_argnums=(0, 1, 2),
                       out_shardings=(whole,) * 5 + (None, None))
        p = init(key)
        m = jax.device_put(jax.tree.map(jnp.zeros_like, p), whole)
        v = jax.device_put(jax.tree.map(jnp.zeros_like, p), whole)
        bias = jax.jit(lambda k: init_route_state(c, k)["bias"],
                       out_shardings=whole)(key)
        bias_start = np.asarray(bias)
        losses, first_grad, loads = [], None, None
        for i, (tok, lab) in enumerate(batches):
            p, m, v, bias, loads, lval, gn = step(
                p, m, v, bias, jnp.int32(i), jax.device_put(tok, rows),
                jax.device_put(lab, rows))
            losses.append(float(lval))
            if first_grad is None:
                first_grad = np.asarray(gn)
        del m, v
        p0 = init(key)
        change = np.asarray(jax.jit(lambda a, b: leaf_norms(
            jax.tree.map(lambda x, y: x.astype(jnp.float32)
                         - y.astype(jnp.float32), a, b)))(p, p0))
    return {"losses": losses, "first_grad": first_grad, "change": change,
            "bias_start": bias_start, "bias": np.asarray(bias),
            "load": np.asarray(loads)}
