"""Plain float32 reference of a Granite-MoE decoder (GraniteMoe in HF
transformers): embedding x ``embedding_multiplier``; per layer RMSNorm ->
GQA attention with rotary positions (rotate-half, ``rope_theta``),
scores x ``attention_multiplier`` -> residual x ``residual_multiplier``
-> RMSNorm -> top-k routed SwiGLU experts (softmax over the k chosen
router logits) -> residual x ``residual_multiplier``; final RMSNorm;
logits = h @ head / ``logits_scaling``.  All sizes and multipliers are
read from the configuration file, which states them as run.

With ``tie_word_embeddings`` the head is the embedding's transpose.

Written from that description in straightforward ``jax.numpy``: every
expert computes every token and the router's weights select (no
dispatch, no kernels), attention is a masked softmax over the whole
sequence (queries in blocks), one layer at a time under
``jax.checkpoint`` so it fits beside the optimizer state.  With
``capacity_factor`` set, an expert takes at most ``ceil(tokens * k / E * factor)`` (at least
``min_capacity``) of the (token, slot) pairs of each dispatch chunk, in
token-major order, and drops the rest; without it routing is dropless.

The parameter layout (stacked on a leading layer axis) is the one the
benchmark also hands the system under test: ``init_params`` makes it
from the seed.
"""

from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

# Attention takes this many queries at a time (memory only).
QUERY_BLOCK = 1024

# ---------------------------------------------------------------------------
# Weights, from the seed
# ---------------------------------------------------------------------------


def _dims(c: dict):
    d, h = c["hidden_size"], c["num_attention_heads"]
    return dict(L=c["num_hidden_layers"], d=d, H=h,
                KV=c["num_key_value_heads"], hd=d // h,
                E=c["num_local_experts"], k=c["num_experts_per_tok"],
                f=c["intermediate_size"], V=c["vocab_size"])


def param_shapes(c: dict) -> Dict:
    """Leaf shapes, and each leaf's fan-in (0: an RMSNorm scale)."""
    m = _dims(c)
    L, d, H, KV, hd, E, f, V = (m[k] for k in "L d H KV hd E f V".split())
    tree = {
        "embed": ((V, d), -1),
        "final_norm": ((d,), 0),
        "layers": {
            "ln1": ((L, d), 0),
            "attn": {"wq": ((L, d, H, hd), d), "wk": ((L, d, KV, hd), d),
                     "wv": ((L, d, KV, hd), d), "wo": ((L, H, hd, d), H * hd)},
            "ln2": ((L, d), 0),
            "moe": {"router": ((L, d, E), d), "w_gate": ((L, E, d, f), d),
                    "w_up": ((L, E, d, f), d), "w_down": ((L, E, f, d), f)},
        },
    }
    if not c["tie_word_embeddings"]:
        tree["head"] = ((d, V), d)
    return tree


def _is_spec(x):
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple)


def init_params(c: dict, key, dtypes: Dict[str, str]) -> Dict:
    """Weights drawn from ``key``: normal / sqrt(fan-in) (the embedding:
    / sqrt(d)), RMSNorm scales 1.  ``dtypes`` maps a leaf name to its
    type (``"*"`` for the rest)."""
    specs = param_shapes(c)
    leaves, tree = jax.tree_util.tree_flatten_with_path(specs,
                                                        is_leaf=_is_spec)
    d = c["hidden_size"]
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


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _rms(x, w, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * w.astype(jnp.float32)).astype(x.dtype)


def _rope(x, theta):
    s, half = x.shape[1], x.shape[-1] // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(
        jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           -1).astype(x.dtype)


def _attention(c, lp, x):
    m = _dims(c)
    lp = lp["attn"]
    s = x.shape[1]
    q = jnp.einsum("bsd,dhk->bshk", x, lp["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, lp["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, lp["wv"])
    q, k = _rope(q, c["rope_theta"]), _rope(k, c["rope_theta"])
    g = m["H"] // m["KV"]
    k = jnp.repeat(k, g, axis=2)
    v = jnp.repeat(v, g, axis=2)

    @jax.checkpoint
    def rows(q_blk, first):
        """Queries ``first ..`` against every key, causally masked."""
        scores = jnp.einsum("bqhk,bshk->bhqs", q_blk, k).astype(jnp.float32) \
            * c["attention_multiplier"]
        qi = first + jnp.arange(q_blk.shape[1])[:, None]
        scores = jnp.where(jnp.arange(s)[None, :] <= qi, scores, -jnp.inf)
        probs = jax.nn.softmax(scores, -1).astype(x.dtype)
        return jnp.einsum("bhqs,bshk->bqhk", probs, v)

    blk = min(s, QUERY_BLOCK)
    out = jnp.concatenate([rows(q[:, i:i + blk], i)
                           for i in range(0, s, blk)], axis=1)
    return jnp.einsum("bqhk,hkd->bqd", out, lp["wo"])


def _route(c, router_logits, capacity_factor):
    """(T, E) combine weights: the softmax of each token's top-k router
    logits on its chosen experts, zero where capacity drops the pair."""
    m = _dims(c)
    t = router_logits.shape[0]
    top_v, top_i = jax.lax.top_k(router_logits, m["k"])
    gates = jax.nn.softmax(top_v, -1)
    if capacity_factor:
        chunk = min(c["moe_dispatch_chunk"], t)
        if t % chunk:
            chunk = t
        cap = max(c["moe_min_capacity"],
                  math.ceil(chunk * m["k"] / m["E"] * capacity_factor))
        onehot = jax.nn.one_hot(top_i, m["E"], dtype=jnp.int32)
        flat = onehot.reshape(t // chunk, chunk * m["k"], m["E"])
        seen = (jnp.cumsum(flat, axis=1) - flat).reshape(t, m["k"], m["E"])
        pos = jnp.sum(seen * onehot, -1)
        gates = jnp.where(pos < cap, gates, 0.0)
    w = jnp.zeros((t, m["E"]), jnp.float32)
    return w.at[jnp.arange(t)[:, None], top_i].add(gates)


def _moe(c, lp, x, capacity_factor):
    b, s, d = x.shape
    lp = lp["moe"]
    xt = x.reshape(b * s, d)
    logits = jnp.einsum("td,de->te", xt, lp["router"].astype(x.dtype)
                        ).astype(jnp.float32)
    w = _route(c, logits, capacity_factor).astype(x.dtype)
    gate = jnp.einsum("td,edf->tef", xt, lp["w_gate"])
    up = jnp.einsum("td,edf->tef", xt, lp["w_up"])
    hidden = jax.nn.silu(gate) * up * w[:, :, None]
    return jnp.einsum("tef,efd->td", hidden, lp["w_down"]).reshape(b, s, d)


def hidden(c: dict, p: Dict, tokens, *, capacity_factor=None):
    """Final-normed hidden states (B, S, d) of a token batch."""
    eps = c["rms_norm_eps"]
    x = p["embed"][tokens] * c["embedding_multiplier"]
    rm = c["residual_multiplier"]

    @jax.checkpoint
    def layer(x, lp):
        x = x + rm * _attention(c, lp, _rms(x, lp["ln1"], eps))
        x = x + rm * _moe(c, lp, _rms(x, lp["ln2"], eps), capacity_factor)
        return x

    for i in range(c["num_hidden_layers"]):
        x = layer(x, jax.tree.map(lambda a: a[i], p["layers"]))
    return _rms(x, p["final_norm"], eps)


def logits(c: dict, p: Dict, h):
    head = p["embed"].T if c["tie_word_embeddings"] else p["head"]
    out = jnp.einsum("bsd,dv->bsv", h, head).astype(jnp.float32)
    return out / c["logits_scaling"]


def loss(c: dict, p: Dict, tokens, labels, *, capacity_factor=None,
         share: float = 1.0):
    """Mean next-token cross entropy over every position (``share`` < 1:
    over that leading share of the positions only — a planted fault)."""
    lg = logits(c, p, hidden(c, p, tokens, capacity_factor=capacity_factor))
    lse = jax.nn.logsumexp(lg, -1)
    tgt = jnp.take_along_axis(lg, labels[..., None], -1)[..., 0]
    n = int(round(share * tokens.size))
    return jnp.mean((lse - tgt).reshape(-1)[:n])


# ---------------------------------------------------------------------------
# Training: AdamW with global-norm clipping and warmup-cosine steps
# ---------------------------------------------------------------------------


def learning_rate(opt: dict, step):
    """Warmup-cosine: linear to ``peak_lr`` over ``warmup_steps``, then
    cosine to ``min_lr_ratio`` of it at ``total_steps``; ``step`` counts
    updates already made."""
    step = jnp.asarray(step, jnp.float32)
    peak, w, tot = opt["peak_lr"], opt["warmup_steps"], opt["total_steps"]
    warm = peak * step / max(w, 1)
    frac = jnp.clip((step - w) / max(tot - w, 1), 0.0, 1.0)
    r = opt["min_lr_ratio"]
    cos = peak * (r + (1 - r) * 0.5 * (1.0 + jnp.cos(math.pi * frac)))
    return jnp.where(step < w, warm, cos)


def adamw(opt: dict, p, g, m, v, step):
    """One update; ``step`` updates were made before it."""
    dt = jax.tree.leaves(p)[0].dtype
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                         for x in jax.tree.leaves(g)))
    scale = jnp.minimum(1.0, opt["clip_norm"] / jnp.maximum(gnorm, 1e-12))
    g = jax.tree.map(lambda x: x * scale.astype(x.dtype), g)
    b1, b2, eps, wd = opt["b1"], opt["b2"], opt["eps"], opt["weight_decay"]
    lr = learning_rate(opt, step)
    n = step + 1
    c1, c2 = 1.0 - b1 ** n, 1.0 - b2 ** n
    m = jax.tree.map(lambda m_, g_: b1 * m_ + (1 - b1) * g_, m, g)
    v = jax.tree.map(lambda v_, g_: b2 * v_ + (1 - b2) * g_ * g_, v, g)
    p = jax.tree.map(
        lambda p_, m_, v_: (p_ - lr * ((m_ / c1) / (jnp.sqrt(v_ / c2) + eps)
                                       + wd * p_)).astype(dt), p, m, v)
    return p, g, m, v


def leaf_norms(tree) -> jnp.ndarray:
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree.leaves(tree)])


def train_readings(c: dict, key, batches, *, dtype="float32",
                   precision="highest", share: float = 1.0,
                   devices=None) -> dict:
    """The first steps of training from the seed's weights on
    ``batches`` (host arrays of tokens and labels): each step's loss,
    the per-leaf norms of the first gradient as the optimizer applies
    it (after clipping), and of the parameters' change over all steps.
    ``dtype`` and ``precision`` are the arithmetic's (the control takes
    a lower one); ``share`` plants the half-batch fault.  With several
    ``devices`` the batch's rows are split among them and the weights
    copied to each (the compiler adds the reductions), so that it fits."""
    tc = c["train"]
    opt = tc["optimizer"]
    cf = tc["capacity_factor"]
    dts = {"*": dtype}
    mesh = jax.sharding.Mesh(np.asarray(devices or jax.devices()[:1]),
                             ("rows",))
    whole = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    rows = jax.sharding.NamedSharding(mesh,
                                      jax.sharding.PartitionSpec("rows"))

    def step_fn(p, m, v, step, tokens, labels):
        lval, g = jax.value_and_grad(
            lambda q: loss(c, q, tokens, labels, capacity_factor=cf,
                           share=share))(p)
        p, g, m, v = adamw(opt, p, g, m, v, step)
        return p, m, v, lval, leaf_norms(g)

    with jax.default_matmul_precision(precision):
        init = jax.jit(lambda k: init_params(c, k, dts), out_shardings=whole)
        step = jax.jit(step_fn, donate_argnums=(0, 1, 2),
                       out_shardings=(whole, whole, whole, None, None))
        p = init(key)
        m = jax.device_put(jax.tree.map(jnp.zeros_like, p), whole)
        v = jax.device_put(jax.tree.map(jnp.zeros_like, p), whole)
        losses, first_grad = [], None
        for i, (tok, lab) in enumerate(batches):
            p, m, v, lval, gn = step(p, m, v, jnp.int32(i),
                                     jax.device_put(tok, rows),
                                     jax.device_put(lab, rows))
            losses.append(float(lval))
            if first_grad is None:
                first_grad = np.asarray(gn)
        del m, v
        p0 = init(key)
        change = np.asarray(jax.jit(lambda a, b: leaf_norms(
            jax.tree.map(lambda x, y: x.astype(jnp.float32)
                         - y.astype(jnp.float32), a, b)))(p, p0))
    return {"losses": losses, "first_grad": first_grad, "change": change}

