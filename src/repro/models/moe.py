"""Mixture-of-experts FFN with top-k routing (chunked index dispatch).

Expert-parallel design notes (what makes this GSPMD-friendly):

  * tokens are routed in fixed-size CHUNKS inside a rematerialized scan —
    capacity is per-chunk, so dispatch buffers are bounded regardless of
    global token count (a naive global-capacity scatter was measured to
    make GSPMD all-gather a 48 GiB f32 update tensor on the 32k-prefill
    cell);
  * the scatter moves token *indices* (int32), never token vectors; the
    (E, cap, D) expert batch is then a gather, and only that gather's
    operand (one chunk of activations) is replicated across the expert
    shards;
  * expert weights are stacked on a leading E axis, sharded over 'model'
    (EP); padded experts (granite: 40 -> 48 under EP=16) get -inf router
    logits so routing semantics match the logical expert count exactly.

Per-chunk dispatch is also the realistic regime for the paper's lens: each
chunk's expert batches are independent partitions whose all-to-all can
overlap the previous chunk's expert compute.

Two routings.  ``softmax`` (Granite): the softmax of each token's top-k
router logits.  ``sigmoid`` (DeepSeek-V3 ``noaux_tc``, Moonlight): an
expert's score is the sigmoid of its logit; a per-expert bias, which the
train step keeps outside the parameters, takes part in picking the top-k
only; the picked scores are renormalised to sum 1 and scaled by
``routed_scale``.  That layer also runs ``n_shared`` always-on shared
experts and reports each expert's load and the sequence-wise balance loss.

An expert share (``held`` experts from ``first``) is what one member of
an expert-parallel group holds: the router still scores every expert and
keeps its top-k, capacity is counted as for the whole layer, and the
layer returns its held experts' part of the result alone.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Callable, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.runtime.spans import scope

from .layers import dense_init, silu, swiglu


@dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    d_expert: int                 # per-expert FFN hidden size
    n_experts_padded: int = 0     # 0 -> equal to n_experts
    capacity_factor: float = 1.25
    min_capacity: int = 4
    dispatch_chunk: int = 4096    # tokens routed per scan step
    score: str = "softmax"        # softmax | sigmoid (see the module doc)
    routed_scale: float = 1.0     # sigmoid: scale of the renormalised gates
    n_shared: int = 0             # sigmoid: always-on experts, each d_expert
    held: int = 0                 # routed experts held here; 0 -> all
    first: int = 0                # the first held expert
    bias_rate: float = 0.0        # sigmoid: the bias update's step (gamma)
    balance_weight: float = 0.0   # sigmoid: sequence-wise loss weight (alpha)

    def __post_init__(self):
        if self.score not in ("softmax", "sigmoid"):
            raise ValueError(f"unknown MoE score {self.score!r}")
        if self.score == "softmax" and (self.n_shared or self.held):
            raise ValueError("shared experts and expert shares need the "
                             "sigmoid routing")
        if self.held and not (0 <= self.first
                              and self.first + self.held <= self.n_experts):
            raise ValueError(f"experts {self.first}..{self.first + self.held}"
                             f" are not among {self.n_experts}")

    @property
    def e_pad(self) -> int:
        return self.n_experts_padded or self.n_experts

    @property
    def n_held(self) -> int:
        """Routed experts whose weights live here."""
        return self.held or self.e_pad

    @property
    def biased(self) -> bool:
        """Routing with a selection bias and a load counter (sigmoid)."""
        return self.score == "sigmoid"

    def capacity(self, n_tokens: int) -> int:
        cap = int(math.ceil(n_tokens * self.top_k / self.n_experts
                            * self.capacity_factor))
        return max(self.min_capacity, cap)


def init_moe(key, *, d_model: int, mo: MoEConfig, dtype) -> Dict:
    """Per-expert independent init; weights stacked on a leading E axis
    (the held experts only)."""
    e = mo.n_held
    k0, k1, k2, k3 = jax.random.split(key, 4)

    def stack(key, in_dim, out_dim):
        keys = jax.random.split(key, e)
        return jnp.stack([dense_init(k, in_dim, (out_dim,), dtype)
                          for k in keys])

    p = {
        "router": dense_init(k0, d_model, (mo.e_pad,), jnp.float32),
        "w_gate": stack(k1, d_model, mo.d_expert),
        "w_up": stack(k2, d_model, mo.d_expert),
        "w_down": stack(k3, mo.d_expert, d_model),
    }
    if mo.n_shared:
        ks = jax.random.split(jax.random.fold_in(key, 4), 3)
        f = mo.n_shared * mo.d_expert  # the shared experts as one SwiGLU
        p["shared"] = {"w_gate": dense_init(ks[0], d_model, (f,), dtype),
                       "w_up": dense_init(ks[1], d_model, (f,), dtype),
                       "w_down": dense_init(ks[2], f, (d_model,), dtype)}
    return p


def _route_chunk(p: Dict, xc: jax.Array, mo: MoEConfig,
                 e_shard: Callable) -> jax.Array:
    """Route one chunk of tokens (softmax routing).  xc: (T_c, D) ->
    (T_c, D)."""
    e = mo.e_pad
    with scope("moe.route"):
        # router matmul in activation dtype (casting xc to f32 would make
        # XLA hoist the convert out of the chunk scan and materialize
        # every chunk in f32 — measured 4 GiB/device on 32k prefill);
        # ranking precision of the (T_c, E) logits is restored in f32
        # afterwards.
        logits = (xc @ p["router"].astype(xc.dtype)).astype(jnp.float32)
        if e > mo.n_experts:  # padded experts are never routable
            eids = jax.lax.broadcasted_iota(jnp.int32, (1, e), 1)
            logits = jnp.where(eids < mo.n_experts, logits, -jnp.inf)
        top_vals, top_idx = jax.lax.top_k(logits, mo.top_k)  # (T_c, k)
        gates = jax.nn.softmax(top_vals, axis=-1)
    return _dispatch(p, xc, top_idx, gates, mo, e_shard)


def _dispatch(p: Dict, xc: jax.Array, top_idx: jax.Array,
              gates: jax.Array, mo: MoEConfig,
              e_shard: Callable) -> jax.Array:
    """The held experts' part of the result for one chunk of tokens
    routed to ``top_idx`` (T_c, k) with weights ``gates``."""
    tc, d = xc.shape
    k = mo.top_k
    held = mo.n_held
    cap = mo.capacity(tc)

    with scope("moe.route"):
        # position of each (token, slot) within its expert's capacity
        # buffer, counted over every expert routed to
        flat_e = top_idx.reshape(-1)                       # (T_c * k,)
        onehot = jax.nn.one_hot(flat_e, mo.e_pad, dtype=jnp.int32)
        pos = jnp.cumsum(onehot, axis=0) - 1
        pos = jnp.take_along_axis(pos, flat_e[:, None], axis=1)[:, 0]
        keep = pos < cap
        pos_c = jnp.where(keep, pos, cap)                  # overflow slot
        rows = back = flat_e
        if mo.held:  # pairs routed to experts held elsewhere are dropped
            local = flat_e - mo.first
            mine = (local >= 0) & (local < held)
            keep = keep & mine
            rows = jnp.where(mine, local, held)            # held: dropped
            back = jnp.where(mine, local, 0)

        # scatter token INDICES (not vectors); sentinel T_c -> zero row
        tok_idx = jnp.repeat(jnp.arange(tc, dtype=jnp.int32), k)
        buf_idx = jnp.full((held, cap + 1), tc, jnp.int32)
        buf_idx = buf_idx.at[rows, pos_c].set(tok_idx, mode="drop")
        buf_idx = buf_idx[:, :cap]

        xc_ext = jnp.concatenate([xc, jnp.zeros((1, d), xc.dtype)])
        buf = e_shard(xc_ext[buf_idx])                     # (E, cap, D)

    with scope("moe.experts"):
        h = silu(jnp.einsum("ecd,edf->ecf", buf, p["w_gate"])) * \
            jnp.einsum("ecd,edf->ecf", buf, p["w_up"])
        out = e_shard(jnp.einsum("ecf,efd->ecd", h, p["w_down"]))

    with scope("moe.route"):
        # gather back per slot; dropped slots are zero-weighted
        per_slot = out[back, pos_c % cap]                  # (T_c * k, D)
        w = (gates.reshape(-1) * keep).astype(xc.dtype)
        return jnp.sum((per_slot * w[:, None]).reshape(tc, k, d), axis=1)


def _chunking(t: int, mo: MoEConfig) -> int:
    chunk = min(mo.dispatch_chunk, t)
    return chunk if t % chunk == 0 else t  # odd token counts: one chunk


def moe_fwd(p: Dict, x: jax.Array, *, mo: MoEConfig,
            e_shard: Callable = lambda v: v,
            tok_shard: Callable = lambda v: v) -> jax.Array:
    """x: (B, S, D) -> (B, S, D).  Top-k routed SwiGLU experts.

    ``e_shard``: sharding hint pinning (E, ...) tensors to the EP axis.
    ``tok_shard``: hint for the (nc, chunk, D) stacked chunks — the chunk
    dim must NOT be sharded on the scan axis (dim 0), or every scan slice
    all-gathers the full token buffer (measured: a per-layer f32 4 GiB
    all-gather on 32k prefill).  Sharding dim 1 keeps slices local.
    """
    b, s, d = x.shape
    t = b * s
    xt = x.reshape(t, d)
    chunk = _chunking(t, mo)
    nc = t // chunk
    if nc == 1:
        return _route_chunk(p, xt, mo, e_shard).reshape(b, s, d)

    @jax.checkpoint
    def body(_, xc):
        return (), _route_chunk(p, xc, mo, e_shard)

    xs = tok_shard(xt.reshape(nc, chunk, d))
    _, out = jax.lax.scan(body, (), xs)
    return out.reshape(b, s, d)


def select_sigmoid(p: Dict, xt: jax.Array, mo: MoEConfig,
                   bias: jax.Array) -> Tuple[jax.Array, jax.Array,
                                             jax.Array]:
    """(scores (T, E) f32, top_idx (T, k), gates (T, k)) of tokens
    ``xt`` (T, D): the bias moves the choice, never the gates."""
    logits = (xt @ p["router"].astype(xt.dtype)).astype(jnp.float32)
    scores = jax.nn.sigmoid(logits)
    choice = scores + jnp.pad(bias, (0, mo.e_pad - bias.shape[-1]))
    if mo.e_pad > mo.n_experts:  # padded experts are never routable
        eids = jax.lax.broadcasted_iota(jnp.int32, (1, mo.e_pad), 1)
        choice = jnp.where(eids < mo.n_experts, choice, -jnp.inf)
    _, top_idx = jax.lax.top_k(choice, mo.top_k)
    gates = jnp.take_along_axis(scores, top_idx, axis=-1)
    gates = gates / (jnp.sum(gates, -1, keepdims=True) + 1e-20) \
        * mo.routed_scale
    return scores[:, :mo.n_experts], top_idx, gates


def balance_loss(scores: jax.Array, picked: jax.Array,
                 mo: MoEConfig) -> jax.Array:
    """DeepSeek-V3's sequence-wise balance loss, ``balance_weight`` x the
    mean over sequences of sum_i f_i P_i: ``f_i`` = E / (k S) x the
    sequence's picks of expert i (a constant), ``P_i`` = the mean over
    its S tokens of expert i's score over the token's score sum.
    ``scores``, ``picked``: (B, S, E)."""
    s = scores.shape[1]
    p_i = jnp.mean(scores / jnp.sum(scores, -1, keepdims=True), axis=1)
    f_i = jax.lax.stop_gradient(jnp.sum(picked, axis=1)) \
        * (mo.n_experts / (mo.top_k * s))
    return mo.balance_weight * jnp.mean(jnp.sum(f_i * p_i, -1))


def routed_moe_fwd(p: Dict, x: jax.Array, *, mo: MoEConfig,
                   bias: jax.Array, e_shard: Callable = lambda v: v,
                   tok_shard: Callable = lambda v: v,
                   ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """The sigmoid-routed layer: x (B, S, D) -> (held experts' part +
    shared experts, {'load': (E,) routed pairs per expert, 'balance':
    the sequence-wise balance loss}).  ``bias``: (E,) selection bias."""
    b, s, d = x.shape
    t = b * s
    xt = x.reshape(t, d)
    with scope("moe.route"):
        scores, top_idx, gates = select_sigmoid(p, xt, mo, bias)
        picked = jnp.sum(jax.nn.one_hot(top_idx, mo.n_experts,
                                        dtype=jnp.float32), axis=1)
        stats = {"load": jnp.sum(picked, axis=0),
                 "balance": (balance_loss(scores.reshape(b, s, -1),
                                          picked.reshape(b, s, -1), mo)
                             if mo.balance_weight else jnp.float32(0.0))}
    chunk = _chunking(t, mo)
    nc = t // chunk
    if nc == 1:
        out = _dispatch(p, xt, top_idx, gates, mo, e_shard)
    else:
        @jax.checkpoint
        def body(_, xs):
            return (), _dispatch(p, *xs, mo, e_shard)

        k = mo.top_k
        _, out = jax.lax.scan(body, (), (
            tok_shard(xt.reshape(nc, chunk, d)),
            top_idx.reshape(nc, chunk, k), gates.reshape(nc, chunk, k)))
        out = out.reshape(t, d)
    if mo.n_shared:
        with scope("moe.shared"):
            out = out + swiglu(p["shared"], xt)
    return out.reshape(b, s, d), stats
