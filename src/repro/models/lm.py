"""Full language model: config, init, forward, loss, prefill, decode.

A single ``ModelConfig`` covers all 10 assigned architectures (dense GQA,
MLA, MoE, SSM, hybrid, audio/vision-stub frontends).  Layers are stacked on
a leading L axis and executed with ``jax.lax.scan`` (optionally remat'ed),
which keeps compile time flat in depth and is the structural hook for the
paper's technique: per-layer gradient collectives issued *inside* the
backward scan (see repro.core.earlybird).

A model may lead with ``first_dense`` dense SwiGLU layers (DeepSeek's
``first_k_dense_replace``) before its MoE layers: they are a stack of
their own, ``params["prefix"]`` (and ``cache["prefix"]``), scanned
before ``params["layers"]``.  A sigmoid-routed MoE (``MoEConfig.biased``)
takes a selection bias per layer and expert, ``route_bias`` (L_moe, E),
which lives in the train state, not in the parameters: every forward
pass of such a model is handed it, and raises without it.
"""

from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass, field
from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.runtime.spans import scope

from .attention import head_to_kv_map, init_attention, init_mla
from .blocks import block_fwd
from .layers import chunked_cross_entropy, embed_init, rms_norm, softcap
from .mamba import MambaConfig, init_mamba, init_mamba_cache
from .moe import MoEConfig, init_moe

MODEL_AXIS = "model"


@dataclass(frozen=True)
class MLAConfig:
    q_lora: Optional[int] = 768   # None: one direct query projection
    kv_lora: int = 256
    qk_nope: int = 64
    qk_rope: int = 32
    v_dim: int = 64


@dataclass(frozen=True)
class ModelConfig:
    name: str
    n_layers: int
    d_model: int
    vocab: int
    n_heads: int = 0            # 0 for attention-free archs
    n_kv: int = 0
    d_ff: int = 0               # dense FFN hidden; 0 = no FFN (mamba2)
    head_dim: int = 0           # 0 -> d_model // n_heads
    mixer: str = "attn"         # attn | mamba | hybrid
    qkv_bias: bool = False
    attn_softcap: Optional[float] = None
    final_softcap: Optional[float] = None
    rope_theta: float = 1e4
    mrope_sections: Optional[Tuple[int, int, int]] = None
    # per-layer windows: "global" | "gemma_alt" | "hymba"
    window_pattern: str = "global"
    window_size: int = 0
    post_norm: bool = False
    tie_embeddings: bool = False
    zero_centered_norm: bool = False
    emb_scale: bool = False     # gemma: embeddings scaled by sqrt(d_model)
    frontend: str = "tokens"    # tokens | audio_stub | vision_stub
    mla: Optional[MLAConfig] = None
    moe: Optional[MoEConfig] = None
    mamba: Optional[MambaConfig] = None
    q_scale: Optional[float] = None
    first_dense: int = 0        # leading dense layers (FFN width d_ff)
    norm_eps: float = 1e-6      # every RMSNorm's epsilon
    q_chunk: int = 512
    loss_chunk: int = 512
    tp_pad: int = 1             # pad heads/experts to a multiple of this
    param_dtype: str = "float32"

    # ---- derived ----
    @property
    def head_dim_(self) -> int:
        return self.head_dim or (self.d_model // max(self.n_heads, 1))

    @property
    def n_heads_padded(self) -> int:
        if self.n_heads == 0:
            return 0
        return -(-self.n_heads // self.tp_pad) * self.tp_pad

    @property
    def vocab_padded(self) -> int:
        """Vocab rounded up for TP sharding; padded logits are masked to
        -inf so semantics match the logical vocab exactly."""
        return -(-self.vocab // self.tp_pad) * self.tp_pad

    @property
    def head_map(self) -> Tuple[int, ...]:
        return head_to_kv_map(self.n_heads, self.n_kv, self.n_heads_padded)

    @property
    def dtype(self):
        return jnp.dtype(self.param_dtype)

    def windows(self) -> Tuple[int, ...]:
        L = self.n_layers
        if self.window_pattern == "global":
            return (0,) * L
        if self.window_pattern == "gemma_alt":  # local on even layers
            return tuple(self.window_size if i % 2 == 0 else 0
                         for i in range(L))
        if self.window_pattern == "hymba":  # global at first/middle/last
            g = {0, L // 2, L - 1}
            return tuple(0 if i in g else self.window_size for i in range(L))
        raise ValueError(self.window_pattern)

    def with_tp(self, tp: int) -> "ModelConfig":
        """Return a copy padded for a TP degree (heads + experts)."""
        moe = self.moe
        if moe is not None:
            epad = -(-moe.n_experts // tp) * tp
            moe = dataclasses.replace(moe, n_experts_padded=epad)
        return dataclasses.replace(self, tp_pad=tp, moe=moe)

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)

    @property
    def n_main_layers(self) -> int:
        """Layers of the main stack, after the leading dense ones."""
        return self.n_layers - self.first_dense

    # ---- parameter counting (logical, for MODEL_FLOPS) ----
    def param_count(self, padded: bool = False) -> int:
        """Parameters held (of an expert share: its experts alone)."""
        nh = self.n_heads_padded if padded else self.n_heads
        hd = self.head_dim_
        d = self.d_model
        vv = self.vocab_padded if padded else self.vocab
        n = vv * d  # embed
        if not self.tie_embeddings:
            n += d * vv
        per_layer = 0
        if self.mixer in ("attn", "hybrid"):
            if self.mla is not None:
                m = self.mla
                qk = nh * (m.qk_nope + m.qk_rope)
                per_layer += (d * qk if m.q_lora is None
                              else d * m.q_lora + m.q_lora * qk)
                per_layer += (d * m.kv_lora + m.kv_lora * nh * m.qk_nope
                              + m.kv_lora * nh * m.v_dim + d * m.qk_rope
                              + nh * m.v_dim * d)
            else:
                per_layer += d * nh * hd + 2 * d * self.n_kv * hd + nh * hd * d
        if self.mixer in ("mamba", "hybrid"):
            mc = self.mamba
            di = mc.d_inner(d)
            gn = mc.n_groups * mc.d_state
            per_layer += 2 * d * di + 2 * d * gn + d * mc.n_heads(d) + di * d
        dense = 3 * d * self.d_ff
        ffn = dense
        if self.moe is not None:
            mo = self.moe
            e = mo.e_pad if padded else mo.n_experts
            ffn = d * e + (mo.held or e) * 3 * d * mo.d_expert \
                + mo.n_shared * 3 * d * mo.d_expert
        return (n + self.n_layers * per_layer
                + self.first_dense * dense + self.n_main_layers * ffn)

    def active_param_count(self) -> int:
        """Per-token active parameters (MoE: top_k experts only)."""
        if self.moe is None:
            return self.param_count()
        mo = self.moe
        full = self.param_count()
        expert = 3 * self.d_model * mo.d_expert
        all_experts = self.n_main_layers * (mo.held or mo.n_experts) * expert
        active = self.n_main_layers * mo.top_k * expert
        return full - all_experts + active


# ---------------------------------------------------------------------------
# Initialization
# ---------------------------------------------------------------------------

def _init_layer(cfg: ModelConfig, key) -> Dict:
    ks = jax.random.split(key, 8)
    dt = cfg.dtype
    d = cfg.d_model
    lp: Dict[str, Any] = {"ln1": jnp.zeros((d,), dt) if cfg.zero_centered_norm
                          else jnp.ones((d,), dt)}
    if cfg.mixer in ("attn", "hybrid"):
        if cfg.mla is not None:
            m = cfg.mla
            lp["attn"] = init_mla(
                ks[0], d_model=d, n_heads_padded=cfg.n_heads_padded,
                n_heads=cfg.n_heads, q_lora=m.q_lora, kv_lora=m.kv_lora,
                qk_nope=m.qk_nope, qk_rope=m.qk_rope, v_dim=m.v_dim, dtype=dt)
        else:
            lp["attn"] = init_attention(
                ks[0], d_model=d, n_heads=cfg.n_heads,
                n_heads_padded=cfg.n_heads_padded, n_kv=cfg.n_kv,
                head_dim=cfg.head_dim_, qkv_bias=cfg.qkv_bias, dtype=dt)
    if cfg.mixer in ("mamba", "hybrid"):
        lp["mamba"] = init_mamba(ks[1], d_model=d, mc=cfg.mamba, dtype=dt)
    if cfg.mixer == "hybrid":
        lp["norm_attn"] = jnp.ones((d,), dt)
        lp["norm_mamba"] = jnp.ones((d,), dt)
    if cfg.post_norm:
        lp["ln1_post"] = jnp.zeros((d,), dt) if cfg.zero_centered_norm \
            else jnp.ones((d,), dt)
    if cfg.moe is not None or cfg.d_ff > 0:
        lp["ln2"] = jnp.zeros((d,), dt) if cfg.zero_centered_norm \
            else jnp.ones((d,), dt)
        if cfg.moe is not None:
            lp["moe"] = init_moe(ks[2], d_model=d, mo=cfg.moe, dtype=dt)
        else:
            lp["mlp"] = {
                "w_gate": _dense(ks[3], d, cfg.d_ff, dt),
                "w_up": _dense(ks[4], d, cfg.d_ff, dt),
                "w_down": _dense(ks[5], cfg.d_ff, d, dt),
            }
        if cfg.post_norm:
            lp["ln2_post"] = jnp.zeros((d,), dt) if cfg.zero_centered_norm \
                else jnp.ones((d,), dt)
    return lp


def _dense(key, i, o, dt):
    from .layers import dense_init
    return dense_init(key, i, (o,), dt)


def init_params(cfg: ModelConfig, key) -> Dict:
    k_emb, k_head, k_layers = jax.random.split(key, 3)
    dt = cfg.dtype
    params: Dict[str, Any] = {
        "embed": embed_init(k_emb, cfg.vocab_padded, cfg.d_model, dt),
        "final_norm": (jnp.zeros((cfg.d_model,), dt)
                       if cfg.zero_centered_norm
                       else jnp.ones((cfg.d_model,), dt)),
    }
    if cfg.vocab_padded > cfg.vocab:  # padded rows are never valid tokens
        params["embed"] = params["embed"].at[cfg.vocab:].set(0.0)
    if not cfg.tie_embeddings:
        params["head"] = _dense(k_head, cfg.d_model, cfg.vocab_padded, dt)
        if cfg.vocab_padded > cfg.vocab:
            params["head"] = params["head"].at[:, cfg.vocab:].set(0.0)
    layer_keys = jax.random.split(k_layers, cfg.n_layers)
    nd = cfg.first_dense

    def stack(c, keys):
        layers = [_init_layer(c, k) for k in keys]
        return jax.tree.map(lambda *xs: jnp.stack(xs), *layers)

    if nd:
        params["prefix"] = stack(dense_config(cfg), layer_keys[:nd])
    params["layers"] = stack(cfg, layer_keys[nd:])
    return params


def dense_config(cfg: ModelConfig) -> ModelConfig:
    """The config of the leading dense stack (``first_dense`` layers)."""
    return cfg.replace(moe=None, n_layers=cfg.first_dense, first_dense=0)


def init_route_state(cfg: ModelConfig) -> Dict:
    """The train state's routing part: each MoE layer's selection bias
    and the last step's routed (token, slot) pairs per expert."""
    shape = (cfg.n_main_layers, cfg.moe.n_experts)
    return {"bias": jnp.zeros(shape, jnp.float32),
            "load": jnp.zeros(shape, jnp.float32)}


def param_shapes(cfg: ModelConfig):
    """Abstract parameter tree (no allocation) — used by the dry-run."""
    return jax.eval_shape(lambda k: init_params(cfg, k),
                          jax.random.PRNGKey(0))


# ---------------------------------------------------------------------------
# Sharding specs (model/TP axis only; DP handled by the caller)
# ---------------------------------------------------------------------------

def param_specs(cfg: ModelConfig, axis: str = MODEL_AXIS) -> Dict:
    """PartitionSpec tree matching init_params' structure."""
    A = axis

    def attn_specs():
        if cfg.mla is not None:
            q = ({"w_q": P(None, None, A, None)} if cfg.mla.q_lora is None
                 else {"w_dq": P(None, None, None), "norm_q": P(None, None),
                       "w_uq": P(None, None, A, None)})
            return {
                **q,
                "w_dkv": P(None, None, None), "norm_kv": P(None, None),
                "w_uk": P(None, None, A, None),
                "w_uv": P(None, None, A, None),
                "w_kr": P(None, None, None),
                "wo": P(None, A, None, None),
            }
        s = {
            "wq": P(None, None, A, None),
            "wk": P(None, None, None, None),
            "wv": P(None, None, None, None),
            "wo": P(None, A, None, None),
        }
        if cfg.qkv_bias:
            s.update({"bq": P(None, A, None), "bk": P(None, None, None),
                      "bv": P(None, None, None)})
        return s

    def mamba_specs():
        return {
            "w_z": P(None, None, A), "w_x": P(None, None, A),
            "w_B": P(None, None, None), "w_C": P(None, None, None),
            "w_dt": P(None, None, None),
            "conv_x": P(None, None, A), "conv_B": P(None, None, None),
            "conv_C": P(None, None, None),
            "conv_bx": P(None, A), "conv_bB": P(None, None),
            "conv_bC": P(None, None),
            "A_log": P(None, None), "D": P(None, None),
            "dt_bias": P(None, None),
            "norm": P(None, A), "out_proj": P(None, A, None),
        }

    lp: Dict[str, Any] = {"ln1": P(None, None)}
    if cfg.mixer in ("attn", "hybrid"):
        lp["attn"] = attn_specs()
    if cfg.mixer in ("mamba", "hybrid"):
        lp["mamba"] = mamba_specs()
    if cfg.mixer == "hybrid":
        lp["norm_attn"] = P(None, None)
        lp["norm_mamba"] = P(None, None)
    if cfg.post_norm:
        lp["ln1_post"] = P(None, None)
    if cfg.moe is not None or cfg.d_ff > 0:
        lp["ln2"] = P(None, None)
        if cfg.moe is not None:
            lp["moe"] = {
                "router": P(None, None, None),
                "w_gate": P(None, A, None, None),
                "w_up": P(None, A, None, None),
                "w_down": P(None, A, None, None),
            }
            if cfg.moe.n_shared:
                lp["moe"]["shared"] = {"w_gate": P(None, None, A),
                                       "w_up": P(None, None, A),
                                       "w_down": P(None, A, None)}
        else:
            lp["mlp"] = {"w_gate": P(None, None, A), "w_up": P(None, None, A),
                         "w_down": P(None, A, None)}
        if cfg.post_norm:
            lp["ln2_post"] = P(None, None)

    specs: Dict[str, Any] = {
        "embed": P(A, None),
        "final_norm": P(None),
        "layers": lp,
    }
    if cfg.first_dense:
        specs["prefix"] = param_specs(dense_config(cfg), axis)["layers"]
    if not cfg.tie_embeddings:
        specs["head"] = P(None, A)
    return specs


# ---------------------------------------------------------------------------
# Cache
# ---------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=None) -> Dict:
    """Stacked (L-leading) decode cache for the configured mixer; the
    dense prefix's under ``"prefix"``."""
    dt = dtype or cfg.dtype
    L = cfg.n_main_layers
    c: Dict[str, Any] = {}
    if cfg.mixer in ("attn", "hybrid"):
        if cfg.mla is not None:
            c["ckv"] = jnp.zeros((L, batch, max_len, cfg.mla.kv_lora), dt)
            c["kr"] = jnp.zeros((L, batch, max_len, cfg.mla.qk_rope), dt)
        else:
            c["k"] = jnp.zeros((L, batch, max_len, cfg.n_kv, cfg.head_dim_), dt)
            c["v"] = jnp.zeros((L, batch, max_len, cfg.n_kv, cfg.head_dim_), dt)
    if cfg.mixer in ("mamba", "hybrid"):
        one = init_mamba_cache(batch, cfg.d_model, cfg.mamba, dt)
        for k, v in one.items():
            c[k] = jnp.broadcast_to(v[None], (L, *v.shape)).copy()
    if cfg.first_dense:
        c["prefix"] = init_cache(dense_config(cfg), batch, max_len, dt)
    return c


def cache_specs(cfg: ModelConfig, axis: str = MODEL_AXIS,
                data_axis=None, seq_axis=None) -> Dict:
    """Sharding specs for the cache: batch->data, seq->seq_axis."""
    c: Dict[str, Any] = {}
    if cfg.mixer in ("attn", "hybrid"):
        if cfg.mla is not None:
            c["ckv"] = P(None, data_axis, seq_axis, None)
            c["kr"] = P(None, data_axis, seq_axis, None)
        else:
            c["k"] = P(None, data_axis, seq_axis, None, None)
            c["v"] = P(None, data_axis, seq_axis, None, None)
    if cfg.mixer in ("mamba", "hybrid"):
        c["state"] = P(None, data_axis, axis, None, None)
        c["conv_x"] = P(None, data_axis, None, axis)
        c["conv_B"] = P(None, data_axis, None, None)
        c["conv_C"] = P(None, data_axis, None, None)
    if cfg.first_dense:
        c["prefix"] = cache_specs(dense_config(cfg), axis, data_axis,
                                  seq_axis)
    return c


# ---------------------------------------------------------------------------
# Forward / loss / decode
# ---------------------------------------------------------------------------

def _embed_inputs(cfg: ModelConfig, params: Dict, batch: Dict) -> jax.Array:
    if cfg.frontend == "audio_stub":
        # musicgen: the EnCodec frontend is a stub; precomputed frame
        # embeddings come straight in (input_specs provides them).
        return batch["embeds"].astype(cfg.dtype)
    h = jnp.take(params["embed"], batch["tokens"], axis=0)
    if cfg.emb_scale:
        h = h * jnp.asarray(math.sqrt(cfg.d_model), h.dtype)
    if cfg.frontend == "vision_stub" and "patch_embeds" in batch:
        pe = batch["patch_embeds"].astype(h.dtype)
        h = jax.lax.dynamic_update_slice(h, pe, (0, 0, 0))
    return h


def _positions(cfg: ModelConfig, batch: Dict, b: int, s: int,
               cache_pos) -> jax.Array:
    if "positions" in batch:
        return batch["positions"]
    if cache_pos is not None and s == 1:  # decode
        pos = jnp.full((b, 1), cache_pos, jnp.int32)
        if cfg.mrope_sections is not None:
            pos = jnp.broadcast_to(pos[None], (3, b, 1))
        return pos
    pos = jnp.arange(s, dtype=jnp.int32)
    if cfg.mrope_sections is not None:
        pos = jnp.broadcast_to(pos[None, None, :], (3, b, s))
    return pos


def forward(cfg: ModelConfig, params: Dict, batch: Dict, *,
            cache: Optional[Dict] = None, cache_pos=None,
            remat: bool = False, seq_shard: Callable = lambda x: x,
            e_shard: Callable = lambda x: x,
            param_hooks: Optional[Dict[str, Callable]] = None,
            decode_attn=None, route_bias=None,
            ) -> Tuple[jax.Array, Optional[Dict]]:
    """Run the decoder stack.

    ``param_hooks``: by stack key (``"layers"``, ``"prefix"``), a hook that
    wraps each of that stack's layer parameter slices inside the scan
    body — the attach point for the early-bird gradient-sync engine.
    Returns (hidden (B,S,D), new stacked cache or None).
    """
    h, new_cache, _ = forward_stats(
        cfg, params, batch, cache=cache, cache_pos=cache_pos, remat=remat,
        seq_shard=seq_shard, e_shard=e_shard, param_hooks=param_hooks,
        decode_attn=decode_attn, route_bias=route_bias)
    return h, new_cache


def forward_stats(cfg: ModelConfig, params: Dict, batch: Dict, *,
                  cache: Optional[Dict] = None, cache_pos=None,
                  remat: bool = False, seq_shard: Callable = lambda x: x,
                  e_shard: Callable = lambda x: x,
                  param_hooks: Optional[Dict[str, Callable]] = None,
                  decode_attn=None, route_bias=None,
                  ) -> Tuple[jax.Array, Optional[Dict], Optional[Dict]]:
    """:func:`forward`, and the sigmoid-routed layers' stats stacked over
    the MoE layers ({'load': (L, E), 'balance': (L,)}; None without
    them).  ``route_bias``: (L, E) selection bias, which a biased router
    needs: the trained bias (the train state's ``router['bias']``), or
    ``init_route_state(cfg)['bias']`` for a model that was never trained."""
    h = _embed_inputs(cfg, params, batch)
    b, s = h.shape[0], h.shape[1]
    positions = _positions(cfg, batch, b, s, cache_pos)
    windows = cfg.windows()
    nd = cfg.first_dense
    hooks = param_hooks or {}

    def run(key, c, h, win, stack_cache, bias):
        hook = hooks.get(key, lambda lp: lp)

        def body(carry, xs):
            lp, window, layer_cache, lb = xs
            lp = hook(lp)
            h_new, c_new, st = block_fwd(
                c, lp, carry, positions=positions, window=window,
                cache=layer_cache, cache_pos=cache_pos, seq_shard=seq_shard,
                e_shard=e_shard, decode_attn=decode_attn, route_bias=lb)
            return h_new, (c_new, st)

        if remat:
            body = jax.checkpoint(body)
        xs = (params[key], jnp.asarray(win, jnp.int32), stack_cache, bias)
        h, (c_new, st) = jax.lax.scan(body, h, xs)
        return h, c_new, st

    if nd:
        h, prefix_cache, _ = run(
            "prefix", dense_config(cfg), h, windows[:nd],
            None if cache is None else cache["prefix"], None)
    if cfg.moe is not None and cfg.moe.biased and route_bias is None:
        raise ValueError("a router with a selection bias needs route_bias: "
                         "the trained bias, as the train state's "
                         "router['bias'] holds it")
    stack_cache = cache
    if nd and cache is not None:
        stack_cache = {k: v for k, v in cache.items() if k != "prefix"}
    h, new_cache, stats = run("layers", cfg, h, windows[nd:], stack_cache,
                              route_bias)
    if nd and cache is not None:
        new_cache = {**new_cache, "prefix": prefix_cache}
    h = rms_norm(h, params["final_norm"], cfg.norm_eps,
                 zero_centered=cfg.zero_centered_norm)
    return h, new_cache, stats


def output_head(cfg: ModelConfig, params: Dict) -> jax.Array:
    return (params["embed"].T if cfg.tie_embeddings else params["head"])


def _final_logits(cfg: ModelConfig, h_last: jax.Array,
                  params: Dict) -> jax.Array:
    """Last-position logits with softcap + TP-padding mask applied."""
    logits = h_last @ output_head(cfg, params)
    logits = softcap(logits.astype(jnp.float32), cfg.final_softcap)
    if cfg.vocab_padded > cfg.vocab:
        pad = jax.lax.broadcasted_iota(jnp.int32, (1, cfg.vocab_padded), 1)
        logits = jnp.where(pad < cfg.vocab, logits, -jnp.inf)
    return logits


def loss_fn(cfg: ModelConfig, params: Dict, batch: Dict, *,
            remat: bool = True, seq_shard: Callable = lambda x: x,
            e_shard: Callable = lambda x: x,
            param_hooks: Optional[Dict[str, Callable]] = None,
            gather_targets: bool = False, route_bias=None,
            with_stats: bool = False):
    """Next-token cross entropy (labels = batch['labels']), plus the
    sequence-wise balance loss of sigmoid-routed layers.  With
    ``with_stats``: (loss, routing stats), as :func:`forward_stats`."""
    h, _, stats = forward_stats(cfg, params, batch, remat=remat,
                                seq_shard=seq_shard, e_shard=e_shard,
                                param_hooks=param_hooks,
                                route_bias=route_bias)
    with scope("head"):
        loss = chunked_cross_entropy(
            h, output_head(cfg, params), batch["labels"],
            chunk=cfg.loss_chunk, final_softcap=cfg.final_softcap,
            mask=batch.get("loss_mask"),
            valid_vocab=(cfg.vocab if cfg.vocab_padded > cfg.vocab
                         else None),
            gather_targets=gather_targets)
    if stats is not None:
        loss = loss + jnp.sum(stats["balance"])
    return (loss, stats) if with_stats else loss


def prefill(cfg: ModelConfig, params: Dict, batch: Dict, *,
            cache: Optional[Dict] = None,
            seq_shard: Callable = lambda x: x,
            e_shard: Callable = lambda x: x,
            route_bias=None) -> Tuple[jax.Array, Dict]:
    """Forward pass that fills a KV cache; returns last-token logits."""
    tokens_like = batch.get("tokens", batch.get("embeds"))
    b, s = tokens_like.shape[0], tokens_like.shape[1]
    if cache is None:
        cache = init_cache(cfg, b, s)
    h, new_cache = forward(cfg, params, batch, cache=cache,
                           cache_pos=jnp.int32(0), seq_shard=seq_shard,
                           e_shard=e_shard, route_bias=route_bias)
    return _final_logits(cfg, h[:, -1, :], params), new_cache


def decode_step(cfg: ModelConfig, params: Dict, cache: Dict,
                tokens: jax.Array, pos, *,
                embeds: Optional[jax.Array] = None,
                seq_shard: Callable = lambda x: x,
                e_shard: Callable = lambda x: x,
                decode_attn=None, route_bias=None) -> Tuple[jax.Array, Dict]:
    """One decode step: tokens (B,) int32, pos scalar write offset.

    Returns (logits (B, V) f32, updated cache).
    """
    batch: Dict[str, Any] = {}
    if cfg.frontend == "audio_stub" and embeds is not None:
        batch["embeds"] = embeds
    else:
        batch["tokens"] = tokens[:, None]
    h, new_cache = forward(cfg, params, batch, cache=cache, cache_pos=pos,
                           seq_shard=seq_shard, e_shard=e_shard,
                           decode_attn=decode_attn, route_bias=route_bias)
    return _final_logits(cfg, h[:, -1, :], params), new_cache
