"""Step functions (train / prefill / decode) with production sharding.

Distribution layout:
  * params: TP over 'model' (repro.models.lm.param_specs), replicated over
    the DP axes ('pod', 'data');
  * gradient sync: the paper's partitioned engine inside shard_map over
    the DP axes (bulk | per_leaf | partitioned modes, aggregation bytes,
    optional compressed comm dtype);
  * optimizer: ZeRO-1 — flat moments sharded over ALL mesh axes;
  * activations: sequence-parallel residual stream (seq over 'model')
    between layers;
  * decode caches: batch over DP, sequence over 'model' (over every axis
    when batch==1, e.g. long_500k).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.earlybird import SyncConfig, value_and_synced_grad
from repro.models import lm
from repro.optim.adamw import AdamWConfig, adamw_update, init_opt_state
from repro.optim.schedule import warmup_cosine

from .mesh import all_axes, dp_axes, dp_size, model_size
from repro.compat import shard_map
from repro.runtime.spans import scope


@dataclass(frozen=True)
class StepConfig:
    sync_mode: str = "partitioned"     # bulk | per_leaf | partitioned
    aggr_bytes: int = 4 << 20
    comm_dtype: Optional[str] = None   # e.g. 'bfloat16' (grad compression)
    remat: bool = True
    param_dtype: str = "bfloat16"
    seq_parallel: bool = True
    peak_lr: float = 3e-4
    warmup_steps: int = 100
    total_steps: int = 10000
    adam: AdamWConfig = field(default_factory=AdamWConfig)
    cache_dtype: str = "bfloat16"
    ce_gather_targets: bool = False  # True = naive take_along_axis CE
    flash_decode: bool = False       # partitioned-KV decode attention
    moe_chunk: int = 0               # override MoE dispatch chunk (0=default)
    capacity_factor: float = 0.0     # override MoE capacity factor (0=default)


def _seq_shard_fn(mesh, enabled: bool) -> Callable:
    """Residual-stream constraint: shard seq over 'model' (SP)."""
    if not enabled:
        return lambda x: x
    ms = model_size(mesh)

    def f(x):
        if x.ndim == 3 and x.shape[1] % ms == 0 and x.shape[1] >= ms:
            return jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, P(None, "model", None)))
        return x

    return f


def _e_shard_fn(mesh) -> Callable:
    """Expert-parallel constraint: pin (E, ...) tensors to 'model'."""
    ms = model_size(mesh)

    def f(x):
        if x.ndim >= 2 and x.shape[0] % ms == 0:
            spec = P("model", *([None] * (x.ndim - 1)))
            return jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, spec))
        return x

    return f


def _batch_struct(cfg, seq_len: int, global_batch: int, mesh,
                  with_labels: bool) -> Tuple[Dict, Dict]:
    """(ShapeDtypeStruct tree, shard_map local-spec tree) for one batch."""
    dp = dp_axes(mesh)
    structs: Dict[str, Any] = {}
    specs: Dict[str, Any] = {}

    def add(name, shape, dtype, spec):
        structs[name] = jax.ShapeDtypeStruct(
            shape, dtype, sharding=NamedSharding(mesh, spec))
        specs[name] = spec

    if cfg.frontend == "audio_stub":
        add("embeds", (global_batch, seq_len, cfg.d_model), jnp.bfloat16,
            P(dp, None, None))
    else:
        add("tokens", (global_batch, seq_len), jnp.int32, P(dp, None))
    if cfg.frontend == "vision_stub":
        add("patch_embeds", (global_batch, 256, cfg.d_model), jnp.bfloat16,
            P(dp, None, None))
        add("positions", (3, global_batch, seq_len), jnp.int32,
            P(None, dp, None))
    if with_labels:
        add("labels", (global_batch, seq_len), jnp.int32, P(dp, None))
    return structs, specs


def param_shardings(cfg, mesh):
    specs = lm.param_specs(cfg)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------------------
# Train
# ---------------------------------------------------------------------------

def _apply_overrides(cfg, scfg):
    if cfg.moe is not None and (scfg.moe_chunk or scfg.capacity_factor):
        import dataclasses
        moe = cfg.moe
        if scfg.moe_chunk:
            moe = dataclasses.replace(moe, dispatch_chunk=scfg.moe_chunk)
        if scfg.capacity_factor:
            moe = dataclasses.replace(moe,
                                      capacity_factor=scfg.capacity_factor)
        cfg = cfg.replace(moe=moe)
    return cfg


def make_train_step(cfg, mesh, scfg: StepConfig, *, seq_len: int,
                    global_batch: int):
    """Returns (step_fn, state_structs, batch_structs, grad_fn).

    step_fn(state, batch) -> (state, loss); state = {'params', 'opt'}.
    grad_fn(params, batch) -> (loss, grads): the synced gradients the
    step applies (what the sync modes must agree on).

    A sigmoid-routed MoE (``cfg.moe.biased``) adds state['router'] =
    {'bias', 'load'}, each (L_moe, E) f32 (``lm.init_route_state``): the
    selection bias, which gets no gradient and no weight decay, and the
    step's routed (token, slot) pairs per expert, summed over the data
    axes.  The step then moves the bias by DeepSeek-V3's aux-loss-free
    rule, ``bias += bias_rate * sign(mean load - load)``, and
    grad_fn(params, batch, bias) -> ((loss, load), grads).
    """
    cfg = cfg.with_tp(model_size(mesh)).replace(param_dtype=scfg.param_dtype)
    cfg = _apply_overrides(cfg, scfg)
    dp = dp_axes(mesh)
    adam = scfg.adam
    biased = cfg.moe is not None and cfg.moe.biased

    sync = SyncConfig(mode=scfg.sync_mode, axes=dp,
                      aggr_bytes=scfg.aggr_bytes,
                      comm_dtype=scfg.comm_dtype)
    seq_shard = _seq_shard_fn(mesh, scfg.seq_parallel)
    pspecs = lm.param_specs(cfg)

    e_shard = _e_shard_fn(mesh)

    def local_loss(p, batch, *bias, param_hooks=None):
        out = lm.loss_fn(cfg, p, batch, remat=scfg.remat,
                         seq_shard=seq_shard, e_shard=e_shard,
                         param_hooks=param_hooks,
                         gather_targets=scfg.ce_gather_targets,
                         route_bias=bias[0] if biased else None,
                         with_stats=biased)
        if not biased:
            return out
        loss, stats = out
        load = stats["load"]
        for ax in dp:
            load = jax.lax.psum(load, ax)
        return loss, load

    vg = value_and_synced_grad(local_loss, sync, has_aux=biased,
                               param_specs=pspecs)

    batch_structs, batch_local_specs = _batch_struct(
        cfg, seq_len, global_batch, mesh, with_labels=True)

    params_struct = lm.param_shapes(cfg)
    whole = jax.tree.map(lambda _: P(), params_struct)
    grad_fn = shard_map(
        vg, mesh=mesh,
        in_specs=(whole, batch_local_specs, *([P()] if biased else [])),
        out_specs=((P(), P()) if biased else P(), whole),
        check_vma=False, axis_names=set(dp))

    def step_fn(state, batch):
        if biased:
            bias = state["router"]["bias"]
            (loss, load), grads = grad_fn(state["params"], batch, bias)
        else:
            loss, grads = grad_fn(state["params"], batch)
        lr = warmup_cosine(state["opt"]["step"], peak_lr=scfg.peak_lr,
                           warmup_steps=scfg.warmup_steps,
                           total_steps=scfg.total_steps)
        with scope("optim"):
            new_params, new_opt = adamw_update(state["params"], grads,
                                               state["opt"], lr, adam)
        new_state = {"params": new_params, "opt": new_opt}
        if biased:
            mean = jnp.mean(load, axis=-1, keepdims=True)
            new_state["router"] = {
                "bias": bias + cfg.moe.bias_rate * jnp.sign(mean - load),
                "load": load}
        return new_state, loss

    # shardings / abstract inputs
    psh = param_shardings(cfg, mesh)
    opt_struct = jax.eval_shape(lambda p: init_opt_state(p, adam),
                                params_struct)
    from repro.optim.adamw import opt_state_specs
    ospecs = opt_state_specs(pspecs, params_struct, dp_axes=dp,
                             dp_total=dp_size(mesh))
    opt_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), ospecs,
                          is_leaf=lambda x: isinstance(x, P))

    def with_sh(struct, sh):
        return jax.tree.map(
            lambda s, h: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=h),
            struct, sh)

    state_structs = {"params": with_sh(params_struct, psh),
                     "opt": with_sh(opt_struct, opt_sh)}
    if biased:
        state_structs["router"] = with_sh(
            jax.eval_shape(lambda: lm.init_route_state(cfg)),
            {"bias": NamedSharding(mesh, P()),
             "load": NamedSharding(mesh, P())})
    return step_fn, state_structs, batch_structs, grad_fn


# ---------------------------------------------------------------------------
# Serve: prefill + decode
# ---------------------------------------------------------------------------

def _cache_shardings(cfg, mesh, global_batch: int):
    dp = dp_axes(mesh)
    batch_shardable = global_batch >= dp_size(mesh) \
        and global_batch % dp_size(mesh) == 0
    if batch_shardable:
        b_ax, s_ax = dp, ("model",)
    else:  # e.g. long_500k batch=1: give every axis to the sequence
        b_ax, s_ax = None, tuple(mesh.axis_names)
    specs = lm.cache_specs(cfg, data_axis=b_ax, seq_axis=s_ax)
    # mamba state: heads over model; with tiny batch keep heads on model only
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


def make_prefill_step(cfg, mesh, scfg: StepConfig, *, seq_len: int,
                      global_batch: int):
    """prefill_step(params, batch, cache, route_bias=None) -> (logits,
    cache); a biased router needs ``route_bias``, the trained bias."""
    cfg = cfg.with_tp(model_size(mesh)).replace(param_dtype=scfg.param_dtype)
    cfg = _apply_overrides(cfg, scfg)
    seq_shard = _seq_shard_fn(mesh, scfg.seq_parallel)

    e_shard = _e_shard_fn(mesh)

    def prefill_step(params, batch, cache, route_bias=None):
        return lm.prefill(cfg, params, batch, cache=cache,
                          seq_shard=seq_shard, e_shard=e_shard,
                          route_bias=route_bias)

    batch_structs, _ = _batch_struct(cfg, seq_len, global_batch, mesh,
                                     with_labels=False)
    cache_struct = jax.eval_shape(
        lambda: lm.init_cache(cfg, global_batch, seq_len,
                              jnp.dtype(scfg.cache_dtype)))
    csh = _cache_shardings(cfg, mesh, global_batch)
    cache_structs = jax.tree.map(
        lambda s, h: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=h),
        cache_struct, csh)
    params_struct = lm.param_shapes(cfg)
    psh = param_shardings(cfg, mesh)
    params_structs = jax.tree.map(
        lambda s, h: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=h),
        params_struct, psh)
    return prefill_step, params_structs, batch_structs, cache_structs


def _flash_decode_fn(mesh, global_batch: int):
    """Partitioned-KV decode attention hook (shard_map flash decode).

    The KV cache is sequence-sharded (over 'model', or over every axis at
    batch==1); each shard computes its partial attention and the partitions
    combine via tiny pmax/psum collectives — the paper's partition-consume
    pattern on the inference side.
    """
    from repro.core.flash_decode import flash_decode_shard

    batch_shardable = global_batch >= dp_size(mesh) \
        and global_batch % dp_size(mesh) == 0
    seq_axes = ("model",) if batch_shardable else tuple(mesh.axis_names)
    kv_spec = P(None, seq_axes, None, None)

    def hook(q, k, v, *, pos, window, attn_softcap, scale):
        def inner(q_, k_, v_, pos_, window_):
            return flash_decode_shard(q_, k_, v_, axis=seq_axes, pos=pos_,
                                      window=window_,
                                      attn_softcap=attn_softcap, scale=scale)

        return shard_map(
            inner, mesh=mesh,
            in_specs=(P(), kv_spec, kv_spec, P(), P()),
            out_specs=P(), check_vma=False,
            axis_names=set(seq_axes))(q, k, v, pos, window)

    return hook


def make_decode_step(cfg, mesh, scfg: StepConfig, *, seq_len: int,
                     global_batch: int):
    """decode_step(params, cache, tokens, pos) -> (logits, cache).

    ``seq_len`` is the KV-cache length; one new token is decoded.  A
    biased router needs ``route_bias``, the trained bias.
    """
    cfg = cfg.with_tp(model_size(mesh)).replace(param_dtype=scfg.param_dtype)
    cfg = _apply_overrides(cfg, scfg)
    dp = dp_axes(mesh)
    batch_shardable = global_batch >= dp_size(mesh) \
        and global_batch % dp_size(mesh) == 0
    tok_spec = P(dp) if batch_shardable else P()

    e_shard = _e_shard_fn(mesh)
    decode_attn = (_flash_decode_fn(mesh, global_batch)
                   if scfg.flash_decode else None)

    def decode_step(params, cache, tokens, pos, embeds=None,
                    route_bias=None):
        return lm.decode_step(cfg, params, cache, tokens, pos,
                              embeds=embeds, e_shard=e_shard,
                              decode_attn=decode_attn,
                              route_bias=route_bias)

    cache_struct = jax.eval_shape(
        lambda: lm.init_cache(cfg, global_batch, seq_len,
                              jnp.dtype(scfg.cache_dtype)))
    csh = _cache_shardings(cfg, mesh, global_batch)
    cache_structs = jax.tree.map(
        lambda s, h: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=h),
        cache_struct, csh)
    params_struct = lm.param_shapes(cfg)
    psh = param_shardings(cfg, mesh)
    params_structs = jax.tree.map(
        lambda s, h: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=h),
        params_struct, psh)
    tok_structs = jax.ShapeDtypeStruct(
        (global_batch,), jnp.int32, sharding=NamedSharding(mesh, tok_spec))
    pos_struct = jax.ShapeDtypeStruct((), jnp.int32)
    extra = {}
    if cfg.frontend == "audio_stub":
        extra["embeds"] = jax.ShapeDtypeStruct(
            (global_batch, 1, cfg.d_model), jnp.bfloat16,
            sharding=NamedSharding(mesh, P(dp if batch_shardable else None,
                                           None, None)))
    return decode_step, params_structs, cache_structs, tok_structs, \
        pos_struct, extra
