"""Decoder block: mixer (attn / mamba / hybrid / MLA) + FFN (dense / MoE).

One block function is scanned over the stacked layer parameters; per-layer
heterogeneity (sliding-window vs global attention) rides in as a scanned
``window`` scalar so a single compiled body serves all layers.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.runtime.spans import scope

from .attention import attention_fwd, mla_fwd
from .layers import rms_norm, swiglu
from .mamba import mamba_fwd
from .moe import moe_fwd, routed_moe_fwd


def block_fwd(cfg, lp: Dict, h: jax.Array, *, positions, window,
              cache: Optional[Dict] = None, cache_pos=None,
              seq_shard=lambda x: x, e_shard=lambda x: x,
              decode_attn=None, route_bias=None,
              ) -> Tuple[jax.Array, Optional[Dict], Optional[Dict]]:
    """One decoder layer.  ``cfg`` is a ModelConfig (static).

    cache (decode/prefill): per-layer slice of the stacked cache pytree.
    ``route_bias``: the layer's selection bias (sigmoid-routed MoE).
    Returns (h', new per-layer cache or None, routing stats or None).
    """
    zc = cfg.zero_centered_norm
    eps = cfg.norm_eps
    h = seq_shard(h)
    new_cache: Dict = {}
    stats = None

    # ---- mixer ----
    hin = rms_norm(h, lp["ln1"], eps, zero_centered=zc)
    outs = []
    if cfg.mixer in ("attn", "hybrid"):
        if cfg.mla is not None:
            with scope("mla"):
                a_out, kvc = mla_fwd(
                    lp["attn"], hin, positions=positions,
                    qk_nope=cfg.mla.qk_nope, qk_rope=cfg.mla.qk_rope,
                    rope_theta=cfg.rope_theta, window=window,
                    cache=None if cache is None else
                    {"ckv": cache["ckv"], "kr": cache["kr"]},
                    cache_pos=cache_pos, q_chunk=cfg.q_chunk, eps=eps)
        else:
            a_out, kvc = attention_fwd(
                lp["attn"], hin, positions=positions,
                head_map=cfg.head_map, window=window,
                attn_softcap=cfg.attn_softcap, rope_theta=cfg.rope_theta,
                mrope_sections=cfg.mrope_sections, q_scale=cfg.q_scale,
                cache=None if cache is None else
                {"k": cache["k"], "v": cache["v"]},
                cache_pos=cache_pos, q_chunk=cfg.q_chunk,
                decode_attn=decode_attn)
        if kvc is not None:
            new_cache.update(kvc)
        outs.append(("attn", a_out))
    if cfg.mixer in ("mamba", "hybrid"):
        m_out, mst = mamba_fwd(
            lp["mamba"], hin, mc=cfg.mamba, d_model=cfg.d_model,
            cache=None if cache is None else
            {k: cache[k] for k in ("state", "conv_x", "conv_B", "conv_C")})
        if mst is not None:
            new_cache.update(mst)
        outs.append(("mamba", m_out))

    if cfg.mixer == "hybrid":
        # Hymba: per-branch normalization, then mean-combine.
        mix = (rms_norm(outs[0][1], lp["norm_attn"], eps, zero_centered=zc)
               + rms_norm(outs[1][1], lp["norm_mamba"], eps,
                          zero_centered=zc)) * 0.5
    else:
        mix = outs[0][1]
    if cfg.post_norm:
        mix = rms_norm(mix, lp["ln1_post"], eps, zero_centered=zc)
    h = h + mix

    # ---- FFN ----
    if cfg.d_ff > 0 or cfg.moe is not None:
        hin2 = rms_norm(h, lp["ln2"], eps, zero_centered=zc)
        if cfg.moe is not None and cfg.moe.biased:
            f_out, stats = routed_moe_fwd(
                lp["moe"], hin2, mo=cfg.moe, bias=route_bias,
                e_shard=e_shard, tok_shard=seq_shard)
        elif cfg.moe is not None:
            f_out = moe_fwd(lp["moe"], hin2, mo=cfg.moe, e_shard=e_shard,
                            tok_shard=seq_shard)
        else:
            with scope("mlp.dense"):
                f_out = swiglu(lp["mlp"], hin2)
        if cfg.post_norm:
            f_out = rms_norm(f_out, lp["ln2_post"], eps, zero_centered=zc)
        h = h + f_out

    return h, (new_cache if cache is not None else None), stats
