"""The system under test of model cells, built as the configuration says.

``model_config`` turns a configuration file (HF-style keys, as run)
into the program's ``ModelConfig`` for its registered ``arch``, and
refuses one the program would not run as stated.
"""

from __future__ import annotations

import dataclasses
import math


def model_config(c: dict, param_dtype: str, capacity_factor: float):
    from repro.configs import get_config
    base = get_config(c["arch"])
    d, h = c["hidden_size"], c["num_attention_heads"]
    moe = dataclasses.replace(
        base.moe, n_experts=c["num_local_experts"],
        top_k=c["num_experts_per_tok"], d_expert=c["intermediate_size"],
        capacity_factor=capacity_factor,
        dispatch_chunk=c["moe_dispatch_chunk"],
        min_capacity=c["moe_min_capacity"])
    cfg = base.replace(
        n_layers=c["num_hidden_layers"], d_model=d, n_heads=h,
        n_kv=c["num_key_value_heads"], head_dim=0, vocab=c["vocab_size"],
        rope_theta=c["rope_theta"], q_scale=c["attention_multiplier"],
        tie_embeddings=c["tie_word_embeddings"],
        qkv_bias=c["attention_bias"], moe=moe, param_dtype=param_dtype)
    as_stated(cfg, c)
    return cfg


def as_stated(cfg, c: dict) -> None:
    """Raise where the program departs from the configuration file."""
    want = {
        "mixer": "attn", "window_pattern": "global", "attn_softcap": None,
        "final_softcap": None, "post_norm": False,
        "zero_centered_norm": False, "emb_scale": False,
        "frontend": "tokens", "mla": None, "mamba": None,
        "mrope_sections": None, "d_ff": 0,
    }
    bad = {k: getattr(cfg, k) for k, v in want.items()
           if getattr(cfg, k) != v}
    # multipliers the program has no option for must be identities
    for key in ("embedding_multiplier", "residual_multiplier",
                "logits_scaling"):
        if c[key] != 1.0:
            bad[key] = c[key]
    if c["hidden_act"] != "silu" or not math.isclose(c["rms_norm_eps"],
                                                     1e-6):
        bad["hidden_act/rms_norm_eps"] = (c["hidden_act"],
                                          c["rms_norm_eps"])
    if bad:
        raise ValueError(f"{c['arch']}: the program would not run the "
                         f"configuration as stated: {bad}")


def mesh_for(n_chips: int):
    from repro.runtime import elastic
    return elastic.build_mesh(elastic.plan_mesh(n_chips, 1))


def check_tree(made, structs, what: str) -> None:
    """The benchmark's weights have the program's layout and types."""
    import jax
    a = jax.tree.map(lambda x: (tuple(x.shape), str(x.dtype)), made)
    b = jax.tree.map(lambda x: (tuple(x.shape), str(x.dtype)), structs)
    if a != b:
        raise ValueError(f"{what}: the benchmark's layout {a} is not the "
                         f"program's {b}")
