"""A small copy of the Moonlight configuration, for CPU tests: every
mechanism of the cell (MLA without a query low-rank, a leading dense
layer, sigmoid routing with a selection bias, shared experts, an expert
share) at widths that run in seconds."""

from __future__ import annotations

from chipbench import harness


def config(**over) -> dict:
    """d 64, 4 heads (kv_lora 16, qk 16 + 8, v 16), 1 dense + 2 MoE
    layers, 16 routed experts top-4 of which 4 (from 4) are held, 2
    shared, vocabulary 256.  The dense width is the held experts' (the
    ``dense_routed`` fault reads it as them)."""
    c = harness.load_json(harness.PKG / "configs" / "moonlight-16b-a3b.json")
    c.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
             kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
             v_head_dim=16, num_hidden_layers=3, first_k_dense_replace=1,
             moe_intermediate_size=24, intermediate_size=96,
             num_experts_per_tok=4, n_shared_experts=2, n_routed_experts=4,
             vocab_size=256, moe_dispatch_chunk=64,
             expert_share={"router_experts": 16, "first": 4, "held": 4,
                           "group_size": 4, "group_rank": 1})
    c.update(over)
    return c
