"""Operations and bytes that a DeepSeek-V3-style training step needs
(latent attention, a leading dense layer, sigmoid-routed experts of
which this chip holds a share, shared experts), counted from shapes.

As in ``flops.py``: the work the algorithm needs, never padded lanes,
masked scores or idle capacity slots.  Attention counts causal pairs;
the routed experts count the (token, slot) pairs their held experts
keep (the caller reads them off the load counter), and the head the
vocabulary slice held.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

from chipbench.flops import causal_pairs


@dataclass(frozen=True)
class MLAShape:
    d: int
    heads: int
    nope: int
    rope: int
    v: int
    kv_lora: int
    q_lora: Optional[int]
    dense_layers: int
    moe_layers: int
    d_ff: int                   # the dense layers' FFN width
    d_expert: int
    router_experts: int         # the router's outputs
    held: int                   # routed experts held here
    top_k: int
    shared: int                 # shared experts, d_expert wide each
    vocab: int                  # vocabulary rows held here

    @staticmethod
    def from_config(c: dict) -> "MLAShape":
        nd = c["first_k_dense_replace"]
        share = c["expert_share"]
        return MLAShape(
            d=c["hidden_size"], heads=c["num_attention_heads"],
            nope=c["qk_nope_head_dim"], rope=c["qk_rope_head_dim"],
            v=c["v_head_dim"], kv_lora=c["kv_lora_rank"],
            q_lora=c["q_lora_rank"], dense_layers=nd,
            moe_layers=c["num_hidden_layers"] - nd,
            d_ff=c["intermediate_size"],
            d_expert=c["moe_intermediate_size"],
            router_experts=share["router_experts"], held=share["held"],
            top_k=c["num_experts_per_tok"], shared=c["n_shared_experts"],
            vocab=c["vocab_size"])

    @property
    def mla_params(self) -> int:
        """Projections of one attention layer: query (direct or low
        rank), latent, rope key, key and value up-projections, output."""
        qk = self.heads * (self.nope + self.rope)
        q = self.d * qk if self.q_lora is None \
            else self.d * self.q_lora + self.q_lora * qk
        return (q + self.d * (self.kv_lora + self.rope)
                + self.kv_lora * self.heads * (self.nope + self.v)
                + self.heads * self.v * self.d)

    @property
    def expert_params(self) -> int:
        return 3 * self.d * self.d_expert

    def param_count(self) -> int:
        """Parameters held (RMSNorm scales included)."""
        norms = 2 * self.d + self.kv_lora + (self.q_lora or 0)
        layers = self.dense_layers + self.moe_layers
        moe = (self.d * self.router_experts
               + (self.held + self.shared) * self.expert_params)
        return (2 * self.vocab * self.d + self.d
                + layers * (self.mla_params + norms)
                + self.dense_layers * 3 * self.d * self.d_ff
                + self.moe_layers * moe)


def forward_flops(s: MLAShape, tokens: int, attn_pairs: int,
                  expert_rows: int, head_rows: int) -> float:
    """Matmul FLOPs of one forward pass.

    ``tokens`` rows through the projections, the dense FFN, the router
    and the shared experts; ``attn_pairs`` (query, key) pairs per head
    through QK^T (``nope`` + ``rope`` dims) and PV (``v``);
    ``expert_rows`` rows through held experts, summed over the MoE
    layers; ``head_rows`` through the head.
    """
    attn = 2 * tokens * s.mla_params \
        + 2 * attn_pairs * s.heads * (s.nope + s.rope + s.v)
    moe = 2 * tokens * (s.d * s.router_experts
                        + s.shared * s.expert_params)
    return float((s.dense_layers + s.moe_layers) * attn
                 + s.dense_layers * 2 * tokens * 3 * s.d * s.d_ff
                 + s.moe_layers * moe
                 + 2 * expert_rows * s.expert_params
                 + 2 * head_rows * s.d * s.vocab)


def train_step_flops(s: MLAShape, batch: int, seq: int,
                     expert_rows: int) -> float:
    """Forward + backward (twice the forward's matmuls); recomputation
    for rematerialisation does not count."""
    t = batch * seq
    return 3.0 * forward_flops(s, t, causal_pairs(batch, seq), expert_rows,
                               t)


def train_step_bytes(s: MLAShape, param_bytes: int = 4) -> float:
    """State traffic of one optimizer step: parameters read and written,
    gradients written and read, Adam's two moments read and written."""
    return float(s.param_count() * (4 * param_bytes + 4 * 4))


def kept_rows(load, first: int, held: int, capacity: int) -> int:
    """Routed (token, slot) pairs the held experts keep, summed over the
    MoE layers, from a step's picks per expert (L, E) under one dispatch
    chunk's capacity."""
    return int(sum(min(int(x), capacity) for row in load
                   for x in row[first:first + held]))
