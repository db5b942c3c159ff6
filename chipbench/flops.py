"""Operations and bytes that the work needs, counted from shapes.

These are the numerators of every roofline and ``mfu`` share: the least
time the chip could take is ``max(flops / peak_flops, bytes /
peak_bandwidth)``.  They count the work the algorithm needs — causal
attention pairs, the experts each token is routed to, real messages —
never padded lanes, masked scores or idle capacity slots, so a share
reads the same work whatever implements it.
"""

from __future__ import annotations

from dataclasses import dataclass


# ---------------------------------------------------------------------------
# Decoder LM with top-k routed SwiGLU experts (GQA attention)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class LMShape:
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    experts: int
    top_k: int
    d_expert: int
    vocab: int
    tied: bool = False          # output head = embedding transposed

    @staticmethod
    def from_config(c: dict) -> "LMShape":
        d, h = c["hidden_size"], c["num_attention_heads"]
        return LMShape(layers=c["num_hidden_layers"], d=d, heads=h,
                       kv_heads=c["num_key_value_heads"], head_dim=d // h,
                       experts=c["num_local_experts"],
                       top_k=c["num_experts_per_tok"],
                       d_expert=c["intermediate_size"],
                       vocab=c["vocab_size"],
                       tied=c["tie_word_embeddings"])

    @property
    def attn_params(self) -> int:
        """q, k, v and output projections of one layer."""
        return self.d * self.head_dim * (2 * self.heads + 2 * self.kv_heads)

    @property
    def expert_params(self) -> int:
        return 3 * self.d * self.d_expert

    def param_count(self) -> int:
        per_layer = (self.attn_params + self.d * self.experts
                     + self.experts * self.expert_params + 2 * self.d)
        tables = (1 if self.tied else 2) * self.vocab * self.d
        return tables + self.d + self.layers * per_layer


def forward_flops(s: LMShape, tokens: int, attn_pairs: int, head_rows: int,
                  expert_rows: int = -1) -> float:
    """Matmul FLOPs of one forward pass.

    ``tokens`` rows go through the projections and the router;
    ``expert_rows`` (default ``tokens * top_k``) through expert FFNs;
    ``attn_pairs`` (query, key) pairs per head through QK^T and PV;
    ``head_rows`` rows through the output head.
    """
    if expert_rows < 0:
        expert_rows = tokens * s.top_k
    per_layer = (2 * tokens * (s.attn_params + s.d * s.experts)
                 + 2 * expert_rows * s.expert_params
                 + 4 * attn_pairs * s.heads * s.head_dim)
    return float(s.layers * per_layer + 2 * head_rows * s.d * s.vocab)


def causal_pairs(batch: int, seq: int) -> int:
    return batch * seq * (seq + 1) // 2


def train_step_flops(s: LMShape, batch: int, seq: int) -> float:
    """Forward + backward (twice the forward's matmuls); recomputation
    for rematerialisation does not count."""
    t = batch * seq
    return 3.0 * forward_flops(s, t, causal_pairs(batch, seq), t)


def train_step_bytes(s: LMShape, param_bytes: int = 4) -> float:
    """State traffic of one optimizer step: parameters read and written,
    gradients written and read, Adam's two moments read and written."""
    return float(s.param_count() * (2 * param_bytes + 2 * param_bytes
                                    + 4 * 4))


# ---------------------------------------------------------------------------
# Fabric point (three queue stages over wire messages)
# ---------------------------------------------------------------------------

F32 = 4


def fabric_point_bytes(n_messages: int, n_ranks: int) -> float:
    """What a point must move through device memory at the least: its
    four per-message columns (release time, stage-1 cost, wire cost,
    rendezvous delay) in and one completion time per rank out.  The
    stages between may stay on the chip."""
    return float(n_messages * 4 * F32 + n_ranks * F32)


def least_time(flops: float, nbytes: float, peak: dict) -> float:
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])
