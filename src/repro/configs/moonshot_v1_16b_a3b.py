"""moonshot-v1-16b-a3b (Moonlight-16B-A3B, ``deepseek_v3``): 27L d=2048
vocab=163840, untied; MLA 16H (no query low-rank, kv_lora=512, qk
128+64, v=128); layer 0 a dense SwiGLU of 11264, then 26 MoE layers of
64 experts (d_expert=1408) top-6 with 2 shared, sigmoid ``noaux_tc``
routing renormalised and scaled by 2.446; rope_theta 5e4, RMSNorm eps
1e-5 [hf:moonshotai/Moonlight-16B-A3B].  Routing-bias speed 1e-3 and
balance weight 1e-4 are DeepSeek-V3's (the config gives none)."""
from repro.models.lm import MLAConfig, ModelConfig
from repro.models.moe import MoEConfig

ARCH_ID = "moonshot-v1-16b-a3b"

BIAS_RATE = 1e-3        # DeepSeek-V3 report: gamma
BALANCE_WEIGHT = 1e-4   # DeepSeek-V3 report: alpha


def config() -> ModelConfig:
    return ModelConfig(
        name=ARCH_ID, n_layers=27, d_model=2048, n_heads=16, n_kv=16,
        d_ff=11264, vocab=163840, rope_theta=50000.0, norm_eps=1e-5,
        first_dense=1,
        mla=MLAConfig(q_lora=None, kv_lora=512, qk_nope=128, qk_rope=64,
                      v_dim=128),
        moe=MoEConfig(n_experts=64, top_k=6, d_expert=1408,
                      score="sigmoid", routed_scale=2.446, n_shared=2,
                      bias_rate=BIAS_RATE, balance_weight=BALANCE_WEIGHT))


def smoke_config() -> ModelConfig:
    return ModelConfig(
        name=ARCH_ID + "-smoke", n_layers=3, d_model=64, n_heads=4, n_kv=4,
        d_ff=96, vocab=128, rope_theta=50000.0, norm_eps=1e-5,
        first_dense=1,
        mla=MLAConfig(q_lora=None, kv_lora=16, qk_nope=16, qk_rope=8,
                      v_dim=16),
        moe=MoEConfig(n_experts=8, top_k=3, d_expert=24, score="sigmoid",
                      routed_scale=2.446, n_shared=2, bias_rate=BIAS_RATE,
                      balance_weight=BALANCE_WEIGHT))
