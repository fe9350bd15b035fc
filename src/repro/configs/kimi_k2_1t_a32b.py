"""kimi-k2-1t-a32b [moe] — hf:moonshotai/Kimi-K2-Instruct config.json
(model_type kimi_k2, the DeepSeek-V3 block).
61L d_model=7168, layer 0 a dense SwiGLU (18432), then 60 MoE layers:
MLA with 64 heads (q_lora 1536, kv_lora 512, nope 128 + rope 64, v 128),
rope_theta 50000 under YaRN (factor 32 of 4096), 384 routed experts top-8
(sigmoid scores, noaux_tc correction bias, normalised, x2.827) of width
2048 plus 1 shared expert, vocab 163840, untied head, RMSNorm eps 1e-6."""
from ..models.config import ModelConfig

CONFIG = ModelConfig(
    name="kimi-k2-1t-a32b",
    family="moe",
    n_layers=61,
    d_model=7168,
    n_heads=64,
    n_kv_heads=64,
    d_ff=18432,
    vocab=163840,
    dense_layers=1,
    q_lora_rank=1536,
    kv_lora_rank=512,
    qk_nope_dim=128,
    qk_rope_dim=64,
    v_head_dim=128,
    rope_theta=50000.0,
    # factor, original context, beta_fast, beta_slow, mscale, mscale_all_dim
    yarn=(32.0, 4096, 1.0, 1.0, 1.0, 1.0),
    norm_eps=1e-6,
    moe_experts=384,
    moe_topk=8,
    moe_dff=2048,
    moe_shared_ff=2048,
    moe_score="sigmoid",
    moe_score_bias=True,
    moe_route_scale=2.827,
    # the grouped expert layer, here holding every expert; a deployment
    # gives each chip its share (moe_held, moe_held_offset)
    moe_held=384,
    moe_capacity=2.0,
)
