"""lfm2-8b-a1b [hybrid moe] — LiquidAI LFM2-8B-A1B
[hf:LiquidAI/LFM2-8B-A1B config.json, model_type lfm2_moe].
24L: 18 gated short-conv layers (L_cache 3) and 6 GQA layers at 2, 6, 10,
14, 18, 21; d=2048, 32H (kv=8) of 64; the first 2 feed-forwards dense
SwiGLU 7168, then MoE: 32 experts of 1792, top-4, no shared expert,
sigmoid router with a selection-only expert bias; vocab=65536.

Each block is ``h = x + op(operator_norm(x))``, ``out = h +
ffn(ffn_norm(h))``; the final norm sits before a tied head.
"""
from repro.configs.base import ModelConfig, MoeSpec
from repro.models.api import register

LAYER_TYPES = (
    "conv", "conv", "full_attention", "conv", "conv", "conv",
    "full_attention", "conv", "conv", "conv", "full_attention", "conv",
    "conv", "conv", "full_attention", "conv", "conv", "conv",
    "full_attention", "conv", "conv", "full_attention", "conv", "conv")

# what config.json does not state, taken from LFM2's published modelling
ASSUMED = (
    "tie_embeddings: the head is the embedding table (LFM2 ties them)",
    "conv: in_proj d->3d split as B, C, x; y = out_proj(C * "
    "causal_dwconv(B * x)); no biases (conv_bias false)",
    "attention: per-head RMSNorm on q and k (q_layernorm, k_layernorm) "
    "before rotate-half RoPE; no biases",
    "moe: expert and dense feed-forwards are w2(silu(w1 x) * w3 x); router "
    "weights renormalised with +1e-6",
)

CONFIG = register(ModelConfig(
    name="lfm2-8b-a1b", family="lm",
    n_layers=24, d_model=2048, n_heads=32, kv_heads=8, d_ff=7168,
    vocab=65536, act="swiglu", norm="rmsnorm", norm_eps=1e-5,
    tie_embeddings=True, rope_theta=1e6, qk_norm=True,
    layer_types=LAYER_TYPES, n_dense_layers=2, d_conv=3,
    moe=MoeSpec(n_experts=32, top_k=4, d_ff=1792, shared_expert=False,
                router="sigmoid", expert_bias=True, norm_topk=True,
                routed_scale=1.0),
))


def smoke_config():
    return ModelConfig(
        name="lfm2-smoke", family="lm",
        n_layers=4, d_model=64, n_heads=4, kv_heads=2, d_ff=128,
        vocab=128, act="swiglu", norm="rmsnorm", norm_eps=1e-5,
        tie_embeddings=True, rope_theta=1e6, qk_norm=True,
        layer_types=("conv", "conv", "full_attention", "conv"),
        n_dense_layers=1, d_conv=3,
        moe=MoeSpec(n_experts=8, top_k=2, d_ff=64, shared_expert=False,
                    router="sigmoid", expert_bias=True, norm_topk=True),
        remat=False)
