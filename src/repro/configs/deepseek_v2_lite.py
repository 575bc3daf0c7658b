"""deepseek-v2-lite [moe] — 27L d_model=2048 16H vocab=102400, untied head —
MLA without a query LoRA (kv_lora=512, nope 128 + rope 64, v 128) with YaRN
rope (factor 40 over 4096 positions); layer 0 a dense SwiGLU FFN (10944),
layers 1-26 2 shared + 64 routed experts (1408) top-6, softmax gates left
unnormalised. [hf:deepseek-ai/DeepSeek-V2-Lite config.json]"""
from repro.models.attention import MLAConfig
from repro.models.layers import YaRN
from repro.models.lm import LMConfig
from repro.models.moe import MoEConfig

ARCH_ID = "deepseek-v2-lite"
YARN = YaRN(factor=40.0, original_max_position=4096, beta_fast=32.0, beta_slow=1.0,
            mscale=0.707, mscale_all_dim=0.707)


def config() -> LMConfig:
    return LMConfig(
        name=ARCH_ID,
        family="moe",
        n_layers=27,
        d_model=2048,
        n_heads=16,
        n_kv=16,
        d_ff=1408,
        vocab=102400,
        attn_kind="mla",
        mla=MLAConfig(
            d_model=2048, n_heads=16, q_lora=None, kv_lora=512,
            d_nope=128, d_rope=64, d_v=128, rope_theta=10000.0, rope_scaling=YARN,
        ),
        first_k_dense=1,
        dense_d_ff=10944,
        moe=MoEConfig(
            d_model=2048, n_experts=64, top_k=6, d_expert=1408,
            n_shared=2, d_shared=2816, norm_topk=False,
        ),
        tie_embeddings=False,
    )


def smoke_config() -> LMConfig:
    return LMConfig(
        name=ARCH_ID + "-smoke",
        family="moe",
        n_layers=3,
        d_model=64,
        n_heads=4,
        n_kv=4,
        d_ff=32,
        vocab=512,
        attn_kind="mla",
        mla=MLAConfig(
            d_model=64, n_heads=4, q_lora=None, kv_lora=32,
            d_nope=16, d_rope=8, d_v=16, rope_scaling=YARN,
        ),
        first_k_dense=1,
        dense_d_ff=128,
        moe=MoEConfig(d_model=64, n_experts=8, top_k=2, d_expert=32, n_shared=2, d_shared=64,
                      norm_topk=False),
        tie_embeddings=False,
        remat=False,
    )
