"""moonlight-16b-a3b: deepseek-v3 block. Multi-head latent attention
(kv_lora_rank 512, q_lora_rank null), one leading dense layer (FFN 11264),
then 64 routed experts of width 1408, top-6 by sigmoid scores plus a
selection-only bias (noaux_tc, n_group 1), weights renormalised and scaled
by 2.446, and 2 shared experts as one SwiGLU of width 2816.
[hf:moonshotai/Moonlight-16B-A3B]"""

from repro.configs.base import ModelConfig

ID = "moonlight-16b-a3b"


def config(**overrides) -> ModelConfig:
    return ModelConfig(
        name=ID,
        family="moe",
        n_layers=27,
        d_model=2048,
        n_heads=16,
        n_kv_heads=16,
        d_ff=11264,
        vocab_size=163840,
        block_pattern=("mla",),
        ffn_pattern=("moe",),
        first_dense_layers=1,
        n_experts=64,
        experts_per_token=6,
        moe_d_ff=1408,
        moe_shared_expert=True,
        moe_shared_d_ff=2 * 1408,
        moe_score="sigmoid",
        moe_select_bias=True,
        moe_routed_scale=2.446,
        kv_lora_rank=512,
        qk_nope_head_dim=128,
        qk_rope_head_dim=64,
        v_head_dim=128,
        rope_theta=50_000.0,
        norm_eps=1e-5,
        act="silu",
        norm="rmsnorm",
        tie_embeddings=False,
        n_workers=16,
    ).with_(**overrides)


def reduced(**overrides) -> ModelConfig:
    import jax.numpy as jnp
    defaults = dict(
        n_layers=3, d_model=64, n_heads=4, n_kv_heads=4, d_ff=128,
        moe_d_ff=32, moe_shared_d_ff=64, vocab_size=256, n_experts=8,
        experts_per_token=3, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, n_workers=2, dtype=jnp.float32,
        param_dtype=jnp.float32, remat=False)
    defaults.update(overrides)
    return config().with_(**defaults)
