"""moonlight-16b-a3b [mla_moe]: 27L d2048 16H, multi-head latent attention
(kv_lora_rank 512, qk 128 + 64 rotary, v 128, theta 50000), one leading
dense SwiGLU layer of 11264, then 26 layers of 64 routed experts of 1408
(top-6, sigmoid scores with a choice-only bias, normalised and scaled by
2.446) plus 2 shared experts, vocab 163840.
[hf:moonshotai/Moonlight-16B-A3B, model_type deepseek_v3].

This entry is one chip of an 8-chip expert-parallel deployment: the chip
holds 8 of each MoE layer's 64 routed experts (the first share), and the
router keeps all 64 outputs and its 6 choices; attention, the shared
experts and the dense layer are whole on every chip."""

from repro.configs.base import MlaConfig, ModelConfig, MoEConfig, register

CONFIG = register(
    ModelConfig(
        name="moonlight-16b-a3b",
        family="mla_moe",
        n_layers=27,
        d_model=2048,
        n_heads=16,
        n_kv_heads=16,
        d_ff=11264,  # the leading dense layer's SwiGLU width
        vocab_size=163840,
        rope_theta=50000.0,
        norm_eps=1e-5,
        max_seq_len=8192,
        first_k_dense=1,
        embed_scale=False,
        mla=MlaConfig(kv_lora_rank=512, qk_nope_head_dim=128,
                      qk_rope_head_dim=64, v_head_dim=128),
        moe=MoEConfig(num_experts=64, top_k=6, d_ff_expert=1408,
                      d_ff_shared=2 * 1408, routed_scaling=2.446,
                      experts_held=8),
    )
)
