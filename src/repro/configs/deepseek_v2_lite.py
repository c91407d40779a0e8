"""DeepSeek-V2-Lite: multi-head latent attention and DeepSeekMoE.

[arXiv:2405.04434; hf:deepseek-ai/DeepSeek-V2-Lite config.json] 27L
d_model=2048 16H, MLA (kv_lora_rank 512 with an RMSNorm on the latent,
qk_nope_head_dim 128, qk_rope_head_dim 64 shared by all heads, v_head_dim
128, no query compression), YaRN rope (theta 10,000, factor 40 over 4,096
positions, betas 32 and 1, mscale = mscale_all_dim = 0.707).  Layer 0 is
dense (SwiGLU 10,944); layers 1-26 route a float32 softmax over 64 experts
of width 1,408, greedy top-6 with no renormalisation
(routed_scaling_factor 1), plus 2 shared experts.  Vocabulary 102,400,
untied head, RMSNorm eps 1e-6.  15.7B parameters.

Every routed token is computed (dropless), as in the published model.
"""

from repro.configs.base import ArchConfig, MLAConfig, MoEConfig, YarnConfig

CONFIG = ArchConfig(
    name="deepseek-v2-lite",
    family="moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    d_ff=10944,  # dense layer 0
    vocab_size=102400,
    norm="rmsnorm",
    activation="swiglu",
    rope_theta=10_000.0,
    rope_scaling=YarnConfig(
        factor=40.0,
        original_max_position_embeddings=4096,
        beta_fast=32.0,
        beta_slow=1.0,
        mscale=0.707,
        mscale_all_dim=0.707,
    ),
    moe=MoEConfig(
        n_experts=64,
        top_k=6,
        d_expert=1408,
        n_shared=2,
        first_layer_dense=True,
        renormalize=False,
        dropless=True,
    ),
    mla=MLAConfig(
        kv_lora_rank=512,
        qk_nope_head_dim=128,
        qk_rope_head_dim=64,
        v_head_dim=128,
    ),
    max_seq_len=163_840,
    subquadratic=False,
)
