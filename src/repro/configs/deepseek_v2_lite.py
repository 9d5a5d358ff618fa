"""DeepSeek-V2-Lite [arXiv:2405.04434; config.json of
deepseek-ai/DeepSeek-V2-Lite] — 27 layers at width 2048: layer 0 has a
dense SwiGLU of 10,944, layers 1-26 an MoE of 64 routed experts (width
1,408, softmax top-6, weights not renormalised) plus 2 shared experts;
MLA with 16 heads, kv_lora_rank 512 and no q compression; YaRN RoPE
(factor 40 over 4,096 positions); vocabulary 102,400, untied head.

Departure: the router aux loss is applied per sequence as ``seq_aux``
asks, at the coefficient ``router_aux_loss`` assumes (0.001), which
the published config leaves to the training recipe."""
from repro.configs.base import ModelConfig, RopeScaling, register

CONFIG = register(ModelConfig(
    name="deepseek-v2-lite", family="moe",
    num_layers=27, d_model=2048, num_heads=16, num_kv_heads=16,
    d_ff=10944, vocab_size=102400, first_k_dense=1,
    num_experts=64, num_experts_per_tok=6, num_shared_experts=2,
    moe_d_ff=1408, moe_period=1, router_aux_loss=0.001,
    moe_capacity_factor=0.0, norm_topk_prob=False,
    routed_scaling_factor=1.0,
    use_mla=True, kv_lora_rank=512, q_lora_rank=0,
    qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
    rope_theta=10000.0,
    rope_scaling=RopeScaling(factor=40.0,
                             original_max_position_embeddings=4096,
                             beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                             mscale_all_dim=0.707),
    max_seq_len=163840,
    # FedPT: freeze every FFN (routed and shared experts, the leading
    # dense SwiGLU), the embedding and the head; MLA, routers and norms
    # train (the analogue of the paper's Table 11 FFN freeze)
    freeze_spec=(r"^embed/", r"^unembed/", r"/ffn/",
                 r"/moe/(wi_gate|wi_up|wo)", r"/moe/shared/"),
    source="arXiv:2405.04434; huggingface.co/deepseek-ai/DeepSeek-V2-Lite",
))
