"""DeepSeek-V2-Lite 15.7B (2.4B active) — MLA without query compression,
YaRN, one leading dense layer, then 26 MoE layers of 64 routed experts
(top-6, softmax, no renormalisation) and 2 shared.
[arXiv:2405.04434; hf:deepseek-ai/DeepSeek-V2-Lite config.json]

The published model, all experts held. A chip's share of an
expert-parallel deployment sets ``moe.held``/``moe.first_held``, and its
slice of the vocabulary ``vocab_size`` (``bench/families/deepseek_v2.py``).
``aux_coef`` is the release's ``aux_loss_alpha``.
"""
from repro.configs.base import ArchConfig, MLAConfig, MoEConfig, YarnConfig
from repro.configs.registry import register

CONFIG = register(ArchConfig(
    name="deepseek-v2-lite",
    family="moe",
    n_layers=27,
    first_k_dense=1,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    d_ff=10944,
    vocab_size=102400,
    head_dim=128,
    attention="mla",
    layer_pattern=("attn",),
    moe=MoEConfig(num_experts=64, top_k=6, d_ff_expert=1408,
                  num_shared_experts=2, norm_topk_prob=False,
                  routed_scaling_factor=1.0, aux_coef=0.001),
    mla=MLAConfig(kv_lora_rank=512, q_lora_rank=None,
                  qk_nope_head_dim=128, qk_rope_head_dim=64,
                  v_head_dim=128,
                  yarn=YarnConfig(factor=40.0,
                                  original_max_position_embeddings=4096,
                                  beta_fast=32.0, beta_slow=1.0,
                                  mscale=0.707, mscale_all_dim=0.707)),
    rope="rope",
    rope_theta=10_000.0,
    norm="rmsnorm",
    act="swiglu",
    source="hf:deepseek-ai/DeepSeek-V2-Lite",
))
