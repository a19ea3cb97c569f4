"""DeepSeek-V2-Lite 16B [moe] — arXiv:2405.04434 (hf-verified tier).

Published config (huggingface.co/deepseek-ai/DeepSeek-V2-Lite config.json):
27 layers, d_model 2048, 16 heads of MLA (kv_lora_rank 512, no q
compression, qk_nope 128 + qk_rope 64, v 128); the first layer is dense
(``first_k_dense_replace`` 1, intermediate_size 10944) and the other 26 are
MoE layers of 64 routed experts (width 1408, top-6 of a softmax, gates not
renormalized, ``routed_scaling_factor`` 1) plus 2 shared experts; YaRN rope
(factor 40 over 4096 positions, beta 32/1, mscale = mscale_all_dim = 0.707);
rms_norm_eps 1e-6; vocabulary 102,400.

The assignment line's "160 routed" is DeepSeek-V2's (236B) count; the Lite
model has 64, as its config states.  Departure kept: the rope columns of
``q_pe``/``k_pe`` use the rotate-half pairing, where the published code
de-interleaves them first — a fixed permutation of those weight columns.
"""

from repro.configs.base import ArchConfig, Yarn

CONFIG = ArchConfig(
    name="deepseek-v2-lite-16b",
    family="moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    d_ff=10_944,
    vocab_size=102_400,
    use_mla=True,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    n_experts=64,
    n_shared_experts=2,
    moe_top_k=6,
    d_ff_expert=1408,
    first_k_dense=1,
    norm_topk_prob=False,
    routed_scaling_factor=1.0,
    rope_theta=10_000.0,
    rope_yarn=Yarn(factor=40.0, original_max_position=4096, beta_fast=32.0,
                   beta_slow=1.0, mscale=0.707, mscale_all_dim=0.707),
    norm_eps=1e-6,
    notes="MLA latent cache (512+64 per token); dense layer 0, then 2 shared "
          "+ 64 routed experts top-6.",
)
