"""A.X-K1's block at a size the CPU runs in seconds: every mechanism of
benchmark/configs/axk1-ep16.json (latent attention with a padded row, YaRN
past its original length, a leading dense layer, routed experts of which a
share is held, a shared expert), none of its widths."""

import tiny_engines
from benchmark.models import axk1, axk1_reference as ref

CFG = dict(
    model="axk1", hidden_size=64, intermediate_size=96,
    num_attention_heads=8, q_lora_rank=48, kv_lora_rank=128,
    qk_nope_head_dim=16, qk_rope_head_dim=16, v_head_dim=16,
    moe_intermediate_size=256, n_routed_experts=4, router_width=16,
    num_experts_per_tok=4, n_shared_experts=1, first_k_dense_replace=1,
    routed_scaling_factor=2.5, norm_topk_prob=True, scoring_func="sigmoid",
    topk_method="none", hidden_act="silu", rms_norm_eps=1e-6,
    rope_theta=10000,
    rope_scaling=dict(type="yarn", factor=32, beta_fast=32, beta_slow=1,
                      mscale=1, mscale_all_dim=1,
                      original_max_position_embeddings=16),
    num_layers=3, num_hidden_layers=3, vocab=97, weights_dtype="bfloat16",
    cache_dtype="bfloat16", max_len=64)
ENGINE = {"class": "PagedKVEngine", "n_slots": 4, "max_len": 64,
          "block_size": 8, "n_blocks": 40}
TINY = tiny_engines.Tiny(axk1, ref, CFG, ENGINE)
