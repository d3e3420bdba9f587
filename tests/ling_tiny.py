"""Ling-3.0-flash's stack at a size the CPU runs in seconds: every mechanism
of benchmark/configs/ling3-flash-ep4.json (seven layers `K K K K K M K`: the
channel-wise gated delta-rule mixer with its short convolution over q, k and
v and a matrix state a head, ONE latent-attention layer with no query
bottleneck and a gate a head; a dense first layer, then 16 experts in 4
groups of which the two best are eligible, two whole groups held, beside a
shared expert; an untied head), none of its widths."""

import tiny_engines
from benchmark.models import ling
from benchmark.models import ling_reference as ref

CFG = dict(
    model="ling", hidden_size=64, intermediate_size=96,
    num_attention_heads=4, num_key_value_heads=4, head_dim=16,
    num_layers=7, num_hidden_layers=7, vocab=97, vocab_size=97,
    layer_group_size=6, first_k_dense_replace=1,
    kda_lower_bound=-5, kda_safe_gate=True, no_kda_lora=True,
    use_kda_lora=False, linear_silu=True, short_conv_kernel_size=4,
    num_kv_heads_for_linear_attn=0, group_norm_size=1, use_qk_norm=True,
    q_lora_rank=None, kv_lora_rank=32, qk_nope_head_dim=16,
    qk_rope_head_dim=8, qk_head_dim=24, v_head_dim=16, rope_theta=6000000,
    rope_scaling=None, gated_attention_proj_granularity_type="head_wise",
    num_experts=8, router_width=16, num_experts_per_tok=3, n_group=4,
    topk_group=2, num_shared_experts=1, moe_intermediate_size=32,
    moe_shared_expert_intermediate_size=32, routed_scaling_factor=2.5,
    norm_topk_prob=True, score_function="sigmoid", topk_method="noaux_tc",
    moe_router_enable_expert_bias=True, hidden_act="silu",
    expert_swiglu_limit_list=[0] * 7, share_expert_swiglu_limit_list=[0] * 7,
    rms_norm_eps=1e-6, use_bias=False, use_qkv_bias=False,
    tie_word_embeddings=False, system_prompt_tokens=24, chunk_size=16,
    weights_dtype="bfloat16", cache_dtype="bfloat16", max_len=64)
ENGINE = {"class": "PagedKVEngine", "n_slots": 4, "max_len": 64,
          "block_size": 8, "n_blocks": 40, "n_snapshots": 4}
TINY = tiny_engines.Tiny(ling, ref, CFG, ENGINE)
