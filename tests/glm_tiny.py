"""GLM-5.3-Flash's stack at a size the CPU runs in seconds: every mechanism of
benchmark/configs/glm53-flash-ep8.json (five layers `K K K D K`: four residual
streams mixed through Sinkhorn around every sub-layer, the gated delta-rule
mixer with low-rank gate pairs, ONE sparse NoPE latent layer whose indexer
picks `index_topk` 8 positions = 2 groups of `index_kpool` 4 and the tail; a
dense first layer, then 16 experts of which 8 are held beside a shared one,
every gated pair clamped at `swiglu_limit`; an untied head), none of its
widths."""

import tiny_engines
from benchmark.models import glm
from benchmark.models import glm_reference as ref

CFG = dict(
    model="glm", hidden_size=64, intermediate_size=96,
    num_attention_heads=4, num_key_value_heads=4, head_dim=16,
    num_layers=5, num_hidden_layers=5, vocab=97, vocab_size=97,
    layer_types=["linear_attention"] * 3 + ["deepseek_sparse_attention",
                                            "linear_attention"],
    mlp_layer_types=["dense"] + ["sparse"] * 4, indexer_types=["full"] * 5,
    first_k_dense_replace=1,
    linear_attn_config=dict(num_heads=4, head_dim=16,
                            short_conv_kernel_size=4, gate_lower_bound=-5,
                            kda_layers=[0, 1, 2, 4], full_attn_layers=[3]),
    kda_gate_rank=8, q_lora_rank=24, kv_lora_rank=32, qk_nope_head_dim=16,
    qk_head_dim=16, qk_rope_head_dim=0, v_head_dim=16, mla_use_nope=True,
    index_n_heads=4, index_head_dim=16, index_topk=8, index_kpool=4,
    index_kpool_compress=True, index_kpool_always_select_tail=True,
    indexer_rope_interleave=True, index_rope_dim=8, index_rope_theta=1000000,
    mhc=True, hc_mult=4, hc_sinkhorn_iters=20, hc_eps=1e-6,
    n_routed_experts=8, router_width=16, num_experts_per_tok=3, n_group=1,
    topk_group=1, n_shared_experts=1, moe_intermediate_size=32,
    routed_scaling_factor=2.5, norm_topk_prob=True, scoring_func="sigmoid",
    topk_method="noaux_tc", hidden_act="silu", swiglu_limit=1.5,
    rms_norm_eps=1e-5, attention_bias=False, tie_word_embeddings=False,
    system_prompt_tokens=24, chunk_size=16,
    weights_dtype="bfloat16", cache_dtype="bfloat16", max_len=64)
ENGINE = {"class": "PagedKVEngine", "n_slots": 4, "max_len": 64,
          "block_size": 8, "n_blocks": 40, "n_snapshots": 4}
TINY = tiny_engines.Tiny(glm, ref, CFG, ENGINE)
