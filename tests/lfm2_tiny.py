"""LFM2's hybrid block at a size the CPU runs in seconds: every mechanism of
benchmark/configs/lfm2-8b-a1b.json (gated short convolutions with a state,
grouped-query attention with QK-norm and rotary positions, a leading dense
layer, routed experts all held under a bias-corrected top-k, no shared expert,
a tied head), none of its widths."""

import tiny_engines
from benchmark.models import lfm2, lfm2_reference as ref

CFG = dict(
    model="lfm2", hidden_size=64, intermediate_size=96,
    num_attention_heads=8, num_key_value_heads=2, head_dim=8,
    conv_L_cache=3, conv_bias=False,
    layer_types=["conv", "conv", "full_attention", "conv", "full_attention"],
    num_layers=5, num_hidden_layers=5, num_dense_layers=1,
    moe_intermediate_size=256, num_experts=8, num_experts_per_tok=2,
    norm_topk_prob=True, use_expert_bias=True, routed_scaling_factor=1,
    expert_bias_sigma=0.05, norm_eps=1e-5, rope_theta=1000000, vocab=97,
    weights_dtype="bfloat16", cache_dtype="bfloat16", max_len=64)
ENGINE = {"class": "PagedKVEngine", "n_slots": 4, "max_len": 64,
          "block_size": 8, "n_blocks": 40}
# three periods of the published layer pattern and two leading dense layers:
# deep enough that bfloat16 reads against the reference as it does at the
# published depth (a routing flip in most rows, the rows after it reading its
# state), which is what the cell's limit is set for
DEEP = dict(layer_types=["conv", "conv", "full_attention", "conv"] * 3,
            num_layers=12, num_hidden_layers=12, num_dense_layers=2)
TINY = tiny_engines.Tiny(lfm2, ref, CFG, ENGINE)
