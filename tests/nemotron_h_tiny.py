"""Nemotron-H's hybrid stack at a size the CPU runs in seconds: every
mechanism of benchmark/configs/nemotron3-super-ep4.json (one sublayer a layer
in the published period `MEMEMEM*EME`; Mamba-2 mixers with fewer groups than
heads, a float32 state and a convolution with bias; attention over 2
key/value heads without positions; latent routed experts of which a share is
held, two matrices an expert of a width that is no multiple of 256, a shared
expert of its own width; an untied head), none of its widths."""

import tiny_engines
from benchmark.models import nemotron_h as nemo
from benchmark.models import nemotron_h_reference as ref

CFG = dict(
    model="nemotron_h", hidden_size=64, num_attention_heads=8,
    num_key_value_heads=2, head_dim=8, hybrid_override_pattern="MEMEMEM*EME",
    num_layers=11, num_hidden_layers=11, mamba_num_heads=16, mamba_head_dim=8,
    n_groups=4, ssm_state_size=16, conv_kernel=4, chunk_size=16,
    use_conv_bias=True, mlp_hidden_act="relu2", moe_latent_size=32,
    moe_intermediate_size=48, moe_shared_expert_intermediate_size=96,
    n_routed_experts=4, router_width=16, num_experts_per_tok=6,
    n_shared_experts=1, n_group=1, topk_group=1, norm_topk_prob=True,
    routed_scaling_factor=5, layer_norm_epsilon=1e-5,
    time_step_min=0.001, time_step_max=0.1, time_step_floor=0.0001,
    system_prompt_tokens=16, vocab=97, weights_dtype="bfloat16",
    cache_dtype="bfloat16", max_len=64)
ENGINE = {"class": "PagedKVEngine", "n_slots": 4, "max_len": 64,
          "block_size": 8, "n_blocks": 40, "n_snapshots": 4}
TINY = tiny_engines.Tiny(nemo, ref, CFG, ENGINE)
