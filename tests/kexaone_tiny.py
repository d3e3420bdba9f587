"""K-EXAONE's block at a size the CPU runs in seconds: every mechanism of
benchmark/configs/k-exaone-ep8.json (sliding-window layers beside full ones in
the published 3 : 1, rotation on the window layers alone, an RMSNorm a head on
q and k, heads wider together than the model, a leading dense layer, routed
experts of which a rank's share is held, a shared expert, an untied head),
none of its widths: a window of 8 positions, blocks of 4, 16 experts of which
a rank holds 2."""

import tiny_engines
from benchmark.models import kexaone as kex
from benchmark.models import kexaone_reference as ref

LAYERS = ["sliding_attention", "sliding_attention", "sliding_attention",
          "full_attention", "sliding_attention"]
CFG = dict(
    model="kexaone", hidden_size=64, intermediate_size=96,
    num_attention_heads=8, num_key_value_heads=2, head_dim=16,
    layer_types=LAYERS, mlp_layer_types=["dense"] + ["sparse"] * 4,
    sliding_window=8, sliding_windows=[8, 8, 8, 0, 8],
    rope_parameters=dict(rope_theta=10000.0, rope_type="default"),
    moe_intermediate_size=48, num_experts=2, router_width=16,
    num_experts_per_tok=4, num_shared_experts=1, first_k_dense_replace=1,
    routed_scaling_factor=2.5, norm_topk_prob=True, scoring_func="sigmoid",
    topk_method="none", n_group=1, topk_group=1, hidden_act="silu",
    rms_norm_eps=1e-5, num_layers=5, num_hidden_layers=5, vocab=97,
    system_prompt_tokens=24, weights_dtype="bfloat16",
    cache_dtype="bfloat16", max_len=64)
ENGINE = {"class": "PagedKVEngine", "n_slots": 4, "max_len": 64,
          "block_size": 4, "n_blocks": 80, "n_window_blocks": 40}
TINY = tiny_engines.Tiny(kex, ref, CFG, ENGINE)
