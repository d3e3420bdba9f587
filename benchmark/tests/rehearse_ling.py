"""`rehearse.py` for the cell ling3-flash-ep4_serve_reasoning: the same copy
of the benchmark with throw-away files ADDED, among them a tiny configuration
of the cell's adapter, its mix and its cell, which stands for the committed
cell in every list that names it (and, through `rehearse_falcon_h1.py`, the
stand-ins of the six cells added before it: a table that holds all eleven).
A seventh link of the chain and not a data file: `rehearse.py` holds its table
in code and is the benchmark's own file (ROADMAP.md R-A6 (4)).

    python3 benchmark/tests/rehearse_ling.py <scratch dir> <run|control|witness|train_witness> [--devices N] -- <arguments>

`run` is benchmark/run.py, `control` benchmark/control.py, `witness`
benchmark/witness.py, from the copy, on the CPU. The tiny cell takes its
`logit_gap_tol` from the committed cell, and it computes in FLOAT32 (its
configuration states float32 and the package multiplies in float32,
`PTPU_USE_BF16_MATMUL=0`): over a vocabulary of 97, with 3 of 16 experts of
width 32 a row, a bfloat16 program's flipped selections read 0.4-0.7 under the
loop's statistic, over the committed limit, which is set for 39,296 logits and
8 of 512 experts (my CPU runs, PR 59); the control one precision below is then
the bfloat16 reading, and fails as it has to.
"""

from __future__ import annotations

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
import rehearse             # noqa: E402
import rehearse_falcon_h1   # noqa: E402
import rehearse_mellum      # noqa: E402

CELL = "ling3-flash-ep4_serve_reasoning"
# every mechanism of configs/ling3-flash-ep4.json, none of its widths (as
# tests/ling_tiny.py has it)
TINY_LING = dict(
    name="tiny-ling", source="throw-away", model="ling", hidden_size=64,
    intermediate_size=96, num_attention_heads=4, num_key_value_heads=4,
    head_dim=16, num_layers=7, num_hidden_layers=7, vocab=97, vocab_size=97,
    layer_group_size=6, first_k_dense_replace=1, kda_lower_bound=-5,
    kda_safe_gate=True, no_kda_lora=True, use_kda_lora=False,
    linear_silu=True, short_conv_kernel_size=4,
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
    tie_word_embeddings=False, system_prompt_tokens=16, chunk_size=16,
    check_rows_held=0.9, check_echo=1.65,
    weights_dtype="float32", cache_dtype="float32", max_len=128,
    reduced=[], assumed={})
TINY_REASONING = {
    "name": "tiny_reasoning", "kind": "open_loop", "rate_per_s": 4.0,
    "system_prompts": {"count": 2, "tokens": 16,
                       "popularity": {"dist": "zipf", "exponent": 1.0}},
    "user_tokens": {"dist": "lognormal_quantiles", "median": 8, "sigma": 0.8,
                    "min": 2, "max": 40},
    "output_tokens": {"dist": "lognormal_quantiles", "median": 32,
                      "sigma": 0.45, "min": 16, "max": 64},
    "pairing": "golden_stride",
    "arrivals": {"process": "uniform_order_statistics", "burst_size": 1},
    "drain_deadline_s": 60, "schedule_seed": 59}


def build_tree(dst):
    committed = json.load(open(os.path.join(
        rehearse.REPO, "benchmark", "cells", CELL + ".json")))
    rehearse.MIXES.append(TINY_REASONING)
    rehearse.CELLS.append((
        {"name": "tiny_reasoning_serve", "loop": "serve",
         "engine": {"class": "PagedKVEngine", "n_slots": 6, "block_size": 8,
                    "n_blocks": 120, "max_len": 128, "n_snapshots": 4},
         "trace_seconds": 1, "check_requests": 4,
         "logit_gap_tol": committed["logit_gap_tol"]},
        "tiny-ling", "tiny_reasoning", 1, CELL))
    added = rehearse_falcon_h1.build_tree(dst)
    path = os.path.join(dst, "benchmark", "configs", "tiny-ling.json")
    assert not os.path.exists(path)
    with open(path, "w") as f:
        json.dump(TINY_LING, f)
    manifest = os.path.join(dst, "BENCHMARK.json")
    bench = json.load(open(manifest))
    bench["configs"].append({"name": "tiny-ling", "source": "throw-away",
                             "file": "benchmark/configs/tiny-ling.json",
                             "reduced": [], "why": "throw-away"})
    with open(manifest, "w") as f:
        json.dump(bench, f)
    return added + ["benchmark/configs/tiny-ling.json"]


def main(argv):
    rehearse_mellum.build_tree = build_tree     # its `main`, over this table
    if "tiny_reasoning_serve" in argv:
        os.environ["PTPU_USE_BF16_MATMUL"] = "0"    # before the package loads
    return rehearse_mellum.main(argv)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
