"""`rehearse.py` for the cell glm53-flash-ep8_serve_repo_sessions: the same
copy of the benchmark with throw-away files ADDED, among them a tiny
configuration of the cell's adapter, its mix and its cell, which stands for
the committed cell in every list that names it (and, through
`rehearse_ling.py`, the stand-ins of the seven cells added before it: a table
that holds all twelve). An eighth link of the chain and not a data file:
`rehearse.py` holds its table in code and is the benchmark's own file
(ROADMAP.md R-A6 (4)).

    python3 benchmark/tests/rehearse_glm.py <scratch dir> <run|control|witness|train_witness> [--devices N] -- <arguments>

The tiny cell takes its `logit_gap_tol` from the committed cell and computes
in FLOAT32 (`PTPU_USE_BF16_MATMUL=0`), as the reasoning cell's stand-in does
and for its reason: over 97 logits, 3 of 16 experts and 2 of 12 index groups a
row, a bfloat16 program's flipped selections read over a limit set for 19,360
logits, 8 of 288 experts and 512 of 8,200 groups.
"""

from __future__ import annotations

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
import rehearse             # noqa: E402
import rehearse_ling        # noqa: E402
import rehearse_mellum      # noqa: E402

CELL = "glm53-flash-ep8_serve_repo_sessions"
# every mechanism of configs/glm53-flash-ep8.json, none of its widths (as
# tests/glm_tiny.py has it)
TINY_GLM = dict(
    name="tiny-glm", source="throw-away", model="glm", hidden_size=64,
    intermediate_size=96, num_attention_heads=4, num_key_value_heads=4,
    head_dim=0, num_layers=5, num_hidden_layers=5, vocab=97, vocab_size=97,
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
    system_prompt_tokens=32, chunk_size=16, check_rows_held=0.9,
    check_echo=1.65, weights_dtype="float32", cache_dtype="float32",
    max_len=128, reduced=[], assumed={})
TINY_SESSIONS = {
    "name": "tiny_repo_sessions", "kind": "open_loop", "rate_per_s": 4.0,
    "system_prompts": {"count": 2, "tokens": 32,
                       "popularity": {"dist": "zipf", "exponent": 1.0}},
    "user_tokens": {"dist": "lognormal_quantiles", "median": 8, "sigma": 1.0,
                    "min": 2, "max": 40},
    "output_tokens": {"dist": "lognormal_quantiles", "median": 16,
                      "sigma": 0.7, "min": 4, "max": 48},
    "pairing": "golden_stride",
    "arrivals": {"process": "uniform_order_statistics", "burst_size": 1},
    "drain_deadline_s": 60, "schedule_seed": 61}


def build_tree(dst):
    committed = json.load(open(os.path.join(
        rehearse.REPO, "benchmark", "cells", CELL + ".json")))
    rehearse.MIXES.append(TINY_SESSIONS)
    rehearse.CELLS.append((
        {"name": "tiny_repo_sessions_serve", "loop": "serve",
         "engine": {"class": "PagedKVEngine", "n_slots": 6, "block_size": 8,
                    "n_blocks": 120, "max_len": 128, "n_snapshots": 4},
         "trace_seconds": 1, "check_requests": 4,
         "logit_gap_tol": committed["logit_gap_tol"]},
        "tiny-glm", "tiny_repo_sessions", 1, CELL))
    added = rehearse_ling.build_tree(dst)
    path = os.path.join(dst, "benchmark", "configs", "tiny-glm.json")
    assert not os.path.exists(path)
    with open(path, "w") as f:
        json.dump(TINY_GLM, f)
    manifest = os.path.join(dst, "BENCHMARK.json")
    bench = json.load(open(manifest))
    bench["configs"].append({"name": "tiny-glm", "source": "throw-away",
                             "file": "benchmark/configs/tiny-glm.json",
                             "reduced": [], "why": "throw-away"})
    with open(manifest, "w") as f:
        json.dump(bench, f)
    return added + ["benchmark/configs/tiny-glm.json"]


def main(argv):
    rehearse_mellum.build_tree = build_tree     # its `main`, over this table
    if "tiny_repo_sessions_serve" in argv or "tiny_reasoning_serve" in argv:
        os.environ["PTPU_USE_BF16_MATMUL"] = "0"    # before the package loads
    return rehearse_mellum.main(argv)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
