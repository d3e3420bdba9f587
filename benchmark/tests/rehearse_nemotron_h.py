"""`rehearse.py` for the cell nemotron3-super-ep4_serve_chat_bursts: the same
copy of the benchmark with throw-away files ADDED, among them a tiny
configuration of the cell's adapter, its mix and its cell, which stands for the
committed cell in every list that names it (and, through `rehearse_lfm2.py`,
the stand-ins of the two cells the same lists name).

    python3 benchmark/tests/rehearse_nemotron_h.py <scratch dir> <run|control|witness> [--devices N] -- <arguments>

`run` is benchmark/run.py, `control` benchmark/control.py and `witness`
benchmark/witness.py, from the copy, on the CPU. The tiny cell takes its limit
(`logit_gap_tol`) and the tiny configuration its check's shaping from the
committed files, so what passes and fails here is the committed comparison at
a small size.
"""

from __future__ import annotations

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
import rehearse         # noqa: E402
import rehearse_lfm2    # noqa: E402

CELL = "nemotron3-super-ep4_serve_chat_bursts"
_with_lfm2 = rehearse_lfm2.build_tree
# every mechanism of configs/nemotron3-super-ep4.json, none of its widths,
# its whole period (as tests/nemotron_h_tiny.py has it)
TINY_NEMOTRON = dict(
    name="tiny-nemotron-h", source="throw-away", model="nemotron_h",
    hidden_size=64, num_attention_heads=8, num_key_value_heads=2, head_dim=8,
    hybrid_override_pattern="MEMEMEM*EME", num_layers=11,
    num_hidden_layers=11, mamba_num_heads=16, mamba_head_dim=8, n_groups=4,
    ssm_state_size=16, conv_kernel=4, chunk_size=16, use_conv_bias=True,
    mlp_hidden_act="relu2", moe_latent_size=32, moe_intermediate_size=48,
    moe_shared_expert_intermediate_size=96, n_routed_experts=4,
    router_width=16, num_experts_per_tok=6, n_shared_experts=1, n_group=1,
    topk_group=1, norm_topk_prob=True, routed_scaling_factor=5,
    layer_norm_epsilon=1e-5, time_step_min=0.001,
    time_step_max=0.1, time_step_floor=0.0001, system_prompt_tokens=24,
    typical_context_tokens=45, vocab=97, weights_dtype="bfloat16",
    cache_dtype="bfloat16", max_len=96, reduced=[], assumed={})
TINY_BURSTS = {
    "name": "tiny_chat_bursts", "kind": "open_loop", "rate_per_s": 8.0,
    "system_prompts": {"count": 2, "tokens": 24,
                       "popularity": {"dist": "zipf", "exponent": 1.0}},
    "user_tokens": {"dist": "lognormal_quantiles", "median": 6, "sigma": 0.8,
                    "min": 2, "max": 20},
    "output_tokens": {"dist": "lognormal_quantiles", "median": 30,
                      "sigma": 0.3, "min": 20, "max": 40},
    "pairing": "golden_stride",
    "arrivals": {"process": "uniform_order_statistics", "burst_size": 4},
    "drain_deadline_s": 60, "schedule_seed": 43}


def build_tree(dst):
    load = lambda *p: json.load(open(os.path.join(      # noqa: E731
        rehearse.REPO, "benchmark", *p)))
    committed = load("cells", CELL + ".json")
    config = load("configs", "nemotron3-super-ep4.json")
    rehearse.MIXES.append(TINY_BURSTS)
    rehearse.CELLS.append((
        {"name": "tiny_chat_bursts_serve", "loop": "serve",
         "engine": {"class": "PagedKVEngine", "n_slots": 4, "block_size": 8,
                    "n_blocks": 64, "max_len": 96, "n_snapshots": 3},
         "trace_seconds": 1, "check_requests": 6,
         "logit_gap_tol": committed["logit_gap_tol"]},
        "tiny-nemotron-h", "tiny_chat_bursts", 1, CELL))
    added = _with_lfm2(dst)
    tiny = dict(TINY_NEMOTRON, **{k: config[k] for k in (
        "check_rows_held", "check_echo") if k in config})
    path = os.path.join(dst, "benchmark", "configs", "tiny-nemotron-h.json")
    assert not os.path.exists(path)
    with open(path, "w") as f:
        json.dump(tiny, f)
    manifest = os.path.join(dst, "BENCHMARK.json")
    bench = json.load(open(manifest))
    bench["configs"].append({"name": "tiny-nemotron-h",
                             "source": "throw-away",
                             "file": "benchmark/configs/tiny-nemotron-h.json",
                             "reduced": [], "why": "throw-away"})
    with open(manifest, "w") as f:
        json.dump(bench, f)
    return added + ["benchmark/configs/tiny-nemotron-h.json"]


def main(argv):
    rehearse_lfm2.build_tree = build_tree   # its `main`, over this table
    return rehearse_lfm2.main(argv)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
