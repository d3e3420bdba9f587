"""`rehearse.py` for the cell k-exaone-ep8_serve_long_sessions: the same copy
of the benchmark with throw-away files ADDED, among them a tiny configuration
of the cell's adapter, its mix and its cell, which stands for the committed
cell in every list that names it (and, through `rehearse_nemotron_h.py`, the
stand-ins of the three cells the same lists name: tiny stand-ins of ALL
serving cells).

    python3 benchmark/tests/rehearse_kexaone.py <scratch dir> <run|control|witness> [--devices N] -- <arguments>

`run` is benchmark/run.py, `control` benchmark/control.py and `witness`
benchmark/witness.py, from the copy, on the CPU. The tiny cell takes its limit
(`logit_gap_tol`) and the tiny configuration its tie margin from the committed
files, so what passes and fails here is the committed comparison at a small
size.
"""

from __future__ import annotations

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
import rehearse             # noqa: E402
import rehearse_lfm2        # noqa: E402
import rehearse_nemotron_h  # noqa: E402

CELL = "k-exaone-ep8_serve_long_sessions"
_with_nemotron = rehearse_nemotron_h.build_tree
# every mechanism of configs/k-exaone-ep8.json, none of its widths, the
# leading dense layer and one whole period (as tests/kexaone_tiny.py has it):
# a window of 8 positions over blocks of 4
TINY_KEXAONE = dict(
    name="tiny-kexaone", source="throw-away", model="kexaone",
    hidden_size=64, intermediate_size=96, num_attention_heads=8,
    num_key_value_heads=2, head_dim=16,
    layer_types=["sliding_attention"] * 3 + ["full_attention",
                                             "sliding_attention"],
    mlp_layer_types=["dense"] + ["sparse"] * 4, sliding_window=8,
    sliding_windows=[8, 8, 8, 0, 8],
    rope_parameters=dict(rope_theta=10000.0, rope_type="default"),
    moe_intermediate_size=48, num_experts=2, router_width=16,
    num_experts_per_tok=4, num_shared_experts=1, first_k_dense_replace=1,
    routed_scaling_factor=2.5, norm_topk_prob=True, scoring_func="sigmoid",
    topk_method="none", n_group=1, topk_group=1, hidden_act="silu",
    rms_norm_eps=1e-5, num_layers=5, num_hidden_layers=5, vocab=97,
    system_prompt_tokens=24, weights_dtype="bfloat16",
    cache_dtype="bfloat16", max_len=96, reduced=[], assumed={})
TINY_SESSIONS = {
    "name": "tiny_long_sessions", "kind": "open_loop", "rate_per_s": 8.0,
    "system_prompts": {"count": 3, "tokens": 24,
                       "popularity": {"dist": "zipf", "exponent": 1.0}},
    "user_tokens": {"dist": "lognormal_quantiles", "median": 6, "sigma": 0.8,
                    "min": 2, "max": 20},
    "output_tokens": {"dist": "lognormal_quantiles", "median": 30,
                      "sigma": 0.3, "min": 20, "max": 40},
    "pairing": "golden_stride",
    "arrivals": {"process": "uniform_order_statistics", "burst_size": 1},
    "drain_deadline_s": 60, "schedule_seed": 45}


def build_tree(dst):
    load = lambda *p: json.load(open(os.path.join(      # noqa: E731
        rehearse.REPO, "benchmark", *p)))
    committed = load("cells", CELL + ".json")
    config = load("configs", "k-exaone-ep8.json")
    rehearse.MIXES.append(TINY_SESSIONS)
    rehearse.CELLS.append((
        {"name": "tiny_long_sessions_serve", "loop": "serve",
         "engine": {"class": "PagedKVEngine", "n_slots": 4, "block_size": 4,
                    "n_blocks": 128, "n_window_blocks": 40, "max_len": 96},
         "trace_seconds": 1, "check_requests": 6,
         "logit_gap_tol": committed["logit_gap_tol"]},
        "tiny-kexaone", "tiny_long_sessions", 1, CELL))
    added = _with_nemotron(dst)
    tiny = dict(TINY_KEXAONE, router_tie_margin=config["router_tie_margin"])
    path = os.path.join(dst, "benchmark", "configs", "tiny-kexaone.json")
    assert not os.path.exists(path)
    with open(path, "w") as f:
        json.dump(tiny, f)
    manifest = os.path.join(dst, "BENCHMARK.json")
    bench = json.load(open(manifest))
    bench["configs"].append({"name": "tiny-kexaone", "source": "throw-away",
                             "file": "benchmark/configs/tiny-kexaone.json",
                             "reduced": [], "why": "throw-away"})
    with open(manifest, "w") as f:
        json.dump(bench, f)
    return added + ["benchmark/configs/tiny-kexaone.json"]


def main(argv):
    rehearse_lfm2.build_tree = build_tree   # its `main`, over this table
    return rehearse_lfm2.main(argv)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
