"""`rehearse.py` for the cell lfm2-8b-a1b_serve_assistant: the same copy of the
benchmark with throw-away files ADDED, among them a tiny configuration of the
cell's adapter, its mix and its cell, which stands for the committed cell in
every list that names it (and, through `rehearse_axk1.py`, the stand-in of
axk1-ep16_serve_docqa, which the same lists name).

    python3 benchmark/tests/rehearse_lfm2.py <scratch dir> <run|control|witness> [--devices N] -- <arguments>

`run` is benchmark/run.py, `control` benchmark/control.py and `witness`
benchmark/witness.py, from the copy, on the CPU. The tiny cell takes its limit (`logit_gap_tol`) and the tiny
configuration its `router_tie_margin` from the committed files, so what passes
and fails here is the committed comparison at a small size.
"""

from __future__ import annotations

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
import rehearse         # noqa: E402
import rehearse_axk1    # noqa: E402

CELL = "lfm2-8b-a1b_serve_assistant"
_with_axk1 = rehearse_axk1.build_tree
# every mechanism of configs/lfm2-8b-a1b.json, none of its widths, three of
# its periods (as tests/lfm2_tiny.py has it, `DEEP`: at that depth bfloat16
# reads against the reference as it does at the published one)
TINY_LFM2 = dict(
    name="tiny-lfm2", source="throw-away", model="lfm2", hidden_size=64,
    intermediate_size=96, num_attention_heads=8, num_key_value_heads=2,
    head_dim=8, conv_L_cache=3, conv_bias=False,
    layer_types=["conv", "conv", "full_attention", "conv"] * 3,
    num_layers=12, num_hidden_layers=12, num_dense_layers=2,
    moe_intermediate_size=256, num_experts=8, num_experts_per_tok=2,
    norm_topk_prob=True, use_expert_bias=True, routed_scaling_factor=1,
    expert_bias_sigma=0.05, norm_eps=1e-5, rope_theta=1000000, vocab=97,
    weights_dtype="bfloat16", cache_dtype="bfloat16", max_len=96, reduced=[],
    assumed={})
TINY_ASSISTANT = {
    "name": "tiny_assistant", "kind": "open_loop", "rate_per_s": 8.0,
    "system_prompts": {"count": 2, "tokens": 24,
                       "popularity": {"dist": "zipf", "exponent": 1.0}},
    "user_tokens": {"dist": "lognormal_quantiles", "median": 6, "sigma": 0.8,
                    "min": 2, "max": 20},
    "output_tokens": {"dist": "lognormal_quantiles", "median": 30,
                      "sigma": 0.3, "min": 20, "max": 40},
    "pairing": "golden_stride",
    "arrivals": {"process": "uniform_order_statistics", "burst_size": 1},
    "drain_deadline_s": 60, "schedule_seed": 9}


def build_tree(dst):
    load = lambda *p: json.load(open(os.path.join(      # noqa: E731
        rehearse.REPO, "benchmark", *p)))
    committed = load("cells", CELL + ".json")
    config = load("configs", "lfm2-8b-a1b.json")
    rehearse.MIXES.append(TINY_ASSISTANT)
    rehearse.CELLS.append((
        {"name": "tiny_assistant_serve", "loop": "serve",
         "engine": {"class": "PagedKVEngine", "n_slots": 4, "block_size": 8,
                    "n_blocks": 64, "max_len": 96},
         "trace_seconds": 1, "check_requests": 6,
         "logit_gap_tol": committed["logit_gap_tol"]},
        "tiny-lfm2", "tiny_assistant", 1, CELL))
    added = _with_axk1(dst)
    tiny = dict(TINY_LFM2, **{k: config[k] for k in (
        "router_tie_margin", "check_rows_held", "check_echo")})
    path = os.path.join(dst, "benchmark", "configs", "tiny-lfm2.json")
    assert not os.path.exists(path)
    with open(path, "w") as f:
        json.dump(tiny, f)
    manifest = os.path.join(dst, "BENCHMARK.json")
    bench = json.load(open(manifest))
    bench["configs"].append({"name": "tiny-lfm2", "source": "throw-away",
                             "file": "benchmark/configs/tiny-lfm2.json",
                             "reduced": [], "why": "throw-away"})
    with open(manifest, "w") as f:
        json.dump(bench, f)
    return added + ["benchmark/configs/tiny-lfm2.json"]


def main(argv):
    rehearse_axk1.build_tree = build_tree   # its `main`, over this table
    if argv[1] != "witness":
        return rehearse_axk1.main(argv)
    # its `main` knows `run` and `control`; the same steps for witness.py
    dst, rest = argv[0], argv[2:]
    rest = rest[1:] if rest[0] == "--" else rest
    build_tree(dst)
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.chdir(dst)
    sys.path[:0] = [dst, rehearse.REPO]
    from benchmark import harness, witness
    harness.device_facts = rehearse.admit_cpu
    return witness.main(rest)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
