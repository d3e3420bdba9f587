"""`rehearse.py` for the cell axk1-ep16_serve_docqa: the same copy of the
benchmark with throw-away files ADDED, among them a tiny configuration of
the cell's adapter, its mix and its cell, which stands for the committed cell
in every list that names it.

    python3 benchmark/tests/rehearse_axk1.py <scratch dir> <run|control> [--devices N] -- <arguments>

`run` is benchmark/run.py and `control` benchmark/control.py, from the copy,
on the CPU. The tiny cell takes its limit (`logit_gap_tol`) and the tiny
configuration its `router_tie_margin` from the committed files, so what
passes and fails here is the committed comparison at a small size.
"""

from __future__ import annotations

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
import rehearse     # noqa: E402

CELL = "axk1-ep16_serve_docqa"
# every mechanism of configs/axk1-ep16.json, none of its widths (as
# tests/axk1_tiny.py has it)
TINY_AXK1 = dict(
    name="tiny-axk1", source="throw-away", model="axk1", hidden_size=64,
    intermediate_size=96, num_attention_heads=8, q_lora_rank=48,
    kv_lora_rank=128, qk_nope_head_dim=16, qk_rope_head_dim=16,
    v_head_dim=16, moe_intermediate_size=256, n_routed_experts=4,
    router_width=16, num_experts_per_tok=4, n_shared_experts=1,
    first_k_dense_replace=1, routed_scaling_factor=2.5, norm_topk_prob=True,
    scoring_func="sigmoid", topk_method="none", hidden_act="silu",
    rms_norm_eps=1e-6, rope_theta=10000,
    rope_scaling=dict(type="yarn", factor=32, beta_fast=32, beta_slow=1,
                      mscale=1, mscale_all_dim=1,
                      original_max_position_embeddings=16),
    num_layers=3, num_hidden_layers=3, vocab=97, weights_dtype="bfloat16",
    cache_dtype="bfloat16", max_len=64, reduced=[], assumed={})
TINY_DOCQA = {
    "name": "tiny_docqa", "kind": "open_loop", "rate_per_s": 8.0,
    "system_prompts": {"count": 2, "tokens": 24,
                       "popularity": {"dist": "zipf", "exponent": 1.0}},
    "user_tokens": {"dist": "lognormal_quantiles", "median": 6, "sigma": 0.8,
                    "min": 2, "max": 16},
    "output_tokens": {"dist": "lognormal_quantiles", "median": 12,
                      "sigma": 0.7, "min": 4, "max": 20},
    "pairing": "golden_stride",
    "arrivals": {"process": "uniform_order_statistics", "burst_size": 1},
    "drain_deadline_s": 60, "schedule_seed": 9}


def build_tree(dst):
    committed = json.load(open(os.path.join(rehearse.REPO, "benchmark",
                                            "cells", CELL + ".json")))
    config = json.load(open(os.path.join(rehearse.REPO, "benchmark",
                                         "configs", "axk1-ep16.json")))
    rehearse.MIXES.append(TINY_DOCQA)
    rehearse.CELLS.append((
        {"name": "tiny_docqa_serve", "loop": "serve",
         "engine": {"class": "PagedKVEngine", "n_slots": 4, "block_size": 8,
                    "n_blocks": 40, "max_len": 64},
         "trace_seconds": 1, "check_requests": 3,
         "logit_gap_tol": committed["logit_gap_tol"]},
        "tiny-axk1", "tiny_docqa", 1, CELL))
    added = rehearse.build_tree(dst)
    # the configuration: `rehearse.build_tree` writes only its own two
    tiny = dict(TINY_AXK1, router_tie_margin=config["router_tie_margin"])
    path = os.path.join(dst, "benchmark", "configs", "tiny-axk1.json")
    assert not os.path.exists(path)
    with open(path, "w") as f:
        json.dump(tiny, f)
    manifest = os.path.join(dst, "BENCHMARK.json")
    bench = json.load(open(manifest))
    bench["configs"].append({"name": "tiny-axk1", "source": "throw-away",
                             "file": "benchmark/configs/tiny-axk1.json",
                             "reduced": [], "why": "throw-away"})
    with open(manifest, "w") as f:
        json.dump(bench, f)
    return added + ["benchmark/configs/tiny-axk1.json"]


def main(argv):
    dst, tool, rest = argv[0], argv[1], argv[2:]
    n_dev = 1
    if rest[0] == "--devices":
        n_dev, rest = int(rest[1]), rest[2:]
    rest = rest[1:] if rest[0] == "--" else rest
    added = build_tree(dst)
    print("rehearsal: added " + " ".join(added), file=sys.stderr)
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.chdir(dst)
    sys.path[:0] = [dst, rehearse.REPO]
    import jax
    jax.config.update("jax_num_cpu_devices", n_dev)
    from benchmark import control, harness, run
    harness.device_facts = rehearse.admit_cpu
    return {"run": run, "control": control}[tool].main(rest)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
