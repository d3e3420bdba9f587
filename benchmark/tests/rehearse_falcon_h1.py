"""`rehearse.py` for the cell falcon-h1-34b-pp12_serve_long_prompts: the same
copy of the benchmark with throw-away files ADDED, among them a tiny
configuration of the cell's adapter, its mix and its cell, which stands for
the committed cell in every list that names it (and, through
`rehearse_mellum.py`, the stand-ins of the five cells added before it: a table
that holds all ten).

    python3 benchmark/tests/rehearse_falcon_h1.py <scratch dir> <run|control|witness|train_witness> [--devices N] -- <arguments>

`run` is benchmark/run.py, `control` benchmark/control.py, `witness`
benchmark/witness.py, from the copy, on the CPU. The tiny cell takes its
`logit_gap_tol` from the committed cell and its multipliers from the committed
configuration.
"""

from __future__ import annotations

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
import rehearse             # noqa: E402
import rehearse_mellum      # noqa: E402

CELL = "falcon-h1-34b-pp12_serve_long_prompts"
_with_mellum = rehearse_mellum.build_tree
MULTIPLIERS = ("attention_in_multiplier", "attention_out_multiplier",
               "embedding_multiplier", "key_multiplier", "lm_head_multiplier",
               "mlp_multipliers", "ssm_in_multiplier", "ssm_multipliers",
               "ssm_out_multiplier")
# every mechanism of configs/falcon-h1-34b-pp12.json, none of its widths (as
# tests/falcon_h1_tiny.py has it)
TINY_FALCON = dict(
    name="tiny-falcon-h1", source="throw-away", model="falcon_h1",
    hidden_size=64, intermediate_size=96, num_attention_heads=10,
    num_key_value_heads=2, head_dim=8, num_layers=3, num_hidden_layers=3,
    vocab=97, vocab_size=97, mamba_n_heads=8, mamba_d_head=8, mamba_d_ssm=64,
    mamba_n_groups=2, mamba_d_state=16, mamba_d_conv=4, mamba_chunk_size=16,
    mamba_num_heads=8, mamba_head_dim=8, ssm_state_size=16,
    mamba_conv_bias=True, mamba_norm_before_gate=False, mamba_rms_norm=True,
    mamba_proj_bias=False, attention_bias=False, mlp_bias=False,
    projectors_bias=False, hidden_act="silu", tie_word_embeddings=False,
    rope_scaling=None, rope_theta=100000000000, rms_norm_eps=1e-5,
    time_step_min=0.001, time_step_max=0.1, time_step_floor=0.0001,
    check_stale_at=16, check_stale_block=8, weights_dtype="bfloat16", cache_dtype="bfloat16",
    max_len=128, reduced=[], assumed={})
TINY_LONG_PROMPTS = {
    "name": "tiny_long_prompts", "kind": "open_loop", "rate_per_s": 6.0,
    "user_tokens": {"dist": "lognormal_quantiles", "median": 40, "sigma": 0.6,
                    "min": 18, "max": 80},
    "output_tokens": {"dist": "lognormal_quantiles", "median": 24,
                      "sigma": 0.3, "min": 16, "max": 36},
    "pairing": "golden_stride",
    "arrivals": {"process": "uniform_order_statistics", "burst_size": 1},
    "drain_deadline_s": 60, "schedule_seed": 54}


def build_tree(dst):
    load = lambda *p: json.load(open(os.path.join(      # noqa: E731
        rehearse.REPO, "benchmark", *p)))
    committed = load("cells", CELL + ".json")
    config = load("configs", "falcon-h1-34b-pp12.json")
    rehearse.MIXES.append(TINY_LONG_PROMPTS)
    rehearse.CELLS.append((
        {"name": "tiny_long_prompts_serve", "loop": "serve",
         "engine": {"class": "PagedKVEngine", "n_slots": 4, "block_size": 8,
                    "n_blocks": 80, "max_len": 128, "n_snapshots": 3},
         "trace_seconds": 1, "check_requests": 6,
         "logit_gap_tol": committed["logit_gap_tol"]},
        "tiny-falcon-h1", "tiny_long_prompts", 1, CELL))
    added = _with_mellum(dst)
    tiny = dict(TINY_FALCON, **{k: config[k] for k in MULTIPLIERS})
    path = os.path.join(dst, "benchmark", "configs", "tiny-falcon-h1.json")
    assert not os.path.exists(path)
    with open(path, "w") as f:
        json.dump(tiny, f)
    manifest = os.path.join(dst, "BENCHMARK.json")
    bench = json.load(open(manifest))
    bench["configs"].append({"name": "tiny-falcon-h1", "source": "throw-away",
                             "file": "benchmark/configs/tiny-falcon-h1.json",
                             "reduced": [], "why": "throw-away"})
    with open(manifest, "w") as f:
        json.dump(bench, f)
    return added + ["benchmark/configs/tiny-falcon-h1.json"]


def main(argv):
    rehearse_mellum.build_tree = build_tree     # its `main`, over this table
    return rehearse_mellum.main(argv)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
