"""`rehearse.py` for the cell mellum2-ep4_train_8k_1chip: the same copy of the
benchmark with throw-away files ADDED, among them a tiny configuration of the
cell's adapter, its mix and its cell, which stands for the committed cell in
every list that names it (and, through `rehearse_kexaone.py`, the stand-ins of
the four serving cells the same lists name).

    python3 benchmark/tests/rehearse_mellum.py <scratch dir> <run|train_witness|control|witness> [--devices N] -- <arguments>

`run` is benchmark/run.py and `train_witness` benchmark/train_witness.py,
from the copy, on the CPU (`control` and `witness` are the serving cells', as
`rehearse_kexaone.py` has them). The tiny configuration takes its `aux_coef`
and `qk_init_gain` from the committed file and the tiny cell its
`loss_rel_tol` from the committed cell, ONE PRECISION UP: the tiny cell
multiplies in float32 (the package's switch `PTPU_USE_BF16_MATMUL=0`, set
here for it alone), its control in bfloat16, and its limit on the loss is
the committed one over 16: the stated precision and the control are both
four mantissa bits finer than the committed cell's bfloat16 and float8.
"""

from __future__ import annotations

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
import rehearse             # noqa: E402
import rehearse_kexaone     # noqa: E402
import rehearse_lfm2        # noqa: E402

CELL = "mellum2-ep4_train_8k_1chip"
_with_kexaone = rehearse_kexaone.build_tree
# every mechanism of configs/mellum2-ep4.json, none of its widths: one whole
# period S S S F, a window of 8 under sequences of 32, 8 heads over 2, YaRN
# on the full layer, 8 experts under top-2 of which rank 0 of two holds 4
TINY_MELLUM = dict(
    name="tiny-mellum", source="throw-away", model="mellum",
    hidden_size=64, intermediate_size=128, num_attention_heads=8,
    num_key_value_heads=2, head_dim=16,
    layer_types=["sliding_attention"] * 3 + ["full_attention"],
    mlp_layer_types=["sparse"] * 4, sliding_window=8,
    rope_parameters={
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
            "original_max_position_embeddings": 8, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default", "rope_theta": 500000}},
    moe_intermediate_size=32, num_experts=4, router_width=8, expert_rank=0,
    num_experts_per_tok=2, norm_topk_prob=True, hidden_act="silu",
    rms_norm_eps=1e-6, num_layers=4, num_hidden_layers=4, vocab=96,
    vocab_size=96, max_len=32, weights_dtype="float32",
    matmul_dtype="float32", reduced=[], assumed={})
TINY_CODE = {"name": "tiny_code", "kind": "train_tokens",
             "batch_per_chip": 2, "seq_len": 32, "ring": 3,
             "optimizer": rehearse.ADAM}


def build_tree(dst):
    load = lambda *p: json.load(open(os.path.join(      # noqa: E731
        rehearse.REPO, "benchmark", *p)))
    committed = load("cells", CELL + ".json")
    config = load("configs", "mellum2-ep4.json")
    rehearse.MIXES.append(TINY_CODE)
    rehearse.CELLS.append((
        {"name": "tiny_code_train", "loop": "train", "executor": "Executor",
         "trace_seconds": 1, "steps_ahead": 8,
         "loss_rel_tol": committed["loss_rel_tol"] / 16},
        "tiny-mellum", "tiny_code", 1, CELL))
    added = _with_kexaone(dst)
    tiny = dict(TINY_MELLUM, aux_coef=config["aux_coef"],
                qk_init_gain=config["qk_init_gain"])
    path = os.path.join(dst, "benchmark", "configs", "tiny-mellum.json")
    assert not os.path.exists(path)
    with open(path, "w") as f:
        json.dump(tiny, f)
    manifest = os.path.join(dst, "BENCHMARK.json")
    bench = json.load(open(manifest))
    bench["configs"].append({"name": "tiny-mellum", "source": "throw-away",
                             "file": "benchmark/configs/tiny-mellum.json",
                             "reduced": [], "why": "throw-away"})
    with open(manifest, "w") as f:
        json.dump(bench, f)
    return added + ["benchmark/configs/tiny-mellum.json"]


def main(argv):
    rehearse_lfm2.build_tree = build_tree   # its `main`, over this table
    if "tiny_code_train" in argv:
        os.environ["PTPU_USE_BF16_MATMUL"] = "0"    # before the package loads
    if argv[1] != "train_witness":
        return rehearse_lfm2.main(argv)
    dst, rest = argv[0], argv[2:]
    rest = rest[1:] if rest[0] == "--" else rest
    build_tree(dst)
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.chdir(dst)
    sys.path[:0] = [dst, rehearse.REPO]
    from benchmark import train_witness
    return train_witness.main(rest)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
