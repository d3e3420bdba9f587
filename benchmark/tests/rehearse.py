"""CPU rehearsal of the benchmark at tiny sizes, and the proof that it is
driven by data.

    python3 benchmark/tests/rehearse.py <scratch dir> [--devices N] -- <run.py arguments>

Copies the benchmark into <scratch dir>, ADDS throw-away files there (tiny
configurations, mixes and cells, and one per-layer metric that reads a span
no committed metric reads) and the entries that name them in a copy of
BENCHMARK.json, edits no file that was there, and runs benchmark/run.py from
the copy with the device check replaced by one that admits the CPU. The
replacement is made here, in the test's own process: the benchmark has no
option that lets it run without a TPU.
"""

from __future__ import annotations

import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))

TINY_LM = {"name": "tiny-lm", "source": "throw-away", "model": "lm",
           "num_layers": 2, "d_model": 32, "d_inner": 64, "num_heads": 2,
           "head_dim": 16, "max_len": 64, "vocab": 97, "dropout": 0.0,
           "reduced": [], "assumed": {}}
TINY_NMT = {"name": "tiny-nmt", "source": "throw-away", "model": "nmt",
            "num_layers": 1, "d_model": 32, "d_inner": 64, "num_heads": 2,
            "head_dim": 16, "max_len": 16, "src_vocab": 61, "tgt_vocab": 67,
            "label_smooth": 0.1, "dropout": 0.0, "reduced": [], "assumed": {}}
ADAM = {"type": "adam", "learning_rate": 1e-4}
MIXES = [
    {"name": "tiny_stream", "kind": "train_tokens", "batch_per_chip": 2,
     "seq_len": 16, "ring": 3, "optimizer": ADAM},
    {"name": "tiny_pairs", "kind": "train_pairs", "batch_per_chip": 4,
     "seq_len": 16, "ring": 3, "optimizer": ADAM,
     "lengths": {"dist": "lognormal_quantiles", "median": 6, "sigma": 0.5,
                 "min": 2, "max": 16}},
    {"name": "tiny_chat", "kind": "open_loop", "rate_per_s": 6.0,
     "system_prompts": {"count": 2, "tokens": 8,
                        "popularity": {"dist": "zipf", "exponent": 1.0}},
     "user_tokens": {"dist": "lognormal_quantiles", "median": 6, "sigma": 0.8,
                     "min": 2, "max": 16},
     "output_tokens": {"dist": "lognormal_quantiles", "median": 5,
                       "sigma": 0.7, "min": 2, "max": 12},
     "pairing": "golden_stride",
     "arrivals": {"process": "uniform_order_statistics", "burst_size": 1},
     "drain_deadline_s": 60, "schedule_seed": 5},
]
CELLS = [
    ({"name": "tiny_train", "loop": "train", "executor": "Executor",
      "trace_seconds": 1, "steps_ahead": 8, "loss_rel_tol": 0.002},
     "tiny-lm", "tiny_stream", 1,
     "lm-big_train_1chip"),
    ({"name": "tiny_nmt_train", "loop": "train", "executor": "Executor",
      "trace_seconds": 1, "steps_ahead": 8, "loss_rel_tol": 0.002},
     "tiny-nmt", "tiny_pairs", 1,
     "nmt-big_train_1chip"),
    ({"name": "tiny_train_dp4", "loop": "train",
      "executor": "ParallelExecutor", "mesh": {"dp": 4}, "trace_seconds": 1,
      "steps_ahead": 8, "loss_rel_tol": 0.002},
     "tiny-lm", "tiny_stream", 4, "lm-big_train_dp4"),
    ({"name": "tiny_serve", "loop": "serve",
      "engine": {"class": "PagedKVEngine", "n_slots": 4, "block_size": 4,
                 "n_blocks": 64, "max_len": 64},
      "trace_seconds": 1, "check_requests": 3, "logit_gap_tol": 0.05},
     "tiny-lm", "tiny_chat", 1, "lm-big_serve_chat"),
]
# a per-layer metric of its own, reading a span no committed metric reads
THROWAWAY_METRIC = '''"""Throw-away: median of the program's engine/admit spans."""

from ..harness import quantile

UNIT = "ms"
SOURCE = "program_span"
LAYER = "scheduler"
MOVES = "ttft_p50_ms"


def read(run):
    return quantile(run.span_ms("engine/admit"), 0.5)
'''


def build_tree(dst: str) -> list:
    """The copy with the throw-away files added; returns the files added."""
    shutil.rmtree(dst, ignore_errors=True)
    shutil.copytree(os.path.join(REPO, "benchmark"),
                    os.path.join(dst, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    added = []

    def add(rel, text):
        path = os.path.join(dst, "benchmark", rel)
        if os.path.exists(path):
            raise SystemExit(f"rehearsal would edit {rel}")
        with open(path, "w") as f:
            f.write(text)
        added.append("benchmark/" + rel)

    for cfg in (TINY_LM, TINY_NMT):
        add(f"configs/{cfg['name']}.json", json.dumps(cfg))
        bench["configs"].append({
            "name": cfg["name"], "source": "throw-away",
            "file": f"benchmark/configs/{cfg['name']}.json", "reduced": [],
            "why": "throw-away"})
    for mix in MIXES:
        add(f"traffic/{mix['name']}.json", json.dumps(mix))
    like = {}      # committed cell -> the tiny cell that reports its metrics
    for spec, config, mix, chips, stands_for in CELLS:
        add(f"cells/{spec['name']}.json", json.dumps(spec))
        bench["workloads"].append({"name": spec["name"], "config": config,
                                   "traffic": mix, "chips": chips,
                                   "why": "throw-away"})
        like[stands_for] = spec["name"]
    add("metrics/admit_ms_p50.py", THROWAWAY_METRIC)
    bench["per_layer"].append({
        "name": "admit_ms_p50", "unit": "ms", "better": "lower",
        "source": "program_span", "layer": "scheduler",
        "moves": "ttft_p50_ms", "workloads": [like["lm-big_serve_chat"]]})
    for m in bench["end_to_end"] + bench["per_layer"][:-1]:
        if "workloads" in m:
            m["workloads"] = m["workloads"] + [like[w] for w in m["workloads"]]
    with open(os.path.join(dst, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return added


def admit_cpu(chips):
    """Stands in for harness.device_facts in a rehearsal."""
    import jax
    devs = jax.devices()
    if len(devs) < chips:
        raise SystemExit(f"rehearsal: {chips} devices asked, {len(devs)} found")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips, "devices": devs[:chips],
            "peaks": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11,
                      "hbm_bytes": 1e10}}


def main(argv):
    dst, rest = argv[0], argv[1:]
    n_dev = 1
    if rest[0] == "--devices":
        n_dev, rest = int(rest[1]), rest[2:]
    rest = rest[1:] if rest[0] == "--" else rest
    added = build_tree(dst)
    print("rehearsal: added " + " ".join(added), file=sys.stderr)
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.chdir(dst)
    sys.path[:0] = [dst, REPO]          # the copy's benchmark, the repo's program
    import jax
    jax.config.update("jax_num_cpu_devices", n_dev)
    from benchmark import harness, run
    harness.device_facts = admit_cpu
    return run.main(rest)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
