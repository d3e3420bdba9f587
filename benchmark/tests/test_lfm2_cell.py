"""The cell lfm2-8b-a1b_serve_assistant: its files load, its mix sends what the
issue fixed, the adapter counts what the arithmetic says, each new reader
reads its attr, counter or kernel (and nothing where there is none), and the
committed comparison holds at a tiny size through the harness itself."""

import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from benchmark import harness, kernel_ops, traffic
from benchmark.models import lfm2

CELL = "lfm2-8b-a1b_serve_assistant"
HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
         "hbm_bytes": 16e9}


@pytest.fixture(scope="module")
def cell():
    return harness.Cell(CELL)


def test_the_cells_files_load_and_name_each_other(cell):
    assert cell.chips == 1 and cell.adapter is lfm2
    assert cell.spec["engine"] == {"class": "PagedKVEngine", "n_slots": 64,
                                   "block_size": 64, "n_blocks": 2048,
                                   "max_len": 3072}
    assert cell.spec["check_requests"] == 2 and cell.spec["loop"] == "serve"
    assert cell.traffic["name"] == "serve_assistant"
    e2e = {m["name"] for m in cell.metrics["end_to_end"]}
    # the TTFT metrics are left out: 180 requests a window spread them wider
    # than half their bounds over sets of six (PERF.md section 6, PR 39)
    assert e2e == {"tpot_p50_ms", "setup_s"}
    layers = {m["name"] for m in cell.metrics["per_layer"]}
    assert {"gqa_decode_roofline", "conv_state_restore_share",
            "moe_tick_roofline", "moe_experts_roofline",
            "experts_touched_p50", "expert_load_max_over_mean",
            "serve_engine_peak_hbm_gb", "tick_kv_blocks_p50"} <= layers
    assert all(m["moves"] == "tpot_p50_ms" for m in cell.metrics["per_layer"])
    assert not {"mla_decode_roofline", "decode_tick_roofline",
                "window_stolen_ms", "prefix_hit_rate"} & layers
    for m in cell.metrics["per_layer"] + cell.metrics["end_to_end"]:
        reader = harness.load_module("metrics", m["name"])
        assert (reader.UNIT, reader.SOURCE) == (m["unit"], m["source"])


def test_the_configuration_holds_every_published_number(cell):
    cfg = cell.config
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(json.loads(line) for line in f
                       if json.loads(line)["name"] == "LFM2-8B-A1B")
        pub = row["config"]
        assert cfg["source"] == row["source_url"]
        differs = {k for k, v in pub.items() if cfg.get(k) != v}
        assert differs == {"num_hidden_layers", "layer_types"}
        assert differs <= set(cfg["reduced"])
        assert {k: cfg["published"][k] for k in differs} == \
            {k: pub[k] for k in differs}
        assert cfg["layer_types"] == pub["layer_types"][:16]
    assert cfg["reduced"] == ["num_hidden_layers", "num_layers", "layer_types"]
    assert cfg["num_layers"] == cfg["num_hidden_layers"] == 16
    assert cfg["vocab"] == cfg["vocab_size"] == 65536
    # depth only: four whole periods, 12 conv + 4 attention, 2 dense + 14
    # routed layers
    kinds = lfm2.layer_kinds(cfg)
    assert kinds == ("conv", "conv", "attention", "conv") * 4
    for key in ("tie_word_embeddings", "head_dim", "qk_norm", "rope_pairing",
                "router", "conv", "init", "expert_bias_sigma", "precision",
                "max_len", "router_tie_margin"):
        assert cfg["assumed"][key]
    assert "two pipeline stages" in cfg["deployment"]


def test_the_cut_is_the_issues_arithmetic(cell):
    cfg = cell.config
    H, F, Fe = 2048, 7168, 1792
    expert = 3 * H * Fe
    assert expert == 11_010_048 and lfm2.expert_bytes(cfg) == 2 * expert
    conv, attn = 3 * H * H + H * H + 3 * H, \
        H * H + 2 * H * 512 + H * H + 2 * 64
    routed = 32 * expert + 32 * H + 32
    want = (65536 * H + 12 * conv + 4 * attn + 2 * 3 * H * F + 14 * routed
            + 16 * 2 * H + H)
    assert lfm2.n_params(cfg) == want
    assert 5.39e9 < want < 5.41e9               # 10.80 GB at 2 bytes
    # the whole model would not fit: 8 more layers (6 conv + 2 attention,
    # 8 routed)
    whole = want + 6 * conv + 2 * attn + 8 * routed + 8 * 2 * H
    assert 16.6e9 < 2 * whole < 16.8e9
    eng = cell.spec["engine"]
    assert lfm2.kv_row_bytes(cfg) == 2 * 8 * 64 * 2
    block = eng["block_size"] * 4 * lfm2.kv_row_bytes(cfg)
    assert block == 524288 and block * eng["n_blocks"] == 1_073_741_824
    assert lfm2.conv_state_bytes(cfg) == 12 * 2 * 2048 * 2 == 98304
    resident = 2 * want + block * eng["n_blocks"] \
        + 98304 * (eng["n_blocks"] + eng["n_slots"])
    assert 0.74 < resident / 16e9 < 0.77        # the floor is 25%


def test_assistant_sends_what_the_issue_fixed(cell):
    mix = cell.traffic
    assert mix["system_prompts"] == {"count": 8, "tokens": 1024,
                                     "popularity": {"dist": "zipf",
                                                    "exponent": 1.0}}
    assert mix["user_tokens"] == {"dist": "lognormal_quantiles",
                                  "median": 128, "sigma": 0.9, "min": 16,
                                  "max": 1024}
    assert mix["output_tokens"] == {"dist": "lognormal_quantiles",
                                    "median": 192, "sigma": 0.7, "min": 32,
                                    "max": 768}
    assert mix["schedule_seed"] == 39 and mix["arrivals"]["burst_size"] == 1
    assert mix["drain_deadline_s"] == 60 and mix["pairing"] == "golden_stride"
    assert isinstance(mix["rate_per_s"], (int, float))
    load = traffic.open_loop_requests(mix, 2 ** 31 + 3, 45.0,
                                      cell.config["vocab"])
    reqs = load["requests"]
    assert len(reqs) == round(mix["rate_per_s"] * 45)
    assert len(load["system_prompts"]) == 8
    again = traffic.open_loop_requests(mix, 7, 45.0, cell.config["vocab"])
    assert [(r["due"], r["user_len"], r["max_new"], r["system"])
            for r in reqs] == [(r["due"], r["user_len"], r["max_new"],
                                r["system"]) for r in again["requests"]]
    for r in reqs:
        assert r["prompt"][:1024] == load["system_prompts"][r["system"]]
        assert 16 <= r["user_len"] <= 1024 and 32 <= r["max_new"] <= 768
        assert len(r["prompt"]) + r["max_new"] <= cell.spec["engine"]["max_len"]
    assert max(max(r["prompt"]) for r in reqs) > 32767      # the whole vocabulary
    # every preamble is asked for, the first most (Zipf): every admission of
    # the window is a prefix hit that restores conv state
    counts = np.bincount([r["system"] for r in reqs], minlength=8)
    assert counts.min() > 0 and counts[0] == counts.max()


def _span(name="engine/tick", **attrs):
    return types.SimpleNamespace(name=name, attrs=attrs, duration_ms=1.0)


class _Trace:
    """A trace with one decode-tick program run three times: 4 grouped reads
    and 14 expert products an execution."""
    def __init__(self, gqa_s, moe_s, busy_s):
        gqa = kernel_ops.kernel_key("paged_gqa_attention", "float32",
                                    (64, 8, 8, 128))
        moe = kernel_ops.kernel_key("moe_experts", "float32", (64, 2048))
        ops, modules, t = [], [], 0.0
        for _ in range(3):
            start = t
            for key, n, dur in ((gqa, 4, gqa_s), (moe, 14, moe_s)):
                for _ in range(n):
                    ops.append((t, t + dur, key, "custom-call", True))
                    t += dur
            modules.append((start, t, "jit_tick", 1))
            t += 1e-3
        self.devices = [types.SimpleNamespace(ops=ops, modules=modules)]
        self._busy = busy_s

    def main_module(self):
        return "jit_tick"

    def module_busy_seconds(self, name=None):
        return [self._busy] * 3


def _run(cell, spans, trace=None):
    return types.SimpleNamespace(cell=cell, spans=spans, trace=trace,
                                 counters={}, requests=[],
                                 device={"peaks": PEAKS})


def test_new_readers_read_their_attrs_and_kernels(cell):
    read = lambda name, run: harness.load_module("metrics", name).read(run)   # noqa: E731
    cfg = cell.config
    ticks = [_span(prefill=0, experts_touched=400, routed_rows=104,
                   decode_rows=30000, kv_blocks=b, expert_rows=[1] * 448)
             for b in (420, 427, 440)]
    mixed = _span(prefill=1, experts_touched=448, routed_rows=900,
                  decode_rows=5, kv_blocks=9999, expert_rows=[2] * 448)
    admits = [_span("engine/admit", state_restored=3),
              _span("engine/admit", state_restored=1),
              _span("engine/admit", pool_used=4)]
    run = _run(cell, ticks + [mixed] + admits,
               _Trace(gqa_s=100e-6, moe_s=800e-6, busy_s=17e-3))
    # four requests started from a shared span, a fifth prefilled all its own
    run.requests = [{"shared_len": n} for n in (1024, 1024, 0, 1024, 64)]
    # one grouped read over 427 live blocks of 64 positions: K and V of 8
    # heads of 64 in bfloat16 = 2,048 B a position -> 55.97 MB at 819 GB/s =
    # 68.3 us (the operations, 4 * 32 * 64 a position, need 1.1 us); it took
    # 100 us
    flops, nbytes = lfm2.gqa_decode_call(cfg, 427, 64)
    assert nbytes == 427 * 64 * 2048 and flops == 427 * 64 * 4 * 32 * 64
    assert read("gqa_decode_roofline", run) == pytest.approx(
        100 * (nbytes / 819e9) / 100e-6)
    assert 60 < read("gqa_decode_roofline", run) < 75
    assert read("conv_state_restore_share", run) == 100.0
    run.spans[-2].attrs["state_restored"] = 0       # resumed from no snapshot
    assert read("conv_state_restore_share", run) == 75.0
    # the shared readers, through this adapter's counts
    least = lfm2.moe_tick_bytes(cfg, 64, 400, 30000) / 819e9
    assert read("moe_tick_roofline", run) == pytest.approx(
        100 * least / 17e-3)
    assert 50 < read("moe_tick_roofline", run) < 100
    flops, nbytes = lfm2.experts_call(cfg, 64, 400, 104)
    assert nbytes == 400 * 22_020_096 + 14 * 64 * 2048 * 6
    assert read("moe_experts_roofline", run) == pytest.approx(
        100 * max(flops / 197e12, nbytes / 819e9) / (14 * 800e-6))
    # what a tick cannot avoid: everything but the experts (the embedding is
    # the head, read whole), the touched experts, the live K/V, the slots'
    # state in and out
    dense = 2 * (lfm2.n_params(cfg) - 14 * 32 * 11_010_048)
    assert lfm2.moe_tick_bytes(cfg, 64, 400, 30000) == (
        dense + 2 * 64 * 98304 + 400 * 22_020_096 + 30000 * 4 * 2048)


def test_new_readers_return_none_where_there_is_nothing_to_read(cell):
    names = ("gqa_decode_roofline", "conv_state_restore_share")
    old = [_span(prefill=0, kv_blocks=12),             # the parent's spans
           _span("engine/admit", pool_used=3)]
    for run in (_run(cell, old, _Trace(1e-4, 1e-3, 2e-2)),
                _run(cell, [], None)):
        for name in names:
            assert harness.load_module("metrics", name).read(run) is None
    # the attrs without the kernel in the trace: the share stays out
    ticks = [_span(prefill=0, experts_touched=3, kv_blocks=9)]
    bare = _Trace(1e-4, 1e-3, 2e-2)
    bare.devices[0].ops = [o for o in bare.devices[0].ops
                           if "gqa" not in o[2]]
    assert harness.load_module("metrics", "gqa_decode_roofline").read(
        _run(cell, ticks, bare)) is None
    # an adapter without the counts (another configuration's)
    other = types.SimpleNamespace(adapter=types.SimpleNamespace(),
                                  config=cell.config, spec=cell.spec)
    assert harness.load_module("metrics", "gqa_decode_roofline").read(
        _run(other, ticks, _Trace(1e-4, 1e-3, 2e-2))) is None


# -- the committed comparison at a tiny size, through the harness itself ------

def _rehearse(tmp, tool, *args):
    cmd = [sys.executable, os.path.join(HERE, "tests", "rehearse_lfm2.py"),
           str(tmp), tool, "--", "--workload", "tiny_assistant_serve", *args]
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    return subprocess.run(cmd, capture_output=True, text=True, timeout=900,
                          env=env)


def test_the_tiny_cell_traced_reports_the_new_readers(tmp_path):
    p = _rehearse(tmp_path, "run", "--seed", str(2 ** 31 + 5), "--seconds",
                  "1.5", "--trace", "1")
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["metrics"]["conv_state_restore_share"]["value"] == 100.0
    assert {"experts_touched_p50", "expert_load_max_over_mean",
            "tick_kv_blocks_p50"} <= set(line["metrics"])
    # a CPU gives no device trace: a kernel's share is never written there
    assert not {"gqa_decode_roofline", "moe_tick_roofline",
                "moe_experts_roofline"} & set(line["metrics"])
    value, limit = (line["checks"]["worst_logit_gap"][k]
                    for k in ("value", "limit"))
    assert limit == harness.Cell(CELL).spec["logit_gap_tol"] and value < limit


@pytest.mark.parametrize("seed", [2 ** 31 + 7, 3900000011])
def test_the_control_fails_the_loops_own_check_where_the_cell_passes_it(
        tmp_path, seed):
    p = _rehearse(tmp_path, "control", "--seed", str(seed), "--seconds",
                  "1.5", "--requests", "6")
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    reads = {ln.split(": ", 1)[0]: json.loads(ln.split(": ", 1)[1])
             for ln in p.stdout.splitlines()
             if ln.startswith(("clean: ", "control: "))}
    assert reads["clean"]["passes"] and not reads["control"]["passes"]
    assert reads["clean"]["worst_logit_gap"] < reads["clean"]["limit"] \
        < reads["control"]["worst_logit_gap"]
