"""The cell k-exaone-ep8_serve_long_sessions: its files load, its mix sends
what the issue fixed, the adapter counts what the arithmetic says, each new
reader reads its kernel or counter (and nothing where there is none), and the
committed comparison holds at a tiny size through the harness itself."""

import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from benchmark import harness, kernel_ops, traffic
from benchmark.models import kexaone

CELL = "k-exaone-ep8_serve_long_sessions"
HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
         "hbm_bytes": 16e9}
NEW = ("window_decode_roofline", "window_blocks_per_slot_p50",
       "window_tail_hit_share")


@pytest.fixture(scope="module")
def cell():
    return harness.Cell(CELL)


def test_the_cells_files_load_and_name_each_other(cell):
    assert cell.chips == 1 and cell.adapter is kexaone
    assert cell.spec["engine"] == {
        "class": "PagedKVEngine", "n_slots": 32, "block_size": 64,
        "n_blocks": 8192, "n_window_blocks": 512, "max_len": 17408}
    assert cell.spec["check_requests"] == 2 and cell.spec["loop"] == "serve"
    assert cell.traffic["name"] == "serve_long_sessions"
    e2e = {m["name"] for m in cell.metrics["end_to_end"]}
    assert {"tpot_p50_ms", "setup_s"} <= e2e
    layers = {m["name"] for m in cell.metrics["per_layer"]}
    assert set(NEW) | {"gqa_decode_roofline", "moe_tick_roofline",
                       "moe_experts_roofline", "experts_touched_p50",
                       "expert_load_max_over_mean", "tick_kv_blocks_p50",
                       "serve_engine_peak_hbm_gb"} <= layers
    assert not {"mla_decode_roofline", "ssm_decode_roofline",
                "latent_experts_roofline", "decode_tick_roofline",
                "conv_state_restore_share"} & layers
    for m in cell.metrics["per_layer"] + cell.metrics["end_to_end"]:
        reader = harness.load_module("metrics", m["name"])
        assert (reader.UNIT, reader.SOURCE) == (m["unit"], m["source"])
        if m["name"] in NEW:
            assert (reader.LAYER, reader.MOVES) == (m["layer"], m["moves"])
            assert m["workloads"] == [CELL]


def test_the_configuration_holds_every_published_number(cell):
    cfg = cell.config
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    reduced = {"num_hidden_layers", "layer_types", "mlp_layer_types",
               "sliding_windows", "num_experts", "vocab_size"}
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(json.loads(line) for line in f if json.loads(line)[
                "name"] == "K-EXAONE-236B-A23B")
        pub = row["config"]
        assert cfg["source"] == row["source_url"]
        differs = {k for k, v in pub.items() if cfg.get(k) != v}
        assert differs == reduced and differs <= set(cfg["reduced"])
        assert {k: cfg["published"][k] for k in differs} == \
            {k: pub[k] for k in differs}
        for key in ("layer_types", "mlp_layer_types", "sliding_windows"):
            assert cfg[key] == pub[key][:5]
    assert set(cfg["reduced"]) == reduced | {"num_layers", "vocab"}
    assert cfg["num_layers"] == cfg["num_hidden_layers"] == 5
    assert cfg["vocab"] == cfg["vocab_size"] == 19200 == 150 * 128
    assert cfg["num_experts"] == 16 and cfg["router_width"] == 128
    # the leading dense layer once, then one whole period L L G L (3 : 1)
    assert kexaone.attention_kinds(cfg) == ("window",) * 3 + ("full",
                                                              "window")
    assert cfg["mlp_layer_types"] == ["dense"] + ["sparse"] * 4
    assert cfg["num_experts"] >= 8 and cfg["vocab"] * 8 == 153600
    for key in ("block", "attention", "window", "topk_method", "partial_sum",
                "rope_pairing", "init", "precision", "max_len",
                "router_tie_margin"):
        assert cfg["assumed"][key]
    assert "eight chips share each layer" in cfg["deployment"]
    assert "rank 0" in cfg["deployment"]
    assert "multi_token_prediction" in cfg["left_out"]
    assert cfg["num_nextn_predict_layers"] == 1       # carried, read by nothing
    spec = kexaone.spec_of(cfg)
    assert spec.window == 128 and spec.d_head == 128
    assert spec.num_heads * spec.d_head == 8192 > spec.d_model == 6144
    assert spec.moe.held == tuple(range(16)) and spec.moe.n_routed == 128
    assert not spec.tied_head and spec.qk_norm


def test_the_cut_is_the_issues_arithmetic(cell):
    cfg = cell.config
    H, F, Fe = 6144, 18432, 2048
    attn = H * 8192 * 2 + H * 1024 * 2 + 2 * 128
    expert = 3 * H * Fe
    assert expert == 37_748_736 and kexaone.expert_bytes(cfg) == 2 * expert
    routed = H * 128 + expert + 16 * expert
    dense = 3 * H * F
    want = (2 * 19200 * H + 5 * attn + dense + 4 * routed + 10 * H + H)
    assert kexaone.n_params(cfg) == want
    assert 3.70e9 < want < 3.73e9               # 7.42 GB at 2 bytes
    assert round(attn / 1e6, 2) == 113.25 and round(dense / 1e6, 2) == 339.74
    assert round(routed / 1e6, 1) == 642.5
    eng = cell.spec["engine"]
    spec = kexaone.spec_of(cfg)
    assert kexaone.kv_row_bytes(cfg) == 4096 == spec.cache_row_bytes()
    assert spec.window_row_bytes() == 4 * 4096
    full = 4096 * eng["block_size"] * eng["n_blocks"]
    window = 4 * 4096 * eng["block_size"] * eng["n_window_blocks"]
    assert round(full / 1e9, 2) == 2.15 and round(window / 1e9, 2) == 0.54
    resident = 2 * want + full + window
    assert 0.62 < resident / 16e9 < 0.66        # the floor is 25%
    # held as one kind the same positions would not fit beside the weights
    assert 2 * want + 5 * full > 16e9
    # every slot's bound fits the window pool beside the sessions' tails
    bound = -(-(128 + 128) // 64) + 1
    assert eng["n_slots"] * bound + 24 * 3 + 1 <= eng["n_window_blocks"]


def test_long_sessions_sends_what_the_issue_fixed(cell):
    mix = cell.traffic
    assert mix["system_prompts"]["tokens"] == 16384
    assert mix["system_prompts"]["count"] in (24, 16)
    assert mix["system_prompts"]["popularity"] == {"dist": "zipf",
                                                   "exponent": 1.0}
    assert mix["user_tokens"] == {"dist": "lognormal_quantiles", "median": 64,
                                  "sigma": 0.8, "min": 16, "max": 512}
    assert mix["output_tokens"] == {"dist": "lognormal_quantiles",
                                    "median": 128, "sigma": 0.7, "min": 16,
                                    "max": 512}
    assert mix["arrivals"] == {"process": "uniform_order_statistics",
                               "burst_size": 1}
    assert mix["pairing"] == "golden_stride" and mix["drain_deadline_s"] == 60
    load = traffic.open_loop_requests(mix, 3000000001, 45.0,
                                      cell.config["vocab"])
    reqs = load["requests"]
    assert len(reqs) == round(mix["rate_per_s"] * 45)
    assert len(load["system_prompts"]) == mix["system_prompts"]["count"]
    assert all(16384 + 16 <= len(r["prompt"]) <= 16384 + 512 for r in reqs)
    assert all(len(r["prompt"]) + r["max_new"]
               <= cell.spec["engine"]["max_len"] for r in reqs)
    assert max(max(r["prompt"]) for r in reqs) < 19200
    again = traffic.open_loop_requests(mix, 7, 45.0, cell.config["vocab"])
    assert [r["due"] for r in again["requests"]] == [r["due"] for r in reqs]


def _span(name="engine/tick", **attrs):
    return types.SimpleNamespace(name=name, attrs=attrs, duration_ms=1.0)


class _Trace:
    """A trace with one decode-tick program run three times: 4 window reads,
    1 full read and 4 expert products an execution."""
    def __init__(self, window_s, full_s, moe_s, busy_s):
        shape = (32, 8, 8, 128)
        win = kernel_ops.kernel_key("paged_window_attention", "float32", shape)
        full = kernel_ops.kernel_key("paged_gqa_attention", "float32", shape)
        moe = kernel_ops.kernel_key("moe_experts", "float32", (32, 6144))
        ops, modules, t = [], [], 0.0
        for _ in range(3):
            start = t
            for key, dur, n in ((win, window_s, 4), (full, full_s, 1),
                                (moe, moe_s, 4)):
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


class _Engine:
    def __init__(self, window):
        self._window = window

    def stats(self):
        return {"pager": {"window": self._window}}


def _run(cell, spans, trace=None):
    return types.SimpleNamespace(cell=cell, spans=spans, trace=trace,
                                 counters={}, requests=[],
                                 device={"peaks": PEAKS})


def test_new_readers_read_their_attrs_kernels_and_counters(cell, monkeypatch):
    read = lambda name, run: harness.load_module("metrics", name).read(run)   # noqa: E731
    cfg = cell.config
    ticks = [_span(prefill=0, active=a, experts_touched=50, routed_rows=30,
                   decode_rows=a * 16500, kv_blocks=a * 261,
                   window_blocks=a * 3, window_rows=a * 128,
                   expert_rows=[1] * 64) for a in (14, 16, 19)]
    mixed = _span(prefill=2, active=30, experts_touched=64, routed_rows=900,
                  decode_rows=5, kv_blocks=9999, window_blocks=99,
                  window_rows=5, expert_rows=[2] * 64)
    run = _run(cell, ticks + [mixed],
               _Trace(window_s=60e-6, full_s=1500e-6, moe_s=1200e-6,
                      busy_s=9e-3))
    # 16 live rows x 128 positions x 4,096 B a window layer = 8.4 MB:
    # 10.2 us at 819 GB/s; a call took 60
    flops, nbytes = kexaone.window_decode_call(cfg, 16 * 128)
    assert nbytes == 16 * 128 * 4096 and flops == 4 * 64 * 128 * 16 * 128
    assert read("window_decode_roofline", run) == pytest.approx(
        100 * (nbytes / 819e9) / 60e-6)
    # the full layer's read: kv_blocks counts both pools; the full pool's
    # part is at least 256 / 259 of it
    flops, nbytes = kexaone.gqa_decode_call(cfg, 16 * 261, 64)
    assert nbytes == pytest.approx(16 * 261 * 256 / 259 * 64 * 4096)
    assert 16 * 257.9 * 64 * 4096 <= nbytes <= 16 * 258 * 64 * 4096
    assert read("gqa_decode_roofline", run) == pytest.approx(
        100 * (nbytes / 819e9) / 1500e-6)
    assert 80 < read("gqa_decode_roofline", run) < 100
    flops, nbytes = kexaone.experts_call(cfg, 32, 50, 30)
    assert nbytes == 50 * 75_497_472 + 4 * 32 * 6144 * 6
    assert read("moe_experts_roofline", run) == pytest.approx(
        100 * (nbytes / 819e9) / (4 * 1200e-6))
    skipped = 4 * 16 * 37_748_736 + 19200 * 6144
    dense = 2 * (kexaone.n_params(cfg) - skipped + 32 * 6144)
    assert kexaone.moe_tick_bytes(cfg, 32, 50, 16 * 16500) == (
        dense + 50 * 75_497_472 + 16 * 16500 * 4096)
    assert 70 < read("moe_tick_roofline", run) < 100
    # the pager's counters, through the engine the adapter built
    held = [0, 0, 40, 900, 60] + [0] * 59
    monkeypatch.setattr(kexaone, "last_engine", _Engine(
        {"blocks_held": held, "tail_lookups": 200, "tail_hits": 199}))
    assert read("window_blocks_per_slot_p50", run) == 3.0
    assert read("window_tail_hit_share", run) == 99.5
    held[3], held[62] = 0, 5000          # the release lost: hundreds held
    assert read("window_blocks_per_slot_p50", run) == 62.0


def test_new_readers_return_none_where_there_is_nothing_to_read(
        cell, monkeypatch):
    monkeypatch.setattr(kexaone, "last_engine", None)
    old = [_span(prefill=0, kv_blocks=12, experts_touched=3),   # the parent's
           _span("engine/admit", pool_used=3)]
    for run in (_run(cell, old, _Trace(1e-4, 1e-3, 1e-3, 2e-2)),
                _run(cell, [], None)):
        for name in NEW:
            assert harness.load_module("metrics", name).read(run) is None
    # an engine without a window pool; counters that counted nothing
    for window in (None, {"blocks_held": [0] * 64, "tail_lookups": 0,
                          "tail_hits": 0}):
        monkeypatch.setattr(kexaone, "last_engine", _Engine(window))
        for name in NEW[1:]:
            assert harness.load_module("metrics", name).read(
                _run(cell, old)) is None
    # the attr without the kernel in the trace: the share stays out
    ticks = [_span(prefill=0, active=3, experts_touched=3, window_rows=300)]
    bare = _Trace(1e-4, 1e-3, 1e-3, 2e-2)
    bare.devices[0].ops = []
    assert harness.load_module("metrics", "window_decode_roofline").read(
        _run(cell, ticks, bare)) is None
    # another configuration's adapter: no counts, no engine kept
    lfm2 = harness.Cell("lfm2-8b-a1b_serve_assistant")
    for name in NEW:
        assert harness.load_module("metrics", name).read(
            _run(lfm2, ticks, _Trace(1e-4, 1e-3, 1e-3, 2e-2))) is None


# -- the committed comparison at a tiny size, through the harness itself ------

def _rehearse(tmp, tool, *args):
    cmd = [sys.executable,
           os.path.join(HERE, "tests", "rehearse_kexaone.py"), str(tmp),
           tool, "--", "--workload", "tiny_long_sessions_serve", *args]
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    return subprocess.run(cmd, capture_output=True, text=True, timeout=900,
                          env=env)


def test_the_tiny_cell_traced_reports_the_new_readers(tmp_path):
    p = _rehearse(tmp_path, "run", "--seed", str(2 ** 31 + 5), "--seconds",
                  "1.5", "--trace", "1")
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["metrics"]["window_tail_hit_share"]["value"] == 100.0
    assert 2 <= line["metrics"]["window_blocks_per_slot_p50"]["value"] <= 4
    assert {"experts_touched_p50", "expert_load_max_over_mean",
            "tick_kv_blocks_p50"} <= set(line["metrics"])
    # a CPU gives no device trace: a kernel's share is never written there
    assert not {"window_decode_roofline", "gqa_decode_roofline",
                "moe_tick_roofline"} & set(line["metrics"])
    value, limit = (line["checks"]["worst_logit_gap"][k]
                    for k in ("value", "limit"))
    assert limit == harness.Cell(CELL).spec["logit_gap_tol"] and value < limit


@pytest.mark.parametrize("seed", [2 ** 31 + 7, 4500000011])
def test_the_control_and_the_planted_faults_fail_the_loops_own_check(
        tmp_path, seed):
    p = _rehearse(tmp_path, "witness", "--seed", str(seed), "--seconds",
                  "1.5", "--requests", "6", "--control", "--faults",
                  "window_ignored,rope_on_full", "--out",
                  str(tmp_path / "witness"))
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    reads = {ln.split(": ", 1)[0]: json.loads(ln.split(": ", 1)[1])
             for ln in p.stdout.splitlines()
             if ln.startswith(("clean: {", "control: {", "fault:"))
             and ln.split(": ", 1)[1].startswith("{")}
    assert reads["clean"]["passes"]
    for tag in ("control", "fault:window_ignored", "fault:rope_on_full"):
        assert not reads[tag]["passes"], tag
        assert reads["clean"]["worst_logit_gap"] < reads["clean"]["limit"] \
            < reads[tag]["worst_logit_gap"]
