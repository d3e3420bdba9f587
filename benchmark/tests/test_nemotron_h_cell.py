"""The cell nemotron3-super-ep4_serve_chat_bursts: its files load, its mix
sends what the issue fixed, the adapter counts what the arithmetic says, each
new reader reads its kernel (and nothing where there is none), and the
committed comparison holds at a tiny size through the harness itself."""

import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from benchmark import harness, kernel_ops, traffic
from benchmark.models import nemotron_h

CELL = "nemotron3-super-ep4_serve_chat_bursts"
HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
         "hbm_bytes": 16e9}


@pytest.fixture(scope="module")
def cell():
    return harness.Cell(CELL)


def test_the_cells_files_load_and_name_each_other(cell):
    assert cell.chips == 1 and cell.adapter is nemotron_h
    assert cell.spec["engine"] == {"class": "PagedKVEngine", "n_slots": 64,
                                   "block_size": 64, "n_blocks": 2048,
                                   "max_len": 1536, "n_snapshots": 32}
    assert cell.spec["check_requests"] == 2 and cell.spec["loop"] == "serve"
    assert cell.traffic["name"] == "serve_chat_bursts"
    e2e = {m["name"] for m in cell.metrics["end_to_end"]}
    assert {"tpot_p50_ms", "setup_s"} <= e2e
    layers = {m["name"] for m in cell.metrics["per_layer"]}
    assert {"ssm_decode_roofline", "latent_experts_roofline",
            "moe_tick_roofline", "conv_state_restore_share",
            "experts_touched_p50", "expert_load_max_over_mean",
            "serve_engine_peak_hbm_gb", "tick_kv_blocks_p50"} <= layers
    # their readers key a call this program does not make (8 query rows a
    # key/value head; an expert product whose rows are hidden_size wide)
    assert not {"gqa_decode_roofline", "moe_experts_roofline",
                "mla_decode_roofline", "decode_tick_roofline"} & layers
    for m in cell.metrics["per_layer"] + cell.metrics["end_to_end"]:
        reader = harness.load_module("metrics", m["name"])
        assert (reader.UNIT, reader.SOURCE) == (m["unit"], m["source"])
        if m["name"] in ("ssm_decode_roofline", "latent_experts_roofline"):
            assert (reader.LAYER, reader.MOVES) == (m["layer"], m["moves"])
            assert m["workloads"] == [CELL]


def test_the_configuration_holds_every_published_number(cell):
    cfg = cell.config
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    reduced = {"num_hidden_layers", "hybrid_override_pattern",
               "n_routed_experts", "vocab_size"}
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(json.loads(line) for line in f if json.loads(line)[
                "name"] == "NVIDIA-Nemotron-3-Super-120B-A12B-BF16")
        pub = row["config"]
        assert cfg["source"] == row["source_url"]
        differs = {k for k, v in pub.items() if cfg.get(k) != v}
        assert differs == reduced and differs <= set(cfg["reduced"])
        assert {k: cfg["published"][k] for k in differs} == \
            {k: pub[k] for k in differs}
        assert cfg["hybrid_override_pattern"] == \
            pub["hybrid_override_pattern"][:11]
    assert set(cfg["reduced"]) == reduced | {"num_layers", "vocab"}
    assert cfg["num_layers"] == cfg["num_hidden_layers"] == 11
    assert cfg["vocab"] == cfg["vocab_size"] == 32768
    assert cfg["n_routed_experts"] == 128 and cfg["router_width"] == 512
    # a whole period: 5 mixers, 5 routed layers, 1 attention (40 : 40 : 8)
    kinds = nemotron_h.layer_kinds(cfg)
    assert [kinds.count(k) for k in ("ssm", "moe", "attention")] == [5, 5, 1]
    for key in ("attention", "block", "mixer", "state", "router", "experts",
                "init", "dt", "expert_bias", "precision", "max_len"):
        assert cfg["assumed"][key]
    assert "stage 0, rank 0" in cfg["deployment"]
    assert "multi_token_prediction" in cfg["left_out"]


def test_the_cut_is_the_issues_arithmetic(cell):
    cfg = cell.config
    H, Z, F, Fs = 4096, 1024, 2688, 5376
    expert = 2 * Z * F
    assert expert == 5_505_024 and nemotron_h.expert_bytes(cfg) == 2 * expert
    mixer = H * 18560 + 8192 * H + 10240 * 4 + 10240 + 3 * 128 + 8192
    attn = 2 * H * 4096 + 2 * H * 256
    routed = H * 512 + 512 + 2 * H * Z + 2 * H * Fs + 128 * expert
    want = 2 * 32768 * H + 5 * mixer + attn + 5 * routed + 11 * H + H
    assert nemotron_h.n_params(cfg) == want
    assert 4.64e9 < want < 4.66e9               # 9.30 GB at 2 bytes
    assert round(mixer / 1e6, 2) == 109.64 and round(attn / 1e6, 2) == 35.65
    assert round((routed - 128 * expert) / 1e6, 2) == 54.53
    eng = cell.spec["engine"]
    state = 5 * (128 * 64 * 128 * 4 + 3 * 10240 * 2)
    assert nemotron_h.spec_of(cfg).state_bytes() == state == 21_278_720
    kv = nemotron_h.kv_row_bytes(cfg) * eng["block_size"] * eng["n_blocks"]
    assert nemotron_h.kv_row_bytes(cfg) == 1024 and kv == 134_217_728
    resident = 2 * want + kv + state * (eng["n_slots"] + eng["n_snapshots"])
    assert 0.70 < resident / 16e9 < 0.74        # the floor is 25%
    # a snapshot a block, as the short convolutions keep them, would not fit
    assert state * eng["n_blocks"] > 43e9


def test_chat_bursts_sends_what_the_issue_fixed(cell):
    mix = cell.traffic
    assert mix["system_prompts"] == {"count": 4, "tokens": 512,
                                     "popularity": {"dist": "zipf",
                                                    "exponent": 1.0}}
    assert mix["user_tokens"] == {"dist": "lognormal_quantiles",
                                  "median": 64, "sigma": 0.8, "min": 8,
                                  "max": 512}
    assert mix["output_tokens"] == {"dist": "lognormal_quantiles",
                                    "median": 96, "sigma": 0.7, "min": 16,
                                    "max": 384}
    assert mix["schedule_seed"] == 43 and mix["arrivals"]["burst_size"] == 4
    assert mix["drain_deadline_s"] == 60 and mix["pairing"] == "golden_stride"
    assert isinstance(mix["rate_per_s"], (int, float))
    load = traffic.open_loop_requests(mix, 2 ** 31 + 3, 45.0,
                                      cell.config["vocab"])
    reqs = load["requests"]
    assert len(reqs) == round(mix["rate_per_s"] * 45)
    assert len(load["system_prompts"]) == 4
    again = traffic.open_loop_requests(mix, 7, 45.0, cell.config["vocab"])
    assert [(r["due"], r["user_len"], r["max_new"], r["system"])
            for r in reqs] == [(r["due"], r["user_len"], r["max_new"],
                                r["system"]) for r in again["requests"]]
    for r in reqs:
        assert r["prompt"][:512] == load["system_prompts"][r["system"]]
        assert 8 <= r["user_len"] <= 512 and 16 <= r["max_new"] <= 384
        assert len(r["prompt"]) + r["max_new"] <= cell.spec["engine"]["max_len"]
    # arrivals come four at a time
    due = [r["due"] for r in reqs]
    assert len(set(due)) == -(-len(reqs) // 4)
    assert cell.config["system_prompt_tokens"] == 512
    assert cell.config["typical_context_tokens"] == 512 + 64 + 96
    counts = np.bincount([r["system"] for r in reqs], minlength=4)
    assert counts.min() > 0 and counts[0] == counts.max()


def _span(name="engine/tick", **attrs):
    return types.SimpleNamespace(name=name, attrs=attrs, duration_ms=1.0)


class _Trace:
    """A trace with one decode-tick program run three times: 5 state updates
    and 5 latent expert products an execution."""
    def __init__(self, ssm_s, moe_s, busy_s):
        ssm = kernel_ops.kernel_key("ssm_decode_update", "float32",
                                    (64, 128, 1, 64))
        moe = kernel_ops.kernel_key("latent_experts", "float32", (64, 1024))
        ops, modules, t = [], [], 0.0
        for _ in range(3):
            start = t
            for key, dur in ((ssm, ssm_s), (moe, moe_s)):
                for _ in range(5):
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
    ticks = [_span(prefill=0, active=a, experts_touched=420, routed_rows=130,
                   decode_rows=16128, kv_blocks=260, expert_rows=[1] * 640)
             for a in (22, 24, 27)]
    mixed = _span(prefill=2, active=30, experts_touched=600, routed_rows=900,
                  decode_rows=5, kv_blocks=9999, expert_rows=[2] * 640)
    admits = [_span("engine/admit", state_restored=3, snapshots_used=7,
                    snapshot_evictions=0),
              _span("engine/admit", state_restored=1, snapshots_used=8,
                    snapshot_evictions=1)]
    run = _run(cell, ticks + [mixed] + admits,
               _Trace(ssm_s=260e-6, moe_s=1300e-6, busy_s=10e-3))
    run.requests = [{"shared_len": 512}] * 4
    # 24 live rows: 24 x 2 x 4.19 MB of state a mixer and the rows' small
    # inputs, five mixers: 1.007 GB at 819 GB/s = 1.23 ms; they took 1.3
    flops, nbytes = nemotron_h.ssm_decode_call(cfg, 24)
    row_io = 4 * (2 * 8192 + 2 * 1024 + 2 * 128)
    assert nbytes == 5 * 24 * (2 * 4_194_304 + row_io)
    assert flops == 5 * 24 * 6 * 128 * 64 * 128
    assert read("ssm_decode_roofline", run) == pytest.approx(
        100 * (nbytes / 819e9) / (5 * 260e-6))
    assert 90 < read("ssm_decode_roofline", run) < 100
    flops, nbytes = nemotron_h.experts_call(cfg, 64, 420, 130)
    assert nbytes == 420 * 11_010_048 + 5 * 64 * 1024 * 6
    assert flops == 130 * 4 * 1024 * 2688
    assert read("latent_experts_roofline", run) == pytest.approx(
        100 * (nbytes / 819e9) / (5 * 1300e-6))
    assert read("conv_state_restore_share", run) == 100.0
    # what a tick cannot avoid: everything but the routed experts and the
    # embedding, the touched experts, the live K/V, the live rows' state in
    # AND out (16,128 attended positions are 24 rows of 672)
    skipped = 5 * 128 * 5_505_024 + 32768 * 4096
    dense = 2 * (nemotron_h.n_params(cfg) - skipped)
    assert nemotron_h.moe_tick_bytes(cfg, 64, 420, 16128) == (
        dense + 420 * 11_010_048 + 16128 * 1024 + 2 * 24 * 5 * 4_194_304)
    least = nemotron_h.moe_tick_bytes(cfg, 64, 420, 16128) / 819e9
    assert read("moe_tick_roofline", run) == pytest.approx(100 * least / 10e-3)
    assert 80 < read("moe_tick_roofline", run) < 100


def test_new_readers_return_none_where_there_is_nothing_to_read(cell):
    names = ("ssm_decode_roofline", "latent_experts_roofline")
    old = [_span(prefill=0, kv_blocks=12),             # the parent's spans
           _span("engine/admit", pool_used=3)]
    for run in (_run(cell, old, _Trace(1e-4, 1e-3, 2e-2)),
                _run(cell, [], None)):
        for name in names:
            assert harness.load_module("metrics", name).read(run) is None
    # the attrs without the kernels in the trace: the shares stay out
    ticks = [_span(prefill=0, active=3, experts_touched=3, routed_rows=5)]
    bare = _Trace(1e-4, 1e-3, 2e-2)
    bare.devices[0].ops = []
    for name in names:
        assert harness.load_module("metrics", name).read(
            _run(cell, ticks, bare)) is None
    # another configuration's adapter and file: no counts, no latent width
    lfm2 = harness.Cell("lfm2-8b-a1b_serve_assistant")
    for name in names:
        assert harness.load_module("metrics", name).read(
            _run(lfm2, ticks, _Trace(1e-4, 1e-3, 2e-2))) is None


# -- the committed comparison at a tiny size, through the harness itself ------

def _rehearse(tmp, tool, *args):
    cmd = [sys.executable,
           os.path.join(HERE, "tests", "rehearse_nemotron_h.py"), str(tmp),
           tool, "--", "--workload", "tiny_chat_bursts_serve", *args]
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
    assert not {"ssm_decode_roofline", "latent_experts_roofline",
                "moe_tick_roofline"} & set(line["metrics"])
    value, limit = (line["checks"]["worst_logit_gap"][k]
                    for k in ("value", "limit"))
    assert limit == harness.Cell(CELL).spec["logit_gap_tol"] and value < limit


@pytest.mark.parametrize("seed", [2 ** 31 + 7, 4300000011])
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
