"""The cell axk1-ep16_serve_docqa: its files load, its mix sends what the
issue fixed, the adapter counts what the arithmetic says, and each new reader
reads its attr, counter or kernel (and nothing where there is none)."""

import json
import os
import types

import numpy as np
import pytest

from benchmark import harness, kernel_ops, traffic
from benchmark.models import axk1

CELL = "axk1-ep16_serve_docqa"
HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
         "hbm_bytes": 16e9}


@pytest.fixture(scope="module")
def cell():
    return harness.Cell(CELL)


def test_the_cells_files_load_and_name_each_other(cell):
    assert cell.chips == 1 and cell.adapter is axk1
    assert cell.spec["engine"] == {"class": "PagedKVEngine", "n_slots": 32,
                                   "block_size": 64, "n_blocks": 2048,
                                   "max_len": 17408}
    assert cell.spec["check_requests"] == 2 and cell.spec["loop"] == "serve"
    assert cell.traffic["name"] == "serve_docqa"
    e2e = {m["name"] for m in cell.metrics["end_to_end"]}
    assert {"tpot_p50_ms", "ttft_p50_ms", "setup_s"} <= e2e
    layers = {m["name"] for m in cell.metrics["per_layer"]}
    assert {"moe_tick_roofline", "mla_decode_roofline", "moe_experts_roofline",
            "experts_touched_p50", "expert_load_max_over_mean",
            "tick_device_ms_p50", "prefix_hit_rate"} <= layers
    assert "window_stolen_ms" not in layers and "decode_tick_roofline" not in layers
    for m in cell.metrics["per_layer"] + cell.metrics["end_to_end"]:
        reader = harness.load_module("metrics", m["name"])
        assert (reader.UNIT, reader.SOURCE) == (m["unit"], m["source"])


def test_the_configuration_holds_every_published_number(cell):
    cfg = cell.config
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            pub = next(json.loads(line) for line in f
                       if json.loads(line)["name"] == "A.X-K1")["config"]
        differs = {k for k, v in pub.items() if cfg.get(k) != v}
        assert differs == {"num_hidden_layers", "n_routed_experts",
                           "vocab_size"}
        assert differs <= set(cfg["reduced"])
        assert {k: cfg["published"][k] for k in differs} == \
            {k: pub[k] for k in differs}
    assert cfg["reduced"][:3] == ["num_layers", "n_routed_experts", "vocab"]
    assert cfg["num_layers"] == cfg["num_hidden_layers"] == 7
    assert cfg["vocab"] == cfg["vocab_size"] == 20480
    assert (cfg["n_routed_experts"], cfg["router_width"]) == (12, 192)
    assert all(cfg[k] == "bfloat16" for k in ("weights_dtype", "matmul_dtype",
                                              "cache_dtype"))
    spec = axk1.spec_of(cfg)
    assert spec.moe.held == tuple(range(12)) and spec.moe_layers == tuple(range(1, 7))
    assert spec.latent.row_lanes == 640
    assert spec.latent.softmax_scale == pytest.approx(192 ** -0.5 * 1.8133, rel=1e-4)
    assert spec.cache_row_bytes() == 7 * 640 * 2


def test_the_cut_is_the_issues_arithmetic(cell):
    cfg = cell.config
    shapes = axk1.param_shapes(cfg)
    per = lambda pred: sum(int(np.prod(s)) for n, (s, _) in shapes.items()
                           if pred(n))
    assert per(lambda n: n.startswith("l1_attn") and n.endswith("w_0")) == \
        7168 * 1536 + 1536 * 12288 + 7168 * 576 + 512 * 16384 + 8192 * 7168
    assert per(lambda n: n.startswith("l1_moe_experts")) == 12 * 3 * 7168 * 2048
    assert per(lambda n: n.startswith("l0_ffn")) == 3 * 7168 * 18432
    total = axk1.n_params(cfg)
    assert 4.83e9 < total < 4.85e9            # 1 dense + 6 routed layers
    assert axk1.expert_bytes(cfg) == 3 * 7168 * 2048 * 2     # 88.1 MB
    assert axk1.latent_row_bytes(cfg) == 1152
    dense = axk1.dense_tick_bytes(cfg, 32)
    assert dense == 2 * (total - 6 * 12 * 3 * 7168 * 2048
                         - 20480 * 7168 + 32 * 7168)
    assert 2.9e9 < dense < 3.3e9
    assert axk1.moe_tick_bytes(cfg, 32, 36, 400000) == \
        dense + 36 * axk1.expert_bytes(cfg) + 400000 * 7 * 1152
    flops, nbytes = axk1.mla_call(cfg, 1, 400000)
    assert flops == 2 * 64 * (576 + 512) * 400000 and nbytes == 400000 * 1152
    assert flops / nbytes == pytest.approx(120.9, abs=0.1)
    flops, nbytes = axk1.experts_call(cfg, 32, 36, 100)
    assert flops == 100 * 6 * 7168 * 2048
    assert nbytes == 36 * axk1.expert_bytes(cfg) + 6 * 32 * 7168 * 6


def test_docqa_sends_what_the_issue_fixed(cell):
    mix = cell.traffic
    assert mix["system_prompts"] == {"count": 4, "tokens": 16384,
                                     "popularity": {"dist": "zipf",
                                                    "exponent": 1.0}}
    assert mix["arrivals"] == {"process": "uniform_order_statistics",
                               "burst_size": 1}
    assert mix["user_tokens"] == {"dist": "lognormal_quantiles", "median": 64,
                                  "sigma": 0.8, "min": 16, "max": 512}
    assert mix["output_tokens"] == {"dist": "lognormal_quantiles",
                                    "median": 96, "sigma": 0.7, "min": 16,
                                    "max": 512}
    assert mix["drain_deadline_s"] == 60 and mix["pairing"] == "golden_stride"
    assert isinstance(mix["rate_per_s"], (int, float))
    load = traffic.open_loop_requests(mix, 3000000001, 45, cell.config["vocab"])
    n = len(load["requests"])
    assert n == round(mix["rate_per_s"] * 45)
    assert [len(p) for p in load["system_prompts"]] == [16384] * 4
    shared = traffic.zipf_counts(n, 4, 1.0)
    assert sorted(np.bincount([r["system"] for r in load["requests"]],
                              minlength=4).tolist()) == sorted(shared.tolist())
    for r in load["requests"]:
        assert r["prompt"][:16384] == load["system_prompts"][r["system"]]
        assert 16 <= r["user_len"] <= 512 and 16 <= r["max_new"] <= 512
        assert len(r["prompt"]) + r["max_new"] <= cell.spec["engine"]["max_len"]
        assert max(r["prompt"][-r["user_len"]:]) < cell.config["vocab"]
    # the pool holds the documents and every slot's private blocks
    eng = cell.spec["engine"]
    private = -(-1024 // eng["block_size"])
    assert 4 * 256 + eng["n_slots"] * private + 1 <= eng["n_blocks"]


def _span(**attrs):
    return types.SimpleNamespace(name="engine/tick", attrs=attrs,
                                 duration_ms=1.0)


class _Trace:
    """A trace with one decode-tick program run three times: 7 latent reads
    and 6 expert products an execution."""
    def __init__(self, mla_s, moe_s, busy_s):
        mla = kernel_ops.kernel_key("latent_paged_attention", "bfloat16",
                                    (32, 64, 512))
        moe = kernel_ops.kernel_key("moe_experts", "float32", (32, 7168))
        ops, modules, t = [], [], 0.0
        for _ in range(3):
            start = t
            for key, n, dur in ((mla, 7, mla_s), (moe, 6, moe_s)):
                for _ in range(n):
                    ops.append((t, t + dur, key, "custom-call", True))
                    t += dur
            ops.append((t, t + 1e-3, "fusion_f32_8_", "fusion", False))
            t += 1e-3
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
                                 counters={}, device={"peaks": PEAKS})


def test_new_readers_read_their_attrs_and_kernels(cell):
    ticks = [_span(prefill=0, experts_touched=e, routed_rows=r, decode_rows=d,
                   expert_rows=[1, 3] + [0] * 70)
             for e, r, d in ((30, 100, 390000), (36, 128, 400000),
                             (40, 150, 410000))]
    mixed = _span(prefill=1, experts_touched=72, routed_rows=999,
                  decode_rows=5, expert_rows=[0, 4] + [0] * 70)
    read = lambda name, run: harness.load_module("metrics", name).read(run)
    run = _run(cell, ticks + [mixed], _Trace(mla_s=1e-3, moe_s=1e-3,
                                             busy_s=20e-3))
    assert read("experts_touched_p50", run) == 36       # decode ticks only
    # rows 1+1+1+0 = 3 and 3+3+3+4 = 13 of 72 counters: 13 / (16 / 72)
    assert read("expert_load_max_over_mean", run) == pytest.approx(13 / (16 / 72))
    cfg = cell.config
    least = axk1.moe_tick_bytes(cfg, 32, 36, 400000) / 819e9
    assert read("moe_tick_roofline", run) == pytest.approx(100 * least / 20e-3)
    assert 50 < read("moe_tick_roofline", run) < 100
    # one latent read: 400000 rows x 1152 B at 819 GB/s = 0.563 ms (the
    # operations need 0.283 ms); it took 1 ms
    assert read("mla_decode_roofline", run) == pytest.approx(
        100 * 400000 * 1152 / 819e9 / 1e-3)
    flops, nbytes = axk1.experts_call(cfg, 32, 36, 128)
    assert read("moe_experts_roofline", run) == pytest.approx(
        100 * max(flops / 197e12, nbytes / 819e9) / 6e-3)


def test_new_readers_return_none_where_there_is_nothing_to_read(cell):
    names = ("experts_touched_p50", "expert_load_max_over_mean",
             "moe_tick_roofline", "mla_decode_roofline",
             "moe_experts_roofline")
    old = [_span(prefill=0, kv_blocks=12)]            # the parent's tick span
    for run in (_run(cell, old, _Trace(1e-3, 1e-3, 2e-2)),
                _run(cell, [], None)):
        for name in names:
            assert harness.load_module("metrics", name).read(run) is None
    # the attrs without the kernels in the trace: the counts read, the
    # kernels' shares stay out
    ticks = [_span(prefill=0, experts_touched=3, routed_rows=4,
                   decode_rows=9, expert_rows=[1] * 72)]
    bare = _Trace(1e-3, 1e-3, 2e-2)
    bare.devices[0].ops = [o for o in bare.devices[0].ops if not o[4]]
    run = _run(cell, ticks, bare)
    assert harness.load_module("metrics", "mla_decode_roofline").read(run) is None
    assert harness.load_module("metrics", "moe_experts_roofline").read(run) is None
    assert harness.load_module("metrics", "experts_touched_p50").read(run) == 3
    assert harness.load_module("metrics", "expert_load_max_over_mean").read(run) == 1.0


def test_kernel_ops_finds_a_kernel_by_its_result():
    assert kernel_ops.kernel_key("latent_paged_attention", "bfloat16",
                                 (32, 64, 512)) == \
        "latent_paged_attention_custom-call_bf16_32_64_512_"
    trace = _Trace(2e-3, 1e-3, 2e-2)
    got = kernel_ops.per_execution_seconds(
        trace, kernel_ops.kernel_key("latent_paged_attention", "bfloat16",
                                     (32, 64, 512)))
    assert [n for _, n in got] == [7, 7, 7]
    assert [t for t, _ in got] == pytest.approx([14e-3] * 3)
    assert kernel_ops.per_execution_seconds(trace, "custom-call_f32_1_") == []
    assert kernel_ops.per_execution_seconds(None, "x") == []


# -- the committed comparison at a tiny size, through the harness itself ------

def _rehearse(tmp, tool, *args):
    import subprocess
    import sys
    cmd = [sys.executable, os.path.join(HERE, "tests", "rehearse_axk1.py"),
           str(tmp), tool, "--", "--workload", "tiny_docqa_serve", *args]
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    return subprocess.run(cmd, capture_output=True, text=True, timeout=900,
                          env=env)


def test_the_tiny_cell_runs_through_run_py_and_reports_the_cells_metrics(
        tmp_path):
    p = _rehearse(tmp_path, "run", "--seed", str(2 ** 31 + 5), "--seconds",
                  "1.5", "--trace", "0")
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert {"tpot_p50_ms", "ttft_p50_ms", "setup_s"} <= set(line["metrics"])
    value, limit = (line["checks"]["worst_logit_gap"][k]
                    for k in ("value", "limit"))
    assert limit == harness.Cell(CELL).spec["logit_gap_tol"] and value < limit


@pytest.mark.parametrize("seed", [2 ** 31 + 7, 3000000011])
def test_the_control_fails_the_loops_own_check_where_the_cell_passes_it(
        tmp_path, seed):
    """benchmark/control.py: the loop's `_check` on the same requests, with
    the reference one precision below; exit 0 only if the cell passes and
    the control fails."""
    p = _rehearse(tmp_path, "control", "--seed", str(seed), "--seconds",
                  "1.5", "--requests", "4")
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    reads = {l.split(": ", 1)[0]: json.loads(l.split(": ", 1)[1])
             for l in p.stdout.splitlines()
             if l.startswith(("clean: ", "control: "))}
    assert reads["clean"]["passes"] and not reads["control"]["passes"]
    assert reads["clean"]["worst_logit_gap"] < reads["clean"]["limit"] \
        < reads["control"]["worst_logit_gap"]


def test_the_engines_own_peak_is_what_the_adapter_noted():
    reader = harness.load_module("metrics", "serve_engine_peak_hbm_gb")
    run = types.SimpleNamespace(cell=types.SimpleNamespace(
        adapter=types.SimpleNamespace(peak_before_reference=11_650_000_000)))
    assert reader.read(run) == pytest.approx(11.65)
    run.cell.adapter = types.SimpleNamespace()
    assert reader.read(run) is None
