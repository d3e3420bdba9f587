"""The cell mellum2-ep4_train_8k_1chip: its files load, its mix sends what the
issue fixed, the adapter counts what the arithmetic says, each new reader
reads its kernels or counters (and nothing where there are none), and the
committed comparison holds at a tiny size through the harness itself and
through the builder's tool (clean passes, the control and the planted faults
fail)."""

import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from benchmark import harness, traffic
from benchmark.models import mellum

CELL = "mellum2-ep4_train_8k_1chip"
HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
         "hbm_bytes": 16e9}
NEW = ("window_flash_roofline", "gqa_flash_roofline",
       "moe_train_experts_roofline", "train_expert_rows_max_over_mean",
       "routed_pairs_held_share")


@pytest.fixture(scope="module")
def cell():
    return harness.Cell(CELL)


def test_the_cells_files_load_and_name_each_other(cell):
    assert cell.chips == 1 and cell.adapter is mellum
    assert (cell.spec["loop"], cell.spec["executor"]) == ("train", "Executor")
    assert cell.spec["steps_ahead"] == 30
    assert 0 < cell.spec["loss_rel_tol"] < 1
    # no limit that no run enforces
    assert not [k for k in cell.spec if k.endswith("_tol")
                and k != "loss_rel_tol"]
    mix = cell.traffic
    assert (mix["name"], mix["kind"]) == ("train_code_8k_1chip",
                                          "train_tokens")
    assert (mix["batch_per_chip"], mix["seq_len"]) == (1, 8192)
    assert mix["ring"] == 8
    assert mix["optimizer"] == {"type": "adam", "learning_rate": 1e-4}
    e2e = {m["name"] for m in cell.metrics["end_to_end"]}
    assert e2e == {"train_tokens_per_s", "setup_s"}
    layers = {m["name"] for m in cell.metrics["per_layer"]}
    assert layers == set(NEW) | {
        "feed_ms_p50", "step_ms_p50", "step_stall_share",
        "step_device_ms_p50", "mfu", "train_device_idle_share",
        "train_peak_hbm_gb", "step_host_ms_p50", "step_lookup_ms_p50",
        "step_launch_ms_p50", "step_untraced_ms_p50"}
    for m in cell.metrics["per_layer"] + cell.metrics["end_to_end"]:
        reader = harness.load_module("metrics", m["name"])
        assert (reader.UNIT, reader.SOURCE) == (m["unit"], m["source"])
        if m["name"] in NEW:
            assert (reader.LAYER, reader.MOVES) == (m["layer"], m["moves"])
            assert m["workloads"] == [CELL]


def test_the_mix_makes_full_rows_of_the_vocabularys_slice(cell):
    ring = traffic.train_batches(dict(cell.traffic, seq_len=64, ring=2),
                                 2 ** 31 + 9, 1, mellum.vocabs(cell.config))
    assert len(ring) == 2 and ring[0]["tokens"] == 64
    feed = ring[0]["feed"]
    assert feed["tokens"].shape == feed["targets"].shape == (1, 64)
    assert (feed["tokens"][0, 1:] == feed["targets"][0, :-1]).all()
    assert 0 <= feed["tokens"].min() and feed["tokens"].max() < 24576


def test_the_configuration_holds_every_published_number(cell):
    cfg = cell.config
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    reduced = {"num_hidden_layers", "layer_types", "mlp_layer_types",
               "num_experts", "vocab_size"}
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(json.loads(line) for line in f if json.loads(line)[
                "name"] == "Mellum2-12B-A2.5B-Instruct")
        pub = row["config"]
        assert cfg["source"] == row["source_url"]
        differs = {k for k, v in pub.items() if cfg.get(k) != v}
        assert differs == reduced and differs <= set(cfg["reduced"])
        assert {k: cfg["published"][k] for k in differs} == \
            {k: pub[k] for k in differs}
        for key in ("layer_types", "mlp_layer_types"):
            assert cfg[key] == pub[key][:4]
    assert set(cfg["reduced"]) == reduced | {"num_layers", "vocab"}
    assert cfg["num_layers"] == cfg["num_hidden_layers"] == 4
    assert cfg["layer_types"] == ["sliding_attention"] * 3 + ["full_attention"]
    assert cfg["vocab"] == cfg["vocab_size"] == 24576 == 192 * 128
    assert cfg["vocab"] * 4 == 98304
    assert (cfg["num_experts"], cfg["router_width"], cfg["expert_rank"]) == \
        (16, 64, 0)
    assert mellum.held_experts(cfg) == list(range(16))
    for key in ("block", "qk_norm", "rotation", "routing", "aux_coef", "init",
                "qk_init_gain", "precision", "max_len"):
        assert cfg["assumed"][key]
    assert "four chips share each layer" in cfg["deployment"]
    assert "rank 0" in cfg["deployment"]
    assert any("multi-token-prediction" in x for x in cfg["left_out"])
    assert any("exchange" in x for x in cfg["left_out"])
    assert cfg["max_position_embeddings"] == 131072     # carried, unread
    spec = mellum.spec_of(cfg)
    assert (spec.window, spec.d_head, spec.kv_heads) == (1024, 128, 4)
    assert spec.num_heads * spec.d_head == 4096 > spec.d_model == 2304
    assert spec.moe.held == tuple(range(16)) and spec.moe.n_routed == 64
    assert (spec.moe.scoring, spec.moe.n_shared, spec.moe.top_k) == \
        ("softmax", 0, 8)
    assert spec.moe.aux_coef == cfg["aux_coef"] == 0.001
    assert not spec.tied_head and not spec.qk_norm and spec.dtype == "float32"
    assert [spec.rope_of(i).factor for i in range(4)] == [1.0] * 3 + [16.0]
    assert spec.rope_of(3).original_max == 8192
    assert spec.rope_of(3).table_scale == pytest.approx(1.2772588722239782)


def test_the_cut_is_the_issues_arithmetic(cell):
    cfg = cell.config
    H, F, V = 2304, 896, 24576
    attn = H * 4096 + 2 * H * 512 + 4096 * H
    expert = 3 * H * F
    assert round(attn / 1e6, 2) == 21.23 and round(expert / 1e6, 2) == 6.19
    layer = attn + H * 64 + 16 * expert + 2 * H
    assert round(layer / 1e6, 1) == 120.5
    total = 4 * layer + 2 * V * H + H
    names = mellum.param_names(cfg)
    assert len(names) == 3 + 4 * 10 and names[0] == "tok_emb"
    assert 594e6 < total < 596e6
    assert round(16 * total / 1e9, 2) == 9.52       # of 16 GB: 59%
    # whole (64 experts) one layer is 6.7 GB trained: a chip holds two
    assert round(16 * (attn + 64 * expert) / 1e9, 1) == 6.7


def test_the_adapter_counts_live_pairs_and_held_pairs(cell):
    cfg, mix = cell.config, cell.traffic
    T = mix["seq_len"]
    assert mellum.live_pairs(T, 0) == T * (T + 1) / 2
    assert round(mellum.live_pairs(T, 1024) / 1e6, 2) == 7.86
    assert round(mellum.live_pairs(T, 1024) / mellum.live_pairs(T, 0), 2) \
        == 0.23
    assert mellum.live_pairs(512, 1024) == mellum.live_pairs(512, 0)
    assert mellum.held_pairs_expected(cfg, T) == 16384
    batch = {"feed": {"tokens": np.zeros((1, T), "int64")}}
    flops = mellum.train_flops(cfg, mix, batch)
    dense = 6 * T * (4 * (2 * 2304 * 4096 + 2 * 2304 * 512 + 2304 * 64)
                     + 2304 * 24576)
    experts = 4 * 6 * 16384 * 3 * 2304 * 896
    assert round((dense + experts) / 1e12, 1) == 9.4
    window = 6 * 2 * 32 * mellum.live_pairs(T, 1024) * 128
    full = 6 * 2 * 32 * mellum.live_pairs(T, 0) * 128
    assert round(window / 1e12, 2) == 0.39 and round(full / 1e12, 2) == 1.65
    assert flops == pytest.approx(dense + experts + 3 * window + full)
    calls = mellum.flash_calls(cfg, mix, 1, "window")
    assert len(calls) == 6 and len(mellum.flash_calls(cfg, mix, 1,
                                                      "full")) == 2
    assert sum(f for f, _ in calls) == pytest.approx(3 * window)
    # K and V once a group: 4 heads' bytes, not 32
    q = 32 * T * 128 * 2
    kv = 4 * T * 128 * 2
    assert calls[0][1] == 2 * q + 2 * kv + 32 * T * 4
    f, b = mellum.experts_train_call(cfg, 16384)
    assert f == 6 * 16384 * 3 * 2304 * 896
    assert b == 3 * 16 * 3 * 2304 * 896 * 2 + 6 * 16384 * 2304 * 2


def _trace(ops, step=(0.0, 1.0)):
    dev = types.SimpleNamespace(
        ops=[(s, e, k, "custom-call", True) for s, e, k in ops],
        modules=[(step[0], step[1], "jit_step", 1)])
    return types.SimpleNamespace(devices=[dev],
                                 main_module=lambda: "jit_step")


def _run(cell, trace, counters):
    adapter = types.SimpleNamespace(**{
        n: getattr(mellum, n) for n in (
            "flash_calls", "is_flash", "kernel_seconds", "is_grouped_product",
            "experts_train_call")})
    adapter.counters = lambda cfg: counters
    fake = types.SimpleNamespace(spec=cell.spec, config=cell.config,
                                 traffic=cell.traffic, adapter=adapter)
    return types.SimpleNamespace(cell=fake, trace=trace,
                                 device={"peaks": PEAKS})


def test_each_reader_reads_its_kernels_and_counters(cell):
    tail = "_custom-call_bf16_32_8192_128_"
    ops = [(0.00, 0.01, "flash_fwd_streamed_q512_k512_g8_w1024" + tail),
           (0.01, 0.03, "flash_bwd_dkv_streamed_q512_k512_g8_w1024" + tail),
           (0.03, 0.05, "flash_fwd_streamed_q1024_k1024_g8" + tail),
           (0.05, 0.06, "gmm_custom-call_bf16_20480_1792_"),
           (0.06, 0.08, "tgmm_custom-call_bf16_16_2304_1792_"),
           (0.08, 0.09, "fusion_bf16_20480_2304_")]
    counters = {"rows": np.array([[1024] * 15 + [2048]] * 4),
                # a run's worth: a step's pairs times these passes 2**31
                "pairs": np.array([[65536 * 300, 16384 * 300, 0]] * 4,
                                  np.int32),
                "aux": np.ones((4, 1))}
    run = _run(cell, _trace(ops), counters)
    peak = PEAKS["bf16_flops_per_s"]
    window = 3 * 6 * 2 * 32 * mellum.live_pairs(8192, 1024) * 128 / peak
    full = 6 * 2 * 32 * mellum.live_pairs(8192, 0) * 128 / peak
    read = {n: harness.load_module("metrics", n).read(run) for n in NEW}
    assert read["window_flash_roofline"] == pytest.approx(
        100 * window / 0.03)
    assert read["gqa_flash_roofline"] == pytest.approx(100 * full / 0.02)
    assert read["moe_train_experts_roofline"] == pytest.approx(
        100 * 4 * 6 * 16384 * 3 * 2304 * 896 / peak / 0.03)
    assert read["train_expert_rows_max_over_mean"] == pytest.approx(
        2048 / (17 * 1024 / 16))
    assert read["routed_pairs_held_share"] == pytest.approx(25.0)


def test_a_program_without_them_leaves_the_metrics_out(cell):
    # the parent's side of a traced run: no such kernels, no counters
    run = _run(cell, _trace([(0.0, 0.5, "fusion_f32_8_")]), None)
    for name in NEW:
        assert harness.load_module("metrics", name).read(run) is None
    bare = types.SimpleNamespace(
        cell=types.SimpleNamespace(adapter=types.SimpleNamespace(),
                                   config={}, traffic={}, spec={}),
        trace=_trace([]), device={"peaks": PEAKS})
    for name in NEW:
        assert harness.load_module("metrics", name).read(bare) is None


def _rehearse(tmp, what, *args, timeout=900):
    cmd = [sys.executable, os.path.join(HERE, "tests", "rehearse_mellum.py"),
           str(tmp), what, "--", *args]
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    return subprocess.run(cmd, capture_output=True, text=True,
                          timeout=timeout, env=env)


def test_the_tiny_stand_in_runs_correct_and_reads_its_counters(tmp_path):
    p = _rehearse(tmp_path, "run", "--workload", "tiny_code_train", "--seed",
                  str(2 ** 31 + 11), "--seconds", "1.0", "--trace", "1")
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    got = line["metrics"]
    assert {"mfu", "step_ms_p50", "train_expert_rows_max_over_mean",
            "routed_pairs_held_share"} <= set(got)
    # two ranks of four under a seeded router: about a half, never all
    assert 30 < got["routed_pairs_held_share"]["value"] < 70
    assert got["train_expert_rows_max_over_mean"]["value"] >= 1.0
    p = _rehearse(tmp_path, "run", "--workload", "tiny_code_train", "--seed",
                  "5", "--seconds", "0.6", "--trace", "0")
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert {"train_tokens_per_s", "setup_s"} == set(line["metrics"])
    assert line["correct"] is True


def test_the_builders_tool_refuses_the_control_and_the_planted_faults(
        tmp_path):
    """benchmark/train_witness.py on the tiny stand-in, under the committed
    tolerances: the clean reading passes, the control (every matmul one
    precision down) and each planted fault fail one."""
    out = os.path.join(str(tmp_path), "witness")
    p = _rehearse(tmp_path / "tree", "train_witness", "--workload",
                  "tiny_code_train", "--seed", "7", "--update", "--control",
                  "--faults", ",".join(mellum.FAULTS), "--out", out)
    assert p.returncode == 0, (p.stdout[-3000:], p.stderr[-3000:])
    record = json.load(open(out + "_7.json"))
    assert record["clean"]["passes"] and not record["clean"]["fails_by"]
    for tag in ["control"] + ["fault:" + f for f in mellum.FAULTS]:
        assert not record[tag]["passes"] and record[tag]["fails_by"], tag
    # one Adam step: the program's change of every group is the reference's
    assert record["update"]["passes"] and record["update"]["worst"] < 1e-3
