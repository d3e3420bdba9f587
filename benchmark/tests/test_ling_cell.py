"""The cell ling3-flash-ep4_serve_reasoning: its files load, its mix sends
what the issue fixed, the adapter counts what the arithmetic says, each new
reader reads its span or its trace (and nothing where there is none), and the
committed comparison holds at a tiny size through the harness itself."""

import json
import os
import subprocess
import sys
import types

import pytest

from benchmark import harness, kernel_ops, traffic
from benchmark.counts import roofline_min_seconds
from benchmark.models import ling

CELL = "ling3-flash-ep4_serve_reasoning"
HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
         "hbm_bytes": 16e9}
NEW = ("kda_decode_roofline", "router_held_picks_share",
       "experts_walk_roofline")


@pytest.fixture(scope="module")
def cell():
    return harness.Cell(CELL)


def test_the_cells_files_load_and_name_each_other(cell):
    assert cell.chips == 1 and cell.adapter is ling
    assert cell.spec["engine"] == {"class": "PagedKVEngine", "n_slots": 96,
                                   "block_size": 64, "n_blocks": 8192,
                                   "max_len": 5632, "n_snapshots": 32}
    assert cell.spec["check_requests"] == 2 and cell.spec["loop"] == "serve"
    assert cell.traffic["name"] == "serve_reasoning"
    e2e = {m["name"] for m in cell.metrics["end_to_end"]}
    assert e2e == {"tpot_p50_ms", "setup_s"}        # no first-token metric
    layers = {m["name"] for m in cell.metrics["per_layer"]}
    assert {"tick_ms_p50", "tick_device_ms_p50", "tick_kv_blocks_p50",
            "tpot_p90_ms", "serve_device_idle_share", "serve_peak_hbm_gb",
            "serve_engine_peak_hbm_gb", "experts_touched_p50", "expert_load_max_over_mean",
            "expert_runs_p50", "conv_state_restore_share", "state_rows_p50",
            "setup_compile_s", *NEW} <= layers
    # read against the window's median counts the two kernels' shares came
    # out at 154% and 146% in the builder's traced run (the traced phase
    # holds fewer live rows), and the whole tick's share has the same form:
    # the cell is on none of the three lists (PERF.md 7)
    assert not {"mla_decode_roofline", "moe_experts_roofline",
                "moe_tick_roofline", "ssm_decode_roofline", "hybrid_tick_roofline",
                "gqa_decode_roofline", "window_decode_roofline"} & layers
    for m in cell.metrics["per_layer"] + cell.metrics["end_to_end"]:
        reader = harness.load_module("metrics", m["name"])
        assert (reader.UNIT, reader.SOURCE) == (m["unit"], m["source"])
        if m["name"] in NEW:
            assert (reader.LAYER, reader.MOVES) == (m["layer"], m["moves"])
            assert m["workloads"] == [CELL] and m["moves"] == "tpot_p50_ms"


def test_the_configuration_holds_every_published_number(cell):
    cfg = cell.config
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    cut = {"num_hidden_layers", "first_k_dense_replace", "num_experts",
           "vocab_size", "expert_swiglu_limit_list",
           "share_expert_swiglu_limit_list"}
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(json.loads(line) for line in f if json.loads(line)[
                "name"] == "Ling-3.0-flash")
        pub = row["config"]
        assert cfg["source"] == row["source_url"]
        assert {k for k, v in pub.items() if cfg.get(k) != v} == cut
        for k in cut:
            assert cfg["published"][k] == pub[k]
        assert cfg["expert_swiglu_limit_list"] == pub[
            "expert_swiglu_limit_list"][:7] == [0] * 7
    assert set(cfg["reduced"]) == cut | {"num_layers", "vocab"}
    assert cfg["num_layers"] == cfg["num_hidden_layers"] == 7
    assert cfg["vocab"] == cfg["vocab_size"] == 39296 == 307 * 128
    assert cfg["router_width"] == 512 and cfg["num_experts"] == 128
    for key in ("layer_kinds", "kda", "latent", "gate", "rope_pairing",
                "router", "router_tie_margin", "unread_keys", "precision",
                "init", "expert_bias", "max_len", "check_rows"):
        assert cfg["assumed"][key]
    assert "stage 0" in cfg["deployment"] and "rank 0" in cfg["deployment"]
    assert "K K K K K M K" in cfg["reduced_note"]
    assert set(cfg["left_out"]) == {"multi_token_prediction",
                                    "clamped_expert_activations"}
    assert (cfg["weights_dtype"], cfg["cache_dtype"], cfg["state_dtype"]) == (
        "bfloat16", "bfloat16", "float32")


def test_the_cut_is_the_issues_arithmetic(cell):
    cfg = cell.config
    H, D = 2560, 4096
    kda = 3 * H * D + 3 * H * D + H * 32 + 3 * D * 4 + 32 + D + 128
    latent = H * 32 * 192 + H * 576 + 512 + 512 * 32 * 256 + H * 32 + D * H
    routed = H * 512 + 512 + 3 * H * 768 + 128 * 3 * H * 768
    dense = 3 * H * 6144
    assert round(kda / 1e6, 2) == 63.05 and round(latent / 1e6, 2) == 31.97
    assert round(routed / 1e6, 1) == 762.2 and round(dense / 1e6, 2) == 47.19
    want = (2 * 39296 * H + H + (kda + dense + 2 * H) + 5 * (kda + routed + 2 * H)
            + (latent + routed + 2 * H))
    assert ling.n_params(cfg) == want
    assert 10.45e9 < 2 * want < 10.48e9             # 10.46 GB at 2 bytes
    eng = cell.spec["engine"]
    spec = ling.spec_of(cfg)
    assert spec.state_bytes() == 6 * (2097152 + 3 * 12288 * 2) == 13_025_280
    pool = spec.cache_row_bytes() * eng["block_size"] * eng["n_blocks"]
    assert spec.cache_row_bytes() == 1280 and round(pool / 1e9, 2) == 0.67
    resident = 2 * want + pool + spec.state_bytes() * (
        eng["n_slots"] + eng["n_snapshots"])
    assert 0.79 < resident / 16e9 < 0.81            # the floor is 25%
    # every slot's longest span fits the pool beside the shared preambles
    assert eng["n_blocks"] >= eng["n_slots"] * (
        eng["max_len"] - 1024) // eng["block_size"] + 2 * 16 + 1


def test_reasoning_sends_what_the_issue_fixed(cell):
    mix = cell.traffic
    assert mix["system_prompts"] == {"count": 2, "tokens": 1024,
                                     "popularity": {"dist": "zipf",
                                                    "exponent": 1.0}}
    assert mix["user_tokens"] == {"dist": "lognormal_quantiles",
                                  "median": 192, "sigma": 0.8, "min": 32,
                                  "max": 1536}
    assert mix["output_tokens"] == {"dist": "lognormal_quantiles",
                                    "median": 1536, "sigma": 0.45, "min": 512,
                                    "max": 3072}
    assert mix["schedule_seed"] == 59 and mix["arrivals"]["burst_size"] == 1
    assert mix["drain_deadline_s"] == 60 and mix["pairing"] == "golden_stride"
    load = traffic.open_loop_requests(mix, 2 ** 31 + 3, 45.0,
                                      cell.config["vocab"])
    reqs = load["requests"]
    assert len(reqs) == round(mix["rate_per_s"] * 45)
    assert [len(p) for p in load["system_prompts"]] == [1024, 1024]
    again = traffic.open_loop_requests(mix, 7, 45.0, cell.config["vocab"])
    assert [(r["due"], r["user_len"], r["max_new"]) for r in reqs] == \
        [(r["due"], r["user_len"], r["max_new"]) for r in again["requests"]]
    for r in reqs:
        assert 1024 + 32 <= len(r["prompt"]) <= 1024 + 1536
        assert 512 <= r["max_new"] <= 3072
        assert len(r["prompt"]) + r["max_new"] <= cell.spec["engine"]["max_len"]
        assert max(r["prompt"]) < 39296
    # decode-heavy: a request's answer is many times its own prompt chunks
    assert sum(r["max_new"] for r in reqs) > 5 * sum(r["user_len"]
                                                     for r in reqs)
    assert cell.config["system_prompt_tokens"] == 1024


def test_the_counts_are_the_arithmetic(cell):
    cfg = cell.config
    flops, nbytes = ling.kda_decode_call(cfg, 24)
    assert nbytes == 6 * 24 * (2 * 2097152 + 4 * 6 * 4096)
    assert flops == 6 * 24 * 7 * 32 * 128 * 128
    # memory bounds it: 24 live rows' state in six layers is 0.74 ms
    assert roofline_min_seconds(flops, nbytes, PEAKS) == nbytes / 819e9
    assert 0.7e-3 < nbytes / 819e9 < 0.8e-3
    assert ling.latent_row_bytes(cfg) == 1280
    assert ling.mla_call(cfg, 1, 1000) == (2 * 32 * (576 + 512) * 1000,
                                           1000 * 1280)
    assert ling.expert_bytes(cfg) == 3 * 2560 * 768 * 2
    f, b = ling.experts_call(cfg, 96, 200, 190)
    assert f == 190 * 6 * 2560 * 768
    assert b == 200 * ling.expert_bytes(cfg) + 6 * 96 * 2560 * 6
    # memory bounds the walk at a decode tick's counts: 200 experts' 2.4 GB
    assert roofline_min_seconds(f, b, PEAKS) == b / 819e9
    assert ling.n_moe(cfg) == 6 and cfg["num_experts_per_tok"] == 8


def _span(name="engine/tick", duration_ms=1.0, **attrs):
    return types.SimpleNamespace(name=name, attrs=attrs,
                                 duration_ms=duration_ms)


class _Trace:
    """Three decode ticks and one mixed tick, six state updates (and six
    expert walks) each, and a small transfer program that is neither."""
    def __init__(self, kda_s, decode_s, mixed_s, walk_s=0.0):
        key = kernel_ops.kernel_key("kda_decode", "float32", (96, 32, 128))
        walk = {"jit_decode": kernel_ops.kernel_key(
                    "moe_experts", "float32", (96, 2560)),
                "jit_mixed": kernel_ops.kernel_key(
                    "moe_experts", "float32", (352, 2560))}
        ops, modules, t = [], [], 0.0
        self._busy = {"jit_decode": [], "jit_mixed": [], "jit_copy": []}
        for name, busy in (("jit_decode", decode_s), ("jit_mixed", mixed_s),
                           ("jit_decode", decode_s), ("jit_decode", decode_s),
                           ("jit_copy", 1e-5)):
            start = t
            if name != "jit_copy":
                for _ in range(6):
                    ops.append((t, t + kda_s, key, "custom-call", True))
                    t += kda_s
                    if walk_s:
                        ops.append((t, t + walk_s, walk[name], "custom-call",
                                    True))
                        t += walk_s
            t = max(t, start + busy)
            modules.append((start, t, name, 1))
            self._busy[name].append(busy)
            t += 1e-3
        self.devices = [types.SimpleNamespace(ops=ops, modules=modules)]

    def main_module(self):
        return "jit_decode"

    def module_busy_seconds(self, name=None):
        return list(self._busy[name or "jit_decode"])


def _run(cell, spans, trace=None):
    return types.SimpleNamespace(cell=cell, spans=spans, trace=trace,
                                 counters={}, requests=[],
                                 device={"peaks": PEAKS})


def test_new_readers_read_their_attrs_and_the_trace(cell):
    read = lambda name, run: harness.load_module("metrics", name).read(run)   # noqa: E731
    rows = (20, 22, 24, 26)
    # the second tick is mixed: a lane's 128 tokens beside its decode rows;
    # a quarter of a row's 8 x 6 picks falls on the held experts
    spans = [_span(prefill=0 if i != 1 else 1, state_rows=n, kv_blocks=30 * n,
                   prefill_tokens=0 if i != 1 else 128,
                   experts_touched=200 if i != 1 else 300,
                   routed_rows=12 * (n + (0 if i != 1 else 128)))
             for i, n in enumerate(rows)]
    run = _run(cell, spans, _Trace(150e-6, 6e-3, 9e-3, 800e-6))
    # the sum of the four ticks' least times over the 24 calls' seconds
    least = sum(ling.kda_decode_call(cell.config, n)[1] / 819e9 for n in rows)
    assert read("kda_decode_roofline", run) == pytest.approx(
        100 * least / (24 * 150e-6))
    assert 60 < read("kda_decode_roofline", run) < 100
    assert read("router_held_picks_share", run) == pytest.approx(25.0)
    # each tick's OWN counts, its rows the slots and the lane's tokens
    least = sum(roofline_min_seconds(
        *ling.experts_call(cell.config, 96 + s.attrs["prefill_tokens"],
                           s.attrs["experts_touched"],
                           s.attrs["routed_rows"]), PEAKS) for s in spans)
    assert read("experts_walk_roofline", run) == pytest.approx(
        100 * least / (24 * 800e-6))
    assert 60 < read("experts_walk_roofline", run) < 100
    # a traced tick without its counts (the phase's last: they ride on the
    # next tick's read): the state update's seconds are not guessed, and the
    # walk leaves the tick out with its own execution's seconds
    partial = spans[:3] + [_span(prefill=0, kv_blocks=9)]
    run = _run(cell, partial, _Trace(150e-6, 6e-3, 9e-3, 800e-6))
    assert read("kda_decode_roofline", run) is None
    least = sum(roofline_min_seconds(
        *ling.experts_call(cell.config, 96 + s.attrs["prefill_tokens"],
                           s.attrs["experts_touched"],
                           s.attrs["routed_rows"]), PEAKS) for s in spans[:3])
    assert read("experts_walk_roofline", run) == pytest.approx(
        100 * least / (18 * 800e-6))
    assert read("router_held_picks_share", run) == pytest.approx(25.0)


def test_new_readers_return_none_where_there_is_nothing_to_read(cell):
    old = [_span(prefill=0, kv_blocks=12, experts_touched=3),  # the parent's
           _span("engine/admit", pool_used=3)]
    for run in (_run(cell, old, _Trace(1e-4, 1e-2, 2e-2)),
                _run(cell, [], None)):
        for name in NEW:
            assert harness.load_module("metrics", name).read(run) is None
    # another configuration's adapter: no counts of the delta-rule update
    other = harness.Cell("nemotron3-super-ep4_serve_chat_bursts")
    ticks = [_span(prefill=0, state_rows=3, kv_blocks=9)]
    assert harness.load_module("metrics", "kda_decode_roofline").read(
        _run(other, ticks, _Trace(1e-4, 1e-2, 2e-2))) is None


def test_the_parents_tree_has_no_such_workload():
    """What the driver's first try of the cell on the parent reads: a
    manifest without the cell exits at once, before JAX is touched."""
    with pytest.raises(SystemExit, match="BENCHMARK.json has no workload"):
        harness.Cell("ling3-flash-ep4_serve_no_such_cell")


# -- the committed comparison at a tiny size, through the harness itself ------

def _rehearse(tmp, tool, *args):
    cmd = [sys.executable, os.path.join(HERE, "tests", "rehearse_ling.py"),
           str(tmp), tool, "--", "--workload", "tiny_reasoning_serve", *args]
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    return subprocess.run(cmd, capture_output=True, text=True, timeout=900,
                          env=env)


def test_the_tiny_cell_traced_reports_the_new_readers(tmp_path):
    p = _rehearse(tmp_path, "run", "--seed", str(2 ** 31 + 5), "--seconds",
                  "1.5", "--trace", "1")
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert {"router_held_picks_share", "state_rows_p50", "tick_kv_blocks_p50",
            "experts_touched_p50", "conv_state_restore_share"} <= set(
                line["metrics"])
    # two of four groups held: about half of the picks
    assert 30 < line["metrics"]["router_held_picks_share"]["value"] < 70
    # a CPU gives no device trace: a kernel's share is never written there
    assert not {"kda_decode_roofline", "experts_walk_roofline"} & set(
        line["metrics"])
    value, limit = (line["checks"]["worst_logit_gap"][k]
                    for k in ("value", "limit"))
    assert limit == harness.Cell(CELL).spec["logit_gap_tol"] and value < limit


def test_the_control_fails_the_loops_own_check_where_the_cell_passes_it(
        tmp_path):
    p = _rehearse(tmp_path, "control", "--seed", "5900000011", "--seconds",
                  "1.5", "--requests", "6")
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    reads = {ln.split(": ", 1)[0]: json.loads(ln.split(": ", 1)[1])
             for ln in p.stdout.splitlines()
             if ln.startswith(("clean: ", "control: "))}
    assert reads["clean"]["passes"] and not reads["control"]["passes"]
