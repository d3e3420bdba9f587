"""The cell glm53-flash-ep8_serve_repo_sessions: its files load, its mix sends
what the issue fixed, the adapter counts what the arithmetic says, each new
reader reads its span or its trace (and nothing where there is none; a tick
without counts is left out WITH its seconds; a share stays under 100%), and
the committed comparison holds at a tiny size through the harness itself."""

import json
import os
import subprocess
import sys
import types

import pytest

from benchmark import harness, kernel_ops, scopes, traffic
from benchmark.counts import roofline_min_seconds
from benchmark.models import glm

CELL = "glm53-flash-ep8_serve_repo_sessions"
HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
         "hbm_bytes": 16e9}
NEW = ("dsa_read_roofline", "dsa_selected_share", "mhc_tick_share",
       "tick_roofline_mfu")


@pytest.fixture(scope="module")
def cell():
    return harness.Cell(CELL)


def test_the_cells_files_load_and_name_each_other(cell):
    assert cell.chips == 1 and cell.adapter is glm
    assert cell.spec["engine"] == {"class": "PagedKVEngine", "n_slots": 64,
                                   "block_size": 64, "n_blocks": 8192,
                                   "max_len": 35328, "n_snapshots": 16}
    assert cell.spec["check_requests"] == 2 and cell.spec["loop"] == "serve"
    assert cell.traffic["name"] == "serve_repo_sessions"
    e2e = {m["name"] for m in cell.metrics["end_to_end"]}
    assert e2e == {"tpot_p50_ms", "setup_s"}        # no first-token metric
    layers = {m["name"] for m in cell.metrics["per_layer"]}
    assert {"tick_ms_p50", "tick_device_ms_p50", "tick_kv_blocks_p50",
            "tpot_p90_ms", "serve_device_idle_share", "serve_peak_hbm_gb",
            "serve_engine_peak_hbm_gb", "experts_touched_p50",
            "expert_load_max_over_mean", "expert_runs_p50",
            "conv_state_restore_share", "state_rows_p50", "setup_compile_s",
            "router_held_picks_share", "experts_walk_roofline",
            *NEW} <= layers
    # the delta-rule kernel's reader takes its key from the configuration's
    # head_dim, which stays the published 0 here (PERF.md section 7)
    assert "kda_decode_roofline" not in layers
    for m in cell.metrics["per_layer"] + cell.metrics["end_to_end"]:
        reader = harness.load_module("metrics", m["name"])
        assert (reader.UNIT, reader.SOURCE) == (m["unit"], m["source"])
        if m["name"] in NEW:
            assert (reader.LAYER, reader.MOVES) == (m["layer"], m["moves"])
            assert m["workloads"] == [CELL] and m["moves"] == "tpot_p50_ms"


def test_the_configuration_holds_every_published_number(cell):
    cfg = cell.config
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    cut = {"num_hidden_layers", "first_k_dense_replace", "layer_types",
           "mlp_layer_types", "indexer_types", "linear_attn_config",
           "n_routed_experts", "vocab_size"}
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(json.loads(line) for line in f if json.loads(line)[
                "name"] == "GLM-5.3-Flash")
        pub = row["config"]
        assert cfg["source"] == row["source_url"]
        assert {k for k, v in pub.items() if cfg.get(k) != v} == cut
        for k in ("num_hidden_layers", "first_k_dense_replace",
                  "n_routed_experts", "vocab_size"):
            assert cfg["published"][k] == pub[k]
        # the nested group keeps every width; its two index lists are cut
        lin, plin = cfg["linear_attn_config"], pub["linear_attn_config"]
        assert {k for k in plin if lin[k] != plin[k]} == {
            "kda_layers", "full_attn_layers"}
        assert cfg["layer_types"] == pub["layer_types"][:3] + pub[
            "layer_types"][3:5]
    assert set(cfg["reduced"]) == cut | {"num_layers", "vocab"}
    assert cfg["num_layers"] == cfg["num_hidden_layers"] == 5
    assert cfg["vocab"] == cfg["vocab_size"] == 19360 == 154880 // 8
    assert cfg["router_width"] == 288 and cfg["n_routed_experts"] == 36
    assert cfg["head_dim"] == 0         # as published: no width under a name
    for key in ("residual", "kda", "dsa", "indexer", "half_full_group",
                "swiglu_limit", "router", "unread_keys", "precision", "init",
                "max_len", "check_rows"):
        assert cfg["assumed"][key]
    assert "stage 0" in cfg["deployment"] and "rank 0" in cfg["deployment"]
    assert "K K K D K" in cfg["reduced_note"]
    assert set(cfg["left_out"]) == {"multi_token_prediction", "vision_tower",
                                    "expert_exchange"}
    assert (cfg["weights_dtype"], cfg["cache_dtype"], cfg["state_dtype"]) == (
        "bfloat16", "bfloat16", "float32")


def test_the_cut_is_the_issues_arithmetic(cell):
    cfg = cell.config
    H, D = 4096, 8192
    kda = 3 * H * D + D * H + 2 * (H * 128 + 128 * D) + H * 64 + 3 * D * 4 \
        + 64 + D + 128
    indexer = 1536 * 32 * 128 + H * 128 + 2 * 128 + H * 32
    dsa = H * 1536 + 1536 + 1536 * 64 * 256 + H * 512 + 512 \
        + 512 * 64 * 512 + 64 * 256 * H + indexer
    routed = H * 288 + 288 + 3 * H * 2048 + 36 * 3 * H * 2048
    dense = 3 * H * 12288
    maps = 2 * (4 * H * 24 + 3 + 24)
    assert round(kda / 1e6, 1) == 137.7 and round(dsa / 1e6, 1) == 124.4
    assert round(indexer / 1e6, 1) == 6.9 and round(maps / 1e6, 2) == 0.79
    assert round(routed / 1e6, 1) == 932.3 and round(dense / 1e6, 1) == 151.0
    want = (2 * 19360 * H + H + (kda + dense) + 3 * (kda + routed)
            + (dsa + routed) + 5 * (maps + 2 * H))
    assert glm.n_params(cfg) == want
    assert 9.43e9 < 2 * want < 9.45e9               # 9.44 GB at 2 bytes
    eng = cell.spec["engine"]
    spec = glm.spec_of(cfg)
    assert spec.state_bytes() == 4 * (4194304 + 3 * 24576 * 2) == 17_367_040
    pool = spec.cache_row_bytes() * eng["block_size"] * eng["n_blocks"]
    assert spec.cache_row_bytes() == 1024 + 64 and round(pool / 1e9, 2) == 0.57
    resident = 2 * want + pool + spec.state_bytes() * (
        eng["n_slots"] + eng["n_snapshots"])
    assert 0.70 < resident / 16e9 < 0.72            # the floor is 25%
    # eight resident contexts and every slot's own turn and answer fit
    assert eng["n_blocks"] >= 8 * 512 + eng["n_slots"] * (
        eng["max_len"] - 32768) // eng["block_size"] + 1
    assert eng["max_len"] == 32768 + 2048 + 512 == 23 * 1536


def test_repo_sessions_sends_what_the_issue_fixed(cell):
    mix = cell.traffic
    assert mix["system_prompts"] == {"count": 8, "tokens": 32768,
                                     "popularity": {"dist": "zipf",
                                                    "exponent": 1.0}}
    assert mix["user_tokens"] == {"dist": "lognormal_quantiles",
                                  "median": 256, "sigma": 1.0, "min": 32,
                                  "max": 2048}
    assert mix["output_tokens"] == {"dist": "lognormal_quantiles",
                                    "median": 128, "sigma": 0.7, "min": 16,
                                    "max": 512}
    assert mix["schedule_seed"] == 61 and mix["arrivals"]["burst_size"] == 1
    assert mix["drain_deadline_s"] == 60 and mix["pairing"] == "golden_stride"
    load = traffic.open_loop_requests(mix, 2 ** 31 + 3, 45.0,
                                      cell.config["vocab"])
    reqs = load["requests"]
    assert len(reqs) == round(mix["rate_per_s"] * 45) < 500   # no TTFT cell
    assert [len(p) for p in load["system_prompts"]] == [32768] * 8
    for r in reqs:
        assert 32768 + 32 <= len(r["prompt"]) <= 32768 + 2048
        assert 16 <= r["max_new"] <= 512
        assert len(r["prompt"]) + r["max_new"] <= cell.spec["engine"]["max_len"]
        assert max(r["prompt"]) < 19360
    assert cell.config["system_prompt_tokens"] == 32768


def test_the_counts_are_the_arithmetic(cell):
    cfg = cell.config
    assert glm.h_bytes(cfg) == 64 * 128 * 128 * 4 == 4194304
    assert glm.latent_row_bytes(cfg) == 1024 and glm.pooled_row_bytes(
        cfg) == 256
    assert glm.expert_bytes(cfg) == 3 * 4096 * 2048 * 2
    # one row at 33,000 positions that attends 2,048 + 1
    flops, nbytes = glm.dsa_call(cfg, 1, 33001, 2049)
    assert flops == 2 * 32 * 128 * 33001 / 4 + 2 * 64 * 1024 * 2049
    assert nbytes == 33001 / 4 * 256 + 2049 * 1024 \
        + (64 * 512 * 2 + 32 * 128) * 2
    f, b = glm.kda_decode_call(cfg, 24)
    assert b == 4 * 24 * (2 * 4194304 + 4 * 6 * 64 * 128)
    assert f == 4 * 24 * 7 * 64 * 128 * 128
    assert glm.n_moe(cfg) == 4 and cfg["num_experts_per_tok"] == 8
    # everything but the stacks and the embedding: 2.03 GB a tick
    assert round(glm.dense_bytes(cfg) / 1e9, 2) == 2.03
    tf, tb = glm.tick_call(cfg, 25, 25, 100, 100, 25 * 33000, 25 * 2050)
    assert tb > glm.dense_bytes(cfg) + 100 * glm.expert_bytes(cfg) \
        + 25 * 2 * 4 * 4194304


def _span(name="engine/tick", duration_ms=1.0, **attrs):
    return types.SimpleNamespace(name=name, attrs=attrs,
                                 duration_ms=duration_ms)


class _Trace:
    """Three decode ticks and one mixed tick: in each the stream mixing, the
    index work, the gather and the attend kernel, and a small transfer
    program that is neither."""
    def __init__(self, hc_s, index_s, gather_s, attend_s, decode_s, mixed_s,
                 with_scopes=True):
        key = {"jit_decode": kernel_ops.kernel_key(
                   "latent_paged_attention", "bfloat16", (64, 64, 512)),
               "jit_mixed": kernel_ops.kernel_key(
                   "latent_paged_attention", "bfloat16", (320, 64, 512))}
        ops, modules, t = [], [], 0.0
        found = {s: [] for s in scopes.SCOPES}
        for name, busy in (("jit_decode", decode_s), ("jit_mixed", mixed_s),
                           ("jit_decode", decode_s), ("jit_decode", decode_s),
                           ("jit_copy", 1e-5)):
            start = t
            if name != "jit_copy":
                for scope, secs in (("hyper_connection", hc_s),
                                    ("dsa_index", index_s),
                                    ("sparse_latent_attention", gather_s)):
                    ops.append((t, t + secs, "fusion_f32_64_", "fusion",
                                False))
                    found[scope].append((t, t + secs))
                    t += secs
                ops.append((t, t + attend_s, key[name], "custom-call", True))
                t += attend_s
                rest = start + busy - t
                ops.append((t, t + rest, "fusion_bf16_64_4096_", "fusion",
                            False))
                t += rest
            else:
                ops.append((t, t + busy, "copy_f32_8_", "copy", False))
                t += busy
            modules.append((start, t, name, 1))
            t += 1e-3
        self.devices = [types.SimpleNamespace(
            ops=ops, modules=modules,
            busy=lambda: [(s, e) for s, e, *_ in ops])]
        if with_scopes:
            self.scope_ops = found


def _run(cell, spans, trace=None):
    return types.SimpleNamespace(cell=cell, spans=spans, trace=trace,
                                 counters={}, requests=[],
                                 device={"peaks": PEAKS})


def _ticks():
    rows = (20, 22, 24, 26)
    return [_span(prefill=0 if i != 1 else 1, state_rows=n,
                  prefill_tokens=0 if i != 1 else 128,
                  dsa_rows=n + (0 if i != 1 else 128),
                  dsa_live_positions=33000 * (n + (0 if i != 1 else 128)),
                  dsa_selected_positions=2050 * (n + (0 if i != 1 else 128)),
                  index_pool_rows=n, experts_touched=100 if i != 1 else 140,
                  routed_rows=4 * (n + (0 if i != 1 else 128)))
            for i, n in enumerate(rows)]


def test_new_readers_read_their_attrs_and_the_trace(cell):
    read = lambda name, run: harness.load_module("metrics", name).read(run)   # noqa: E731
    spans = _ticks()
    trace = _Trace(400e-6, 300e-6, 200e-6, 100e-6, 12e-3, 20e-3)
    run = _run(cell, spans, trace)
    assert read("dsa_selected_share", run) == pytest.approx(
        100 * 2050 / 33000)
    assert read("mhc_tick_share", run) == pytest.approx(
        100 * 4 * 400e-6 / (3 * 12e-3 + 20e-3))
    least = sum(roofline_min_seconds(*glm.dsa_call(
        cell.config, s.attrs["dsa_rows"], s.attrs["dsa_live_positions"],
        s.attrs["dsa_selected_positions"]), PEAKS) for s in spans)
    assert read("dsa_read_roofline", run) == pytest.approx(
        100 * least / (4 * 600e-6))
    assert 0 < read("dsa_read_roofline", run) < 100
    least = sum(roofline_min_seconds(*glm.tick_call(
        cell.config, s.attrs["state_rows"] + s.attrs["prefill_tokens"],
        s.attrs["state_rows"], s.attrs["experts_touched"],
        s.attrs["routed_rows"], s.attrs["dsa_live_positions"],
        s.attrs["dsa_selected_positions"]), PEAKS) for s in spans)
    assert read("tick_roofline_mfu", run) == pytest.approx(
        100 * least / (3 * 12e-3 + 20e-3))
    assert 30 < read("tick_roofline_mfu", run) < 100
    # a traced tick without its counts (the phase's last: the routed counts
    # ride on the next tick's read) is left out WITH its seconds
    partial = spans[:3] + [_span(prefill=0, state_rows=26, dsa_rows=26,
                                 dsa_live_positions=33000 * 26,
                                 dsa_selected_positions=2050 * 26)]
    run = _run(cell, partial, trace)
    least = sum(roofline_min_seconds(*glm.tick_call(
        cell.config, s.attrs["state_rows"] + s.attrs["prefill_tokens"],
        s.attrs["state_rows"], s.attrs["experts_touched"],
        s.attrs["routed_rows"], s.attrs["dsa_live_positions"],
        s.attrs["dsa_selected_positions"]), PEAKS) for s in spans[:3])
    assert read("tick_roofline_mfu", run) == pytest.approx(
        100 * least / (2 * 12e-3 + 20e-3))
    # the sparse read's own counts are all there: all four ticks
    assert read("dsa_read_roofline", run) == pytest.approx(
        read("dsa_read_roofline", _run(cell, spans, trace)))


def test_new_readers_return_none_where_there_is_nothing_to_read(cell):
    old = [_span(prefill=0, kv_blocks=12, experts_touched=3),  # the parent's
           _span("engine/admit", pool_used=3)]
    bare = _Trace(4e-4, 3e-4, 2e-4, 1e-4, 12e-3, 20e-3, with_scopes=False)
    for run in (_run(cell, old, bare), _run(cell, [], None)):
        for name in NEW:
            assert harness.load_module("metrics", name).read(run) is None
    # the counters without a trace that kept the scopes: the two shares of
    # device time stay out, the counter's share reads
    run = _run(cell, _ticks(), bare)
    assert harness.load_module("metrics", "dsa_read_roofline").read(run) is None
    assert harness.load_module("metrics", "mhc_tick_share").read(run) is None
    assert harness.load_module("metrics", "dsa_selected_share").read(run) > 0
    # another configuration's adapter: no counts of the sparse read
    other = harness.Cell("ling3-flash-ep4_serve_reasoning")
    scoped = _Trace(4e-4, 3e-4, 2e-4, 1e-4, 12e-3, 20e-3)
    for name in ("dsa_read_roofline", "tick_roofline_mfu"):
        assert harness.load_module("metrics", name).read(
            _run(other, _ticks(), scoped)) is None


def test_the_parents_tree_has_no_such_workload():
    with pytest.raises(SystemExit, match="BENCHMARK.json has no workload"):
        harness.Cell("glm53-flash-ep8_serve_no_such_cell")


# -- the committed comparison at a tiny size, through the harness itself ------

def _rehearse(tmp, tool, *args):
    cmd = [sys.executable, os.path.join(HERE, "tests", "rehearse_glm.py"),
           str(tmp), tool, "--", "--workload", "tiny_repo_sessions_serve",
           *args]
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
            "experts_touched_p50", "conv_state_restore_share",
            "dsa_selected_share"} <= set(line["metrics"])
    # contexts of 34-120 positions, of which 8 and the tail are attended
    assert 5 < line["metrics"]["dsa_selected_share"]["value"] < 40
    # a CPU gives no device trace: a share of device time is never written
    assert not {"dsa_read_roofline", "mhc_tick_share", "tick_roofline_mfu",
                "experts_walk_roofline"} & set(line["metrics"])
    value, limit = (line["checks"]["worst_logit_gap"][k]
                    for k in ("value", "limit"))
    assert limit == harness.Cell(CELL).spec["logit_gap_tol"] and value < limit


def test_the_control_fails_the_loops_own_check_where_the_cell_passes_it(
        tmp_path):
    p = _rehearse(tmp_path, "control", "--seed", "6100000011", "--seconds",
                  "1.5", "--requests", "6")
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    reads = {ln.split(": ", 1)[0]: json.loads(ln.split(": ", 1)[1])
             for ln in p.stdout.splitlines()
             if ln.startswith(("clean: ", "control: "))}
    assert reads["clean"]["passes"] and not reads["control"]["passes"]


def test_an_operation_is_placed_under_its_scope_by_the_programs_text():
    """A TPU's trace names an operation by its instruction's text without the
    metadata; the compiled program's text has both."""
    hlo = '''HloModule jit_step
fused_computation.3 {
  %p = bf16[64,16384]{1,0} parameter(0)
  ROOT %m = bf16[64,16384]{1,0} multiply(%p, %p), metadata={op_name="jit(step)/jit(main)/hyper_connection/mul"}
}
ENTRY main {
  %fusion.7 = bf16[64,16384]{1,0:T(8,128)(2,1)} fusion(bf16[64,16384]{1,0} %a), kind=kLoop, calls=%fused_computation.3, metadata={op_name="jit(step)/jit(main)/hyper_connection/mul" source_file="x.py"}
  %sort.2 = (f32[64,8832]{1,0}, s32[64,8832]{1,0}) sort(%k, %v), dimensions={1}, metadata={op_name="jit(step)/jit(main)/dsa_index/sort"}
  fusion.9 = f32[64,4096]{1,0} fusion(%b), kind=kOutput, metadata={op_name="jit(step)/jit(main)/fc/dot_general"}
}'''
    found = scopes.scoped_instructions([hlo])
    assert found[("fusion.7", "bf16[64,16384]")] == "hyper_connection"
    assert found[("sort.2", "f32[64,8832]")] == "dsa_index"
    assert ("fusion.9", "f32[64,4096]") not in found
    event = ("%fusion.7 = bf16[64,16384]{1,0:T(8,128)(2,1)} fusion(bf16[64,"
             "16384]{1,0} %a), kind=kLoop, calls=%fused_computation.3")
    assert found[scopes.instruction_key(event)] == "hyper_connection"
    assert scopes.instruction_key("not an instruction") is None
