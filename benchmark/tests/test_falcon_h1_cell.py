"""The cell falcon-h1-34b-pp12_serve_long_prompts: its files load, its mix
sends what the issue fixed, the adapter counts what the arithmetic says, each
new reader reads its span or its trace (and nothing where there is none), and
the committed comparison holds at a tiny size through the harness itself."""

import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from benchmark import harness, kernel_ops, traffic
from benchmark.counts import roofline_min_seconds
from benchmark.models import falcon_h1

CELL = "falcon-h1-34b-pp12_serve_long_prompts"
HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
         "hbm_bytes": 16e9}
NEW = ("hybrid_tick_roofline", "mixed_tick_share", "state_rows_p50")


@pytest.fixture(scope="module")
def cell():
    return harness.Cell(CELL)


def test_the_cells_files_load_and_name_each_other(cell):
    assert cell.chips == 1 and cell.adapter is falcon_h1
    assert cell.spec["engine"] == {"class": "PagedKVEngine", "n_slots": 16,
                                   "block_size": 64, "n_blocks": 3264,
                                   "max_len": 12800, "n_snapshots": 8}
    assert cell.spec["check_requests"] == 2 and cell.spec["loop"] == "serve"
    assert cell.spec["trace_seconds"] == 4
    assert cell.traffic["name"] == "serve_long_prompts"
    e2e = {m["name"] for m in cell.metrics["end_to_end"]}
    assert {"tpot_p50_ms", "setup_s"} <= e2e
    assert "train_tokens_per_s" not in e2e
    layers = {m["name"] for m in cell.metrics["per_layer"]}
    assert {"gqa_decode_roofline", "tick_ms_p50",
            "tick_device_ms_p50", "tick_kv_blocks_p50", "tpot_p90_ms",
            "serve_device_idle_share", "serve_peak_hbm_gb",
            "serve_engine_peak_hbm_gb", "tick_late_read_share",
            "tick_run_ahead_share", *NEW} <= layers
    # no routed layer, no latent, no window: their readers are not the cell's;
    # `ssm_decode_roofline` reads the MAIN module's kernel time against the
    # decode ticks' rows, and here the main module is the mixed tick (111% in
    # the builder's traced run): the cell is not on its list (PERF.md 7)
    assert not {"ssm_decode_roofline", "moe_tick_roofline", "moe_experts_roofline",
                "experts_touched_p50", "mla_decode_roofline",
                "window_decode_roofline", "decode_tick_roofline"} & layers
    for m in cell.metrics["per_layer"] + cell.metrics["end_to_end"]:
        reader = harness.load_module("metrics", m["name"])
        assert (reader.UNIT, reader.SOURCE) == (m["unit"], m["source"])
        if m["name"] in NEW:
            assert (reader.LAYER, reader.MOVES) == (m["layer"], m["moves"])
            assert m["workloads"] == [CELL] and m["moves"] == "tpot_p50_ms"


def test_the_configuration_holds_every_published_number(cell):
    cfg = cell.config
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(json.loads(line) for line in f if json.loads(line)[
                "name"] == "Falcon-H1-34B-Instruct")
        pub = row["config"]
        assert cfg["source"] == row["source_url"]
        differs = {k for k, v in pub.items() if cfg.get(k) != v}
        assert differs == {"num_hidden_layers"}
        assert cfg["published"]["num_hidden_layers"] == pub[
            "num_hidden_layers"] == 72
    assert cfg["reduced"] == ["num_hidden_layers", "num_layers"]
    assert cfg["num_layers"] == cfg["num_hidden_layers"] == 6
    assert cfg["vocab"] == cfg["vocab_size"] == 261120
    # the names the shared readers read, kept equal to the source's
    assert cfg["mamba_num_heads"] == cfg["mamba_n_heads"] == 32
    assert cfg["mamba_head_dim"] == cfg["mamba_d_head"] == 128
    assert cfg["ssm_state_size"] == cfg["mamba_d_state"] == 256
    for key in ("block", "multipliers", "rotary", "mixer", "state", "init",
                "dt", "precision", "max_len", "check_stale_at"):
        assert cfg["assumed"][key]
    assert "unit scale" in cfg["assumed"]["init"].lower()
    assert "stage 0" in cfg["deployment"] and "twelve" in cfg["deployment"]
    assert "6 OF 72" in cfg["reduced_note"]
    assert (cfg["weights_dtype"], cfg["cache_dtype"], cfg["state_dtype"]) == (
        "bfloat16", "bfloat16", "float32")


def test_the_cut_is_the_issues_arithmetic(cell):
    cfg = cell.config
    D, F, V = 5120, 21504, 261120
    attn = D * 2560 + 2 * D * 512 + 2560 * D
    mixer = D * 9248 + 4096 * D + 5120 * 4 + 5120 + 3 * 32 + 4096
    ffn = 3 * D * F
    layer = attn + mixer + ffn + 2 * D
    want = 2 * V * D + 6 * layer + D
    assert falcon_h1.n_params(cfg) == want
    assert round(attn / 1e6, 2) == 31.46 and round(mixer / 1e6, 1) == 68.4
    assert round(ffn / 1e6, 1) == 330.3 and round(layer / 1e6, 1) == 430.1
    assert 10.50e9 < 2 * want < 10.52e9             # 10.51 GB at 2 bytes
    eng = cell.spec["engine"]
    state = falcon_h1.spec_of(cfg).state_bytes()
    assert state == 6 * (32 * 128 * 256 * 4 + 3 * 5120 * 2) == 25_350_144
    kv = falcon_h1.spec_of(cfg).cache_row_bytes() * eng["block_size"] \
        * eng["n_blocks"]
    assert round(kv / 1e9, 2) == 2.57
    resident = 2 * want + kv + state * (eng["n_slots"] + eng["n_snapshots"])
    assert 0.84 < resident / 16e9 < 0.87            # the floor is 25%
    # 16 requests of the longest span fit the pool
    assert eng["n_blocks"] >= 16 * (eng["max_len"] // eng["block_size"]) + 1


def test_long_prompts_sends_what_the_issue_fixed(cell):
    mix = cell.traffic
    assert not mix.get("system_prompts")
    assert mix["user_tokens"] == {"dist": "lognormal_quantiles",
                                  "median": 4096, "sigma": 0.6, "min": 1024,
                                  "max": 12288}
    assert mix["output_tokens"] == {"dist": "lognormal_quantiles",
                                    "median": 128, "sigma": 0.7, "min": 32,
                                    "max": 384}
    assert mix["schedule_seed"] == 54 and mix["arrivals"]["burst_size"] == 1
    assert mix["drain_deadline_s"] == 60 and mix["pairing"] == "golden_stride"
    assert isinstance(mix["rate_per_s"], (int, float))
    load = traffic.open_loop_requests(mix, 2 ** 31 + 3, 45.0,
                                      cell.config["vocab"])
    reqs = load["requests"]
    assert len(reqs) == round(mix["rate_per_s"] * 45)
    assert load["system_prompts"] == []
    again = traffic.open_loop_requests(mix, 7, 45.0, cell.config["vocab"])
    assert [(r["due"], r["user_len"], r["max_new"]) for r in reqs] == \
        [(r["due"], r["user_len"], r["max_new"]) for r in again["requests"]]
    assert [r["prompt"] for r in reqs] != [r["prompt"]
                                           for r in again["requests"]]
    for r in reqs:
        assert 1024 <= len(r["prompt"]) <= 12288 and 32 <= r["max_new"] <= 384
        assert len(r["prompt"]) + r["max_new"] <= cell.spec["engine"]["max_len"]
        # the planted stale restore lies inside every prompt
        assert cell.config["check_stale_at"] <= len(r["prompt"])
        assert max(r["prompt"]) < 261120
    # no two prompts share a block: nothing is served from the prefix cache
    heads = {tuple(r["prompt"][:64]) for r in reqs}
    assert len(heads) == len(reqs)


def _span(name="engine/tick", duration_ms=1.0, **attrs):
    return types.SimpleNamespace(name=name, attrs=attrs,
                                 duration_ms=duration_ms)


class _Trace:
    """A trace of two tick programs: three decode ticks (six state updates
    and six decode reads an execution) and two mixed ticks, and a small
    transfer program that is neither."""
    def __init__(self, ssm_s, gqa_s, decode_s, mixed_s):
        ssm = kernel_ops.kernel_key("ssm_decode_update", "float32",
                                    (16, 32, 1, 128))
        gqa = kernel_ops.kernel_key("paged_gqa_attention", "float32",
                                    (16, 4, 8, 128))
        ops, modules, t = [], [], 0.0
        self._busy = {"jit_decode": [], "jit_mixed": [], "jit_copy": []}
        for name, busy in (("jit_decode", decode_s), ("jit_mixed", mixed_s),
                           ("jit_decode", decode_s), ("jit_mixed", mixed_s),
                           ("jit_decode", decode_s), ("jit_copy", 1e-5)):
            start = t
            if name != "jit_copy":
                for key, dur in ((ssm, ssm_s), (gqa, gqa_s)):
                    for _ in range(6):
                        ops.append((t, t + dur, key, "custom-call", True))
                        t += dur
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
    cfg = cell.config
    decode = [_span(duration_ms=11.0, prefill=0, active=a, state_rows=a,
                    kv_blocks=a * 70, decode_rows=a * 4400,
                    experts_touched=0) for a in (6, 7, 8)]
    mixed = [_span(duration_ms=16.0, prefill=2, prefill_tokens=256, active=9,
                   state_rows=7, kv_blocks=7 * 70 + 60, lane_kv_blocks=60,
                   experts_touched=0) for _ in range(2)]
    spans = [decode[0], mixed[0], decode[1], mixed[1], decode[2]]
    run = _run(cell, spans, _Trace(150e-6, 30e-6, 10.5e-3, 15e-3))
    assert read("mixed_tick_share", run) == pytest.approx(
        100 * 32.0 / (33.0 + 32.0))
    assert read("state_rows_p50", run) == 7.0
    # the whole tick: the sum of each tick's least time over the busy seconds
    # of the five executions of the two tick programs (the transfer's apart)
    least = sum(roofline_min_seconds(*falcon_h1.hybrid_tick_counts(
        cfg, s.attrs["state_rows"], s.attrs.get("prefill_tokens", 0),
        s.attrs["kv_blocks"], s.attrs.get("lane_kv_blocks", 0), 64), PEAKS)
        for s in spans)
    assert read("hybrid_tick_roofline", run) == pytest.approx(
        100 * least / (3 * 10.5e-3 + 2 * 15e-3))
    assert 80 < read("hybrid_tick_roofline", run) < 100
    # the two shared readers find the kernels at this cell's shapes
    flops, nbytes = falcon_h1.ssm_decode_call(cfg, 7)
    assert nbytes == 6 * 7 * (2 * 4_194_304 + 4 * (2 * 4096 + 2 * 512 + 64))
    assert flops == 6 * 7 * 6 * 32 * 128 * 256
    assert read("ssm_decode_roofline", run) == pytest.approx(
        100 * (nbytes / 819e9) / (6 * 150e-6))
    flops, nbytes = falcon_h1.gqa_decode_call(cfg, 490, 64)
    assert nbytes == 490 * 64 * 2048 and flops == 4 * 20 * 128 * 490 * 64
    assert read("gqa_decode_roofline", run) == pytest.approx(
        100 * (nbytes / 819e9) / 30e-6)


def test_the_ticks_counts_are_the_arithmetic(cell):
    cfg = cell.config
    layer = (falcon_h1.n_params(cfg) - 2 * 261120 * 5120 - 5120) // 6
    params = 2 * (6 * layer + 5120 + 5120 * 261120)
    # a decode tick of 8 rows over 560 blocks
    flops, nbytes = falcon_h1.hybrid_tick_counts(cfg, 8, 0, 560, 0, 64)
    assert nbytes == (params + 2 * 8 * 5120 + 6 * 2 * 8 * 4_194_304
                      + 6 * (560 * 64 + 8) * 2048)
    assert flops == pytest.approx(
        2 * 8 * 6 * layer + 2 * 8 * 5120 * 261120
        + 6 * 4 * 20 * 128 * 560 * 64 + 6 * 8 * 6 * 32 * 128 * 256)
    # 7.84 GB of parameters: memory bounds a decode tick at ~10 ms
    assert 9.5e-3 < roofline_min_seconds(flops, nbytes, PEAKS) < 11e-3
    # two full lanes beside them: 2 x 128 more rows through every weight
    f2, b2 = falcon_h1.hybrid_tick_counts(cfg, 8, 256, 560 + 120, 120, 64)
    assert b2 > nbytes and f2 > 2 * 264 * 6 * layer
    assert roofline_min_seconds(f2, b2, PEAKS) < 0.012


def test_new_readers_return_none_where_there_is_nothing_to_read(cell):
    old = [_span(prefill=0, kv_blocks=12),             # the parent's spans
           _span("engine/admit", pool_used=3)]
    for run in (_run(cell, old, _Trace(1e-4, 1e-4, 1e-2, 2e-2)),
                _run(cell, [], None)):
        for name in ("hybrid_tick_roofline", "state_rows_p50"):
            assert harness.load_module("metrics", name).read(run) is None
    assert harness.load_module("metrics", "mixed_tick_share").read(
        _run(cell, [], None)) is None
    # another configuration's adapter: no counts of the whole tick
    lfm2 = harness.Cell("lfm2-8b-a1b_serve_assistant")
    ticks = [_span(prefill=0, state_rows=3, kv_blocks=9)]
    assert harness.load_module("metrics", "hybrid_tick_roofline").read(
        _run(lfm2, ticks, _Trace(1e-4, 1e-4, 1e-2, 2e-2))) is None


def test_the_parents_tree_has_no_such_workload():
    """What the driver's first try of the cell on the parent reads: a
    manifest without the cell exits at once, before JAX is touched."""
    with pytest.raises(SystemExit, match="BENCHMARK.json has no workload"):
        harness.Cell("falcon-h1-34b-pp12_serve_no_such_cell")


# -- the committed comparison at a tiny size, through the harness itself ------

def _rehearse(tmp, tool, *args):
    cmd = [sys.executable,
           os.path.join(HERE, "tests", "rehearse_falcon_h1.py"), str(tmp),
           tool, "--", "--workload", "tiny_long_prompts_serve", *args]
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    return subprocess.run(cmd, capture_output=True, text=True, timeout=900,
                          env=env)


def test_the_tiny_cell_traced_reports_the_new_readers(tmp_path):
    p = _rehearse(tmp_path, "run", "--seed", str(2 ** 31 + 5), "--seconds",
                  "1.5", "--trace", "1")
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert {"mixed_tick_share", "state_rows_p50", "tick_kv_blocks_p50",
            "tick_late_read_share"} <= set(line["metrics"])
    assert 0 < line["metrics"]["mixed_tick_share"]["value"] < 100
    # a CPU gives no device trace: a kernel's share is never written there
    assert not {"ssm_decode_roofline", "gqa_decode_roofline",
                "hybrid_tick_roofline"} & set(line["metrics"])
    value, limit = (line["checks"]["worst_logit_gap"][k]
                    for k in ("value", "limit"))
    assert limit == harness.Cell(CELL).spec["logit_gap_tol"] and value < limit


@pytest.mark.parametrize("seed", [2 ** 31 + 7, 5400000011])
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


def test_the_witness_refuses_the_planted_faults_at_a_tiny_size(tmp_path):
    """Through benchmark/witness.py, the tool that reads them on the chip.
    Under the loop's statistic (the emitted token's gap below the row's
    largest logit) a vocabulary of 97 leaves half a deviation between a
    row's two largest logits, so the two finer faults (B's and C's
    multipliers swapped, a restore one chunk stale) move no emitted token
    here: tests/test_falcon_h1_engine.py refuses all six at 1e-4 of the
    logits, and the chip's witness reads them over 261,120 logits."""
    p = _rehearse(tmp_path, "witness", "--seed", str(2 ** 31 + 9),
                  "--seconds", "1.5", "--requests", "4", "--control",
                  "--faults", ",".join(falcon_h1.FAULTS), "--out",
                  os.path.join(str(tmp_path), "witness"))
    reads = {ln.split(": ", 1)[0]: json.loads(ln.split(": ", 1)[1])
             for ln in p.stdout.splitlines()
             if ln.startswith(("clean: {", "control: {", "fault:"))
             and ln.split(": ", 1)[1].startswith("{")}
    assert set(reads) == {"clean", "control"} | {
        "fault:" + f for f in falcon_h1.FAULTS}, p.stderr[-2000:]
    assert reads["clean"]["passes"] and not reads["control"]["passes"]
    assert np.isfinite(reads["control"]["worst"])
    for fault in ("ssm_out_dropped", "attention_out_dropped",
                  "key_multiplier_one", "no_rotation"):
        assert not reads["fault:" + fault]["passes"], fault
    for fault in ("ssm_ranges_swapped", "snapshot_stale"):
        assert reads["fault:" + fault]["worst"] > 0.0, fault
