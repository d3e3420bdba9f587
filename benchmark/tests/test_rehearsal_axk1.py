"""test_rehearsal.py's tests through rehearse_axk1.py: the same rehearsal,
its table of stand-ins extended by a tiny cell for axk1-ep16_serve_docqa.

rehearse.py maps every cell a metric's `workloads` names to a tiny stand-in
(`like[w]`) and has none for a cell it was not written with, so since
BENCHMARK.json lists this cell its own tests stop at a KeyError before
anything runs; the file is the benchmark's and a `model_config` PR may not
edit it (PERF.md section 7 has the one-line repair). These are the same test
functions, run on the tables rehearse_axk1.py extends: what they prove is
unchanged, that the harness takes a configuration, a mix, a cell and a
per-layer metric as added files and entries alone, every committed file byte
for byte in the copy."""

import json
import os
import subprocess
import sys

import pytest

import test_rehearsal as base


def rehearse(tmp, workload, trace, devices=1, seconds=0.8, seed=2 ** 31 + 11):
    cmd = [sys.executable, os.path.join(base.HERE, "rehearse_axk1.py"),
           str(tmp), "run", "--devices", str(devices), "--", "--workload",
           workload, "--seed", str(seed), "--seconds", str(seconds),
           "--trace", str(trace)]
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    p = subprocess.run(cmd, capture_output=True, text=True, timeout=600,
                       env=env)
    assert p.returncode == 0, p.stderr[-3000:]
    lines = p.stdout.strip().splitlines()
    assert lines[-2].startswith("benchmark: ")
    return json.loads(lines[-1]), json.loads(lines[-2][len("benchmark: "):]), p


@pytest.fixture(autouse=True)
def _extended_tables(monkeypatch):
    monkeypatch.setattr(base, "rehearse", rehearse)


test_training_cells = base.test_training_cells
test_training_cell_traced = base.test_training_cell_traced
test_serving_cell_and_the_throwaway_metric = \
    base.test_serving_cell_and_the_throwaway_metric


def test_the_tiny_document_cell_traced_reports_the_new_readers(tmp_path):
    """The stand-in itself, traced: the readers this PR adds find their
    attrs and counters in a run of the real loop (the kernels' shares need a
    device trace and are left out on a CPU, never written as a number)."""
    line, _, _ = rehearse(tmp_path, "tiny_docqa_serve", 1, seconds=1.5,
                          seed=2 ** 31 + 5)
    base.check_schema(line, True)
    assert {"experts_touched_p50", "expert_load_max_over_mean", "tick_ms_p50",
            "prefix_hit_rate", "tick_kv_blocks_p50"} <= set(line["metrics"])
    # a CPU keeps no memory statistics and gives no device trace
    assert not {"moe_tick_roofline", "mla_decode_roofline",
                "moe_experts_roofline", "serve_engine_peak_hbm_gb"} \
        & set(line["metrics"])
