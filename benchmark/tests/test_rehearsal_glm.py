"""test_rehearsal.py's tests through rehearse_glm.py: the same rehearsal,
its table of stand-ins extended by tiny cells for ALL eight added cells, the
seven before and glm53-flash-ep8_serve_repo_sessions: twelve cells.

rehearse.py maps every cell a metric's `workloads` names to a tiny stand-in
and has none for a cell it was not written with, so since BENCHMARK.json lists
the newest cell, test_rehearsal_ling.py's tests stop at a KeyError too,
as test_rehearsal.py's and the six files' between them have since the cells
before it; all are the benchmark's own files and a `model_config` PR may not
edit them (PERF.md section 7 has the one-line repair). These are the same
test functions, run on the table rehearse_glm.py extends."""

import json
import os
import subprocess
import sys

import pytest

import test_rehearsal as base


def rehearse(tmp, workload, trace, devices=1, seconds=0.8, seed=2 ** 31 + 11):
    cmd = [sys.executable, os.path.join(base.HERE, "rehearse_glm.py"),
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


@pytest.mark.parametrize("workload", ["tiny_repo_sessions_serve",
                                      "tiny_reasoning_serve",
                                      "tiny_long_prompts_serve",
                                      "tiny_long_sessions_serve",
                                      "tiny_chat_bursts_serve",
                                      "tiny_assistant_serve",
                                      "tiny_docqa_serve"])
def test_every_serving_stand_in_reports_end_to_end_metrics(tmp_path, workload):
    """Each added serving cell's stand-in, untraced: the end-to-end metrics of
    the committed cell, under the committed limit."""
    line, _, _ = rehearse(tmp_path, workload, 0, seconds=1.5,
                          seed=2 ** 31 + 5)
    base.check_schema(line, False)
    assert {"tpot_p50_ms", "setup_s"} <= set(line["metrics"])
    assert line["correct"] is True


def test_the_training_stand_in_reports_end_to_end_metrics(tmp_path):
    line, _, _ = rehearse(tmp_path, "tiny_code_train", 0, seconds=1.0,
                          seed=2 ** 31 + 5)
    base.check_schema(line, False)
    assert {"train_tokens_per_s", "setup_s"} == set(line["metrics"])
    assert line["correct"] is True


def test_the_table_holds_all_twelve_cells(tmp_path):
    """Every committed cell has a stand-in in the copy's manifest, so every
    list that names a committed cell names its stand-in beside it."""
    import rehearse_glm
    rehearse_glm.build_tree(str(tmp_path))
    bench = json.load(open(os.path.join(str(tmp_path), "BENCHMARK.json")))
    committed = json.load(open(os.path.join(base.HERE, os.pardir, os.pardir,
                                            "BENCHMARK.json")))
    assert len(committed["workloads"]) == 12
    assert len(bench["workloads"]) == 24
    assert sum(w["chips"] == 4 for w in committed["workloads"]) == 1
