"""End to end on the CPU at tiny sizes, through benchmark/run.py itself:
the last line's schema, the system against its plain reference, and that a
configuration, a mix, a cell and a per-layer metric are added by adding
files and entries alone (rehearse.py refuses to overwrite a file)."""

import filecmp
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))


def rehearse(tmp, workload, trace, devices=1, seconds=0.8, seed=2 ** 31 + 11):
    cmd = [sys.executable, os.path.join(HERE, "rehearse.py"), str(tmp),
           "--devices", str(devices), "--", "--workload", workload,
           "--seed", str(seed), "--seconds", str(seconds),
           "--trace", str(trace)]
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    p = subprocess.run(cmd, capture_output=True, text=True, timeout=600,
                       env=env)
    assert p.returncode == 0, p.stderr[-3000:]
    lines = p.stdout.strip().splitlines()
    assert lines[-2].startswith("benchmark: ")
    return json.loads(lines[-1]), json.loads(lines[-2][len("benchmark: "):]), p


def check_schema(line, traced, chips=1):
    assert set(line) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert line["device"]["count"] == chips
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and isinstance(m["value"], float)
    if not traced:
        assert "setup_s" in line["metrics"]
    # what `correct` compared, each number beside its limit, comes last
    assert list(line)[-1] == "checks"
    assert {"compilations_in_window", "failed"} < set(line["checks"])
    assert all(set(c) == {"value", "limit"} for c in line["checks"].values())


def check_setup(line, info):
    """setup_s is the parts of set-up less the runtime's start."""
    parts = info["setup_parts"]
    assert set(parts) == {"import", "runtime_start", "build", "init",
                          "compile_or_load", "warm"}
    rest = sum(v for k, v in parts.items() if k != "runtime_start")
    assert line["metrics"]["setup_s"]["value"] == pytest.approx(rest, abs=0.5)


@pytest.mark.parametrize("cell,devices", [("tiny_train", 1),
                                          ("tiny_nmt_train", 1),
                                          ("tiny_train_dp4", 4)])
def test_training_cells(tmp_path, cell, devices):
    line, info, _ = rehearse(tmp_path, cell, 0, devices)
    check_schema(line, False, devices)
    assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}
    # the system's loss against the float32 reference on the same weights
    assert info["notes"]["check_rel_err"] <= info["notes"]["check_tol"]
    assert info["compilations_in_window"] == 0
    assert info["notes"]["steps"] == line["attempted"]
    # steps are dispatched ahead, every one sent is waited for, and the
    # window is read from the first dispatch to the last loss on the host
    assert info["notes"]["steps_ahead"] == 8
    assert info["notes"]["window_s"] >= 0.8
    tokens_per_s = line["metrics"]["train_tokens_per_s"]["value"]
    assert tokens_per_s > 0
    assert "loss_rel_err" in line["checks"]
    check_setup(line, info)


def test_training_cell_traced(tmp_path):
    line, _, _ = rehearse(tmp_path, "tiny_train", 1)
    check_schema(line, True)
    # spans and counters are read on any platform; what needs the device's
    # trace is left out of the line on a CPU, never written as a number
    assert {"feed_ms_p50", "step_ms_p50", "step_stall_share", "mfu"} \
        <= set(line["metrics"])
    assert not {"train_device_idle_share", "flash_attention_roofline",
                "step_device_ms_p50"} & set(line["metrics"])
    assert "busy_s" not in line["device"]


def test_serving_cell_and_the_throwaway_metric(tmp_path):
    line, info, _ = rehearse(tmp_path, "tiny_serve", 0, seconds=2)
    check_schema(line, False)
    assert set(line["metrics"]) == {"ttft_p50_ms", "ttft_p90_ms",
                                    "tpot_p50_ms", "setup_s"}
    assert line["attempted"] == 12 and info["notes"]["check_requests"] == 3
    assert info["notes"]["check_worst_logit_gap"] <= info["notes"]["check_tol"]
    assert line["checks"]["worst_logit_gap"]["limit"] == info["notes"]["check_tol"]
    check_setup(line, info)
    # the machine beside the program, in every untraced run's notes
    n = info["notes"]
    assert n["window_stolen_ms"] == pytest.approx(
        1e3 * (n["window_wall_s"] - n["window_thread_cpu_s"]
               - n["window_engine_wait_s"]))
    assert n["window_wall_s"] >= 2 and n["window_thread_cpu_s"] > 0
    traced, _, p = rehearse(tmp_path, "tiny_serve", 1, seconds=2)
    check_schema(traced, True)
    assert {"admit_ms_p50", "prefix_hit_rate", "tick_ms_p50",
            "slot_occupancy", "window_stolen_ms"} <= set(traced["metrics"])
    assert "prefill_tick_share" not in traced["metrics"]
    assert 0 < traced["metrics"]["prefix_hit_rate"]["value"] < 100
    # every committed file of the benchmark is byte for byte in the copy:
    # the throw-away cell, mix, configuration and metric only ADDED files
    added = p.stderr.split("rehearsal: added ")[1].splitlines()[0].split()
    assert "benchmark/metrics/admit_ms_p50.py" in added
    cmp = filecmp.dircmp(os.path.join(REPO, "benchmark"),
                         os.path.join(tmp_path, "benchmark"),
                         ignore=["__pycache__"])

    def walk(c, prefix="benchmark"):
        assert not c.diff_files and not c.left_only, (prefix, c.diff_files)
        for name in c.right_only:
            assert f"{prefix}/{name}" in added
        for name, sub in c.subdirs.items():
            walk(sub, f"{prefix}/{name}")
    walk(cmp)


def test_no_tpu_no_result():
    """Without the rehearsal's stand-in the benchmark refuses a CPU."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "benchmark/run.py", "--workload",
                        "lm-big_train_1chip", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=REPO, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "runs on a TPU and nowhere else" in p.stderr
    assert not p.stdout.strip().startswith("{")
