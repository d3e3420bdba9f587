"""Distributed job layer tests.

≙ reference go/master tests (task dispatch/retry/snapshot semantics,
go/master/service.go) and test_dist_base.py's forked-local-subprocess
pattern (tests run master + workers on 127.0.0.1, no cluster).
"""

import time

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers
from paddle_tpu.distributed import (ElasticTrainer, FailureDetector, Master,
                                    MasterClient, PreemptionGuard, parse_env)


class TestMasterQueue:
    def test_dispatch_and_finish_full_pass(self):
        m = Master(timeout_s=60)
        n = m.set_dataset([f"chunk{i}" for i in range(6)])
        assert n == 6
        seen = []
        while True:
            t = m.get_task("w0")
            if t is None:
                break
            seen.extend(t["chunks"])
            m.task_finished(t["task_id"])
        assert sorted(seen) == [f"chunk{i}" for i in range(6)]
        assert m.stats()["done"] == 6

    def test_new_pass_recycles_done(self):
        m = Master(num_passes=2)
        m.set_dataset(["a", "b"])
        for _ in range(2):
            t = m.get_task()
            m.task_finished(t["task_id"])
        # all done -> next get_task starts epoch 1 (the final pass)
        t = m.get_task()
        assert t is not None and t["epoch"] == 1
        m.task_finished(t["task_id"])
        t = m.get_task()
        assert t is not None and t["epoch"] == 1
        m.task_finished(t["task_id"])
        assert m.get_task() is None   # num_passes exhausted

    def test_timeout_requeues_with_failure_count(self):
        m = Master(timeout_s=0.05, max_retry=3)
        m.set_dataset(["a"])
        t1 = m.get_task("w0")
        assert t1 is not None
        time.sleep(0.1)
        t2 = m.get_task("w1")    # lease expired -> requeued -> re-leased
        assert t2 is not None and t2["task_id"] == t1["task_id"]

    def test_max_retry_discards(self):
        m = Master(timeout_s=60, max_retry=2)
        m.set_dataset(["a"])
        for _ in range(2):
            t = m.get_task()
            m.task_failed(t["task_id"])
        assert m.get_task() is None
        assert m.stats()["discarded"] == 1

    def test_finish_unknown_task_rejected(self):
        m = Master()
        m.set_dataset(["a"])
        assert m.task_finished(123) is False

    def test_snapshot_recover(self, tmp_path):
        snap = str(tmp_path / "master.snap")
        m = Master(snapshot_path=snap, timeout_s=60)
        m.set_dataset(["a", "b", "c"])
        t = m.get_task("w0")
        m.task_finished(t["task_id"])
        t2 = m.get_task("w0")          # leave one pending
        del m

        m2 = Master(snapshot_path=snap, timeout_s=60)
        s = m2.stats()
        # pending lease did not survive: it is back in todo
        assert s["done"] == 1 and s["pending"] == 0 and s["todo"] == 2
        remaining = set()
        while True:
            t = m2.get_task("w1")
            if t is None:
                break
            remaining.update(t["chunks"])
            m2.task_finished(t["task_id"])
        assert len(remaining) == 2

    def test_heartbeat_liveness(self):
        m = Master()
        m.heartbeat("w0")
        m.heartbeat("w1")
        assert m.live_workers(horizon_s=10) == ["w0", "w1"]
        assert m.live_workers(horizon_s=0) == []


# Worker subprocess: loads ONLY master.py by file path — importing the full
# paddle_tpu package in a bare child would pull in jax, which the lease
# protocol under test does not need.
_WORKER_SCRIPT = r"""
import importlib.util, json, sys
spec = importlib.util.spec_from_file_location("ptd_master", sys.argv[1])
mod = importlib.util.module_from_spec(spec)
sys.modules["ptd_master"] = mod   # dataclasses needs the module registered
spec.loader.exec_module(mod)
endpoint, worker_id, fail_first = sys.argv[2], sys.argv[3], sys.argv[4] == "1"
c = mod.MasterClient(endpoint, worker_id=worker_id)
done, failed_once = [], False
for task_id, chunks in c.tasks(poll_interval_s=0.05, max_polls=10):
    if fail_first and not failed_once:
        failed_once = True
        c.task_failed(task_id)
        continue
    done.extend(chunks)
    c.task_finished(task_id)
print(json.dumps({"worker": worker_id, "done": done}))
"""


class TestMasterMultiProcess:
    def test_two_workers_share_dataset_with_retry(self):
        import subprocess
        import sys
        import json
        from paddle_tpu.distributed import master as master_mod

        m = Master(timeout_s=30, max_retry=5)
        server, _ = m.serve_forever()
        host, port = server.server_address
        endpoint = f"{host}:{port}"
        m.set_dataset([f"c{i}" for i in range(8)])

        master_path = master_mod.__file__
        procs = [
            subprocess.Popen([sys.executable, "-c", _WORKER_SCRIPT,
                              master_path, endpoint, wid, fail],
                             stdout=subprocess.PIPE, text=True)
            for wid, fail in (("w0", "1"), ("w1", "0"))
        ]
        got = {}
        for p in procs:
            out, _ = p.communicate(timeout=60)
            rec = json.loads(out.strip().splitlines()[-1])
            got[rec["worker"]] = rec["done"]
        server.shutdown()

        all_chunks = sorted(got.get("w0", []) + got.get("w1", []))
        # every chunk processed exactly once per pass despite the failure
        assert all_chunks == sorted(f"c{i}" for i in range(8))


class TestEnv:
    def test_parse_env_roles(self):
        env = parse_env({"PADDLE_TRAINING_ROLE": "pserver",
                         "PADDLE_TRAINER_ID": "3",
                         "PADDLE_TRAINERS_NUM": "8",
                         "PADDLE_COORDINATOR_ENDPOINT": "10.0.0.1:1234",
                         "PADDLE_PSERVER_IPS": "a:1,b:2"})
        assert env.training_role == "PSERVER"
        assert env.trainer_id == 3 and env.num_trainers == 8
        assert env.coordinator == "10.0.0.1:1234"
        assert env.pserver_endpoints == ("a:1", "b:2")
        assert not env.is_chief

    def test_single_host_bootstrap_noop(self):
        from paddle_tpu.distributed import init_parallel_env
        env = init_parallel_env(parse_env({}))  # no coordinator -> no-op
        assert env.num_trainers == 1


class TestElasticTrainer:
    def _build(self):
        from paddle_tpu.core import unique_name
        with unique_name.guard():   # stable param names across rebuilds
            x = layers.data("x", shape=[4])
            loss = layers.mean(layers.fc(x, size=4, name="el_fc"))
            pt.optimizer.SGDOptimizer(learning_rate=0.1).minimize(loss)
        exe = pt.Executor()
        exe.run(pt.default_startup_program())
        return exe, loss

    def test_preemption_checkpoint_and_resume(self, rng, tmp_path):
        exe, loss = self._build()
        guard = PreemptionGuard(signals=())
        et = ElasticTrainer(exe, str(tmp_path / "ckpt"),
                            save_interval_steps=1000, guard=guard)
        feed = {"x": rng.rand(4, 4).astype("float32")}

        def step(i):
            if i == 4:
                guard.request()       # preemption mid-run
            return exe.run(feed=feed, fetch_list=[loss])[0]

        out = et.run(step, num_steps=100)
        assert out["preempted"] and out["last_step"] == 4

        # "restart": fresh scope, resume from checkpoint, continue to end
        pt.reset_global_scope()
        pt.reset_default_programs()
        exe2, loss2 = self._build()
        w_before = np.asarray(pt.global_scope().get("el_fc.w_0")).copy()
        et2 = ElasticTrainer(exe2, str(tmp_path / "ckpt"),
                             save_interval_steps=1000)
        assert et2.resume_step() == 4
        w_after = np.asarray(pt.global_scope().get("el_fc.w_0"))
        assert not np.allclose(w_before, w_after)  # restored trained weights

        out2 = et2.run(lambda i: exe2.run(feed=feed,
                                          fetch_list=[loss2])[0],
                       num_steps=10)
        assert out2["last_step"] == 9 and not out2["preempted"]

    def test_failure_detector_fires(self):
        m = Master()
        m.heartbeat("w0")
        fired = []
        det = FailureDetector(m, expected_workers={"w0", "w1"},
                              horizon_s=10, poll_s=0.01, grace_s=0)
        det.start(lambda dead: fired.append(dead))
        time.sleep(0.2)
        det.stop()
        assert fired and fired[0] == {"w1"}

    def test_failure_detector_grace_tolerates_slow_boot(self):
        # workers that have not yet joined must not count as dead during
        # the startup grace window; ones that joined and vanished do
        m = Master()
        fired = []
        det = FailureDetector(m, expected_workers={"w0", "w1"},
                              horizon_s=0.1, poll_s=0.01, grace_s=30)
        det.start(lambda dead: fired.append(dead))
        time.sleep(0.1)
        assert not fired          # nobody joined yet -> silence, not alarm
        m.heartbeat("w0")         # w0 boots...
        time.sleep(0.3)           # ...then misses the 0.1s horizon
        det.stop()
        assert fired and fired[0] == {"w0"}
