"""Multi-process distributed tests: real jax.distributed bootstrap + elastic
kill/reassign/resume.

≙ reference test_dist_base.py:27 (forked localhost pserver/trainer harness)
and go/master/service.go:313 (task lease timeout -> requeue). Two scenarios:

1. Two localhost processes join one jax.distributed world through
   paddle_tpu.distributed.init_parallel_env (the PADDLE_* env protocol), form
   a global device mesh spanning both processes, and run a cross-process
   collective — the capability the reference proves with its nccl2 tests.

2. Elastic training: a master leases dataset chunks to two trainer
   subprocesses which chain model state through a locked checkpoint
   directory. One trainer is hard-killed mid-lease; the master requeues the
   expired lease, the survivor trains the reassigned chunk, and the final
   loss matches a single-process sequential run within a small delta.
"""

from __future__ import annotations

import json
import os
import socket
import subprocess
import sys
import time

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Preamble for every child: CPU-only jax (children do not inherit
# conftest's bootstrap).
_BOOT = r"""
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax
sys.path.insert(0, __REPO__)
"""


def _script(body):
    """Template a child script (scripts contain literal {} so str.format is
    unusable)."""
    return body.replace("__REPO__", repr(REPO))


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


# ---------------------------------------------------------------------------
# 1. jax.distributed bootstrap through the framework env protocol
# ---------------------------------------------------------------------------

_JOIN_SCRIPT = _BOOT + r"""
import json
import jax.numpy as jnp
from paddle_tpu.distributed import init_parallel_env
from paddle_tpu.distributed.env import global_rank, world_size

env = init_parallel_env()          # reads the PADDLE_* vars from os.environ
assert world_size() == 2, world_size()
assert global_rank() == env.trainer_id

# global mesh across both processes; one cross-process collective
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import make_array_from_process_local_data
mesh = Mesh(jax.devices(), ("dp",))
local = jnp.ones((2, 4)) * (env.trainer_id + 1)   # rank0: 1s, rank1: 2s
garr = make_array_from_process_local_data(
    NamedSharding(mesh, P("dp")), local, (4, 4))
total = jax.jit(lambda a: a.sum(),
                out_shardings=NamedSharding(mesh, P()))(garr)
print(json.dumps({"rank": env.trainer_id,
                  "world": world_size(),
                  "global_devices": len(jax.devices()),
                  "sum": float(total)}), flush=True)
"""


def test_two_process_jax_distributed_bootstrap(tmp_path):
    port = _free_port()
    procs = []
    for rank in range(2):
        env = dict(os.environ)
        env.update({
            "PADDLE_TRAINING_ROLE": "TRAINER",
            "PADDLE_TRAINER_ID": str(rank),
            "PADDLE_TRAINERS_NUM": "2",
            "PADDLE_COORDINATOR_ENDPOINT": f"127.0.0.1:{port}",
        })
        procs.append(subprocess.Popen(
            [sys.executable, "-c", _script(_JOIN_SCRIPT)],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env=env, cwd=str(tmp_path)))
    results = {}
    for p in procs:
        out, err = p.communicate(timeout=150)
        assert p.returncode == 0, f"child failed:\n{err[-2000:]}"
        rec = json.loads(out.strip().splitlines()[-1])
        results[rec["rank"]] = rec
    assert set(results) == {0, 1}
    for rec in results.values():
        assert rec["world"] == 2
        assert rec["global_devices"] == 4      # 2 virtual cpu devs/process
        # rows: two of 1s (rank 0) + two of 2s (rank 1), each of width 4
        assert rec["sum"] == 24.0


# ---------------------------------------------------------------------------
# 1b. multi-process ParallelExecutor: the framework's OWN PE program runs
#     across two processes (2 virtual devices each) on one global 4-device
#     mesh, and its loss trajectory matches the single-process 4-device run.
#     ≙ reference test_dist_base.py:27 proving the real trainer program
#     multi-process over an nccl2 world (nccl_helper.h:118).
# ---------------------------------------------------------------------------

_PE_MODEL = r"""
import numpy as np


def build_and_train(steps=6, reduce_strategy=False, fused=False):
    import paddle_tpu as pt
    from paddle_tpu import layers
    from paddle_tpu.parallel import (BuildStrategy, DeviceMesh,
                                     ParallelExecutor, ReduceStrategy)
    from paddle_tpu.core import unique_name
    import jax

    with unique_name.guard():
        x = layers.data("x", shape=[8])
        y = layers.data("y", shape=[1])
        h = layers.fc(x, size=16, act="relu", name="pe_fc1")
        pred = layers.fc(h, size=1, name="pe_fc2")
        loss = layers.reduce_mean(layers.square(pred - y))
        pt.optimizer.MomentumOptimizer(learning_rate=0.05,
                                       momentum=0.9).minimize(loss)
    exe = pt.Executor()
    exe.run(pt.default_startup_program())

    bs = BuildStrategy()
    if reduce_strategy:
        bs.reduce_strategy = ReduceStrategy.Reduce     # ZeRO-1 over dp
    pe = ParallelExecutor(loss_name=loss.name,
                          mesh=DeviceMesh(jax.devices()),
                          build_strategy=bs)

    r = np.random.RandomState(7)
    W = r.randn(8, 1).astype("float32")
    feeds = []
    for i in range(steps):
        rb = np.random.RandomState(100 + i)
        xb = rb.rand(16, 8).astype("float32")          # global batch
        feeds.append({"x": xb, "y": (xb @ W).astype("float32")})
    if fused:
        # scan-fused multi-step loop over the cross-process mesh
        return [float(v) for v in
                pe.run_steps(feeds, fetch_list=[loss.name])[0]]
    return [float(pe.run(feed=f, fetch_list=[loss.name])[0])
            for f in feeds]
"""

_PE_SINGLE = r"""
import json
from pe_model import build_and_train
out = {"plain": build_and_train(), }
import paddle_tpu as pt
pt.reset_default_programs(); pt.reset_global_scope()
out["zero1"] = build_and_train(reduce_strategy=True)
print(json.dumps(out), flush=True)
"""

_PE_MULTI = _BOOT + r"""
import json
import jax
from paddle_tpu.distributed import init_parallel_env

env = init_parallel_env()
assert jax.process_count() == 2
assert len(jax.devices()) == 4
from pe_model import build_and_train
out = {"rank": env.trainer_id, "plain": build_and_train()}
import paddle_tpu as pt
pt.reset_default_programs(); pt.reset_global_scope()
out["zero1"] = build_and_train(reduce_strategy=True)
pt.reset_default_programs(); pt.reset_global_scope()
out["fused"] = build_and_train(fused=True)
print(json.dumps(out), flush=True)
"""


def test_multiprocess_parallel_executor_loss_parity(tmp_path):
    with open(tmp_path / "pe_model.py", "w") as f:
        f.write(_PE_MODEL)

    # single-process reference: one child with 4 virtual devices
    boot4 = _BOOT.replace('host_platform_device_count=2',
                          'host_platform_device_count=4')
    ref = subprocess.run(
        [sys.executable, "-c", _script(boot4 + _PE_SINGLE)],
        capture_output=True, text=True, timeout=300, cwd=str(tmp_path))
    assert ref.returncode == 0, ref.stderr[-2000:]
    ref_losses = json.loads(ref.stdout.strip().splitlines()[-1])

    # two processes x 2 devices = the SAME 4-device global mesh
    port = _free_port()
    procs = []
    for rank in range(2):
        env = dict(os.environ)
        env.update({
            "PADDLE_TRAINING_ROLE": "TRAINER",
            "PADDLE_TRAINER_ID": str(rank),
            "PADDLE_TRAINERS_NUM": "2",
            "PADDLE_COORDINATOR_ENDPOINT": f"127.0.0.1:{port}",
        })
        procs.append(subprocess.Popen(
            [sys.executable, "-c", _script(_PE_MULTI)],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env=env, cwd=str(tmp_path)))
    results = {}
    for p in procs:
        out, err = p.communicate(timeout=300)
        assert p.returncode == 0, f"child failed:\n{err[-2500:]}"
        rec = json.loads(out.strip().splitlines()[-1])
        results[rec["rank"]] = rec

    assert set(results) == {0, 1}
    # the scan-fused multi-process loop matches the per-step trajectory
    np.testing.assert_allclose(results[0]["fused"], results[0]["plain"],
                               rtol=2e-4)
    np.testing.assert_allclose(results[0]["fused"], results[1]["fused"],
                               rtol=1e-6)
    for variant in ("plain", "zero1"):
        # both ranks observe the identical (replicated-fetch) trajectory
        np.testing.assert_allclose(results[0][variant], results[1][variant],
                                   rtol=1e-6)
        # and it matches the single-process 4-device run: same global
        # batch, same seeded init, same SPMD program — only the process
        # split differs (collective reduction order -> tiny fp delta)
        np.testing.assert_allclose(results[0][variant],
                                   ref_losses[variant], rtol=2e-4)
        # real training happened
        assert results[0][variant][-1] < results[0][variant][0]


# ---------------------------------------------------------------------------
# 2. elastic: kill a trainer mid-lease, master requeues, survivor resumes
#    from the shared checkpoint chain
# ---------------------------------------------------------------------------

# Deterministic per-chunk regression data; the model is a single fc layer so
# the run is fast and the loss trajectory is smooth.
_TRAINER_SCRIPT = _BOOT + r"""
import fcntl, json
import numpy as np

endpoint, worker_id, ckpt_dir, lock_path, die_after, result_path = \
    sys.argv[1:7]
die_after = int(die_after)

import paddle_tpu as pt
from paddle_tpu.distributed import MasterClient
from chunk_common import train_chunk, build

exe, loss_var, step_fn = build()
client = MasterClient(endpoint, worker_id=worker_id)
done = []
losses = []
for task_id, chunks in client.tasks(poll_interval_s=0.1, max_polls=100):
    if die_after and len(done) >= die_after:
        os._exit(9)                    # hard crash while holding the lease
    with open(lock_path, "w") as lk:
        fcntl.flock(lk, fcntl.LOCK_EX)
        try:
            if os.path.exists(os.path.join(ckpt_dir, "params")):
                pt.io.load_persistables(exe, os.path.join(ckpt_dir, "params"))
            for chunk in chunks:
                losses.append(train_chunk(step_fn, chunk))
                done.append(chunk)
            os.makedirs(os.path.join(ckpt_dir, "params"), exist_ok=True)
            pt.io.save_persistables(exe, os.path.join(ckpt_dir, "params"))
        finally:
            fcntl.flock(lk, fcntl.LOCK_UN)
    client.task_finished(task_id)
with open(result_path, "w") as f:
    json.dump({"worker": worker_id, "done": done, "losses": losses}, f)
"""

_CHUNK_COMMON = r"""
import numpy as np

W_TRUE = np.arange(1, 5, dtype="float32").reshape(4, 1) / 4.0


def chunk_data(chunk):
    seed = int(chunk[1:])
    r = np.random.RandomState(seed)
    x = r.rand(16, 4).astype("float32")
    y = (x @ W_TRUE).astype("float32")
    return x, y


def build():
    import paddle_tpu as pt
    from paddle_tpu import layers
    from paddle_tpu.core import unique_name
    with unique_name.guard():
        x = layers.data("x", shape=[4])
        y = layers.data("y", shape=[1])
        pred = layers.fc(x, size=1, name="el_fc", bias_attr=False)
        loss = layers.reduce_mean(layers.square(pred - y))
        pt.optimizer.SGDOptimizer(learning_rate=0.2).minimize(loss)
    exe = pt.Executor()
    exe.run(pt.default_startup_program())

    def step_fn(xb, yb):
        return float(exe.run(feed={"x": xb, "y": yb},
                             fetch_list=[loss])[0])
    return exe, loss, step_fn


def train_chunk(step_fn, chunk, steps=5):
    xb, yb = chunk_data(chunk)
    last = None
    for _ in range(steps):
        last = step_fn(xb, yb)
    return last
"""


def test_elastic_kill_reassign_resume(tmp_path):
    """Kill a trainer mid-lease; master requeues; survivor resumes from the
    checkpoint chain; final loss matches a single-process sequential run."""
    from paddle_tpu.distributed import Master

    with open(tmp_path / "chunk_common.py", "w") as f:
        f.write(_CHUNK_COMMON)

    chunks = [f"c{i}" for i in range(8)]

    base_script = (_BOOT + r"""
import json
from chunk_common import build, train_chunk
exe, loss, step_fn = build()
losses = [train_chunk(step_fn, c) for c in CHUNKS]
print(json.dumps(losses), flush=True)
""").replace("CHUNKS", repr(chunks))
    out = subprocess.run(
        [sys.executable, "-c", _script(base_script)],
        capture_output=True, text=True, timeout=150, cwd=str(tmp_path))
    assert out.returncode == 0, out.stderr[-2000:]
    baseline_final = json.loads(out.stdout.strip().splitlines()[-1])[-1]

    m = Master(timeout_s=3.0, max_retry=5)
    server, _ = m.serve_forever()
    host, port = server.server_address
    endpoint = f"{host}:{port}"
    m.set_dataset(chunks)

    ckpt = tmp_path / "ckpt"
    ckpt.mkdir()
    lock = str(tmp_path / "ckpt.lock")

    def spawn(worker_id, die_after):
        return subprocess.Popen(
            [sys.executable, "-c", _script(_TRAINER_SCRIPT),
             endpoint, worker_id, str(ckpt), lock, str(die_after),
             str(tmp_path / f"{worker_id}.json")],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            cwd=str(tmp_path))

    # victim runs alone first: finishes exactly 2 chunks, then hard-crashes
    # the moment it leases its 3rd — deterministic, no scheduling race
    victim = spawn("victim", die_after=2)
    v_out, v_err = victim.communicate(timeout=200)
    assert victim.returncode == 9, f"victim should crash:\n{v_err[-1500:]}"

    # survivor joins after the crash; the victim's expired lease requeues
    # (timeout_s=3) and the survivor trains the reassigned chunk too
    survivor = spawn("survivor", die_after=0)
    s_out, s_err = survivor.communicate(timeout=200)
    server.shutdown()
    assert survivor.returncode == 0, f"survivor failed:\n{s_err[-1500:]}"

    with open(tmp_path / "survivor.json") as f:
        surv = json.load(f)

    stats = m.stats()
    # every chunk finished despite the crash: the victim's expired lease was
    # requeued and trained by the survivor
    assert stats["done"] == len(chunks), stats
    trained = sorted(surv["done"])
    victim_trained = sorted(set(chunks) - set(surv["done"]))
    assert len(victim_trained) == 2          # the two the victim finished
    assert sorted(set(trained + victim_trained)) == chunks

    # loss parity vs the sequential single-process run: same chunk multiset
    # through the same checkpoint-chained model, only the order differs
    elastic_final = surv["losses"][-1]
    assert elastic_final < 0.05, elastic_final      # actually converged
    assert abs(elastic_final - baseline_final) < 0.05, (
        elastic_final, baseline_final)


# ---------------------------------------------------------------------------
# 3. elastic WORLD RESIZE: one of two jax.distributed processes is
#    hard-killed; the chief detects the failure through master heartbeats
#    (FailureDetector), re-execs itself into a 1-process world, restores
#    from the SHARDED checkpoint written by both processes, and training
#    continues with loss parity vs an uninterrupted run.
#    ≙ SURVEY §5 failure-detection row + hard part #3 (XLA worlds are
#    static -> checkpoint-restart elasticity); reference
#    go/master/service.go:313 task requeue + etcd liveness.
# ---------------------------------------------------------------------------

_RESIZE_MODEL = r"""
import numpy as np


def build():
    import paddle_tpu as pt
    from paddle_tpu import layers
    from paddle_tpu.core import unique_name
    with unique_name.guard():
        x = layers.data("x", shape=[8])
        y = layers.data("y", shape=[1])
        h = layers.fc(x, size=16, act="relu", name="rz_fc1")
        pred = layers.fc(h, size=1, name="rz_fc2")
        loss = layers.reduce_mean(layers.square(pred - y))
        pt.optimizer.MomentumOptimizer(learning_rate=0.05,
                                       momentum=0.9).minimize(loss)
    exe = pt.Executor()
    exe.run(pt.default_startup_program())
    return exe, loss


def global_batch(i):
    r = np.random.RandomState(7)
    W = r.randn(8, 1).astype("float32")
    rb = np.random.RandomState(100 + i)
    xb = rb.rand(16, 8).astype("float32")
    return xb, (xb @ W).astype("float32")


def pe_step(pe, loss, i):
    xb, yb = global_batch(i)
    return float(pe.run(feed={"x": xb, "y": yb},
                        fetch_list=[loss.name])[0])
"""

_RESIZE_JOINT_STEPS = 4
_RESIZE_TOTAL_STEPS = 8

_RESIZE_CHIEF = _BOOT + r"""
import glob, json, threading, time
import numpy as np
import jax

import paddle_tpu as pt
from paddle_tpu.distributed import init_parallel_env, MasterClient
from paddle_tpu.distributed.elastic import FailureDetector
from paddle_tpu.parallel import DeviceMesh, ParallelExecutor
from resize_model import build, pe_step

WORK = os.environ["RESIZE_WORKDIR"]
PHASE = os.environ.get("RESIZE_PHASE", "joint")
MASTER = os.environ["RESIZE_MASTER"]

client = MasterClient(MASTER, worker_id="chief")
stop_hb = threading.Event()
def hb():
    while not stop_hb.is_set():
        try:
            client.heartbeat()
        except Exception:
            pass
        time.sleep(0.2)
threading.Thread(target=hb, daemon=True).start()

def latest_complete_ckpt():
    dirs = sorted(glob.glob(os.path.join(WORK, "ckpt", "step-*")))
    best = None
    for d in dirs:
        if len(glob.glob(os.path.join(d, "manifest-*.json"))) == 2:
            best = d
    return best

if PHASE == "joint":
    env = init_parallel_env()
    assert jax.process_count() == 2
    exe, loss = build()
    pe = ParallelExecutor(loss_name=loss.name,
                          mesh=DeviceMesh(jax.devices()))
    losses = []
    for i in range(JOINT):
        losses.append(pe_step(pe, loss, i))
        d = os.path.join(WORK, "ckpt", f"step-{i}")
        pt.io.save_persistables(dirname=d, sharded=True)
        # wait until BOTH processes finished writing this step's shards
        while len(glob.glob(os.path.join(d, "manifest-*.json"))) < 2:
            time.sleep(0.05)
    with open(os.path.join(WORK, "chief_joint.json"), "w") as f:
        json.dump(losses, f)

    # joint quota done: hold here, heartbeating, until the peer's death is
    # DETECTED (not assumed) through the master heartbeat horizon
    failed = threading.Event()
    # own client: xmlrpc ServerProxy is not thread-safe, and the heartbeat
    # thread is still using `client`
    det_client = MasterClient(MASTER, worker_id="chief-detector")
    det = FailureDetector(det_client, expected_workers={"peer"},
                          horizon_s=1.5, poll_s=0.2, grace_s=60.0)
    det.start(lambda dead: failed.set())
    assert failed.wait(timeout=120), "peer death was never detected"
    det.stop()

    # restart-based elasticity (XLA worlds are static): re-exec into a
    # 1-process world and resume from the sharded checkpoint
    env2 = dict(os.environ)
    env2.update({"RESIZE_PHASE": "solo", "PADDLE_TRAINERS_NUM": "1",
                 "PADDLE_TRAINER_ID": "0"})
    env2.pop("PADDLE_COORDINATOR_ENDPOINT", None)
    stop_hb.set()
    os.execve(sys.executable, [sys.executable, sys.argv[0]], env2)

else:  # solo: fresh 1-process world over the local 2-device mesh
    env = init_parallel_env()
    assert jax.process_count() == 1
    exe, loss = build()
    ck = latest_complete_ckpt()
    assert ck is not None
    pt.io.load_persistables(dirname=ck, sharded=True)
    resume_from = int(os.path.basename(ck).split("-")[1]) + 1
    pe = ParallelExecutor(loss_name=loss.name,
                          mesh=DeviceMesh(jax.devices()))
    losses = []
    for i in range(resume_from, TOTAL):
        losses.append(pe_step(pe, loss, i))
    with open(os.path.join(WORK, "chief_solo.json"), "w") as f:
        json.dump({"resume_from": resume_from, "losses": losses}, f)
""".replace("JOINT", str(_RESIZE_JOINT_STEPS)).replace(
    "TOTAL", str(_RESIZE_TOTAL_STEPS))

_RESIZE_PEER = _BOOT + r"""
import glob, json, threading, time
import jax

import paddle_tpu as pt
from paddle_tpu.distributed import init_parallel_env, MasterClient
from paddle_tpu.parallel import DeviceMesh, ParallelExecutor
from resize_model import build, pe_step

WORK = os.environ["RESIZE_WORKDIR"]
client = MasterClient(os.environ["RESIZE_MASTER"], worker_id="peer")
def hb():
    while True:
        try:
            client.heartbeat()
        except Exception:
            pass
        time.sleep(0.2)
threading.Thread(target=hb, daemon=True).start()

env = init_parallel_env()
exe, loss = build()
pe = ParallelExecutor(loss_name=loss.name, mesh=DeviceMesh(jax.devices()))
for i in range(JOINT):
    pe_step(pe, loss, i)
    d = os.path.join(WORK, "ckpt", f"step-{i}")
    pt.io.save_persistables(dirname=d, sharded=True)
    while len(glob.glob(os.path.join(d, "manifest-*.json"))) < 2:
        time.sleep(0.05)
with open(os.path.join(WORK, "peer_done"), "w") as f:
    f.write("ok")
time.sleep(600)   # idle (heartbeating) until the parent SIGKILLs us
""".replace("JOINT", str(_RESIZE_JOINT_STEPS))

_RESIZE_REF = _BOOT + r"""
import json
from resize_model import build, pe_step
import jax
from paddle_tpu.parallel import DeviceMesh, ParallelExecutor
exe, loss = build()
pe = ParallelExecutor(loss_name=loss.name, mesh=DeviceMesh(jax.devices()))
print(json.dumps([pe_step(pe, loss, i) for i in range(TOTAL)]), flush=True)
""".replace("TOTAL", str(_RESIZE_TOTAL_STEPS))


def test_elastic_world_resize(tmp_path):
    import signal as _signal

    from paddle_tpu.distributed import Master

    with open(tmp_path / "resize_model.py", "w") as f:
        f.write(_RESIZE_MODEL)
    (tmp_path / "ckpt").mkdir()

    m = Master(timeout_s=5.0)
    server, _ = m.serve_forever()
    host, port = server.server_address
    master_ep = f"{host}:{port}"

    # uninterrupted reference: single process, 4 virtual devices
    boot4 = _BOOT.replace('host_platform_device_count=2',
                          'host_platform_device_count=4')
    ref = subprocess.run(
        [sys.executable, "-c", _script(boot4 + _RESIZE_REF.split(_BOOT)[1])],
        capture_output=True, text=True, timeout=300, cwd=str(tmp_path))
    assert ref.returncode == 0, ref.stderr[-2000:]
    ref_losses = json.loads(ref.stdout.strip().splitlines()[-1])

    coord_port = _free_port()
    chief_path = tmp_path / "chief.py"
    with open(chief_path, "w") as f:
        f.write(_script(_RESIZE_CHIEF))

    def env_for(rank):
        env = dict(os.environ)
        env.update({
            "PADDLE_TRAINING_ROLE": "TRAINER",
            "PADDLE_TRAINER_ID": str(rank),
            "PADDLE_TRAINERS_NUM": "2",
            "PADDLE_COORDINATOR_ENDPOINT": f"127.0.0.1:{coord_port}",
            "RESIZE_WORKDIR": str(tmp_path),
            "RESIZE_MASTER": master_ep,
        })
        return env

    chief = subprocess.Popen(
        [sys.executable, str(chief_path)],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        env=env_for(0), cwd=str(tmp_path))
    peer = subprocess.Popen(
        [sys.executable, "-c", _script(_RESIZE_PEER)],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        env=env_for(1), cwd=str(tmp_path))

    # wait for the peer to finish its joint quota, then murder it
    deadline = time.time() + 240
    while not (tmp_path / "peer_done").exists():
        assert time.time() < deadline, "joint phase never completed"
        if peer.poll() is not None:
            _, perr = peer.communicate()
            raise AssertionError(f"peer died early:\n{perr[-2000:]}")
        if chief.poll() is not None:
            _, cerr = chief.communicate()
            raise AssertionError(f"chief died early:\n{cerr[-2000:]}")
        time.sleep(0.2)
    peer.send_signal(_signal.SIGKILL)
    peer.wait(timeout=30)

    out, err = chief.communicate(timeout=300)
    server.shutdown()
    assert chief.returncode == 0, f"chief failed:\n{err[-3000:]}"

    with open(tmp_path / "chief_joint.json") as f:
        joint = json.load(f)
    with open(tmp_path / "chief_solo.json") as f:
        solo = json.load(f)

    # detection -> resize really happened where expected
    assert solo["resume_from"] == _RESIZE_JOINT_STEPS
    full = joint + solo["losses"]
    assert len(full) == _RESIZE_TOTAL_STEPS
    # same global batches, same math, different world shape: parity with
    # the uninterrupted run within collective-reorder tolerance
    np.testing.assert_allclose(full, ref_losses, rtol=2e-4)
    assert full[-1] < full[0]


# ---------------------------------------------------------------------------
# 4. multi-process sharded save_checkpoint: the barrier-separated commit
#    protocol (chief cleans -> all write shards -> chief marks _SUCCESS)
#    produces exactly one complete serial dir that load_checkpoint restores.
# ---------------------------------------------------------------------------

_CKPT_SCRIPT = _BOOT + r"""
import json
import numpy as np
import jax

import paddle_tpu as pt
from paddle_tpu import layers
from paddle_tpu.distributed import init_parallel_env
from paddle_tpu.trainer import (get_latest_checkpoint_serial,
                                load_checkpoint, save_checkpoint)

env = init_parallel_env()
root = os.environ["CKPT_ROOT"]

x = layers.data("x", shape=[4])
w_out = layers.fc(x, size=2, name="mpfc")
exe = pt.Executor()
exe.run(pt.default_startup_program())

serial = save_checkpoint(exe, root, pt.default_main_program(),
                         trainer_args={"step": 5}, sharded=True)
# both processes agree on the serial and see a COMPLETE checkpoint
assert serial == 0, serial
assert get_latest_checkpoint_serial(root) == 0

w_before = np.asarray(pt.global_scope().get("mpfc.w_0"))
pt.reset_global_scope()
args = load_checkpoint(exe, root, pt.default_main_program(), sharded=True)
assert args == {"step": 5}, args
np.testing.assert_array_equal(
    np.asarray(pt.global_scope().get("mpfc.w_0")), w_before)

# a second save lands in serial 1 on every process (no split-brain dirs)
serial2 = save_checkpoint(exe, root, pt.default_main_program(),
                          trainer_args={"step": 9}, sharded=True)
assert serial2 == 1, serial2
print(json.dumps({"rank": env.trainer_id, "ok": True}), flush=True)
"""


def test_multiprocess_sharded_save_checkpoint(tmp_path):
    port = _free_port()
    procs = []
    for rank in range(2):
        env = dict(os.environ)
        env.update({
            "PADDLE_TRAINING_ROLE": "TRAINER",
            "PADDLE_TRAINER_ID": str(rank),
            "PADDLE_TRAINERS_NUM": "2",
            "PADDLE_COORDINATOR_ENDPOINT": f"127.0.0.1:{port}",
            "CKPT_ROOT": str(tmp_path / "ck"),
        })
        procs.append(subprocess.Popen(
            [sys.executable, "-c", _script(_CKPT_SCRIPT)],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env=env, cwd=str(tmp_path)))
    for p in procs:
        out, err = p.communicate(timeout=300)
        assert p.returncode == 0, f"child failed:\n{err[-2500:]}"
        assert json.loads(out.strip().splitlines()[-1])["ok"]
    # one complete dir per serial, two manifests each (one per process)
    import glob
    for serial in (0, 1):
        d = tmp_path / "ck" / f"checkpoint_{serial}"
        assert (d / "_SUCCESS").exists()
        assert len(glob.glob(str(d / "manifest-*.json"))) == 2
