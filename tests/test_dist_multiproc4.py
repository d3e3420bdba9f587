"""Multi-process (N > 2) distributed depth tests (VERDICT r3 #2 / #5).

≙ reference test_dist_base.py:27 forking N-trainer worlds over
nccl_helper.h:118's multi-rank bootstrap. Capabilities the 2-process
suite (test_dist_multiproc.py) cannot witness:

1. FOUR- and EIGHT-process jax.distributed worlds;
2. a dp×tp mesh whose TENSOR-parallel groups span process boundaries
   (tp=4 over 2-device processes ⇒ every tp collective crosses processes),
   with loss parity against the single-process 8-device run — plain,
   scan-fused run_steps, and ZeRO-1;
3. a pp=8 pipeline ring and an 8-way-sharded embedding table whose every
   ppermute hop / psum combine crosses processes;
4. elastic resize 4→2: a 4-process world saves a sharded checkpoint
   (4 per-process shard manifests), a FRESH 2-process world re-shards it
   onto half the processes and finishes training with loss parity against
   an uninterrupted single-process run.
"""

from __future__ import annotations

import glob
import json
import os
import socket
import subprocess
import sys
import time

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_BOOT = r"""
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax
sys.path.insert(0, __REPO__)
"""


def _script(body):
    return body.replace("__REPO__", repr(REPO))


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _spawn_world(tmp_path, script, n, port, extra_env=None):
    procs = []
    for rank in range(n):
        env = dict(os.environ)
        env.update({
            "PADDLE_TRAINING_ROLE": "TRAINER",
            "PADDLE_TRAINER_ID": str(rank),
            "PADDLE_TRAINERS_NUM": str(n),
            "PADDLE_COORDINATOR_ENDPOINT": f"127.0.0.1:{port}",
        })
        env.update(extra_env or {})
        procs.append(subprocess.Popen(
            [sys.executable, "-c", _script(script)],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env=env, cwd=str(tmp_path)))
    return procs


def _join_world(procs, timeout=420):
    results = {}
    for p in procs:
        out, err = p.communicate(timeout=timeout)
        assert p.returncode == 0, f"child failed:\n{err[-3000:]}"
        rec = json.loads(out.strip().splitlines()[-1])
        results[rec["rank"]] = rec
    return results


# ---------------------------------------------------------------------------
# shared tp model: column-parallel fc -> row-parallel fc, tp groups span
# process boundaries on the 4x2 world
# ---------------------------------------------------------------------------

_TP_MODEL = r"""
import numpy as np


def build_and_train(steps=5, fused=False, zero1=False, dp=2, tp=4):
    import jax
    import paddle_tpu as pt
    from paddle_tpu import layers
    from paddle_tpu.core import unique_name
    from paddle_tpu.parallel import (BuildStrategy, DeviceMesh,
                                     ParallelExecutor, ReduceStrategy)

    with unique_name.guard():
        x = layers.data("x", shape=[8])
        y = layers.data("y", shape=[1])
        # column-parallel then row-parallel: the Megatron pair — forward
        # needs one cross-process all-reduce on the row-parallel output
        h = layers.fc(x, size=16, act="relu", name="tp_fc1",
                      param_attr=pt.ParamAttr(name="tp_fc1.w",
                                              sharding_spec=(None, "tp")))
        pred = layers.fc(h, size=1, name="tp_fc2",
                         param_attr=pt.ParamAttr(name="tp_fc2.w",
                                                 sharding_spec=("tp", None)))
        loss = layers.reduce_mean(layers.square(pred - y))
        pt.optimizer.MomentumOptimizer(learning_rate=0.05,
                                       momentum=0.9).minimize(loss)
    exe = pt.Executor()
    exe.run(pt.default_startup_program())

    bs = BuildStrategy()
    if zero1:
        bs.reduce_strategy = ReduceStrategy.Reduce
    mesh = DeviceMesh(jax.devices(), axes={"dp": dp, "tp": tp})
    pe = ParallelExecutor(loss_name=loss.name, mesh=mesh,
                          build_strategy=bs)

    W = np.random.RandomState(7).randn(8, 1).astype("float32")
    feeds = []
    for i in range(steps):
        rb = np.random.RandomState(100 + i)
        xb = rb.rand(16, 8).astype("float32")          # global batch
        feeds.append({"x": xb, "y": (xb @ W).astype("float32")})
    if fused:
        return [float(v) for v in
                pe.run_steps(feeds, fetch_list=[loss.name])[0]]
    return [float(pe.run(feed=f, fetch_list=[loss.name])[0])
            for f in feeds]
"""

_TP_SINGLE = r"""
import json
import paddle_tpu as pt
from tp_model import build_and_train
out = {"plain": build_and_train()}
pt.reset_default_programs(); pt.reset_global_scope()
out["zero1"] = build_and_train(zero1=True)
print(json.dumps(out), flush=True)
"""

_TP_MULTI = _BOOT + r"""
import json
import jax
import paddle_tpu as pt
from paddle_tpu.distributed import init_parallel_env
from tp_model import build_and_train

env = init_parallel_env()
assert jax.process_count() == 4, jax.process_count()
assert len(jax.devices()) == 8
out = {"rank": env.trainer_id, "plain": build_and_train()}
pt.reset_default_programs(); pt.reset_global_scope()
out["zero1"] = build_and_train(zero1=True)
pt.reset_default_programs(); pt.reset_global_scope()
out["fused"] = build_and_train(fused=True)
print(json.dumps(out), flush=True)
"""


def test_four_process_tp_spanning_parity(tmp_path):
    with open(tmp_path / "tp_model.py", "w") as f:
        f.write(_TP_MODEL)

    # single-process reference: 8 virtual devices, same dp=2 x tp=4 mesh
    boot8 = _BOOT.replace("host_platform_device_count=2",
                          "host_platform_device_count=8")
    ref = subprocess.run(
        [sys.executable, "-c", _script(boot8 + _TP_SINGLE)],
        capture_output=True, text=True, timeout=420, cwd=str(tmp_path))
    assert ref.returncode == 0, ref.stderr[-3000:]
    ref_losses = json.loads(ref.stdout.strip().splitlines()[-1])

    procs = _spawn_world(tmp_path, _TP_MULTI, 4, _free_port())
    results = _join_world(procs)

    assert set(results) == {0, 1, 2, 3}
    # scan-fused == per-step on the 4-process world
    np.testing.assert_allclose(results[0]["fused"], results[0]["plain"],
                               rtol=2e-4)
    for variant in ("plain", "zero1"):
        for rank in (1, 2, 3):
            np.testing.assert_allclose(results[0][variant],
                                       results[rank][variant], rtol=1e-6)
        np.testing.assert_allclose(results[0][variant],
                                   ref_losses[variant], rtol=2e-4)
        assert results[0][variant][-1] < results[0][variant][0]


# ---------------------------------------------------------------------------
# pipeline ring spanning processes: pp=8 over 4x2-device processes means
# EVERY ppermute hop crosses a process boundary (the reference never ran a
# pipeline schedule at all; this witnesses ours at multi-host topology)
# ---------------------------------------------------------------------------

_PP_MODEL = r"""
import numpy as np


def run_pipeline():
    import jax
    import jax.numpy as jnp
    from paddle_tpu.parallel import DeviceMesh
    from paddle_tpu.parallel.pipeline import pipeline_apply

    n, d, b, m = 8, 16, 32, 4
    mesh = DeviceMesh(jax.devices(), axes={"pp": n})
    rng = np.random.RandomState(11)
    stacked_w = jnp.asarray(
        rng.randn(n, d, d).astype("float32") / np.sqrt(d))
    x = jnp.asarray(rng.randn(b, d).astype("float32"))
    tgt = jnp.asarray(rng.randn(b, d).astype("float32"))

    def stage_fn(w, h):
        return jnp.tanh(h @ w)

    def loss_fn(w, x):
        y = pipeline_apply(mesh, stage_fn, w, x, num_microbatches=m)
        return jnp.mean((y - tgt) ** 2)

    loss, grad = jax.jit(jax.value_and_grad(loss_fn))(stacked_w, x)
    y = jax.jit(lambda w, x: pipeline_apply(mesh, stage_fn, w, x,
                                            num_microbatches=m))(stacked_w, x)
    return {"loss": float(loss),
            "grad_norm": float(jnp.linalg.norm(grad)),
            "y_head": np.asarray(y)[0, :4].tolist()}
"""

_PP_SINGLE = r"""
import json
from pp_model import run_pipeline
print(json.dumps(run_pipeline()), flush=True)
"""

_PP_MULTI = _BOOT + r"""
import json
import jax
from paddle_tpu.distributed import init_parallel_env
from pp_model import run_pipeline

env = init_parallel_env()
assert jax.process_count() == 4
out = run_pipeline()
out["rank"] = env.trainer_id
print(json.dumps(out), flush=True)
"""


def test_four_process_pipeline_ring_parity(tmp_path):
    with open(tmp_path / "pp_model.py", "w") as f:
        f.write(_PP_MODEL)

    boot8 = _BOOT.replace("host_platform_device_count=2",
                          "host_platform_device_count=8")
    ref = subprocess.run(
        [sys.executable, "-c", _script(boot8 + _PP_SINGLE)],
        capture_output=True, text=True, timeout=420, cwd=str(tmp_path))
    assert ref.returncode == 0, ref.stderr[-3000:]
    expect = json.loads(ref.stdout.strip().splitlines()[-1])

    results = _join_world(_spawn_world(tmp_path, _PP_MULTI, 4, _free_port()))
    assert set(results) == {0, 1, 2, 3}
    for rank in range(4):
        got = results[rank]
        np.testing.assert_allclose(got["loss"], expect["loss"], rtol=2e-5)
        np.testing.assert_allclose(got["grad_norm"], expect["grad_norm"],
                                   rtol=2e-4)
        np.testing.assert_allclose(got["y_head"], expect["y_head"],
                                   rtol=2e-4, atol=1e-6)


# ---------------------------------------------------------------------------
# sharded embedding (EP) spanning processes: the table's 8 row-shards live
# on 4x2-device processes, so every lookup's psum combine crosses process
# boundaries (≙ reference distributed lookup table, the pserver-sharded
# capability; here the gradient also stays sharded)
# ---------------------------------------------------------------------------

_EP_MODEL = r"""
import numpy as np


def run_ep():
    import jax
    import jax.numpy as jnp
    from paddle_tpu.parallel import DeviceMesh
    from paddle_tpu.parallel.sharded_embedding import (
        embedding_table_sharding, sharded_embedding_lookup)

    v, d, n_ids = 64, 8, 12
    mesh = DeviceMesh(jax.devices(), axes={"tp": 8})
    rng = np.random.RandomState(21)
    table_h = rng.randn(v, d).astype("float32")
    ids_h = rng.randint(0, v, (n_ids,))
    table = jax.device_put(jnp.asarray(table_h),
                           embedding_table_sharding(mesh, "tp"))
    ids = jnp.asarray(ids_h.astype("int32"))

    vals = jax.jit(
        lambda t, i: sharded_embedding_lookup(mesh, t, i, "tp"))(table, ids)
    expect = table_h[ids_h]

    def loss_fn(t):
        y = sharded_embedding_lookup(mesh, t, ids, "tp")
        return jnp.sum(y * y)

    grad = jax.jit(jax.grad(loss_fn))(table)
    # dense reference: d/dt sum((t[ids])^2) scatters 2*t[row] per hit
    gref = np.zeros_like(table_h)
    for r in ids_h:
        gref[r] += 2.0 * table_h[r]
    # the gradient is row-sharded across PROCESSES (non-addressable here),
    # so compare in-graph and fetch only replicated scalars
    gerr = jax.jit(lambda g: jnp.max(jnp.abs(g - jnp.asarray(gref))))(grad)
    gnorm = jax.jit(jnp.linalg.norm)(grad)
    return {"lookup_ok": bool(np.allclose(np.asarray(vals), expect,
                                          atol=1e-5)),
            "grad_ok": bool(float(gerr) < 1e-4),
            "grad_norm": float(gnorm)}
"""

_EP_MULTI = _BOOT + r"""
import json
import jax
from paddle_tpu.distributed import init_parallel_env
from ep_model import run_ep

env = init_parallel_env()
assert jax.process_count() == 4
out = run_ep()
out["rank"] = env.trainer_id
print(json.dumps(out), flush=True)
"""


def test_four_process_sharded_embedding_parity(tmp_path):
    with open(tmp_path / "ep_model.py", "w") as f:
        f.write(_EP_MODEL)

    results = _join_world(_spawn_world(tmp_path, _EP_MULTI, 4, _free_port()))
    assert set(results) == {0, 1, 2, 3}
    norms = []
    for rank in range(4):
        assert results[rank]["lookup_ok"], results[rank]
        assert results[rank]["grad_ok"], results[rank]
        norms.append(results[rank]["grad_norm"])
    np.testing.assert_allclose(norms, norms[0], rtol=1e-6)


# ---------------------------------------------------------------------------
# elastic resize 4 -> 2 via sharded checkpoint re-shard
# ---------------------------------------------------------------------------

_RS_MODEL = r"""
import numpy as np


def build():
    import paddle_tpu as pt
    from paddle_tpu import layers
    from paddle_tpu.core import unique_name
    with unique_name.guard():
        x = layers.data("x", shape=[8])
        y = layers.data("y", shape=[1])
        h = layers.fc(x, size=16, act="relu", name="rs_fc1")
        pred = layers.fc(h, size=1, name="rs_fc2")
        loss = layers.reduce_mean(layers.square(pred - y))
        pt.optimizer.MomentumOptimizer(learning_rate=0.05,
                                       momentum=0.9).minimize(loss)
    exe = pt.Executor()
    exe.run(pt.default_startup_program())
    return exe, loss


def step_feed(i):
    W = np.random.RandomState(7).randn(8, 1).astype("float32")
    rb = np.random.RandomState(100 + i)
    xb = rb.rand(16, 8).astype("float32")
    return {"x": xb, "y": (xb @ W).astype("float32")}
"""

_RS_PHASE_A = _BOOT + r"""
import glob, json, time
import jax
import paddle_tpu as pt
from paddle_tpu.distributed import init_parallel_env
from paddle_tpu.parallel import DeviceMesh, ParallelExecutor
from rs_model import build, step_feed

env = init_parallel_env()
assert jax.process_count() == 4
exe, loss = build()
pe = ParallelExecutor(loss_name=loss.name, mesh=DeviceMesh(jax.devices()))
losses = []
for i in range(3):
    losses.append(float(pe.run(feed=step_feed(i),
                               fetch_list=[loss.name])[0]))
d = os.path.join(os.environ["RS_WORK"], "ckpt")
pt.io.save_persistables(dirname=d, sharded=True)
# a 4-process checkpoint is complete once all 4 manifests landed
while len(glob.glob(os.path.join(d, "manifest-*.json"))) < 4:
    time.sleep(0.05)
print(json.dumps({"rank": env.trainer_id, "losses": losses}), flush=True)
"""

_RS_PHASE_B = _BOOT + r"""
import json
import jax
import paddle_tpu as pt
from paddle_tpu.distributed import init_parallel_env
from paddle_tpu.parallel import DeviceMesh, ParallelExecutor
from rs_model import build, step_feed

env = init_parallel_env()
assert jax.process_count() == 2          # the RESIZED world
exe, loss = build()
# restore the 4-process (8-way) checkpoint onto this 2-process (4-way)
# world: ShardedCheckpoint re-assembles slices per var and re-shards
pt.io.load_persistables(dirname=os.path.join(os.environ["RS_WORK"], "ckpt"),
                        sharded=True)
pe = ParallelExecutor(loss_name=loss.name, mesh=DeviceMesh(jax.devices()))
losses = []
for i in range(3, 6):
    losses.append(float(pe.run(feed=step_feed(i),
                               fetch_list=[loss.name])[0]))
print(json.dumps({"rank": env.trainer_id, "losses": losses}), flush=True)
"""

_RS_REF = r"""
import json
from rs_model import build, step_feed
import jax
from paddle_tpu.parallel import DeviceMesh, ParallelExecutor
exe, loss = build()
pe = ParallelExecutor(loss_name=loss.name, mesh=DeviceMesh(jax.devices()))
print(json.dumps([float(pe.run(feed=step_feed(i),
                               fetch_list=[loss.name])[0])
                  for i in range(6)]), flush=True)
"""


def test_elastic_resize_4_to_2(tmp_path):
    with open(tmp_path / "rs_model.py", "w") as f:
        f.write(_RS_MODEL)

    # uninterrupted single-process reference (4 devices)
    boot4 = _BOOT.replace("host_platform_device_count=2",
                          "host_platform_device_count=4")
    ref = subprocess.run(
        [sys.executable, "-c", _script(boot4 + _RS_REF)],
        capture_output=True, text=True, timeout=420, cwd=str(tmp_path))
    assert ref.returncode == 0, ref.stderr[-3000:]
    ref_losses = json.loads(ref.stdout.strip().splitlines()[-1])

    extra = {"RS_WORK": str(tmp_path)}
    a = _join_world(_spawn_world(tmp_path, _RS_PHASE_A, 4, _free_port(),
                                 extra))
    assert set(a) == {0, 1, 2, 3}
    manifests = glob.glob(str(tmp_path / "ckpt" / "manifest-*.json"))
    assert len(manifests) == 4       # one shard manifest per process

    b = _join_world(_spawn_world(tmp_path, _RS_PHASE_B, 2, _free_port(),
                                 extra))
    assert set(b) == {0, 1}

    full = a[0]["losses"] + b[0]["losses"]
    np.testing.assert_allclose(b[0]["losses"], b[1]["losses"], rtol=1e-6)
    np.testing.assert_allclose(full, ref_losses, rtol=2e-4)
    assert full[-1] < full[0]


# ---------------------------------------------------------------------------
# eight-process world, one device per process: the largest rank count the
# suite witnesses (≙ reference N-trainer worlds, nccl_helper.h:118) — pure
# dp over 8 single-device processes with loss parity vs single-process
# ---------------------------------------------------------------------------

_DP8_MULTI = _BOOT.replace(
    "host_platform_device_count=2", "host_platform_device_count=1") + r"""
import json
import jax
import paddle_tpu as pt
from paddle_tpu.distributed import init_parallel_env
from tp_model import build_and_train

env = init_parallel_env()
assert jax.process_count() == 8, jax.process_count()
assert len(jax.devices()) == 8
out = {"rank": env.trainer_id,
       "plain": build_and_train(dp=8, tp=1)}
print(json.dumps(out), flush=True)
"""

_DP8_SINGLE = r"""
import json
from tp_model import build_and_train
print(json.dumps(build_and_train(dp=8, tp=1)), flush=True)
"""


def test_eight_process_dp_parity(tmp_path):
    with open(tmp_path / "tp_model.py", "w") as f:
        f.write(_TP_MODEL)

    boot8 = _BOOT.replace("host_platform_device_count=2",
                          "host_platform_device_count=8")
    ref = subprocess.run(
        [sys.executable, "-c", _script(boot8 + _DP8_SINGLE)],
        capture_output=True, text=True, timeout=420, cwd=str(tmp_path))
    assert ref.returncode == 0, ref.stderr[-3000:]
    expect = json.loads(ref.stdout.strip().splitlines()[-1])

    results = _join_world(_spawn_world(tmp_path, _DP8_MULTI, 8,
                                       _free_port()), timeout=600)
    assert set(results) == set(range(8))
    for rank in range(8):
        np.testing.assert_allclose(results[rank]["plain"], expect,
                                   rtol=2e-4)
    assert expect[-1] < expect[0]
