"""The executor's store of loaded-and-ready executables
(`core/compile_cache.py`, `Executor._store_key`, `_CompiledStep.first_run`):
a process that finds a launch function's executable in the store traces and
lowers nothing, runs bit for bit what the process that wrote it ran, and
never takes an entry whose key differs. The CPU tier keeps no compile cache,
so every test here puts a temporary directory in effect through `jax.config`
and takes it out again."""

import hashlib
import json
import os
import subprocess
import sys
import types

import jax
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.core import compile_cache, flags
from paddle_tpu.framework import executor as executor_mod
from paddle_tpu.observability import tracing

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def store(tmp_path):
    """A compile-cache directory in effect for one test, so a store too."""
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    tracing.clear()
    try:
        yield os.path.join(str(tmp_path), compile_cache.STORE_SUBDIR)
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        compilation_cache.reset_cache()


def _fresh():
    pt.reset_default_programs()
    pt.reset_global_scope()


def _tiny_train(width=16, rows=4, feed_dtype="float32", act="relu"):
    """A two-layer regression under Adam, built from scratch: (loss, feed)."""
    _fresh()
    with pt.core.unique_name.guard():
        x = pt.layers.data("x", [8], dtype=feed_dtype)
        y = pt.layers.data("y", [1], dtype="float32")
        if feed_dtype != "float32":
            x = pt.layers.cast(x, "float32")
        h = pt.layers.fc(x, width, act=act)
        loss = pt.layers.mean(pt.layers.square_error_cost(
            pt.layers.fc(h, 1), y))
        pt.optimizer.AdamOptimizer(1e-2).minimize(loss)
    rng = np.random.RandomState(0)
    feed = {"x": rng.rand(rows, 8).astype(feed_dtype),
            "y": rng.rand(rows, 1).astype("float32")}
    return loss, feed


def _first_runs():
    return [s for s in tracing.compile_spans()
            if s.name == "executor/compile_or_load"]


def _train(steps=3, **kw):
    """Startup and `steps` steps on a NEW executor: (losses, executor)."""
    loss, feed = _tiny_train(**kw)
    exe = pt.Executor()
    exe.run(pt.default_startup_program())
    return [float(exe.run(feed=feed, fetch_list=[loss])[0])
            for _ in range(steps)], exe


# -- (1) a second process on a filled store ----------------------------------

CHILD = r'''
import glob, hashlib, json, os, sys
import jax
jax.config.update("jax_compilation_cache_dir", sys.argv[1])
import jax.monitoring
import numpy as np
import paddle_tpu as pt
from paddle_tpu.observability import tracing

events = {"trace": 0, "lower": 0, "in_first_run": 0}
def heard(event, seconds, **_):
    what = ("trace" if event.endswith("jaxpr_trace_duration") else
            "lower" if event.endswith("jaxpr_to_mlir_module_duration")
            else None)
    if what:
        events[what] += 1
        events["in_first_run"] += any(
            isinstance(s, tracing.compile_span)
            for s in getattr(tracing._tls, "stack", ()))
jax.monitoring.register_event_duration_secs_listener(heard)

def build():
    pt.reset_default_programs(); pt.reset_global_scope()
    with pt.core.unique_name.guard():
        x = pt.layers.data("x", [8], dtype="float32")
        y = pt.layers.data("y", [1], dtype="float32")
        h = pt.layers.dropout(pt.layers.fc(x, 16, act="relu"), 0.1)
        loss = pt.layers.mean(pt.layers.square_error_cost(
            pt.layers.fc(h, 1), y))
        pt.optimizer.AdamOptimizer(1e-2).minimize(loss)
    pt.default_startup_program().random_seed = 7
    pt.default_main_program().random_seed = 11
    rng = np.random.RandomState(0)
    return loss, {"x": rng.rand(4, 8).astype("float32"),
                  "y": rng.rand(4, 1).astype("float32")}

def state():
    scope, h = pt.global_scope(), hashlib.sha256()
    for v in sorted(pt.default_main_program().global_block().vars.values(),
                    key=lambda v: v.name):
        if v.persistable and scope.has_var(v.name):
            h.update(v.name.encode() + np.asarray(scope.get(v.name)).tobytes())
    return h.hexdigest()

out = {}
for mode in ("run", "run_bound", "run_steps"):
    loss, feed = build()
    before, n0 = dict(events), len(tracing.compile_spans())
    exe = pt.Executor()
    exe.run(pt.default_startup_program())
    if mode == "run":
        got = [exe.run(feed=feed, fetch_list=[loss])[0] for _ in range(3)]
    elif mode == "run_bound":
        step = exe.prepare(feed=feed, fetch_list=[loss]).bind(dict(feed))
        got = [np.asarray(step.run_bound()[0]) for _ in range(3)]
    else:
        got = exe.run_steps([feed] * 3, fetch_list=[loss])[0]
    spans = [s for s in tracing.compile_spans()[n0:]
             if s.name == "executor/compile_or_load"]
    out[mode] = {
        "fetches": np.asarray(got, np.float32).tobytes().hex(),
        "state": state(),
        "events": {k: events[k] - before[k] for k in events},
        "spans": [{k: s.attrs.get(k) for k in (
            "program", "stored", "trace_s", "lower_s", "jits", "cache_loads",
            "executables", "store_write_s")} for s in spans],
        "keys": sorted(os.path.basename(p) for p in glob.glob(
            sys.argv[1] + "/paddle_tpu_executables/*.exe")),
    }
print("RESULT " + json.dumps(out))
'''


def _child(cache_dir):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    done = subprocess.run([sys.executable, "-c", CHILD, cache_dir], env=env,
                          capture_output=True, text=True, timeout=300)
    assert done.returncode == 0, done.stderr[-3000:]
    line = [ln for ln in done.stdout.splitlines() if ln.startswith("RESULT ")]
    return json.loads(line[-1][len("RESULT "):])


@pytest.fixture(scope="module")
def two_processes(tmp_path_factory):
    """What a process on an empty store and a second one on the store it
    filled each report: (first, second)."""
    cache_dir = str(tmp_path_factory.mktemp("cache"))
    return _child(cache_dir), _child(cache_dir)


@pytest.mark.parametrize("mode", ["run", "run_bound", "run_steps"])
def test_a_second_process_traces_and_lowers_nothing(two_processes, mode):
    first, second = (p[mode] for p in two_processes)
    # the process that filled the store traced, lowered and wrote
    assert first["events"]["trace"] > 0 and first["events"]["lower"] > 0
    assert first["spans"][-1]["stored"] == 0
    assert first["spans"][-1]["store_write_s"] > 0
    # the second heard not one trace and not one lowering from JAX, startup
    # program included (`run_steps` stacks its feeds with an eager
    # `jnp.stack`, outside any first run), and its spans say so themselves
    assert first["events"]["in_first_run"] > 0
    assert second["events"]["in_first_run"] == 0
    if mode != "run_steps":
        assert second["events"]["trace"] == second["events"]["lower"] == 0
    assert len(second["spans"]) == 2
    for span in second["spans"]:
        assert span["stored"] == 1 and span["jits"] == 0
        assert span["trace_s"] == 0 and span["lower_s"] == 0
        assert span["cache_loads"] == 1 and span["executables"] == 1
    # and it ran the same executables: three launches with donated state
    # threaded through them, dropout's seeds among the arguments
    assert second["fetches"] == first["fetches"]
    assert second["state"] == first["state"]


def test_two_processes_make_the_same_keys(two_processes):
    first, second = two_processes
    names = first["run_steps"]["keys"]
    assert len(names) == 4      # startup, train_step, its packed form, the loop
    assert second["run_steps"]["keys"] == names


# -- (2) what the key holds --------------------------------------------------

def _plain_args(compiled, feed, scope, rw=None):
    """What `Executor.run` hands the step's plain launch function."""
    return (tuple(feed[n] for n in compiled.feed_names),
            tuple(scope.get(n) for n in compiled.ro_names),
            tuple(rw or (scope.get(n) for n in compiled.rw_names)),
            np.uint32(1))


def _key(flag=None, packed=False, **kw):
    """The store's (path, key) of the tiny step's executable, made as a
    first run makes it."""
    loss, feed = _tiny_train(**kw)
    exe = pt.Executor()
    exe.run(pt.default_startup_program())
    if flag:
        flags.set_flag(*flag)
    try:
        scope = pt.global_scope()
        if packed:
            step = exe.prepare(feed=feed, fetch_list=[loss])
            compiled, fn = step._compiled, step._fn
            args = (step._buf, step._b_rest_vals,
                    tuple(scope.get(n) for n in compiled.ro_names),
                    tuple(scope.get(n) for n in compiled.rw_names))
        else:
            compiled = exe._lookup_or_compile(
                pt.default_main_program(), dict(feed), [loss.name], scope)
            fn, args = compiled.fn, _plain_args(compiled, feed, scope)
        entry = exe._store_key(compiled, fn, args)
    finally:
        if flag:
            flags.set_flag(flag[0], not flag[1])
    assert entry is not None
    return entry


def _other_fetch_list():
    loss, feed = _tiny_train()
    exe = pt.Executor()
    exe.run(pt.default_startup_program())
    scope = pt.global_scope()
    compiled = exe._lookup_or_compile(pt.default_main_program(), dict(feed),
                                      [], scope)
    return exe._store_key(compiled, compiled.fn,
                          _plain_args(compiled, feed, scope))


def _bf16_state():
    """The tiny step with one state value cast to bfloat16 in the scope."""
    loss, feed = _tiny_train()
    exe = pt.Executor()
    exe.run(pt.default_startup_program())
    scope = pt.global_scope()
    compiled = exe._lookup_or_compile(pt.default_main_program(), dict(feed),
                                      [loss.name], scope)
    rw = [scope.get(n) for n in compiled.rw_names]
    rw[0] = rw[0].astype("bfloat16")
    return exe._store_key(compiled, compiled.fn,
                          _plain_args(compiled, feed, scope, rw))


def _with(monkeypatch, target, name, value, **kw):
    monkeypatch.setattr(target, name, value)
    return _key(**kw)


KEY_CHANGES = {
    "an_op_attr": lambda mp: _key(act="tanh"),
    "a_feed_shape": lambda mp: _key(rows=6),
    "a_feed_dtype": lambda mp: _key(feed_dtype="int32"),
    "a_state_dtype": lambda mp: _bf16_state(),
    "the_fetch_list": lambda mp: _other_fetch_list(),
    "a_flag": lambda mp: _key(flag=("use_bf16_matmul", False)),
    "the_source_digest": lambda mp: _with(
        mp, compile_cache, "source_digest", lambda: "another tree"),
    "the_jax_version": lambda mp: _with(mp, jax, "__version__", "0.0.1"),
}


@pytest.mark.parametrize("what", sorted(KEY_CHANGES))
def test_the_key_changes_with(store, monkeypatch, what):
    assert flags.get_flag("use_bf16_matmul") is True
    path, key = _key()
    assert (path, key) == _key()        # built again: the same entry
    other_path, other_key = KEY_CHANGES[what](monkeypatch)
    assert other_key != key
    # an entry of another SOURCE is the same file, replaced; anything else
    # that differs is an entry of its own
    assert (other_path == path) == (what == "the_source_digest")


def test_the_key_changes_with_the_packed_spans(store):
    """A feed that moves into the pack is another layout of the same step."""
    whole = _key(packed=True)
    loss, feed = _tiny_train()
    exe = pt.Executor()
    exe.run(pt.default_startup_program())
    feed["y"] = jax.numpy.asarray(feed["y"])    # a device array stays out
    step = exe.prepare(feed=feed, fetch_list=[loss])
    assert len(step._spans) == 1
    scope, compiled = pt.global_scope(), step._compiled
    part = exe._store_key(compiled, step._fn, (
        step._buf, step._b_rest_vals,
        tuple(scope.get(n) for n in compiled.ro_names),
        tuple(scope.get(n) for n in compiled.rw_names)))
    assert part[1] != whole[1] and part[0] != whole[0]
    assert _key() != whole              # and the plain launch another still


def test_the_key_leaves_the_seed_out(store):
    """`random_seed` is an argument of every launch and in no trace."""
    path, key = _key()
    loss, feed = _tiny_train()
    pt.default_main_program().random_seed = 12345
    exe = pt.Executor()
    exe.run(pt.default_startup_program())
    scope = pt.global_scope()
    compiled = exe._lookup_or_compile(pt.default_main_program(), dict(feed),
                                      [loss.name], scope)
    assert exe._store_key(compiled, compiled.fn, _plain_args(
        compiled, feed, scope)) == (path, key)


@pytest.mark.parametrize("codec", sorted(compile_cache._CODECS))
def test_an_entry_names_its_codec(store, monkeypatch, codec):
    """A process without zstandard reads what one with it wrote, and back."""
    import pickle
    monkeypatch.setattr(compile_cache, "_CODEC", codec)
    want, _ = _train()
    for name in os.listdir(store):
        with open(os.path.join(store, name), "rb") as f:
            assert pickle.load(f)["codec"] == codec
    monkeypatch.undo()
    tracing.clear()
    assert _train()[0] == want
    assert [s.attrs["stored"] for s in _first_runs()] == [1, 1]


def test_an_option_jax_defines_late_is_not_in_the_key(store, monkeypatch):
    """Pallas's switches exist from the first kernel a process traces, and a
    process that loads every program never defines them: the key holds the
    options JAX had when the package was imported, with today's values."""
    before = _key()
    import jax.experimental.pallas  # noqa: F401
    assert "jax_pallas_enable_debug_checks" in jax.config._value_holders
    assert "jax_pallas_enable_debug_checks" not in compile_cache.CONFIG_NAMES
    assert _key() == before
    assert "jax_enable_x64" in compile_cache.CONFIG_NAMES
    with jax.default_matmul_precision("highest"):
        assert _key()[1] != before[1]
    monkeypatch.setenv("JAX_PALLAS_ENABLE_DEBUG_CHECKS", "1")
    assert _key()[1] != before[1]


def test_no_directory_in_effect_no_store():
    assert compile_cache.store_dir() is None
    _, exe = _train(steps=1)
    (compiled,) = [c for c in exe._cache.values() if c.feed_names]
    assert compiled.launch[compiled.fn] is compiled.fn      # the jit itself


# -- (3) entries that cannot be used -----------------------------------------

def _truncate(path):
    with open(path, "rb") as f:
        head = f.read(os.path.getsize(path) // 2)
    with open(path, "wb") as f:
        f.write(head)


def _foreign(path):
    import pickle
    with open(path, "rb") as f:
        entry = pickle.load(f)
    entry["key"] = hashlib.sha256(b"another program").hexdigest()
    with open(path, "wb") as f:
        pickle.dump(entry, f)


@pytest.mark.parametrize("damage", [_truncate, _foreign])
def test_a_bad_entry_is_a_miss_and_is_replaced(store, damage):
    want, _ = _train()
    (path,) = [os.path.join(store, n) for n in os.listdir(store)
               if n.startswith("train_step")]
    damage(path)
    tracing.clear()
    got, _ = _train()
    assert got == want
    startup, step = _first_runs()
    assert startup.attrs["stored"] == 1
    assert step.attrs["stored"] == 0 and step.attrs["store_write_s"] > 0
    assert step.attrs["trace_s"] > 0
    tracing.clear()
    assert _train()[0] == want          # the entry it left is good
    assert [s.attrs["stored"] for s in _first_runs()] == [1, 1]
    assert not [n for n in os.listdir(store) if n.endswith(".tmp")]


def test_an_unwritable_directory_is_a_miss_every_time(store):
    want, _ = _train()
    for name in os.listdir(store):
        os.unlink(os.path.join(store, name))
    os.rmdir(store)
    with open(store, "w") as f:         # a FILE where the directory was:
        f.write("in the way")           # nothing reads or writes under it
    tracing.clear()
    assert _train()[0] == want
    for s in _first_runs():
        assert s.attrs["stored"] == 0 and "store_write_s" not in s.attrs
        assert s.attrs["trace_s"] > 0


# -- (4) a step with no key --------------------------------------------------

def test_an_attr_with_no_json_form_takes_the_lazy_path(store):
    loss, feed = _tiny_train()
    block = pt.default_main_program().global_block()
    block.ops[0].attrs["note"] = object()       # nothing reads it
    exe = pt.Executor()
    exe.run(pt.default_startup_program())
    first = float(exe.run(feed=feed, fetch_list=[loss])[0])
    (compiled,) = [c for c in exe._cache.values() if c.feed_names]
    assert compiled.launch[compiled.fn] is compiled.fn
    startup, step = _first_runs()
    assert startup.attrs["stored"] == 0 and "stored" not in step.attrs
    assert not [n for n in os.listdir(store) if n.startswith("train_step")]
    del block.ops[0].attrs["note"]
    assert _train(steps=1)[0] == [first]


# -- (5) what a hit tells ----------------------------------------------------

def test_a_hit_tells_its_span_and_the_benchmark_reads_100(store):
    import time
    _train()
    tracing.clear()
    _train()
    for s in _first_runs():
        a = s.attrs
        assert a["stored"] == 1 and a["cache_hit"] == 1
        assert a["trace_s"] == 0 and a["lower_s"] == 0 and a["jits"] == 0
        assert a["compile_s"] == 0 and a["cache_load_s"] > 0
        assert a["cache_loads"] == 1 and a["executables"] == 1
        assert a["kernel_bodies_traced"] == 0
        assert a["cache_load_s"] <= s.end - s.start
    sys.path.insert(0, ROOT)
    try:
        from benchmark.metrics import (setup_cache_hit_share,
                                       setup_cache_load_s, setup_jit_calls,
                                       setup_lower_s, setup_programs,
                                       setup_trace_s)
    finally:
        sys.path.remove(ROOT)
    run = types.SimpleNamespace(t0=0.0, setup_s=time.perf_counter(),
                                setup_parts={"runtime_start": 0.0})
    assert setup_cache_hit_share.read(run) == 100.0
    assert setup_programs.read(run) == 2
    assert setup_cache_load_s.read(run) == sum(
        s.attrs["cache_load_s"] for s in _first_runs())
    # what is left of trace and lowering is outside any first run
    outside = [s for s in tracing.compile_spans()
               if s.name == "jax/unscoped"]
    assert setup_trace_s.read(run) == sum(
        s.attrs["trace_s"] for s in outside)
    assert setup_lower_s.read(run) == sum(
        s.attrs["lower_s"] for s in outside)
    assert setup_jit_calls.read(run) == sum(s.attrs["jits"] for s in outside)


# -- (6) a mesh ---------------------------------------------------------------

def test_a_four_device_mesh_program_round_trips(store):
    from paddle_tpu.parallel import DeviceMesh, ParallelExecutor

    def train():
        loss, feed = _tiny_train(rows=8)
        pt.Executor().run(pt.default_startup_program())
        exe = ParallelExecutor(loss_name=loss.name, mesh=DeviceMesh(
            jax.devices()[:4], {"dp": 4}))
        return [float(exe.run(fetch_list=[loss], feed=feed)[0])
                for _ in range(3)]

    want = train()
    tracing.clear()
    assert train() == want
    startup, step = _first_runs()
    assert step.attrs["program"] == "train_step"
    assert step.attrs["stored"] == 1 and step.attrs["trace_s"] == 0
    # its entry names the mesh's four devices, in the mesh's order
    import pickle
    (name,) = [n for n in os.listdir(store) if n.startswith("train_step")]
    with open(os.path.join(store, name), "rb") as f:
        assert pickle.load(f)["devices"] == [
            d.id for d in jax.devices()[:4]]


# -- arguments an executable was not built for --------------------------------

def test_a_drifted_call_goes_to_the_jit(store):
    """`PreparedStep.run`'s promise: a signature the step was not prepared
    for recompiles through jit's own check. A stored executable refuses it,
    and `_Stored` hands the call on."""
    loss, feed = _tiny_train()
    exe = pt.Executor()
    exe.run(pt.default_startup_program())
    want = float(exe.run(feed=feed, fetch_list=[loss])[0])
    (compiled,) = [c for c in exe._cache.values() if c.feed_names]
    launch = compiled.launch[compiled.fn]
    assert isinstance(launch, executor_mod._Stored)
    assert launch.call is launch.executable
    scope = pt.global_scope()
    w = compiled.rw_names[0]
    scope.set_var(w, scope.get(w).astype("bfloat16"))   # another dtype
    got = float(exe.run(feed=feed, fetch_list=[loss])[0])
    assert np.isfinite(got) and got != want
    assert launch.call is launch.jit
    with pytest.raises(TypeError):
        launch()                        # the jit's own refusal still raises
