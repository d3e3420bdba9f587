"""Tests: host-side metrics accumulators, profiler, debugger, Trainer +
checkpoint/resume (≙ reference test_metrics.py / test_profiler.py /
trainer checkpoint tests)."""

import json
import os

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import metrics, profiler


class TestMetrics:
    def test_accuracy(self):
        m = metrics.Accuracy()
        m.update(0.5, 10)
        m.update(1.0, 10)
        assert abs(m.eval() - 0.75) < 1e-9
        m.reset()
        with pytest.raises(Exception):
            m.eval()

    def test_precision_recall(self):
        preds = np.array([1, 1, 0, 1, 0])
        labels = np.array([1, 0, 0, 1, 1])
        p = metrics.Precision()
        p.update(preds, labels)
        assert abs(p.eval() - 2 / 3) < 1e-9
        r = metrics.Recall()
        r.update(preds, labels)
        assert abs(r.eval() - 2 / 3) < 1e-9

    def test_composite(self):
        c = metrics.CompositeMetric()
        c.add_metric(metrics.Precision())
        c.add_metric(metrics.Recall())
        c.update(np.array([1, 0]), np.array([1, 1]))
        p, r = c.eval()
        assert p == 1.0 and r == 0.5

    def test_auc_perfect_and_random(self):
        auc = metrics.Auc(num_thresholds=1023)
        scores = np.concatenate([np.full(50, 0.9), np.full(50, 0.1)])
        labels = np.concatenate([np.ones(50), np.zeros(50)])
        auc.update(scores, labels)
        assert auc.eval() > 0.99
        auc2 = metrics.Auc(num_thresholds=1023)
        rng = np.random.RandomState(0)
        auc2.update(rng.rand(2000), rng.randint(0, 2, 2000))
        assert 0.45 < auc2.eval() < 0.55
        auc2.reset()
        auc2.update(scores, labels)
        assert auc2.eval() > 0.99  # reset really cleared the buckets

    def test_edit_distance(self):
        m = metrics.EditDistance()
        m.update(np.array([[0.0], [2.0], [1.0]]), 3)
        avg, err = m.eval()
        assert abs(avg - 1.0) < 1e-9 and abs(err - 2 / 3) < 1e-9

    def test_chunk_evaluator(self):
        m = metrics.ChunkEvaluator()
        m.update(10, 8, 4)
        p, r, f1 = m.eval()
        assert abs(p - 0.4) < 1e-9 and abs(r - 0.5) < 1e-9
        assert abs(f1 - 2 * 0.4 * 0.5 / 0.9) < 1e-9

    def test_detection_map_perfect(self):
        m = metrics.DetectionMAP()
        # one image, one class, one perfectly-matching detection
        dets = np.array([[0, 0.9, 0.1, 0.1, 0.5, 0.5]])
        gts = np.array([[0, 0.1, 0.1, 0.5, 0.5]])
        m.update(dets, [1], gts, [1])
        assert m.eval() == pytest.approx(1.0)

    def test_detection_map_miss(self):
        m = metrics.DetectionMAP()
        dets = np.array([[0, 0.9, 0.6, 0.6, 0.9, 0.9]])  # no overlap
        gts = np.array([[0, 0.1, 0.1, 0.5, 0.5]])
        m.update(dets, [1], gts, [1])
        assert m.eval() == pytest.approx(0.0)


class TestProfiler:
    def test_record_and_summary(self, tmp_path, capsys):
        trace = str(tmp_path / "t.json")
        with profiler.profiler("CPU", sorted_key="total",
                               profile_path=trace):
            with profiler.RecordEvent("outer"):
                with profiler.RecordEvent("inner"):
                    pass
        out = capsys.readouterr().out
        assert "outer" in out and "inner" in out
        with open(trace) as f:
            data = json.load(f)
        names = {e["name"] for e in data["traceEvents"]}
        assert {"outer", "inner"} <= names

    def test_merge_process_traces(self, tmp_path):
        """Per-process traces merge into one timeline with disjoint,
        labeled per-rank lanes (≙ reference tools/timeline.py multi-
        profile_path mode)."""
        paths = []
        for r in range(3):
            p = str(tmp_path / f"trace_rank{r}.json")
            with open(p, "w") as f:
                json.dump({"traceEvents": [
                    {"name": f"step_{r}", "cat": "host", "ph": "X",
                     "ts": 10.0 * r, "dur": 5.0, "pid": 0, "tid": 1},
                    {"name": "dev", "cat": "device", "ph": "X",
                     "ts": 11.0 * r, "dur": 2.0, "pid": 1, "tid": 0},
                ]}, f)
            paths.append(p)
        out = profiler.merge_process_traces(
            paths, str(tmp_path / "merged.json"))
        with open(out) as f:
            merged = json.load(f)
        evs = merged["traceEvents"]
        pids = {e["pid"] for e in evs if e.get("ph") != "M"}
        assert pids == {0, 1, 100, 101, 200, 201}, pids
        labels = {e["args"]["name"] for e in evs if e.get("ph") == "M"}
        assert "rank0/host" in labels and "rank2/device0" in labels, labels
        # every rank's host events survive with their names
        names = {e["name"] for e in evs}
        assert {"step_0", "step_1", "step_2"} <= names

    def test_executor_events_recorded(self, capsys):
        x = pt.layers.data("x", shape=[4], dtype="float32")
        y = pt.layers.fc(x, size=2)
        exe = pt.Executor()
        exe.run(pt.default_startup_program())
        with profiler.profiler("CPU"):
            exe.run(feed={"x": np.ones((2, 4), "float32")},
                    fetch_list=[y])
        out = capsys.readouterr().out
        assert "executor/run" in out


class TestDebugger:
    def _build(self):
        x = pt.layers.data("x", shape=[4], dtype="float32")
        y = pt.layers.fc(x, size=2, act="relu")
        return x, y

    def test_pprint(self):
        self._build()
        text = pt.debugger.pprint_program_codes(pt.default_main_program())
        assert "matmul" in text or "fc" in text or "mul" in text
        assert "block 0" in text

    def test_graphviz(self, tmp_path):
        self._build()
        path = pt.debugger.draw_block_graphviz(
            pt.default_main_program().global_block(),
            str(tmp_path / "g.dot"))
        content = open(path).read()
        assert content.startswith("digraph") and "->" in content

    def test_dump_hlo(self):
        x, y = self._build()
        text = pt.debugger.dump_hlo(pt.default_main_program(),
                                    {"x": ((2, 4), "float32")},
                                    fetch_list=[y])
        assert "stablehlo" in text or "mhlo" in text or "func" in text


def _reader(n=8, batch=4, seed=0):
    def r():
        rng = np.random.RandomState(seed)
        for _ in range(n):
            yield [(rng.rand(4).astype("float32"),
                    np.array([rng.randint(2)], dtype="int64"))
                   for _ in range(batch)]
    return r


def _train_func():
    x = pt.layers.data("x", shape=[4], dtype="float32")
    label = pt.layers.data("label", shape=[1], dtype="int64")
    logits = pt.layers.fc(x, size=2)
    loss = pt.layers.mean(
        pt.layers.softmax_with_cross_entropy(logits, label))
    return loss


class TestTrainer:
    def test_train_events_and_test(self):
        events = []

        def handler(ev):
            events.append(type(ev).__name__)

        t = pt.Trainer(train_func=_train_func,
                       optimizer_func=lambda:
                       pt.optimizer.SGDOptimizer(learning_rate=0.1))
        t.train(num_epochs=2, event_handler=handler, reader=_reader(),
                feed_order=["x", "label"])
        assert events.count("BeginEpochEvent") == 2
        assert events.count("EndStepEvent") == 16
        w_name = [v.name for v in
                  t.train_program.global_block().vars.values()
                  if getattr(v, "trainable", False)][0]
        before = np.asarray(t.scope.get(w_name)).copy()
        vals = t.test(reader=_reader(), feed_order=["x", "label"])
        assert np.isfinite(vals[0])
        # evaluation must not touch parameters
        np.testing.assert_array_equal(before, np.asarray(t.scope.get(w_name)))

    def test_checkpoint_save_resume(self, tmp_path):
        ckpt_dir = str(tmp_path / "ckpt")
        cfg = pt.CheckpointConfig(checkpoint_dir=ckpt_dir,
                                  max_num_checkpoints=2, step_interval=3)
        t = pt.Trainer(train_func=_train_func,
                       optimizer_func=lambda:
                       pt.optimizer.SGDOptimizer(learning_rate=0.1),
                       checkpoint_config=cfg)
        t.train(num_epochs=1, event_handler=lambda ev: None,
                reader=_reader(), feed_order=["x", "label"])
        serials = [d for d in os.listdir(ckpt_dir)
                   if d.startswith("checkpoint_")]
        assert 1 <= len(serials) <= 2  # retention enforced
        for d in serials:
            assert os.path.exists(os.path.join(ckpt_dir, d, "_SUCCESS"))

        # resume: a fresh process rebuilds the same program (names restart);
        # emulate with a fresh unique-name scope
        from paddle_tpu.core import unique_name
        pt.reset_default_programs()
        pt.reset_global_scope()
        cfg2 = pt.CheckpointConfig(checkpoint_dir=ckpt_dir)
        with unique_name.guard():
            t2 = pt.Trainer(train_func=_train_func,
                            optimizer_func=lambda:
                            pt.optimizer.SGDOptimizer(learning_rate=0.1),
                            checkpoint_config=cfg2)
        assert cfg2.load_serial is not None and cfg2.load_serial >= 0
        w_name = [v.name for v in
                  t2.train_program.global_block().vars.values()
                  if getattr(v, "trainable", False)][0]
        np.testing.assert_allclose(
            np.asarray(t2.scope.get(w_name)),
            np.asarray(t.scope.get(w_name)))
        # the first run COMPLETED num_epochs=1, so resuming train(1) must be
        # a no-op (no re-training of finished epochs)
        steps = []
        t2.train(num_epochs=1,
                 event_handler=lambda ev: steps.append(ev)
                 if isinstance(ev, pt.EndStepEvent) else None,
                 reader=_reader(), feed_order=["x", "label"])
        assert steps == []

    def test_incomplete_checkpoint_ignored(self, tmp_path):
        ckpt_dir = str(tmp_path / "ckpt")
        os.makedirs(os.path.join(ckpt_dir, "checkpoint_7"))  # no _SUCCESS
        from paddle_tpu.trainer import get_latest_checkpoint_serial
        assert get_latest_checkpoint_serial(ckpt_dir) == -1

    def test_stop(self):
        def handler(ev):
            if isinstance(ev, pt.EndStepEvent) and ev.step == 1:
                t.stop()

        t = pt.Trainer(train_func=_train_func,
                       optimizer_func=lambda:
                       pt.optimizer.SGDOptimizer(learning_rate=0.1))
        t.train(num_epochs=5, event_handler=handler, reader=_reader(),
                feed_order=["x", "label"])


def test_memory_usage_estimate(rng):
    """≙ reference contrib/memory_usage_calc.py test coverage."""
    import paddle_tpu as pt
    from paddle_tpu import layers
    from paddle_tpu.contrib import memory_usage

    x = layers.data("x", shape=[256])
    h = layers.fc(x, size=512)
    layers.fc(h, size=10)
    m = memory_usage(batch_size=32)
    # fc params: 256*512 + 512 + 512*10 + 10 floats
    expected_params = (256 * 512 + 512 + 512 * 10 + 10) * 4
    assert m["parameters"] == expected_params
    # activations scale with batch size
    m2 = memory_usage(batch_size=64)
    assert m2["activations"] > m["activations"]
    assert "state" in m["summary"]


def test_weighted_average_and_evaluator_aliases():
    """≙ reference average.py + evaluator.py surfaces."""
    import pytest as _pytest
    from paddle_tpu.average import WeightedAverage
    from paddle_tpu import evaluator

    w = WeightedAverage()
    with _pytest.raises(Exception):
        w.eval()
    w.add(1.0, weight=1)
    w.add(3.0, weight=3)
    assert abs(w.eval() - 2.5) < 1e-9
    w.reset()
    w.add(5.0)
    assert w.eval() == 5.0
    assert evaluator.ChunkEvaluator is not None


def test_get_places_lists_devices():
    from paddle_tpu.layers import get_places
    places = get_places()
    assert len(places) == 8  # the virtual CPU mesh
    assert get_places(device_count=2) == places[:2]


_THRESHOLD = "jax_persistent_cache_min_compile_time_secs"
_THRESHOLD_ENV = "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"
_BOUND = "jax_compilation_cache_max_size"


@pytest.fixture
def jax_cache_config():
    """What `compile_cache.configure()` reads and sets, put back after."""
    import jax
    was = {k: getattr(jax.config, k) for k in (
        "jax_platforms", "jax_compilation_cache_dir", _THRESHOLD, _BOUND)}
    yield
    for k, v in was.items():
        jax.config.update(k, v)


def test_compile_cache_rule_in_process(monkeypatch, jax_cache_config):
    """core/compile_cache.py replaced the PTPU_JIT_CACHE flag with one
    rule: JAX_COMPILATION_CACHE_DIR set -> the package sets no directory;
    unset -> the fixed in-checkout directory, except in a CPU-pinned process
    like this one (tests/test_chip_smoke.py checks the TPU-side half from
    fresh interpreters)."""
    import os
    import jax
    from paddle_tpu.core import compile_cache, flags

    assert "jit_cache" not in flags.all_flags()
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert compile_cache.CACHE_DIR == os.path.join(repo, ".jax_cache")
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    compile_cache.configure()
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    compile_cache.configure()
    assert jax.config.jax_compilation_cache_dir == before


@pytest.mark.parametrize("platforms, directory, named, directory_set, reads", [
    # a directory in effect -> the cache admits every executable
    ("", None, None, True, 0.0),             # unpinned: the in-checkout one
    ("", "/given/cache", None, False, 0.0),  # given from outside, unpinned
    ("cpu", "/given/cache", None, False, 0.0),   # ... and pinned to the CPU
    ("tpu,cpu", None, None, True, 0.0),
    # pinned to the CPU with no directory given: no cache, nothing set
    ("cpu", None, None, False, 1.0),
    (" CPU ", None, None, False, 1.0),
    # a threshold named from outside is left as given, like the directory
    ("", None, "2.5", True, 2.5),
    ("cpu", "/given/cache", "0.5", False, 0.5),
])
def test_compile_cache_admits_every_executable(monkeypatch, jax_cache_config,
                                               platforms, directory, named,
                                               directory_set, reads):
    """ISSUE 58: wherever a cache directory is in effect after the rule has
    run, JAX's minimum compile time for an entry goes from 1 s to 0. A bound
    on the directory's size is JAX's own and stays as it was named."""
    import jax
    from paddle_tpu.core import compile_cache

    for env, value in (("JAX_COMPILATION_CACHE_DIR", directory),
                       (_THRESHOLD_ENV, named)):
        if value is None:
            monkeypatch.delenv(env, raising=False)
        else:
            monkeypatch.setenv(env, value)
    jax.config.update("jax_platforms", platforms)
    jax.config.update("jax_compilation_cache_dir", None)
    # what JAX itself read from the environment when it was imported
    jax.config.update(_THRESHOLD, float(named) if named else 1.0)
    jax.config.update(_BOUND, 201326592)
    compile_cache.configure()
    assert getattr(jax.config, _BOUND) == 201326592
    assert jax.config.jax_compilation_cache_dir == (
        compile_cache.CACHE_DIR if directory_set else None)
    assert getattr(jax.config, _THRESHOLD) == reads


class TestNanGuard:
    def test_in_graph_guard_fires_on_cpu(self, rng):
        """PTPU_CHECK_NAN_INF on CPU: the per-op in-graph guard localizes
        the producing op (≙ CheckTensorNANOrInf, operator.cc:726)."""
        from paddle_tpu.core import flags
        import paddle_tpu as pt
        from paddle_tpu import layers

        x = layers.data("x", shape=[4], dtype="float32")
        y = layers.log(x)   # log of a negative -> nan
        exe = pt.Executor()
        exe.run(pt.default_startup_program())
        old = flags.get_flag("check_nan_inf")
        flags.set_flag("check_nan_inf", True)
        try:
            with pytest.raises(Exception, match="NaN/Inf"):
                exe.run(feed={"x": np.full((2, 4), -1.0, "float32")},
                        fetch_list=[y])
        finally:
            flags.set_flag("check_nan_inf", old)

    def test_fetch_time_sweep_fires_off_cpu(self, rng, monkeypatch):
        """Off-CPU the in-graph guard cannot host-callback; the executor's
        fetch-time isfinite sweep still fails loudly, naming the bad var."""
        import jax
        from paddle_tpu.core import flags
        import paddle_tpu as pt
        from paddle_tpu import layers
        import paddle_tpu.framework.executor as exec_mod
        import paddle_tpu.framework.lowering as low_mod

        x = layers.data("x", shape=[4], dtype="float32")
        y = layers.log(x)
        exe = pt.Executor()
        exe.run(pt.default_startup_program())
        # simulate a TPU backend: both the in-graph guard (which then
        # no-ops) and the executor sweep consult jax.default_backend()
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        old = flags.get_flag("check_nan_inf")
        flags.set_flag("check_nan_inf", True)
        try:
            with pytest.raises(FloatingPointError, match="fetch-time"):
                exe.run(feed={"x": np.full((2, 4), -1.0, "float32")},
                        fetch_list=[y])
        finally:
            flags.set_flag("check_nan_inf", old)


class TestDeviceTimeline:
    def test_device_trace_merges_into_chrome_export(self, rng, tmp_path):
        """profiler(state='All', trace_dir=...) captures a device (XPlane)
        trace; RecordEvent names ride onto the device timeline as
        TraceAnnotations, and export merges host + device events into ONE
        chrome trace file (≙ device_tracer.h:49 + tools/timeline.py)."""
        import json as _json
        import paddle_tpu as pt
        from paddle_tpu import layers, profiler

        x = layers.data("x", shape=[32], dtype="float32")
        y = layers.fc(x, size=16, act="relu")
        loss = layers.reduce_mean(y)
        pt.optimizer.SGD(learning_rate=0.1).minimize(loss)
        exe = pt.Executor()
        exe.run(pt.default_startup_program())
        feed = {"x": rng.rand(8, 32).astype("float32")}

        trace_dir = str(tmp_path / "xplane")
        out_path = str(tmp_path / "timeline.json")
        with profiler.profiler(state="All", profile_path=out_path,
                               trace_dir=trace_dir):
            for _ in range(3):
                with profiler.RecordEvent("train_step"):
                    exe.run(feed=feed, fetch_list=[loss])

        with open(out_path) as f:
            trace = _json.load(f)
        evs = trace["traceEvents"]
        host = [e for e in evs if e.get("pid") == 0]
        device = [e for e in evs if e.get("pid", 0) >= 1]
        assert any(e["name"] == "train_step" for e in host)
        assert device, "device timeline missing from merged chrome trace"
        # the RecordEvent annotation is correlated onto the device side
        names = " ".join(str(e.get("name", "")) + str(e.get("args", ""))
                         for e in device)
        assert "train_step" in names
