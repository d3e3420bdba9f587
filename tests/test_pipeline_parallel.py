"""Executor-level tests for the program-level pipeline-parallel mode:
`pipeline_partition_pass` (framework/passes.py) + the GPipe/1F1B schedule
engine (parallel/pipeline.py) behind `BuildStrategy.pipeline_stages`.

Discipline mirrors tests/test_zero_comm.py: fixed-seed loss parity against
the single-device baseline, structure asserted from the program (one
pp_send/pp_recv pair per boundary) and the compiled HLO (exactly one
boundary-activation + one boundary-gradient collective-permute per tick),
and the schedule census read from the SAME tick tables the device executes
— bubble fraction pinned to the analytic (K-1)/(M+K-1), 1F1B's peak
stashed-activation count strictly below GPipe's at M >= 2*stages.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers
from paddle_tpu.core import flags
from paddle_tpu.core.enforce import InvalidArgumentError
from paddle_tpu.framework.costs import collective_census
from paddle_tpu.framework.passes import get_pass
from paddle_tpu.parallel import ParallelExecutor
from paddle_tpu.parallel.mesh import DeviceMesh
from paddle_tpu.parallel.pipeline import (build_schedule, pipeline_apply,
                                          schedule_census)
from paddle_tpu.parallel.strategy import BuildStrategy, ReduceStrategy


def _build_mlp(depth=4):
    x = layers.data("x", shape=[32])
    label = layers.data("label", shape=[1], dtype="int64")
    h = x
    for _ in range(depth):
        h = layers.fc(h, size=64, act="relu")
    logits = layers.fc(h, size=10)
    loss = layers.mean(layers.softmax_with_cross_entropy(logits, label))
    pt.optimizer.MomentumOptimizer(0.1, momentum=0.9).minimize(loss)
    return loss


def _build_conv():
    img = layers.data("img", shape=[8, 8, 3])
    label = layers.data("label", shape=[1], dtype="int64")
    h = layers.conv2d(img, 8, 3, padding=1, act="relu", data_format="NHWC")
    h = layers.pool2d(h, 2, "max", 2, data_format="NHWC")
    h = layers.conv2d(h, 16, 3, padding=1, act="relu", data_format="NHWC")
    h = layers.pool2d(h, 2, "max", 2, data_format="NHWC")
    h = layers.fc(h, size=32, act="relu", num_flatten_dims=1)
    loss = layers.mean(layers.softmax_with_cross_entropy(
        layers.fc(h, size=10), label))
    pt.optimizer.SGDOptimizer(0.1).minimize(loss)
    return loss


def _mlp_feed(i, bs=16):
    return {"x": np.random.RandomState(100 + i).rand(bs, 32).astype("f4"),
            "label": np.random.RandomState(200 + i)
            .randint(0, 10, (bs, 1)).astype("int64")}


def _conv_feed(i, bs=16):
    return {"img": np.random.RandomState(300 + i)
            .rand(bs, 8, 8, 3).astype("f4"),
            "label": np.random.RandomState(400 + i)
            .randint(0, 10, (bs, 1)).astype("int64")}


def _baseline(build, feeds, fetch_extra=()):
    pt.reset_default_programs()
    pt.reset_global_scope()
    with pt.core.unique_name.guard():
        loss = build()
    exe = pt.Executor()
    exe.run(pt.default_startup_program())
    return [float(exe.run(feed=f, fetch_list=[loss])[0]) for f in feeds]


def _pipeline_run(build, feeds, axes, stages, microbatches, schedule,
                  reduce_strategy=ReduceStrategy.AllReduce, quant=""):
    pt.reset_default_programs()
    pt.reset_global_scope()
    with pt.core.unique_name.guard():
        loss = build()
    bst = BuildStrategy(pipeline_stages=stages,
                        num_microbatches=microbatches,
                        pipeline_schedule=schedule)
    bst.reduce_strategy = reduce_strategy
    bst.quant_comm = quant
    n = 1
    for s in axes.values():
        n *= s
    mesh = DeviceMesh(jax.devices()[:n], axes)
    exe = ParallelExecutor(loss_name=loss.name, mesh=mesh,
                           build_strategy=bst)
    pt.Executor().run(pt.default_startup_program())
    losses = [float(exe.run(feed=f, fetch_list=[loss])[0]) for f in feeds]
    return losses, exe, loss


def _compiled_hlo(exe, feed):
    scope = pt.global_scope()
    cs = list(exe._cache.values())[-1]
    feed_vals = tuple(jnp.asarray(feed[n]) for n in cs.feed_names)
    ro = tuple(scope.get(n) for n in cs.ro_names)
    rw = tuple(scope.get(n) for n in cs.rw_names)
    return cs.fn.lower(feed_vals, ro, rw, np.uint32(0)).compile().as_text()


# ---------------------------------------------------------------------------
# schedule tables (fast: host-side simulation only, no compile)
# ---------------------------------------------------------------------------

@pytest.mark.quick
class TestScheduleTables:
    def test_bubble_census_pins_analytic_model(self):
        for name in ("gpipe", "1f1b"):
            for m, k in ((4, 2), (8, 2), (16, 2), (4, 4), (8, 4), (16, 4)):
                c = schedule_census(name, m, k)
                assert c["ticks"] == 2 * (m + k - 1), (name, m, k, c)
                assert c["bubble_fraction"] == pytest.approx(
                    (k - 1) / (m + k - 1), abs=1e-12), (name, m, k, c)
                # per-stage: every stage idles exactly the bubble slots
                for frac in c["bubble_fraction_per_stage"]:
                    assert frac == pytest.approx(c["bubble_fraction"],
                                                 abs=1e-12), (name, m, k, c)

    def test_1f1b_stash_strictly_below_gpipe_at_2k_microbatches(self):
        # the acceptance claim, asserted via the census (the same tables
        # the engine executes), not assumed
        for k in (2, 4):
            for m in (2 * k, 4 * k):
                g = schedule_census("gpipe", m, k)
                f = schedule_census("1f1b", m, k)
                assert f["peak_stash"] < g["peak_stash"], (m, k, f, g)
                assert g["peak_stash"] == m, (m, k, g)
                assert f["peak_stash"] <= k, (m, k, f)

    def test_tables_cover_every_microbatch_in_dependency_order(self):
        for name in ("gpipe", "1f1b"):
            s = build_schedule(name, 6, 3)
            m_count, k_count = s.num_microbatches, s.num_stages
            for tbl in (s.fwd_mb, s.bwd_mb):
                for k in range(k_count):
                    mbs = [int(v) for v in tbl[:, k] if v >= 0]
                    assert sorted(mbs) == list(range(m_count)), (name, k)
            fs = {(k, m): t for t in range(s.ticks)
                  for k in range(k_count)
                  if (m := int(s.fwd_mb[t, k])) >= 0}
            bs = {(k, m): t for t in range(s.ticks)
                  for k in range(k_count)
                  if (m := int(s.bwd_mb[t, k])) >= 0}
            for m in range(m_count):
                for k in range(k_count - 1):
                    assert fs[(k, m)] < fs[(k + 1, m)], (name, k, m)
                    assert bs[(k + 1, m)] < bs[(k, m)], (name, k, m)
                assert fs[(k_count - 1, m)] < bs[(k_count - 1, m)], (name, m)


# ---------------------------------------------------------------------------
# the partition pass (program-level structure, no compile)
# ---------------------------------------------------------------------------

@pytest.mark.quick
class TestPartitionPass:
    def _partitioned(self, stages=2):
        with pt.core.unique_name.guard():
            loss = _build_mlp()
        prog = pt.default_main_program()
        out = get_pass("pipeline_partition_pass", num_stages=stages,
                       num_microbatches=4, schedule="1f1b", dp_axis="",
                       reduce_dp=False)(prog)
        return loss, prog, out

    def test_one_send_recv_pair_per_boundary(self):
        for stages in (2, 4):
            pt.reset_default_programs()
            loss, prog, out = self._partitioned(stages)
            ops = out.global_block().ops
            sends = [op for op in ops if op.type == "pp_send"]
            recvs = [op for op in ops if op.type == "pp_recv"]
            regions = [op for op in ops if op.type == "pp_pipeline_region"]
            assert len(sends) == stages - 1, [op.type for op in ops]
            assert len(recvs) == stages - 1
            assert len(regions) == 1
            assert not any(op.type == "vjp_region" for op in ops)
            # each send/recv pair shares one buffer and one crossing set
            for s, r in zip(sends, recvs):
                assert s.outputs["Out"] == r.inputs["X"]
                assert s.inputs["X"] == r.outputs["Out"]
            # the caller's program is untouched
            assert any(op.type == "vjp_region"
                       for op in prog.global_block().ops)

    def test_stages_contiguous_and_cost_balanced(self):
        pt.reset_default_programs()
        loss, prog, out = self._partitioned(2)
        region = next(op for op in out.global_block().ops
                      if op.type == "pp_pipeline_region")
        stages = region.attrs["stages"]
        assert len(stages) == 2
        flat = [i for lst in stages for i in lst]
        assert flat == sorted(flat)          # contiguous program order
        costs = region.attrs["stage_costs"]
        assert len(costs) == 2 and all(c > 0 for c in costs)
        # a 5-fc stack splits so neither stage carries everything
        assert max(costs) / sum(costs) < 0.9, costs

    def test_downstream_metric_head_pruned_and_fetch_gated(self):
        """A pure sink chain reading a forward activation (a metric head)
        is pruned — its values only exist per-microbatch inside the
        schedule — and fetching its output raises the clear pipeline
        error instead of a confusing trace failure."""
        with pt.core.unique_name.guard():
            x = layers.data("x", shape=[8])
            label = layers.data("label", shape=[1], dtype="int64")
            h = layers.fc(x, size=16, act="relu")
            logits = layers.fc(h, size=4)
            loss = layers.mean(layers.softmax_with_cross_entropy(
                logits, label))
            pt.optimizer.SGDOptimizer(0.1).minimize(loss)
            # a metric op outside the region reading a forward activation
            metric = layers.mean(h)
        out = get_pass("pipeline_partition_pass", num_stages=2,
                       num_microbatches=2, schedule="1f1b", dp_axis="",
                       reduce_dp=False)(pt.default_main_program())
        kept = [op.type for op in out.global_block().ops]
        # the sink mean over h is gone; the loss path survives
        assert kept.count("mean") == 1, kept
        assert metric.name in out._pp_hidden
        assert loss.name not in out._pp_hidden


# ---------------------------------------------------------------------------
# gates + kill switch
# ---------------------------------------------------------------------------

class TestGatesAndKillSwitch:
    def _exe(self, loss, stages=2, m=4):
        bst = BuildStrategy(pipeline_stages=stages, num_microbatches=m)
        mesh = DeviceMesh(jax.devices()[:stages], {"pp": stages})
        return ParallelExecutor(loss_name=loss.name, mesh=mesh,
                                build_strategy=bst)

    def test_batch_norm_rejected(self):
        with pt.core.unique_name.guard():
            x = layers.data("x", shape=[16])
            label = layers.data("label", shape=[1], dtype="int64")
            h = layers.batch_norm(layers.fc(x, size=16))
            loss = layers.mean(layers.softmax_with_cross_entropy(
                layers.fc(h, size=4), label))
            pt.optimizer.SGDOptimizer(0.1).minimize(loss)
        exe = self._exe(loss)
        pt.Executor().run(pt.default_startup_program())
        with pytest.raises(InvalidArgumentError, match="batch_norm"):
            exe.run(feed={"x": np.zeros((8, 16), np.float32),
                          "label": np.zeros((8, 1), np.int64)},
                    fetch_list=[loss])

    def test_non_mean_loss_rejected(self):
        with pt.core.unique_name.guard():
            x = layers.data("x", shape=[16])
            label = layers.data("label", shape=[1], dtype="int64")
            per_row = layers.softmax_with_cross_entropy(
                layers.fc(x, size=4), label)
            loss = layers.reduce_sum(per_row)
            pt.optimizer.SGDOptimizer(0.1).minimize(loss)
        exe = self._exe(loss)
        pt.Executor().run(pt.default_startup_program())
        with pytest.raises(InvalidArgumentError, match="MEAN-reduced"):
            exe.run(feed={"x": np.zeros((8, 16), np.float32),
                          "label": np.zeros((8, 1), np.int64)},
                    fetch_list=[loss])

    def test_non_divisible_microbatches_rejected(self):
        with pt.core.unique_name.guard():
            loss = _build_mlp()
        exe = self._exe(loss, m=4)
        pt.Executor().run(pt.default_startup_program())
        with pytest.raises(InvalidArgumentError, match="num_microbatches"):
            exe.run(feed=_mlp_feed(0, bs=14), fetch_list=[loss])

    def test_hidden_activation_fetch_rejected(self):
        with pt.core.unique_name.guard():
            x = layers.data("x", shape=[8])
            label = layers.data("label", shape=[1], dtype="int64")
            h = layers.fc(x, size=16, act="relu")
            loss = layers.mean(layers.softmax_with_cross_entropy(
                layers.fc(h, size=4), label))
            pt.optimizer.SGDOptimizer(0.1).minimize(loss)
        exe = self._exe(loss)
        pt.Executor().run(pt.default_startup_program())
        with pytest.raises(InvalidArgumentError,
                           match="forward activation"):
            exe.run(feed=_mlp_feed(0, bs=8) | {
                "x": np.zeros((8, 8), np.float32)},
                fetch_list=[loss, h])

    def test_mesh_without_pp_axis_rejected(self):
        with pt.core.unique_name.guard():
            loss = _build_mlp()
        bst = BuildStrategy(pipeline_stages=2, num_microbatches=4)
        mesh = DeviceMesh(jax.devices()[:2], {"dp": 2})
        exe = ParallelExecutor(loss_name=loss.name, mesh=mesh,
                               build_strategy=bst)
        pt.Executor().run(pt.default_startup_program())
        with pytest.raises(InvalidArgumentError, match="pp"):
            exe.run(feed=_mlp_feed(0), fetch_list=[loss])


@pytest.mark.quick
class TestPipelineApplyBoundary:
    def test_divisibility_enforced_with_clear_message(self):
        """Satellite (r09): the bare `assert` at the pipeline_apply API
        boundary is now an enforce-style error."""
        mesh = DeviceMesh(jax.devices()[:2], {"pp": 2})
        w = {"w": jnp.zeros((2, 4), jnp.float32)}
        x = jnp.zeros((6, 4), jnp.float32)
        with pytest.raises(InvalidArgumentError,
                           match="not divisible by num_microbatches"):
            pipeline_apply(mesh, lambda p, h: h, w, x, num_microbatches=4)
        with pytest.raises(InvalidArgumentError, match=">= 1"):
            pipeline_apply(mesh, lambda p, h: h, w, x, num_microbatches=0)
