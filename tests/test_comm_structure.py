"""Compile-level scaling evidence (VERDICT r4 #6).

Loss parity across worlds shows the programs compute the right thing; these
tests assert the *communication structure* of the compiled HLO — the
strongest scaling evidence a single-host environment can commit, ≙ the
reference's multi-devices graph invariants
(framework/details/multi_devices_graph_check_pass.cc):

  - dp:      total all-reduce bytes == gradient bytes (+ scalar loss
             reductions), nothing more
  - ZeRO-1:  gradients travel as reduce-scatter + all-gather, not
             all-reduce
  - tp:      a column->row Megatron pair costs exactly ONE all-reduce
  - pp:      the microbatch ring is collective-permutes, no all-to-all
  - ep:      a sharded-embedding lookup combines with exactly one psum
             (plus the id broadcast's gather machinery), table stays put

All on the 8-virtual-CPU-device mesh; byte counts parsed from the
partitioned, optimized HLO.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers
# the census the explicit-pipeline suite (tests/test_zero_comm.py) reads
# too: one byte model
from paddle_tpu.framework.costs import collective_census
from paddle_tpu.parallel.mesh import DeviceMesh


@pytest.fixture
def rng():
    return np.random.RandomState(0)


@pytest.fixture(autouse=True)
def _fresh():
    pt.reset_default_programs()
    pt.reset_global_scope()
    yield


def _compiled_step_hlo(exe, feed, loss, scope=None):
    """Optimized (post-SPMD-partitioning) HLO of the last compiled step."""
    scope = scope or pt.global_scope()
    cs = list(exe._cache.values())[-1]
    feed_vals = tuple(feed[n] for n in cs.feed_names)
    ro = tuple(scope.get(n) for n in cs.ro_names)
    rw = tuple(scope.get(n) for n in cs.rw_names)
    return cs.fn.lower(feed_vals, ro, rw, np.uint32(0)).compile().as_text()


def _build_mlp(bs):
    x = layers.data("x", shape=[64])
    label = layers.data("label", shape=[1], dtype="int64")
    h = layers.fc(x, size=128, act="relu")
    logits = layers.fc(h, size=10)
    loss = layers.mean(layers.softmax_with_cross_entropy(logits, label))
    return x, label, loss


def _param_grad_bytes():
    """f32 bytes of every trainable parameter (== gradient bytes)."""
    scope = pt.global_scope()
    prog = pt.default_main_program()
    total = 0
    for v in prog.global_block().vars.values():
        if getattr(v, "persistable", False) and scope.has_var(v.name) \
                and not getattr(v, "is_optimizer_state", False) \
                and not v.name.startswith("learning_rate"):
            n = 1
            for d in v.shape:
                n *= d
            total += n * 4
    return total


class TestDataParallelStructure:
    def test_allreduce_bytes_equal_grad_bytes(self, rng):
        from paddle_tpu.parallel import ParallelExecutor
        from paddle_tpu.parallel.strategy import BuildStrategy

        mesh = DeviceMesh(jax.devices(), {"dp": 8})
        bs = 32
        _build = _build_mlp(bs)
        loss = _build[2]
        pt.optimizer.SGDOptimizer(learning_rate=0.1).minimize(loss)
        exe = ParallelExecutor(loss_name=loss.name, mesh=mesh)
        pt.Executor().run(pt.default_startup_program())
        feed = {"x": rng.rand(bs, 64).astype("float32"),
                "label": rng.randint(0, 10, (bs, 1)).astype("int64")}
        exe.run(feed=feed, fetch_list=[loss])
        hlo = _compiled_step_hlo(
            exe, {k: jnp.asarray(v) for k, v in feed.items()}, loss)

        census = collective_census(hlo)
        grad_bytes = _param_grad_bytes()
        assert grad_bytes == (64 * 128 + 128 + 128 * 10 + 10) * 4
        ar_bytes = sum(b for b, _ in census.get("all-reduce", []))
        # every gradient is all-reduced exactly once; the only other
        # all-reduces are scalar/row loss+softmax reductions (mean over the
        # sharded batch). No reduce-scatter (that is ZeRO's signature).
        assert ar_bytes >= grad_bytes, (ar_bytes, grad_bytes)
        assert ar_bytes <= grad_bytes + 64 * 1024, (ar_bytes, grad_bytes)
        assert "reduce-scatter" not in census, census.keys()
        assert "all-to-all" not in census, census.keys()
        # no all-gather either: replicated params update redundantly on
        # every shard — the ZeRO-1 test asserts the opposite
        assert "all-gather" not in census, census.keys()


class TestZeroStructure:
    def test_zero1_uses_reduce_scatter_plus_all_gather(self, rng):
        from paddle_tpu.parallel import ParallelExecutor
        from paddle_tpu.parallel.strategy import (BuildStrategy,
                                                  ReduceStrategy)

        mesh = DeviceMesh(jax.devices(), {"dp": 8})
        bs = 32
        x = layers.data("x", shape=[64])
        label = layers.data("label", shape=[1], dtype="int64")
        h = layers.fc(x, size=128, act="relu")
        logits = layers.fc(h, size=10)
        loss = layers.mean(layers.softmax_with_cross_entropy(logits, label))
        # momentum: a [shape]-sized accumulator per param, sharded by ZeRO-1
        pt.optimizer.MomentumOptimizer(learning_rate=0.1,
                                       momentum=0.9).minimize(loss)
        bst = BuildStrategy()
        bst.reduce_strategy = ReduceStrategy.Reduce
        exe = ParallelExecutor(loss_name=loss.name, mesh=mesh,
                               build_strategy=bst)
        pt.Executor().run(pt.default_startup_program())
        feed = {"x": rng.rand(bs, 64).astype("float32"),
                "label": rng.randint(0, 10, (bs, 1)).astype("int64")}
        exe.run(feed=feed, fetch_list=[loss])
        hlo = _compiled_step_hlo(
            exe, {k: jnp.asarray(v) for k, v in feed.items()}, loss)

        census = collective_census(hlo)
        # the ZeRO-1 signature vs plain dp: the updated param comes BACK
        # via an all-gather (each data shard owns 1/8 of the accumulator
        # and applies 1/8 of the update). The grad half is reduce-scatter
        # where the partitioner forms it; XLA:CPU lowers psum+slice as
        # all-reduce + dynamic-slice instead (bitwise the same movement on
        # the virtual mesh; the TPU partitioner emits reduce-scatter) — so
        # accept either, but the all-gather is non-negotiable.
        assert "all-gather" in census, census.keys()
        ag_bytes = sum(b for b, _ in census.get("all-gather", []))
        shardable = 64 * 128 * 4  # w1 bytes (f32): dim0 % 8 == 0 -> shards
        assert ag_bytes >= shardable, (ag_bytes, shardable)
        if "reduce-scatter" in census:
            rs_bytes = sum(b for b, _ in census["reduce-scatter"])
            assert rs_bytes >= shardable // 8, (rs_bytes, shardable)
        # the sharded optimizer math is real: the all-gather's operand is
        # the fused update computation, not a plain parameter copy
        ag_line = census["all-gather"][0][1]
        assert "fusion" in ag_line or "subtract" in ag_line, ag_line[:160]


class TestTensorParallelStructure:
    def test_column_row_pair_costs_one_allreduce(self, rng):
        from paddle_tpu.parallel import tensor_parallel as tp

        mesh = DeviceMesh(jax.devices(), {"tp": 8})
        x = jnp.asarray(rng.rand(16, 64).astype("float32"))
        w1 = jnp.asarray(rng.rand(64, 128).astype("float32"))
        w2 = jnp.asarray(rng.rand(128, 64).astype("float32"))

        @jax.jit
        def mlp(x, w1, w2):
            with mesh.jax_mesh:
                h = jax.nn.relu(tp.column_parallel_matmul(x, w1))
                return tp.row_parallel_matmul(h, w2)

        with mesh.jax_mesh:
            hlo = mlp.lower(x, w1, w2).compile().as_text()
        census = collective_census(hlo)
        ars = census.get("all-reduce", [])
        assert len(ars) == 1, [l[:120] for _, l in ars]
        # ... and it moves exactly the row-matmul's output [16, 64] f32
        assert ars[0][0] == 16 * 64 * 4, ars[0]
        assert "all-to-all" not in census
        assert "collective-permute" not in census

    def test_two_pairs_cost_two_allreduces(self, rng):
        from paddle_tpu.parallel import tensor_parallel as tp

        mesh = DeviceMesh(jax.devices(), {"tp": 8})
        x = jnp.asarray(rng.rand(16, 64).astype("float32"))
        ws = [jnp.asarray(rng.rand(64, 128).astype("float32")),
              jnp.asarray(rng.rand(128, 64).astype("float32")),
              jnp.asarray(rng.rand(64, 128).astype("float32")),
              jnp.asarray(rng.rand(128, 64).astype("float32"))]

        @jax.jit
        def mlp2(x, w1, w2, w3, w4):
            with mesh.jax_mesh:
                h = jax.nn.relu(tp.column_parallel_matmul(x, w1))
                h = tp.row_parallel_matmul(h, w2)
                h = jax.nn.relu(tp.column_parallel_matmul(h, w3))
                return tp.row_parallel_matmul(h, w4)

        with mesh.jax_mesh:
            hlo = mlp2.lower(x, *ws).compile().as_text()
        ars = collective_census(hlo).get("all-reduce", [])
        assert len(ars) == 2, [l[:120] for _, l in ars]


class TestPipelineStructure:
    def test_ring_is_collective_permutes_only(self, rng):
        from paddle_tpu.parallel.pipeline import pipeline_apply

        n_stage, d, mb = 8, 16, 4
        mesh = DeviceMesh(jax.devices(), {"pp": 8})
        ws = jnp.asarray(rng.randn(n_stage, d, d).astype("float32") * 0.1)
        x = jnp.asarray(rng.randn(32, d).astype("float32"))

        def stage(p, h):
            return jnp.tanh(h @ p["w"])

        @jax.jit
        def run(ws, x):
            return pipeline_apply(mesh, stage, {"w": ws}, x,
                                  num_microbatches=mb)

        hlo = run.lower(ws, x).compile().as_text()
        census = collective_census(hlo)
        assert "collective-permute" in census, census.keys()
        assert "all-to-all" not in census
        # the schedule is a ROLLED lax.scan: exactly ONE collective-permute
        # instruction lives in the loop body and executes M + n - 1 times;
        # the loop structure itself must be present in the module
        n_cp = len(census["collective-permute"])
        assert n_cp == 1, n_cp
        assert re.search(r"\bwhile\(", hlo), "pipeline loop was unrolled?"
        # one final psum surfaces the last stage's outputs
        ars = census.get("all-reduce", [])
        assert len(ars) == 1, [l[:120] for _, l in ars]
        # the rotation moves one microbatch activation [mb-rows, d] f32
        assert census["collective-permute"][0][0] == (32 // mb) * d * 4, \
            census["collective-permute"][0]


class TestShardedEmbeddingStructure:
    def test_lookup_is_one_psum_table_stays_put(self, rng):
        from paddle_tpu.parallel.sharded_embedding import (
            sharded_embedding_lookup)

        mesh = DeviceMesh(jax.devices(), {"tp": 8})
        table = jnp.asarray(rng.rand(64, 16).astype("float32"))
        ids = jnp.asarray(rng.randint(0, 64, (4, 7)))

        @jax.jit
        def lookup(table, ids):
            return sharded_embedding_lookup(mesh, table, ids,
                                            axis_name="tp")

        hlo = lookup.lower(table, ids).compile().as_text()
        census = collective_census(hlo)
        ars = census.get("all-reduce", [])
        assert len(ars) == 1, [l[:120] for _, l in ars]
        # the psum moves activation-sized data ([4, 7, 16] f32), NOT the
        # table: shipping rows, not the table, is the point of EP
        assert ars[0][0] == 4 * 7 * 16 * 4, ars[0]
        table_bytes = 64 * 16 * 4
        for kind, items in census.items():
            for b, line in items:
                assert b < table_bytes, (kind, b, line[:120])
