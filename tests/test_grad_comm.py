"""Unit tests for the gradient-comm collective primitives (fast tier).

Numpy parity of the block-scale compress/decompress round trip, the
reduce_scatter divisibility contract at the API boundary, and the shared
wire-byte accounting model. The executor-level pipeline suite (HLO census,
loss parity, error-feedback state) lives in tests/test_zero_comm.py.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import paddle_tpu as pt
from paddle_tpu.core.enforce import InvalidArgumentError
from paddle_tpu.framework.costs import (collective_census,
                                        collective_wire_bytes)
from paddle_tpu.parallel import collective as C
from paddle_tpu.parallel.mesh import DeviceMesh


def _np_quantize_blocks(flat, block):
    """Independent numpy reimplementation of collective.quantize_blocks."""
    xb = flat.reshape(-1, block)
    amax = np.max(np.abs(xb), axis=1, keepdims=True)
    scale = np.where(amax > 0, amax / 127.0, 1.0).astype(np.float32)
    q = np.clip(np.round(xb / scale), -127, 127).astype(np.int8)
    return q, scale


class TestBlockQuantization:
    def test_roundtrip_matches_numpy(self, rng):
        flat = (rng.randn(4 * 256) * 3).astype(np.float32)
        q, s = C.quantize_blocks(jnp.asarray(flat), block=256)
        qn, sn = _np_quantize_blocks(flat, 256)
        np.testing.assert_array_equal(np.asarray(q), qn)
        np.testing.assert_allclose(np.asarray(s), sn, rtol=1e-7)
        deq = np.asarray(C.dequantize_blocks(q, s))
        np.testing.assert_allclose(deq, (qn.astype(np.float32) * sn).ravel(),
                                   rtol=1e-7)

    def test_roundtrip_error_bound(self, rng):
        flat = (rng.randn(8 * 128) * 10).astype(np.float32)
        q, s = C.quantize_blocks(jnp.asarray(flat), block=128)
        deq = np.asarray(C.dequantize_blocks(q, s))
        # symmetric round-to-nearest: per-value error <= scale/2
        bound = np.repeat(np.asarray(s).ravel(), 128) / 2 + 1e-7
        assert np.all(np.abs(deq - flat) <= bound)

    def test_zero_blocks_exact(self):
        flat = jnp.zeros((512,), jnp.float32)
        q, s = C.quantize_blocks(flat, block=256)
        assert np.all(np.asarray(q) == 0)
        assert np.all(np.asarray(s) == 1.0)
        np.testing.assert_array_equal(np.asarray(C.dequantize_blocks(q, s)),
                                      np.zeros(512, np.float32))

    def test_residual_is_exact_complement(self, rng):
        # flat == dequant(compress(flat)) + residual, in the exact padded
        # chunk layout the wire transfer uses
        flat = (rng.randn(8 * 100) * 2).astype(np.float32)   # chunks of 100
        res = np.asarray(C.quantization_residual_flat(
            jnp.asarray(flat), 8, wire_dtype="int8", block=64))
        xb = flat.reshape(8, 100)
        xp = np.pad(xb, ((0, 0), (0, 28)))                    # cpad 128
        qn, sn = _np_quantize_blocks(xp.reshape(-1), 64)
        deq = (qn.astype(np.float32) * sn).reshape(8, 128)[:, :100]
        np.testing.assert_allclose(res, flat - deq.reshape(-1),
                                   rtol=1e-6, atol=1e-7)

    def test_bf16_compress(self, rng):
        flat = (rng.randn(256)).astype(np.float32)
        res = np.asarray(C.quantization_residual_flat(
            jnp.asarray(flat), 8, wire_dtype="bf16"))
        np.testing.assert_allclose(
            res, flat - flat.astype(jnp.bfloat16).astype(np.float32),
            rtol=1e-6, atol=1e-7)


class TestReduceScatterBoundary:
    """Satellite: reduce_scatter for dims not divisible by the axis size
    used to surface a shape error from deep inside psum_scatter; now the
    API boundary raises a clear enforce error."""

    def _mesh(self):
        return DeviceMesh(jax.devices(), {"dp": 8})

    def test_divisible_ok(self):
        mesh = self._mesh()
        f = jax.shard_map(lambda x: C.reduce_scatter(x, "dp"),
                      mesh=mesh.jax_mesh, in_specs=(P(),),
                      out_specs=P("dp"), check_vma=False)
        out = jax.jit(f)(jnp.ones((16, 4), jnp.float32))
        # every shard contributed identical ones: each owned slice sums to 8
        np.testing.assert_array_equal(np.asarray(out),
                                      np.full((16, 4), 8.0, np.float32))

    def test_non_divisible_raises_clear_error(self):
        mesh = self._mesh()
        f = jax.shard_map(lambda x: C.reduce_scatter(x, "dp"),
                      mesh=mesh.jax_mesh, in_specs=(P(),),
                      out_specs=P("dp"), check_vma=False)
        with pytest.raises(InvalidArgumentError, match="not divisible"):
            jax.jit(f)(jnp.ones((10, 4), jnp.float32))

    def test_bad_dim_raises(self):
        mesh = self._mesh()
        f = jax.shard_map(lambda x: C.reduce_scatter(x, "dp", scatter_dim=3),
                      mesh=mesh.jax_mesh, in_specs=(P(),),
                      out_specs=P("dp"), check_vma=False)
        with pytest.raises(InvalidArgumentError, match="out of range"):
            jax.jit(f)(jnp.ones((16, 4), jnp.float32))


class TestCollectiveCensusParsing:
    def test_tuple_shape_with_tpu_layout(self):
        # TPU HLO prints tiled layouts with parens INSIDE the tuple shape
        # — the census must not silently drop such instructions (that
        # would make no-gradient-all-reduce asserts pass vacuously)
        hlo = ("  %ar = (f32[128,256]{1,0:T(8,128)}, f32[64]{0:T(256)}) "
               "all-reduce(f32[128,256]{1,0:T(8,128)} %a, f32[64]{0} %b), "
               "replica_groups={{0,1}}\n"
               "  %a2a = (s8[8,256]{1,0:T(8,128)(4,1)}) "
               "all-to-all(s8[8,256]{1,0} %q), replica_groups={{0,1}}\n")
        census = collective_census(hlo)
        assert sum(b for b, _ in census["all-reduce"]) == 128 * 256 * 4 + 256
        assert sum(b for b, _ in census["all-to-all"]) == 8 * 256

    def test_async_pairs_counted_once(self):
        hlo = ("  %s = f32[64]{0} all-reduce-start(f32[64]{0} %x)\n"
               "  %d = f32[64]{0} all-reduce-done(f32[64]{0} %s)\n")
        assert len(collective_census(hlo)["all-reduce"]) == 1


class TestMeanLossGate:
    def test_sum_reduced_loss_rejected(self, rng):
        """The explicit pipeline averages per-shard gradients — only exact
        for a batch-MEAN loss. A sum-reduced loss must be rejected, not
        silently trained at 1/dp gradient scale."""
        from paddle_tpu import layers
        from paddle_tpu.parallel import ParallelExecutor
        from paddle_tpu.parallel.strategy import (BuildStrategy,
                                                  ReduceStrategy)
        x = layers.data("x", shape=[16])
        label = layers.data("label", shape=[1], dtype="int64")
        loss = layers.reduce_sum(layers.softmax_with_cross_entropy(
            layers.fc(x, size=4), label))
        pt.optimizer.SGDOptimizer(0.1).minimize(loss)
        bst = BuildStrategy()
        bst.reduce_strategy = ReduceStrategy.ReduceScatter
        exe = ParallelExecutor(loss_name=loss.name,
                               mesh=DeviceMesh(jax.devices(), {"dp": 8}),
                               build_strategy=bst)
        pt.Executor().run(pt.default_startup_program())
        with pytest.raises(InvalidArgumentError, match="MEAN-reduced"):
            exe.run(feed={"x": np.zeros((16, 16), np.float32),
                          "label": np.zeros((16, 1), np.int64)},
                    fetch_list=[loss])


class TestWireByteModel:
    def test_allreduce_equals_rs_plus_ag(self):
        # the ring identity the reduce-scatter mode exploits: an all-reduce
        # costs exactly its reduce-scatter + all-gather decomposition
        n, dev = 1 << 20, 8
        ar = collective_wire_bytes("all-reduce", n, dev)
        rs = collective_wire_bytes("reduce-scatter", n // dev, dev)
        ag = collective_wire_bytes("all-gather", n, dev)
        assert ar == rs + ag

    def test_compressed_ratio(self):
        # int8 + one f32 scale per 256 values: 3.94x fewer bytes than f32
        assert 1 / C.compressed_size_ratio("int8", 256) > 3.9
        assert C.compressed_size_ratio("bf16") == 0.5
