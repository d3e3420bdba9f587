"""Ring attention v2 evidence suite (VERDICT r4 #2).

Three committed claims:
  (a) ring-of-1 is exactly the flash formulation (parity incl. gradients);
  (b) a causal ring executes only the live half of the block grid —
      n(n+1)/2 of n^2 — and segment-disjoint steps are skipped too;
  (c) the forward ring's comm structure is exactly n-1 KV ppermute hops
      (x2 arrays), visible in the compiled HLO.

(a) is this file; (b) and (c) are tests/test_ring_dead_steps.py, a file of
their own so that `--dist loadfile` can give the two halves of what was the
suite's longest file to two workers.

The pallas kernel path itself is exercised through the interpreter
(backend="pallas_interpret") so the CPU suite pins the same code the TPU
runs, block tilings included.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.parallel.mesh import DeviceMesh
from paddle_tpu.parallel.ring_attention import (
    ring_attention, ring_attention_live_blocks, ring_attention_sharded)


@pytest.fixture
def rng():
    return np.random.RandomState(0)


def make_mesh(axes):
    n = int(np.prod(list(axes.values())))
    return DeviceMesh(jax.devices()[:n], axes)


def _full_reference(q, k, v, causal, seg=None):
    scale = 1.0 / np.sqrt(q.shape[-1])
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    t, tk = q.shape[1], k.shape[1]
    mask = np.ones((t, tk), bool)
    if causal:
        mask &= np.tril(np.ones((t, tk), bool))
    m = jnp.asarray(mask)[None, None]
    if seg is not None:
        m = m & (seg[:, :, None] == seg[:, None, :])[:, None]
    s = jnp.where(m, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(jnp.any(m, axis=-1)[..., None], p, 0.0)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


class TestRingFlashParity:
    """(a): the ring's per-block computation IS the flash kernel."""

    @pytest.mark.parametrize("causal", [False, True])
    def test_ring_of_1_matches_flash(self, rng, causal):
        from paddle_tpu.ops.pallas_kernels import flash_attention
        mesh = make_mesh({"sp": 1})
        b, t, h, d = 2, 128, 2, 16
        q, k, v = (jnp.asarray(rng.randn(b, t, h, d).astype("float32"))
                   for _ in range(3))
        out = ring_attention_sharded(mesh, q, k, v, causal=causal,
                                     backend="pallas_interpret")
        # flash_attention runs head-major [B, H, T, D]
        ref = flash_attention(
            jnp.transpose(q, (0, 2, 1, 3)), jnp.transpose(k, (0, 2, 1, 3)),
            jnp.transpose(v, (0, 2, 1, 3)), causal=causal,
            backend="pallas_interpret")
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(jnp.transpose(ref, (0, 2, 1, 3))),
            rtol=2e-4, atol=2e-5)

    @pytest.mark.parametrize("n,causal", [(2, False), (2, True), (4, True)])
    def test_ring_pallas_blocks_match_full_attention(self, rng, n, causal):
        mesh = make_mesh({"sp": n})
        b, t, h, d = 1, 128 * n, 1, 16
        q, k, v = (jnp.asarray(rng.randn(b, t, h, d).astype("float32"))
                   for _ in range(3))
        out = ring_attention_sharded(mesh, q, k, v, causal=causal,
                                     backend="pallas_interpret")
        ref = _full_reference(q, k, v, causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-5)

    def test_ring_pallas_gradients_match_composite(self, rng):
        """Flash-backward ring (global-residual block bwd + dKV rotation)
        against jax autodiff of the dense reference."""
        mesh = make_mesh({"sp": 2})
        b, t, h, d = 1, 256, 1, 16
        q = jnp.asarray(rng.randn(b, t, h, d).astype("float32"))
        k = jnp.asarray(rng.randn(b, t, h, d).astype("float32"))
        v = jnp.asarray(rng.randn(b, t, h, d).astype("float32"))
        w = jnp.asarray(rng.randn(b, t, h, d).astype("float32"))

        def ring_loss(q, k, v):
            out = ring_attention_sharded(mesh, q, k, v, causal=True,
                                         backend="pallas_interpret")
            return jnp.sum(out * w)

        def ref_loss(q, k, v):
            return jnp.sum(_full_reference(q, k, v, True) * w)

        g_ring = jax.grad(ring_loss, argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
        for gr, gf, name in zip(g_ring, g_ref, "qkv"):
            np.testing.assert_allclose(
                np.asarray(gr), np.asarray(gf), rtol=5e-4, atol=5e-5,
                err_msg=f"d{name} mismatch")

    def test_ring_packed_segments_gradients(self, rng):
        """Backward ring WITH segment ids (seg_blk rotation + segment
        masking inside _block_bwd) against autodiff of the dense
        reference."""
        mesh = make_mesh({"sp": 2})
        b, t, h, d = 1, 256, 1, 16
        q = jnp.asarray(rng.randn(b, t, h, d).astype("float32"))
        k = jnp.asarray(rng.randn(b, t, h, d).astype("float32"))
        v = jnp.asarray(rng.randn(b, t, h, d).astype("float32"))
        w = jnp.asarray(rng.randn(b, t, h, d).astype("float32"))
        seg = jnp.asarray(
            np.repeat(np.arange(1, 5), t // 4)[None], jnp.int32)

        def ring_loss(q, k, v):
            out = ring_attention_sharded(mesh, q, k, v, segment_ids=seg,
                                         backend="pallas_interpret")
            return jnp.sum(out * w)

        def ref_loss(q, k, v):
            return jnp.sum(_full_reference(q, k, v, False, seg) * w)

        g_ring = jax.grad(ring_loss, argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
        for gr, gf, name in zip(g_ring, g_ref, "qkv"):
            np.testing.assert_allclose(
                np.asarray(gr), np.asarray(gf), rtol=5e-4, atol=5e-5,
                err_msg=f"d{name} mismatch")

    def test_live_blocks_sums_over_data_axis(self, rng):
        """Heterogeneous packing across a dp-sharded batch: the live
        count is the MESH total, not one data shard's."""
        mesh = make_mesh({"dp": 2, "sp": 4})
        t = 32
        q = jnp.asarray(rng.randn(2, t, 1, 8).astype("float32"))
        # batch row 0: one segment (all 16 sp-blocks live on that shard);
        # batch row 1: four disjoint per-shard segments (only the 4
        # diagonal steps live)
        seg = jnp.asarray(np.stack([
            np.ones(t), np.repeat(np.arange(1, 5), t // 4)]), jnp.int32)
        _, live = ring_attention_live_blocks(mesh, q, q, q,
                                             segment_ids=seg,
                                             backend="xla")
        assert live == 16 + 4, live

    def test_live_blocks_not_inflated_by_replicated_axes(self, rng):
        """Regression (ADVICE r5 #1): on a dp×tp×sp mesh the live-block
        psum must run only over the axes the body is sharded on (dp, sp);
        summing over the replicated tp axis would double the count."""
        mesh = make_mesh({"dp": 2, "tp": 2, "sp": 2})
        n = 2
        q = jnp.asarray(rng.randn(2, 8 * n, 1, 8).astype("float32"))
        _, live = ring_attention_live_blocks(mesh, q, q, q, causal=True,
                                             backend="xla")
        # per data shard a causal sp=2 ring executes n(n+1)/2 = 3 of 4
        # steps; dp=2 shards -> 6. The tp=2 replicas must NOT double it.
        assert live == 2 * (n * (n + 1) // 2), live

    def test_ring_packed_segments_pallas(self, rng):
        """Packed segment ids through the flash blocks on the ring."""
        mesh = make_mesh({"sp": 2})
        b, t, h, d = 2, 256, 1, 16
        q, k, v = (jnp.asarray(rng.randn(b, t, h, d).astype("float32"))
                   for _ in range(3))
        seg = np.repeat(np.arange(1, 5), t // 4)[None].repeat(b, 0)
        out = ring_attention_sharded(
            mesh, q, k, v, segment_ids=jnp.asarray(seg, jnp.int32),
            backend="pallas_interpret")
        ref = _full_reference(q, k, v, False, jnp.asarray(seg))
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-5)
