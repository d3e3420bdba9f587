"""Ring attention v2 evidence suite, claims (b) and (c) (the file's first
half and the claims are in tests/test_ring_attention_v2.py): a causal ring
executes only the live half of the block grid, segment-disjoint steps are
skipped too, and the forward ring is exactly n-1 KV hops in the compiled
HLO."""

import re

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.parallel.ring_attention import (ring_attention_live_blocks,
                                                ring_attention_sharded)

from test_ring_attention_v2 import (_full_reference, make_mesh,  # noqa: F401
                                    rng)


class TestRingDeadStepSkipping:
    """(b): whole ring steps with no visible keys execute nothing."""

    def test_causal_ring_executes_half_the_blocks(self, rng):
        n = 8
        mesh = make_mesh({"sp": n})
        q = jnp.asarray(rng.randn(1, 8 * n, 1, 8).astype("float32"))
        _, live = ring_attention_live_blocks(mesh, q, q, q, causal=True,
                                             backend="xla")
        assert live == n * (n + 1) // 2          # 36 of 64
        _, live_full = ring_attention_live_blocks(mesh, q, q, q,
                                                  causal=False,
                                                  backend="xla")
        assert live_full == n * n

    def test_segment_disjoint_steps_are_dead(self, rng):
        n = 8
        mesh = make_mesh({"sp": n})
        t = 8 * n
        q = jnp.asarray(rng.randn(1, t, 1, 8).astype("float32"))
        # two macro-segments, each spanning half the shards: shards only
        # compute against same-half KV blocks -> 2 * (n/2)^2 live steps
        seg = jnp.asarray(
            np.repeat([1, 2], t // 2)[None], jnp.int32)
        out, live = ring_attention_live_blocks(mesh, q, q, q,
                                               segment_ids=seg,
                                               backend="xla")
        assert live == 2 * (n // 2) ** 2         # 32 of 64
        ref = _full_reference(q, q, q, False, seg)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-5)

    def test_skipping_changes_nothing_numerically(self, rng):
        """Causal output with skipping == dense reference (the dead steps
        contributed exactly nothing)."""
        n = 8
        mesh = make_mesh({"sp": n})
        q = jnp.asarray(rng.randn(2, 8 * n, 2, 8).astype("float32"))
        out, _ = ring_attention_live_blocks(mesh, q, q, q, causal=True,
                                            backend="xla")
        ref = _full_reference(q, q, q, True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-5)


class TestRingCommStructure:
    """(c): exactly n-1 KV rotation hops in the forward ring HLO."""

    def _count_collective_permutes(self, fn, *args):
        ex = jax.jit(fn).lower(*args).compile()
        hlo = ex.as_text()
        starts = len(re.findall(r"collective-permute-start", hlo))
        if starts:
            return starts
        return len(re.findall(r"= \S+ collective-permute\(", hlo))

    def test_forward_ring_has_n_minus_1_kv_hops(self, rng):
        n = 8
        mesh = make_mesh({"sp": n})
        q = jnp.asarray(rng.randn(1, 8 * n, 1, 8).astype("float32"))

        def fwd(q):
            return ring_attention_sharded(mesh, q, q, q, causal=True,
                                          backend="xla")

        count = self._count_collective_permutes(fwd, q)
        # k and v each take n-1 hops; XLA may fuse the pair into one
        # collective-permute per hop but must not exceed 2(n-1)
        assert n - 1 <= count <= 2 * (n - 1), count

    def test_backward_ring_comm_volume(self, rng):
        n = 4
        mesh = make_mesh({"sp": n})
        q = jnp.asarray(rng.randn(1, 8 * n, 1, 8).astype("float32"))

        def loss(q):
            return ring_attention_sharded(mesh, q, q, q, causal=True,
                                          backend="xla").sum()

        count = self._count_collective_permutes(jax.grad(loss), q)
        # fwd ring: 2(n-1) (k, v) + bwd ring: 2(n-1) (k, v) + 2n (dk, dv);
        # allow pairwise fusion down to half
        upper = 4 * (n - 1) + 2 * n
        assert upper // 2 <= count <= upper, count
