"""Flash-attention kernel tests (pallas interpret mode on CPU) + fused op.

≙ SURVEY.md §7 stage 4 (Pallas kernels for hot ops). The kernel's tiling /
online-softmax logic is pinned against the XLA composite; gradients flow
through the custom VJP; the transformer uses the fused op when attention
dropout is off.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops.pallas_kernels import (_attention_reference,
                                           flash_attention)


def _qkv(rng, B=2, H=2, T=64, D=16):
    return (rng.randn(B, H, T, D).astype("float32") * 0.5,
            rng.randn(B, H, T, D).astype("float32") * 0.5,
            rng.randn(B, H, T, D).astype("float32"))


class TestFlashKernel:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_composite(self, rng, causal):
        q, k, v = _qkv(rng)
        ref = flash_attention(q, k, v, causal=causal, backend="xla")
        got = flash_attention(q, k, v, causal=causal, block_q=32,
                              block_k=16, backend="pallas_interpret")
        np.testing.assert_allclose(got, ref, atol=2e-5, rtol=2e-5)

    def test_uneven_block_sizes_padded_correctly(self, rng):
        q, k, v = _qkv(rng, T=48)
        ref = flash_attention(q, k, v, backend="xla")
        got = flash_attention(q, k, v, block_q=32, block_k=32,
                              backend="pallas_interpret")
        np.testing.assert_allclose(got, ref, atol=2e-5, rtol=2e-5)

    def test_cross_attention_lengths(self, rng):
        q = rng.randn(1, 2, 32, 16).astype("float32")
        k = rng.randn(1, 2, 64, 16).astype("float32")
        v = rng.randn(1, 2, 64, 16).astype("float32")
        ref = flash_attention(q, k, v, backend="xla")
        got = flash_attention(q, k, v, block_q=16, block_k=16,
                              backend="pallas_interpret")
        np.testing.assert_allclose(got, ref, atol=2e-5, rtol=2e-5)

    def test_softmax_stability_large_logits(self, rng):
        # online softmax must not overflow with large score magnitudes
        q, k, v = _qkv(rng, T=32, D=8)
        q = q * 30.0
        ref = flash_attention(q, k, v, backend="xla")
        got = flash_attention(q, k, v, block_q=16, block_k=16,
                              backend="pallas_interpret")
        assert np.isfinite(np.asarray(got)).all()
        np.testing.assert_allclose(got, ref, atol=1e-4, rtol=1e-4)


class TestFusedOpAndGrad:
    def test_op_lowering_and_custom_vjp(self, rng):
        from op_test import run_op
        q, k, v = _qkv(rng, T=32)
        out = run_op("fused_attention", {"Q": q, "K": k, "V": v},
                     attrs={"causal": True})["Out"][0]
        ref = _attention_reference(jnp.asarray(q), jnp.asarray(k),
                                   jnp.asarray(v), 1.0 / 4.0, True)
        np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)

    def test_gradients_match_composite(self, rng):
        from paddle_tpu.ops.pallas_kernels import _fused_attention
        q, k, v = _qkv(rng, B=1, H=1, T=16, D=8)
        scale = 1.0 / np.sqrt(8)

        def via_fused(q_, k_, v_):
            return jnp.sum(_fused_attention(q_, k_, v_, None, scale, True, "xla"))

        def via_ref(q_, k_, v_):
            return jnp.sum(_attention_reference(q_, k_, v_, scale, True))

        g1 = jax.grad(via_fused, argnums=(0, 1, 2))(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
        g2 = jax.grad(via_ref, argnums=(0, 1, 2))(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-5)

    def test_transformer_uses_fused_op_without_dropout(self, rng):
        import paddle_tpu as pt
        from paddle_tpu.models import transformer

        loss, logits = transformer.transformer_lm(
            vocab=50, max_len=16, d_model=32, num_heads=2, num_layers=1,
            d_inner=64, dropout=0.0)
        types = [op.type
                 for op in pt.default_main_program().global_block().ops]
        assert "fused_attention" in types

        pt.optimizer.AdamOptimizer(learning_rate=1e-3).minimize(loss)
        exe = pt.Executor()
        exe.run(pt.default_startup_program())
        toks = rng.randint(0, 50, (4, 16)).astype("int64")
        lab = rng.randint(0, 50, (4, 16)).astype("int64")
        sl = np.full((4,), 16, dtype="int32")
        feed = {"tokens": toks, "tokens@SEQLEN": sl, "targets": lab}
        l0 = exe.run(feed=feed, fetch_list=[loss])[0]
        for _ in range(5):
            l1 = exe.run(feed=feed, fetch_list=[loss])[0]
        assert np.isfinite(l1).all() and l1 < l0  # trains through the vjp


class TestFlashKernelEdgeCases:
    def test_causal_cross_attention_bottom_right_aligned(self, rng):
        """Regression: incremental-decode shape (Tq=1, Tk=64) must see all
        keys, matching the composite's bottom-right causal alignment."""
        q = rng.randn(1, 2, 1, 16).astype("float32")
        k = rng.randn(1, 2, 64, 16).astype("float32")
        v = rng.randn(1, 2, 64, 16).astype("float32")
        ref = flash_attention(q, k, v, causal=True, backend="xla")
        got = flash_attention(q, k, v, causal=True, block_q=8, block_k=16,
                              backend="pallas_interpret")
        np.testing.assert_allclose(got, ref, atol=2e-5, rtol=2e-5)

    def test_non_divisible_lengths_padded(self, rng):
        """Regression: T=200 with 128-blocks must pad+mask, not raise."""
        q, k, v = _qkv(rng, T=200, D=16)
        ref = flash_attention(q, k, v, causal=True, backend="xla")
        got = flash_attention(q, k, v, causal=True, block_q=128,
                              block_k=128, backend="pallas_interpret")
        np.testing.assert_allclose(got, ref, atol=2e-5, rtol=2e-5)


class TestFlashBackwardKernels:
    """FlashAttention-2-style backward: dq/dk/dv recomputed tile-wise from
    (q, k, lse) — gradients must match the composite exactly."""

    @pytest.mark.parametrize("shape,causal", [
        ((1, 2, 64, 64, 16), False),
        ((1, 2, 64, 64, 16), True),
        ((2, 1, 48, 48, 8), True),      # block padding path
        ((1, 1, 16, 64, 8), True),      # cross-attention decode shape
    ])
    def test_grads_match_composite(self, rng, shape, causal):
        from paddle_tpu.ops.pallas_kernels import _fused_attention
        B, H, T, Tk, D = shape
        q = (rng.randn(B, H, T, D) * 0.5).astype("float32")
        k = (rng.randn(B, H, Tk, D) * 0.5).astype("float32")
        v = rng.randn(B, H, Tk, D).astype("float32")
        g = jnp.asarray(rng.randn(B, H, T, D).astype("float32"))
        scale = 1.0 / np.sqrt(D)

        def f(backend):
            def fn(q_, k_, v_):
                return jnp.vdot(
                    _fused_attention(q_, k_, v_, None, scale, causal, backend), g)
            return jax.grad(fn, argnums=(0, 1, 2))(
                jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))

        for a, b in zip(f("xla"), f("pallas_interpret")):
            np.testing.assert_allclose(a, b, atol=3e-5, rtol=3e-5)

    def test_forward_lse_residual(self, rng):
        from paddle_tpu.ops.pallas_kernels import _flash_attention_pallas
        q, k, v = _qkv(rng, T=32, D=8)
        out, lse = _flash_attention_pallas(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
            1.0 / np.sqrt(8), False, 16, 16, interpret=True, with_lse=True)
        # lse must equal logsumexp of the raw scores
        s = np.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(8)
        ref = np.log(np.sum(np.exp(s - s.max(-1, keepdims=True)), -1)) + \
            s.max(-1)
        np.testing.assert_allclose(lse, ref, atol=1e-5, rtol=1e-5)

    def test_no_visible_keys_rows_zero_on_all_backends(self, rng):
        """Regression: causal T > Tk leaves head query rows with no visible
        keys; both backends must output zeros there and agree on grads
        (the composite previously produced softmax's uniform-weight
        artifact)."""
        from paddle_tpu.ops.pallas_kernels import _fused_attention
        B, H, T, Tk, D = 1, 1, 8, 4, 4
        q = (rng.randn(B, H, T, D) * 0.5).astype("float32")
        k = (rng.randn(B, H, Tk, D) * 0.5).astype("float32")
        v = rng.randn(B, H, Tk, D).astype("float32")
        scale = 1.0 / np.sqrt(D)
        outs, grads = {}, {}
        for backend in ("xla", "pallas_interpret"):
            outs[backend] = _fused_attention(
                jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), None, scale,
                True, backend)
            grads[backend] = jax.grad(
                lambda q_, k_, v_: jnp.sum(_fused_attention(
                    q_, k_, v_, None, scale, True, backend) ** 2),
                argnums=(0, 1, 2))(jnp.asarray(q), jnp.asarray(k),
                                   jnp.asarray(v))
        # rows 0..T-Tk-1 see no keys: zero output
        np.testing.assert_array_equal(np.asarray(outs["xla"])[:, :, :T - Tk],
                                      0.0)
        np.testing.assert_allclose(outs["xla"], outs["pallas_interpret"],
                                   atol=2e-5)
        for a, b in zip(grads["xla"], grads["pallas_interpret"]):
            np.testing.assert_allclose(a, b, atol=3e-5, rtol=3e-5)


class TestSegmentIds:
    """Packed-batch (segment-id) masking in the flash kernel — the
    static-shape LoD translation (SURVEY §5). Semantics must match
    parallel.ring_attention: attend iff ids equal; composes with causal."""

    @staticmethod
    def _ragged_pack(rng, B, T, n_seqs=3):
        """Segment ids like [0,0,0,1,1,2,2,2,...] per row — a ragged pack
        of n_seqs sequences of uneven lengths."""
        ids = np.zeros((B, T), np.int32)
        for b in range(B):
            cuts = np.sort(rng.choice(np.arange(1, T), n_seqs - 1,
                                      replace=False))
            ids[b] = np.searchsorted(cuts, np.arange(T), side="right")
        return ids

    @pytest.mark.parametrize("causal", [False, True])
    def test_values_match_composite(self, rng, causal):
        q, k, v = _qkv(rng, B=2, H=2, T=64, D=16)
        seg = self._ragged_pack(rng, 2, 64)
        ref = flash_attention(q, k, v, causal=causal, backend="xla",
                              segment_ids=seg)
        got = flash_attention(q, k, v, causal=causal, block_q=32,
                              block_k=32, backend="pallas_interpret",
                              segment_ids=seg)
        np.testing.assert_allclose(got, ref, atol=2e-5, rtol=2e-5)

    def test_segment_isolation_vs_separate_calls(self, rng):
        """Ground truth, not just backend parity: a packed row [seq A | seq
        B] must equal attending A and B separately."""
        D = 8
        qa, ka, va = _qkv(rng, B=1, H=1, T=24, D=D)
        qb, kb, vb = _qkv(rng, B=1, H=1, T=40, D=D)
        q = np.concatenate([qa, qb], axis=2)
        k = np.concatenate([ka, kb], axis=2)
        v = np.concatenate([va, vb], axis=2)
        seg = np.concatenate([np.zeros((1, 24), np.int32),
                              np.ones((1, 40), np.int32)], axis=1)
        scale = 1.0 / np.sqrt(D)
        packed = flash_attention(q, k, v, scale=scale, causal=True,
                                 block_q=16, block_k=16,
                                 backend="pallas_interpret",
                                 segment_ids=seg)
        outa = flash_attention(qa, ka, va, scale=scale, causal=True,
                               backend="xla")
        outb = flash_attention(qb, kb, vb, scale=scale, causal=True,
                               backend="xla")
        np.testing.assert_allclose(packed[:, :, :24], outa, atol=2e-5,
                                   rtol=2e-5)
        np.testing.assert_allclose(packed[:, :, 24:], outb, atol=2e-5,
                                   rtol=2e-5)

    @pytest.mark.parametrize("causal", [False, True])
    def test_grads_match_composite_ragged(self, rng, causal):
        from paddle_tpu.ops.pallas_kernels import _fused_attention
        B, H, T, D = 2, 2, 48, 8
        q = (rng.randn(B, H, T, D) * 0.5).astype("float32")
        k = (rng.randn(B, H, T, D) * 0.5).astype("float32")
        v = rng.randn(B, H, T, D).astype("float32")
        g = jnp.asarray(rng.randn(B, H, T, D).astype("float32"))
        seg = jnp.asarray(self._ragged_pack(rng, B, T))
        scale = 1.0 / np.sqrt(D)

        def f(backend):
            def fn(q_, k_, v_):
                return jnp.vdot(_fused_attention(
                    q_, k_, v_, seg, scale, causal, backend, 16, 16), g)
            return jax.grad(fn, argnums=(0, 1, 2))(
                jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))

        for a, b in zip(f("xla"), f("pallas_interpret")):
            np.testing.assert_allclose(a, b, atol=3e-5, rtol=3e-5)

    def test_matches_ring_attention_semantics(self, rng):
        """The kernel and parallel.ring_attention implement the same
        packed-batch contract: compare on an unsharded single 'ring'."""
        from paddle_tpu.parallel.ring_attention import _block_attn
        B, H, T, D = 1, 2, 32, 8
        q, k, v = _qkv(rng, B=B, H=H, T=T, D=D)
        seg = self._ragged_pack(rng, B, T)
        scale = 1.0 / np.sqrt(D)
        out = flash_attention(q, k, v, scale=scale, backend="xla",
                              segment_ids=seg)
        # ring-style reference: one block, segment bias applied
        same = seg[:, :, None] == seg[:, None, :]
        bias = np.where(same[:, None], 0.0, -1e30).astype("float32")
        import jax.numpy as jnp_
        m0 = jnp_.full((B, H, T), -1e30)
        l0 = jnp_.zeros((B, H, T))
        o0 = jnp_.zeros((B, T, H, D))
        qt = jnp_.asarray(q.transpose(0, 2, 1, 3))
        kt = jnp_.asarray(k.transpose(0, 2, 1, 3))
        vt = jnp_.asarray(v.transpose(0, 2, 1, 3))
        m, l, o = _block_attn(qt, kt, vt, jnp_.asarray(bias), m0, l0, o0,
                              scale)
        ring_out = (o / jnp_.maximum(l, 1e-20).transpose(0, 2, 1)[..., None]
                    ).transpose(0, 2, 1, 3)
        np.testing.assert_allclose(out, ring_out, atol=2e-5, rtol=2e-5)

    def test_cross_attention_segment_pair(self, rng):
        """(q_ids, kv_ids) pair with Tq != Tk."""
        D = 8
        q = (rng.randn(1, 1, 16, D) * 0.5).astype("float32")
        k = (rng.randn(1, 1, 32, D) * 0.5).astype("float32")
        v = rng.randn(1, 1, 32, D).astype("float32")
        q_ids = np.repeat(np.array([[0, 1]], np.int32), 8, axis=1)
        kv_ids = np.repeat(np.array([[0, 1]], np.int32), 16, axis=1)
        ref = flash_attention(q, k, v, backend="xla",
                              segment_ids=(q_ids, kv_ids))
        got = flash_attention(q, k, v, block_q=8, block_k=16,
                              backend="pallas_interpret",
                              segment_ids=(q_ids, kv_ids))
        np.testing.assert_allclose(got, ref, atol=2e-5, rtol=2e-5)

    def test_default_blocks_with_midrange_lengths(self, rng):
        """Regression: the 512/1024 default blocks must clamp to
        128-multiples for sequence lengths like 200/300 (a raw min() gave
        Mosaic-illegal ragged block shapes and broke the segment-id
        tiling precondition)."""
        from paddle_tpu.ops.pallas_kernels import _clamp_block
        assert _clamp_block(512, 300) == 384      # 128-multiple, >= T
        assert _clamp_block(1024, 200) == 256
        assert _clamp_block(512, 8192) == 512     # big T: full block
        assert _clamp_block(32, 64) == 32         # explicit small blocks
        q, k, v = _qkv(rng, B=1, H=2, T=300, D=16)
        seg = self._ragged_pack(rng, 1, 300)
        ref = flash_attention(q, k, v, causal=True, backend="xla",
                              segment_ids=seg)
        # default (unspecified) blocks through the interpret kernel
        got = flash_attention(q, k, v, causal=True,
                              backend="pallas_interpret", segment_ids=seg)
        np.testing.assert_allclose(got, ref, atol=2e-5, rtol=2e-5)

    def test_layer_routes_segment_ids(self, rng):
        """layers.fused_attention(segment_ids=...) lowers and runs."""
        import paddle_tpu as pt
        from paddle_tpu import layers
        q = layers.data(name="q", shape=[2, 32, 8])
        seg = layers.data(name="seg", shape=[32], dtype="int32")
        out = layers.fused_attention(q, q, q, causal=True, segment_ids=seg)
        exe = pt.Executor()
        qv = (rng.randn(1, 2, 32, 8) * 0.5).astype("float32")
        segv = self._ragged_pack(rng, 1, 32)
        got = exe.run(feed={"q": qv, "seg": segv}, fetch_list=[out])[0]
        ref = flash_attention(qv, qv, qv, causal=True, backend="xla",
                              segment_ids=segv)
        np.testing.assert_allclose(got, np.asarray(ref), atol=1e-5)


# ---------------------------------------------------------------------------
# the plan: every tiling `_flash_plan` can return, held to the composite
# ---------------------------------------------------------------------------

def _case(name, B=2, H=2, T=64, Tk=None, D=16, causal=False, seg=None,
          bq=None, bk=None, budget=None, tile_scores=None, resident=True,
          rows=None):
    return pytest.param(dict(B=B, H=H, T=T, Tk=Tk or T, D=D, causal=causal,
                             seg=seg, bq=bq, bk=bk, budget=budget,
                             tile_scores=tile_scores, resident=resident,
                             rows=rows), id=name)


# `budget=0` leaves no head resident: the streamed plan at a size the
# interpreter can run. `rows`: what the plan has to choose (None: not pinned).
_PLAN_CASES = [
    # resident: the key loop inside the kernel
    _case("resident-rows4-causal-blocks", causal=True, bq=16, bk=16, rows=4),
    _case("resident-rows4-full-blocks", bq=32, bk=16, rows=4),
    _case("resident-rows1", B=1, H=1, causal=True, bq=16, bk=32, rows=1),
    _case("resident-rows3-of-6", B=1, H=6, causal=True, bq=16, bk=16,
          tile_scores=4 * 16 * 16, rows=3),
    _case("resident-single-tile-rows8", B=2, H=4, causal=True, rows=8),
    _case("resident-single-tile-full", B=1, H=3, T=48, rows=3),
    _case("resident-default-T300", B=1, H=2, T=300, causal=True, rows=2),
    _case("resident-default-T200-Tk456", B=1, H=2, T=200, Tk=456, rows=2),
    _case("resident-decode-Tq1", T=1, Tk=64, causal=True, bq=8, bk=16),
    _case("resident-prefill-Tq16-Tk64", T=16, Tk=64, causal=True, bq=8,
          bk=16),
    _case("resident-no-visible-key-rows", T=64, Tk=16, causal=True, bq=16,
          bk=16),
    _case("resident-no-visible-key-single", T=8, Tk=4, D=4, causal=True),
    _case("resident-uneven-T48", T=48, causal=True, bq=32, bk=32),
    _case("resident-segments", seg="self", bq=32, bk=32, rows=2),
    _case("resident-segments-causal", seg="self", causal=True, bq=16, bk=16,
          rows=2),
    _case("resident-segments-pair", T=16, Tk=32, seg="pair", bq=8, bk=16),
    _case("resident-segments-no-matching-key", T=16, Tk=32, seg="orphan",
          bq=8, bk=16),
    _case("resident-segments-default-T300", B=1, T=300, seg="self",
          causal=True, rows=2),
    # streamed: key blocks through the grid, the backward in two passes
    _case("streamed-causal", causal=True, bq=16, bk=16, budget=0,
          resident=False, rows=1),
    _case("streamed-full", bq=32, bk=16, budget=0, resident=False),
    _case("streamed-Tq16-Tk64", T=16, Tk=64, causal=True, bq=8, bk=16,
          budget=0, resident=False),
    _case("streamed-no-visible-key-rows", T=64, Tk=16, causal=True, bq=16,
          bk=16, budget=0, resident=False),
    _case("streamed-uneven-T48", T=48, causal=True, bq=32, bk=32, budget=0,
          resident=False),
    _case("streamed-segments-causal", seg="self", causal=True, bq=16, bk=16,
          budget=0, resident=False),
    _case("streamed-segments-pair", T=16, Tk=32, seg="pair", bq=8, bk=16,
          budget=0, resident=False),
    _case("streamed-default-T300", B=1, H=2, T=300, causal=True, budget=0,
          resident=False),
]


@pytest.fixture
def traced_bodies():
    """The names of the flash kernels whose bodies are traced from here on.
    The two jitted callables first forget what earlier tests traced, so a
    case whose shapes an earlier case ran traces its own plan's kernels."""
    from paddle_tpu.observability import tracing
    from paddle_tpu.ops import pallas_kernels as pk
    pk._flash_fwd.clear_cache()
    pk._flash_bwd.clear_cache()
    mark = tracing.mark()
    return lambda: {s.attrs["scope"] for s in tracing.spans_since(mark)
                    if s.name == "flash/body_traced"}


class TestEveryPlan:
    """Forward, lse, dq, dk and dv of every plan the function can return
    (resident and streamed, one head a step and several, a head count the
    step's heads do not divide, the single-tile path) against
    `_attention_reference`, and the kernels that ran are the plan's."""

    @staticmethod
    def _segments(rng, kind, B, T, Tk):
        if kind is None:
            return None
        if kind == "self":
            ids = TestSegmentIds._ragged_pack(rng, B, T)
            return ids, ids
        q_ids = np.repeat(np.arange(2, dtype=np.int32)[None], T // 2, axis=1)
        kv_ids = np.repeat(np.arange(2, dtype=np.int32)[None], Tk // 2,
                           axis=1)
        q_ids = np.repeat(q_ids, B, axis=0)
        kv_ids = np.repeat(kv_ids, B, axis=0)
        if kind == "orphan":        # the last queries match no key at all
            q_ids[:, -3:] = 7
        return q_ids, kv_ids

    @pytest.mark.parametrize("c", _PLAN_CASES)
    def test_matches_reference(self, rng, monkeypatch, traced_bodies, c):
        from paddle_tpu.ops import pallas_kernels as pk
        if c["budget"] is not None:
            monkeypatch.setattr(pk, "_VMEM_BUDGET", c["budget"])
        if c["tile_scores"] is not None:
            monkeypatch.setattr(pk, "_TILE_SCORES", c["tile_scores"])
        B, H, T, Tk, D = (c[n] for n in ("B", "H", "T", "Tk", "D"))
        seg = self._segments(rng, c["seg"], B, T, Tk)
        plan = pk._flash_plan(T, Tk, D, 4, H if seg else B * H, c["bq"],
                              c["bk"])
        assert plan.resident == c["resident"], plan
        if c["rows"] is not None:
            assert plan.rows == c["rows"], plan
        assert (H if seg else B * H) % plan.rows == 0

        q = jnp.asarray((rng.randn(B, H, T, D) * 0.5).astype("float32"))
        k = jnp.asarray((rng.randn(B, H, Tk, D) * 0.5).astype("float32"))
        v = jnp.asarray(rng.randn(B, H, Tk, D).astype("float32"))
        g = jnp.asarray(rng.randn(B, H, T, D).astype("float32"))
        scale = 1.0 / np.sqrt(D)
        causal = c["causal"]

        def grads(backend):
            def fn(q_, k_, v_):
                return jnp.vdot(pk._fused_attention(
                    q_, k_, v_, seg, scale, causal, backend, c["bq"],
                    c["bk"]), g)
            return jax.grad(fn, argnums=(0, 1, 2))(q, k, v)

        out, lse = pk._flash_attention_pallas(
            q, k, v, scale, causal, c["bq"], c["bk"], interpret=True,
            with_lse=True, segment_ids=seg)
        ref = pk._attention_reference(q, k, v, scale, causal, seg)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

        # lse against logsumexp of the live scores, where a row has any
        s = np.einsum("bhqd,bhkd->bhqk", q, k) * scale
        live = np.ones((B, 1, T, Tk), bool)
        if causal:
            live &= np.tril(np.ones((T, Tk), bool), Tk - T)[None, None]
        if seg:
            live &= (seg[0][:, :, None] == seg[1][:, None, :])[:, None]
        s = np.where(live, s, -np.inf)
        any_key = np.broadcast_to(live.any(-1), (B, H, T))
        with np.errstate(invalid="ignore", divide="ignore"):
            ref_lse = np.log(np.sum(np.exp(s - s.max(-1, keepdims=True)),
                                    -1)) + s.max(-1)
        np.testing.assert_allclose(np.asarray(lse)[any_key],
                                   ref_lse[any_key], atol=2e-5, rtol=2e-5)
        # a row with no visible key: zero output, and a residual the ring
        # merge weighs as nothing
        assert (np.asarray(out)[~any_key] == 0).all()
        assert (np.asarray(lse)[~any_key] < -1e29).all()

        for a, b in zip(grads("xla"), grads("pallas_interpret")):
            np.testing.assert_allclose(a, b, atol=3e-5, rtol=3e-5)
        # what ran is this plan's forward and backward: a streamed case is
        # not handed the jaxpr of its resident twin of the same shapes
        assert traced_bodies() == set(plan.scopes())
        assert all(("streamed" in s) != c["resident"] for s in plan.scopes())


def _tm_case(name, B=1, H=4, T=256, Tk=None, D=64, causal=False, seg=None,
             budget=None, rows=None, token_major=True):
    return pytest.param(dict(B=B, H=H, T=T, Tk=Tk or T, D=D, causal=causal,
                             seg=seg, budget=budget, rows=rows,
                             token_major=token_major), id=name)


# `rows`: the heads a step must take; `token_major=False`: a shape the rule
# sends back to the head-major kernels between two transposes
_TOKEN_MAJOR_CASES = [
    _tm_case("lm-rows4-causal-four-tiles", T=512, causal=True, rows=4),
    _tm_case("lm-rows4-full-four-tiles", T=512, rows=4),
    _tm_case("nmt-rows16-single-tile", H=16, T=128, rows=16),
    _tm_case("nmt-rows16-single-tile-causal", B=2, H=16, T=128, causal=True,
             rows=16),
    _tm_case("cross-Tk-longer", H=4, T=128, Tk=384, rows=4),
    _tm_case("cross-Tk-shorter-causal", H=2, T=256, Tk=128, causal=True),
    _tm_case("padded-T200-Tk300", B=2, T=200, Tk=300, rows=4),
    _tm_case("padded-T200-causal", T=200, causal=True, rows=4),
    _tm_case("D128-rows2", H=2, T=256, D=128, causal=True, rows=2),
    _tm_case("D128-one-head", H=1, T=128, D=128, rows=1),
    _tm_case("D32-rows4", H=4, T=128, D=32, rows=4),
    _tm_case("rows-rounded-to-a-pair-of-6", H=6, T=512, causal=True, rows=2),
    _tm_case("segments", B=2, T=256, seg="self", rows=4),
    _tm_case("segments-causal-four-tiles", T=512, seg="self", causal=True,
             rows=4),
    _tm_case("segments-pair-cross", B=2, T=128, Tk=256, seg="pair", rows=4),
    # the shapes the rule leaves out
    _tm_case("odd-lanes-3-heads-of-64", H=3, T=128, causal=True,
             token_major=False),
    _tm_case("odd-lanes-one-head-of-64", H=1, T=128, token_major=False),
    _tm_case("odd-lanes-D16", H=4, T=128, D=16, token_major=False),
    _tm_case("streamed", H=2, T=256, causal=True, budget=0,
             token_major=False),
]


class TestTokenMajor:
    """`fused_attention` on q, k, v as the projections leave them, [B, T,
    H*D]: the context and all three gradients against the head-major
    kernels on the same numbers, both through the interpreter. The two forms
    do the same arithmetic in the same order, so they agree to rounding of
    the last place, whichever form the rule picks."""

    @pytest.mark.parametrize("c", _TOKEN_MAJOR_CASES)
    def test_matches_head_major(self, rng, monkeypatch, traced_bodies, c):
        from paddle_tpu.ops import pallas_kernels as pk
        if c["budget"] is not None:
            monkeypatch.setattr(pk, "_VMEM_BUDGET", c["budget"])
        B, H, T, Tk, D = (c[n] for n in ("B", "H", "T", "Tk", "D"))
        seg = TestEveryPlan._segments(rng, c["seg"], B, T, Tk)
        q = jnp.asarray((rng.randn(B, T, H * D) * 0.5).astype("float32"))
        k = jnp.asarray((rng.randn(B, Tk, H * D) * 0.5).astype("float32"))
        v = jnp.asarray(rng.randn(B, Tk, H * D).astype("float32"))
        g = jnp.asarray(rng.randn(B, T, H * D).astype("float32"))
        scale, causal = 1.0 / np.sqrt(D), c["causal"]
        plan = pk._plan_for(q, k, seg is not None, num_heads=H)
        assert plan.token_major == c["token_major"], plan
        if c["rows"] is not None:
            assert plan.rows == c["rows"], plan
        if plan.token_major:
            assert H % plan.rows == 0 and plan.rows * D % 128 == 0
            assert plan.scope("fwd").endswith(f"rows{plan.rows}_tm")

        def heads(x):
            return jnp.swapaxes(x.reshape(B, -1, H, D), 1, 2)

        def token_major(q_, k_, v_):
            return pk._attend(q_, k_, v_, seg, scale, causal,
                              "pallas_interpret", H)

        def head_major(q_, k_, v_):
            out = pk._fused_attention(heads(q_), heads(k_), heads(v_), seg,
                                      scale, causal, "pallas_interpret")
            return jnp.swapaxes(out, 1, 2).reshape(B, T, H * D)

        def both(fn):
            out, vjp = jax.vjp(fn, q, k, v)
            return (out,) + vjp(g)

        for got, want in zip(both(token_major), both(head_major)):
            np.testing.assert_allclose(got, want, atol=2e-6, rtol=2e-6)
        # both forms ran: the rule's own kernels and the rank-4 caller's
        # (one and the same where the rule sends the shape back)
        head_major_plan = pk._plan_for(heads(q), heads(k), seg is not None)
        assert traced_bodies() == {*plan.scopes(), *head_major_plan.scopes()}
        assert head_major_plan.resident == (c["budget"] is None)
        # and the composite, so that the pair cannot be wrong together
        ref = pk._attend(q, k, v, seg, scale, causal, "xla", H)
        np.testing.assert_allclose(token_major(q, k, v), ref, atol=2e-5,
                                   rtol=2e-5)


class TestFlashPlan:
    """The plan is a pure function of the shape."""

    def test_the_benchmark_cells(self):
        from paddle_tpu.ops.pallas_kernels import FlashPlan, _flash_plan
        # lm-big_train_1chip, and lm-big_train_dp4's shard: [8 * 16, 1024, 64]
        lm = _flash_plan(1024, 1024, 64, 2, 8 * 16)
        assert lm == FlashPlan(True, 256, 256, 4)
        assert lm.scope("fwd") == "flash_fwd_resident_q256_k256_rows4"
        assert lm.scope("bwd") == "flash_bwd_resident_q256_k256_rows4"
        # nmt-big_train_1chip: [64 * 16, 128, 64], three attentions a layer
        nmt = _flash_plan(128, 128, 64, 2, 64 * 16)
        assert nmt == FlashPlan(True, 128, 128, 16)
        assert nmt.scope("bwd") == "flash_bwd_resident_q128_k128_rows16"

    @pytest.mark.parametrize("T, D, H, plan", [
        # the plans docs/fusion.md lists, asked for token-major [B, T, H*D]
        (1024, 64, 16, "flash_fwd_resident_q256_k256_rows4_tm"),
        (128, 64, 16, "flash_fwd_resident_q128_k128_rows16_tm"),
        (1024, 128, 16, "flash_fwd_resident_q256_k256_rows2_tm"),
        (2048, 64, 16, "flash_fwd_resident_q256_k256_rows2_tm"),
        (1024, 64, 6, "flash_fwd_resident_q256_k256_rows2_tm"),
        # no number of heads that fits fills whole 128-lane tiles
        (4096, 64, 16, "flash_fwd_resident_q256_k256_rows1"),
        (1024, 64, 7, "flash_fwd_resident_q256_k256_rows1"),
        (128, 64, 3, "flash_fwd_resident_q128_k128_rows3"),
        (128, 16, 4, "flash_fwd_resident_q128_k128_rows4"),
        # a head over the budget streams, head-major
        (8192, 128, 8, "flash_fwd_streamed_q1024_k1024"),
        (32768, 64, 16, "flash_fwd_streamed_q1024_k1024"),
    ])
    def test_token_major_where_heads_fill_whole_lane_tiles(self, T, D, H,
                                                           plan):
        from paddle_tpu.ops.pallas_kernels import _flash_plan
        got = _flash_plan(T, T, D, 2, H, num_heads=H)
        assert got.scope("fwd") == plan
        assert got.token_major == plan.endswith("_tm")
        if got.token_major:
            assert H % got.rows == 0 and got.rows * D % 128 == 0
        # a rank-4 caller's plan does not know the field
        assert not _flash_plan(T, T, D, 2, H).token_major

    @pytest.mark.parametrize("T, D", [(8192, 128), (32768, 64),
                                      (32768, 128), (4096, 128)])
    def test_a_head_over_the_vmem_budget_streams(self, T, D):
        from paddle_tpu.ops.pallas_kernels import FlashPlan, _flash_plan
        plan = _flash_plan(T, T, D, 2, 8)
        assert plan == FlashPlan(False, 1024, 1024, 1)
        assert plan.scope("bwd_dq") == "flash_bwd_dq_streamed_q1024_k1024"
        assert plan.scope("bwd_dkv") == "flash_bwd_dkv_streamed_q1024_k1024"

    @pytest.mark.parametrize("T, D, itemsize, heads, rows", [
        (4096, 64, 2, 16, 1),      # fits, alone
        (2048, 64, 2, 16, 2),      # the VMEM budget bounds the heads a step
        (1024, 64, 4, 128, 2),     # float32 operands take twice the room
        (1024, 128, 2, 64, 2),
        (512, 64, 2, 64, 4),
        (128, 64, 2, 6, 6),        # fewer heads than a step would take
        (128, 64, 2, 1000, 10),    # rows divides the heads it is given
        (128, 64, 2, 7, 7),
        (1024, 64, 2, 7, 1),
    ])
    def test_resident_rows(self, T, D, itemsize, heads, rows):
        from paddle_tpu.ops import pallas_kernels as pk
        plan = pk._flash_plan(T, T, D, itemsize, heads)
        assert plan.resident and plan.rows == rows, plan
        assert heads % plan.rows == 0
        assert plan.rows * plan.block_q * plan.block_k <= pk._TILE_SCORES

    @pytest.mark.parametrize("T, block", [(1024, 256), (128, 128), (64, 128),
                                          (1, 128), (200, 256), (300, 128),
                                          (384, 128), (640, 128), (768, 256),
                                          (1536, 256)])
    def test_blocks_pad_to_the_lane_width_only(self, T, block):
        from paddle_tpu.ops.pallas_kernels import _flash_plan
        plan = _flash_plan(T, T, 64, 2, 16)
        assert plan.block_q == plan.block_k == block
        assert -(-T // block) * block == -(-T // 128) * 128

    def test_explicit_blocks_are_honoured(self):
        from paddle_tpu.ops.pallas_kernels import _flash_plan
        plan = _flash_plan(1024, 2048, 64, 2, 16, block_q=512, block_k=1024)
        assert (plan.block_q, plan.block_k) == (512, 1024)
        assert plan.resident and plan.rows == 1
        plan = _flash_plan(64, 64, 16, 4, 4, block_q=32, block_k=16)
        assert (plan.block_q, plan.block_k, plan.rows) == (32, 16, 4)
        # clamped to the padded length, as before
        assert _flash_plan(200, 300, 64, 2, 4, 512, 1024).block_q == 256
        assert _flash_plan(200, 300, 64, 2, 4, 512, 1024).block_k == 384
