"""Fusion subsystem evidence suite (paddle_tpu/fusion/ + the fuse passes).

Three committed claims, mirroring tests/test_pass_verification.py's
discipline (every rewrite numerically verified on REAL model programs, not
toy blocks):

  (a) kernel parity: the fused LSTM/GRU whole-sequence cells and the fused
      decode-attention step match the unfused math — forward AND gradient —
      with the Pallas kernels additionally pinned through the interpreter
      (the same tiling logic the TPU runs);
  (b) pass correctness: `fuse_recurrent_cell_pass` /
      `fuse_decode_attention_pass` rewrite real programs (stacked-LSTM
      train graph, the KV-cached LM decode graph) into the fused ops and
      leave them numerically equivalent end to end, parameters-after-update
      included;
  (c) pass safety: non-default activations, multi-consumer intermediates
      and multi-position queries are NOT rewritten.
"""

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers, models
from paddle_tpu.core import flags


@pytest.fixture(autouse=True)
def _fusion_flags_restored():
    """Tests flip the fuse_* flags; leave the session defaults intact."""
    rnn = flags.get_flag("fuse_recurrent_cells")
    dec = flags.get_flag("fuse_decode_attention")
    yield
    flags.set_flag("fuse_recurrent_cells", rnn)
    flags.set_flag("fuse_decode_attention", dec)


# ---------------------------------------------------------------------------
# (a) kernel-level parity
# ---------------------------------------------------------------------------


class TestFusedRecurrentKernels:
    def _lstm_args(self, rng, b=4, t=6, h=128):
        import jax.numpy as jnp
        return (jnp.asarray(rng.randn(b, t, 4 * h).astype("float32") * .3),
                jnp.asarray(rng.randn(b, h).astype("float32") * .1),
                jnp.asarray(rng.randn(b, h).astype("float32") * .1),
                jnp.asarray(rng.randn(h, 4 * h).astype("float32") * .1),
                jnp.asarray(np.array([t, t - 2, 1, t], "int32")))

    @pytest.mark.parametrize("reverse", [False, True])
    def test_lstm_interpret_matches_xla(self, rng, reverse):
        from paddle_tpu.fusion import fused_lstm_sequence
        x, h0, c0, w, sl = self._lstm_args(rng)
        hx, cx = fused_lstm_sequence(x, h0, c0, w, sl, reverse=reverse,
                                     backend="xla")
        hp, cp = fused_lstm_sequence(x, h0, c0, w, sl, reverse=reverse,
                                     backend="pallas_interpret")
        np.testing.assert_allclose(hx, hp, atol=2e-6, rtol=2e-6)
        np.testing.assert_allclose(cx, cp, atol=2e-6, rtol=2e-6)

    def test_lstm_matches_unfused_op_and_grads(self, rng):
        """Fused vs the registered dynamic_lstm lowering, fwd + full vjp
        (the fused backward is a manual custom_vjp — pin it against what
        jax.vjp derives from the unfused scan)."""
        import jax
        import jax.numpy as jnp
        from paddle_tpu.fusion import fused_lstm_sequence
        from paddle_tpu.ops.sequence_ops import _lstm_scan
        x, h0, c0, w, sl = self._lstm_args(rng)

        def ref(args):
            hs, cs = _lstm_scan(args[0], args[1], args[2], args[3], sl,
                                jax.nn.sigmoid, jnp.tanh, jnp.tanh)
            return hs, cs

        def fused(args):
            return fused_lstm_sequence(args[0], args[1], args[2], args[3],
                                       sl, backend="xla")

        rf, ff = ref((x, h0, c0, w)), fused((x, h0, c0, w))
        np.testing.assert_allclose(rf[0], ff[0], atol=1e-6, rtol=1e-6)
        np.testing.assert_allclose(rf[1], ff[1], atol=1e-6, rtol=1e-6)

        def loss(f):
            def inner(args):
                hs, cs = f(args)
                wgt = jnp.cos(jnp.arange(hs.size)).reshape(hs.shape)
                return jnp.sum(hs * wgt) + jnp.sum(cs ** 2)
            return inner

        gr = jax.grad(loss(ref))((x, h0, c0, w))
        gf = jax.grad(loss(fused))((x, h0, c0, w))
        for a, b, name in zip(gf, gr, ["x", "h0", "c0", "w"]):
            np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4,
                                       err_msg=f"d{name}")

    @pytest.mark.parametrize("reverse", [False, True])
    def test_gru_interpret_matches_xla_and_grads(self, rng, reverse):
        import jax
        import jax.numpy as jnp
        from paddle_tpu.fusion import fused_gru_sequence
        from paddle_tpu.ops.sequence_ops import _dynamic_gru
        b, t, h = 4, 5, 128
        x = jnp.asarray(rng.randn(b, t, 3 * h).astype("float32") * .3)
        h0 = jnp.asarray(rng.randn(b, h).astype("float32") * .1)
        w = jnp.asarray(rng.randn(h, 3 * h).astype("float32") * .1)
        sl = jnp.asarray(np.array([t, 2, t, 1], "int32"))
        ax = fused_gru_sequence(x, h0, w, sl, reverse=reverse,
                                backend="xla")
        ap = fused_gru_sequence(x, h0, w, sl, reverse=reverse,
                                backend="pallas_interpret")
        np.testing.assert_allclose(ax, ap, atol=2e-6, rtol=2e-6)
        # fwd + grad vs the registered unfused lowering
        ins = {"Input": [x], "Weight": [w], "SeqLen": [sl], "H0": [h0]}
        ref = _dynamic_gru(None, ins, {"is_reverse": reverse})["Hidden"][0]
        np.testing.assert_allclose(ax, ref, atol=1e-6, rtol=1e-6)

        def loss_f(args):
            return jnp.sum(fused_gru_sequence(
                args[0], args[1], args[2], sl, reverse=reverse,
                backend="xla") ** 2)

        def loss_r(args):
            out = _dynamic_gru(None, {"Input": [args[0]], "Weight": [args[2]],
                                      "SeqLen": [sl], "H0": [args[1]]},
                               {"is_reverse": reverse})["Hidden"][0]
            return jnp.sum(out ** 2)

        gf = jax.grad(loss_f)((x, h0, w))
        gr = jax.grad(loss_r)((x, h0, w))
        for a, b_, name in zip(gf, gr, ["x", "h0", "w"]):
            np.testing.assert_allclose(a, b_, atol=1e-4, rtol=1e-4,
                                       err_msg=f"d{name}")

    def test_misaligned_hidden_falls_back_to_xla(self, rng):
        """H not a lane multiple: the Pallas path must silently take the
        composite (identical results, no crash)."""
        import jax.numpy as jnp
        from paddle_tpu.fusion import fused_lstm_sequence
        b, t, h = 2, 3, 24
        x = jnp.asarray(rng.randn(b, t, 4 * h).astype("float32"))
        h0 = jnp.zeros((b, h), jnp.float32)
        c0 = jnp.zeros((b, h), jnp.float32)
        w = jnp.asarray(rng.randn(h, 4 * h).astype("float32") * .1)
        sl = jnp.full((b,), t, jnp.int32)
        a = fused_lstm_sequence(x, h0, c0, w, sl, backend="pallas_interpret")
        b_ = fused_lstm_sequence(x, h0, c0, w, sl, backend="xla")
        np.testing.assert_allclose(a[0], b_[0], atol=1e-6)


class TestFusedDecodeAttentionKernel:
    def _args(self, rng, b=3, k=4, nh=2, t=10, dh=16):
        import jax.numpy as jnp
        q = jnp.asarray(rng.randn(b, k, nh, 1, dh).astype("float32"))
        kc = jnp.asarray(rng.randn(b, k, nh, t, dh).astype("float32"))
        vc = jnp.asarray(rng.randn(b, k, nh, t, dh).astype("float32"))
        keep = (np.arange(t)[None] < np.array([3, 5, t][:b])[:, None])
        bias = jnp.asarray((keep.astype("float32") * 1e9 - 1e9)
                           .reshape(b, 1, 1, 1, t))
        return q, kc, vc, bias

    def test_matches_unfused_chain_all_backends(self, rng):
        import jax
        import jax.numpy as jnp
        from paddle_tpu.fusion import fused_decode_attention
        q, k, v, bias = self._args(rng)
        scale = q.shape[-1] ** -0.5
        s = jnp.matmul(q, jnp.swapaxes(k, -1, -2),
                       preferred_element_type=jnp.float32) * scale + bias
        ref = jnp.matmul(jax.nn.softmax(s, -1), v,
                         preferred_element_type=jnp.float32)
        for backend in ("xla", "pallas_interpret"):
            out = fused_decode_attention(q, k, v, bias, scale=scale,
                                         backend=backend)
            np.testing.assert_allclose(out, ref, atol=2e-6, rtol=2e-5,
                                       err_msg=backend)

    def test_gradients_match_unfused_chain(self, rng):
        import jax
        import jax.numpy as jnp
        from paddle_tpu.fusion import fused_decode_attention
        q, k, v, bias = self._args(rng)
        scale = q.shape[-1] ** -0.5

        def f_fused(args):
            return jnp.sum(fused_decode_attention(
                *args, scale=scale, backend="xla") ** 2)

        def f_ref(args):
            q_, k_, v_, b_ = args
            s = jnp.matmul(q_, jnp.swapaxes(k_, -1, -2),
                           preferred_element_type=jnp.float32) * scale + b_
            return jnp.sum(jnp.matmul(jax.nn.softmax(s, -1), v_,
                           preferred_element_type=jnp.float32) ** 2)

        gf = jax.grad(f_fused)((q, k, v, bias))
        gr = jax.grad(f_ref)((q, k, v, bias))
        for a, b_, name in zip(gf, gr, ["q", "k", "v", "bias"]):
            np.testing.assert_allclose(a, b_, atol=1e-4, rtol=1e-4,
                                       err_msg=f"d{name}")


# ---------------------------------------------------------------------------
# (b) pass verification on real model programs
# ---------------------------------------------------------------------------


def _lstm_losses_and_params(fuse, rng):
    pt.reset_default_programs()
    pt.reset_global_scope()
    flags.set_flag("fuse_recurrent_cells", fuse)
    from paddle_tpu.core import unique_name
    with unique_name.guard():
        loss, acc, _ = models.stacked_lstm.stacked_lstm_net(
            dict_dim=300, emb_dim=16, hid_dim=16, max_len=10)
        pt.optimizer.Adam(learning_rate=1e-2).minimize(loss)
    pt.default_startup_program().random_seed = 11
    exe = pt.Executor()
    exe.run(pt.default_startup_program())
    r = np.random.RandomState(7)
    feed = {"words": r.randint(0, 300, (4, 10)).astype("int64"),
            "words@SEQLEN": np.array([10, 6, 2, 10], "int32"),
            "label": r.randint(0, 2, (4, 1)).astype("int64")}
    losses = [float(exe.run(feed=feed, fetch_list=[loss])[0])
              for _ in range(3)]
    params = {p.name: np.asarray(pt.global_scope().get(p.name))
              for p in pt.default_main_program().all_parameters()}
    return losses, params


@pytest.mark.quick
class TestPagedDecodeAttentionComposite:
    """`paged_decode_attention`'s composite lowering IS the slot tick's
    fused decode attention over the gathered table view (the kernel against
    the composite: tests/test_kv_pager.py)."""

    NB, NH, BS, DH, NLB = 11, 2, 4, 8, 3

    def _pools(self, rng):
        shape = (self.NB, self.NH, self.BS, self.DH)
        return (rng.randn(*shape).astype("float32"),
                rng.randn(*shape).astype("float32"))

    def _dense(self, pool, btab):
        return np.stack([np.concatenate([pool[b] for b in row], axis=1)
                         for row in btab])[:, None]       # [S,1,nh,T,dh]

    @pytest.mark.parametrize("g", [1, 3])
    def test_equals_fused_decode_attention_on_the_table_view(self, rng, g):
        """G query rows a slot: row i attends 0..pos+i, the verify
        window's causal mask."""
        from paddle_tpu.fusion import (fused_decode_attention,
                                       paged_decode_attention)
        k_pool, v_pool = self._pools(rng)
        btab = np.array([[4, 9, 2], [7, 1, 0]], "int64")
        pos = np.array([5, 2], "int64")
        S, T = 2, self.NLB * self.BS
        q = rng.randn(S, g, self.NH * self.DH).astype("float32")
        got = paged_decode_attention(
            q, k_pool, v_pool, btab, pos.astype("float32"), self.NH,
            scale=0.25, backend="xla")
        valid = (np.arange(T)[None, None]
                 <= (pos[:, None] + np.arange(g)[None])[:, :, None])
        bias = np.where(valid, 0.0, -1e9).astype("float32")[:, None, None]
        q5 = q.reshape(S, g, self.NH, self.DH).transpose(0, 2, 1, 3)[:, None]
        ref = fused_decode_attention(
            q5, self._dense(k_pool, btab), self._dense(v_pool, btab), bias,
            scale=0.25, backend="xla")                    # [S,1,nh,G,dh]
        ref = np.asarray(ref)[:, 0].transpose(0, 2, 1, 3).reshape(q.shape)
        np.testing.assert_allclose(got, ref, rtol=1e-6, atol=1e-6)

    def test_int8_pools_dequantize_against_their_scale_pools(self, rng):
        from paddle_tpu.fusion import paged_decode_attention
        k_pool, v_pool = self._pools(rng)

        def quant(pool):
            sc = np.abs(pool).max(-1, keepdims=True) / 127.0
            return np.round(pool / sc).astype("int8"), sc.astype("float32")
        (kq, ks), (vq, vs) = quant(k_pool), quant(v_pool)
        btab = np.array([[4, 9, 2], [7, 1, 0]], "int64")
        pos = np.array([9, 6], "float32")
        q = rng.randn(2, 1, self.NH * self.DH).astype("float32")
        got = paged_decode_attention(q, kq, vq, btab, pos, self.NH,
                                     scale=0.25, k_scale=ks, v_scale=vs)
        ref = paged_decode_attention(
            q, kq.astype("float32") * ks, vq.astype("float32") * vs, btab,
            pos, self.NH, scale=0.25, backend="xla")
        np.testing.assert_allclose(got, ref, rtol=1e-6, atol=1e-6)


def test_fuse_recurrent_cell_pass_preserves_stacked_lstm_training(rng):
    """stacked_lstm_net + Adam, 3 steps: losses AND updated parameters are
    identical with the fuse pass on vs off — forward and gradient of the
    fused cells are drop-in (the training path exercises the custom_vjp)."""
    base_l, base_p = _lstm_losses_and_params(False, rng)
    fuse_l, fuse_p = _lstm_losses_and_params(True, rng)
    np.testing.assert_allclose(fuse_l, base_l, atol=1e-6, rtol=1e-6)
    assert base_p.keys() == fuse_p.keys()
    for name in base_p:
        np.testing.assert_allclose(fuse_p[name], base_p[name], atol=1e-5,
                                   rtol=1e-4, err_msg=name)


def _decode(fuse, beam, seed=3):
    from paddle_tpu.core import unique_name
    from paddle_tpu.models import transformer
    pt.reset_default_programs()
    pt.reset_global_scope()
    flags.set_flag("fuse_decode_attention", fuse)
    with unique_name.guard():
        seqs, scores = transformer.transformer_lm_generate(
            vocab=60, max_gen=6, d_model=16, d_inner=32, num_heads=2,
            num_layers=2, bos_id=1, beam_size=beam)
    exe = pt.Executor()
    pt.default_startup_program().random_seed = seed
    exe.run(pt.default_startup_program())
    feed = {"prompt": np.full((3, 1), 1, "int64")}
    out, sc = exe.run(feed=feed, fetch_list=[seqs, scores])
    return np.asarray(out), np.asarray(sc)


@pytest.mark.quick
@pytest.mark.parametrize("beam", [1, 4])
def test_fuse_decode_attention_pass_preserves_lm_decode(beam):
    """KV-cached LM decode (greedy + beam-4): generated sequences are
    IDENTICAL and scores agree to a bf16 ulp (the rewrite changes XLA's
    f32 summation order upstream of the bf16 lm_head) with the pass on
    vs off."""
    o0, s0 = _decode(False, beam)
    o1, s1 = _decode(True, beam)
    assert np.array_equal(o0, o1)
    np.testing.assert_allclose(s1, s0, atol=2e-2, rtol=1e-3)


def test_fuse_decode_attention_pass_rewrites_the_decode_subgraph():
    """Structural evidence: the pass replaces every per-layer 4-op decode
    attention chain (matmul/add/softmax/matmul) in the StaticRNN sub-block
    with one fused_decode_attention op, and drops the glue vars."""
    from paddle_tpu.core import unique_name
    from paddle_tpu.framework.passes import apply_fusion_passes
    from paddle_tpu.models import transformer
    with unique_name.guard():
        seqs, _ = transformer.transformer_lm_generate(
            vocab=60, max_gen=6, d_model=16, d_inner=32, num_heads=2,
            num_layers=3, bos_id=1, beam_size=4)
    prog = pt.default_main_program()

    def count(p, t):
        return sum(op.type == t for b in p.blocks for op in b.ops)

    flags.set_flag("fuse_decode_attention", True)
    rewritten = apply_fusion_passes(prog, protected=[seqs.name])
    assert rewritten is not prog, "pass should clone, not mutate"
    assert count(prog, "fused_decode_attention") == 0
    assert count(rewritten, "fused_decode_attention") == 3  # one per layer
    assert count(rewritten, "softmax") == count(prog, "softmax") - 3
    assert count(rewritten, "matmul") == count(prog, "matmul") - 2 * 3
    assert count(rewritten, "cache_write") == count(prog, "cache_write")
    # the glue vars are gone; every remaining op input still resolves
    from paddle_tpu.framework.passes import get_pass
    get_pass("check_pass")(rewritten)


# ---------------------------------------------------------------------------
# (c) pass safety: what must NOT be rewritten
# ---------------------------------------------------------------------------


def test_recurrent_pass_skips_non_default_activations():
    from paddle_tpu.core import unique_name
    from paddle_tpu.framework.passes import apply_fusion_passes
    with unique_name.guard():
        data = layers.data("w2", shape=[8], dtype="int64", lod_level=1)
        seqlen = layers.sequence.get_seqlen(data)
        emb = layers.embedding(input=data, size=[50, 16])
        emb = layers.sequence.tag_sequence(emb, seqlen)
        proj = layers.fc(emb, size=64, num_flatten_dims=2)
        proj = layers.sequence.tag_sequence(proj, seqlen)
        layers.dynamic_lstm(input=proj, size=64, gate_activation="relu")
        layers.dynamic_lstm(input=proj, size=64)
    flags.set_flag("fuse_recurrent_cells", True)
    prog = apply_fusion_passes(pt.default_main_program())
    types = [op.type for op in prog.global_block().ops]
    assert types.count("dynamic_lstm") == 1   # the relu one stays
    assert types.count("fused_lstm") == 1


def test_decode_pass_skips_multi_position_queries():
    """A full-sequence attention chain (Tq > 1) is not a decode step."""
    from paddle_tpu.core import unique_name
    from paddle_tpu.framework.passes import apply_fusion_passes
    with unique_name.guard():
        q = layers.data("q", shape=[2, 8, 16], dtype="float32")
        k = layers.data("k", shape=[2, 8, 16], dtype="float32")
        v = layers.data("v", shape=[2, 8, 16], dtype="float32")
        bias = layers.data("b", shape=[2, 8, 8], dtype="float32")
        s = layers.matmul(q, k, transpose_y=True, alpha=0.25)
        s = layers.elementwise_add(s, bias)
        w = layers.softmax(s)
        layers.matmul(w, v)
    flags.set_flag("fuse_decode_attention", True)
    prog = apply_fusion_passes(pt.default_main_program())
    types = [op.type for op in prog.global_block().ops]
    assert "fused_decode_attention" not in types


def test_decode_pass_skips_multi_consumer_intermediates():
    """If the attention weights are read elsewhere (e.g. fetched for
    attention maps), the chain must survive unfused."""
    from paddle_tpu.core import unique_name
    from paddle_tpu.framework.passes import apply_fusion_passes
    with unique_name.guard():
        q = layers.data("q", shape=[2, 1, 16], dtype="float32")
        k = layers.data("k", shape=[2, 8, 16], dtype="float32")
        v = layers.data("v", shape=[2, 8, 16], dtype="float32")
        bias = layers.data("b", shape=[2, 1, 8], dtype="float32")
        s = layers.matmul(q, k, transpose_y=True, alpha=0.25)
        s = layers.elementwise_add(s, bias)
        w = layers.softmax(s)
        layers.matmul(w, v)
        layers.reduce_mean(w)          # second consumer of the weights
    flags.set_flag("fuse_decode_attention", True)
    prog = apply_fusion_passes(pt.default_main_program())
    types = [op.type for op in prog.global_block().ops]
    assert "fused_decode_attention" not in types


def test_kill_switch_disables_rewrite():
    from paddle_tpu.core import unique_name
    from paddle_tpu.framework.passes import apply_fusion_passes
    with unique_name.guard():
        data = layers.data("w3", shape=[8], dtype="int64", lod_level=1)
        seqlen = layers.sequence.get_seqlen(data)
        emb = layers.embedding(input=data, size=[50, 16])
        emb = layers.sequence.tag_sequence(emb, seqlen)
        proj = layers.fc(emb, size=64, num_flatten_dims=2)
        proj = layers.sequence.tag_sequence(proj, seqlen)
        layers.dynamic_lstm(input=proj, size=64)
    flags.set_flag("fuse_recurrent_cells", False)
    flags.set_flag("fuse_decode_attention", False)
    prog = pt.default_main_program()
    assert apply_fusion_passes(prog) is prog   # untouched, not even cloned


# ---------------------------------------------------------------------------
# satellite: cache_write uniform-Pos contract (ADVICE r5 #3)
# ---------------------------------------------------------------------------


class TestCacheWriteUniformPos:
    def _build(self):
        from paddle_tpu.core import unique_name
        with unique_name.guard():
            cache = layers.data("cache", shape=[4, 8], dtype="float32")
            new = layers.data("new", shape=[1, 8], dtype="float32")
            pos = layers.data("pos", shape=[2], dtype="int32")
            out = layers.cache_write(cache, new, pos, axis=1)
        return out

    def test_uniform_pos_ok(self):
        out = self._build()
        exe = pt.Executor()
        got = exe.run(feed={
            "cache": np.zeros((2, 4, 8), "float32"),
            "new": np.ones((2, 1, 8), "float32"),
            "pos": np.full((2, 2), 2, "int32")}, fetch_list=[out])[0]
        assert got[:, 2].sum() == 2 * 8 and got.sum() == 2 * 8

    def test_non_uniform_pos_raises(self):
        out = self._build()
        exe = pt.Executor()
        with pytest.raises(Exception, match="uniform position"):
            exe.run(feed={
                "cache": np.zeros((2, 4, 8), "float32"),
                "new": np.ones((2, 1, 8), "float32"),
                "pos": np.array([[1, 3], [1, 1]], "int32")},
                fetch_list=[out])
