"""Per-op checks: NN family (matmul, conv, pool, norms, losses, optimizers).

≙ reference tests/unittests/test_{mul,conv2d,pool2d,batch_norm,layer_norm,
softmax,cross_entropy,sgd,adam,...}_op.py.
"""

import numpy as np
import pytest

from op_test import check_grad, check_output, run_op


class TestMatmul:
    def test_mul(self, rng):
        x = rng.rand(4, 6).astype(np.float32)
        y = rng.rand(6, 3).astype(np.float32)
        check_output("mul", {"X": x, "Y": y}, {"Out": x @ y}, rtol=1e-5)
        check_grad("mul", {"X": x, "Y": y}, ["X", "Y"])

    def test_mul_flatten(self, rng):
        x = rng.rand(2, 3, 4).astype(np.float32)
        y = rng.rand(12, 5).astype(np.float32)
        out = run_op("mul", {"X": x, "Y": y}, {"x_num_col_dims": 1})
        np.testing.assert_allclose(out["Out"][0],
                                   x.reshape(2, 12) @ y, rtol=1e-5)

    def test_matmul_transpose(self, rng):
        x = rng.rand(3, 4).astype(np.float32)
        y = rng.rand(5, 4).astype(np.float32)
        check_output("matmul", {"X": x, "Y": y}, {"Out": x @ y.T},
                     attrs={"transpose_Y": True}, rtol=1e-5)

    def test_matmul_batched(self, rng):
        x = rng.rand(2, 3, 4).astype(np.float32)
        y = rng.rand(2, 4, 5).astype(np.float32)
        check_output("matmul", {"X": x, "Y": y}, {"Out": x @ y}, rtol=1e-5)


class TestConvPool:
    def test_conv2d_forward(self, rng):
        x = rng.rand(2, 3, 8, 8).astype(np.float32)
        w = rng.rand(4, 3, 3, 3).astype(np.float32)
        out = run_op("conv2d", {"Input": x, "Filter": w},
                     {"strides": [1, 1], "paddings": [1, 1]})
        assert out["Output"][0].shape == (2, 4, 8, 8)
        # compare against naive correlation at one output position
        ref00 = (x[0, :, 0:3, 0:3] * w[0]).sum()
        np.testing.assert_allclose(out["Output"][0][0, 0, 1, 1], ref00,
                                   rtol=1e-4)

    def test_conv2d_grad(self, rng):
        x = rng.rand(1, 2, 5, 5).astype(np.float32)
        w = rng.rand(3, 2, 3, 3).astype(np.float32)
        check_grad("conv2d", {"Input": x, "Filter": w},
                   ["Input", "Filter"], out_slot="Output",
                   attrs={"strides": [1, 1], "paddings": [0, 0]})

    def test_depthwise(self, rng):
        x = rng.rand(1, 4, 6, 6).astype(np.float32)
        w = rng.rand(4, 1, 3, 3).astype(np.float32)
        out = run_op("depthwise_conv2d", {"Input": x, "Filter": w},
                     {"strides": [1, 1], "paddings": [1, 1]})
        assert out["Output"][0].shape == (1, 4, 6, 6)

    def test_pool2d(self, rng):
        x = rng.rand(2, 3, 6, 6).astype(np.float32)
        out = run_op("pool2d", {"X": x}, {"pooling_type": "max",
                                          "ksize": [2, 2], "strides": [2, 2],
                                          "paddings": [0, 0]})
        ref = x.reshape(2, 3, 3, 2, 3, 2).max(axis=(3, 5))
        np.testing.assert_allclose(out["Out"][0], ref, rtol=1e-6)
        out = run_op("pool2d", {"X": x}, {"pooling_type": "avg",
                                          "ksize": [2, 2], "strides": [2, 2],
                                          "paddings": [0, 0]})
        ref = x.reshape(2, 3, 3, 2, 3, 2).mean(axis=(3, 5))
        np.testing.assert_allclose(out["Out"][0], ref, rtol=1e-5)

    def test_global_pool(self, rng):
        x = rng.rand(2, 3, 5, 5).astype(np.float32)
        out = run_op("pool2d", {"X": x}, {"pooling_type": "avg",
                                          "global_pooling": True,
                                          "ksize": [1, 1]})
        np.testing.assert_allclose(out["Out"][0][..., 0, 0],
                                   x.mean(axis=(2, 3)), rtol=1e-5)


class TestNorms:
    def test_batch_norm_train(self, rng):
        x = rng.rand(4, 3, 5, 5).astype(np.float32)
        scale = np.ones(3, np.float32)
        bias = np.zeros(3, np.float32)
        mean = np.zeros(3, np.float32)
        var = np.ones(3, np.float32)
        out = run_op("batch_norm",
                     {"X": x, "Scale": scale, "Bias": bias, "Mean": mean,
                      "Variance": var}, {"momentum": 0.9, "epsilon": 1e-5})
        y = out["Y"][0]
        np.testing.assert_allclose(y.mean(axis=(0, 2, 3)), np.zeros(3),
                                   atol=1e-5)
        np.testing.assert_allclose(y.std(axis=(0, 2, 3)), np.ones(3),
                                   atol=1e-3)
        # moving stats updated toward batch stats
        np.testing.assert_allclose(
            out["MeanOut"][0], 0.9 * mean + 0.1 * x.mean(axis=(0, 2, 3)),
            rtol=1e-4)

    def test_batch_norm_infer(self, rng):
        x = rng.rand(4, 3, 5, 5).astype(np.float32)
        mean = rng.rand(3).astype(np.float32)
        var = rng.rand(3).astype(np.float32) + 0.5
        out = run_op("batch_norm",
                     {"X": x, "Scale": np.ones(3, np.float32),
                      "Bias": np.zeros(3, np.float32), "Mean": mean,
                      "Variance": var},
                     {"epsilon": 1e-5, "is_test": True})
        ref = (x - mean.reshape(1, 3, 1, 1)) / np.sqrt(
            var.reshape(1, 3, 1, 1) + 1e-5)
        np.testing.assert_allclose(out["Y"][0], ref, rtol=1e-4)

    def test_layer_norm(self, rng):
        x = rng.rand(4, 10).astype(np.float32)
        scale = rng.rand(10).astype(np.float32)
        bias = rng.rand(10).astype(np.float32)
        out = run_op("layer_norm", {"X": x, "Scale": scale, "Bias": bias},
                     {"begin_norm_axis": 1, "epsilon": 1e-5})
        mu = x.mean(axis=1, keepdims=True)
        sd = x.std(axis=1, keepdims=True)
        ref = (x - mu) / np.sqrt(sd ** 2 + 1e-5) * scale + bias
        np.testing.assert_allclose(out["Y"][0], ref, rtol=1e-4)


class TestLosses:
    def test_softmax(self, rng):
        x = rng.rand(4, 7).astype(np.float32)
        e = np.exp(x - x.max(axis=1, keepdims=True))
        check_output("softmax", {"X": x}, {"Out": e / e.sum(1, keepdims=True)},
                     rtol=1e-5)
        check_grad("softmax", {"X": x}, ["X"],
                   reduce_fn=lambda o: (o * o).sum())

    def test_softmax_with_cross_entropy(self, rng):
        logits = rng.rand(4, 5).astype(np.float32)
        label = np.array([[0], [2], [4], [1]], dtype=np.int32)
        out = run_op("softmax_with_cross_entropy",
                     {"Logits": logits, "Label": label}, {})
        lse = np.log(np.exp(logits).sum(axis=1, keepdims=True))
        ref = lse - np.take_along_axis(logits, label, axis=1)
        np.testing.assert_allclose(out["Loss"][0], ref, rtol=1e-4)

    def test_softmax_ce_soft_label(self, rng):
        logits = rng.rand(3, 4).astype(np.float32)
        soft = rng.rand(3, 4).astype(np.float32)
        soft /= soft.sum(1, keepdims=True)
        out = run_op("softmax_with_cross_entropy",
                     {"Logits": logits, "Label": soft}, {"soft_label": True})
        logp = logits - np.log(np.exp(logits).sum(1, keepdims=True))
        ref = -(soft * logp).sum(1, keepdims=True)
        np.testing.assert_allclose(out["Loss"][0], ref, rtol=1e-4)

    def test_cross_entropy(self, rng):
        probs = rng.rand(4, 5).astype(np.float32) + 0.1
        probs /= probs.sum(1, keepdims=True)
        label = np.array([[1], [0], [3], [2]], dtype=np.int32)
        out = run_op("cross_entropy", {"X": probs, "Label": label}, {})
        ref = -np.log(np.take_along_axis(probs, label, axis=1))
        np.testing.assert_allclose(out["Y"][0], ref, rtol=1e-4)

    def test_sigmoid_ce_and_mse(self, rng):
        x = rng.randn(4, 3).astype(np.float32)
        lbl = (rng.rand(4, 3) > 0.5).astype(np.float32)
        out = run_op("sigmoid_cross_entropy_with_logits",
                     {"X": x, "Label": lbl}, {})
        sig = 1 / (1 + np.exp(-x))
        ref = -(lbl * np.log(sig) + (1 - lbl) * np.log(1 - sig))
        np.testing.assert_allclose(out["Out"][0], ref, rtol=1e-4, atol=1e-5)


class TestOptimizers:
    def test_sgd(self, rng):
        p = rng.rand(4, 3).astype(np.float32)
        g = rng.rand(4, 3).astype(np.float32)
        lr = np.array([0.1], dtype=np.float32)
        out = run_op("sgd", {"Param": p, "Grad": g, "LearningRate": lr}, {})
        np.testing.assert_allclose(out["ParamOut"][0], p - 0.1 * g, rtol=1e-6)

    def test_momentum(self, rng):
        p = rng.rand(3).astype(np.float32)
        g = rng.rand(3).astype(np.float32)
        v = rng.rand(3).astype(np.float32)
        lr = np.array([0.1], dtype=np.float32)
        out = run_op("momentum", {"Param": p, "Grad": g, "Velocity": v,
                                  "LearningRate": lr}, {"mu": 0.9})
        v_new = 0.9 * v + g
        np.testing.assert_allclose(out["VelocityOut"][0], v_new, rtol=1e-6)
        np.testing.assert_allclose(out["ParamOut"][0], p - 0.1 * v_new,
                                   rtol=1e-6)

    def test_adam(self, rng):
        n = 6
        p, g, m, v = (rng.rand(n).astype(np.float32) for _ in range(4))
        lr = np.array([0.01], dtype=np.float32)
        b1p = np.array([0.9], dtype=np.float32)
        b2p = np.array([0.999], dtype=np.float32)
        out = run_op("adam", {"Param": p, "Grad": g, "Moment1": m,
                              "Moment2": v, "Beta1Pow": b1p, "Beta2Pow": b2p,
                              "LearningRate": lr},
                     {"beta1": 0.9, "beta2": 0.999, "epsilon": 1e-8})
        m_new = 0.9 * m + 0.1 * g
        v_new = 0.999 * v + 0.001 * g * g
        lr_t = 0.01 * np.sqrt(1 - b2p) / (1 - b1p)
        ref = p - lr_t * m_new / (np.sqrt(v_new) + 1e-8)
        np.testing.assert_allclose(out["ParamOut"][0], ref, rtol=1e-5)

    @pytest.mark.parametrize("op,extra", [
        ("adagrad", {"Moment": None}),
        ("rmsprop", {"MeanSquare": None, "Moment": None}),
    ])
    def test_accumulator_updates_finite(self, rng, op, extra):
        n = 5
        feed = {"Param": rng.rand(n).astype(np.float32),
                "Grad": rng.rand(n).astype(np.float32),
                "LearningRate": np.array([0.1], np.float32)}
        for k in extra:
            feed[k] = rng.rand(n).astype(np.float32)
        out = run_op(op, feed, {})
        assert np.all(np.isfinite(out["ParamOut"][0]))


class TestMetrics:
    def test_accuracy(self, rng):
        indices = np.array([[0], [1], [2], [2]], dtype=np.int64)
        label = np.array([[0], [1], [0], [2]], dtype=np.int64)
        out = run_op("accuracy", {"Out": indices.astype(np.float32),
                                  "Indices": indices, "Label": label}, {})
        np.testing.assert_allclose(out["Accuracy"][0], 0.75, rtol=1e-6)


class TestDropout:
    def test_dropout_train_test(self, rng):
        x = np.ones((100, 100), dtype=np.float32)
        out = run_op("dropout", {"X": x}, {"dropout_prob": 0.3})
        keep = (np.asarray(out["Out"][0]) != 0).mean()
        assert 0.6 < keep < 0.8
        out = run_op("dropout", {"X": x}, {"dropout_prob": 0.3},
                     is_test=True)
        np.testing.assert_allclose(out["Out"][0], x * 0.7, rtol=1e-6)
        out = run_op("dropout", {"X": x},
                     {"dropout_prob": 0.3,
                      "dropout_implementation": "upscale_in_train"},
                     is_test=True)
        np.testing.assert_allclose(out["Out"][0], x, rtol=1e-6)


class TestReviewRegressions:
    def test_conv2d_transpose_channels(self, rng):
        """num_filters != C_in (regression: kernel layout was swapped)."""
        x = rng.rand(1, 3, 5, 5).astype(np.float32)
        w = rng.rand(3, 4, 3, 3).astype(np.float32)  # (C_in, C_out, kh, kw)
        out = run_op("conv2d_transpose", {"Input": x, "Filter": w},
                     {"strides": [1, 1], "paddings": [0, 0]})
        assert out["Output"][0].shape == (1, 4, 7, 7)
        # cross-check against autograd: conv_transpose is the VJP of conv
        import jax
        import jax.numpy as jnp

        def fwd(inp):
            return jax.lax.conv_general_dilated(
                inp, jnp.asarray(w).transpose(1, 0, 2, 3)[:, :, ::-1, ::-1],
                (1, 1), [(2, 2), (2, 2)],
                dimension_numbers=("NCHW", "OIHW", "NCHW"))

        ref = fwd(jnp.asarray(x))
        np.testing.assert_allclose(out["Output"][0], ref, rtol=1e-4,
                                   atol=1e-5)

    def test_softmax_ce_ignore_index(self, rng):
        logits = rng.rand(4, 5).astype(np.float32)
        label = np.array([[0], [-100], [2], [-100]], dtype=np.int32)
        out = run_op("softmax_with_cross_entropy",
                     {"Logits": logits, "Label": label}, {})
        loss = out["Loss"][0]
        assert loss[1, 0] == 0.0 and loss[3, 0] == 0.0
        assert loss[0, 0] > 0.0 and loss[2, 0] > 0.0

    def test_pool2d_ceil_mode(self, rng):
        # 8x8, k=3, s=2: floor -> 3, ceil -> 4 (span 5 not divisible by 2)
        x = rng.rand(1, 1, 8, 8).astype(np.float32)
        out = run_op("pool2d", {"X": x},
                     {"pooling_type": "max", "ksize": [3, 3],
                      "strides": [2, 2], "paddings": [0, 0],
                      "ceil_mode": True})
        assert out["Out"][0].shape == (1, 1, 4, 4)
        np.testing.assert_allclose(out["Out"][0][0, 0, 3, 3],
                                   x[0, 0, 6:8, 6:8].max(), rtol=1e-6)
        out = run_op("pool2d", {"X": x},
                     {"pooling_type": "max", "ksize": [3, 3],
                      "strides": [2, 2], "paddings": [0, 0]})
        assert out["Out"][0].shape == (1, 1, 3, 3)

    def test_lookup_table_negative_padding_idx(self, rng):
        w = rng.rand(10, 4).astype(np.float32)
        ids = np.array([[1], [9], [3]], dtype=np.int32)
        out = run_op("lookup_table", {"W": w, "Ids": ids},
                     {"padding_idx": -1})  # means row 9
        np.testing.assert_allclose(out["Out"][0][1], 0.0, atol=1e-7)
        np.testing.assert_allclose(out["Out"][0][0], w[1], rtol=1e-6)


class TestUnitCellsAndMisc:
    def test_row_conv_matches_numpy(self, rng):
        x = rng.rand(2, 6, 3).astype("float32")
        w = rng.rand(3, 3).astype("float32")  # lookahead 2
        out = run_op("row_conv", {"X": x, "Filter": w})["Out"][0]
        exp = np.zeros_like(x)
        for t in range(6):
            for i in range(3):
                if t + i < 6:
                    exp[:, t] += x[:, t + i] * w[i]
        np.testing.assert_allclose(out, exp, rtol=1e-5)
        check_grad("row_conv", {"X": x, "Filter": w},
                   grad_slots=["X", "Filter"], atol=5e-3, rtol=5e-3)

    def test_lstm_unit(self, rng):
        B, H = 3, 4
        x = rng.randn(B, 4 * H).astype("float32")
        c = rng.randn(B, H).astype("float32")
        out = run_op("lstm_unit", {"X": x, "C_prev": c},
                     attrs={"forget_bias": 1.0})
        # REFERENCE slot order (lstm_unit_op.h:63-66): i, f, o, g
        i, f, o, g = np.split(x, 4, axis=1)
        sig = lambda v: 1 / (1 + np.exp(-v))  # noqa: E731
        exp_c = c * sig(f + 1.0) + sig(i) * np.tanh(g)
        exp_h = np.tanh(exp_c) * sig(o)
        np.testing.assert_allclose(out["C"][0], exp_c, rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(out["H"][0], exp_h, rtol=1e-5, atol=1e-5)

    def test_gru_unit_consistency(self, rng):
        B, H = 2, 3
        x = rng.randn(B, 3 * H).astype("float32")
        h0 = rng.randn(B, H).astype("float32")
        w = rng.randn(H, 3 * H).astype("float32") * 0.5
        out = run_op("gru_unit", {"Input": x, "HiddenPrev": h0,
                                  "Weight": w})
        sig = lambda v: 1 / (1 + np.exp(-v))  # noqa: E731
        u = sig(x[:, :H] + h0 @ w[:, :H])
        r = sig(x[:, H:2*H] + h0 @ w[:, H:2*H])
        c = np.tanh(x[:, 2*H:] + (r * h0) @ w[:, 2*H:])
        # REFERENCE update semantics (gru_unit_op.h:116): toward candidate
        exp = u * c + (1 - u) * h0
        np.testing.assert_allclose(out["Hidden"][0], exp, rtol=1e-4,
                                   atol=1e-4)
        assert out["Gate"][0].shape == (B, 3 * H)

    def test_spp_pyramid(self, rng):
        x = rng.rand(2, 3, 8, 8).astype("float32")
        out = run_op("spp", {"X": x},
                     attrs={"pyramid_height": 2,
                            "pooling_type": "max"})["Out"][0]
        assert out.shape == (2, 3 * (1 + 4))
        np.testing.assert_allclose(out[:, :3], x.max(axis=(2, 3)), rtol=1e-6)
        # level-1 first bin = top-left quadrant max
        np.testing.assert_allclose(out[:, 3:6],
                                   x[:, :, :4, :4].max(axis=(2, 3)),
                                   rtol=1e-6)
