"""The routed expert layer that is told which experts it holds (ISSUE 36):
plain top-k of sigmoid scores over every expert, normalised and scaled; the
shares of an expert-parallel deployment add up to the uncut layer; an
untouched expert costs nothing and changes nothing."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from axk1_tiny import ref

from paddle_tpu.fusion import moe
from paddle_tpu.models.decoder_spec import MoESpec

N, D, F, E, K = 48, 128, 256, 32, 8
RNG = np.random.default_rng(4)
X = jnp.asarray(RNG.normal(size=(N, D)), jnp.float32)
W_R = jnp.asarray(RNG.normal(size=(D, E)) * D ** -0.5, jnp.float32)
GATE, UP = (jnp.asarray(RNG.normal(size=(E, D, F)) * D ** -0.5, jnp.float32)
            for _ in range(2))
DOWN = jnp.asarray(RNG.normal(size=(E, F, D)) * F ** -0.5, jnp.float32)
SHARED = [jnp.asarray(RNG.normal(size=s) * s[0] ** -0.5, jnp.float32)
          for s in ((D, F), (D, F), (F, D))]
CFG = dict(num_experts_per_tok=K, norm_topk_prob=True,
           routed_scaling_factor=2.5, topk_method="none")


def test_route_is_plain_top_k_of_sigmoid_scores_normalised_and_scaled():
    held = tuple(range(E))
    w, rows = moe.route(X, W_R, held, K, 2.5)
    w = np.asarray(w)[:, :, 0].T                       # [N, E]
    sigma = 1.0 / (1.0 + np.exp(-(np.asarray(X) @ np.asarray(W_R))))
    for n in range(N):
        top = np.argsort(-sigma[n])[:K]
        want = np.zeros(E)
        want[top] = sigma[n, top] / sigma[n, top].sum() * 2.5
        np.testing.assert_allclose(w[n], want, atol=1e-6)
    assert np.asarray(rows).sum() == N * K
    np.testing.assert_allclose(w.sum(1), 2.5, rtol=1e-5)
    # a share's weights are the same numbers: the sum runs over all eight
    # selected, held here or not
    part, part_rows = moe.route(X, W_R, (3, 9, 30), K, 2.5)
    np.testing.assert_allclose(np.asarray(part)[:, :, 0].T, w[:, [3, 9, 30]],
                               atol=1e-6)
    assert np.asarray(part_rows).tolist() == (w[:, [3, 9, 30]] > 0).sum(0).tolist()
    # a dead row selects nothing
    live = jnp.asarray((np.arange(N) % 2).astype(np.float32))
    half, half_rows = moe.route(X, W_R, held, K, 2.5, live=live)
    assert not np.asarray(half)[:, ::2].any()
    assert np.asarray(half_rows).sum() == N // 2 * K


def test_sixteen_shares_and_one_shared_expert_add_up_to_the_uncut_layer():
    """Each of sixteen chips holds two of the 32 experts and computes its
    part of the routed sum; the parts, with the shared expert counted once,
    are the reference's layer with every expert held."""
    p = {"m_router.w_0": W_R, "m_experts_gate": GATE, "m_experts_up": UP,
         "m_experts_down": DOWN}
    p.update({f"m_shared_{n}.w_0": w
              for n, w in zip(("gate", "up", "down"), SHARED)})
    with jax.default_matmul_precision("highest"):
        uncut = np.asarray(ref.moe(p, "m", X, CFG, tuple(range(E))))
        shared = np.asarray(ref.gated_ffn(X, *SHARED))
        total = shared.copy()
        touched = 0
        for rank in range(16):
            held = (2 * rank, 2 * rank + 1)
            w, rows = moe.route(X, W_R, held, K, 2.5)
            total += np.asarray(moe.experts(
                X, w, rows, GATE[2 * rank:2 * rank + 2],
                UP[2 * rank:2 * rank + 2], DOWN[2 * rank:2 * rank + 2]))
            touched += int(np.asarray(rows).sum())
            # ... and the reference computes the same share, given `held`
            pr = dict(p, m_experts_gate=GATE[2 * rank:2 * rank + 2],
                      m_experts_up=UP[2 * rank:2 * rank + 2],
                      m_experts_down=DOWN[2 * rank:2 * rank + 2])
            if rank in (0, 7):
                share = np.asarray(ref.moe(pr, "m", X, CFG, held)) - shared
                got = np.asarray(moe.experts(
                    X, w, rows, *(pr[f"m_experts_{n}"]
                                  for n in ("gate", "up", "down"))))
                np.testing.assert_allclose(got, share, atol=2e-5)
    assert touched == N * K
    np.testing.assert_allclose(total, uncut, atol=5e-5)


@pytest.mark.parametrize("held", [(0, 1, 2, 3), (5, 11, 17, 29)])
def test_kernel_equals_composite_and_skips_what_no_row_selected(held):
    live = jnp.asarray((np.arange(N) < 6).astype(np.float32))
    w, rows = moe.route(X, W_R, held, 2, 2.5, live=live)
    assert (np.asarray(rows) == 0).any() and (np.asarray(rows) > 0).any()
    idx = jnp.asarray(held)
    args = (X, w, rows, GATE[idx], UP[idx], DOWN[idx])
    a = np.asarray(moe.experts(*args, backend="xla"))
    b = np.asarray(moe.experts(*args, backend="pallas_interpret"))
    np.testing.assert_allclose(a, b, atol=2e-5)
    assert not a[6:].any() and np.abs(a[:6]).max() > 0.01
    # an expert no row selected is never read: poison its weights
    dead = np.asarray(rows) == 0
    poison = jnp.where(jnp.asarray(dead)[:, None, None], jnp.nan, GATE[idx])
    c = np.asarray(moe.experts(X, w, rows, poison, UP[idx], DOWN[idx],
                               backend="pallas_interpret"))
    np.testing.assert_allclose(c, b, atol=1e-6)
    none = moe.experts(X, jnp.zeros_like(w), jnp.zeros_like(rows), GATE[idx],
                       UP[idx], DOWN[idx], backend="pallas_interpret")
    assert not np.asarray(none).any()


def test_an_unknown_topk_method_raises_by_name():
    with pytest.raises(NotImplementedError, match="group_limited_greedy"):
        MoESpec(n_routed=E, top_k=K, d_expert=F, held=(0, 1),
                topk_method="group_limited_greedy")
    with pytest.raises(NotImplementedError, match="noaux_tc"):
        ref.route(X, W_R, dict(CFG, topk_method="noaux_tc"))
    with pytest.raises(NotImplementedError, match="softmax"):
        MoESpec(n_routed=E, top_k=K, d_expert=F, held=(0,), scoring="softmax")
    with pytest.raises(ValueError, match="held"):
        MoESpec(n_routed=E, top_k=K, d_expert=F, held=(3, 1))
    assert MoESpec(n_routed=E, top_k=K, d_expert=F, held=(1, 3)).held == (1, 3)


def test_lowering_is_chosen_by_shape_and_backend():
    assert moe.experts_lowering(32, 7168, 2048, "pallas") == moe.KERNEL
    assert moe.experts_lowering(288, 7168, 2048, "pallas") == moe.KERNEL
    assert moe.experts_lowering(30, 7168, 2048, "pallas") == moe.COMPOSITE
    assert moe.experts_lowering(32, 64, 96) == moe.COMPOSITE


def _scores_as_router(scores):
    """(x, w_router) whose product is `scores` [n, E]: x the identity."""
    return jnp.eye(scores.shape[0], dtype=jnp.float32), jnp.asarray(scores)


def test_route_near_swaps_one_pair_the_scores_do_not_tell_apart():
    """The reference's second selections: an expert inside the top-k and one
    outside it within the margin, at least one of them held."""
    n = 256                         # a block of rows; only the first 3 count
    s = np.tile(np.linspace(3.0, -3.0, E, dtype=np.float32), (n, 1))
    # rows 0 and 1: the 8th and 9th (experts 7, 8) lie 0.01 apart; in row 2,
    # as everywhere else, 0.19
    s[:2, 7], s[:2, 8] = 1.50, 1.49
    x, w_r = _scores_as_router(s)
    cfg = dict(CFG)
    # the inner one held, neither (a pair of absent experts), the outer one
    for held, want in (((7, 20), [0, 1]), ((20, 21), []), ((8,), [0, 1])):
        ids, w, src, ids2, w2, dist = ref.route_near(x, w_r, cfg, held, 0.05,
                                                     n_rows=3)
        assert src.tolist() == want
        assert sorted(ids[0].tolist()) == list(range(8))
        np.testing.assert_allclose(w.sum(1), 2.5, rtol=1e-5)
        for k, r in enumerate(src):
            assert sorted(ids2[k].tolist()) == [0, 1, 2, 3, 4, 5, 6, 8]
            assert dist[k] == pytest.approx(0.01, abs=1e-5)
            np.testing.assert_allclose(w2[k].sum(), 2.5, rtol=1e-5)
            sig = 1 / (1 + np.exp(-s[r, ids2[k]].astype(np.float64)))
            np.testing.assert_allclose(w2[k], sig / sig.sum() * 2.5, rtol=1e-5)
    # a margin of nothing: one selection a row
    assert len(ref.route_near(x, w_r, cfg, (7,), 0.0, n_rows=3)[2]) == 0
    # rows past `n_rows` (padding) start nothing
    assert len(ref.route_near(x, w_r, cfg, (7,), 0.05, n_rows=0)[2]) == 0


# -- a bias-corrected selection, no shared expert (ISSUE 39) -----------------

BIAS = jnp.asarray(np.random.default_rng(5).normal(size=E) * 0.05, jnp.float32)


def test_the_bias_chooses_and_the_unbiased_score_weighs():
    k = 4
    held = tuple(range(E))
    w, rows = moe.route(X, W_R, held, k, 1.0, bias=BIAS, norm_eps=1e-6)
    w = np.asarray(w)[:, :, 0].T                       # [N, E]
    sigma = 1.0 / (1.0 + np.exp(-(np.asarray(X) @ np.asarray(W_R))))
    moved = 0
    for n in range(N):
        top = np.argsort(-(sigma[n] + np.asarray(BIAS)))[:k]
        plain = np.argsort(-sigma[n])[:k]
        moved += set(top) != set(plain)
        want = np.zeros(E)
        want[top] = sigma[n, top] / (sigma[n, top].sum() + 1e-6)
        np.testing.assert_allclose(w[n], want, atol=1e-6)
    # the bias moves the selection of a measurable share of rows, and where
    # it does the weights are still the scores': nothing of it is summed in
    assert 0 < moved < N
    assert np.asarray(rows).sum() == N * k
    # without it: the plain router, to the bit, whatever norm_eps is named
    a = moe.route(X, W_R, held, k, 1.0)
    b = moe.route(X, W_R, held, k, 1.0, bias=None, norm_eps=0.0)
    np.testing.assert_array_equal(np.asarray(a[0]), np.asarray(b[0]))


def test_four_shares_of_eight_and_no_shared_expert_add_up_to_the_uncut_layer():
    """Four chips that hold eight of the 32 experts each: their parts of the
    routed sum under the biased top-4 are the reference's layer with every
    expert held and nothing shared."""
    from lfm2_tiny import ref as lfm2_ref
    p = {"m_router.w_0": W_R, "m_router_bias": BIAS, "m_experts_gate": GATE,
         "m_experts_up": UP, "m_experts_down": DOWN}
    cfg = dict(num_experts_per_tok=4, norm_topk_prob=True,
               routed_scaling_factor=1, use_expert_bias=True)
    with jax.default_matmul_precision("highest"):
        ids, w, *_ = lfm2_ref.route_near(X, p, "m", cfg, 0.0, 0)
        want = lfm2_ref.experts(p, "m", X, ids, w, E, jnp.zeros((N, D)))
        total = jnp.zeros((N, D))
        for share in range(4):
            held = tuple(range(share * 8, share * 8 + 8))
            hw, hrows = moe.route(X, W_R, held, 4, 1.0, bias=BIAS,
                                  norm_eps=1e-6)
            sl = slice(held[0], held[-1] + 1)
            total = total + moe.experts(X, hw, hrows, GATE[sl], UP[sl],
                                        DOWN[sl], backend="xla")
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=2e-5)


def test_a_spec_without_a_shared_expert_and_with_the_bias():
    spec = MoESpec(n_routed=32, top_k=4, d_expert=64, held=tuple(range(32)),
                   n_shared=0, first_dense=2, topk_method="bias",
                   norm_eps=1e-6)
    assert spec.n_shared == 0 and spec.topk_method == "bias"
    with pytest.raises(NotImplementedError, match="group_limited_greedy"):
        MoESpec(n_routed=32, top_k=4, d_expert=64, held=(0,),
                topk_method="group_limited_greedy")
