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
    with pytest.raises(NotImplementedError, match="tanh"):
        MoESpec(n_routed=E, top_k=K, d_expert=F, held=(0,), scoring="tanh")
    # softmax scores are a training graph's (PR 50); a serving tick refuses
    # them by name (tests/test_mellum_train.py)
    assert MoESpec(n_routed=E, top_k=K, d_expert=F, held=(0,),
                   scoring="softmax").scoring == "softmax"
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


# -- latent experts of two matrices (relu^2, no gate) ------------------------

Z, FL, FS = 128, 384, 256        # latent width, expert width (3 x 128), shared
L_DN = jnp.asarray(RNG.normal(size=(D, Z)) * D ** -0.5, jnp.float32)
L_UP = jnp.asarray(RNG.normal(size=(Z, D)) * Z ** -0.5, jnp.float32)
UP2 = jnp.asarray(RNG.normal(size=(E, Z, FL)) * Z ** -0.5, jnp.float32)
DOWN2 = jnp.asarray(RNG.normal(size=(E, FL, Z)) * FL ** -0.5, jnp.float32)
S_UP = jnp.asarray(RNG.normal(size=(D, FS)) * D ** -0.5, jnp.float32)
S_DOWN = jnp.asarray(RNG.normal(size=(FS, D)) * FS ** -0.5, jnp.float32)
BIAS2 = jnp.asarray(RNG.normal(size=(E,)) * 0.05, jnp.float32)


def test_four_latent_shares_and_one_shared_expert_add_up_to_the_uncut_layer():
    """Four expert ranks that hold eight of the 32 latent experts each: their
    parts of the routed sum (biased top-6, scaling 5) go through the shared
    up-projection, which is linear and has no bias, so the parts add up; with
    the shared expert counted once they are the reference's layer with every
    expert held (benchmark/models/nemotron_h_reference.py)."""
    from nemotron_h_tiny import ref as nemo_ref
    p = {"m_router.w_0": W_R, "m_router_bias": BIAS2,
         "m_latent_down.w_0": L_DN, "m_latent_up.w_0": L_UP,
         "m_experts_up": UP2, "m_experts_down": DOWN2,
         "m_shared_up.w_0": S_UP, "m_shared_down.w_0": S_DOWN}
    cfg = dict(num_experts_per_tok=6, n_routed_experts=E,
               routed_scaling_factor=5)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(nemo_ref.moe(X, p, "m", cfg))
        z = X @ L_DN
        total = jnp.square(jax.nn.relu(X @ S_UP)) @ S_DOWN
        touched = 0
        for share in range(4):
            held = tuple(range(share * 8, share * 8 + 8))
            w, rows = moe.route(X, W_R, held, 6, 5.0, bias=BIAS2,
                                norm_eps=1e-20)
            sl = slice(held[0], held[-1] + 1)
            part = moe.experts(z, w, rows, None, UP2[sl], DOWN2[sl],
                               backend="xla")
            total = total + part @ L_UP
            touched += int(np.asarray(rows).sum())
            # one rank alone is the reference given that rank's experts
            if share == 0:
                alone = nemo_ref.moe(X, dict(p), "m",
                                     dict(cfg, n_routed_experts=8))
                shared = jnp.square(jax.nn.relu(X @ S_UP)) @ S_DOWN
                np.testing.assert_allclose(
                    np.asarray(part @ L_UP + shared), np.asarray(alone),
                    atol=5e-5)
    assert touched == N * 6
    np.testing.assert_allclose(np.asarray(total), want, atol=1e-4)


@pytest.mark.parametrize("held", [(0, 1, 2, 3), (5, 11, 17, 29)])
def test_two_matrix_kernel_equals_composite_and_skips_the_unselected(held):
    live = jnp.asarray((np.arange(N) < 6).astype(np.float32))
    w, rows = moe.route(X, W_R, held, 2, 5.0, live=live, bias=BIAS2,
                        norm_eps=1e-20)
    assert (np.asarray(rows) == 0).any() and (np.asarray(rows) > 0).any()
    idx = jnp.asarray(held)
    z = X @ L_DN
    a = np.asarray(moe.experts(z, w, rows, None, UP2[idx], DOWN2[idx],
                               backend="xla"))
    b = np.asarray(moe.experts(z, w, rows, None, UP2[idx], DOWN2[idx],
                               backend="pallas_interpret"))
    np.testing.assert_allclose(a, b, atol=2e-5)
    assert not a[6:].any() and np.abs(a[:6]).max() > 0.01
    dead = np.asarray(rows) == 0
    poison = jnp.where(jnp.asarray(dead)[:, None, None], jnp.nan, UP2[idx])
    c = np.asarray(moe.experts(z, w, rows, None, poison, DOWN2[idx],
                               backend="pallas_interpret"))
    np.testing.assert_allclose(c, b, atol=1e-6)


def test_the_two_matrix_kernels_tile_comes_from_the_shape():
    def tile(rows, d_model, d_expert, itemsize):
        return moe.experts_tile(rows, d_model, d_expert, itemsize, matrices=2)
    # the largest divisor in whole 128-lane rows of at most 512 columns
    # (PR 51: a whole expert of 2,688 a step cost the call's first fetch)
    assert tile(64, 1024, 2688, 2) == 384
    assert tile(64, 128, 384, 4) == 384
    # a wider row: the largest whose two tiles fit a step's bytes
    assert tile(64, 8192, 2688, 2) == 384
    # a width that is no multiple of 128 has none: the composite
    assert tile(64, 32, 48, 4) == 0
    assert moe.experts_lowering(64, 1024, 2688, "pallas",
                                tile(64, 1024, 2688, 2)) == moe.KERNEL
    assert moe.experts_lowering(64, 32, 48, "pallas", 0) == moe.COMPOSITE
    # the gated kernel takes its tile from the shape too (PR 51)
    assert moe.experts_lowering(64, 1024, 2688, "pallas") == moe.KERNEL


# the four routed serving cells: (held, d_model, d_expert, matrices, decode
# rows, mixed rows) -> the tile of a decode tick, of a mixed tick
CELL_SHAPES = {
    "assistant": ((32, 2048, 1792, 3, 64, 320), (256, 256)),
    "bursts": ((128, 1024, 2688, 2, 64, 320), (384, 384)),
    "long_sessions": ((16, 6144, 2048, 3, 32, 288), (512, 256)),
    "document": ((12, 7168, 2048, 3, 32, 288), (256, 256)),
}


@pytest.mark.parametrize("cell", sorted(CELL_SHAPES))
def test_the_tile_is_a_function_of_the_shape_inside_the_vmem_budget(cell):
    (_, d_model, d_expert, matrices, decode, mixed), want = CELL_SHAPES[cell]
    got = tuple(moe.experts_tile(n, d_model, d_expert, 2, matrices)
                for n in (decode, mixed))
    assert got == want          # what Step 0 and the cells timed as best
    for n, tile in zip((decode, mixed), got):
        assert d_expert % tile == 0 and tile % 128 == 0
        assert moe.experts_lowering(n, d_model, d_expert, "pallas",
                                    tile) == moe.KERNEL
    # under 320 rows (the widest mixed tick) either tile leaves the call's
    # VMEM limit a quarter of its room
    for tile in got:
        assert moe.experts_vmem_bytes(320, d_model, tile, 2, matrices) \
            <= 0.75 * moe._VMEM_LIMIT
    # the rule sees the shape and nothing else: float32 stacks of the same
    # widths take half the columns or fewer
    assert moe.experts_tile(decode, d_model, d_expert, 4, matrices) \
        <= got[0]


def test_the_gated_product_keeps_its_kernel_and_its_name():
    S = jax.ShapeDtypeStruct
    args = [S((32, 128), jnp.bfloat16), S((4, 32, 1), jnp.float32),
            S((4,), jnp.int32), S((4, 128, 256), jnp.bfloat16),
            S((4, 128, 256), jnp.bfloat16), S((4, 256, 128), jnp.bfloat16)]
    gated = jax.jit(lambda x, w, n, g, u, d: moe.experts(
        x, w, n, g, u, d, backend="pallas")).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text(debug_info=True)
    assert "moe_experts" in gated and "latent_experts" not in gated
    two = jax.jit(lambda x, w, n, u, d: moe.experts(
        x, w, n, None, u, d, backend="pallas")).trace(
        *args[:3], *args[4:]).lower(
        lowering_platforms=("tpu",)).as_text(debug_info=True)
    assert "latent_experts" in two and two.count("tpu_custom_call") == 1


# -- the packed walk over the touched experts (PR 51) -------------------------

H16 = 16                                    # held experts of the walk's tests


def _touched_set(name):
    on = np.zeros(H16, bool)
    if name == "all":
        on[:] = True
    elif name == "first":
        on[0] = True
    elif name == "last":
        on[-1] = True
    elif name == "alternating":
        on[::2] = True
    elif name == "middle_run":
        on[5:11] = True
    elif name.startswith("random"):
        share = int(name[len("random"):]) / 100
        on[np.random.default_rng(int(share * 100)).choice(
            H16, max(1, round(share * H16)), replace=False)] = True
    else:
        assert name == "none"
    return on


SETS = ("none", "all", "first", "last", "alternating", "middle_run",
        "random15", "random75")


@pytest.mark.parametrize("touched", SETS)
def test_the_walks_tables(touched):
    on = _touched_set(touched)
    order, count = (np.asarray(a) for a in moe.packed_walk(
        jnp.asarray(on * 3, jnp.int32)))
    k = int(on.sum())
    assert count.tolist() == [k] and order.dtype == np.int32
    # the touched ids ascending and packed to the front ...
    assert order[:k].tolist() == np.flatnonzero(on).tolist()
    # ... and every step past them holds the LAST touched expert (expert 0
    # where none is touched): its block is the one the step before used
    assert (order[k:] == (np.flatnonzero(on)[-1] if k else 0)).all()


@pytest.mark.parametrize("rows", [16, 48])          # a decode, a mixed tick
@pytest.mark.parametrize("touched", SETS)
@pytest.mark.parametrize("gated", [True, False], ids=["gated", "two_matrix"])
def test_the_walk_equals_the_composite(gated, touched, rows):
    """Both kernels, interpreted, two tiles an expert, against the composite
    over every held expert: what no row selected is never read (its weights
    are NaN here), what a row selected is never skipped."""
    on = _touched_set(touched)
    rng = np.random.default_rng(rows + len(touched))
    w = jnp.asarray(on[:, None, None] * rng.uniform(0.1, 1.0, (H16, rows, 1))
                    * (rng.uniform(size=(H16, rows, 1)) < 0.5), jnp.float32)
    # an expert is touched where the router's count says so, even if the
    # weights it hands over round to nothing
    counts = jnp.asarray(on * 2, jnp.int32)
    x = X[:rows]
    clean = (GATE[:H16], UP[:H16], DOWN[:H16]) if gated else \
        (UP[:H16], DOWN[:H16])
    want = np.asarray(
        moe._experts_composite(x, w, *clean) if gated
        else moe._relu2_composite(x, w, *clean))
    poisoned = tuple(jnp.where(jnp.asarray(on)[:, None, None], m, jnp.nan)
                     for m in clean)
    got = np.asarray(moe._walk_pallas(x, w, counts, poisoned, tile=128,
                                      interpret=True))
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert got.any() == bool(on.any())


def test_a_latent_spec_names_its_activation_and_widths():
    spec = MoESpec(n_routed=512, top_k=22, d_expert=2688,
                   held=tuple(range(128)), n_shared=1, first_dense=0,
                   scaling=5.0, topk_method="bias", norm_eps=1e-20,
                   activation="relu2", latent=1024, d_shared=5376)
    assert spec.shared_width == 5376 and spec.latent == 1024
    assert MoESpec(n_routed=8, top_k=2, d_expert=64, held=(0,),
                   n_shared=2).shared_width == 128
    with pytest.raises(NotImplementedError, match="swiglu"):
        MoESpec(n_routed=8, top_k=2, d_expert=64, held=(0,),
                activation="swiglu")
