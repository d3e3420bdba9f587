"""Group-limited routing (ISSUE 59: DeepSeek-V3's `noaux_tc` with `n_group`
and `topk_group`): `fusion/moe.py route` with `groups` against the plain
reference's `select` on hand-made scores (a winner in a losing group, ties,
the bias selecting and not weighing), and the deployment's arithmetic: the
four expert ranks' partial sums, the shared expert counted once, add up to
the uncut reference's layer."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.models import ling_reference as ref
from paddle_tpu.fusion import moe
from paddle_tpu.models.decoder_spec import MoESpec

F32 = jnp.float32
CFG = dict(n_group=4, topk_group=2, num_experts_per_tok=3)


def _logit(s):
    s = np.asarray(s, np.float64)
    return np.log(s / (1.0 - s))


def _route(scores, bias, held, k=3, groups=(4, 2), scaling=2.5):
    """`route` over rows whose sigmoid scores are `scores` [N, E]: the
    router is the identity on one-hot-free rows (x = logits, w = I)."""
    x = jnp.asarray(_logit(scores), F32)
    w, rows = moe.route(x, jnp.eye(x.shape[1], dtype=F32), held, k, scaling,
                        True, None, jnp.asarray(bias, F32), 1e-20, groups)
    return np.asarray(w)[:, :, 0].T, np.asarray(rows)      # [N, held]


def test_a_winner_in_a_losing_group_is_not_selected():
    """Expert 12 has the largest score of all, but its group's two best sum
    to less than two other groups': the group is dropped and 12 with it."""
    s = np.full((1, 16), 0.10)
    s[0, 12] = 0.95                         # group 3: 0.95 + 0.10 = 1.05
    s[0, [0, 1]] = 0.60, 0.55               # group 0: 1.15
    s[0, [4, 5]] = 0.70, 0.50               # group 1: 1.20
    w, rows = _route(s, np.zeros(16), tuple(range(16)))
    picked = np.flatnonzero(w[0])
    assert picked.tolist() == [0, 1, 4]
    np.testing.assert_allclose(w[0, picked],
                               2.5 * s[0, picked] / s[0, picked].sum(),
                               rtol=1e-5)
    assert rows.sum() == 3
    # without the group step it is the first pick
    w_all, _ = _route(s, np.zeros(16), tuple(range(16)), groups=None)
    assert w_all[0, 12] > 0
    idx = ref.select(jnp.asarray(s, F32), CFG)
    assert sorted(np.asarray(idx)[0].tolist()) == [0, 1, 4]


def test_the_bias_selects_and_does_not_weigh():
    """A bias lifts group 2 over group 0 and expert 9 into the top-3; the
    weights are the UNBIASED scores of the selected."""
    s = np.full((1, 16), 0.10)
    s[0, [0, 1]] = 0.60, 0.55
    s[0, [4, 5]] = 0.70, 0.50
    s[0, [8, 9]] = 0.40, 0.30
    bias = np.zeros(16)
    bias[[8, 9]] = 0.5                      # group 2: 0.90 + 0.80 = 1.70
    w, _ = _route(s, bias, tuple(range(16)))
    picked = np.flatnonzero(w[0])
    assert picked.tolist() == [4, 8, 9]     # keys 0.70, 0.90, 0.80
    np.testing.assert_allclose(
        w[0, picked], 2.5 * s[0, picked] / s[0, picked].sum(), rtol=1e-5)
    idx = ref.select(jnp.asarray(s + bias, F32), CFG)
    assert sorted(np.asarray(idx)[0].tolist()) == [4, 8, 9]


def test_ties_go_to_the_lower_index_as_in_the_reference():
    """Equal groups and equal experts: `lax.top_k` keeps the lower index, in
    the program and in the reference alike, so both pick the same."""
    rng = np.random.default_rng(0)
    s = np.round(rng.uniform(0.1, 0.9, (64, 16)), 1)       # many ties
    bias = np.round(rng.uniform(-0.2, 0.2, 16), 1)
    w, _ = _route(s, bias, tuple(range(16)))
    # the reference on the scores as float32 holds them (equal scores stay
    # equal through the logit and back; equal SUMS of unequal parts do not)
    keys = jax.nn.sigmoid(jnp.asarray(_logit(s), F32)) + jnp.asarray(bias, F32)
    idx = np.asarray(ref.select(keys, CFG))
    for r in range(64):
        assert np.flatnonzero(w[r]).tolist() == sorted(idx[r].tolist())


def test_only_the_held_part_leaves_the_op_and_dead_rows_select_nothing():
    rng = np.random.default_rng(1)
    s = rng.uniform(0.05, 0.95, (8, 16))
    bias = rng.uniform(-0.1, 0.1, 16)
    full, _ = _route(s, bias, tuple(range(16)))
    x = jnp.asarray(_logit(s), F32)
    live = jnp.asarray([1, 1, 0, 1, 0, 1, 1, 1], F32)
    w, rows = moe.route(x, jnp.eye(16, dtype=F32), (4, 5, 6, 7), 3, 2.5,
                        True, live, jnp.asarray(bias, F32), 1e-20, (4, 2))
    w = np.asarray(w)[:, :, 0].T
    alive = np.asarray(live) > 0
    np.testing.assert_allclose(w[alive], full[alive][:, 4:8], rtol=1e-6)
    assert not w[~alive].any()
    assert rows.tolist() == (full[alive][:, 4:8] > 0).sum(0).tolist()


def test_the_spec_refuses_groups_no_router_builds():
    ok = dict(n_routed=16, top_k=3, d_expert=8, held=tuple(range(8)),
              topk_method="group_bias", n_group=4, topk_group=2)
    MoESpec(**ok)
    with pytest.raises(ValueError, match="groups of equal size"):
        MoESpec(**dict(ok, n_group=3))
    with pytest.raises(ValueError, match="cut a group"):
        MoESpec(**dict(ok, held=tuple(range(6))))
    with pytest.raises(ValueError, match="cannot give a top-"):
        MoESpec(**dict(ok, topk_group=1, top_k=5))
    with pytest.raises(ValueError, match="group_bias"):
        MoESpec(**dict(ok, topk_method="bias"))
    with pytest.raises(NotImplementedError, match="topk_method"):
        MoESpec(**dict(ok, topk_method="noaux"))


def test_four_ranks_partial_sums_add_up_to_the_uncut_layer():
    """The deployment: each of four ranks holds one whole group of four of
    the 16 experts, the router and the shared expert. Every rank's routed
    part (the shared expert left out of three of them) sums to the layer a
    chip with all 16 experts computes."""
    rng = np.random.default_rng(2)
    H, Fe, E, n = 32, 16, 16, 24
    norm = lambda *shape: jnp.asarray(  # noqa: E731
        rng.standard_normal(shape) * shape[-2] ** -0.5, F32)
    name = "l1_moe"
    p = {f"{name}_router.w_0": norm(H, E),
         f"{name}_router_bias": jnp.asarray(rng.uniform(-0.2, 0.2, E), F32),
         f"{name}_experts_gate": norm(E, H, Fe),
         f"{name}_experts_up": norm(E, H, Fe),
         f"{name}_experts_down": norm(E, Fe, H)}
    for m, shape in (("gate", (H, Fe)), ("up", (H, Fe)), ("down", (Fe, H))):
        p[f"{name}_shared_{m}.w_0"] = norm(*shape)
    u = jnp.asarray(rng.standard_normal((n, H)), F32)
    cfg = dict(CFG, num_experts=E, routed_scaling_factor=2.5)
    with jax.default_matmul_precision("highest"):
        whole = ref.moe(u, p, name, cfg)
        shared = ref.gated(u, p, name + "_shared")
        total = jnp.zeros_like(whole)
        for rank in range(4):
            held = range(4 * rank, 4 * rank + 4)
            part = dict(p, **{f"{name}_experts_{m}":
                              p[f"{name}_experts_{m}"][held.start:held.stop]
                              for m in ("gate", "up", "down")})
            # a rank's layer is its routed part + the shared expert: counted
            # once, on rank 0
            total += ref.moe(u, part, name, cfg, held) \
                - (shared if rank else 0.0)
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               atol=1e-5)
    # and the program's router gives each rank the weights the reference's
    # part used
    spec_w, _ = moe.route(u, p[f"{name}_router.w_0"], tuple(range(4, 8)), 3,
                          2.5, True, None, p[f"{name}_router_bias"], 1e-20,
                          (4, 2))
    s, keys = ref.scores_and_keys(u, p, name)
    idx = np.asarray(ref.select(keys, cfg))
    for r in range(n):
        chosen = set(idx[r].tolist())
        for j, e in enumerate(range(4, 8)):
            assert (float(spec_w[j, r, 0]) > 0) == (e in chosen)
