"""K-EXAONE's block through PagedKVEngine (ISSUE 45): prefill through the
lanes and decode through TWO pools (the full layers' and the sliding-window
layers', whose blocks go back to the pool as they slide out of the window), a
prefix hit that brings the span's window TAIL with it and is cut where the
tail is gone, against the plain reference's full forward
(benchmark/models/kexaone_reference.py: the window as a mask, K and V
uncached, experts looped). In float32 with exact matmuls the two agree to
rounding, so the tolerance that accepts the program refuses every planted
fault."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tiny_engines as E
from kexaone_tiny import TINY as T, kex, ref
from paddle_tpu import serving
from paddle_tpu.core import flags
from paddle_tpu.core.enforce import InvalidArgumentError
from paddle_tpu.fusion import moe
from paddle_tpu.models.decoder_spec import DecoderSpec
from paddle_tpu.observability import tracing

TOL = 1e-4          # in standard deviations of the reference's logits
TURNS = (5, 11, 3, 12)
HEAD = 24           # a session's context: six blocks of 4, three windows of 8
NEW = 26            # an answer runs past three windows more


def _prompts(seed=1, head=HEAD):
    return E.prompts(TURNS, head, seed)


exact_matmuls = E.exact_matmuls_fixture(T)
# the context alone first (as the benchmark's warm-up sends it), then four
# turns behind it
exact = E.exact_fixture(T, TURNS, HEAD, new=NEW)


def test_chunks_then_decode_past_three_windows_agree_with_the_full_forward(
        exact):
    cfg, params, eng, runs = exact
    assert eng.prefill == "chunked" and eng.chunk_tokens == 16
    spec = eng.model
    assert spec.window == 8 and spec.window_layers == (0, 1, 2, 4)
    assert spec.full_layers == (3,) and spec.d_head * spec.num_heads == 128
    assert [spec.rotates(i) for i in range(5)] == [True] * 3 + [False, True]
    # the warm-up prefilled the context; every turn started from all of it
    assert [r.shared_len for r, _ in runs] == [0, 24, 24, 24, 24]
    assert T.worst(cfg, params, runs) < TOL
    w = eng.stats()["window_pool"]
    assert w["tail_lookups"] == 4 == w["tail_hits"] and not w["hits_truncated"]
    assert w["blocks_released"] > 4 * (HEAD + NEW - 8) // 4 - 8
    assert w["request_bound"] == 7 and max(
        np.nonzero(w["blocks_held"])[0]) <= 4
    assert w["block_bytes"] == spec.window_row_bytes() * 4
    assert eng.block_bytes == spec.cache_row_bytes() * 4
    # five layers: one holds every position, four the window pool's
    assert spec.window_row_bytes() == 4 * spec.cache_row_bytes()


def test_a_prefix_hit_equals_its_self_prefilled_twin(exact):
    E.a_prefix_hit_equals_its_twin(T, exact, _prompts(), new=NEW)


def _holders(eng, reqs):
    """window-pool block -> how many hold it: live tables and index tails."""
    held = {}
    for r in reqs:
        if r.table is not None and r.table.window_blocks is not None:
            for b in r.table.window_blocks:
                if b:
                    held[b] = held.get(b, 0) + 1
    for node in eng.pager._tails.values():
        held[node.wblock] = held.get(node.wblock, 0) + 1
    return held


def test_a_request_holds_a_bounded_window_and_both_pools_balance_every_tick(
        exact_matmuls):
    cfg = exact_matmuls
    eng, _ = T.engine(cfg, 7)
    bound = eng.pager.window_bound(eng.chunk_tokens)
    assert bound == -(-(8 + 16) // 4) + 1
    prompts = _prompts(seed=2, head=36)
    reqs = [eng.submit(p, 16) for p in prompts]
    most = 0
    while eng.n_active or eng.n_pending:
        eng.step()
        eng.pager.pool.check()
        eng.pager.check_window()
        live = [r for r in reqs if r.table is not None]
        for r in live:
            most = max(most, r.table.window_held)
            assert r.table.window_held <= bound
        # every refcount of the window pool is its holders, exactly
        held = _holders(eng, live)
        wpool = eng.pager.wpool
        assert wpool.n_used == len(held)
        assert all(wpool.refcount(b) == n for b, n in held.items())
    assert 3 <= most <= bound
    # nothing live: what is left is the index's tails, three a span
    assert eng.pager.wpool.n_used == len(eng.pager._tails)
    assert all(r.done and r.error is None and len(r.tokens) == 16
               for r in reqs)


def test_a_hit_is_cut_where_the_spans_window_tail_is_gone(exact_matmuls):
    """A context of six blocks leaves three tails (blocks 3, 4, 5). With the
    tail of block 4 dropped a turn that matches all six is handed four: the
    deepest span whose last window - 1 positions are resident (blocks 2 and 3
    of a span of four: block 3 holds one, and a window of 8 over blocks of 4
    reads two blocks back... so the span falls to where every block it reads
    is there)."""
    cfg = exact_matmuls
    eng, params = T.engine(cfg, 7, scored=True)
    prompts = _prompts(seed=3)
    runs = [E.emitted_logits(eng, prompts[0], 2)]
    pager = eng.pager
    assert sorted(pager.stats()["window"].items())
    assert len(pager._tails) == 3
    node = pager.index.node_of(prompts[0], 4)
    assert node.wblock is not None
    pager._drop_tail(node)
    runs.append(E.emitted_logits(eng, prompts[1], 12))
    req = runs[-1][0]
    # spans of 6 and 5 blocks read block 4; a span of 4 reads blocks 2 and 3,
    # and block 2 was never a tail: nothing is handed out, never a wrong span
    assert req.shared_len == 0 and pager.hits_truncated == 1
    assert pager.window_tail_lookups == 1 and pager.window_tail_hits == 0
    # the request prefilled the context again and gave the node its tail back
    assert pager.index.node_of(prompts[0], 4).wblock is not None
    runs.append(E.emitted_logits(eng, prompts[2], 12))
    assert runs[-1][0].shared_len == 24 and pager.window_tail_hits == 1
    assert T.worst(cfg, params, runs) < TOL
    pager.pool.check()
    pager.check_window()


def test_tails_are_evicted_under_window_pool_pressure_and_with_their_nodes(
        exact_matmuls):
    cfg = exact_matmuls
    # the smallest window pool the engine takes: every slot's bound + null
    eng, params = T.engine(cfg, 7, scored=True, n_slots=2,
                           n_window_blocks=2 * 7 + 1)
    rng = np.random.default_rng(6)
    heads = [rng.integers(0, 97, HEAD).tolist() for _ in range(6)]
    runs = [E.emitted_logits(eng, h, 2) for h in heads]
    pager = eng.pager
    # six spans' tails are 18 blocks, the pool has 14: the oldest went alone
    assert pager.window_tail_evictions >= 4 and len(pager._tails) <= 14
    assert pager.index.n_cached == 36           # ... and every node stayed
    turn = rng.integers(0, 97, 5).tolist()
    late, _ = runs.append(E.emitted_logits(eng, heads[-1] + turn, 10)) \
        or runs[-1]
    early, _ = runs.append(E.emitted_logits(eng, heads[0] + turn, 10)) \
        or runs[-1]
    assert late.shared_len == 24 and early.shared_len == 0
    assert T.worst(cfg, params, runs) < TOL
    # under FULL-pool pressure a node goes leaf first and takes its tail
    # along: with every node gone no tail is left, and neither pool holds one
    assert pager._tails and pager.index.evict_all(pager.pool) == 38
    assert not pager._tails
    assert pager.wpool.n_used == 0 == pager.pool.n_used
    pager.pool.check()
    pager.check_window()
    with pytest.raises(InvalidArgumentError, match="n_window_blocks"):
        T.engine(cfg, 7, n_slots=2, n_window_blocks=2 * 7)


@pytest.mark.parametrize("fault", ["window_off_by_one", "rope_on_full",
                                   "qk_norm_dropped", "window_ignored"])
def test_a_planted_fault_is_refused(exact, fault):
    """The reference with one fault planted: from the comparison's side the
    program is then the one that lacks it."""
    cfg, params, eng, runs = exact
    req, got = runs[1]
    with kex.planted(fault, cfg, None) as faulty:
        err = T.logit_error(faulty, params, req, got)
    assert err > 30 * TOL, (fault, err)


def test_eight_shares_and_one_shared_expert_add_up_to_the_uncut_layer():
    """THE SHARE TEST. Eight ranks hold two of the 16 experts each and compute
    their part of a routed layer's sum over the same rows (sigmoid scores,
    the top-4 of all 16, normalised, x 2.5); the parts, with the shared
    expert counted once, are the reference's layer with every expert held
    (`axk1_reference.moe`, which kexaone_reference.py runs)."""
    n, d, f, e, k = 48, 64, 48, 16, 4
    rng = np.random.default_rng(45)
    x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    w_r = jnp.asarray(rng.normal(size=(d, e)) * d ** -0.5, jnp.float32)
    gate, up = (jnp.asarray(rng.normal(size=(e, d, f)) * d ** -0.5,
                            jnp.float32) for _ in range(2))
    down = jnp.asarray(rng.normal(size=(e, f, d)) * f ** -0.5, jnp.float32)
    shared_w = [jnp.asarray(rng.normal(size=s) * s[0] ** -0.5, jnp.float32)
                for s in ((d, f), (d, f), (f, d))]
    p = {"m_router.w_0": w_r, "m_experts_gate": gate, "m_experts_up": up,
         "m_experts_down": down}
    p.update({f"m_shared_{nm}.w_0": w
              for nm, w in zip(("gate", "up", "down"), shared_w)})
    cfg = dict(num_experts_per_tok=k, norm_topk_prob=True,
               routed_scaling_factor=2.5, topk_method="none")
    blocks = ref.blocks
    with jax.default_matmul_precision("highest"):
        uncut = np.asarray(blocks.moe(p, "m", x, cfg, tuple(range(e))))
        shared = np.asarray(blocks.gated_ffn(x, *shared_w))
        total, touched = shared.copy(), 0
        for rank in range(8):
            held = kex.held_experts(dict(num_experts=2, expert_rank=rank))
            assert held == (2 * rank, 2 * rank + 1)
            sl = slice(held[0], held[-1] + 1)
            w, rows = moe.route(x, w_r, held, k, 2.5)
            part = np.asarray(moe.experts(x, w, rows, gate[sl], up[sl],
                                          down[sl]))
            total += part
            touched += int(np.asarray(rows).sum())
            if rank in (0, 5):      # the reference computes the same share
                pr = dict(p, m_experts_gate=gate[sl], m_experts_up=up[sl],
                          m_experts_down=down[sl])
                alone = np.asarray(blocks.moe(pr, "m", x, cfg, held))
                np.testing.assert_allclose(part, alone - shared, atol=2e-5)
    assert touched == n * k
    np.testing.assert_allclose(total, uncut, atol=5e-5)


def test_bfloat16_pools_and_weights_track_the_reference(exact_matmuls):
    """The stated precision at the tiny size: every emitted token within a
    fraction of a standard deviation of the reference's largest logit."""
    cfg = T.cfg()
    eng, params = T.engine(cfg, 7)
    prompts = _prompts(seed=4)
    reqs = [eng.submit(p, 20) for p in prompts]
    eng.run_until_idle()
    for req in reqs[1:]:
        assert req.shared_len in (0, 24)
        r = T.reference(cfg, params, req)
        gap = (r.max(-1) - r[np.arange(len(req.tokens)), req.tokens]) \
            / r.std(-1)
        assert np.quantile(gap, 0.9) < 0.35
    eng.pager.pool.check()
    eng.pager.check_window()


def test_tick_attrs_count_both_pools_and_the_window_part(exact_matmuls):
    cfg = exact_matmuls
    eng, _ = T.engine(cfg, 7)
    old = flags.get_flag("trace")
    flags.set_flag("trace", True)
    try:
        mark = tracing.mark()
        req = eng.submit(_prompts(seed=5)[1], 12)
        eng.run_until_idle()
        ticks = [s for s in tracing.spans_since(mark)
                 if s.name == "engine/tick"]
    finally:
        flags.set_flag("trace", old)
    decode = [s for s in ticks if not s.attrs["prefill"]]
    assert decode and all("window_blocks" in s.attrs for s in ticks)
    for s in decode:
        a = s.attrs
        pos = a["decode_rows"] - 1              # one live row
        assert a["window_rows"] == min(pos + 1, 8)
        assert a["window_blocks"] == pos // 4 - max(pos - 7, 0) // 4 + 1
        assert a["kv_blocks"] == pos // 4 + 1 + a["window_blocks"]
    admit = [s for s in tracing.spans_since(mark) if s.name == "engine/admit"]
    assert admit and "window_pool_used" in admit[0].attrs
    assert req.done and len(req.tokens) == 12


@pytest.mark.parametrize("option, value", [
    ("speculative", serving.SpeculativeConfig(gamma=2)
     if hasattr(serving, "SpeculativeConfig") else True),
    ("host_tier", serving.HostTierConfig(host_blocks=8)),
    ("kv_quant", True), ("topk_k", 4)])
def test_what_walks_one_table_is_refused_by_name(option, value):
    spec = kex.spec_of(T.cfg())
    with pytest.raises(InvalidArgumentError) as e:
        serving.PagedKVEngine(n_slots=2, max_len=64, block_size=4,
                              n_blocks=40, n_window_blocks=20, model=spec,
                              **{option: value})
    assert option in str(e.value) and "window table" in str(e.value)
    assert "sliding-window layers" in str(e.value)


def test_the_pager_refuses_fork_rollback_and_spill_over_a_window_table():
    pager = serving.kv_pager.KVPager(40, 4, window=8, n_window_blocks=20)
    table = pager.try_admit(list(range(9)), 16)
    for call in (lambda: pager.fork(table, 4, lambda s, d: None),
                 lambda: pager.rollback(table, 4, 8)):
        with pytest.raises(InvalidArgumentError, match="second table"):
            call()


def test_the_spec_names_what_it_cannot_build():
    good = kex.spec_of(T.cfg())
    assert good.attention_kinds == ("window",) * 3 + ("full", "window")
    with pytest.raises(ValueError, match="attention_kinds"):
        dataclasses.replace(good, attention_kinds=("window", "full"))
    with pytest.raises(ValueError, match="attention_kinds"):
        dataclasses.replace(good, attention_kinds=("local",) * 5)
    with pytest.raises(ValueError, match="window"):
        dataclasses.replace(good, window=0)
    with pytest.raises(ValueError, match="window"):
        dataclasses.replace(good, attention_kinds=("full",) * 5)
    for key, value in (("topk_method", "noaux_tc"), ("n_group", 8),
                       ("sliding_windows", [8] * 5),
                       ("mlp_layer_types", ["sparse"] * 5)):
        with pytest.raises(NotImplementedError):
            kex.spec_of(T.cfg(**{key: value}))
    # a spec without kinds of attention is what it was: every layer full,
    # rotated where it has a rope
    lfm = DecoderSpec.classic()
    assert lfm.window_layers == () and lfm.window_row_bytes() == 0
    assert lfm.full_layers == lfm.attention_layers


def test_the_q_and_k_norms_scales_are_seeded_where_the_configuration_says():
    """`qk_norm_init` (the committed configuration's `assumed.init`: at 1 a
    softmax over 16k keys is flat and nothing the full layer does reaches
    the logits): the q and k norms' scales alone, every other norm's 1."""
    scope = E.weights(kex, T.cfg(qk_norm_init=1.6, **T.F32), 3)
    for name in kex.param_names(T.cfg()):
        if name.endswith(".scale"):
            want = 1.6 if name.endswith(("_q_norm.scale", "_k_norm.scale")) \
                else 1.0
            assert np.allclose(np.asarray(scope.get(name)), want), name
    plain = E.weights(kex, T.cfg(**T.F32), 3)
    assert np.allclose(np.asarray(plain.get("l3_attn_q_norm.scale")), 1.0)
