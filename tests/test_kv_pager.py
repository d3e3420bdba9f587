"""Paged KV-cache subsystem: block pool + radix prefix index + paged
engine (ISSUE r20 tentpole).

Covers the paging contract end to end:
- BlockPool refcount/free-list invariants (null block reserved, alloc
  exhaustion, share/release, `check()` exactness);
- RadixPrefixIndex register/match/LRU-evict semantics incl. the
  missing-ancestor no-op and index-owned refs;
- `paged_cache_write` op parity against a per-row numpy reference;
- greedy decode identity: paged engine token-identical to the slot
  engine AND to a paged engine with prefix sharing disabled — shared
  prefixes change WHERE the KV bytes live, never the tokens;
- prefix-cache hits on a second wave over a warm index;
- CoW at the divergence block, pinned by a mutation test (writing the
  fork's copy must not alter the parent's physical block);
- beam search over forked tables: shared-vs-unshared identity;
- leak-free release/evict/reuse: after run_until_idle the only live
  blocks are the index's cached prefixes, and evict_all returns the
  pool to empty — twice;
- pool-capacity admission keeps requests PENDING (head-of-line) until
  blocks free, while submission-side limits raise with the block-table
  span named;
- census/watermark reconciliation: kv_cache category == pool bytes,
  used watermark == used blocks x per-block bytes;
- the paged tick's cache read is ONE `paged_decode_attention` op a layer:
  the Pallas kernel (interpret mode here) against the composite on ragged
  positions, idle slots, shared and permuted tables and garbage beyond
  the position; the in-place row write against a numpy reference; and the
  lowered tick computes nothing of pool shape.
"""

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers
from paddle_tpu.core.enforce import InvalidArgumentError
from paddle_tpu.serving import (BlockPool, ContinuousBatchingEngine,
                                KVPager, PagedKVEngine,
                                RadixPrefixIndex, paged_beam_search)

pytestmark = pytest.mark.quick

_DIMS = dict(vocab=50, max_len=16, d_model=32, d_inner=64, num_heads=4,
             num_layers=2)
_PREFIX = [2, 7, 1, 9, 4, 8, 5, 6]          # two full 4-token blocks


@pytest.fixture(scope="module")
def engines():
    """slot + paged + paged-without-sharing on ONE scope (same weights:
    identity tests compare token streams across all three)."""
    pt.reset_default_programs()
    pt.reset_global_scope()
    scope = pt.global_scope()
    slot = ContinuousBatchingEngine(n_slots=3, scope=scope, **_DIMS)
    paged = PagedKVEngine(n_slots=3, block_size=4, topk_k=3,
                          scope=scope, **_DIMS)
    unshared = PagedKVEngine(n_slots=3, block_size=4, topk_k=3,
                             prefix_sharing=False, scope=scope, **_DIMS)
    return slot, paged, unshared


def _gen(eng, prompts, max_new=6):
    reqs = [eng.submit(list(p), max_new=max_new) for p in prompts]
    eng.run_until_idle()
    assert all(r.done for r in reqs)
    return [list(r.tokens) for r in reqs]


class TestBlockPool:
    def test_refcount_free_list_invariants(self):
        p = BlockPool(5, 2)                  # 4 data blocks + null
        bs = [p.alloc() for _ in range(4)]
        assert 0 not in bs and None not in bs
        assert p.alloc() is None             # exhausted
        assert p.n_used == 4 and p.n_free == 0
        p.share(bs[0])
        assert p.refcount(bs[0]) == 2
        assert p.release(bs[0]) is False     # still held
        assert p.release(bs[0]) is True      # now freed
        for b in bs[1:]:
            assert p.release(b) is True
        p.check()
        assert p.n_used == 0 and p.n_free == 4
        b = p.alloc()                        # freed blocks are reusable
        assert b in bs
        p.release(b)

    def test_null_block_protected(self):
        p = BlockPool(3, 2)
        with pytest.raises(InvalidArgumentError):
            p.release(0)
        with pytest.raises(InvalidArgumentError):
            p.share(0)
        b = p.alloc()
        p.release(b)
        with pytest.raises(InvalidArgumentError):
            p.release(b)                     # double free


class TestRadixPrefixIndex:
    def test_register_match_evict(self):
        pool = BlockPool(10, 4)
        idx = RadixPrefixIndex(4)
        prompt = [1, 2, 3, 4, 5, 6, 7, 8]
        assert idx.match(prompt) == []
        b0, b1 = pool.alloc(), pool.alloc()
        assert idx.register(prompt, 0, b0, pool)
        assert idx.register(prompt, 1, b1, pool)
        m = idx.match(prompt + [9, 9])       # longer prompt, same lead
        assert [n.block for n in m] == [b0, b1]
        assert idx.match([1, 2, 3, 4, 0, 0, 0, 0]) \
            and idx.match([1, 2, 3, 4, 0, 0, 0, 0])[0].block == b0
        # drop the caller's refs: the index's own refs keep them live
        pool.release(b0)
        pool.release(b1)
        assert pool.n_used == 2
        assert idx.evict_one(pool)           # LRU leaf first: b1
        assert pool.n_used == 1 and pool.refcount(b0) == 1
        assert idx.evict_all(pool) == 1
        assert pool.n_used == 0
        pool.check()

    def test_missing_ancestor_is_noop(self):
        pool = BlockPool(10, 4)
        idx = RadixPrefixIndex(4)
        b = pool.alloc()
        assert not idx.register([1, 2, 3, 4, 5, 6, 7, 8], 1, b, pool)
        assert pool.refcount(b) == 1         # no index ref taken
        pool.release(b)
        pool.check()


def _run_write(pool, new, blocks, offs):
    n, nh, dh = new.shape
    with pt.program_guard(pt.Program(), pt.Program()):
        c = layers.data(name="pc", shape=list(pool.shape),
                        dtype="float32", append_batch_size=False)
        nv = layers.data(name="pn", shape=[n, nh, dh], dtype="float32",
                         append_batch_size=False)
        b = layers.data(name="pb", shape=[n], dtype="int64",
                        append_batch_size=False)
        o = layers.data(name="po", shape=[n], dtype="int64",
                        append_batch_size=False)
        out = layers.paged_cache_write(c, nv, b, o)
        return pt.Executor().run(
            feed={"pc": pool, "pn": new, "pb": blocks, "po": offs},
            fetch_list=[out])[0]


def _run_write_declared(pool, new, blocks, offs):
    """The write into the pool as the tick declares it, read back as
    [NB, nh, BS, dh]."""
    return _run_write(_declared(pool), new, blocks, offs).reshape(pool.shape)


class TestPagedCacheWriteOp:
    def test_parity_vs_numpy(self, rng):
        NB, nh, bs, dh = 6, 2, 4, 3
        pool = rng.randn(NB, nh, bs, dh).astype("float32")
        new = rng.randn(2, nh, dh).astype("float32")
        blocks = np.array([2, 5], "int64")
        offs = np.array([1, 3], "int64")
        got = _run_write(pool, new, blocks, offs)
        ref = pool.copy()
        for i in range(2):
            ref[blocks[i], :, offs[i], :] = new[i]
        np.testing.assert_allclose(got, ref, rtol=1e-6)

    @pytest.mark.parametrize("blocks,offs", [
        ([3, 1, 4], [0, 3, 2]),            # distinct blocks
        ([2, 2, 2], [0, 1, 3]),            # one block, three rows
        ([0, 5, 0, 0], [0, 2, 0, 0]),      # idle slots: duplicate null rows
        ([5, 0, 1, 0], [3, 1, 0, 1]),      # null rows between live ones
    ], ids=["distinct", "same_block", "null_dups", "null_mixed"])
    @pytest.mark.parametrize("dh", [8, 64], ids=["plain", "lane_dense"])
    def test_in_place_rows_vs_numpy(self, rng, blocks, offs, dh):
        """Rows only: every element outside the targeted rows is the
        pool's own, bit for bit; a live target holds its row; duplicate
        targets (only ever the null block) hold ONE of the rows sent.
        dh 64 packs two positions to a 128-lane row: the pool is declared
        [NB, nh, 2, 128] and the row lands at a lane offset."""
        NB, nh, bs = 6, 2, 4
        pool = rng.randn(NB, nh, bs, dh).astype("float32")
        new = rng.randn(len(blocks), nh, dh).astype("float32")
        assert (_declared(pool).shape[-1] == 128) == (dh == 64)
        got = _run_write_declared(pool, new, np.array(blocks, "int64"),
                                  np.array(offs, "int64"))
        touched = np.zeros((NB, bs), bool)
        for i, (b, o) in enumerate(zip(blocks, offs)):
            touched[b, o] = True
            sent = [new[j] for j in range(len(blocks))
                    if (blocks[j], offs[j]) == (b, o)]
            assert any(np.array_equal(got[b, :, o, :], r) for r in sent)
            if len(sent) == 1:
                np.testing.assert_array_equal(got[b, :, o, :], new[i])
        keep = ~touched[:, None, :, None] & np.ones_like(pool, bool)
        np.testing.assert_array_equal(got[keep], pool[keep])


# -- the read: kernel (Pallas interpret mode) against the composite ---------

_NBK, _NH, _BS, _DH, _NLB = 23, 4, 8, 16, 5     # span T = 40


def _attn_case(rng, name):
    """(q, k_pool, v_pool, btab, pos) for one named case. Tables map only
    what the positions need unless the case says otherwise; unmapped
    entries stay 0, the null block."""
    S = 4
    k_pool = rng.randn(_NBK, _NH, _BS, _DH).astype("float32")
    v_pool = rng.randn(_NBK, _NH, _BS, _DH).astype("float32")
    q = rng.randn(S, 1, _NH * _DH).astype("float32")
    T = _NLB * _BS
    pos = {"ragged": [0, _BS - 1, _BS, 2 * _BS + 3],
           "full_span": [T - 1, T - 1, _BS + 1, T - _BS],
           "idle_slots": [0, 2 * _BS + 5, 0, 0],
           "shared_prefix": [3 * _BS + 2, 3 * _BS + 6, 2 * _BS, 5],
           "permuted": [T - 1, 3 * _BS, _BS + 4, 2 * _BS - 1],
           "garbage": [0, _BS - 2, 2 * _BS + 1, 3 * _BS]}[name]
    pos = np.array(pos, "int64")
    ids = list(rng.permutation(np.arange(1, _NBK)))
    if name != "permuted":
        ids = sorted(ids)
    btab = np.zeros((S, _NLB), "int64")
    for s in range(S):
        if name == "idle_slots" and pos[s] == 0 and s != 1:
            continue                       # btab row all null, pos 0
        for j in range(pos[s] // _BS + 1):
            btab[s, j] = ids.pop()
    if name == "shared_prefix":
        btab[1, :3] = btab[0, :3]          # two slots, same physical blocks
        btab[2, :2] = btab[0, :2]
    return q, k_pool, v_pool, btab, pos


def _declared(pool):
    """A [NB, nh, BS, dh] pool as the tick declares it (lane-dense where a
    head's rows pack 128 lanes: the same values in the same order)."""
    from paddle_tpu.ops.tensor_ops import pool_block_shape
    nb, nh, bs, dh = pool.shape
    return pool.reshape((nb,) + pool_block_shape(nh, bs, dh))


def _attn(backend, q, k_pool, v_pool, btab, pos, declared=True):
    from paddle_tpu.fusion import paged_decode_attention
    if declared:
        k_pool, v_pool = _declared(k_pool), _declared(v_pool)
    return np.asarray(paged_decode_attention(
        q, k_pool, v_pool, btab, pos.astype("float32").reshape(-1, 1, 1),
        k_pool.shape[1], scale=(q.shape[-1] // k_pool.shape[1]) ** -0.5,
        backend=backend))


class TestPagedDecodeAttentionKernel:
    @pytest.mark.parametrize("case", ["ragged", "full_span", "idle_slots",
                                      "shared_prefix", "permuted"])
    def test_kernel_matches_composite(self, rng, case):
        args = _attn_case(rng, case)
        ref = _attn("xla", *args)
        got = _attn("pallas_interpret", *args)
        np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("backend", ["xla", "pallas_interpret"])
    def test_garbage_beyond_the_position_is_never_attended(self, rng,
                                                           backend):
        """Rows past `pos` in the last live block, every unmapped block
        and the null block may hold anything: the output is the same bit
        for bit."""
        q, k_pool, v_pool, btab, pos = _attn_case(rng, "garbage")
        clean = _attn(backend, q, k_pool, v_pool, btab, pos)
        k2, v2 = k_pool.copy(), v_pool.copy()
        live = set()
        for s in range(len(pos)):
            last = pos[s] // _BS
            live.update(int(b) for b in btab[s, :last + 1])
            for pool in (k2, v2):
                pool[btab[s, last], :, pos[s] % _BS + 1:, :] = 1e4
        for b in range(_NBK):
            if b not in live:
                k2[b], v2[b] = -3e4, 7e4
        np.testing.assert_array_equal(
            _attn(backend, q, k2, v2, btab, pos), clean)

    @pytest.mark.parametrize("nh,bs,dh", [(2, 16, 64), (4, 8, 32),
                                          (2, 8, 128)])
    def test_kernel_at_other_packings(self, rng, nh, bs, dh):
        """128 // dh positions to a 128-lane row: two (the benchmark's 16
        rows of 64), four, one."""
        S, NB, NLB = 3, 9, 3
        k_pool = rng.randn(NB, nh, bs, dh).astype("float32")
        v_pool = rng.randn(NB, nh, bs, dh).astype("float32")
        q = rng.randn(S, 1, nh * dh).astype("float32")
        btab = np.array([[3, 7, 1], [5, 0, 0], [8, 2, 0]], "int64")
        pos = np.array([3 * bs - 1, bs - 3, bs + 1], "int64")
        assert _declared(k_pool).shape[-1] == 128
        ref = _attn("xla", q, k_pool, v_pool, btab, pos, declared=False)
        np.testing.assert_allclose(
            _attn("xla", q, k_pool, v_pool, btab, pos), ref,
            rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(
            _attn("pallas_interpret", q, k_pool, v_pool, btab, pos), ref,
            rtol=1e-5, atol=1e-5)

    def test_matches_dense_attention(self, rng):
        """The composite against attention written out over each slot's
        own contiguous K/V: the table indirection and the position mask
        mean what they say."""
        q, k_pool, v_pool, btab, pos = _attn_case(rng, "permuted")
        got = _attn("xla", q, k_pool, v_pool, btab, pos)
        for s in range(len(pos)):
            n = pos[s] + 1
            k = np.concatenate([k_pool[b] for b in btab[s]], axis=1)[:, :n]
            v = np.concatenate([v_pool[b] for b in btab[s]], axis=1)[:, :n]
            qh = q[s, 0].reshape(_NH, _DH)
            sc = np.einsum("hd,htd->ht", qh, k) * _DH ** -0.5
            w = np.exp(sc - sc.max(-1, keepdims=True))
            w /= w.sum(-1, keepdims=True)
            ref = np.einsum("ht,htd->hd", w, v).reshape(-1)
            np.testing.assert_allclose(got[s, 0], ref, rtol=2e-5, atol=2e-5)


class TestPagedAttentionLowering:
    @pytest.mark.parametrize("backend,dtype,lanes,g,dh,quant,platform,want", [
        ("pallas", "float32", 128, 1, 64, False, "tpu", "kernel"),
        ("pallas", "float32", 128, 1, 128, False, "tpu", "kernel"),
        ("pallas_interpret", "float32", 128, 1, 16, False, "cpu", "kernel"),
        ("xla", "float32", 128, 1, 64, False, "cpu", "composite"),
        (None, "float32", 128, 1, 64, False, "cpu", "composite"),  # a CPU
        ("pallas", "float32", 128, 3, 64, False, "tpu", "composite"),  # G>1
        ("pallas", "int8", 128, 1, 64, True, "tpu", "composite"),  # kv_quant
        ("pallas", "float32", 8, 1, 8, False, "tpu", "composite"),   # tiny
        ("pallas", "float32", 256, 1, 256, False, "tpu", "composite"),
    ])
    def test_chosen_from_what_the_op_sees(self, backend, dtype, lanes, g, dh,
                                          quant, platform, want):
        from paddle_tpu.fusion import paged_attention_lowering
        assert paged_attention_lowering(dtype, lanes, g, dh, quant,
                                        backend=backend,
                                        platform=platform) == want

    def test_composite_on_a_tpu_is_an_error_not_a_fallback(self):
        """The backend a program gets here is "xla" (a CPU): on a TPU that
        selection, for a shape the kernel serves, raises. A caller that
        ASKS for the composite (the smoke's reference) gets it."""
        from paddle_tpu.fusion import paged_attention_lowering
        with pytest.raises(RuntimeError, match="not a fallback"):
            paged_attention_lowering("float32", 128, 1, 64, False,
                                     platform="tpu")
        assert paged_attention_lowering(
            "float32", 128, 1, 64, False, backend="xla",
            platform="tpu") == "composite"

    def test_engine_reports_its_lowering(self, engines):
        _, paged, _ = engines
        assert paged.stats()["paged_attention_lowering"] == "composite"
        text = paged.metrics_registry.expose()
        assert "ptpu_engine_paged_attention_kernel 0" in text

    def test_tick_span_counts_the_blocks_it_reads(self, engines):
        from paddle_tpu.observability import tracing
        _, _, unshared = engines                       # no prefix hits
        mark = tracing.mark()
        unshared.submit([7, 8, 9, 1, 2], max_new=3)    # fed 0..6: bs = 4
        unshared.submit([4, 5], max_new=1)             # fed 0..1
        unshared.run_until_idle()
        counts = [s.attrs["kv_blocks"] for s in tracing.spans_since(mark)
                  if s.name == "engine/tick"]
        # a slot at position f spans f // 4 + 1 blocks: two ticks with
        # both slots in their first block, two more of the long one
        # alone there, then three in its second
        assert counts == [2, 2, 1, 1, 2, 2, 2]


class TestDecodeIdentity:
    PROMPTS = [[7, 8, 9], [7, 8, 9], [1, 2, 3, 4, 5, 6],
               _PREFIX + [3], _PREFIX + [11, 12]]

    def test_paged_matches_slot_engine(self, engines):
        slot, paged, _ = engines
        assert _gen(paged, self.PROMPTS) == _gen(slot, self.PROMPTS)

    def test_shared_prefix_wave_token_identical_and_hits(self, engines):
        _, paged, unshared = engines
        # wave 1 fills + registers the prefix blocks; wave 2 must HIT
        wave1 = [_PREFIX + [3]]
        wave2 = [_PREFIX + [11], _PREFIX + [12, 13], _PREFIX + [3, 14]]
        _gen(paged, wave1)
        hits0 = paged.pager.prefix_hits
        got = _gen(paged, wave2)
        assert paged.pager.prefix_hits >= hits0 + len(wave2)
        _gen(unshared, wave1)
        assert got == _gen(unshared, wave2)
        assert unshared.pager.prefix_hits == 0
        paged.pager.pool.check()
        unshared.pager.pool.check()


class TestCopyOnWrite:
    def test_fork_shares_full_blocks_and_copies_divergence(self,
                                                           engines):
        _, _, eng = engines                  # unshared: empty index
        pager = eng.pager
        t1 = pager.try_admit(list(range(1, 9)), 12)   # 3 blocks
        assert t1 is not None and len(t1.blocks) == 3
        name = eng.cache_names[0]
        a = np.array(eng.scope.get(name))
        a[t1.blocks[1]] = 7.0                # sentinel in the partial
        eng.scope.set_var(name, a)
        t2 = pager.fork(t1, 6, eng._copy_block)   # 1 full + 2 in part
        assert t2.blocks[0] == t1.blocks[0]       # full block SHARED
        assert pager.pool.refcount(t1.blocks[0]) == 2
        assert t2.blocks[1] != t1.blocks[1]       # divergence COPIED
        assert t2.blocks[2] != t1.blocks[2]       # unwritten: fresh
        a = np.array(eng.scope.get(name))
        np.testing.assert_array_equal(a[t2.blocks[1]],
                                      a[t1.blocks[1]])
        # the mutation test: writing the fork's copy must not reach
        # the parent's physical block (and vice versa)
        a[t2.blocks[1]] = -3.0
        eng.scope.set_var(name, a)
        a = np.array(eng.scope.get(name))
        assert float(a[t1.blocks[1]].min()) == 7.0
        assert float(a[t2.blocks[1]].max()) == -3.0
        pager.release(t1)
        pager.release(t2)
        pager.pool.check()
        assert pager.cow_copies >= 1


class TestPagedBeamSearch:
    def test_shared_vs_unshared_identical(self, engines):
        _, paged, unshared = engines
        prompt = list(_PREFIX)
        a = paged_beam_search(paged, prompt, max_new=5, beam_size=3)
        b = paged_beam_search(unshared, prompt, max_new=5, beam_size=3)
        assert a == b
        assert len(a) == 3 and a[0][1] >= a[-1][1]   # sorted best-first
        assert paged.pager.cow_copies > 0
        paged.pager.pool.check()
        unshared.pager.pool.check()


class TestLeakFree:
    def test_release_evict_reuse_cycles(self, engines):
        _, paged, _ = engines
        pager = paged.pager
        for _ in range(2):
            _gen(paged, [_PREFIX + [11], _PREFIX + [12, 13],
                         [9, 9, 9, 9, 9]])
            pager.pool.check()
            # idle: the ONLY live blocks are the index's cached
            # prefixes — every request ref was dropped
            assert pager.pool.n_used == pager.stats()["blocks_cached"]
        pager.index.evict_all(pager.pool)
        assert pager.pool.n_used == 0
        pager.pool.check()
        # pool drained to empty is immediately reusable
        _gen(paged, [_PREFIX + [11]])
        pager.pool.check()


class TestCapacityAdmission:
    def test_head_of_line_waits_for_blocks(self):
        eng = PagedKVEngine(n_slots=2, max_len=8, block_size=4,
                            n_blocks=3, prefix_sharing=False, vocab=50,
                            d_model=32, d_inner=64, num_heads=4,
                            num_layers=2)
        r1 = eng.submit([1, 2, 3, 4], max_new=4)      # pins both blocks
        r2 = eng.submit([5, 6, 7, 8], max_new=4)
        eng.step()
        # a slot is free but the POOL is not: r2 must stay pending
        assert eng.n_active == 1 and eng.n_pending == 1
        eng.run_until_idle()
        assert r1.done and r2.done
        assert len(r1.tokens) == 4 and len(r2.tokens) == 4
        eng.pager.pool.check()
        assert eng.pager.pool.n_used == 0

    def test_submit_error_names_block_table_span(self):
        eng = PagedKVEngine(n_slots=2, max_len=8, block_size=4,
                            n_blocks=3, prefix_sharing=False, vocab=50,
                            d_model=32, d_inner=64, num_heads=4,
                            num_layers=2)
        with pytest.raises(InvalidArgumentError,
                           match="block-table span"):
            eng.submit(list(range(1, 8)), max_new=4)
        with pytest.raises(InvalidArgumentError, match="ADMISSION"):
            eng.submit(list(range(1, 8)), max_new=4)


class TestCensusReconciliation:
    def test_kv_category_and_watermarks_match_pool(self, engines):
        from paddle_tpu.observability.memory import (state_census,
                                                     watermark_board)
        _, paged, _ = engines
        c = state_census(paged.scope, paged._program, paged.cache_names,
                         kv_names=paged.cache_names)
        assert c["categories"]["kv_cache"] == pytest.approx(
            paged._kv_bytes_static)
        paged._stamp_kv_watermarks({})
        board = watermark_board()
        assert board["kv_cache_bytes"]["current"] == pytest.approx(
            paged._kv_bytes_static)
        per_block = paged._kv_bytes_static / paged.n_blocks
        assert board["kv_cache_used_bytes"]["current"] == pytest.approx(
            paged.pager.pool.n_used * per_block)
        # reserved covers used: the paging invariant in byte terms
        assert (board["kv_cache_used_bytes"]["current"]
                <= board["kv_cache_bytes"]["current"])


def _pool_shaped_results(text, pool_type):
    """(op, line) of every instruction in the body of a lowered module
    whose RESULT has the pool's type (`%x = op ... : ... -> type`, or
    `... : type` for an op whose operands share the result's type)."""
    out = []
    for ln in text.splitlines():
        ln = ln.strip()
        if not ln.startswith("%") or " = " not in ln:
            continue
        tail = ln.rsplit("->", 1)[-1] if "->" in ln else ln.rsplit(":", 1)[-1]
        if pool_type in tail:
            out.append((ln.split(" = ", 1)[1].split()[0].split("(")[0], ln))
    return out


class TestFusedDecodeStructure:
    DIMS = dict(n_slots=3, n_blocks=7, block_size=4, blocks_per_req=2,
                vocab=50, d_model=32, d_inner=64, num_heads=4, num_layers=2)

    @pytest.mark.parametrize("kv_quant", [False, True])
    def test_paged_tick_fuses_attention(self, kv_quant):
        """One `paged_decode_attention` a layer reading the WRITTEN pools,
        and no op that rebuilds the table view in the graph: no gather,
        no transpose, no softmax chain for the decode pass to find."""
        from paddle_tpu.framework.passes import apply_fusion_passes
        from paddle_tpu.models.transformer import \
            transformer_lm_paged_decode_tick
        main, startup = pt.Program(), pt.Program()
        with pt.program_guard(main, startup):
            next_ids, cache_names = transformer_lm_paged_decode_tick(
                cache_prefix="tstpgd", kv_quant=kv_quant, **self.DIMS)
        main = apply_fusion_passes(main, protected={next_ids.name})
        ops = main.global_block().ops
        types = [op.type for op in ops]
        reads = [op for op in ops if op.type == "paged_decode_attention"]
        assert len(reads) == self.DIMS["num_layers"]
        for banned in ("gather", "transpose", "softmax",
                       "fused_decode_attention"):
            assert types.count(banned) == 0, banned
        write = "paged_cache_write_quant" if kv_quant \
            else "paged_cache_write"
        assert types.count(write) == 2 * self.DIMS["num_layers"]
        for op in reads:
            # the read follows the write: its pools are the write's output
            assert op.inputs["KPool"][0] in cache_names
            assert op.inputs["VPool"][0] in cache_names
            assert ("KScale" in op.inputs) == kv_quant

    def test_lowered_tick_computes_nothing_of_pool_shape(self, monkeypatch):
        """The tick as lowered for a TPU with the kernel in: the only
        instructions whose result has a pool's shape are the in-place row
        updates. (The gather → transpose → reshape view this replaces was
        three passes over a whole pool per layer per tick; this is the
        test that would have caught them.)"""
        from paddle_tpu.ops import pallas_kernels
        monkeypatch.setattr(pallas_kernels, "_auto_backend",
                            lambda: "pallas")
        # dh 16, 8 rows a block: one 128-lane row a head, lane-dense
        d = dict(self.DIMS, d_model=64, num_heads=4, block_size=8)
        eng = PagedKVEngine(
            n_slots=d["n_slots"], vocab=d["vocab"],
            max_len=d["block_size"] * d["blocks_per_req"],
            d_model=d["d_model"], d_inner=d["d_inner"],
            num_heads=d["num_heads"], num_layers=d["num_layers"],
            block_size=d["block_size"], n_blocks=d["n_blocks"])
        assert eng.stats()["paged_attention_lowering"] == "kernel"
        # the tick as the engine launches it
        text = eng._step.lower(lowering_platforms=("tpu",)).as_text()
        # the read and the write are one jitted function each, lowered once
        # and called by every layer
        assert text.count("tpu_custom_call") == 1
        assert text.count("call @_paged_pallas(") == d["num_layers"]
        pool = "tensor<%dx%dx1x128xf32>" % (d["n_blocks"], d["num_heads"])
        assert pool in text
        found = _pool_shaped_results(text, pool)
        updates = [ln for op, ln in found
                   if op == "stablehlo.dynamic_update_slice"]
        writes = [ln for op, ln in found
                  if op == "call" and "@_write_pool_rows(" in ln]
        assert len(updates) + len(writes) == len(found), found
        assert len(updates) == d["n_slots"]         # a row a slot, in place
        assert len(writes) == 2 * d["num_layers"]   # K and V, per layer
