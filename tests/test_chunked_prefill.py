"""Chunked prefill: prompts go through the prefill lanes of the paged
engine's mixed tick, many tokens a launch (ISSUE 29).

- the chunked paged engine against the one-token slot engine (the identity
  oracle), token for token on CPU float32: ragged prompts, prefix hits, a
  shared last block, pool pressure, more requests prefilling than lanes, a
  request that finishes in the tick its prompt ends, lane-dense pools and
  weight-quantized engines;
- which engines take the chunk path and what they say of it;
- the lanes' counters add up to the prompts;
- the chunk kernel (Pallas interpret mode) against its composite; the
  lowering-selection rule; the whole-block write against row writes;
- the decode-only tick is the executable it was;
- engine construction initializes what the scope lacks and nothing else.
"""

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.framework.offload import HostTierConfig
from paddle_tpu.models import transformer
from paddle_tpu.observability import tracing
from paddle_tpu.serving import ContinuousBatchingEngine, PagedKVEngine

pytestmark = pytest.mark.quick

_DIMS = dict(vocab=50, d_model=32, d_inner=64, num_heads=2, num_layers=2)
_MAX_LEN, _BS = 64, 4


def _trained_scope(dims=_DIMS, max_len=_MAX_LEN, seed=3):
    """A scope that holds the LM's weights and nothing else, as after
    training: the engines share them by name."""
    pt.reset_default_programs()
    pt.reset_global_scope()
    with pt.core.unique_name.guard():
        transformer.transformer_lm(max_len=max_len, is_test=True, **dims)
    pt.default_startup_program().random_seed = seed
    pt.Executor().run(pt.default_startup_program())
    return pt.global_scope()


@pytest.fixture(scope="module")
def pair():
    scope = _trained_scope()
    slot = ContinuousBatchingEngine(n_slots=4, max_len=_MAX_LEN, scope=scope,
                                    **_DIMS)
    paged = PagedKVEngine(n_slots=4, max_len=_MAX_LEN, block_size=_BS,
                          scope=scope, **_DIMS)
    assert (paged.n_lanes, paged.chunk_tokens) == (2, 16)
    return slot, paged


def _prompt(rng, n):
    return [int(t) for t in rng.randint(1, _DIMS["vocab"], n)]


def _gen(eng, prompts, max_new=5):
    reqs = [eng.submit(list(p), max_new=m) for p, m in
            zip(prompts, max_new if isinstance(max_new, list)
                else [max_new] * len(prompts))]
    eng.run_until_idle()
    assert all(r.done and r.error is None for r in reqs)
    return reqs


def _tokens(reqs):
    return [list(r.tokens) for r in reqs]


C = 16      # the fixture's chunk: 4 blocks of 4


class TestIdentityWithTheSlotEngine:
    @pytest.mark.parametrize("n", [1, _BS - 1, _BS, C - 1, C, C + 1,
                                   2 * C, 2 * C + 3, 3 * C + _BS + 1])
    def test_ragged_prompt(self, pair, n):
        """Shorter than a block, exactly a chunk, a chunk and one, two
        chunks and a partial block: the same tokens, in ceil(n / C) ticks
        of prefill instead of n."""
        slot, paged = pair
        prompt = _prompt(np.random.RandomState(n), n)
        want = _tokens(_gen(slot, [prompt]))
        before = paged.n_ticks
        got = _gen(paged, [prompt])
        assert _tokens(got) == want
        # the first token comes out of the last chunk's tick: 5 tokens take
        # the prefill's ticks and four more
        assert paged.n_ticks - before == -(-n // C) + 4

    def test_prefix_hits_start_at_the_shared_span(self, pair):
        slot, paged = pair
        rng = np.random.RandomState(100)
        system = _prompt(rng, 2 * C + _BS)            # whole blocks
        prompts = [system + _prompt(rng, n) for n in (3, C, C + 2, 1)]
        want = _tokens(_gen(slot, prompts))
        cold = _gen(paged, prompts[:1])
        warm = _gen(paged, prompts)
        assert _tokens(cold) == want[:1] and _tokens(warm) == want
        assert cold[0].shared_len == 0
        assert [r.shared_len for r in warm] == [len(system)] * 4
        assert all(r.shared_len % _BS == 0 for r in warm)

    def test_a_shared_last_block_is_recomputed_in_a_private_one(self, pair):
        """A prompt of whole blocks that the cache holds entirely: sharing
        stops a block short of the end (a request keeps a token to feed),
        so the last block is written again, into a block of the request's
        own — the cached one is read by nobody's write."""
        slot, paged = pair
        prompt = _prompt(np.random.RandomState(7), 2 * C)
        want = _tokens(_gen(slot, [prompt]))
        first = _gen(paged, [prompt])
        cached = {n.block for n in paged.pager.index.match(prompt)}
        assert len(cached) == 2 * C // _BS
        again = paged.submit(prompt, max_new=5)
        paged.step()                                   # admit + one chunk
        assert again.shared_len == 2 * C - _BS
        private = again.table.blocks[again.table.n_shared:]
        assert not cached & set(private)
        paged.run_until_idle()
        assert [again.tokens] == want == _tokens(first)

    def test_the_sanitizer_sees_every_position_of_a_chunk(self, monkeypatch):
        """With the shadow-state sanitizer on, a chunk names each position
        it writes, and a run with prefix hits raises nothing."""
        monkeypatch.setenv("PTPU_KV_SANITIZE", "1")
        scope = _trained_scope()
        paged = PagedKVEngine(n_slots=2, max_len=_MAX_LEN, block_size=_BS,
                              scope=scope, **_DIMS)
        san = paged.pager.sanitizer
        assert san is not None
        seen = []
        note = san.note_write
        monkeypatch.setattr(san, "note_write",
                            lambda table, pos: (seen.append(pos),
                                                note(table, pos))[1])
        rng = np.random.RandomState(5)
        system = _prompt(rng, C)
        _gen(paged, [system + _prompt(rng, 3)], max_new=2)
        assert seen == list(range(C + 3)) + [C + 3]
        del seen[:]
        _gen(paged, [system + _prompt(rng, 6)], max_new=1)
        assert seen == list(range(C, C + 6))           # from the shared span

    def test_more_requests_prefilling_than_lanes(self, pair):
        """L + 1 long prompts at once: the third waits for a lane, in
        admission order, while the first two go through; a decode row rides
        in the same ticks."""
        slot, paged = pair
        rng = np.random.RandomState(11)
        prompts = [_prompt(rng, n) for n in (3, 2 * C + 1, 3 * C, 2 * C + 5)]
        want = _tokens(_gen(slot, prompts, max_new=8))
        mark = tracing.mark()
        got = _gen(paged, prompts, max_new=8)
        assert _tokens(got) == want
        ticks = [s.attrs for s in tracing.spans_since(mark)
                 if s.name == "engine/tick"]
        assert max(t["prefill"] for t in ticks) == paged.n_lanes
        # tick 1: the short prompt and the first chunk of the next in the
        # lanes, two slots waiting; the last prompt starts when a lane frees
        assert [t["prefill_tokens"] for t in ticks[:7]] == [
            3 + C, C + C, 1 + C, C + C, C, 5, 0]
        first = [r.first_token_pc for r in got]
        assert first == sorted(first) and len(set(first)) == 4

    def test_a_request_finishes_in_the_tick_its_prompt_ends(self, pair):
        slot, paged = pair
        rng = np.random.RandomState(13)
        prompts = [_prompt(rng, n) for n in (C + 2, 2, C)]
        want = _tokens(_gen(slot, prompts, max_new=[1, 1, 3]))
        before = paged.n_ticks
        got = _gen(paged, prompts, max_new=[1, 1, 3])
        assert _tokens(got) == want
        assert [len(r.tokens) for r in got] == [1, 1, 3]
        # chunk, chunk, decode, decode: the third request waited one tick
        # for a lane, the first two were done in two ticks
        assert paged.n_ticks - before == 4
        assert paged.n_active == 0 and paged.pager.pool.n_used == \
            paged.pager.index.n_cached

    def test_pool_pressure_keeps_the_head_of_the_line_waiting(self):
        """A pool too small for two requests: the second stays pending
        (no block, no lane) until the first releases its blocks, then
        prefills in chunks; tokens as the slot engine's."""
        scope = _trained_scope()
        slot = ContinuousBatchingEngine(n_slots=2, max_len=_MAX_LEN,
                                        scope=scope, **_DIMS)
        paged = PagedKVEngine(n_slots=2, max_len=_MAX_LEN, block_size=_BS,
                              n_blocks=_MAX_LEN // _BS + 1,
                              prefix_sharing=False, scope=scope, **_DIMS)
        rng = np.random.RandomState(17)
        prompts = [_prompt(rng, 2 * C + 3), _prompt(rng, C + 9)]
        want = _tokens(_gen(slot, prompts, max_new=6))
        reqs = [paged.submit(p, max_new=6) for p in prompts]
        paged.step()
        assert paged.n_active == 1 and paged.n_pending == 1
        assert reqs[1].table is None
        paged.run_until_idle()
        assert _tokens(reqs) == want
        assert reqs[1].admitted_pc >= reqs[0].done_pc
        paged.pager.pool.check()
        assert paged.pager.pool.n_used == 0

    def test_lane_dense_pools(self):
        """Heads of 64 in blocks of 4: a block is two 128-lane rows, the
        pool is declared lane-dense, and a chunk's whole-block write lands
        where the row writes would."""
        dims = dict(vocab=40, d_model=128, d_inner=64, num_heads=2,
                    num_layers=1)
        scope = _trained_scope(dims, max_len=32)
        slot = ContinuousBatchingEngine(n_slots=2, max_len=32, scope=scope,
                                        **dims)
        paged = PagedKVEngine(n_slots=2, max_len=32, block_size=4,
                              scope=scope, **dims)
        assert scope.get(paged.cache_names[0]).shape[-1] == 128
        assert paged.chunk_tokens == 8
        rng = np.random.RandomState(19)
        prompts = [[int(t) for t in rng.randint(1, 40, n)]
                   for n in (8, 11, 3, 17)]
        assert _tokens(_gen(paged, prompts, 4)) == \
            _tokens(_gen(slot, prompts, 4))

    def test_weight_quantized_engine_takes_the_lanes(self):
        """`quant=`: the mixed tick is rewritten onto the payloads the
        decode tick holds; same tokens as the quantized slot engine."""
        scope = _trained_scope()
        slot = ContinuousBatchingEngine(n_slots=2, max_len=_MAX_LEN,
                                        scope=scope, quant="int8", **_DIMS)
        scope2 = _trained_scope()
        paged = PagedKVEngine(n_slots=2, max_len=_MAX_LEN, block_size=_BS,
                              scope=scope2, quant="int8", **_DIMS)
        assert paged.quant == "int8" and paged.stats()["prefill"] == "chunked"
        rng = np.random.RandomState(23)
        prompts = [_prompt(rng, n) for n in (C + 3, 5)]
        assert _tokens(_gen(paged, prompts)) == _tokens(_gen(slot, prompts))


class TestWhichEnginesChunk:
    def test_the_paged_engine_says_chunked(self, pair):
        slot, paged = pair
        assert slot.stats()["prefill"] == "one_token"
        assert paged.stats()["prefill"] == "chunked"
        assert (paged.n_lanes, paged.chunk_tokens) == (2, C)

    @pytest.mark.parametrize("kw", [
        dict(speculative=2), dict(host_tier=HostTierConfig(host_blocks=8)),
        dict(kv_quant=True), dict(topk_k=2)],
        ids=["speculative", "host_tier", "kv_quant", "topk"])
    def test_engines_that_walk_a_position_a_tick_stay_one_token(self, kw):
        scope = _trained_scope()
        if "speculative" in kw:
            from paddle_tpu.serving import SpecConfig
            kw = dict(speculative=SpecConfig(gamma=2))
        eng = PagedKVEngine(n_slots=2, max_len=32, block_size=_BS,
                            scope=scope, **kw, **_DIMS)
        assert eng.stats()["prefill"] == "one_token"
        assert eng._mixed_step is None
        prompt = _prompt(np.random.RandomState(1), 9)
        before = eng.n_ticks
        req = eng.submit(prompt, max_new=2)
        eng.run_until_idle()
        assert len(req.tokens) == 2
        if "speculative" not in kw:
            assert eng.n_ticks - before == 9 + 1

    @pytest.mark.parametrize("bs,max_len,want", [
        (16, 1024, 128), (4, 64, 16), (8, 64, 16), (16, 64, 16),
        (4, 16, 4), (4, 2048, 128), (256, 1024, 256)])
    def test_one_chunk_shape_from_block_size_and_span(self, bs, max_len,
                                                      want):
        """Whole blocks, 128 tokens where the span holds four chunks."""
        from paddle_tpu.serving.kv_pager import prefill_chunk_tokens
        chunk = prefill_chunk_tokens(bs, -(-max_len // bs))
        assert chunk == want and chunk % bs == 0


class TestLaneCounters:
    def test_prefill_tokens_add_up_to_the_unshared_prompts(self, pair):
        _, paged = pair
        rng = np.random.RandomState(29)
        system = _prompt(rng, C)
        prompts = [system + _prompt(rng, n) for n in (2, C + 1, 7)] \
            + [_prompt(rng, 2 * C + 2)]
        mark = tracing.mark()
        reqs = _gen(paged, prompts[:1]) + _gen(paged, prompts[1:])
        ticks = [s.attrs for s in tracing.spans_since(mark)
                 if s.name == "engine/tick"]
        assert all({"prefill", "prefill_tokens", "kv_blocks"} <= set(t)
                   for t in ticks)
        unshared = sum(len(p) - r.shared_len for p, r in zip(prompts, reqs))
        assert sum(r.shared_len for r in reqs) == 2 * C
        assert sum(t["prefill_tokens"] for t in ticks) == unshared
        lanes = sum(-(-(len(p) - r.shared_len) // C)
                    for p, r in zip(prompts, reqs))
        assert sum(t["prefill"] for t in ticks) == lanes
        # a decode-only tick says so
        assert any(t["prefill_tokens"] == 0 and t["prefill"] == 0
                   for t in ticks)

    def test_the_benchmark_metric_reads_the_lanes(self, pair):
        import types
        from benchmark.metrics import prefill_chunk_tokens_p50 as metric
        _, paged = pair
        rng = np.random.RandomState(31)
        mark = tracing.mark()
        _gen(paged, [_prompt(rng, n) for n in (5,)])
        _gen(paged, [_prompt(rng, n) for n in (C + 4,)])
        run = types.SimpleNamespace(spans=tracing.spans_since(mark))
        assert metric.read(run) == np.median([5, C, 4])
        # a program that feeds one token a tick carries no such attr
        old = [types.SimpleNamespace(name="engine/tick",
                                     attrs={"prefill": 1, "active": 2})]
        assert metric.read(types.SimpleNamespace(spans=old)) is None


# -- the chunk kernel (Pallas interpret mode) against its composite ----------

_NBK, _NH, _KBS, _DH, _NLB, _KC = 40, 2, 16, 64, 10, 32


def _chunk_case(rng, name):
    """(q, k_pool, v_pool, btab, pos, rows): three lanes of 32 query rows
    over lane-dense pools (16 rows of 64: eight 128-lane rows a block)."""
    L = 3
    k_pool = rng.randn(_NBK, _NH, _KBS * _DH // 128, 128).astype("float32")
    v_pool = rng.randn(_NBK, _NH, _KBS * _DH // 128, 128).astype("float32")
    q = rng.randn(L, _KC, _NH * _DH).astype("float32")
    pos, rows = {
        "ragged": ([0, _KBS, 3 * _KBS], [_KC, 5, _KC - 1]),
        "idle_lane": ([2 * _KBS, 0, 0], [_KC, 0, 1]),
        "shared_prefix": ([4 * _KBS, 4 * _KBS, 2 * _KBS], [_KC, 9, _KC]),
        "permuted": ([6 * _KBS, _KBS, 0], [_KC, _KC, 17]),
        "full_span": ([(_NLB - 2) * _KBS, 0, 0], [_KC, _KC, _KC]),
    }[name]
    ids = list(rng.permutation(np.arange(1, _NBK)))
    if name != "permuted":
        ids = sorted(ids)
    btab = np.zeros((L, _NLB), "int64")
    for lane in range(L):
        if rows[lane]:
            for j in range(-(-(pos[lane] + rows[lane]) // _KBS)):
                btab[lane, j] = ids.pop()
    if name == "shared_prefix":
        btab[1, :4] = btab[0, :4]
        btab[2, :2] = btab[0, :2]
    return (q, k_pool, v_pool, btab, np.array(pos, "int64"),
            np.array(rows, "int64"))


def _chunk_attn(backend, q, k_pool, v_pool, btab, pos, rows):
    from paddle_tpu.fusion import paged_decode_attention
    out = np.asarray(paged_decode_attention(
        q, k_pool, v_pool, btab, pos, _NH, scale=_DH ** -0.5,
        backend=backend, rows=rows))
    assert np.isfinite(out).all()
    # only a lane's real rows mean anything
    return [out[lane, :n] for lane, n in enumerate(rows)]


class TestChunkAttentionKernel:
    @pytest.mark.parametrize("case", ["ragged", "idle_lane", "shared_prefix",
                                      "permuted", "full_span"])
    def test_kernel_matches_composite(self, rng, case):
        args = _chunk_case(rng, case)
        for got, ref in zip(_chunk_attn("pallas_interpret", *args),
                            _chunk_attn("xla", *args)):
            np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("backend", ["xla", "pallas_interpret"])
    def test_garbage_beyond_the_position_is_never_attended(self, rng,
                                                           backend):
        """The rows of a lane's last block past its last real row, every
        unmapped block and the null block may hold anything finite."""
        q, k_pool, v_pool, btab, pos, rows = _chunk_case(rng, "ragged")
        clean = _chunk_attn(backend, q, k_pool, v_pool, btab, pos, rows)
        k2 = k_pool.reshape(_NBK, _NH, _KBS, _DH).copy()
        v2 = v_pool.reshape(_NBK, _NH, _KBS, _DH).copy()
        live = set()
        for lane in range(len(pos)):
            end = pos[lane] + rows[lane]               # first dead position
            live.update(int(b) for b in btab[lane, :-(-end // _KBS)])
            if end % _KBS:
                for pool in (k2, v2):
                    pool[btab[lane, end // _KBS], :, end % _KBS:] = 1e4
        for b in range(_NBK):
            if b not in live:
                k2[b], v2[b] = -3e4, 7e4
        dirty = _chunk_attn(backend, q, k2.reshape(k_pool.shape),
                            v2.reshape(v_pool.shape), btab, pos, rows)
        for got, ref in zip(dirty, clean):
            np.testing.assert_array_equal(got, ref)

    def test_rows_attend_causally(self, rng):
        """Row c of a lane is the decode read at position pos + c."""
        from paddle_tpu.fusion import paged_decode_attention
        q, k_pool, v_pool, btab, pos, rows = _chunk_case(rng, "permuted")
        got = _chunk_attn("pallas_interpret", q, k_pool, v_pool, btab, pos,
                          rows)
        for lane in (0, 2):
            for c in (0, 7, rows[lane] - 1):
                one = np.asarray(paged_decode_attention(
                    q[lane:lane + 1, c:c + 1], k_pool, v_pool,
                    btab[lane:lane + 1], pos[lane:lane + 1] + c, _NH,
                    scale=_DH ** -0.5, backend="xla"))
                np.testing.assert_allclose(got[lane][c], one[0, 0],
                                           rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("nh,bs,dh", [(4, 8, 32), (2, 8, 128),
                                          (4, 8, 16)])
    def test_kernel_at_other_packings(self, rng, nh, bs, dh):
        from paddle_tpu.fusion import paged_decode_attention
        NB, NLB, c = 12, 5, 16
        shape = (NB, nh, bs * dh // 128, 128)
        k_pool = rng.randn(*shape).astype("float32")
        v_pool = rng.randn(*shape).astype("float32")
        q = rng.randn(2, c, nh * dh).astype("float32")
        btab = np.array([[3, 7, 1, 9, 0], [5, 2, 0, 0, 0]], "int64")
        pos, rows = np.array([2 * bs, 0]), np.array([c, bs + 3])
        outs = [np.asarray(paged_decode_attention(
            q, k_pool, v_pool, btab, pos, nh, scale=dh ** -0.5, backend=be,
            rows=rows)) for be in ("xla", "pallas_interpret")]
        for lane in range(2):
            np.testing.assert_allclose(outs[1][lane, :rows[lane]],
                                       outs[0][lane, :rows[lane]],
                                       rtol=1e-5, atol=1e-5)


class TestChunkLowering:
    @pytest.mark.parametrize("backend,dtype,lanes,g,dh,quant,platform,want", [
        ("pallas", "float32", 128, 128, 64, False, "tpu", "kernel"),
        ("pallas", "float32", 128, 8, 128, False, "tpu", "kernel"),
        ("pallas_interpret", "float32", 128, 16, 16, False, "cpu", "kernel"),
        (None, "float32", 128, 128, 64, False, "cpu", "composite"),  # a CPU
        ("xla", "float32", 128, 128, 64, False, "tpu", "composite"),  # asked
        ("pallas", "float32", 128, 12, 64, False, "tpu", "composite"),
        ("pallas", "float32", 128, 4, 64, False, "tpu", "composite"),  # verify
        ("pallas", "int8", 128, 128, 64, True, "tpu", "composite"),
        ("pallas", "float32", 16, 16, 16, False, "tpu", "composite"),  # tiny
    ])
    def test_chosen_from_what_the_op_sees(self, backend, dtype, lanes, g, dh,
                                          quant, platform, want):
        from paddle_tpu.fusion import paged_attention_lowering
        assert paged_attention_lowering(dtype, lanes, g, dh, quant,
                                        backend=backend,
                                        platform=platform) == want

    def test_composite_on_a_tpu_is_an_error_not_a_fallback(self):
        from paddle_tpu.fusion import paged_attention_lowering
        with pytest.raises(RuntimeError, match="128 query.*not a fallback"):
            paged_attention_lowering("float32", 128, 128, 64, False,
                                     platform="tpu")


class TestWholeBlockWrite:
    @pytest.mark.parametrize("nh,bs,dh", [(2, 4, 64), (4, 4, 8), (2, 16, 64)])
    def test_chunk_blocks_land_where_row_writes_would(self, rng, nh, bs, dh):
        """`paged_cache_write(chunk=...)`: lane l's block b goes whole to
        pool[ids[l * CB + b]], in the pool's declared layout; the rows of
        the same op still land; nothing else changes."""
        import jax.numpy as jnp

        from paddle_tpu.framework.registry import lookup_op
        from paddle_tpu.ops.tensor_ops import pool_block_shape
        NB, L, CB, S = 9, 2, 2, 3
        block = pool_block_shape(nh, bs, dh)
        pool = rng.randn(NB, *block).astype("float32")
        chunk = rng.randn(L, CB * bs, nh * dh).astype("float32")
        ids = np.array([4, 7, 2, 0], "int64")          # the last: null block
        new = rng.randn(S, nh, dh).astype("float32")
        wblock, woff = np.array([1, 5, 0]), np.array([bs - 1, 0, 0])
        out = lookup_op("paged_cache_write").lower(None, {
            "Cache": [jnp.asarray(pool)], "New": [jnp.asarray(new)],
            "BlockIds": [jnp.asarray(wblock)], "Offsets": [jnp.asarray(woff)],
            "Chunk": [jnp.asarray(chunk)],
            "ChunkBlockIds": [jnp.asarray(ids)]}, {})["Out"][0]
        want = pool.reshape(NB, nh, bs, dh).copy()
        rows = chunk.reshape(L * CB, bs, nh, dh)
        for i, b in enumerate(ids):
            want[b] = rows[i].transpose(1, 0, 2)
        for s in range(S):
            want[wblock[s], :, woff[s]] = new[s]
        np.testing.assert_array_equal(
            np.asarray(out).reshape(NB, nh, bs, dh), want)


def _lowered(step):
    """The StableHLO text a bound step's launch lowers to (no source
    locations): the one packed host array cut apart, then the program."""
    return step.lower().as_text()


class TestDecodeTickUnchanged:
    def test_same_lowered_text_with_and_without_the_mixed_program(self):
        """The decode-only tick of a chunked engine is, feed for feed and
        op for op, the tick of an engine that has no mixed program."""
        scope = _trained_scope()
        kw = dict(n_slots=3, max_len=32, block_size=_BS, scope=scope,
                  **_DIMS)
        chunked = PagedKVEngine(**kw)
        one_token = PagedKVEngine(host_tier=HostTierConfig(host_blocks=4),
                                  **kw)
        assert chunked._mixed_step is not None
        assert one_token._mixed_step is None
        names = ["tick_tok", "tick_pos", "tick_btab", "tick_wblock",
                 "tick_woff", "tick_from_last"]
        assert list(chunked._feeds) == list(one_token._feeds) == names
        assert chunked._step._compiled.feed_names == \
            one_token._step._compiled.feed_names
        ops = [[(op.type, sorted(op.inputs), sorted(op.attrs))
                for op in e._program.global_block().ops]
               for e in (chunked, one_token)]
        assert ops[0] == ops[1]
        assert _lowered(chunked._step) == _lowered(one_token._step)
        # the mixed tick is another program over the same state
        mixed = chunked._mixed_step._compiled
        assert mixed.feed_names[:6] == names
        assert set(mixed.rw_names) == set(chunked._step._compiled.rw_names)
        assert chunked._mixed_program is not chunked._program

    def test_a_pool_keeps_one_writer_in_the_mixed_program(self, pair):
        """The decode rows and the lanes' blocks go through ONE
        `paged_cache_write` a pool a layer: the serving lint's aliasing
        check (two writers race on a donated buffer) holds."""
        from paddle_tpu.framework.dataflow import cache_write_aliasing
        _, paged = pair
        assert cache_write_aliasing(paged._mixed_program) == []
        writes = [op for op in paged._mixed_program.global_block().ops
                  if op.type == "paged_cache_write"]
        assert len(writes) == 2 * _DIMS["num_layers"]
        assert all("Chunk" in op.inputs for op in writes)

    def test_a_decode_only_tick_launches_the_decode_program(self, pair,
                                                            monkeypatch):
        _, paged = pair
        launched = []
        run = paged._run_bound_step
        monkeypatch.setattr(
            paged, "_run_bound_step",
            lambda step, owner: (launched.append(owner), run(step, owner))[1])
        _gen(paged, [_prompt(np.random.RandomState(37), C + 2)], max_new=4)
        assert launched == ["mixed", "mixed", "main", "main", "main"]


class TestInitMissingVars:
    def _spy(self, monkeypatch):
        from paddle_tpu.framework.executor import Executor
        ran = []
        run = Executor.run

        def spy(self, program=None, *a, **kw):
            ran.append(program)
            return run(self, program, *a, **kw)
        monkeypatch.setattr(Executor, "run", spy)
        return ran

    def test_only_what_the_scope_lacks_is_initialized(self, monkeypatch):
        scope = _trained_scope()
        scope._vars.pop("lm_head.w_1")                 # an absent parameter
        present = {n: scope.get(n) for n in scope.local_var_names()}
        before = {n: np.asarray(v).copy() for n, v in present.items()}
        ran = self._spy(monkeypatch)
        eng = PagedKVEngine(n_slots=2, max_len=32, block_size=_BS,
                            scope=scope, **_DIMS)
        # one startup run, of the pools (and the ids the decode rows leave
        # on the device for the next tick) and the absent parameter alone:
        # no op of it writes a variable the scope held
        assert len(ran) == 1
        outs = {n for op in ran[0].global_block().ops
                for n in op.output_names()}
        last_ids = f"{eng._cache_prefix}_last_ids"
        assert outs == set(eng.cache_names) | {"lm_head.w_1", last_ids}
        assert np.asarray(scope.get(last_ids)).shape == (2, 1)
        assert not outs & set(present)
        for n, v in present.items():
            assert scope.get(n) is v                   # the same buffers
            np.testing.assert_array_equal(np.asarray(v), before[n])
        for n in eng.cache_names:
            assert not np.asarray(scope.get(n)).any()  # pools start zeroed
        assert np.asarray(scope.get("lm_head.w_1")).shape == (50,)
        # and the mixed program declared nothing of its own to initialize
        assert eng._mixed_step is not None
        assert eng._init_missing_vars(eng._startup) == []

    def test_a_fresh_engine_still_initializes_everything(self, monkeypatch):
        pt.reset_default_programs()
        pt.reset_global_scope()
        ran = self._spy(monkeypatch)
        eng = PagedKVEngine(n_slots=2, max_len=32, block_size=_BS, **_DIMS)
        assert len(ran) == 1
        n_startup = len(eng._startup.global_block().ops)
        assert len(ran[0].global_block().ops) == n_startup
        req = eng.submit([1, 2, 3], max_new=2)
        eng.run_until_idle()
        assert len(req.tokens) == 2

    def test_the_draft_shares_the_target_weights(self, monkeypatch):
        """The speculative draft's weights alias the target's and are not
        initialized; its own caches are."""
        from paddle_tpu.serving import SpecConfig
        scope = _trained_scope()
        ran = self._spy(monkeypatch)
        eng = ContinuousBatchingEngine(
            n_slots=2, max_len=32, scope=scope,
            speculative=SpecConfig(gamma=2, draft="f32"), **_DIMS)
        assert scope.get("draft_tok_emb") is scope.get("tok_emb")
        for program in ran:
            for op in program.global_block().ops:
                assert all("_k" in n or "_v" in n or n.endswith("_last_ids")
                           for n in op.output_names())
        assert len(ran) == 2           # the target's caches, the draft's
        assert eng.spec is not None
