"""Continuous-batching serving engine: slot-indexed KV cache + scheduler +
transport (ISSUE r7 tentpole).

Covers the slot-cache contract end to end:
- per-slot `cache_write(batch_axis=...)` parity against a per-row numpy
  reference (the uniform-`Pos` limitation closed for real);
- SlotAllocator alloc/evict/reuse invariants;
- decode-sequence identity when a request joins mid-batch and when a slot
  is REUSED with a stale cache (no reset needed — masked positions prove
  it);
- greedy-identity of the engine's tick loop against the scan-based
  `transformer_lm_generate` on shared weights;
- the engine tick compiles through the r06 fused decode path (structure
  assert: fuse_decode_attention_pass rewrites its attention chains);
- EngineServer/EngineClient RPC incl. pipelined completion reordering;
- transport v2 framing (vectored multi-part frames, pooled recv buffers).
"""

import threading

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers
from paddle_tpu.serving_engine import (ContinuousBatchingEngine,
                                       EngineClient, EngineServer,
                                       SlotAllocator)

pytestmark = pytest.mark.quick

_ENG_DIMS = dict(vocab=50, max_len=16, d_model=32, d_inner=64,
                 num_heads=4, num_layers=2)


@pytest.fixture(scope="module")
def shared_eng():
    """One compiled 3-slot engine shared by the scheduler/RPC tests
    (every test drains it; the tick compile is the expensive part)."""
    return ContinuousBatchingEngine(n_slots=3, **_ENG_DIMS)


def _cache_write_slots_ref(cache, new, pos, axis, batch_axis):
    """Per-row numpy reference: row b (along batch_axis) written at its
    own position pos[b] along axis."""
    out = cache.copy()
    pos = pos.reshape(-1).astype(np.int64)
    for b in range(cache.shape[batch_axis]):
        idx = [slice(None)] * cache.ndim
        idx[batch_axis] = b
        row_idx = list(idx)
        row_idx[axis] = slice(int(pos[b]), int(pos[b]) + new.shape[axis])
        out[tuple(row_idx)] = new[tuple(idx)].reshape(
            out[tuple(row_idx)].shape)
    return out


class TestPerSlotCacheWrite:
    def _run(self, cache, new, pos, axis, batch_axis):
        c = layers.data(name="c", shape=list(cache.shape), dtype="float32",
                        append_batch_size=False)
        n = layers.data(name="n", shape=list(new.shape), dtype="float32",
                        append_batch_size=False)
        p = layers.data(name="p", shape=list(pos.shape), dtype="float32",
                        append_batch_size=False)
        out = layers.cache_write(c, n, p, axis=axis, batch_axis=batch_axis)
        exe = pt.Executor()
        return exe.run(feed={"c": cache, "n": new, "p": pos},
                       fetch_list=[out])[0]

    def test_parity_vs_numpy(self, rng):
        S, nh, T, dh = 5, 3, 8, 4
        cache = rng.randn(S, nh, T, dh).astype("float32")
        new = rng.randn(S, nh, 1, dh).astype("float32")
        pos = rng.randint(0, T, (S,)).astype("float32")
        got = self._run(cache, new, pos, axis=2, batch_axis=0)
        ref = _cache_write_slots_ref(cache, new, pos, axis=2, batch_axis=0)
        np.testing.assert_allclose(got, ref, rtol=1e-6)

    def test_parity_5d_decode_layout(self, rng):
        """The engine's actual [S,1,nh,T,dh] layout, axis=3."""
        S, nh, T, dh = 4, 2, 6, 4
        cache = rng.randn(S, 1, nh, T, dh).astype("float32")
        new = rng.randn(S, 1, nh, 1, dh).astype("float32")
        pos = np.array([0, 5, 2, 2], "float32")
        got = self._run(cache, new, pos, axis=3, batch_axis=0)
        ref = _cache_write_slots_ref(cache, new, pos, axis=3, batch_axis=0)
        np.testing.assert_allclose(got, ref, rtol=1e-6)

    def test_uniform_mode_unchanged(self, rng):
        """batch_axis=None keeps the old single-position semantics."""
        cache = rng.randn(3, 8, 4).astype("float32")
        new = rng.randn(3, 1, 4).astype("float32")
        pos = np.full((3,), 5.0, "float32")
        c = layers.data(name="c", shape=[3, 8, 4], dtype="float32",
                        append_batch_size=False)
        n = layers.data(name="n", shape=[3, 1, 4], dtype="float32",
                        append_batch_size=False)
        p = layers.data(name="p", shape=[3], dtype="float32",
                        append_batch_size=False)
        out = layers.cache_write(c, n, p, axis=1)
        exe = pt.Executor()
        got = exe.run(feed={"c": cache, "n": new, "p": pos},
                      fetch_list=[out])[0]
        ref = cache.copy()
        ref[:, 5:6, :] = new
        np.testing.assert_allclose(got, ref, rtol=1e-6)

    def test_wrong_pos_length_raises(self, rng):
        with pytest.raises(Exception, match="per-slot Pos"):
            self._run(rng.randn(4, 8, 2).astype("float32"),
                      rng.randn(4, 1, 2).astype("float32"),
                      np.zeros((3,), "float32"), axis=1, batch_axis=0)


class TestSlotAllocator:
    def test_alloc_evict_reuse(self):
        a = SlotAllocator(3)
        s = [a.alloc() for _ in range(3)]
        assert sorted(s) == [0, 1, 2]
        assert a.alloc() is None          # exhausted
        assert a.n_free == 0 and a.n_used == 3
        a.free(s[1])
        assert a.n_free == 1
        assert a.alloc() == s[1]          # freed slot is reusable
        with pytest.raises(Exception):
            a.free(99)                    # never allocated
        a.free(s[0])
        with pytest.raises(Exception):
            a.free(s[0])                  # double free

    def test_engine_slot_lifecycle(self, shared_eng):
        eng = shared_eng
        r1 = eng.submit([1], max_new=6)
        r2 = eng.submit([2], max_new=2)
        r3 = eng.submit([3], max_new=2)
        r4 = eng.submit([4], max_new=2)   # must wait for a slot
        eng.step()
        assert eng.n_active == 3 and eng.n_pending == 1
        done = eng.run_until_idle()
        assert {r.rid for r in done} == {r1.rid, r2.rid, r3.rid, r4.rid}
        assert eng._slots.n_free == 3     # all evicted on completion
        assert [len(r.tokens) for r in (r1, r2, r3, r4)] == [6, 2, 2, 2]


def _solo(eng, prompt, max_new):
    """Run one request ALONE to completion on a drained engine: the
    interference-free reference sequence for those weights."""
    assert eng.n_active == 0 and eng.n_pending == 0
    req = eng.submit(prompt, max_new=max_new)
    eng.run_until_idle()
    return list(req.tokens)


class TestDecodeIdentity:
    def test_mid_batch_join_identity(self, shared_eng):
        """A request admitted INTO an in-flight batch must decode the
        exact sequence it decodes alone — slot independence is the whole
        slot-cache contract."""
        eng = shared_eng
        solo = [_solo(eng, p, 8) for p in ([7], [3, 9], [11])]

        long_req = eng.submit([7], max_new=8)
        for _ in range(3):                 # long request mid-flight
            eng.step()
        join1 = eng.submit([3, 9], max_new=8)
        eng.step()
        join2 = eng.submit([11], max_new=8)
        eng.run_until_idle()
        assert long_req.tokens == solo[0]
        assert join1.tokens == solo[1]
        assert join2.tokens == solo[2]

    def test_slot_reuse_no_cache_reset(self):
        """A slot reused after eviction carries a STALE cache from the
        previous tenant; the per-slot position mask must make it
        invisible (prefill rewrites rows before exposing them). Needs a
        FRESH single-slot engine: the reference run must see a provably
        clean (zero-initialized) cache."""
        eng = ContinuousBatchingEngine(n_slots=1, **_ENG_DIMS)
        fresh = _solo(eng, [5, 8], 6)          # clean zero cache
        first = eng.submit([13], max_new=10)   # pollute the slot cache
        eng.run_until_idle()
        assert len(first.tokens) == 10
        again = eng.submit([5, 8], max_new=6)  # same slot, stale rows
        eng.run_until_idle()
        assert again.tokens == fresh

    def test_identity_vs_scan_generator(self):
        """Engine tick loop == transformer_lm_generate greedy on shared
        weights: the continuous-batching path changes scheduling, not
        math."""
        from paddle_tpu.core import unique_name
        from paddle_tpu.framework.program import program_guard
        from paddle_tpu.models import transformer

        G = 6
        dims = _ENG_DIMS
        gen_prog, gen_startup = pt.Program(), pt.Program()
        with program_guard(gen_prog, gen_startup), unique_name.guard():
            seqs, _ = transformer.transformer_lm_generate(
                vocab=dims["vocab"], max_gen=G, d_model=dims["d_model"],
                d_inner=dims["d_inner"], num_heads=dims["num_heads"],
                num_layers=dims["num_layers"], beam_size=1, eos_id=-1)
        exe = pt.Executor()
        exe.run(gen_startup)
        prompts = np.array([[4], [17], [29]], "int64")
        out = exe.run(program=gen_prog, feed={"prompt": prompts},
                      fetch_list=[seqs])[0]          # [B, G, 1]

        eng = ContinuousBatchingEngine(n_slots=3, scope=pt.global_scope(),
                                       **dims)
        reqs = [eng.submit([int(p[0])], max_new=G) for p in prompts]
        eng.run_until_idle()
        for b, req in enumerate(reqs):
            assert req.tokens == out[b, :, 0].astype(int).tolist(), b

    def test_tick_compiles_through_fused_decode(self, shared_eng):
        """Structure assert (the TPU kernel claim's CPU-checkable half):
        the engine's tick program rewrites every per-layer attention
        chain into fused_decode_attention, and its cache writes are the
        per-slot (batch_axis) form."""
        from paddle_tpu.framework.passes import apply_fusion_passes

        eng = shared_eng
        rewritten = apply_fusion_passes(
            eng._program, protected={eng._next_ids.name})
        ops = [op.type for op in rewritten.global_block().ops]
        assert ops.count("fused_decode_attention") == \
            _ENG_DIMS["num_layers"]
        assert ops.count("softmax") == 0
        cw = [op for op in rewritten.global_block().ops
              if op.type == "cache_write"]
        assert len(cw) == 2 * _ENG_DIMS["num_layers"]
        assert all(op.attrs.get("batch_axis") == 0 for op in cw)
        # and the cache write-back targets the persistable slot caches
        for op in cw:
            assert op.outputs["Out"][0] in eng.cache_names

    def test_static_policy_drains_before_refill(self):
        eng = ContinuousBatchingEngine(n_slots=2, policy="static",
                                       **_ENG_DIMS)
        short = eng.submit([1], max_new=2)
        eng.submit([2], max_new=6)
        late = eng.submit([3], max_new=2)
        eng.step()
        assert eng.n_active == 2 and late.slot is None
        # the short batch member finishes early, but static batching must
        # NOT backfill its freed slot until the WHOLE batch drains
        while eng.n_active:
            eng.step()
            if eng.n_active:
                assert late.slot is None
        assert short.done and late.slot is None
        eng.run_until_idle()
        assert len(late.tokens) == 2


class TestEngineServer:
    def test_rpc_roundtrip_and_pipelining(self, shared_eng):
        solo = _solo(shared_eng, [7], 4)
        with EngineServer(shared_eng) as srv:
            host, port = srv.address
            with EngineClient(host, port) as c:
                assert c.generate([7], max_new=4) == solo
                # pipelined: short request admitted mid-flight overtakes
                t_long = c.send_gen([1], max_new=10)
                t_short = c.send_gen([2], max_new=2)
                done = [c.recv_done() for _ in range(2)]
                tags = [d[0] for d in done]
                assert set(tags) == {t_long, t_short}
                by_tag = {d[0]: d[1] for d in done}
                assert len(by_tag[t_long]) == 10
                assert len(by_tag[t_short]) == 2

    def test_oversized_request_errors_cleanly(self, shared_eng):
        with EngineServer(shared_eng) as srv:
            host, port = srv.address
            with EngineClient(host, port) as c:
                c.send_gen(list(range(10)), max_new=100)
                with pytest.raises(RuntimeError, match="server error"):
                    c.recv_done()
                # connection still serves after the rejected request
                assert len(c.generate([3], max_new=2)) == 2


    def test_shutdown_is_prompt_and_leaves_no_thread(self, shared_eng):
        """Closing a listening socket does not wake accept() on Linux: the
        accept thread used to sit out its whole 10 s join timeout on every
        shutdown (and stay alive after it)."""
        import time
        srv = EngineServer(shared_eng).start()
        with EngineClient(*srv.address) as c:
            assert len(c.generate([5], max_new=2)) == 2
        t0 = time.time()
        srv.shutdown()
        assert time.time() - t0 < 5.0
        assert not any(t.is_alive() for t in srv._threads)

    def test_raising_tick_surfaces_at_client(self, monkeypatch):
        """A tick that raises (on a first chip run: a compile failure,
        device memory exhausted) used to kill the daemon engine thread and
        leave every client blocked in recv. It must fail the in-flight
        requests, show in /healthz, and refuse later ones."""
        eng = ContinuousBatchingEngine(n_slots=2, **_ENG_DIMS)

        def boom():
            raise MemoryError("RESOURCE_EXHAUSTED: out of device memory")
        monkeypatch.setattr(eng, "step", boom)
        with EngineServer(eng) as srv:
            host, port = srv.address
            with EngineClient(host, port) as c:
                c._sock.settimeout(30)     # a hang fails, never blocks CI
                c.send_gen([1, 2], max_new=4)
                c.send_gen([3], max_new=2)
                for _ in range(2):         # both in-flight requests fail
                    with pytest.raises(RuntimeError,
                                       match="engine failed.*MemoryError"):
                        c.recv_done()
                health = srv.health()
                assert health["status"] == "failed"
                assert "RESOURCE_EXHAUSTED" in health["error"]
                c.send_gen([4], max_new=1)  # refused, with the cause
                with pytest.raises(RuntimeError, match="engine failed"):
                    c.recv_done()
        assert eng.n_active == 0 and eng.n_pending == 0
        with pytest.raises(RuntimeError, match="engine failed"):
            eng.submit([1], 1)


class TestPreparedStep:
    def test_batch_row_mask_injected_per_call(self, rng):
        """A prepared program declaring the reserved batch-row mask must
        keep working when callers feed only their own vars — prepare()
        synthesized the mask into the compiled signature, run() must
        re-inject it (regression: KeyError on every prepared call)."""
        x = layers.data(name="x", shape=[6])
        mask = layers.batch_row_mask()
        per_ex = layers.reduce_sum(layers.fc(x, size=3), dim=[1])
        loss = layers.reduce_sum(layers.elementwise_mul(per_ex, mask)) \
            / layers.reduce_sum(mask)
        exe = pt.Executor()
        exe.run(pt.default_startup_program())
        feed = {"x": rng.rand(4, 6).astype("float32")}
        direct = exe.run(feed=dict(feed), fetch_list=[loss])[0]
        prep = exe.prepare(pt.default_main_program(), dict(feed), [loss])
        got = prep.run(dict(feed), return_numpy=True)[0]
        np.testing.assert_allclose(got, direct, rtol=1e-6)
        prep.run(dict(feed))                      # and again: no KeyError

    def test_seed_stream_matches_executor_run(self, rng):
        """PreparedStep must draw from the SAME (program.random_seed,
        run-counter) stream as Executor.run — dropout reproducibility is
        part of the prepared contract (regression: different formula)."""
        x = layers.data(name="x", shape=[32])
        y = layers.dropout(layers.fc(x, size=32, name="ps_fc"),
                           dropout_prob=0.5)
        out = layers.reduce_sum(y, dim=[1])
        pt.default_main_program().random_seed = 7
        pt.Executor().run(pt.default_startup_program())
        feed = {"x": rng.rand(4, 32).astype("float32")}
        # fresh executors so both run counters sit at 0: run call #1 and
        # prepared call #1 must draw the same seed
        a = pt.Executor().run(feed=dict(feed), fetch_list=[out])[0]
        prep = pt.Executor().prepare(pt.default_main_program(),
                                     dict(feed), [out])
        b = prep.run(dict(feed), return_numpy=True)[0]
        np.testing.assert_allclose(a, b, rtol=1e-6)


class TestTransportV2:
    def test_vectored_frame_roundtrip_multi_tensor(self, tmp_path, rng):
        """Multi-feed/multi-fetch predictor through the v2 transport:
        vectored frames + pooled recv + batched writer, values exact."""
        from paddle_tpu.serving import PredictorClient, PredictorServer

        class Echo:
            fetch_names = ["a", "b"]

            def run(self, feed, fetch_names=None, return_numpy=True):
                return [np.ascontiguousarray(feed["a"]) * 2,
                        np.ascontiguousarray(feed["b"]) + 1]

        a = rng.randn(16, 32).astype("float32")
        b = rng.randint(0, 9, (8, 3)).astype("int64")
        with PredictorServer(Echo()) as srv:
            host, port = srv.address
            with PredictorClient(host, port) as c:
                # pipeline several to exercise the batched writer
                for _ in range(6):
                    c.send({"a": a, "b": b})
                for _ in range(6):
                    ra, rb = c.recv()
                    np.testing.assert_allclose(ra, a * 2, rtol=1e-6)
                    np.testing.assert_array_equal(rb, b + 1)

    def test_recv_pool_grows_and_recycles(self):
        from paddle_tpu.serving import _RecvBufferPool

        pool = _RecvBufferPool(2)
        b1 = pool.acquire(100)
        b2 = pool.acquire(10)
        assert len(b1) >= 100 and len(b2) >= 10
        assert pool.acquire(5, timeout=0.05) is None   # both in flight
        pool.release(b1)
        b3 = pool.acquire(50)
        assert b3 is b1                                # reused, big enough
        pool.release(b2)
        pool.release(b3)

    def test_byte_views_zero_copy(self, rng):
        from paddle_tpu.serving import _byte_views

        arr = rng.randn(4, 4).astype("float32")
        views = _byte_views([b"hdr", arr, b""])
        assert len(views) == 2                         # empty part dropped
        assert bytes(views[1]) == arr.tobytes()

    def test_threads_unwound_after_connection(self, rng):
        """The reader/worker/writer trio must fully unwind per closed
        connection (regression guard for the new writer thread)."""
        import time

        from paddle_tpu.serving import PredictorClient, PredictorServer

        class Echo:
            fetch_names = ["x"]

            def run(self, feed, fetch_names=None, return_numpy=True):
                return [np.ascontiguousarray(feed["x"])]

        x = np.ones((4,), "float32")
        with PredictorServer(Echo()) as srv:
            host, port = srv.address
            before = threading.active_count()
            with PredictorClient(host, port) as c:
                c.infer({"x": x})
            deadline = time.time() + 15
            while time.time() < deadline:
                if threading.active_count() <= before:
                    break
                time.sleep(0.1)
            assert threading.active_count() <= before


class TestGracefulDrain:
    """EngineServer SIGTERM drain (the robustness satellite): stop
    admitting, finish in-flight generations, flush the writer threads,
    exit cleanly."""

    def test_drain_idle_server_immediate(self, shared_eng):
        srv = EngineServer(shared_eng).start()
        assert srv.drain(timeout=10) is True
        assert srv._stop.is_set()

    def test_sigterm_finishes_in_flight_and_rejects_new(self, shared_eng):
        import os
        import signal
        import time

        srv = EngineServer(shared_eng).start()
        srv.install_sigterm_handler(exit_process=False)
        try:
            with EngineClient(*srv.address) as c:
                tag = c.send_gen([5], max_new=12)
                deadline = time.time() + 10
                while (shared_eng.n_active == 0
                       and shared_eng.n_pending == 0):
                    assert time.time() < deadline, "never admitted"
                    time.sleep(0.005)
                os.kill(os.getpid(), signal.SIGTERM)
                while not srv._draining.is_set():
                    assert time.time() < deadline, "drain never started"
                    time.sleep(0.005)
                # new work is rejected with an explicit draining error...
                c.send_gen([6], max_new=2)
                with pytest.raises(RuntimeError, match="draining"):
                    c.recv_done()
                # ...while the in-flight generation completes in full and
                # its frame is flushed before the socket closes
                got_tag, tokens, _ = c.recv_done()
                assert got_tag == tag
                assert len(tokens) == 12
            deadline = time.time() + 15
            while not srv._stop.is_set():
                assert time.time() < deadline, "drain never shut down"
                time.sleep(0.01)
            assert shared_eng.n_active == 0 and shared_eng.n_pending == 0
        finally:
            if srv._prev_sigterm is not None:
                signal.signal(signal.SIGTERM, srv._prev_sigterm)
            srv.shutdown()
