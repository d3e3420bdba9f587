"""r24 serving-tier ownership verifier.

Three layers under test, mirroring docs/static_analysis.md §5:

1. the abstract transition model + depth-bounded exhaustive model
   checker (framework/ownership.py) — the shipped protocol is clean at
   small scope and every seeded K-bug mutation is caught BY NAME;
2. the runtime shadow-state sanitizer (serving/sanitizer.py) — zero
   divergences on real KVPager traffic (differential fuzz, the whole
   serving suite runs under the conftest pin), and every seeded
   runtime bug raises SanitizerDivergence under its diagnostic code;
3. the static serving lints — cache-write aliasing over tick programs
   (framework/dataflow.py cache_write_aliasing) and the
   rollback-window extension of the transfer-schedule check
   (framework/offload.py check_schedule).
"""

import numpy as np
import pytest

from paddle_tpu.core import flags
from paddle_tpu.core.enforce import InvalidArgumentError
from paddle_tpu.framework import offload as ofl
from paddle_tpu.framework.offload import HostTierConfig
from paddle_tpu.framework.ownership import (DIAGNOSTICS, MUTATIONS,
                                            AbstractState, ModelChecker,
                                            OwnershipViolation)
from paddle_tpu.serving.kv_pager import KVPager
from paddle_tpu.serving import sanitizer as skv
from paddle_tpu.serving.sanitizer import SanitizerDivergence


@pytest.fixture(autouse=True)
def _sanitize_on():
    """Every test in this file runs with the kill switch ON (the suite
    pins it via PTPU_KV_SANITIZE=1 in conftest, but this file must hold
    standalone); individual tests flip it off through flags.set_flag."""
    prev = flags.get_flag("kv_sanitize")
    flags.set_flag("kv_sanitize", True)
    yield
    flags.set_flag("kv_sanitize", prev)


def _pager(n_blocks=9, block_size=4, host_blocks=None, **kw):
    tier = (HostTierConfig(host_blocks=host_blocks)
            if host_blocks is not None else None)
    p = KVPager(n_blocks=n_blocks, block_size=block_size, host_tier=tier,
                **kw)
    assert p.sanitizer is not None
    return p


# ---------------------------------------------------------------------------
# 1a. the abstract model: transitions and named preconditions
# ---------------------------------------------------------------------------


class TestAbstractModel:
    def test_admit_write_release_roundtrip(self):
        st = AbstractState(n_blocks=5, block_size=2)
        assert st.admit(0, prompt_len=3, need_len=5)
        for _ in range(5):
            st.write(0)
        st.check_invariants()
        st.release_table(0)
        st.check_invariants()
        # full prompt block 0 stays pinned by the index chain
        assert len(st.index_chain) == 1
        assert sum(st.ref) == 1

    def test_double_release_is_kv_double_free(self):
        st = AbstractState(n_blocks=5, block_size=2)
        st.alloc_at(1)
        st.release(1)
        with pytest.raises(OwnershipViolation) as e:
            st.release(1)
        assert e.value.code == "kv-double-free"

    def test_share_of_freed_block_is_use_after_free(self):
        st = AbstractState(n_blocks=5, block_size=2)
        with pytest.raises(OwnershipViolation) as e:
            st.share(2)
        assert e.value.code == "kv-use-after-free"

    def test_write_to_shared_block_is_cow_violation(self):
        st = AbstractState(n_blocks=7, block_size=2)
        assert st.admit(0, prompt_len=3, need_len=4)
        for _ in range(4):
            st.write(0)
        assert st.fork(0, 1)
        with pytest.raises(OwnershipViolation) as e:
            # position 0 lives in a block both hypotheses now hold
            st.note_write(st.tables[1].blocks, 0)
        assert e.value.code == "kv-write-shared-block"

    def test_two_tier_spill_reload_and_double_spill(self):
        st = AbstractState(n_blocks=5, block_size=2, host_blocks=4)
        assert st.admit(0, prompt_len=3, need_len=5)
        for _ in range(4):
            st.write(0)
        assert st.spill(0)
        st.check_invariants()
        assert st.host_used == 2
        with pytest.raises(OwnershipViolation) as e:
            st.spill(0)
        assert e.value.code == "kv-double-spill"
        assert st.reload(0)
        st.check_invariants()
        assert st.host_used == 0
        st.release_table(0)
        st.check_invariants()

    def test_commit_before_arrival_is_prefetch_after_use(self):
        st = AbstractState(n_blocks=5, block_size=2, host_blocks=4)
        assert st.admit(0, prompt_len=3, need_len=5)
        for _ in range(4):
            st.write(0)
        assert st.spill(0)
        with pytest.raises(OwnershipViolation) as e:
            st.reload(0, wait=False)     # commit with the ticket in flight
        assert e.value.code == "kv-prefetch-after-use"


# ---------------------------------------------------------------------------
# 1b. the model checker: shipped protocol clean, K-bug matrix by name
# ---------------------------------------------------------------------------


class TestModelChecker:
    def test_shipped_protocol_clean_at_default_scope(self):
        res = ModelChecker().run()
        assert res.ok, res.violations
        # deterministic BFS over a deterministic op set: the exact
        # coverage IS the spec — a protocol change must update it here
        # and in docs/static_analysis.md §5 together
        assert (res.states_explored, res.transitions) == (238, 686)

    def test_state_space_closes_exhaustively(self):
        # past depth 33 no new states exist at this scope: raising the
        # bound far beyond it proves TOTAL coverage, not a sample
        res = ModelChecker(depth=64).run()
        assert res.ok, res.violations
        assert res.states_explored == 5262
        assert res.transitions == 31021

    @pytest.mark.parametrize("mutation", sorted(MUTATIONS))
    def test_mutation_caught_by_name(self, mutation):
        res = ModelChecker(mutation=mutation).run()
        assert not res.ok
        assert MUTATIONS[mutation] in res.codes(), (
            f"{mutation} expected {MUTATIONS[mutation]}, got {res.codes()}")

    def test_every_mutation_code_is_documented(self):
        for code in MUTATIONS.values():
            assert code in DIAGNOSTICS


# ---------------------------------------------------------------------------
# 2a. the sanitizer catches every seeded runtime K-bug by name
# ---------------------------------------------------------------------------


class _InFlightTicket:
    def done(self):
        return False


class TestSanitizerCatchesSeededBugs:
    def test_leaked_release_is_kv_block_leak(self):
        pager = _pager(prefix_sharing=False)
        t = pager.try_admit([1, 2, 3, 4, 5], 8)
        t.blocks[-1] = 0                 # seeded: one mapping dropped,
        with pytest.raises(SanitizerDivergence) as e:   # release skipped
            pager.release(t)
        assert e.value.code == "kv-block-leak"

    def test_write_to_shared_block_is_caught(self):
        pager = _pager(prefix_sharing=False)
        t = pager.try_admit([1, 2, 3, 4, 5], 8)
        child = pager.fork(t, 8, copy_block=lambda s, d: None)
        with pytest.raises(SanitizerDivergence) as e:
            # position 0's block is now held by both hypotheses
            pager.sanitizer.note_write(child, 0)
        assert e.value.code == "kv-write-shared-block"

    def test_h2d_commit_in_flight_is_prefetch_after_use(self):
        pager = _pager(host_blocks=8, prefix_sharing=False)
        with pytest.raises(SanitizerDivergence) as e:
            pager.sanitizer.note_h2d_commit(_InFlightTicket())
        assert e.value.code == "kv-prefetch-after-use"

    def test_double_release_is_kv_double_free(self):
        # the rollback-double-free mutation reduces to releasing a
        # rejected block twice; the pool-level shadow precondition fires
        # BEFORE the real release can corrupt the free list
        pager = _pager(prefix_sharing=False)
        t = pager.try_admit([1, 2, 3, 4, 5], 8)
        pager.pool.release(t.blocks[-1])
        with pytest.raises(SanitizerDivergence) as e:
            pager.pool.release(t.blocks[-1])
        assert e.value.code == "kv-double-free"


# ---------------------------------------------------------------------------
# 2b. tampering with the real state diverges under the matching code
# ---------------------------------------------------------------------------


class TestSanitizerDivergenceOnTamper:
    def test_refcount_tamper_is_accounting_identity(self):
        pager = _pager(prefix_sharing=False)
        t = pager.try_admit([1, 2, 3, 4], 6)
        pager.pool._ref[t.blocks[0]] += 1
        with pytest.raises(SanitizerDivergence) as e:
            pager.sanitizer.verify_full("tamper")
        assert e.value.code == "kv-accounting-identity"

    def test_free_list_tamper_is_free_refcount(self):
        pager = _pager(prefix_sharing=False)
        t = pager.try_admit([1, 2, 3, 4], 6)
        pager.pool._free.append(t.blocks[0])
        with pytest.raises(SanitizerDivergence) as e:
            pager.sanitizer.verify_full("tamper")
        assert e.value.code == "kv-free-refcount"

    def test_table_maps_freed_block_is_use_after_free(self):
        pager = _pager(prefix_sharing=False)
        t = pager.try_admit([1, 2, 3, 4], 6)
        pager.pool.release(t.blocks[0])  # legal release, mapping kept
        with pytest.raises(SanitizerDivergence) as e:
            pager.sanitizer.verify_full("census")
        assert e.value.code == "kv-use-after-free"

    def test_host_ledger_tamper_is_host_accounting(self):
        pager = _pager(host_blocks=8, prefix_sharing=False)
        pager.host_blocks_used += 1
        with pytest.raises(SanitizerDivergence) as e:
            pager.sanitizer.verify_full("tamper")
        assert e.value.code == "kv-host-accounting"

    def test_double_spill_blocked_before_real_call(self):
        pager = _pager(host_blocks=8, prefix_sharing=False)
        t = pager.try_admit([1, 2, 3, 4, 5], 8)
        assert pager.evict_table_to_host(t, 5) is not None
        ledger = pager.host_blocks_used
        with pytest.raises(SanitizerDivergence) as e:
            pager.evict_table_to_host(t, 5)
        assert e.value.code == "kv-double-spill"
        assert pager.host_blocks_used == ledger   # no double charge
        pager.check_two_tier()

    def test_unadmitted_table_is_use_after_free(self):
        pager = _pager(prefix_sharing=False)
        t = pager.try_admit([1, 2, 3, 4], 6)
        pager.release(t)
        with pytest.raises(SanitizerDivergence) as e:
            pager.sanitizer.note_write(t, 0)
        assert e.value.code == "kv-use-after-free"


# ---------------------------------------------------------------------------
# 2c. differential fuzz: real KVPager vs the shadow after EVERY op
# ---------------------------------------------------------------------------


def _fuzz_two_tier(pager, n_ops, seed):
    """Random admit/write/spill/reload/rollback/release protocol
    traffic; the sanitizer cross-checks inside every wrapped op and we
    run the full census after each one on top."""
    rng = np.random.RandomState(seed)
    san = pager.sanitizer
    bs = pager.block_size
    resident, suspended = [], []
    ops = 0
    while ops < n_ops:
        op = rng.randint(6)
        if op == 0:
            prompt = rng.randint(1, 50, size=rng.randint(2, 9)).tolist()
            t = pager.try_admit(prompt, len(prompt) + 4)
            if t is not None:
                resident.append([t, len(prompt)])
        elif op == 1 and resident:
            i = rng.randint(len(resident))
            t, wl = resident[i]
            if wl < len(t.blocks) * bs:
                san.note_write(t, wl)
                resident[i][1] = wl + 1
        elif op == 2 and resident and pager.host_tier:
            t, wl = resident.pop(rng.randint(len(resident)))
            rec = pager.evict_table_to_host(t, wl)
            if rec is None:
                resident.append([t, wl])
            else:
                suspended.append([t, rec, wl])
        elif op == 3 and suspended:
            t, rec, wl = suspended.pop(rng.randint(len(suspended)))
            moves = pager.reload_table_from_host(t, rec)
            if moves is None:
                suspended.append([t, rec, wl])
            else:
                resident.append([t, wl])
        elif op == 4 and resident:
            i = rng.randint(len(resident))
            t, wl = resident[i]
            if wl >= 2:
                keep = int(rng.randint(1, wl))
                pager.rollback(t, keep, wl)
                resident[i][1] = keep
        elif op == 5 and len(resident) > 2:
            t, _ = resident.pop(rng.randint(len(resident)))
            pager.release(t)
            pager.refund_host_charge(0)
        ops += 1
        san.verify_full("fuzz")
        pager.check_two_tier() if pager.host_tier else pager.pool.check()
    for t, _ in resident:                # free device space first, then
        pager.release(t)                 # reload+release one at a time
    for t, rec, _ in suspended:
        assert pager.reload_table_from_host(t, rec) is not None
        pager.release(t)
    san.verify_full("fuzz-drain")
    assert pager.pool.n_used == 0 and pager.host_blocks_used == 0
    return san.stats()


class TestDifferentialFuzz:
    def test_fuzz_5k_ops_two_tier(self):
        pager = _pager(n_blocks=9, block_size=4, host_blocks=16,
                       prefix_sharing=False)
        stats = _fuzz_two_tier(pager, 5000, seed=24)
        assert stats["ops_mirrored"] >= 5000
        assert stats["tables_live"] == 0

    def test_fuzz_fork_release(self):
        # the beam-shaped op mix: admit / write / CoW fork / release
        pager = _pager(n_blocks=17, block_size=4, prefix_sharing=False)
        rng = np.random.RandomState(7)
        san, bs = pager.sanitizer, pager.block_size
        live = []
        for _ in range(1500):
            op = rng.randint(4)
            if op == 0 and len(live) < 3:
                prompt = rng.randint(1, 50, size=rng.randint(2, 7)).tolist()
                t = pager.try_admit(prompt, len(prompt) + 4)
                if t is not None:
                    live.append([t, len(prompt)])
            elif op == 1 and live:
                i = rng.randint(len(live))
                t, wl = live[i]
                # positions below a fork point are shared: only the
                # frontier block (refcount 1 by CoW) is writable
                if wl < len(t.blocks) * bs:
                    san.note_write(t, wl)
                    live[i][1] = wl + 1
            elif op == 2 and live and len(live) < 4:
                t, wl = live[rng.randint(len(live))]
                try:
                    child = pager.fork(t, wl,
                                       copy_block=lambda s, d: None)
                except InvalidArgumentError:
                    continue                 # pool dry: fork refused
                live.append([child, wl])
            elif op == 3 and len(live) > 1:
                t, _ = live.pop(rng.randint(len(live)))
                pager.release(t)
            san.verify_full("fork-fuzz")
        for t, _ in live:
            pager.release(t)
        san.verify_full("fork-fuzz-drain")
        assert pager.pool.n_used == 0

    @pytest.mark.slow
    def test_fuzz_25k_ops_two_tier_long(self):
        pager = _pager(n_blocks=13, block_size=4, host_blocks=24,
                       prefix_sharing=False)
        stats = _fuzz_two_tier(pager, 25000, seed=2024)
        assert stats["ops_mirrored"] >= 25000


# ---------------------------------------------------------------------------
# 3a. static lint: cache-write aliasing over tick programs
# ---------------------------------------------------------------------------


def _cache_write_fixture():
    from paddle_tpu import layers
    cache = layers.data("cache", shape=[4, 8], dtype="float32")
    new = layers.data("new", shape=[4, 1], dtype="float32")
    pos = layers.data("pos", shape=[], dtype="int64")
    return cache, new, pos


class TestCacheWriteAliasing:
    def test_shipped_paged_builders_clean(self):
        import paddle_tpu as pt
        from paddle_tpu import models
        from paddle_tpu.framework.dataflow import cache_write_aliasing
        models.transformer.transformer_lm_paged_decode_tick(
            n_slots=2, n_blocks=9, block_size=4, blocks_per_req=2,
            vocab=50, d_model=32, d_inner=64, num_heads=4, num_layers=2)
        prog = pt.default_main_program()
        n_writes = sum(1 for b in prog.blocks for op in b.ops
                       if op.type == "paged_cache_write")
        assert n_writes > 0
        assert cache_write_aliasing(prog) == []

    def test_duplicate_writers_flagged(self):
        from paddle_tpu import layers
        import paddle_tpu as pt
        from paddle_tpu.framework.dataflow import cache_write_aliasing
        cache, new, pos = _cache_write_fixture()
        layers.cache_write(cache, new, pos, axis=1, out=cache)
        layers.cache_write(cache, new, pos, axis=1, out=cache)
        diags = cache_write_aliasing(pt.default_main_program())
        assert [d.code for d in diags] == ["serving-cache-write-alias"]

    def test_persistable_fork_flagged(self):
        from paddle_tpu import layers
        import paddle_tpu as pt
        from paddle_tpu.framework.dataflow import cache_write_aliasing
        cache, new, pos = _cache_write_fixture()
        cache.persistable = True
        layers.cache_write(cache, new, pos, axis=1)      # out: fresh temp
        diags = cache_write_aliasing(pt.default_main_program())
        assert "serving-cache-write-alias" in [d.code for d in diags]

    def test_stale_read_after_fork_flagged(self):
        from paddle_tpu import layers
        import paddle_tpu as pt
        from paddle_tpu.framework.dataflow import cache_write_aliasing
        cache, new, pos = _cache_write_fixture()
        layers.cache_write(cache, new, pos, axis=1)      # out: fresh temp
        layers.elementwise_add(cache, cache)             # stale reader
        diags = cache_write_aliasing(pt.default_main_program())
        assert "serving-cache-stale-read" in [d.code for d in diags]


# ---------------------------------------------------------------------------
# 3b. static lint: transfer schedules under speculative rollback windows
# ---------------------------------------------------------------------------


class TestRollbackWindows:
    def test_shipped_policy_clean_with_windows_at_issue(self):
        events = ofl.kv_prefetch_events({"r1": 6, "r2": 9}, 2)
        # the engine re-issues the prefetch after any rollback, so the
        # worst legal window sits exactly at the issue tick
        windows = {ev.var: [ev.issue_tick] for ev in events}
        assert ofl.check_schedule(events, rollback_windows=windows) == []

    def test_straddling_transfer_flagged_by_name(self):
        events = ofl.kv_prefetch_events({"r1": 6}, 2)   # issue 4, read 6
        diags = ofl.check_schedule(events,
                                   rollback_windows={"r1": [5]})
        assert [d.code for d in diags] == ["offload-stale-after-rollback"]

    def test_no_windows_matches_r13_behavior(self):
        events = [ofl.TransferEvent("v", "h2d", 5, 7, 6)]
        diags = ofl.check_schedule(events)
        assert [d.code for d in diags] == ["offload-use-before-arrival"]


# ---------------------------------------------------------------------------
# kill switch: zero-cost when off, participates in the compile cache key,
# and never perturbs the program IR
# ---------------------------------------------------------------------------


class TestKillSwitch:
    def test_attach_absent_when_off(self):
        flags.set_flag("kv_sanitize", False)
        pager = KVPager(n_blocks=9, block_size=4, prefix_sharing=False)
        assert pager.sanitizer is None
        # instance methods are NOT wrapped: the class attributes resolve
        assert "alloc" not in pager.pool.__dict__
        assert "try_admit" not in pager.__dict__

    def test_flag_participates_in_compile_cache_key(self):
        from paddle_tpu.framework.executor import _fusion_flags_key
        on = _fusion_flags_key()
        flags.set_flag("kv_sanitize", False)
        off = _fusion_flags_key()
        assert on != off

    def test_tick_program_identical_on_off(self):
        import paddle_tpu as pt
        from paddle_tpu import models
        from paddle_tpu.core import unique_name

        def build():
            pt.reset_default_programs()
            with unique_name.guard():
                models.transformer.transformer_lm_paged_decode_tick(
                    n_slots=2, n_blocks=9, block_size=4, blocks_per_req=2,
                    vocab=50, d_model=32, d_inner=64, num_heads=4,
                    num_layers=2)
            prog = pt.default_main_program()
            return [(op.type, sorted(op.inputs.items()),
                     sorted(op.outputs.items()))
                    for b in prog.blocks for op in b.ops]

        with_san = build()
        flags.set_flag("kv_sanitize", False)
        without = build()
        assert with_san == without


class TestSnapshotPoolRule:
    """The snapshot pool's rule: a shared span ends at the block its
    snapshot is the state after (`kv-span-past-snapshot`)."""

    def test_a_span_that_ends_at_its_snapshot_passes(self):
        from paddle_tpu.framework.ownership import check_span_snapshot
        check_span_snapshot([3, 4, 9], 9, "admit")
        check_span_snapshot([], None, "admit")

    def test_a_span_past_its_snapshot_is_named(self):
        from paddle_tpu.framework.ownership import check_span_snapshot
        with pytest.raises(OwnershipViolation) as e:
            check_span_snapshot([3, 4, 9], 4, "admit")
        assert e.value.code == "kv-span-past-snapshot" and e.value.block == 9
        assert "kv-span-past-snapshot" in DIAGNOSTICS

    def test_the_pager_refuses_a_snapshot_that_moved_under_its_node(self):
        from paddle_tpu.serving.kv_pager import KVPager
        pager = KVPager(12, 4, n_snapshots=2)
        prompt = list(range(9))
        table = pager.try_admit(prompt, 12)
        for lb in range(2):
            pager.note_block_filled(table, lb, prompt)
        assert pager.take_snapshot_entry(table, 1) == 0
        pager.snapshot_written(table, prompt)
        hit = pager.try_admit(prompt, 12)
        assert hit.shared_len == 8 and hit.snapshot == 0
        # the planted bug: the entry is said to be another block's state
        pager._snap_node[0] = pager.index.node_of(prompt, 0)
        with pytest.raises(OwnershipViolation, match="kv-span-past-snapshot"):
            pager.try_admit(prompt, 12)


class TestWindowReadRule:
    """The window pool's rule: a read through a request's window table spans
    mapped blocks only (`kv-window-read-after-release`, kin to
    `kv-span-past-snapshot`)."""

    def test_a_read_inside_the_mapped_span_passes(self):
        from paddle_tpu.framework.ownership import check_window_read
        check_window_read([0, 0, 7, 9, 4, 0], 2, 4, "map_window")
        check_window_read([0, 0], 0, -1, "try_admit")       # spans nothing

    def test_a_read_that_spans_a_released_block_is_named(self):
        from paddle_tpu.framework.ownership import check_window_read
        with pytest.raises(OwnershipViolation) as e:
            check_window_read([0, 0, 7, 9, 4, 0], 1, 4, "map_window")
        assert e.value.code == "kv-window-read-after-release"
        assert e.value.block == 1 and e.value.op == "map_window"
        assert "kv-window-read-after-release" in DIAGNOSTICS

    def test_the_pager_refuses_a_window_that_starts_below_what_it_released(
            self):
        from paddle_tpu.serving.kv_pager import KVPager
        pager = KVPager(40, 4, window=8, n_window_blocks=12)
        prompt = list(range(30))
        table = pager.try_admit(prompt, 40)
        for pos in range(0, 24, 8):                 # three chunks of eight
            pager.map_window(table, pos, 8)
            pager.slide_window(table, pos + 8)
        assert table.window_lo == 4 and table.window_held == 2
        assert pager.window_blocks_released == 4
        pager.map_window(table, 24)                 # the next position: fine
        # the planted bug: a window counted one block too wide
        pager.window = 13
        with pytest.raises(OwnershipViolation,
                           match="kv-window-read-after-release"):
            pager.map_window(table, 24)
        pager.window = 8
        pager.release(table)
        pager.check_window()
        assert pager.wpool.n_used == 0

    def test_a_span_is_handed_out_only_with_its_whole_tail(self):
        from paddle_tpu.serving.kv_pager import KVPager
        pager = KVPager(40, 4, window=8, n_window_blocks=12)
        prompt = list(range(24))
        table = pager.try_admit(prompt, 28)
        for pos in range(0, 24, 8):
            pager.map_window(table, pos, 8)
            for lb in (pos // 4, pos // 4 + 1):
                pager.note_block_filled(table, lb, prompt)
            pager.slide_window(table, pos + 8)
        pager.release(table)
        # blocks 3, 4, 5 of the six are the span's tail
        assert [pager.index.node_of(prompt, j).wblock is not None
                for j in range(6)] == [False] * 3 + [True] * 3
        hit = pager.try_admit(prompt + [1, 2], 32)
        assert hit.shared_len == 24 and hit.window_lo == 4
        assert hit.window_held == 2 and pager.window_tail_hits == 1
        # the planted bug: the tail said to be there with a block of it gone
        node = pager.index.node_of(prompt, 4)
        pager.wpool.release(node.wblock)
        kept, node.wblock = node.wblock, 0
        assert pager.try_admit(prompt + [3], 32).shared_len < 24
        node.wblock = kept
        pager.wpool.share  # (the pool is the test's to leave unbalanced)
