"""A tick's ids read a launch late (ISSUE 44, serving/engine.py `_plain_tick`):
the tick's decode rows take their token from the ids the tick before left on
the device, and the host reads and commits those after the next launch. Since
ISSUE 48 such a tick is not waited for either: `step()` returns while it is on
the device, one launch's own time before its end (`_TickPacer`), and the next
tick is launched behind it.

Every claim is held against the EAGER order: the same engine, same weights,
made to commit every tick before `step()` returns (`_late_ok` off, which the
product sets from how the engine was built and nothing else). Six engines:
the classic block chunked, one token a tick (int8 pools) and on the slot
engine; latent attention with routed experts; short convolutions beside
grouped-query attention; state-space layers (float32, the tiny specs of
test_serving_engine / test_latent_moe_engine / test_lfm2_engine /
test_nemotron_h_engine)."""

import threading
import time

import numpy as np
import pytest

from axk1_tiny import TINY as AXK1
from lfm2_tiny import TINY as LFM2
from nemotron_h_tiny import TINY as NEMOTRON_H
import paddle_tpu as pt
from paddle_tpu import serving
from paddle_tpu.core import flags, unique_name
from paddle_tpu.observability import tracing
from paddle_tpu.serving import EngineClient, EngineServer
from paddle_tpu.serving.kv_pager import HostTierConfig

_DIMS = dict(vocab=50, d_model=32, d_inner=64, num_heads=4, num_layers=2)


def _classic(cls=serving.PagedKVEngine, **kw):
    """-> make(): engines of the classic block over ONE scope's weights (the
    first one's start-up makes them, the next shares them by name). Six
    slots: fed a token a tick, all six prompts of the load overlap."""
    scope = pt.Scope()
    if cls is serving.PagedKVEngine:
        kw = dict(block_size=8, n_blocks=56, **kw)

    def make():
        with unique_name.guard():
            return cls(n_slots=6, max_len=64, scope=scope, **_DIMS, **kw)
    return make


def _tiny(tiny):
    config = tiny.cfg(**tiny.F32)
    return lambda: tiny.engine(config, 7, n_slots=6, n_blocks=56)[0]


KINDS = {
    "classic": lambda: _classic(),
    "classic_one_token": lambda: _classic(kv_quant=True),
    "slot": lambda: _classic(serving.ContinuousBatchingEngine),
    "latent_moe": lambda: _tiny(AXK1),
    "conv_gqa": lambda: _tiny(LFM2),
    "ssm": lambda: _tiny(NEMOTRON_H),
}
ROUTED = ("latent_moe", "conv_gqa", "ssm")


def _load(vocab):
    """(step it is submitted before, prompt, max_new): a shared head of two
    blocks, turns that arrive while others decode, never more than the slots
    at once (a late tick's completions free their slots a step later: with a
    queue the two orders would admit on different ticks)."""
    rng = np.random.default_rng(3)
    head = rng.integers(1, vocab, 16).tolist()
    turn = lambda n: head + rng.integers(1, vocab, n).tolist()  # noqa: E731
    return [(0, turn(5), 9), (0, rng.integers(1, vocab, 3).tolist(), 12),
            (4, turn(11), 6), (7, turn(2), 1), (9, turn(19), 8),
            (16, turn(7), 5)]


def _serve(eng, load, eos=None):
    """Run `load` to the end -> (requests, per step: (that step's spans, the
    requests step() returned))."""
    reqs, steps, k = [], [], 0
    while len(reqs) < len(load) or eng.n_active or eng.n_pending:
        for at, prompt, max_new in load[len(reqs):]:
            if at > k:
                break
            reqs.append(eng.submit(prompt, max_new, eos_id=(
                eos.get(len(reqs)) if eos else None)))
        mark = tracing.mark()
        done = eng.step()
        steps.append((tracing.spans_since(mark), done))
        k += 1
        assert k < 200
    return reqs, steps


def _ticks(steps):
    return [s for spans, _ in steps for s in spans if s.name == "engine/tick"]


@pytest.fixture(autouse=True, scope="module")
def _traced_and_sanitized():
    old = {f: flags.get_flag(f) for f in ("trace", "kv_sanitize",
                                          "use_bf16_matmul")}
    flags.set_flag("trace", True)
    flags.set_flag("kv_sanitize", True)
    flags.set_flag("use_bf16_matmul", False)
    yield
    for f, v in old.items():
        flags.set_flag(f, v)


@pytest.fixture(scope="module", params=sorted(KINDS))
def pair(request):
    """One load through the late order and through the eager one."""
    make = KINDS[request.param]()
    late, eager = make(), make()
    assert late._late_ok and eager._late_ok
    eager._late_ok = False
    load = _load(late._builder_dims["vocab"])
    return (request.param, make, load, (late, *_serve(late, load)),
            (eager, *_serve(eager, load)))


def test_tokens_equal_the_eager_orders(pair):
    _, _, load, (late, reqs, _), (eager, want, _) = pair
    assert all(r.done and r.error is None for r in reqs + want)
    assert [len(r.tokens) for r in reqs] == [n for _, _, n in load]
    assert [r.tokens for r in reqs] == [r.tokens for r in want]
    assert [r.shared_len for r in reqs] == [r.shared_len for r in want]
    assert late.n_ticks == eager.n_ticks and late.tokens_out == eager.tokens_out
    assert late.busy_slot_ticks == eager.busy_slot_ticks
    assert eager.late_reads == 0 < late.late_reads


def test_a_first_token_is_read_with_its_tick(pair):
    """The tick a request's first token comes out of is never read late, so
    the token is stamped in the step that launched it, after as many ticks
    as in the eager order; a request that ended is never ticked again."""
    _, _, _, (late, reqs, steps), (_, want, _) = pair
    assert [r.ticks_to_first for r in reqs] == [r.ticks_to_first for r in want]
    assert [r.lane_wait_ticks for r in reqs] == \
        [r.lane_wait_ticks for r in want]
    ticks = _ticks(steps)
    starts = [t.start for t in ticks] + [float("inf")]
    for r in reqs:
        # the step whose interval holds the stamp launched the tick that
        # made the token, and read it at once
        k, = [k for k in range(len(ticks))
              if starts[k] <= r.first_token_pc < starts[k + 1]]
        assert k == r.admitted_tick + r.ticks_to_first - 1
        assert r.request_id in ticks[k].attrs["request_ids"]
        assert ticks[k].attrs["late"] == 0 and ticks[k].end < r.first_token_pc
    # rows a request rode: one a token after the first, as in the eager order
    rides = {r.request_id: 0 for r in reqs}
    for t in _ticks(steps):
        for rid in t.attrs["request_ids"]:
            rides[rid] += 1
    assert [rides[r.request_id] - r.ticks_to_first for r in reqs] == \
        [len(r.tokens) - 1 for r in reqs]


def test_the_counter_is_the_spans_and_most_ticks_engage(pair):
    _, _, _, (late, _, steps), (eager, _, esteps) = pair
    lates = [t.attrs["late"] for t in _ticks(steps)]
    assert late.stats()["dispatch"]["late_reads"] == sum(lates) \
        == late.late_reads
    assert sum(lates) > len(lates) // 2 and lates[-1] == 0
    assert [t.attrs["late"] for t in _ticks(esteps)] == [0] * len(esteps)
    assert eager.stats()["dispatch"]["late_reads"] == 0
    # a launch still hands over ONE host array, `tick_from_last` inside it
    launches = [s for spans, _ in steps for s in spans
                if s.name == "engine/launch"]
    assert {s.attrs["host_args"] for s in launches} == {1}
    assert "tick_from_last" in late._feeds
    # a completion is returned by the step that delivered it: the step after
    # the tick that made it where that tick was read late
    for (spans, done), late_before in zip(steps, [0] + lates):
        if not late_before:
            tick, = [s for s in spans if s.name == "engine/tick"]
            assert all(r.done_pc >= tick.end for r in done)


def test_counts_stay_on_the_tick_that_made_them(pair):
    """What a tick brings back behind its ids (the rows each held expert got)
    lands on ITS `engine/tick` span, read late or not: tick for tick the
    spans of the late order carry the eager order's counts, beside the counts
    taken at the fill (the five router and roofline readers join them)."""
    kind, _, _, (late, _, steps), (eager, _, esteps) = pair
    keys = ("active", "prefill", "mixed", "kv_blocks", "decode_rows",
            "experts_touched", "expert_runs", "routed_rows", "expert_rows")
    got, want = ([{k: t.attrs[k] for k in keys if k in t.attrs}
                  for t in _ticks(s)] for s in (steps, esteps))
    assert got == want
    if kind in ROUTED:
        assert all(t["routed_rows"] == sum(t["expert_rows"]) for t in got)
        assert sum(t.attrs["late"] for t in _ticks(steps)) > 0
        np.testing.assert_array_equal(late.expert_rows, eager.expert_rows)
        assert late.expert_rows.sum() == sum(t["routed_rows"] for t in got)
    else:
        assert not any("expert_rows" in t for t in got)


# the two tests below serve MORE through the pair's engines (a tiny engine
# takes 10-20 s to build): they come after every test that reads the first
# load's counters


def test_eos_rides_one_more_row_and_the_row_is_dropped(pair):
    """`eos` is the one end the host cannot know ahead: read a launch late,
    the request's next row is in flight already. The row is dropped (the
    request neither advances nor emits), slot and table go back in the step
    that read the `eos`, one tick later than in the eager order, and the
    ownership model and the pool's books stay clean."""
    kind, _, load, (late, plain, _), (eager, _, _) = pair
    # requests 0, 1 and 4 stop at the first token they had not emitted before
    # (their first token comes from an eager tick in either order)
    eos = {}
    for k in (0, 1, 4):
        toks = plain[k].tokens
        new = [j for j in range(1, len(toks) - 1) if toks[j] not in toks[:j]]
        if new:
            eos[k] = toks[new[0]]
    base = late.n_ticks
    assert base == eager.n_ticks
    reqs, steps = _serve(late, load, eos)
    want, esteps = _serve(eager, load, eos)
    assert [r.tokens for r in reqs] == [r.tokens for r in want]
    assert all(reqs[k].tokens[-1] == t and t not in reqs[k].tokens[:-1]
               and len(reqs[k].tokens) < load[k][2] for k, t in eos.items())
    assert [r.fed for r in reqs] == [r.fed for r in want]
    ticks, eticks = _ticks(steps), _ticks(esteps)
    # an idle engine's step() launches nothing: the step a tick belongs to
    step_of, estep_of = ([i for i, (spans, _) in enumerate(st)
                          if any(s.name == "engine/tick" for s in spans)]
                         for st in (steps, esteps))
    rode_on = 0
    for k in eos:
        rid, erid = reqs[k].request_id, want[k].request_id
        rows = [i for i, t in enumerate(ticks) if rid in t.attrs["request_ids"]]
        erows = [i for i, t in enumerate(eticks)
                 if erid in t.attrs["request_ids"]]
        assert want[k] in esteps[estep_of[erows[-1]]][1]
        # the tick that made the `eos`, and whether it was read late
        made = (reqs[k].admitted_tick - base + reqs[k].ticks_to_first
                + len(reqs[k].tokens) - 2)
        late_ = ticks[made].attrs["late"]
        rode_on += late_
        # ... then it rode one more row, dropped, and was delivered and
        # released by the step that launched that row: a tick later
        assert rows == list(range(rows[0], made + late_ + 1))
        assert len(rows) == len(erows) + late_
        assert reqs[k] in steps[step_of[made + late_]][1]
        assert reqs[k].slot not in late._active and reqs[k].table is None
    assert rode_on > 0
    assert late.n_active == 0 and late._slots.n_used == 0
    if kind != "slot":
        for eng in (late, eager):
            eng.pager.pool.check()
            eng.pager.sanitizer.verify_full()
        assert late.pager.pool.n_used == eager.pager.pool.n_used
        assert late.pager.sanitizer.stats()["tables_live"] == 0


def test_run_until_idle_flushes_the_last_tick(pair):
    """`n_active` 0 implies every completion delivered: a request leaves
    `_active` when it is delivered, and the tick that leaves the engine
    idle is read at once."""
    _, _, load, (eng, _, _), _ = pair
    seen, read_late = [], eng.late_reads
    reqs = [eng.submit(p, n, on_done=seen.append) for _, p, n in load[:4]]
    closed = 0
    while eng.n_active or eng.n_pending:
        eng.step()
        with eng._lock:
            held = list(eng._active.values())
        closed += sum(r.closed for r in held)
        # whoever is not delivered yet holds its slot
        assert all(not r.done for r in held)
        assert set(seen) == {r for r in reqs if r.done}
    assert closed > 0          # some ended by count on a tick read late
    assert eng._uncommitted is None and len(seen) == 4
    assert [len(r.tokens) for r in reqs] == [n for _, _, n in load[:4]]
    assert eng.run_until_idle() == [] and eng.late_reads > read_late
    more = [eng.submit(p, n) for _, p, n in load[4:]]
    assert sorted(eng.run_until_idle(), key=lambda r: r.rid) == more
    assert eng._uncommitted is None and eng.n_active == 0


@pytest.mark.parametrize("kind", ["classic", "slot", "classic_one_token"])
def test_a_drain_delivers_every_completion(kind):
    make = KINDS[kind]()
    eng, ref = make(), make()
    ref._late_ok = False
    load = _load(eng._builder_dims["vocab"])[:4]
    want = [ref.submit(p, n) for _, p, n in load]
    ref.run_until_idle()
    got = {}
    with EngineServer(eng, metrics_port=None) as srv:
        with EngineClient(*srv.address) as c:
            tags = [c.send_gen(p, max_new=n) for _, p, n in load]
            while eng._rid < len(load):         # the reader took them all
                time.sleep(0.005)
            drained = []
            th = threading.Thread(
                target=lambda: drained.append(srv.drain(timeout=120)))
            th.start()
            for _ in tags:
                tag, toks, _ = c.recv_done()
                got[tag] = toks
            th.join(130)
    assert drained == [True] and eng.n_active == 0
    assert eng._uncommitted is None and eng.late_reads > 0
    assert [got[t] for t in tags] == [r.tokens for r in want]


def test_a_tick_that_raises_fails_the_unread_ticks_requests():
    eng = KINDS["classic"]()()
    reqs = [eng.submit(p, n) for _, p, n in _load(50)[:2]]
    while eng._uncommitted is None:
        eng.step()
    boom = RuntimeError("device lost")

    def launch():
        raise boom
    eng._launch_tick = launch
    with pytest.raises(RuntimeError):
        eng.step()
    assert eng.fail_all(boom) == reqs
    assert eng._uncommitted is None and eng.n_active == 0
    assert all(r.done and r.error is boom for r in reqs)
    with pytest.raises(RuntimeError):
        reqs[0].wait(1)


@pytest.mark.parametrize("built", ["speculative", "host_tier", "topk_k",
                                   "speculative_slot"])
def test_an_engine_that_needs_its_ids_between_ticks_reads_none_late(built):
    kw = {"speculative": dict(speculative=serving.SpecConfig(gamma=2)),
          "speculative_slot": dict(speculative=serving.SpecConfig(gamma=2)),
          "host_tier": dict(host_tier=HostTierConfig(host_blocks=8)),
          "topk_k": dict(topk_k=2)}[built]
    cls = (serving.ContinuousBatchingEngine if built == "speculative_slot"
           else serving.PagedKVEngine)
    eng, plain = _classic(cls, **kw)(), _classic(cls)()
    assert plain._late_ok and not eng._late_ok
    load = _load(50)[:3]
    reqs, steps = _serve(eng, load)
    assert all(r.done and len(r.tokens) == n
               for r, (_, _, n) in zip(reqs, load))
    assert eng.late_reads == 0 == eng.stats()["dispatch"]["late_reads"]
    assert all(t.attrs["late"] == 0 for t in _ticks(steps))
    assert not eng._feeds["tick_from_last"].any()


# -- the next tick launched while this one is on the device (ISSUE 48) -------


class _Fetch:
    """A tick's fetch for the pacer: ready from `at` on, on `clock`."""

    def __init__(self, clock, at):
        self.clock, self.at, self.blocked = clock, at, 0

    def is_ready(self):
        return self.clock.now >= self.at

    def block_until_ready(self):
        self.blocked += 1
        self.clock.now = max(self.clock.now, self.at)


class _Clock:
    """An injected clock: `sleep` moves it on by what was asked and
    `overrun`; every look at it costs `look`."""

    def __init__(self, overrun=0.0, look=0.0):
        self.now, self.overrun, self.look, self.naps = 100.0, overrun, look, []

    def __call__(self):
        self.now += self.look
        return self.now

    def sleep(self, s):
        self.naps.append(s)
        self.now += s + self.overrun


def _pacer(way_back_s=0.0, **kw):
    from paddle_tpu.serving.engine import _TickPacer
    clock = _Clock(**kw)
    pacer = _TickPacer(clock=clock, sleep=clock.sleep, way_back_s=way_back_s)
    clock.naps.clear()                  # the three that took the floor
    return pacer, clock


def _tick(pacer, clock, program, enter, dispatch, device, free=None):
    """One step on the injected clock: entered at `enter`, the launch returns
    `dispatch` later, the device takes the tick up then (or at `free`, when
    the tick before ends) and is done `device` later -> the launch."""
    clock.now = enter
    pacer.entered()
    clock.now = enter + dispatch
    run = pacer.launched(None, None, program)
    start = run.launched_at if free is None else max(free, run.launched_at)
    run.fetch = _Fetch(clock, start + device)
    return run


def test_the_hold_ends_one_lead_before_the_device_is_done():
    """target = (the later of the tick before done and the launch's return)
    + the program's device time - (own stretch + caller's gap)."""
    pacer, clock = _pacer()
    # two ticks waited out: device 8, dispatch 1, the caller 0.25 between
    a = _tick(pacer, clock, "main", 0.0, 1.0, 8.0)
    assert pacer.hold_until(a) is None           # never seen: waited out
    pacer.wait_for(a)
    assert a.fetch.blocked == 1 and clock.now == 9.0
    assert list(pacer.device_s["main"]) == [8.0] and pacer.free_at == 9.0
    pacer.left()
    b = _tick(pacer, clock, "main", 9.25, 1.0, 8.0)
    assert pacer.own.mean == 1.0 and pacer.gap.mean == 0.25
    assert pacer.lead_s() == 1.25
    # launched on an idle device: from the launch's return
    assert pacer.hold_until(b) == 10.25 + 8.0 - 1.25
    assert pacer.until(b.fetch, pacer.hold_until(b)) is False
    assert clock.now == 17.0 and clock.naps[0] == pytest.approx(6.75)
    assert sum(clock.naps) == pytest.approx(6.75)
    pacer.left()
    # the next launch returns as the device comes free (17 + 0.25 + 1.0),
    # behind a tick that is still running: from that tick's end
    c = _tick(pacer, clock, "main", 17.25, 1.0, 8.0, free=18.25)
    pacer.wait_for(b)
    assert pacer.free_at == 18.25 and list(pacer.device_s["main"]) == [8.0] * 2
    assert pacer.hold_until(c) == 18.25 + 8.0 - 1.25
    # a wait on a tick already seen done is no wait
    blocked = b.fetch.blocked
    pacer.wait_for(b)
    assert b.fetch.blocked == blocked


def test_the_hold_ends_at_once_on_a_tick_found_done():
    pacer, clock = _pacer(look=0.001)
    a = _tick(pacer, clock, "main", 0.0, 1.0, 8.0)
    pacer.wait_for(a)
    pacer.left()
    # the tick takes 2 where 8 were seen last: found ready before the target
    b = _tick(pacer, clock, "main", 9.5, 1.0, 2.0)
    clock.now = 13.0
    target = pacer.hold_until(b)
    assert target > 13.0
    assert pacer.until(b.fetch, target) is True
    assert clock.naps == [] and clock.now < 13.01
    # ... and past its target a tick is not looked at for long either
    c = _tick(pacer, clock, "main", 14.0, 1.0, 50.0)
    clock.now = 40.0
    assert pacer.until(c.fetch, 30.0) is False and clock.naps == []


def test_decode_and_mixed_ticks_are_kept_apart():
    pacer, clock = _pacer()
    t = 0.0
    for program, device in (("main", 1.5), ("mixed", 3.8), ("main", 1.5)):
        run = _tick(pacer, clock, program, t, 0.5, device)
        pacer.wait_for(run)
        pacer.left()
        t = clock.now + 0.1
    assert sorted(pacer.device_s) == ["main", "mixed"]
    assert list(pacer.device_s["main"]) == pytest.approx([1.5, 1.5])
    assert list(pacer.device_s["mixed"]) == pytest.approx([3.8])
    lead = pacer.lead_s()
    for program, device in (("mixed", 3.8), ("main", 1.5)):
        run = _tick(pacer, clock, program, t, 0.5, device)
        assert pacer.hold_until(run) == pytest.approx(t + 0.5 + device - lead)
        pacer.wait_for(run)
        t = clock.now + 0.1


def test_one_tick_seen_across_a_stall_does_not_lengthen_the_hold():
    """The hold goes by the least of the last three device times seen: a
    tick seen done late (the host stood) would hold the next one past its
    end, the chip idle, and be seen long again."""
    pacer, clock = _pacer()
    t = 0.0
    for device in (2.0, 2.0, 30.0):             # the third across a stall
        run = _tick(pacer, clock, "main", t, 0.5, device)
        pacer.wait_for(run)
        pacer.left()
        t = clock.now + 0.1
    run = _tick(pacer, clock, "main", t, 0.5, 2.0)
    assert pacer.hold_until(run) == pytest.approx(t + 0.5 + 2.0
                                                  - pacer.lead_s())
    # ... and a tick that really got longer is believed after three
    for device in (5.0, 5.0, 5.0, 5.0):
        pacer.wait_for(run)
        pacer.left()
        t = clock.now + 0.1
        run = _tick(pacer, clock, "main", t, 0.5, device)
    assert pacer.hold_until(run) == pytest.approx(t + 0.5 + 5.0
                                                  - pacer.lead_s())


def test_a_compile_inside_a_launch_does_not_stay_in_the_lead():
    from paddle_tpu.serving.engine import _Running
    r = _Running()
    r.add(5000.0)                       # the first launch compiled
    r.add(1.0)
    assert r.mean == 1.0                # far under what was believed
    for _ in range(8):
        r.add(1.0)
    r.add(4000.0)                       # the second program compiles
    assert r.mean == pytest.approx(1.125) and r.dev == pytest.approx(0.125)
    r.add(0.9)
    assert 1.0 < r.mean < 1.125         # a reading nearby moves it an eighth


def test_seen_done_is_taken_back_by_the_ids_way_back():
    """The host sees a tick done later than the device was: by the way back
    the engine measured when it was built (`seen_done_lag_s`)."""
    pacer, clock = _pacer(way_back_s=0.4)
    a = _tick(pacer, clock, "mixed", 0.0, 1.0, 4.0)
    pacer.done(a, 5.4)                  # the `np.asarray` returned at 5.4
    assert a.done_at == pytest.approx(5.0) == pacer.free_at
    assert list(pacer.device_s["mixed"]) == pytest.approx([4.0])


class _Ids(_Fetch):
    """A fetch whose ids reach the host `way` after the tick's end: a read
    before that waits for them, one after it costs `found` on the clock."""

    def __init__(self, clock, at, way, found=1e-5):
        super().__init__(clock, at)
        self.way, self.found = way, found

    def __array__(self, *a, **kw):
        self.clock.now = max(self.clock.now, self.at + self.way) + self.found
        return np.zeros((2, 1), np.int32)


def test_a_read_that_costs_nothing_leaves_the_targets_where_they_were():
    """The ids' copy starts with the launch, so the read of a tick that is
    done finds them on the host and takes microseconds. What `done` takes
    off "seen done" is the way back the engine measured, not the read's
    time: `free_at` and the hold's target are the parent's, whose reads took
    the way back's length (a pacer that went by the read's own time would
    see every tick done 0.4 later, and launch as late)."""
    way = 0.4
    pacer, clock = _pacer(way_back_s=way)
    t = 0.0
    for _ in range(4):                  # each waited out, then read: instant
        run = _tick(pacer, clock, "main", t, 1.0, 8.0)
        run.fetch = _Ids(clock, run.fetch.at, way)
        clock.now = run.fetch.at + way  # the block returns the way back late
        pacer.done(run, clock.now)
        ids, found = pacer.read(run.fetch)
        assert found and ids.shape == (2, 1)
        assert clock.now == pytest.approx(run.fetch.at + way + 1e-5)
        assert pacer.free_at == pytest.approx(run.fetch.at) == run.done_at
        pacer.left()
        t = clock.now + 0.25
    assert list(pacer.device_s["main"]) == pytest.approx([8.0] * 3)
    nxt = _tick(pacer, clock, "main", t, 1.0, 8.0)
    assert pacer.hold_until(nxt) == pytest.approx(
        t + 1.0 + 8.0 - pacer.lead_s())
    # behind a tick that is still running: from THAT tick's end, as it was
    pacer.done(nxt, nxt.fetch.at + way)
    behind = _tick(pacer, clock, "main", nxt.fetch.at - 2.0, 1.0, 8.0,
                   free=nxt.fetch.at)
    assert pacer.hold_until(behind) == pytest.approx(
        nxt.fetch.at + 8.0 - pacer.lead_s())


def test_found_is_the_read_that_did_not_wait():
    pacer, clock = _pacer(way_back_s=4e-4)
    limit = pacer.FOUND_WITHIN_S
    done_long_ago = _Ids(clock, clock.now - 1.0, 3e-4)
    _, found = pacer.read(done_long_ago)
    assert found is True
    just_done = _Ids(clock, clock.now, 3e-4)        # the copy is on its way
    t = clock.now
    _, found = pacer.read(just_done)
    assert found is False and clock.now - t >= 3e-4 > limit


def test_a_sleep_is_cut_by_twice_what_sleeps_overran():
    """The hold sleeps once, cut short by twice what its sleeps overran, and
    spins from there: no sleep ends past the target, and a stretch shorter
    than the host's shortest sleep and that cut is all spin."""
    pacer, clock = _pacer(overrun=0.3, look=0.01)
    floor = pacer.nap_floor_s
    assert 0.3 < floor < 0.35 and pacer.oversleep.mean == floor
    a = _tick(pacer, clock, "main", 0.0, 1.0, 500.0)
    clock.now = 10.0
    assert pacer.until(a.fetch, 20.0) is False
    assert clock.naps == [pytest.approx(10 - 0.01 - 2 * floor)]
    assert 20.0 <= clock.now < 20.02
    assert pacer.oversleep.mean == pytest.approx(0.31, abs=0.02)
    # what is left is under the cut and the floor: no sleep at all
    clock.now, clock.naps = 50.0, []
    assert pacer.until(a.fetch, 50.9) is False
    assert clock.naps == [] and 50.9 <= clock.now < 50.92
    # a sleep across a stall of the host teaches little
    clock.now, clock.naps, clock.overrun = 60.0, [], 100.0
    assert pacer.until(a.fetch, 70.0) is False
    assert len(clock.naps) == 1 and pacer.oversleep.mean < 0.4


@pytest.mark.parametrize("kind", ["classic", "slot", "classic_one_token"])
def test_one_tick_at_most_is_unread_and_none_on_an_idle_engine(kind):
    """When `step()` returns at most ONE launched tick is unread, the tick
    before it is read (never is a tick launched behind one that has not
    started), and an arrival submitted between two steps rides the very next
    launch."""
    eng = KINDS[kind]()()
    load = _load(eng._builder_dims["vocab"])
    launches, read_at_launch = [], []
    launch = eng._launch_tick

    def counted():
        # at a launch: the tick before may be on the device, the one before
        # THAT was read and committed by the step that launched it
        read_at_launch.append(
            (len(launches), None if eng._uncommitted is None
             else eng._uncommitted.fetch))
        fetches = launch()
        launches.append(fetches[0])
        return fetches
    eng._launch_tick = counted
    reqs, k, seen_unread = [], 0, 0
    while len(reqs) < len(load) or eng.n_active or eng.n_pending:
        for at, prompt, max_new in load[len(reqs):]:
            if at > k:
                break
            reqs.append(eng.submit(prompt, max_new))
        n_before = len(launches)
        eng.step()
        k += 1
        assert len(launches) == n_before + 1
        # six slots for six requests: whoever arrived is in THIS launch
        assert eng.n_pending == 0 and all(r.slot is not None or r.done
                                          for r in reqs)
        run = eng._uncommitted
        if run is not None:
            seen_unread += 1
            assert run.fetch is launches[-1]     # the newest, and no other
            assert eng.n_active > 0
        else:
            assert all(f.is_ready() for f in launches)
        # every tick but the newest was read before `step()` returned
        assert all(f.is_ready() for f in launches[:-1])
    assert seen_unread > 0 and eng._uncommitted is None and eng.n_active == 0
    for n, fetch in read_at_launch:
        if fetch is not None:
            assert fetch is launches[n - 1]      # only the tick before
    assert all(r.done and len(r.tokens) == n for r, (_, _, n)
               in zip(reqs, load))
    assert eng.run_until_idle() == []


def test_an_arrival_between_two_steps_rides_the_next_launch():
    eng = KINDS["classic"]()()
    load = _load(50)
    first = eng.submit(load[0][1], 12)
    while eng._uncommitted is None:
        eng.step()
    assert not first.done                        # a tick is on the device
    late = eng.submit(load[1][1], 4)
    ticks = eng.n_ticks
    mark = tracing.mark()
    eng.step()
    tick, = [s for s in tracing.spans_since(mark) if s.name == "engine/tick"]
    assert late.request_id in tick.attrs["request_ids"]
    assert late.admitted_tick == ticks and late.slot is not None
    eng.run_until_idle()
    assert late.done and first.done


def test_the_counter_is_the_spans_and_a_tick_runs_ahead_only_behind_a_late_one(
        pair):
    _, _, _, (late, _, steps), (eager, _, esteps) = pair
    ticks = _ticks(steps)
    ahead = [t.attrs["ahead"] for t in ticks]
    lates = [t.attrs["late"] for t in ticks]
    assert late.stats()["dispatch"]["run_ahead"] >= sum(ahead)
    assert all(a <= before for a, before in zip(ahead, [0] + lates))
    assert eager.stats()["dispatch"]["run_ahead"] == 0
    assert [t.attrs["ahead"] for t in _ticks(esteps)] == [0] * len(esteps)
    # every wait for the device is inside `engine/wait`, and so is the hold
    for spans, _ in steps:
        waits = [s for s in spans if s.name == "engine/wait"]
        for s in spans:
            if s.name in ("engine/device_wait", "engine/hold",
                          "engine/copy_back"):
                assert any(w.start <= s.start and s.end <= w.end
                           for w in waits)
    holds = [s for spans, _ in steps for s in spans
             if s.name == "engine/hold"]
    assert holds and all(s.attrs["early"] in (0, 1) for s in holds)


def test_a_tick_that_is_still_running_is_waited_for_after_the_next_launch():
    """Run-ahead proper, on an injected clock (the device made slow: a tick
    is done 50 ms after the later of its launch and the tick before's end,
    a launch costs the host 2-3 ms): the hold ends while
    the tick runs, the next launch is queued behind it (`ahead` 1), its wait
    comes after that launch, tokens as in the eager order."""
    make = KINDS["classic"]()
    eng, ref = make(), make()
    ref._late_ok = False
    load = _load(50)[:3]
    want = [ref.submit(p, n) for _, p, n in load]
    ref.run_until_idle()
    clock = _Clock(look=1e-4)
    eng._pacer = type(eng._pacer)(clock=clock, sleep=clock.sleep)
    free, order = [clock.now], []

    class Slow(_Fetch):
        def __init__(self, fetch):
            free[0] = max(free[0], clock.now) + 0.05
            super().__init__(clock, free[0])
            self.fetch = fetch

        def block_until_ready(self):
            order.append(("waited", self))
            super().block_until_ready()

        def copy_to_host_async(self):
            pass

        def __array__(self, *a, **kw):
            super().block_until_ready()
            return np.asarray(self.fetch)

    launch = eng._launch_tick

    def slow_launch():
        # the launch costs the host 2 or 3 ms in turn: a lead, and a
        # deviation for the lead's margin
        clock.now += 0.002 + 0.001 * (eng.target_forwards % 2)
        fetches = [Slow(f) for f in launch()]
        order.append(("launched", fetches[0]))
        return fetches
    eng._launch_tick = slow_launch
    reqs = [eng.submit(p, n) for _, p, n in load]
    mark = tracing.mark()
    eng.run_until_idle()
    assert [r.tokens for r in reqs] == [r.tokens for r in want]
    spans = tracing.spans_since(mark)
    ticks = [s for s in spans if s.name == "engine/tick"]
    ahead = [t.attrs["ahead"] for t in ticks]
    lates = [t.attrs["late"] for t in ticks]
    assert eng.run_ahead == sum(ahead) == \
        eng.stats()["dispatch"]["run_ahead"]
    # a tick runs ahead only behind a held one, which it found running; a
    # tick waited out (the first late tick of a program) is found done
    holds = [s for s in spans if s.name == "engine/hold"]
    assert 2 < sum(ahead) <= len(holds) <= sum(lates) == eng.late_reads
    assert not any(s.attrs["early"] for s in holds)
    # the tick a launch was queued behind is waited for after that launch
    launched = [f for what, f in order if what == "launched"]
    for k in range(1, len(launched)):
        if ahead[k]:
            assert order.index(("launched", launched[k])) \
                < order.index(("waited", launched[k - 1]))


@pytest.mark.parametrize("kind", ["classic", "slot"])
def test_fail_all_with_a_tick_on_the_device(kind):
    eng = KINDS[kind]()()
    reqs = [eng.submit(p, n) for _, p, n in _load(50)[:2]]
    while eng._uncommitted is None:
        eng.step()
    boom = RuntimeError("device lost")
    assert sorted(eng.fail_all(boom), key=lambda r: r.rid) == reqs
    assert eng._uncommitted is None and eng.n_active == 0 == eng.n_pending
    assert all(r.done and r.error is boom for r in reqs)
    with pytest.raises(RuntimeError):
        eng.submit([1, 2], 2)


# -- a tick's ids start their way back when the tick is launched (ISSUE 55) --


class _Recorded:
    """A tick's fetch that writes down what is done to it, in `log`."""

    def __init__(self, fetch, log):
        self.fetch, self.log = fetch, log

    def copy_to_host_async(self):
        self.log.append(("copy", self))
        self.fetch.copy_to_host_async()

    def is_ready(self):
        return self.fetch.is_ready()

    def block_until_ready(self):
        self.log.append(("wait", self))
        self.fetch.block_until_ready()

    def __array__(self, *a, **kw):
        self.log.append(("read", self))
        return np.asarray(self.fetch)


def _record_launches(eng):
    """-> the log of every launch of `eng` from here on and of what was done
    to its ids."""
    log, launch = [], eng._launch_tick

    def recorded():
        fetches = list(launch())
        fetches[0] = _Recorded(fetches[0], log)
        log.append(("launch", fetches[0]))
        return fetches
    eng._launch_tick = recorded
    return log


@pytest.mark.parametrize("kind", ["slot", "classic", "classic_one_token",
                                  "ssm"])
def test_the_copy_starts_at_the_launch_and_tokens_are_the_eager_orders(kind):
    """Every launch enqueues its ids' copy to the host ONCE, before anything
    waits for the tick or reads it, late ticks and eager ones alike, with
    every eager tick realized in its two parts (the sampled split tick); the
    order of launches, reads and commits is the parent's, so the tokens are
    those of the order that commits every tick."""
    make = KINDS[kind]()
    eng, ref = make(), make()
    ref._late_ok = False
    eng.WAIT_SPLIT_EVERY = 1
    load = _load(eng._builder_dims["vocab"])
    log = _record_launches(eng)
    want, _ = _serve(ref, load)
    mark = tracing.mark()
    reqs, _ = _serve(eng, load)
    assert [r.tokens for r in reqs] == [r.tokens for r in want]
    launched = [f for what, f in log if what == "launch"]
    assert len(launched) == eng.n_ticks and eng.late_reads > 0
    spans = tracing.spans_since(mark)
    lates = [s.attrs["late"] for s in spans if s.name == "engine/tick"]
    for k, fetch in enumerate(launched):
        mine = [what for what, f in log if f is fetch]
        assert mine[:2] == ["launch", "copy"] and mine.count("copy") == 1
        assert mine.count("read") == 1
        # a late tick is read behind the NEXT launch, an eager one at once
        read_at = log.index(("read", fetch))
        nxt = (log.index(("launch", launched[k + 1]))
               if k + 1 < len(launched) else len(log))
        assert (nxt < read_at) == bool(lates[k])
    backs = [s for s in spans if s.name == "engine/copy_back"]
    assert len(backs) == len(launched)      # every read is one: split or late
    assert all(s.attrs["found"] in (0, 1) for s in backs)
    assert eng.stats()["dispatch"]["copies_found"] == \
        sum(s.attrs["found"] for s in backs) == eng.copies_found


def test_found_on_the_span_is_the_read_that_did_not_wait():
    """On an injected clock: the ids reach the host 0.3 ms after their tick's
    end. A tick read long after its end (the host's period is the longer
    one) is `found`; one read the instant it is seen done is not."""
    make = KINDS["classic"]()
    eng = make()
    clock = _Clock(look=1e-6)
    eng._pacer = type(eng._pacer)(clock=clock, sleep=clock.sleep,
                                  way_back_s=3e-4)
    host_s = [0.0]
    free = [clock.now]

    class OnItsWay(_Ids):
        def __init__(self, fetch):
            free[0] = max(free[0], clock.now) + 0.002
            super().__init__(clock, free[0], 3e-4)
            self.fetch = fetch

        def copy_to_host_async(self):
            pass

        def __array__(self, *a, **kw):
            super().__array__()
            return np.asarray(self.fetch)

    launch = eng._launch_tick

    def timed_launch():
        clock.now += host_s[0]
        return [OnItsWay(f) for f in launch()]
    eng._launch_tick = timed_launch
    load = _load(50)[:3]
    # no eager tick is realized in two parts (the first would be): such a
    # read comes the instant its tick is seen done, by construction
    eng.n_ticks, eng.WAIT_SPLIT_EVERY = 1, 1 << 30

    def founds():
        mark = tracing.mark()
        for _, p, n in load:
            eng.submit(p, n)
        eng.run_until_idle()
        spans = tracing.spans_since(mark)
        late = {s.attrs.get("late") for s in spans if s.name == "engine/tick"}
        assert late == {0, 1}
        return [s.attrs["found"] for s in spans
                if s.name == "engine/copy_back"]
    # a launch that costs the host 5 ms behind a 2 ms tick: every late read
    # comes long after its tick's end
    host_s[0] = 0.005
    found = founds()
    assert found and all(found)
    # a launch that costs nothing: the read comes the instant the wait for
    # the tick returns, its copy still on its way
    host_s[0] = 0.0
    found = founds()
    assert found and not any(found)
    assert eng.copies_found == eng.stats()["dispatch"]["copies_found"] > 0
