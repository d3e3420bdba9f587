"""A tick's ids read a launch late (ISSUE 44, serving/engine.py `_plain_tick`):
the tick's decode rows take their token from the ids the tick before left on
the device, and the host reads and commits those after the next launch.

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

import axk1_tiny
import lfm2_tiny
import nemotron_h_tiny
import paddle_tpu as pt
from paddle_tpu import serving
from paddle_tpu.core import flags, unique_name
from paddle_tpu.observability import tracing
from paddle_tpu.serving import EngineClient, EngineServer
from paddle_tpu.serving.kv_pager import HostTierConfig

_F32 = dict(weights_dtype="float32", cache_dtype="float32")
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


def _tiny(mod):
    config = mod.cfg(**_F32)
    return lambda: mod.engine(config, 7, n_slots=6, n_blocks=56)[0]


KINDS = {
    "classic": lambda: _classic(),
    "classic_one_token": lambda: _classic(kv_quant=True),
    "slot": lambda: _classic(serving.ContinuousBatchingEngine),
    "latent_moe": lambda: _tiny(axk1_tiny),
    "conv_gqa": lambda: _tiny(lfm2_tiny),
    "ssm": lambda: _tiny(nemotron_h_tiny),
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
            "experts_touched", "routed_rows", "expert_rows")
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
