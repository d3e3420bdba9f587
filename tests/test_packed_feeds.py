"""A bound step hands the compiled function ONE host array (ISSUE 33).

- `PreparedStep` lays every host feed of 4-byte items on the device, and the
  seed, out in one int32 buffer and cuts it apart inside the jitted step:
  bit-equal to feeding each array by itself (`Executor.run`), with int64,
  int32, float32 and scalar feeds, a feed the pack cannot hold, a feed that
  is on the device already, the synthesized batch mask, in-place fills
  between calls and a `refresh_state()` in the middle;
- a second step over the leading feeds shares the buffer (`bind(share=)`);
- the paged engine, chunked and one-token, emits the tokens of the unpacked
  path (its two programs run through `Executor.run`, a feed an array), and a
  launch of every bound step hands over one host array: `stats()["dispatch"]`
  and the `engine/launch` span's `host_args`;
- speculative and top-k engines likewise.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers
from paddle_tpu.core import flags
from paddle_tpu.core.enforce import InvalidArgumentError
from paddle_tpu.framework.offload import HostTierConfig
from paddle_tpu.framework.scope import Scope
from paddle_tpu.models import transformer
from paddle_tpu.observability import tracing
from paddle_tpu.serving import (ContinuousBatchingEngine, PagedKVEngine,
                                SpecConfig, paged_beam_search)

pytestmark = pytest.mark.quick


# -- the bound step against a feed an array ----------------------------------

B, D = 4, 8
FEEDS = {                      # name -> (shape, dtype as declared and fed)
    "ids": ((B, 1), "int64"),
    "x": ((B, D), "float32"),
    "gate": ((B, D), "float16"),         # 2-byte items: not packable
    "bits": ((B,), "int32"),
    "shift": ((), "int32"),
}


def _program(seed=11):
    """Every feed reaches the fetch; dropout draws from the seed, SGD makes
    the weights read-write state."""
    pt.reset_default_programs()
    pt.reset_global_scope()
    v = {n: layers.data(name=n, shape=list(shape), dtype=dtype,
                        append_batch_size=False)
         for n, (shape, dtype) in FEEDS.items()}
    h = layers.elementwise_mul(v["x"], layers.cast(v["gate"], "float32"))
    h = layers.elementwise_add(h, layers.cast(v["ids"], "float32"))
    h = layers.dropout(layers.fc(h, size=D, name="pk_fc"), dropout_prob=0.5)
    per_row = layers.elementwise_add(
        layers.reduce_sum(h, dim=[1]), layers.cast(v["bits"], "float32"))
    out = layers.elementwise_add(
        per_row, layers.cast(layers.reshape(v["shift"], [1]), "float32"))
    loss = layers.mean(out)
    pt.optimizer.SGD(learning_rate=0.05).minimize(loss)
    pt.default_main_program().random_seed = seed
    pt.Executor().run(pt.default_startup_program())
    return pt.default_main_program(), [out, loss]


def _feed(rng):
    return {"ids": rng.randint(-5, 2 ** 20, (B, 1)).astype("int64"),
            "x": rng.randn(B, D).astype("float32"),
            "gate": rng.rand(B, D).astype("float16"),
            "bits": rng.randint(-2 ** 20, 2 ** 20, (B,)).astype("int32"),
            "shift": np.asarray(rng.randint(-3, 3), "int32")}


def _twin_scope():
    """A copy of the global scope's arrays (a step donates what it holds)."""
    scope, g = Scope(), pt.global_scope()
    for n in g.local_var_names():
        scope.set_var(n, jnp.array(g.get(n), copy=True))
    return scope


def test_bound_step_is_bit_equal_to_a_feed_an_array():
    rng = np.random.RandomState(0)
    program, fetches = _program()
    feed = _feed(rng)
    ref_scope, run_scope, bound_scope = (_twin_scope() for _ in range(3))
    ref_exe, run_exe, bound_exe = pt.Executor(), pt.Executor(), pt.Executor()
    run_step = run_exe.prepare(program, dict(feed), fetches, run_scope)
    bound_feed = {n: a.copy() for n, a in feed.items()}
    bound = bound_exe.prepare(program, dict(bound_feed), fetches,
                              bound_scope).bind(bound_feed)
    assert bound.host_args == 2              # the pack, and the float16 feed
    assert bound_feed["gate"].dtype == np.float16
    assert bound_feed["ids"].dtype == np.int32      # as on the device
    assert bound_feed["x"].dtype == np.float32
    assert bound_feed["shift"].shape == ()

    def check(tick):
        want = ref_exe.run(program, feed=dict(feed), fetch_list=fetches,
                           scope=ref_scope)
        got_run = run_step.run(dict(feed), return_numpy=True)
        got_bound = [np.asarray(f) for f in bound.run_bound()]
        for w, a, b in zip(want, got_run, got_bound):
            np.testing.assert_array_equal(w, a, err_msg=f"run, call {tick}")
            np.testing.assert_array_equal(w, b, err_msg=f"bound, call {tick}")

    for tick in range(6):
        check(tick)
        if tick == 2:
            # a plain step in the middle replaces the read-write state in
            # each scope: the bound step re-points itself at it
            plain = _feed(rng)
            for exe, scope in ((ref_exe, ref_scope), (run_exe, run_scope),
                               (bound_exe, bound_scope)):
                exe.run(program, feed=dict(plain), fetch_list=fetches,
                        scope=scope)
            bound.refresh_state()
        new = _feed(rng)
        for n, a in new.items():             # filled in place, no rebind
            feed[n] = a
            bound_feed[n][...] = a
    # the weights moved with every call, and moved alike
    moved = [n for n in ref_scope.local_var_names() if n.startswith("pk_fc")]
    assert len(moved) == 2
    for n in moved:
        np.testing.assert_array_equal(np.asarray(ref_scope.get(n)),
                                      np.asarray(bound_scope.get(n)))
        assert (np.asarray(ref_scope.get(n))
                != np.asarray(pt.global_scope().get(n))).any()


def test_run_packs_on_the_way_in_and_interleaves_with_bound_calls():
    """`paged_beam_search`'s pattern: `run(feed)` on a bound step, with the
    bound views or with other arrays of the prepared signature."""
    rng = np.random.RandomState(1)
    program, fetches = _program()
    feed = _feed(rng)
    ref_scope, scope = _twin_scope(), _twin_scope()
    ref_exe, exe = pt.Executor(), pt.Executor()
    bound_feed = {n: a.copy() for n, a in feed.items()}
    step = exe.prepare(program, dict(feed), fetches, scope).bind(bound_feed)
    calls = [lambda: step.run_bound(), lambda: step.run(bound_feed),
             lambda: step.run(dict(feed)), lambda: step.run_bound()]
    for k, call in enumerate(calls):
        want = ref_exe.run(program, feed=dict(feed), fetch_list=fetches,
                           scope=ref_scope)
        for w, g in zip(want, call()):
            np.testing.assert_array_equal(w, np.asarray(g), err_msg=str(k))


def test_what_stays_an_argument_of_its_own():
    """A feed already on the device and the synthesized batch mask are not
    host arrays: they stay arguments, and cost a launch no transfer."""
    pt.reset_default_programs()
    pt.reset_global_scope()
    x = layers.data(name="x", shape=[6])
    w = layers.data(name="w", shape=[6])
    mask = layers.batch_row_mask()
    per_ex = layers.reduce_sum(layers.elementwise_mul(x, w), dim=[1])
    loss = layers.reduce_sum(layers.elementwise_mul(per_ex, mask))
    exe = pt.Executor()
    exe.run(pt.default_startup_program())
    rng = np.random.RandomState(2)
    feed = {"x": rng.rand(4, 6).astype("float32"),
            "w": jnp.asarray(rng.rand(4, 6).astype("float32"))}
    want = exe.run(feed=dict(feed), fetch_list=[loss])[0]
    step = exe.prepare(pt.default_main_program(), dict(feed), [loss])
    assert list(step._views) == ["x"]
    np.testing.assert_array_equal(
        step.run(dict(feed), return_numpy=True)[0], want)
    step.bind(feed)
    assert step.host_args == 1               # x and the seed, in the pack
    assert isinstance(feed["w"], jax.Array)  # left as it was
    np.testing.assert_array_equal(np.asarray(step.run_bound()[0]), want)
    hlo = step.compiled_hlo()
    assert hlo.count("parameter(") >= 3 and "s32[25]" in hlo   # 1 + 4 * 6


def test_a_second_step_shares_the_leading_span():
    rng = np.random.RandomState(3)
    program, fetches = _program()
    feed = _feed(rng)
    exe, scope = pt.Executor(), _twin_scope()
    whole = exe.prepare(program, dict(feed), fetches, scope).bind(feed)
    # a program over the first two feeds alone
    pt.reset_default_programs()
    ids = layers.data(name="ids", shape=[B, 1], dtype="int64",
                      append_batch_size=False)
    x = layers.data(name="x", shape=[B, D], dtype="float32",
                    append_batch_size=False)
    out = layers.elementwise_add(x, layers.cast(ids, "float32"))
    lead_feed = {"ids": feed["ids"], "x": feed["x"]}
    lead = exe.prepare(pt.default_main_program(), dict(lead_feed), [out],
                       scope).bind(lead_feed, share=whole)
    assert lead_feed["ids"] is feed["ids"] and lead_feed["x"] is feed["x"]
    assert lead._buf.ctypes.data == whole._buf.ctypes.data
    assert lead._buf.size == 1 + B + B * D < whole._buf.size
    feed["x"][...] = 2.0                       # one fill, both steps see it
    feed["ids"][...] = 3
    np.testing.assert_array_equal(np.asarray(lead.run_bound()[0]),
                                  np.full((B, D), 5.0, "float32"))
    # the other way round the feeds do not lead: refused
    with pytest.raises(InvalidArgumentError, match="start with"):
        whole.bind(feed, share=lead)


def test_a_mesh_executors_prepared_step_packs_too():
    """`ParallelExecutor.prepare`: the pack is replicated like the seed, a
    feed cut out of it is constrained to the sharding it had as an
    argument."""
    from paddle_tpu.parallel.parallel_executor import ParallelExecutor
    pt.reset_default_programs()
    pt.reset_global_scope()
    x = layers.data(name="x", shape=[6])
    y = layers.data(name="y", shape=[1], dtype="int64")
    per_ex = layers.elementwise_add(
        layers.reduce_sum(layers.fc(x, size=3), dim=[1]),
        layers.cast(layers.reshape(y, [-1]), "float32"))
    loss = layers.mean(per_ex)
    pt.Executor().run(pt.default_startup_program())
    pe = ParallelExecutor(loss_name=loss.name)
    rng = np.random.RandomState(4)
    feed = {"x": rng.rand(16, 6).astype("float32"),
            "y": rng.randint(0, 9, (16, 1)).astype("int64")}
    want = pe.run(feed=dict(feed), fetch_list=[loss, per_ex])
    step = pe.prepare(pt.default_main_program(), dict(feed), [loss, per_ex])
    for w, g in zip(want, step.run(dict(feed), return_numpy=True)):
        np.testing.assert_allclose(g, w, rtol=1e-6)
    step.bind(feed)
    assert step.host_args == 1
    for w, g in zip(want, step.run_bound()):
        np.testing.assert_allclose(np.asarray(g), w, rtol=1e-6)


# -- the engines --------------------------------------------------------------

_DIMS = dict(vocab=50, d_model=32, d_inner=64, num_heads=2, num_layers=2)
_MAX_LEN, _BS = 64, 4


def _trained_scope(seed=3):
    pt.reset_default_programs()
    pt.reset_global_scope()
    with pt.core.unique_name.guard():
        transformer.transformer_lm(max_len=_MAX_LEN, is_test=True, **_DIMS)
    pt.default_startup_program().random_seed = seed
    pt.Executor().run(pt.default_startup_program())
    return pt.global_scope()


def _requests(seed=5):
    rng = np.random.RandomState(seed)
    # shorter than a block, two chunks and a bit, a shared prefix
    system = [int(t) for t in rng.randint(1, _DIMS["vocab"], 2 * _BS)]
    lens = (1, 3, 16, 35, 9)
    prompts = [[int(t) for t in rng.randint(1, _DIMS["vocab"], n)]
               for n in lens]
    return prompts + [system + prompts[1], system + prompts[2]]


def _gen(eng, prompts, max_new=6):
    reqs = [eng.submit(list(p), max_new=max_new) for p in prompts]
    eng.run_until_idle(max_ticks=4000)
    assert all(r.done and r.error is None for r in reqs)
    return [list(r.tokens) for r in reqs]


def _run_unpacked(eng, monkeypatch):
    """Launch `eng`'s ticks the way the parent did, but for the binding: the
    same programs through `Executor.run`, every feed an array of its
    declared dtype."""
    programs = {"main": (eng._program, eng._feeds, eng._tick_fetches)}
    if eng._mixed_step is not None:
        programs["mixed"] = (eng._mixed_program, eng._mixed_feeds,
                             lambda: [eng._mixed_ids])
    launched = []

    def run(step, owner):
        program, feeds, fetches = programs[owner]
        block = program.global_block()
        feed = {n: np.array(a, dtype=block.vars[n].dtype)
                for n, a in feeds.items()}
        launched.append(owner)
        return eng._exe.run(program, feed=feed, fetch_list=fetches(),
                            scope=eng.scope, return_numpy=False)
    monkeypatch.setattr(eng, "_run_bound_step", run)
    return launched


@pytest.mark.parametrize("kind", ["chunked", "one_token"])
def test_paged_engine_emits_the_unpacked_paths_tokens(kind, monkeypatch):
    scope = _trained_scope()
    kw = dict(n_slots=4, max_len=_MAX_LEN, block_size=_BS, scope=scope,
              **_DIMS)
    if kind == "one_token":
        kw["host_tier"] = HostTierConfig(host_blocks=4)
    packed, unpacked = PagedKVEngine(**kw), PagedKVEngine(**kw)
    assert packed.prefill == unpacked.prefill == kind
    launched = _run_unpacked(unpacked, monkeypatch)
    prompts = _requests()
    want = _gen(unpacked, prompts)
    assert set(launched) == ({"main", "mixed"} if kind == "chunked"
                             else {"main"})
    prev = flags.get_flag("trace")
    flags.set_flag("trace", True)
    try:
        mark = tracing.mark()
        got = _gen(packed, prompts)
        spans = tracing.spans_since(mark)
    finally:
        flags.set_flag("trace", prev)
    assert got == want
    names = ["main", "mixed"] if kind == "chunked" else ["main"]
    # the chunked engine read most ticks a launch late; a host tier moves
    # blocks between ticks and reads each at once
    late = packed.stats()["dispatch"]["late_reads"]
    ahead = packed.stats()["dispatch"]["run_ahead"]
    found = packed.stats()["dispatch"]["copies_found"]
    assert (late > 0) == (kind == "chunked") and 0 <= ahead <= late
    # a read that finds the ids on the host is one behind a wait for the tick
    assert found == sum(s.attrs["found"] for s in spans
                        if s.name == "engine/copy_back")
    assert packed.stats()["dispatch"] == {
        **{n: {"host_args": 1} for n in names}, "late_reads": late,
        "run_ahead": ahead, "copies_found": found}
    launches = [s for s in spans if s.name == "engine/launch"]
    ticks = [s for s in spans if s.name == "engine/tick"]
    assert len(launches) == len(ticks) == len(launched)
    assert all(s.attrs["host_args"] == 1 for s in launches)
    if kind == "chunked":       # decode and mixed ticks alike
        assert {t.attrs["prefill"] > 0 for t in ticks} == {True, False}


def test_slot_engine_and_its_hlo():
    eng = ContinuousBatchingEngine(n_slots=3, max_len=_MAX_LEN,
                                   scope=_trained_scope(), **_DIMS)
    assert eng.stats()["dispatch"] == {"main": {"host_args": 1},
                                       "late_reads": 0, "run_ahead": 0,
                                       "copies_found": 0}
    hlo = eng.tick_hlo()
    # seed + tick_tok [3,1] + tick_pos [3,1,1] + tick_from_last [3,1]: ONE
    # small host argument
    assert "s32[10]" in hlo
    assert "s64[" not in hlo


@pytest.mark.parametrize("paged", [False, True])
def test_speculative_engine_binds_four_steps_of_one_host_array(paged):
    scope = Scope()
    dims = dict(_DIMS, max_len=32)
    make = (lambda **kw: PagedKVEngine(n_slots=3, scope=scope, block_size=8,
                                       **dims, **kw)) if paged else \
        (lambda **kw: ContinuousBatchingEngine(n_slots=3, scope=scope,
                                               **dims, **kw))
    prompts = _requests()[:3]
    want = _gen(make(), prompts, max_new=8)
    spec = make(speculative=SpecConfig(gamma=3))
    assert _gen(spec, prompts, max_new=8) == want
    assert spec.spec.stats()["rounds"] > 0
    assert spec.stats()["dispatch"] == {
        **{n: {"host_args": 1} for n in ("main", "draft", "verify")},
        "late_reads": 0, "run_ahead": 0,
        "copies_found": spec.copies_found}


def test_topk_engine_and_beam_search_through_run():
    """`paged_beam_search` launches the bound step through `run(feeds)`
    with the views themselves; beam 1 over the top-k tick is greedy."""
    scope = _trained_scope()
    kw = dict(n_slots=4, max_len=_MAX_LEN, block_size=_BS, scope=scope,
              **_DIMS)
    greedy, topk = PagedKVEngine(**kw), PagedKVEngine(topk_k=3, **kw)
    assert topk.prefill == "one_token"
    assert topk.stats()["dispatch"] == {"main": {"host_args": 1},
                                        "late_reads": 0, "run_ahead": 0,
                                        "copies_found": 0}
    prompt = _requests()[2]
    want = _gen(greedy, [prompt], max_new=6)[0]
    (tokens, _), = paged_beam_search(topk, prompt, max_new=6, beam_size=1)
    assert tokens == want
    assert _gen(topk, [prompt], max_new=6)[0] == want    # and it ticks on
    beams = paged_beam_search(topk, prompt, max_new=6, beam_size=3)
    assert len(beams) == 3 and beams[0][1] >= beams[1][1] >= beams[2][1]
