"""The test engines' shared weights (tests/tiny_engines.py): built once per
(adapter, config, seed) under a chunk of the tests' own size, handed to every
engine in a scope of its own, and nothing else shared: one engine's requests
leave another's pools, state, pager and counters as they were. With a
compile-cache directory in effect, as every cell has one, the second engine
of a spec and sizes loads its ticks."""

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.core import compile_cache, unique_name
from paddle_tpu.observability import tracing

import axk1_tiny
import falcon_h1_tiny
import glm_tiny
import kexaone_tiny
import ling_tiny
import nemotron_h_tiny
import tiny_engines
from lfm2_tiny import TINY as T, lfm2
from test_executable_store import store  # noqa: F401  (the fixture)

SEED = 23          # no other test's: the first call here builds


def _engine_state(eng):
    """Everything of `eng`'s scope that is not a parameter (pools, the conv
    state, snapshots) as host arrays under names less the engine's own
    prefix, and the pager's counts."""
    held = set(lfm2.param_names(T.cfg()))
    arrays = {n.replace(eng._cache_prefix, "", 1):
              np.asarray(eng.scope.get(n)).copy()
              for n in eng.scope.local_var_names() if n not in held}
    pool = eng.pager.pool
    return arrays, (pool.n_used, pool.n_free, eng.n_ticks, eng.tokens_out,
                    eng.pager.stats())


def test_weights_are_built_once_and_every_scope_is_new(monkeypatch):
    calls = []
    build = lfm2.build_weights
    monkeypatch.setattr(lfm2, "build_weights",
                        lambda c, s: calls.append(s) or build(c, s))
    first, p1 = T.engine(T.cfg(), SEED)
    second, p2 = T.engine(T.cfg(), SEED)
    assert calls == [SEED]
    assert first.scope is not second.scope
    assert first.pager is not second.pager
    assert sorted(p1) == sorted(lfm2.param_names(T.cfg()))
    assert all(p1[n] is p2[n] for n in p1)          # the arrays themselves
    # another seed or configuration is another build (a stub's: a real
    # one takes 12 s), in a copy of the memo that goes with the test
    monkeypatch.setattr(tiny_engines, "_BUILT", dict(tiny_engines._BUILT))
    monkeypatch.setattr(lfm2, "build_weights",
                        lambda c, s: calls.append(s) or pt.Scope())
    tiny_engines.weights(lfm2, T.cfg(), SEED + 1)
    tiny_engines.weights(lfm2, T.cfg(vocab=31), SEED)
    tiny_engines.weights(lfm2, T.cfg(), SEED)                 # held
    assert calls == [SEED, SEED + 1, SEED]


def test_a_request_on_one_engine_leaves_the_other_as_it_was():
    first, params = T.engine(T.cfg(), SEED)
    second, _ = T.engine(T.cfg(), SEED)
    prompt = np.random.default_rng(5).integers(1, 97, 21).tolist()
    # bring both up (start-up makes the pools), then note the second's state
    for eng in (first, second):
        eng.submit([3, 1, 4], 1)
        eng.run_until_idle()
    arrays, counts = _engine_state(second)
    req = first.submit(prompt, 6)
    first.run_until_idle()
    assert req.done and len(req.tokens) == 6
    after, counts_after = _engine_state(second)
    assert counts_after == counts
    assert sorted(after) == sorted(arrays)
    for n in arrays:
        assert np.array_equal(after[n], arrays[n]), n
    # the first's own pools did take the request ...
    mine, _ = _engine_state(first)
    assert any(not np.array_equal(mine[n], arrays[n]) for n in arrays)
    # ... no tick donated a weight, and the second, given the same prompt,
    # says what the first said
    assert not any(v.is_deleted() for v in params.values())
    twin = second.submit(prompt, 6)
    second.run_until_idle()
    assert twin.tokens == req.tokens


def test_the_adapters_chunk_is_the_tests_for_the_build_and_back_after(
        monkeypatch):
    chips = lfm2.GEN_CHUNK
    assert chips == 1 << 25 > tiny_engines.GEN_CHUNK
    seen = []

    def build(config, seed):
        seen.append(lfm2.GEN_CHUNK)
        if seed == SEED + 2:
            raise RuntimeError("no weights")
        return pt.Scope()
    monkeypatch.setattr(tiny_engines, "_BUILT", dict(tiny_engines._BUILT))
    monkeypatch.setattr(lfm2, "build_weights", build)
    tiny_engines.weights(lfm2, T.cfg(), SEED + 1)
    assert lfm2.GEN_CHUNK == chips
    with pytest.raises(RuntimeError, match="no weights"):
        tiny_engines.weights(lfm2, T.cfg(), SEED + 2)
    assert lfm2.GEN_CHUNK == chips
    assert seen == [tiny_engines.GEN_CHUNK] * 2
    # the smallest power of two that one parameter of every tiny
    # configuration fits: the largest is this model's stacked expert matrix
    largest = max(
        int(np.prod(shape))
        for tiny in (T, axk1_tiny.TINY, falcon_h1_tiny.TINY, glm_tiny.TINY,
                     kexaone_tiny.TINY, ling_tiny.TINY, nemotron_h_tiny.TINY)
        for shape, _ in tiny.adapter.param_shapes(tiny.cfg()).values())
    assert largest == 8 * 64 * 256
    assert tiny_engines.GEN_CHUNK // 2 < largest <= tiny_engines.GEN_CHUNK


def test_the_same_key_gives_the_same_arrays_in_a_new_scope_each_time():
    first = tiny_engines.weights(lfm2, T.cfg(), SEED)
    second = tiny_engines.weights(lfm2, dict(T.cfg()), SEED)
    assert first is not second
    names = sorted(first.local_var_names())
    assert names == sorted(second.local_var_names())
    assert set(lfm2.param_names(T.cfg())) <= set(names)
    assert all(first.get(n) is second.get(n) for n in names)
    # what one scope is given after, the next does not see
    first.set_var("a_pool", np.zeros(3))
    third = tiny_engines.weights(lfm2, T.cfg(), SEED)
    assert sorted(third.local_var_names()) == names


def _tick_builds(build):
    """The `executor/compile_or_load` spans of the steps `build()` binds and
    one request first runs, under names of their own as every test's are
    (conftest's `fresh_state`)."""
    before = len(tracing.compile_spans())
    with unique_name.guard():
        eng = build()
    req = eng.submit([3, 1, 4, 1, 5, 9, 2, 6, 5, 3], 3)
    eng.run_until_idle()
    assert req.done and req.error is None
    return req.tokens, [s for s in tracing.compile_spans()[before:]
                        if s.name == "executor/compile_or_load"]


def test_a_second_engine_of_a_spec_and_sizes_loads_its_ticks(store):  # noqa: F811,E501
    """With a directory in effect the executor's store is, as in a cell (the
    tier itself runs without one: core/compile_cache.py says why): the first
    engine of a spec and sizes writes its steps' executables, the second
    traces, lowers and compiles none of them. Under ONE prefix: an engine
    names its pools by a count of the process's engines (`pgd<n>`), the
    names are in the programs and so in the key, and a cell's warm process
    counts as its fill run did."""
    assert compile_cache.store_dir() == store

    def build():
        return T.engine(T.cfg(), SEED, scored=True, n_slots=3, n_blocks=24,
                        cache_prefix="twice")[0]
    said, first = _tick_builds(build)
    assert [s.attrs["stored"] for s in first] == [0] * len(first) != []
    again, second = _tick_builds(build)
    assert again == said
    assert [s.attrs["program"] for s in second] == [
        s.attrs["program"] for s in first]
    assert all(s.attrs["stored"] == 1 and s.attrs["jits"] == 0
               and s.attrs["trace_s"] == 0 for s in second)
