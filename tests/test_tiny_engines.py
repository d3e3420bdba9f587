"""The test engines' shared weights (tests/tiny_engines.py): built once per
(adapter, config, seed), handed to every engine in a scope of its own, and
nothing else shared: one engine's requests leave another's pools, state,
pager and counters as they were."""

import numpy as np

import paddle_tpu as pt

import lfm2_tiny as T
import tiny_engines
from lfm2_tiny import lfm2

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
