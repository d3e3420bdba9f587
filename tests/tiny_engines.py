"""Engines of the benchmark's adapters at test size: their weights made once,
and a served model's test helpers written once.

An adapter's `build_weights(config, seed)` is a pure function of its
arguments, and it was most of what an engine test waited for. So the arrays
are kept per (adapter, config, seed) for the life of the process, and every
engine gets a FRESH `Scope` holding them: a jax array is immutable, a tick
program donates only what it writes (pools, states, snapshots), and those
each engine's start-up makes anew in its own scope. No engine sees another's
pager, index, state or counters.

The values a tiny weight holds are nothing a test asserts: every comparison
here is a program against a reference on the SAME weights. The adapters'
`GEN_CHUNK` (`1 << 25` normals a draw, whatever the parameter's shape) is the
chip's, right at the published widths and 80 ms a parameter on this CPU, so
`weights` builds under a chunk of the tests' own size and puts the adapter's
back.

A model's `tests/<model>_tiny.py` declares its configuration and ONE record
(`Tiny`: the adapter, its reference module, `CFG`, `ENGINE`, `pad_to`);
`cfg`, `engine`, `reference` and `logit_error` are the record's methods, and
the prompts, the two `exact` fixtures and the tests that every served model's
file states under one name are functions over the record below. A test whose
body would ask which model it serves stays in the model's file."""

import dataclasses
import json
import os
import types

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import serving
from paddle_tpu.core import flags
from paddle_tpu.core.enforce import InvalidArgumentError

#: values one call of an adapter's generator makes under `weights`: the
#: smallest power of two that holds the largest parameter of the seven tiny
#: configurations and their wider variants in one chunk (`lfm2_tiny`'s
#: `l<i>_moe_experts_gate`, `[8, 64, 256]` = 131,072 values; nemotron's
#: 32-expert router test stops at 49,152)
GEN_CHUNK = 1 << 17

_BUILT = {}


def weights(adapter, config, seed):
    """-> a new Scope holding what the adapter's `build_weights` makes of
    (config, seed) under the tests' `GEN_CHUNK` (built the first time, the
    same arrays after). The one place a tier-1 test makes an adapter's
    weights."""
    key = (adapter.__name__, json.dumps(config, sort_keys=True), seed)
    if key not in _BUILT:
        chips, adapter.GEN_CHUNK = adapter.GEN_CHUNK, GEN_CHUNK
        try:
            built = adapter.build_weights(config, seed)
        finally:
            adapter.GEN_CHUNK = chips
        _BUILT[key] = {n: built.get(n) for n in built.local_var_names()}
    scope = pt.Scope()
    for name, value in _BUILT[key].items():
        scope.set_var(name, value)
    return scope


@dataclasses.dataclass(frozen=True)
class Tiny:
    """What differs between the served models' test helpers."""
    adapter: types.ModuleType
    ref: types.ModuleType           # the adapter's plain reference
    CFG: dict
    ENGINE: dict                    # the benchmark's engine spec, tiny
    pad_to: int = 64                # the reference's padded length

    F32 = dict(weights_dtype="float32", cache_dtype="float32")

    def cfg(self, **over):
        return dict(self.CFG, **over)

    def engine(self, config, seed=7, scored=False, **spec):
        """-> (a PagedKVEngine of the adapter at `config` under `ENGINE`
        with `spec` over it, its parameters by name). `scored`: the engine
        also fetches its head's logits (`scored_engine`)."""
        scope = weights(self.adapter, config, seed)
        spec = dict(self.ENGINE, **spec)
        if scored:
            sizes = {k: v for k, v in spec.items() if k != "class"}
            eng = scored_engine(scope=scope,
                                model=self.adapter.spec_of(config), **sizes)
        else:
            eng = self.adapter.build_engine(config, spec, scope)
        params = {n: scope.get(n) for n in self.adapter.param_names(config)}
        return eng, params

    def reference(self, config, params, req):
        """The reference's logits for the positions `req` emitted from."""
        seq = np.asarray(req.prompt + req.tokens[:-1], np.int32)
        return self.adapter.reference_logits(
            config, params, seq, self.pad_to)[len(req.prompt) - 1:]

    def logit_error(self, config, params, req, got):
        """max |program - reference| over the emitted positions' logits, in
        standard deviations of the reference's logits."""
        r = self.reference(config, params, req)
        return float(np.abs(got - r).max() / r.std())

    def worst(self, config, params, runs):
        return max(self.logit_error(config, params, r, got)
                   for r, got in runs)

    def gaps(self, config, params, req):
        """Per emitted token of a finished request: how far its reference
        logit lies below the position's largest, in standard deviations of
        that position's logits (benchmark/loops/serve.py `_check`)."""
        r = self.reference(config, params, req)
        toks = req.tokens
        return (r.max(-1) - r[np.arange(len(toks)), toks]) / r.std(-1)


def _head_logits(program):
    """The variable a tick program's head takes its argmax of."""
    op = next(o for o in program.global_block().ops if o.type == "arg_max")
    return program.global_block().var(op.inputs["X"][0])


def scored_engine(**kw):
    """A PagedKVEngine whose two ticks also fetch the head's float32 logits
    (`last_logits` [rows, 1, vocab]: the decode rows, then in a mixed tick
    the lanes' last rows): a test's view into the programs the engine runs,
    where the ids alone say too little. The engine has no such option.
    `emitted_logits` reads a tick's logits for the token that tick emitted,
    so this engine commits every tick at once, as one that fetches top-k
    does (tests/test_late_read.py has the late order against it)."""

    class Scored(serving.PagedKVEngine):
        last_logits = None

        def _commits_every_tick(self):
            return True

        def _tick_fetches(self):
            return super()._tick_fetches() + [_head_logits(self._program)]

        def _mixed_fetches(self):
            return super()._mixed_fetches() + [
                _head_logits(self._mixed_program)]

        def _launch_tick(self):
            fetches = super()._launch_tick()
            self.last_logits = fetches[1]
            return fetches

    return Scored(**kw)


def emitted_logits(eng, prompt, max_new):
    """Run one request alone through `eng` (a `scored_engine`) and
    return (request, the float32 logits the program made for each token it
    emitted [max_new, vocab]): the lane's last row when a chunk ended the
    prompt, the request's decode row after."""
    req = eng.submit(prompt, max_new)
    rows = []
    while not req.done:
        before = len(req.tokens)
        eng.step()
        if len(req.tokens) > before:
            row = eng.n_slots if eng._lanes else req.slot
            rows.append(np.asarray(eng.last_logits)[row, 0])
    return req, np.stack(rows)


def committed(kind, name):
    """benchmark/<kind>/<name>.json as the repository commits it."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", kind, name + ".json")) as f:
        return json.load(f)


# -- what every served model's file asks, over the model's record -----------

def prompts(turns, head=24, seed=1, alone=True):
    """A shared head of `head` tokens and one prompt a turn behind it;
    `alone`: the head by itself first, as the benchmark's warm-up sends a
    system prompt."""
    rng = np.random.default_rng(seed)
    shared = rng.integers(0, 97, head).tolist()
    return ([shared] if alone else []) + [
        shared + rng.integers(0, 97, n).tolist() for n in turns]


def exact_matmuls_fixture(tiny):
    """-> the module's `exact_matmuls`: the float32 configuration, with the
    matmuls exact while the module's tests run."""
    @pytest.fixture(scope="module")
    def exact_matmuls():
        old = flags.get_flag("use_bf16_matmul")
        flags.set_flag("use_bf16_matmul", False)
        yield tiny.cfg(**tiny.F32)
        flags.set_flag("use_bf16_matmul", old)
    return exact_matmuls


def exact_fixture(tiny, turns, head=24, alone=True, new=10):
    """-> the module's `exact` (over its `exact_matmuls`): float32 weights,
    pools, state and matmuls, the program against the reference with nothing
    but float32 rounding between them. With `alone` the head by itself first
    (two tokens), then every turn behind it, `new` tokens each, one request
    at a time: (cfg, params, engine, [(request, its emitted logits)])."""
    @pytest.fixture(scope="module")
    def exact(exact_matmuls):
        cfg = exact_matmuls
        eng, params = tiny.engine(cfg, 7, scored=True)
        sent = prompts(turns, head, alone=alone)
        runs = [emitted_logits(eng, p, 2 if alone and i == 0 else new)
                for i, p in enumerate(sent)]
        return cfg, params, eng, runs
    return exact


def lanes_then_decode_agree(tiny, exact, tol, shared_lens):
    """`exact`'s requests went through the lanes in chunks of 16, shared
    what `shared_lens` says and read as the reference does: -> the engine."""
    cfg, params, eng, runs = exact
    assert eng.prefill == "chunked" and eng.chunk_tokens == 16
    assert [r.shared_len for r, _ in runs] == shared_lens
    assert tiny.worst(cfg, params, runs) < tol
    return eng


def state_counts(tiny, exact, board, **counts):
    """`stats()[board]` after `exact`'s requests: every hit restored a state,
    a copy is the spec's `state_bytes()`, and `counts` as the model's file
    gives them."""
    cfg, _, eng, _ = exact
    st = eng.stats()[board]
    assert st["restores"] == eng.pager.prefix_hits
    assert st["bytes_per_copy"] == tiny.adapter.spec_of(cfg).state_bytes()
    assert {k: st[k] for k in counts} == counts


def a_prefix_hit_equals_its_twin(tiny, exact, sent, new=10, atol=2e-5,
                                 head=24):
    """Each of `exact`'s hits against the same prompt (`sent`, `exact`'s
    own) on an engine that shares nothing and so prefilled it all itself."""
    cfg, _, _, runs = exact
    alone, _ = tiny.engine(cfg, 7, scored=True)
    alone.pager.prefix_sharing = False
    for (req, got), prompt in zip(runs[1:], sent[1:]):
        twin, twin_got = emitted_logits(alone, prompt, new)
        assert twin.shared_len == 0 and req.shared_len == head
        assert twin.tokens == req.tokens
        np.testing.assert_allclose(twin_got, got, atol=atol)


def a_planted_fault_is_caught(tiny, exact, fault, tol, over, factor=10,
                              faulty=slice(1, None), clean=slice(None)):
    """The adapter's `planted(fault)` (what benchmark/witness.py plants on
    the chip) under `over` puts `exact`'s `faulty` runs past `factor` times
    the tolerance that the `clean` ones pass once it is taken out again."""
    cfg, params, _, runs = exact
    cfg = dict(cfg, **over)
    with tiny.adapter.planted(fault, cfg, None) as c:
        assert tiny.worst(c, params, runs[faulty]) > factor * tol
    assert tiny.ref.FAULT is None
    assert tiny.worst(cfg, params, runs[clean]) < tol


def a_stale_snapshot_is_caught(tiny, cfg, turns, snapshots, bound):
    """The program's own restore, from entries (`snapshots`: their names
    less the engine's prefix) that hold another prompt's state: the twin of
    the reference's `snapshot_stale`."""
    eng, params = tiny.engine(cfg, 7, scored=True)
    sent = prompts(turns)
    emitted_logits(eng, sent[0], 2)
    emitted_logits(eng, prompts(turns, seed=9)[0], 2)
    for name in snapshots:
        name = eng._cache_prefix + name
        snap = eng.scope.get(name)
        eng.scope.set_var(name, snap.at[0].set(snap[1]))
    hit = emitted_logits(eng, sent[1], 6)
    assert hit[0].shared_len == 24
    assert tiny.worst(cfg, params, [hit]) > bound


def a_hit_is_truncated_to_the_deepest_snapshot(tiny, cfg, tol):
    """A prompt of 37 tokens leaves blocks 0-3 in the index and ONE snapshot,
    at the end of block 3 (32). A second prompt that shares its first 29
    tokens matches three blocks, of which none holds a snapshot: it is
    handed nothing and prefills from position 0. A third that shares 36
    matches four and is handed all four. -> the engine."""
    eng, params = tiny.engine(cfg, 7, scored=True)
    rng = np.random.default_rng(5)
    first = rng.integers(0, 97, 37).tolist()
    runs = [emitted_logits(eng, first, 4)]
    shallow = first[:29] + rng.integers(0, 97, 6).tolist()
    runs.append(emitted_logits(eng, shallow, 4))
    assert runs[-1][0].shared_len == 0 and eng.pager.hits_truncated == 1
    deep = first[:36] + rng.integers(0, 97, 6).tolist()
    runs.append(emitted_logits(eng, deep, 4))
    assert runs[-1][0].shared_len == 32
    assert tiny.worst(cfg, params, runs) < tol
    return eng


def a_preempted_request_reads_the_same(tiny, cfg, turns):
    """A pool too small for two requests: the second waits at the head of
    the queue until the first has released its blocks, then runs from the
    first's snapshot; both read as on an engine with room, and the slot the
    first left is the second's, its state overwritten from the snapshot."""
    eng, _ = tiny.engine(cfg, 7, scored=True, n_blocks=9, n_slots=2)
    sent = prompts(turns)
    a = eng.submit(sent[2], 12)
    b = eng.submit(sent[4], 12)
    waited = 0
    while not (a.done and b.done):
        eng.step()
        waited += eng.n_pending
    assert waited > 0                   # b was held back for blocks
    assert a.error is None and b.error is None
    fresh, _ = tiny.engine(cfg, 7, scored=True)
    for req, prompt in ((a, sent[2]), (b, sent[4])):
        twin, _ = emitted_logits(fresh, prompt, 12)
        assert twin.tokens == req.tokens
    eng.pager.pool.check()


#: the options no newer block is built for, as each model's file
#: parametrises `test_what_is_not_built_for_the_model_is_refused_by_name`
REFUSED = [("speculative", serving.SpecConfig(gamma=2)),
           ("host_tier", serving.HostTierConfig()),
           ("kv_quant", True), ("quant", "int8"), ("topk_k", 4)]


def refused_by_name(tiny, option, value, holds="", **sizes):
    """An engine of the model with `option` is refused under the option's
    name and what the model `holds` that the option was not built for."""
    with pytest.raises(InvalidArgumentError, match=option + "=.*" + holds):
        serving.PagedKVEngine(n_slots=2, max_len=32, block_size=8,
                              model=tiny.adapter.spec_of(tiny.cfg()),
                              **sizes, **{option: value})


def without_a_snapshot_pool_is_refused(tiny):
    with pytest.raises(InvalidArgumentError, match="n_snapshots"):
        serving.PagedKVEngine(n_slots=2, max_len=32, block_size=8,
                              model=tiny.adapter.spec_of(tiny.cfg()))
