"""Engines of the benchmark's adapters at test size, their weights made once.

An adapter's `build_weights(config, seed)` is a pure function of its
arguments, and it was most of what an engine test waited for (22 s of the
27.7 of one nemotron test: 67 compiles of eager `jnp` calls and the routers'
balancing loop). So the arrays are kept per (adapter, config, seed) for the
life of the process, and every engine gets a FRESH `Scope` holding them: a
jax array is immutable, a tick program donates only what it writes (pools,
states, snapshots), and those each engine's start-up makes anew in its own
scope. No engine sees another's pager, index, state or counters."""

import json

import numpy as np

import paddle_tpu as pt

_BUILT = {}


def weights(adapter, config, seed):
    """-> a new Scope holding what `adapter.build_weights(config, seed)`
    makes (built the first time, the same arrays after)."""
    key = (adapter.__name__, json.dumps(config, sort_keys=True), seed)
    if key not in _BUILT:
        built = adapter.build_weights(config, seed)
        _BUILT[key] = {n: built.get(n) for n in built.local_var_names()}
    scope = pt.Scope()
    for name, value in _BUILT[key].items():
        scope.set_var(name, value)
    return scope


def engine(adapter, base_spec, config, seed=7, scored=False, **spec):
    """-> (a PagedKVEngine of `adapter` at `config` under `base_spec` with
    `spec` over it, its parameters by name). `scored`: the engine also
    fetches its head's logits (`scored_engine`)."""
    scope = weights(adapter, config, seed)
    spec = dict(base_spec, **spec)
    if scored:
        sizes = {k: v for k, v in spec.items() if k != "class"}
        eng = scored_engine(scope=scope, model=adapter.spec_of(config),
                            **sizes)
    else:
        eng = adapter.build_engine(config, spec, scope)
    params = {n: scope.get(n) for n in adapter.param_names(config)}
    return eng, params


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
    from paddle_tpu import serving

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
