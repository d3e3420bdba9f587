"""Executor: compile-and-run programs on TPU.

Capability equivalent of the reference's Executor (reference:
paddle/fluid/framework/executor.cc:125,221 + python/paddle/fluid/executor.py:256).
Where the reference *interprets* a ProgramDesc op-by-op, this executor traces
the whole global block into one jax function (lowering.py) and XLA-compiles it,
caching executables keyed by (program version, feed signature, fetch list) —
the analogue of the reference's Prepare/RunPreparedContext caching
(executor.cc:294,321) but with whole-program fusion.

State handling is functional: persistable variables (parameters, optimizer
accumulators, counters) are inputs AND outputs of the compiled step; updated
values are written back to the Scope after each run. Buffers for read+written
state are donated to XLA so parameter updates are in-place on device.
"""

from __future__ import annotations

import hashlib
import json
import math
import operator
import os
import sys
import time
from typing import Any, Dict, List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from ..core import compile_cache as _store
from ..core import flags
from ..core.enforce import InvalidArgumentError, NotFoundError, enforce
from ..core.places import Place, default_place
from ..observability import tracing as _tracing
from .lowering import LowerCtx, build_plan, run_plan
from .program import (BATCH_ROW_MASK_NAME, Parameter, Program, Variable,
                      default_main_program, dtype_name)
from .scope import Scope, global_scope


def _fusion_flags_key():
    """Flags that are inputs to compilation (apply_fusion_passes and the
    grad-comm rewrite read them at compile time): they must be part of the
    compile-cache key or toggling a kill switch at runtime would silently
    keep serving the previously compiled variant."""
    return (flags.get_flag("fuse_recurrent_cells"),
            flags.get_flag("fuse_decode_attention"),
            flags.get_flag("quant_comm"),
            flags.get_flag("quant_params"),
            flags.get_flag("pipeline"),
            flags.get_flag("tp_shard"),
            flags.get_flag("memory_plan"),
            flags.get_flag("auto_parallel"),
            # kv_sanitize rewrites nothing today (the shadow bookkeeping
            # is pure host-side), but the kill switch joins the key so a
            # toggled run can never share cached compiled state with its
            # instrumented twin
            flags.get_flag("kv_sanitize"))


def _feed_signature(feed: Dict[str, Any]):
    return tuple(sorted((k, tuple(np.shape(v)), str(np.asarray(v).dtype) if not
                         hasattr(v, "dtype") else str(v.dtype))
                        for k, v in feed.items()))


def as_numpy(x):
    return np.asarray(x)


def _digest(obj) -> str:
    """sha256 of `obj` as canonical JSON. Sets go in sorted and tuples as
    lists; whatever else JSON has no form for raises TypeError: a step
    that holds one has no key in the store."""
    def plain(v):
        if isinstance(v, (set, frozenset)):
            return sorted(v)
        if isinstance(v, np.dtype):
            return str(v)
        raise TypeError(f"{type(v).__name__} has no JSON form")
    return hashlib.sha256(json.dumps(
        obj, sort_keys=True, default=plain).encode()).hexdigest()


def _program_digest(program: Program) -> str:
    """sha256 over everything `Program.to_json()` holds (every block's vars
    with shape, dtype, flags and sharding spec, every op's type, slots and
    attrs) and each var's staging, LESS `random_seed`: every launch passes
    the seed as an argument and no trace reads it. A constant table among
    the attrs (the LM's position encoding is a list of a million floats, 21
    MB as JSON and 0.6 s to write) goes in as its bytes. Raises TypeError on
    an attr JSON has no form for, like `to_json()`."""
    def attr(v):
        if isinstance(v, np.ndarray) or (isinstance(v, (list, tuple))
                                         and len(v) > 256):
            a = np.asarray(v)
            if a.dtype != object:
                return ["array", str(a.dtype), a.shape,
                        hashlib.sha256(a.tobytes()).hexdigest()]
        if isinstance(v, np.generic):
            return v.item()
        if isinstance(v, (list, tuple)):
            return [attr(x) for x in v]
        if isinstance(v, dict):
            return {k: attr(x) for k, x in v.items()}
        return v

    h = hashlib.sha256()
    for b in program.blocks:
        h.update(json.dumps([
            b.idx, b.parent_idx,
            [[v.name, v.shape, dtype_name(v.dtype), v.persistable,
              v.stop_gradient, v.lod_level, v.is_data,
              isinstance(v, Parameter), v.trainable,
              getattr(v, "sharding_spec", None),
              getattr(v, "is_optimizer_state", False), repr(v.staging)]
             for v in b.vars.values()],
            [[op.type, op.inputs, op.outputs,
              {k: attr(v) for k, v in op.attrs.items()}] for op in b.ops],
        ], sort_keys=True).encode())
    return h.hexdigest()


def _sharding_key(sh):
    """A sharding as the store's key holds it: its text (the mesh's axes
    and the spec; a single device's id) and the devices in their order."""
    mesh = getattr(sh, "mesh", None)
    ids = ([d.id for d in mesh.devices.flat] if mesh is not None
           else sorted(d.id for d in sh.device_set))
    return str(sh), ids


def _arg_key(v):
    """One argument of a launch as the key holds it: shape, dtype, and
    for a device array where it lies and whether it is committed there
    (an executable built for committed arguments commits its results)."""
    if isinstance(v, jax.Array):
        return (v.shape, str(v.dtype), bool(v.weak_type),
                _sharding_key(v.sharding), bool(v.committed))
    if isinstance(v, (np.ndarray, np.generic)):
        return np.shape(v), str(v.dtype), "host"
    return type(v).__name__, "host"     # a Python scalar: weakly typed


def _store_world():
    """What the process adds to every key: the versions of jax, jaxlib and
    the device runtime, the devices, EVERY flag of `core/flags.py`, every
    `jax.config` value as this thread sees it (a `with
    jax.default_matmul_precision(...)` around a launch is another program),
    the package's knobs in the environment that are not flags, JAX's, XLA's
    and the TPU runtime's own, and the digest of the package's source.

    The `jax.config` options are those JAX had defined when the package
    was imported (`compile_cache.configure`), each with the value it has
    NOW: JAX defines some of its options where a module is first imported
    (Pallas's three switches, with the first kernel a process TRACES), and
    a process that loads every program never imports it. With every name
    of the moment in, the process that filled the store made the decode
    tick's key after the mixed tick's trace had imported Pallas, and no
    later process found that entry (PERF.md section 6, PR 60). An option
    defined later is in the key through its `JAX_*` environment variable
    alone."""
    import jaxlib
    devices = jax.devices()
    return {
        "jax": jax.__version__, "jaxlib": jaxlib.__version__,
        "python": list(sys.version_info[:3]),
        "runtime": devices[0].client.platform_version,
        "devices": [(d.platform, d.device_kind, d.id) for d in devices],
        "flags": {k: repr(v) for k, v in flags.all_flags().items()},
        "config": {k: repr(jax.config._value_holders[k].value)
                   for k in _store.CONFIG_NAMES},
        "env": {k: v for k, v in os.environ.items()
                if k.startswith(("PTPU_", "JAX_"))
                or k in ("XLA_FLAGS", "LIBTPU_INIT_ARGS")},
        "source": _store.source_digest(),
    }


class _CompiledStep:
    def __init__(self, fn, ro_names, rw_names, feed_names, fetch_names,
                 program, layout):
        self.fn = fn
        self.ro_names = ro_names
        self.rw_names = rw_names
        self.feed_names = feed_names
        self.fetch_names = fetch_names
        self._packed_fns = {}
        #: what the step's `compile` spans call it
        self.program = program
        #: what launches each of its jitted functions that has RUN: the
        #: function itself, or its executable out of the store (`_Stored`).
        #: A function that is not in here has its first run before it
        self.launch = {}
        #: how each jitted function takes its arguments, (kind, ..., the
        #: keywords it was jitted with): part of its executable's key
        self.layouts = {fn: layout}
        #: set where the step is built (`Executor._compile`, `run_steps`):
        #: the executor and the prepared program its key is made from
        self.owner = self.prepared = self.jit_kwargs = None
        self.key_base = None

    def first_run(self, fn, args):
        """The `executor/compile_or_load` span around `fn`'s FIRST call
        with `args`, and the store's part in it. jax.jit is lazy, so a
        jitted function's trace, lowering and XLA compile (or its load from
        JAX's persistent cache) all happen inside that call, and it is where
        the span is (`tracing.compile_span`). Entered, it has looked in the
        executor's store (`core/compile_cache.py`) under a key made WITHOUT
        tracing (`Executor._store_key`), and `launch` says what it found:

        - the executable, loaded and ready (`_Stored`): no jaxpr, no MLIR;
        - `fn` itself where the step has no key (no cache directory in
          effect, attrs that do not serialise, arrays this process cannot
          address whole): today's lazy path;
        - None on a miss: the launcher lowers and compiles with the very
          arguments it has (JAX's cache serves or stores that compile as
          before) and hands the executable to `keep`, which writes the entry.

        A launch path does all of it in its OWN frame, while `launch` lacks
        its function (the steady path pays that one probe):

            launch = compiled.launch.get(fn)
            if launch is None:
                with compiled.first_run(fn, args) as first:
                    launch = first.launch or first.keep(
                        fn.lower(*args).compile())
                    out = launch(*args)
            else:
                out = launch(*args)

        and NOT through a wrapper that calls `fn` or lowers it: a frame
        between the launcher and the jitted function is in every traced
        op's location, so in every Mosaic kernel's serialized body and the
        compile cache's key, and it cost the routed training cell 3 s of
        set-up (PERF.md section 6, PR 57). `fn` has run once the block is
        left without an exception, and `launch` holds what launches it."""
        return _FirstRun(self, fn, args)

    def packed_fn(self, spans):
        """The step as a PreparedStep launches it: `fn(pack, rest_vals,
        ro_vals, rw_vals)`, where `pack` is ONE int32 array holding the
        seed in slot 0 and, at `spans` = ((feed index, offset, shape,
        dtype), ...), the feeds that crossed from the host inside it.
        They are cut out of it here, by static slices (a bitcast where the
        feed is not int32), before the program's ops run; `rest_vals` are
        the other feeds in feed order. One jitted function a layout, kept
        on the compiled step so a second prepare() of it compiles
        nothing."""
        fn = self._packed_fns.get(spans)
        if fn is not None:
            return fn
        inner, n_feeds = self.pure_step, len(self.feed_names)
        kwargs = dict(self.jit_kwargs, donate_argnums=(3,))
        feed_sh = None
        if "in_shardings" in kwargs:
            # a mesh executor's placements: the pack is replicated like
            # the seed it carries, a feed cut out of it is constrained to
            # the sharding it would have arrived with
            feed_sh, ro_sh, rw_sh, repl = kwargs["in_shardings"]
            packed = {i for i, *_ in spans}
            kwargs["in_shardings"] = (
                repl, tuple(sh for i, sh in enumerate(feed_sh)
                            if i not in packed), ro_sh, rw_sh)

        def step(pack, rest_vals, ro_vals, rw_vals):
            rest = iter(rest_vals)
            cut = {}
            for i, off, shape, dtype in spans:
                x = pack[off:off + math.prod(shape)]
                if x.dtype != dtype:
                    x = jax.lax.bitcast_convert_type(x, dtype)
                x = x.reshape(shape)
                if feed_sh is not None:
                    x = jax.lax.with_sharding_constraint(x, feed_sh[i])
                cut[i] = x
            feed_vals = tuple(cut[i] if i in cut else next(rest)
                              for i in range(n_feeds))
            seed = jax.lax.bitcast_convert_type(pack[0], jnp.uint32)
            return inner(feed_vals, ro_vals, rw_vals, seed)

        fn = self._packed_fns[spans] = jax.jit(step, **kwargs)
        self.layouts[fn] = ("packed", spans, kwargs)
        return fn


class _Stored:
    """What launches a jitted function whose executable the store holds (a
    hit's, loaded; a miss's, compiled here and written): the
    `jax.stages.Compiled`, called through the same C++ path as the
    `jax.jit`. An executable refuses arguments it was not built for where
    the `jax.jit` would trace again (a state value of another dtype, a
    committed array of another sharding): from the first such refusal on,
    the `jax.jit` launches, as before the store."""

    __slots__ = ("call", "jit", "executable", "loaded")

    def __init__(self, executable, jit, loaded):
        self.call = self.executable = executable
        self.jit, self.loaded = jit, loaded

    def __call__(self, *args):
        try:
            return self.call(*args)
        except (TypeError, ValueError):
            # raised by the executable's check of its arguments, before
            # anything ran or was donated
            if self.call is self.jit:
                raise
            self.call = self.jit
            return self.jit(*args)


class _FirstRun(_tracing.compile_span):
    __slots__ = ("_step", "_fn", "_args", "_entry", "launch")

    def __init__(self, step, fn, args):
        super().__init__("executor/compile_or_load", step.program)
        self._step, self._fn, self._args, self.launch = step, fn, args, fn

    def __enter__(self):
        super().__enter__()
        owner = self._step.owner
        self._entry = (owner._store_key(self._step, self._fn, self._args)
                       if owner is not None else None)
        self._args = None           # the launcher holds them, not the span
        if self._entry is not None:
            t = time.perf_counter()
            executable = _store.load_executable(*self._entry)
            if executable is None:
                self.launch = None
            else:
                if self._stack is not None:
                    # no event of JAX's tells this load: told as JAX tells
                    # a retrieval from its own cache (one executable, one
                    # load, nothing compiled), so `cache_load_s` goes on
                    # meaning "seconds spent loading"
                    seconds = time.perf_counter() - t
                    self._jax.add("backend", seconds)
                    self._jax.add("load", seconds)
                self.launch = _Stored(executable, self._fn, True)
            self.attrs["stored"] = int(executable is not None)
        return self

    def keep(self, executable):
        """A miss's executable, just compiled by the launcher: written to
        the store (where it serializes and the directory takes it), and
        what launches the function from here on."""
        t = time.perf_counter()
        if _store.store_executable(*self._entry, self._step.program,
                                   executable):
            self.attrs["store_write_s"] = time.perf_counter() - t
        self.launch = _Stored(executable, self._fn, False)
        return self.launch

    def __exit__(self, *exc):
        if exc[0] is None:
            self._step.launch[self._fn] = self.launch
        return super().__exit__(*exc)


def _packed_dtype(v):
    """The dtype `v` has in a PreparedStep's pack, or None where it stays
    an argument of its own: packed is a HOST value whose items are 4 bytes
    on the device (an int64 feed is int32 there, x64 off)."""
    if isinstance(v, jax.Array):
        return None
    dtype = np.dtype(jax.dtypes.canonicalize_dtype(np.asarray(v).dtype))
    return dtype if dtype.itemsize == 4 else None


class PreparedStep:
    """Bound (program, feed-signature, fetch, scope) handle with the
    per-call dispatch overhead stripped: no fetch validation, no feed
    signature hashing, no cache lookup, no batch-mask synthesis — those
    were all paid once in Executor.prepare. ≙ the reference's
    Prepare/RunPreparedContext split (executor.cc:294,321), whose whole
    point is hoisting per-run setup out of a hot serve loop; here the hot
    loop is the serving engine's decode tick, where the Python dispatch
    path IS the measured overhang (PERF.md, `engine/dispatch`).

    A launch hands the compiled function ONE host array: every feed that
    comes from the host with 4-byte items on the device (int32, float32,
    uint32; an int64 feed is int32 there) has a span of one int32 buffer,
    the seed has its slot 0, and the function cuts them apart on the
    device (`_CompiledStep.packed_fn`). A transfer costs the host some
    0.1 ms however small it is, so a tick of eleven feeds paid for twelve.
    What decides is an argument's item size and that it is a host array,
    nothing else: a bool, int8 or float16 feed and a feed that is a
    `jax.Array` already stay arguments of their own.

    State contract matches Executor.run: read-write persistable state is
    donated to XLA and written back to the scope after each call; the
    RNG seed follows the same (program.random_seed, run counter) stream,
    and feed keys prepare() synthesized beyond the caller's example
    (the reserved @batch_row_mask) are re-injected per call."""

    __slots__ = ("_compiled", "_scope", "_owner", "_random_seed",
                 "_injected", "_fn", "_spans", "_buf", "_views",
                 "_rest_names", "_aot", "host_args", "_b_rest_vals",
                 "_b_ro_vals", "_b_rw_vals", "_b_rw_pick", "_b_state_names",
                 "_b_scope_vars", "_b_seed_base", "_b_staged")

    def __init__(self, compiled, scope, owner, random_seed, injected,
                 example):
        self._compiled = compiled
        self._scope = scope
        self._owner = owner
        self._random_seed = random_seed
        self._injected = injected      # name -> constant value (batch mask)
        self._b_rw_vals = None         # set by bind(): zero-dispatch state
        self._aot = None
        # the layout, from the example feed prepare() compiled for: slot 0
        # is the seed's, then every packable feed in feed order
        vals = {n: injected[n] if n in injected else example[n]
                for n in compiled.feed_names}
        spans, names, end = [], [], 1
        for i, (n, v) in enumerate(vals.items()):
            dtype = _packed_dtype(v)
            if dtype is not None:
                spans.append((i, end, tuple(np.shape(v)), dtype))
                names.append(n)
                end += math.prod(np.shape(v))
        self._spans = tuple(spans)
        self._fn = compiled.packed_fn(self._spans)
        self._buf = buf = np.zeros(end, np.int32)
        # one view a packed feed, of its shape and device dtype (a float32
        # feed's is the float32 view of its span)
        self._views = {
            n: buf[off:off + math.prod(shape)].view(dtype).reshape(shape)
            for n, (_, off, shape, dtype) in zip(names, spans)}
        self._rest_names = tuple(n for n in vals if n not in self._views)
        self._b_rest_vals = tuple(vals[n] for n in self._rest_names)
        #: host arrays a bound launch hands over: the pack, and each host
        #: feed it cannot hold (counted at bind(), which captures those)
        self.host_args = None

    @property
    def fetch_names(self):
        return list(self._compiled.fetch_names)

    def _pack(self, feed):
        """Copy the packable feeds of `feed` into the pack (nothing to do
        for one that IS the pack's view: a bound caller fills in place)."""
        for n, view in self._views.items():
            v = feed[n]
            if v is not view:
                view[...] = v

    def run(self, feed, return_numpy=False):
        """feed: dict with EXACTLY the prepared names/shapes/dtypes (not
        re-validated — a drifted signature recompiles via jit's own shape
        check or fails inside XLA; where the launch goes through a stored
        executable, which refuses what it was not built for, `_Stored`
        hands a drifted call to the jit). Packs them on the way in: the
        launch is run_bound()'s. Returns the fetch list (jax arrays unless
        return_numpy)."""
        compiled = self._compiled
        scope = self._scope
        injected = self._injected
        self._pack(feed)
        rest_vals = tuple(
            jnp.asarray(feed[n] if n in feed else injected[n])
            for n in self._rest_names)
        ro_vals = tuple(scope.get(n) for n in compiled.ro_names)
        rw_vals = tuple(scope.get(n) for n in compiled.rw_names)
        self._owner._run_counter += 1
        self._buf[0] = (self._random_seed * 1000003
                        + self._owner._run_counter) % (2 ** 31)
        fn = self._fn
        launch = compiled.launch.get(fn)
        if launch is None:
            args = (self._buf, rest_vals, ro_vals, rw_vals)
            with compiled.first_run(fn, args) as first:
                launch = first.launch or first.keep(fn.lower(*args).compile())
                fetches, new_state = launch(*args)
        else:
            fetches, new_state = launch(self._buf, rest_vals, ro_vals,
                                        rw_vals)
        for name, val in zip(compiled.state_out_names, new_state):
            scope.set_var(name, val)
        if self._b_rw_vals is not None:
            # a bound tick coexists with plain runs (paged_beam_search
            # drives the same compiled step through run()): the donated rw
            # buffers the binding held are dead now, so re-point it at the
            # state this call just produced
            self._b_rw_vals = self._b_rw_pick(new_state)
        if return_numpy:
            return [as_numpy(f) for f in fetches]
        return list(fetches)

    def bind(self, feed, share=None):
        """One-time setup of the zero-dispatch tick: lay the caller's
        feeds out in the pack, pin the read-only state straight out of the
        scope, and precompute everything run() recomputes per call — the
        argument tuples, the rw<-new_state selection, and the seed stream
        base. After bind(), run_bound() is the hot path: no dict probes,
        no per-name scope lookups, no tuple-comprehension rebuilds, one
        host array.

        bind() OWNS the buffer a launch transfers: it copies the values
        `feed` holds into it and REPLACES each packed entry of `feed` with
        the view of its span, so the caller goes on filling `feed[name]`
        in place (the serving engine does, between ticks) and writes the
        pack by doing so. A launch transfers one of TWO copies of it, used
        in turn (`run_bound`), so the pack may be filled again as soon as
        the launch returns, with that launch and the one before it still
        on the device; a third launch waits until the first was read. A
        feed the pack cannot hold stays the caller's own array, mutated in
        place as before, and a launch may read it until its transfer is
        done.

        `share`: a bound step whose feeds START with this step's (same
        names, shapes and dtypes, in order). This step then takes the
        leading span of that step's buffer, and its views, instead of a
        buffer of its own: the caller fills one set of arrays, this step
        transfers the prefix and `share` the whole.

        Contract: read-only persistables are pinned at bind time — swap
        weights in the scope -> bind() again."""
        compiled = self._compiled
        injected = self._injected
        scope = self._scope
        if share is not None:
            n = len(self._spans)
            enforce(self._spans == share._spans[:n]
                    and list(self._views) == list(share._views)[:n],
                    "bind(share=): the feeds of the step shared with must "
                    "start with this step's, shape for shape",
                    exc=InvalidArgumentError)
            self._buf = share._buf[:len(self._buf)]
            self._views = {n: share._views[n] for n in self._views}
        self._pack(feed)
        feed.update(self._views)
        self._b_staged = (np.empty_like(self._buf), np.empty_like(self._buf))
        self._b_rest_vals = tuple(
            feed[n] if n in feed else injected[n] for n in self._rest_names)
        self.host_args = 1 + sum(not isinstance(v, jax.Array)
                                 for v in self._b_rest_vals)
        self._b_ro_vals = tuple(scope.get(n) for n in compiled.ro_names)
        self._b_rw_vals = tuple(scope.get(n) for n in compiled.rw_names)
        self._b_state_names = tuple(compiled.state_out_names)
        idx = tuple(compiled.state_out_names.index(n)
                    for n in compiled.rw_names)
        if len(idx) == 1:
            i0 = idx[0]
            self._b_rw_pick = lambda s, _i=i0: (s[_i],)
        elif idx:
            self._b_rw_pick = operator.itemgetter(*idx)
        else:
            self._b_rw_pick = lambda s: ()
        # Scope.set_var is a bare dict store; write the same dict directly
        # so the per-tick write-back is one store per state var, no method
        # dispatch (shadowing semantics identical to set_var)
        self._b_scope_vars = scope._vars
        self._b_seed_base = self._random_seed * 1000003
        return self

    def refresh_state(self):
        """Re-point the bound rw state at the scope's CURRENT arrays.

        Two bound steps sharing read-write state (the serving engine's
        plain decode tick and the speculative verify forward both own the
        target KV caches) each hold the donated buffers from their own
        last call — after step A writes the scope, step B's held tuple is
        stale (and donated-dead). Call this on B before run_bound() when A
        ran in between. No-op cost is len(rw_names) dict probes, so the
        single-step steady state stays zero-dispatch by simply not calling
        it."""
        if self._b_rw_vals is not None:
            scope = self._scope
            self._b_rw_vals = tuple(
                scope.get(n) for n in self._compiled.rw_names)
        return self

    def run_bound(self):
        """The zero-dispatch steady-state tick over the buffers captured by
        bind(): donated rw state threads call-to-call through a precomputed
        selector, the feeds are the pack the caller filled through its
        views, the seed goes into the pack's slot 0, and the scope
        write-back is a raw dict store per state var. Returns the fetch
        tuple (jax arrays)."""
        owner = self._owner
        owner._run_counter += 1
        buf = self._buf
        buf[0] = (self._b_seed_base + owner._run_counter) % 2147483648
        # the launch transfers a copy, the two copies used in turn: the
        # caller may fill the pack again while this launch and the one
        # before it are still on the device (a backend may read the host
        # array it was handed until the program that takes it has run)
        staged, other = self._b_staged
        self._b_staged = (other, staged)
        np.copyto(staged, buf)
        fn = self._fn
        launch = self._compiled.launch.get(fn)
        if launch is None:
            args = (staged, self._b_rest_vals, self._b_ro_vals,
                    self._b_rw_vals)
            with self._compiled.first_run(fn, args) as first:
                launch = first.launch or first.keep(fn.lower(*args).compile())
                fetches, new_state = launch(*args)
        else:
            fetches, new_state = launch(staged, self._b_rest_vals,
                                        self._b_ro_vals, self._b_rw_vals)
        self._b_rw_vals = self._b_rw_pick(new_state)
        sv = self._b_scope_vars
        for name, val in zip(self._b_state_names, new_state):
            sv[name] = val
        return fetches

    def lower(self, **kw):
        """The launch function lowered for the arguments a launch passes
        (`jax.stages.Traced.lower`'s keywords, e.g. `lowering_platforms`):
        the pack cut apart ahead of the program's ops."""
        compiled, scope = self._compiled, self._scope
        return self._fn.trace(
            self._buf, self._b_rest_vals,
            tuple(scope.get(n) for n in compiled.ro_names),
            tuple(scope.get(n) for n in compiled.rw_names)).lower(**kw)

    def compiled_hlo(self) -> str:
        """Optimized HLO text of the step as this handle launches it: what
        the device actually runs. Compiles ahead of time once, memoized:
        the AOT path bypasses the jit executable cache."""
        if self._aot is None:
            self._aot = self.lower().compile()
        return self._aot.as_text()


class Executor:
    """≙ fluid.Executor (reference python/paddle/fluid/executor.py:256)."""

    def __init__(self, place: Optional[Place] = None):
        self.place = place or default_place()
        self._cache: Dict[Any, _CompiledStep] = {}
        self._persistable_cache: Dict[Any, list] = {}
        self._run_counter = 0

    # -- compilation ------------------------------------------------------
    def _scope_avail_key(self, program: Program, scope: Scope):
        pv = self._persistable_cache.get((id(program), program._version))
        if pv is None:
            pv = sorted({v.name for b in program.blocks
                         for v in b.vars.values() if v.persistable})
            self._persistable_cache[(id(program), program._version)] = pv
        return tuple(n for n in pv if scope.has_var(n))

    def _analyze_state(self, program: Program, scope: Scope, feed_names,
                       fetch_names):
        block = program.global_block()
        read, written = set(), set()
        for op in block.ops:
            read |= set(op.input_names())
            written |= set(op.output_names())
        referenced = read | written | set(fetch_names)
        persistable = {v.name for b in program.blocks
                       for v in b.vars.values() if v.persistable}
        feed_set = set(feed_names)
        state_in = sorted(n for n in persistable
                          if n in referenced and scope.has_var(n)
                          and n not in feed_set)
        state_written = sorted(n for n in persistable if n in written)
        rw = sorted(set(state_in) & set(state_written))
        ro = sorted(set(state_in) - set(rw))
        out_only = sorted(set(state_written) - set(state_in))
        return ro, rw, out_only

    def _build_step_fn(self, program: Program, feed_names, fetch_names,
                       ro, rw, state_out_names):
        """The pure per-step function both the single-step compile and the
        scan-fused run_steps build on."""
        # operator fusion (fused recurrent cells / decode attention): a
        # compile-time rewrite of a CLONE of the program, gated by the
        # default-on fuse_* flags (kill switch PTPU_FUSE_*=0). The caller's
        # program and the compile-cache key (original program version) are
        # untouched — the rewrite is deterministic per version.
        from .passes import apply_fusion_passes
        mesh = self._lowering_mesh(program)
        program = apply_fusion_passes(
            program, protected=set(fetch_names) | set(state_out_names))
        block = program.global_block()
        plan = build_plan(block)
        fetch_names = list(fetch_names)
        feed_names = list(feed_names)

        def step(feed_vals, ro_vals, rw_vals, seed):
            # fetch_names ride along so live-out-narrowed vjp regions
            # (transpiler.memory_optimize) never drop a fetch target
            ctx = LowerCtx(rng_key=jax.random.PRNGKey(seed), mesh=mesh,
                           extras={"program": program,
                                   "fetch_names": tuple(fetch_names)})
            env: Dict[str, Any] = {}
            env.update(zip(ro, ro_vals))
            env.update(zip(rw, rw_vals))
            for name, val in zip(feed_names, feed_vals):
                # byte-lean staging: a data var declared with a staging
                # dtype may be fed compact (e.g. uint8); de-quantize on
                # device so only wire_dtype bytes cross the host->device
                # link (≙ reference buffered_reader.h:27 whose job is
                # keeping the device fed)
                var = block.vars.get(name)
                if (var is not None and var.staging is not None
                        and hasattr(val, "dtype")
                        and str(val.dtype) != str(var.dtype)):
                    # de-quantize ONLY the declared wire dtype; any other
                    # mismatch is a caller bug and silently scaling it
                    # (e.g. int32 ones -> 0.0039) would corrupt the feed.
                    # float64 is exempt: jnp.asarray canonicalizes it to
                    # float32 before the step ever sees it.
                    if str(val.dtype) != str(var.staging[0]):
                        raise TypeError(
                            f"feed '{name}' has dtype {val.dtype} but the "
                            f"var is declared {var.dtype} with staging "
                            f"dtype {var.staging[0]}; feed either of those")
                    val = val.astype(var.dtype)
                    if var.staging[1] is not None:
                        val = val * jnp.asarray(var.staging[1], var.dtype)
                env[name] = val
            run_plan(plan, env, block, ctx)
            fetches = tuple(env[n] for n in fetch_names)
            new_state = tuple(env[n] for n in state_out_names)
            return fetches, new_state

        return step

    def _lowering_mesh(self, program: Program):
        """Hook: the DeviceMesh an SPMD-partitioned step is compiled over,
        handed to lowerings as `LowerCtx.mesh` — a lowering that emits an
        opaque kernel call needs it to run the kernel per shard. None here
        (one device); ParallelExecutor supplies its mesh."""
        return None

    def _prepare_program(self, program: Program, scope: Scope) -> Program:
        """Hook: executor-level program rewrite before state analysis and
        tracing. ParallelExecutor applies the explicit gradient-comm rewrite
        here (parallel/grad_comm.py); the base executor is a no-op. MUST be
        idempotent — both _compile and run_steps call it."""
        return program

    def _stash_flops_estimate(self, compiled: _CompiledStep, program,
                              feed=None):
        """Cache the analytic per-STEP model flops on the compiled step
        for the `ptpu_mfu` gauge — a Python op walk, negligible next to
        the XLA compile. Batch dims resolve to the fed batch when a feed
        signature is at hand (self._feed_shapes is stashed by run()
        callers before compiling)."""
        shapes = (dict(getattr(self, "_feed_shapes", {}) or {}) if feed
                  is None else {n: np.shape(v) for n, v in feed.items()})
        batch = max((s[0] for s in shapes.values() if len(s) >= 1),
                    default=8)
        from .costs import program_flops_bytes
        try:
            compiled.flops_estimate = program_flops_bytes(
                program, nominal_batch=int(batch))["flops"]
        except Exception:
            compiled.flops_estimate = 0.0

    def _note_run_memory(self, compiled: _CompiledStep, step_s: float,
                         steps: int = 1):
        """Per-run memory/utilization sample: the device-state watermark
        (per-device bytes censused once per compiled step) and the
        `ptpu_mfu` gauge — predicted PER-DEVICE model flops (whole-step
        flops over the device count) over the dispatch-window wall time.
        Under donated-state backpressure successive dispatches track
        true step time; the benchmark's training loop reads the
        blocked-measured figure. O(1) per run."""
        from ..observability import memory as _memory
        sb = getattr(compiled, "census_state_bytes", None)
        if sb is not None:
            _memory.update_watermark("device_state_bytes", sb)
        flops = getattr(compiled, "flops_estimate", 0.0)
        # the dispatch window only tracks true step time when donated
        # rw state backpressures successive dispatches — an rw-less
        # (inference) step returns in dispatch time and would publish a
        # meaningless (even >1) utilization. Likewise skip the FIRST
        # window per compiled step: it reads warm-up, not steady state.
        if flops and step_s > 0 and compiled.rw_names:
            if getattr(compiled, "_mfu_warm", False):
                ndev = max(1, int(getattr(self, "device_count", 1)))
                _memory.note_mfu(flops * steps / ndev, step_s)
            else:
                compiled._mfu_warm = True

    def _compile(self, program: Program, scope: Scope, feed_names, fetch_names,
                 in_shardings=None, out_shardings=None, analysis=None,
                 name=None):
        program = self._prepare_program(program, scope)
        ro, rw, out_only = analysis or self._analyze_state(
            program, scope, feed_names, fetch_names)
        state_out_names = sorted(set(rw) | set(out_only))
        fetch_names = list(fetch_names)
        feed_names = list(feed_names)
        step = self._build_step_fn(program, feed_names, fetch_names, ro, rw,
                                   state_out_names)

        flags.vlog(1, "compiling program id=%s version=%s feeds=%s "
                   "fetches=%s", id(program), program._version,
                   list(feed_names), list(fetch_names))
        jit_kwargs: Dict[str, Any] = {"donate_argnums": (2,)}
        if in_shardings is not None:
            jit_kwargs["in_shardings"] = in_shardings
        if out_shardings is not None:
            jit_kwargs["out_shardings"] = out_shardings
        fn = jax.jit(step, **jit_kwargs)
        if name is None:
            name = ("startup" if not feed_names and not fetch_names
                    else "train_step" if state_out_names else "infer_step")
        compiled = _CompiledStep(fn, ro, rw, feed_names, fetch_names, name,
                                 ("plain", jit_kwargs))
        compiled.state_out_names = state_out_names
        # what a PreparedStep builds its one-host-array launch from
        # (_CompiledStep.packed_fn)
        compiled.pure_step, compiled.jit_kwargs = step, jit_kwargs
        compiled.owner, compiled.prepared = self, program
        self._stash_flops_estimate(compiled, program)
        return compiled

    # -- the store of loaded-and-ready executables -------------------------
    def _store_marks(self):
        """Hook: what this EXECUTOR adds to the key of its executables
        beside the program it prepared (ParallelExecutor: its strategies
        and its mesh)."""
        return [type(self).__name__]

    def _store_key_base(self, compiled: _CompiledStep):
        """The part of a step's key that all its launch functions share,
        made from the PREPARED program: `_program_digest` (what its JSON
        holds: every op's attrs, every var's shape, dtype and sharding
        spec); the marks the rewrites left on the program object; the
        names the step is built around; and the file of every op lowering
        the program uses that lies outside the package (those inside are
        in the source digest). Raises where any of it has no JSON form."""
        program = compiled.prepared
        from .registry import lookup_op
        outside = {}
        for op_type in sorted({op.type for b in program.blocks
                               for op in b.ops}):
            path = lookup_op(op_type).lower.__code__.co_filename
            if not _store.in_package(path):
                outside[op_type] = _store.file_digest(path)
        return {
            "program": _program_digest(program),
            "marks": {k: v for k, v in vars(program).items()
                      if k not in ("blocks", "random_seed", "_version",
                                   "_current_block_idx")},
            "names": [compiled.program, compiled.feed_names,
                      compiled.fetch_names, compiled.ro_names,
                      compiled.rw_names, compiled.state_out_names],
            "lowerings": outside, "executor": self._store_marks()}

    def _store_key(self, compiled: _CompiledStep, fn, args):
        """(entry's path, key) of the executable of `fn`, one of
        `compiled`'s jitted functions, for a launch with `args`; None where
        it has none and takes the lazy path: no cache directory in effect,
        a world of several processes or an array this one cannot address
        whole, a program or an argument with no JSON form. Nothing here
        traces: the key is made from what the executor holds before it
        does. The path names the program and the key LESS the source
        digest, so an entry of an older source is replaced."""
        root = _store.store_dir()
        if root is None or jax.process_count() > 1:
            return None
        try:
            if compiled.key_base is None:
                compiled.key_base = self._store_key_base(compiled)
            kind, *layout, kwargs = compiled.layouts[fn]
            leaves, tree = jax.tree_util.tree_flatten(args)
            if not all(getattr(v, "is_fully_addressable", True)
                       for v in leaves):
                return None
            key = dict(
                compiled.key_base, world=_store_world(),
                launch=[kind, layout, str(tree),
                        [_arg_key(v) for v in leaves],
                        {k: jax.tree_util.tree_map(_sharding_key, v)
                         if k.endswith("_shardings") else v
                         for k, v in kwargs.items()}])
            whole = _digest(key)
            if flags.get_flag("vlog") >= 2:     # "why did it miss?"
                flags.vlog(2, "store key of %s: %s %s", compiled.program,
                           whole, json.dumps(key, sort_keys=True,
                                             default=str))
            del key["world"]["source"]
            name = "".join(c if c.isalnum() else "_"
                           for c in compiled.program)
            return (os.path.join(root, f"{name}-{_digest(key)[:32]}.exe"),
                    whole)
        except Exception as e:   # no key, for whatever reason: the lazy path
            flags.vlog(1, "no stored executable for %s: %s: %s",
                       compiled.program, type(e).__name__, e)
            return None

    def _scan_shardings(self, program, feed_names, fetch_names, ro, rw,
                        state_out_names):
        """Hook for subclasses (ParallelExecutor) to shard the scan-fused
        run_steps executable; None = let jax place everything locally."""
        return None

    def _place_feed_stack(self, program, name, vals):
        """Hook: stack K per-step feed values for run_steps. Subclasses
        override to place the stack on a (possibly cross-process) mesh."""
        return jnp.stack([jnp.asarray(v) for v in vals])

    def _validate_fetches(self, program: Program, feed, fetch_names):
        block = program.global_block()
        defined = set(feed)
        for op in block.ops:
            defined.update(op.output_names())
        for name in fetch_names:
            if name not in defined and not block.has_var(name):
                raise NotFoundError(
                    f"fetch target {name!r} is not produced by the program "
                    f"and not fed")

    _isfinite_all_jit = None

    def _sweep_nonfinite(self, pairs, hint: str):
        """Raise FloatingPointError if any floating value in (name, value)
        pairs is non-finite. For global non-fully-addressable arrays
        (multi-process worlds) the check is a tiny jitted SPMD reduction
        that EVERY process executes and whose replicated result every
        process reads — so all processes reach the same verdict and raise
        together, instead of one process raising while its peers block in
        the next step's collectives."""
        cls = type(self)
        for name, val in pairs:
            if not (hasattr(val, "dtype")
                    and jnp.issubdtype(val.dtype, jnp.floating)):
                continue
            if getattr(val, "is_fully_addressable", True):
                ok = bool(jnp.isfinite(val).all())
            else:
                if cls._isfinite_all_jit is None:
                    cls._isfinite_all_jit = jax.jit(
                        lambda a: jnp.isfinite(a).all())
                ok = bool(cls._isfinite_all_jit(val))
            if not ok:
                raise FloatingPointError(
                    f"NaN/Inf detected in {name!r} (fetch-time sweep; "
                    f"{hint})")

    def _synthesize_batch_mask(self, program: Program,
                               feed: Dict[str, Any]) -> Dict[str, Any]:
        """If the program declares the reserved batch-row-mask data var
        (layers.batch_row_mask) and the caller didn't feed it, feed all-ones
        of the batch length: every row of a directly-run batch is real.
        ParallelExecutor overrides the synthesized value with zeros on rows
        it pads for dp divisibility."""
        block = program.global_block()
        if (BATCH_ROW_MASK_NAME not in block.vars
                or BATCH_ROW_MASK_NAME in feed):
            return feed
        bs = None
        for v in feed.values():
            if np.ndim(v) >= 1:
                bs = np.shape(v)[0]
                break
        if bs is not None:
            feed[BATCH_ROW_MASK_NAME] = np.ones((bs,), np.float32)
        return feed

    def _lookup_or_compile(self, program: Program, feed: Dict[str, Any],
                           fetch_names, scope: Scope,
                           name=None) -> _CompiledStep:
        """Validate fetch targets and return the cached compiled step for
        (program, feed signature, fetches, scope contents), compiling on
        miss. The cache key includes which persistable vars currently exist
        in the scope: compiling before the startup program ran must not
        poison the cache for post-initialization runs. `name`: what the
        step's `compile` spans call a step built here (`prepare`)."""
        self._validate_fetches(program, feed, fetch_names)
        avail_key = self._scope_avail_key(program, scope)
        key = (id(program), program._version, _feed_signature(feed),
               tuple(fetch_names), id(scope), avail_key,
               _fusion_flags_key())
        compiled = self._cache.get(key)
        if compiled is None:
            # feed shapes inform the flops estimate's batch resolution
            # (and ParallelExecutor's feed shardings, which stash the
            # same dict in run()); keep them current for this compile
            self._feed_shapes = {n: np.shape(v) for n, v in feed.items()}
            # the step FUNCTION is built here and nothing is compiled:
            # jax.jit is lazy (`_CompiledStep.first_run` holds the compile)
            with _tracing.span("compile", "executor/build_step",
                               program_version=program._version,
                               n_fetches=len(fetch_names)):
                compiled = self._compile(program, scope, list(feed.keys()),
                                         fetch_names, name=name)
            self._cache[key] = compiled
        return compiled

    # -- execution --------------------------------------------------------
    def run(self,
            program: Optional[Program] = None,
            feed: Optional[Dict[str, Any]] = None,
            fetch_list: Optional[Sequence[Union[str, Variable]]] = None,
            scope: Optional[Scope] = None,
            return_numpy: bool = True):
        """≙ Executor.run (reference executor.py:374-473). Missing fetch vars
        raise; feed arrays are validated against declared var dtypes."""
        from .. import profiler as _prof
        program = program or default_main_program()
        scope = scope or global_scope()
        with _tracing.span("step", "executor/lookup"):
            feed = self._synthesize_batch_mask(program, dict(feed or {}))
            fetch_names = [f.name if isinstance(f, Variable) else f
                           for f in (fetch_list or [])]
            compiled = self._lookup_or_compile(program, feed, fetch_names,
                                               scope)

        with _tracing.span("feed_fetch", "executor/feed",
                           n_feeds=len(compiled.feed_names)):
            feed_vals = tuple(jnp.asarray(feed[n])
                              for n in compiled.feed_names)
            ro_vals = tuple(scope.get(n) for n in compiled.ro_names)
            rw_vals = tuple(scope.get(n) for n in compiled.rw_names)
        if getattr(compiled, "census_state_bytes", None) is None:
            # state shapes/placements are pinned by the compile: census
            # the per-device bytes ONCE, before the rw buffers are
            # donated, so the per-run watermark update is O(1)
            from ..observability.memory import per_device_bytes
            compiled.census_state_bytes = sum(
                per_device_bytes(v) for v in ro_vals + rw_vals)
        self._run_counter += 1
        seed = np.uint32((program.random_seed * 1000003 + self._run_counter)
                         % (2 ** 31))

        t0 = time.time()
        with _tracing.span("step", "executor/run",
                           program_version=program._version):
            fn = compiled.fn
            launch = compiled.launch.get(fn)
            if launch is None:
                args = (feed_vals, ro_vals, rw_vals, seed)
                with compiled.first_run(fn, args) as first:
                    launch = first.launch or first.keep(
                        fn.lower(*args).compile())
                    fetches, new_state = launch(*args)
            else:
                fetches, new_state = launch(feed_vals, ro_vals, rw_vals,
                                            seed)
            if _prof.profiler_enabled():
                jax.block_until_ready(fetches)
        if flags.get_flag("check_nan_inf") and jax.default_backend() != "cpu":
            # accelerator counterpart of the in-graph nan guard (whose host
            # callbacks stay off the chip's hot path, lowering.py _nan_guard):
            # sweep every fetch and updated state for non-finite values
            # BEFORE the scope write-back, so the last-good parameters stay
            # checkpointable when the step diverges. Coarser than the per-op
            # guard — it names WHICH var went bad but not which op; rerun
            # under JAX_PLATFORMS=cpu to localize. ≙ reference
            # CheckTensorNANOrInf (framework/operator.cc:726-736).
            with _tracing.span("step", "executor/post"):
                self._sweep_nonfinite(
                    list(zip(compiled.fetch_names, fetches)) +
                    list(zip(compiled.state_out_names, new_state)),
                    "rerun under JAX_PLATFORMS=cpu with "
                    "PTPU_CHECK_NAN_INF=1 to localize the op")
        with _tracing.span("feed_fetch", "executor/state_writeback",
                           n_state=len(compiled.state_out_names)):
            for name, val in zip(compiled.state_out_names, new_state):
                scope.set_var(name, val)
        with _tracing.span("step", "executor/post"):
            self._note_run_memory(compiled, time.time() - t0)
            if flags.get_flag("benchmark"):
                jax.block_until_ready(fetches)
                print(f"[benchmark] program run took "
                      f"{time.time() - t0:.4f}s")
        if not return_numpy:
            return list(fetches)
        with _tracing.span("feed_fetch", "executor/fetch"):
            return [as_numpy(f) for f in fetches]

    def run_steps(self,
                  feed_list: Sequence[Dict[str, Any]],
                  fetch_list: Optional[Sequence[Union[str, Variable]]] = None,
                  program: Optional[Program] = None,
                  scope: Optional[Scope] = None,
                  return_numpy: bool = True):
        """Run len(feed_list) train steps as ONE compiled XLA execution
        (lax.scan over the stacked feeds): the in-graph training loop.

        ≙ the reference's py_reader-driven executor loop (reference
        layers/io.py:474 + executor hot loop), where the device consumes a
        queue without a Python round-trip per step: one dispatch covers K
        steps, so every per-call host cost is paid once per window.

        All feeds must share one signature. Returns a list over
        fetch_list of arrays STACKED over steps (e.g. the per-step loss
        curve). Updated persistable state is written back once, from the
        final step.
        """
        program = program or default_main_program()
        feed_list = [self._synthesize_batch_mask(program, dict(f))
                     for f in feed_list]
        enforce(len(feed_list) >= 1, "run_steps needs at least one feed",
                exc=InvalidArgumentError)
        sig0 = _feed_signature(feed_list[0])
        for f in feed_list[1:]:
            enforce(_feed_signature(f) == sig0,
                    "run_steps feeds must share one signature "
                    "(same names, shapes, dtypes)",
                    exc=InvalidArgumentError)
        scope = scope or global_scope()
        fetch_names = [f.name if isinstance(f, Variable) else f
                       for f in (fetch_list or [])]

        k = len(feed_list)
        program = self._prepare_program(program, scope)
        self._validate_fetches(program, feed_list[0], fetch_names)
        avail_key = self._scope_avail_key(program, scope)
        key = ("scan", k, id(program), program._version, sig0,
               tuple(fetch_names), id(scope), avail_key,
               _fusion_flags_key())
        compiled = self._cache.get(key)
        if compiled is None:
            ro, rw, out_only = self._analyze_state(
                program, scope, list(feed_list[0].keys()), fetch_names)
            state_out_names = sorted(set(rw) | set(out_only))
            feed_names = list(feed_list[0].keys())
            step = self._build_step_fn(program, feed_names, fetch_names,
                                       ro, rw, state_out_names)
            rw_idx = {n: state_out_names.index(n) for n in rw}
            oo_idx = {n: state_out_names.index(n) for n in out_only}

            def loop(feed_stacks, ro_vals, rw_vals, seed):
                def body(carry, xs):
                    rw_vals, i = carry
                    fetches, new_state = step(xs, ro_vals, rw_vals,
                                              seed + i)
                    new_rw = tuple(new_state[rw_idx[n]] for n in rw)
                    # only the write-only slots ride the stacked ys — the
                    # big read-write state (params, accumulators) stays in
                    # the carry so the loop holds ONE copy, not K
                    oo = tuple(new_state[oo_idx[n]] for n in out_only)
                    return (new_rw, i + 1), (fetches, oo)

                (rw_final, _), (fetches, oo_stack) = jax.lax.scan(
                    body, (rw_vals, jnp.uint32(0)), feed_stacks)
                by_name = dict(zip(rw, rw_final))
                by_name.update({n: s[-1] for n, s in zip(out_only,
                                                         oo_stack)})
                final_state = tuple(by_name[n] for n in state_out_names)
                return fetches, final_state

            # donation/aliasing hints: rw state is always donated; a
            # memory-PLANNED program additionally donates the stacked
            # feeds — _place_feed_stack materializes a fresh stack every
            # call (jnp.stack / device_put of host values), so XLA may
            # fold the feed buffers into its temp arena for the planned
            # step without invalidating anything the caller holds
            jit_kwargs: Dict[str, Any] = {
                "donate_argnums": ((0, 2) if getattr(
                    program, "_memory_plan_applied", False) else (2,))}
            scan_sh = self._scan_shardings(program, feed_names, fetch_names,
                                           ro, rw, state_out_names)
            if scan_sh is not None:
                jit_kwargs["in_shardings"] = scan_sh[0]
                jit_kwargs["out_shardings"] = scan_sh[1]
            fn = jax.jit(loop, **jit_kwargs)
            compiled = _CompiledStep(fn, ro, rw, list(feed_list[0].keys()),
                                     fetch_names, "run_steps",
                                     ("run_steps", k, jit_kwargs))
            compiled.state_out_names = state_out_names
            compiled.owner, compiled.prepared = self, program
            self._stash_flops_estimate(compiled, program,
                                       feed=feed_list[0])
            self._cache[key] = compiled

        feed_stacks = tuple(
            self._place_feed_stack(program, n, [f[n] for f in feed_list])
            for n in compiled.feed_names)
        ro_vals = tuple(scope.get(n) for n in compiled.ro_names)
        rw_vals = tuple(scope.get(n) for n in compiled.rw_names)
        # k seeds are consumed (seed+0 .. seed+k-1): advance the counter by
        # k so neither the next run_steps nor a plain run() reuses them
        seed = np.uint32((program.random_seed * 1000003
                          + self._run_counter + 1) % (2 ** 31))
        self._run_counter += k
        if getattr(compiled, "census_state_bytes", None) is None:
            from ..observability.memory import per_device_bytes
            compiled.census_state_bytes = sum(
                per_device_bytes(v) for v in ro_vals + rw_vals)
        t0 = time.time()
        with _tracing.span("step", "executor/run_steps", steps=k):
            fn = compiled.fn
            launch = compiled.launch.get(fn)
            if launch is None:
                args = (feed_stacks, ro_vals, rw_vals, seed)
                with compiled.first_run(fn, args) as first:
                    launch = first.launch or first.keep(
                        fn.lower(*args).compile())
                    fetches, final_state = launch(*args)
            else:
                fetches, final_state = launch(feed_stacks, ro_vals, rw_vals,
                                              seed)
        if flags.get_flag("check_nan_inf") and jax.default_backend() != "cpu":
            # same contract as run(): sweep BEFORE the scope write-back so
            # the last-good parameters stay checkpointable when a step in
            # the fused window diverges
            self._sweep_nonfinite(
                list(zip(compiled.fetch_names, fetches)) +
                list(zip(compiled.state_out_names, final_state)),
                "rerun the window step-by-step under JAX_PLATFORMS=cpu "
                "with PTPU_CHECK_NAN_INF=1 to localize")
        for name, val in zip(compiled.state_out_names, final_state):
            scope.set_var(name, val)
        self._note_run_memory(compiled, time.time() - t0, steps=k)
        if return_numpy:
            return [as_numpy(f) for f in fetches]
        return list(fetches)

    def prepare(self,
                program: Optional[Program] = None,
                feed: Optional[Dict[str, Any]] = None,
                fetch_list: Optional[Sequence[Union[str, Variable]]] = None,
                scope: Optional[Scope] = None,
                name: Optional[str] = None) -> "PreparedStep":
        """Compile (or fetch from cache) the step for this exact
        (program, feed signature, fetch list, scope) and return a
        PreparedStep whose run() skips every per-call setup cost.

        `feed` is an EXAMPLE feed carrying the signature (names, shapes,
        dtypes) every later PreparedStep.run call must match. `name` is the
        `program` of the step's `executor/compile_or_load` spans where this
        call builds it (an engine names its tick programs)."""
        program = program or default_main_program()
        user_names = set(feed or {})
        feed = self._synthesize_batch_mask(program, dict(feed or {}))
        fetch_names = [f.name if isinstance(f, Variable) else f
                       for f in (fetch_list or [])]
        scope = scope or global_scope()
        compiled = self._lookup_or_compile(program, feed, fetch_names, scope,
                                           name=name)
        # keys synthesize added beyond the caller's example feed (the
        # reserved @batch_row_mask) become per-call constants: the batch
        # size is pinned by the prepared signature, so the all-ones mask
        # is too
        injected = {n: jnp.asarray(v) for n, v in feed.items()
                    if n not in user_names}
        return PreparedStep(compiled, scope, self, program.random_seed,
                            injected, feed)

    def _aot_compiled(self, compiled: _CompiledStep, feed, scope):
        """The AOT `lower().compile()` twin of a cached step, memoized on
        it: the object that exposes XLA's cost_analysis / memory_analysis
        / as_text. The AOT path bypasses the jit executable cache, so
        without the memo every analysis call would pay a full XLA
        compile. Feed names absent from `feed` fall back to scope values
        (the bench tools' convention)."""
        aot = getattr(compiled, "aot_cache", None)
        if aot is None:
            launch = compiled.launch.get(compiled.fn)
            if isinstance(launch, _Stored) and not launch.loaded:
                # a miss of the store compiled this very function ahead of
                # time, in this process, for the step's own arguments
                compiled.aot_cache = launch.executable
                return launch.executable
            feed_vals = tuple(
                jnp.asarray(feed[n]) if n in feed else scope.get(n)
                for n in compiled.feed_names)
            ro_vals = tuple(scope.get(n) for n in compiled.ro_names)
            rw_vals = tuple(scope.get(n) for n in compiled.rw_names)
            aot = compiled.fn.lower(feed_vals, ro_vals, rw_vals,
                                    np.uint32(0)).compile()
            compiled.aot_cache = aot
        return aot

    def _compiled_for(self, program, feed, fetch_list, scope):
        """(compiled step, feed, scope) for the analysis entry points below:
        defaults resolved, compiled on a cache miss."""
        program = program or default_main_program()
        feed = dict(feed or {})
        scope = scope or global_scope()
        fetch_names = [f.name if isinstance(f, Variable) else f
                       for f in (fetch_list or [])]
        return (self._lookup_or_compile(program, feed, fetch_names, scope),
                feed, scope)

    def cost_analysis(self, program=None, feed=None, fetch_list=None,
                      scope=None):
        """XLA cost analysis (flops, bytes accessed) of the compiled step for
        the given (program, feed, fetch) — the evidence the reference
        publishes next to its benchmark tables (reference
        benchmark/README.md:33). Compiles if not already cached."""
        compiled, feed, scope = self._compiled_for(program, feed, fetch_list,
                                                   scope)
        ca = getattr(compiled, "cost_analysis_cache", None)
        if ca is None:
            ca = self._aot_compiled(compiled, feed, scope).cost_analysis()
            compiled.cost_analysis_cache = ca
        return ca

    def compiled_hlo(self, program=None, feed=None, fetch_list=None,
                     scope=None) -> str:
        """Optimized (post-partitioning) HLO text of the compiled step for
        the given (program, feed, fetch): what the device actually runs —
        which kernels stayed custom calls, which collectives the
        partitioner inserted. Compiles (AOT, memoized) if needed."""
        compiled, feed, scope = self._compiled_for(program, feed, fetch_list,
                                                   scope)
        return self._aot_compiled(compiled, feed, scope).as_text()

    def memory_analysis(self, program=None, feed=None, fetch_list=None,
                        scope=None):
        """Measured per-device memory of the compiled step from the XLA
        executable's buffer assignment: argument / output / temp / alias
        bytes (`observability.memory.executable_memory`, with the
        documented HLO liveness-walk fallback when the backend reports a
        zero temp figure). Compiles (AOT, memoized) if needed; updates
        the `executor_temp_bytes` watermark with what it measured."""
        compiled, feed, scope = self._compiled_for(program, feed, fetch_list,
                                                   scope)
        from ..observability import memory as _memory
        stats = _memory.executable_memory(
            self._aot_compiled(compiled, feed, scope))
        _memory.update_watermark("executor_temp_bytes",
                                 stats["temp_bytes"])
        return stats

    def memory_census(self, feed=None, program=None, scope=None,
                      kv_names=()):
        """The full measured memory census of the LAST compiled step
        (`observability.memory.device_memory_census`): per-device state
        bytes by category from the actual scope arrays, feed bytes, the
        XLA executable's argument/output/temp/alias figures, and a
        process-wide live-array sweep. Run the step once first."""
        from ..observability import memory as _memory
        return _memory.device_memory_census(
            self, dict(feed or {}), scope or global_scope(),
            program=program, dp=int(getattr(self, "_dp", 1)),
            kv_names=kv_names)

    def close(self):
        """≙ Executor::Close (reference executor.cc:48) — drop caches."""
        self._cache.clear()


def scope_initialize_from(program: Program, scope: Scope):
    """Ensure all persistable vars declared by `program` exist in scope as
    zero arrays — used by tests; real init runs the startup program."""
    for b in program.blocks:
        for v in b.vars.values():
            if v.persistable and not scope.has_var(v.name):
                enforce(v.shape is not None and -1 not in v.shape,
                        f"cannot zero-init var {v.name} with shape {v.shape}",
                        exc=InvalidArgumentError)
                scope.set_var(v.name, jnp.zeros(v.shape, dtype=v.dtype))
