"""Memory + utilization observability: census, watermarks, MFU.

The r12 ledger closed the loop on WIRE bytes (predicted == census
exactly) and r09 on bubbles (2% band); the `memory` section of
`costs.predict` stayed a pure static estimate with no measured side.
This module is the measured side — the sensor layer ROADMAP items 1
(auto-parallel planner) and 2 (memory planner) both stand on:

- **executable census** (`executable_memory`): per-device
  argument/output/temp/alias bytes from the XLA executable's buffer
  assignment (`compiled.memory_analysis()` — per-DEVICE on sharded
  compiles, verified on the virtual mesh). Where the backend reports
  `temp_size_in_bytes == 0` (this container's jaxlib-0.4.x CPU backend
  does for some programs), the documented fallback is a liveness walk
  over the scheduled HLO text (`costs.hlo_liveness_temp_bytes`), tagged
  `temp_source: "hlo_liveness_walk"` so an artifact never passes off an
  estimate as a backend report.
- **live-state census** (`state_census` / `device_memory_census`): the
  executor's state walked from the scope — params, ZeRO accumulators,
  error-feedback residuals, KV-cache slots, everything else — measured
  from the ACTUAL device arrays (committed bytes over the arrays' own
  shard counts = per-device bytes), plus a `jax.live_arrays()` sweep
  that counts device bytes the scope does not track (the host-side
  truth a dossier wants after an OOM-shaped death).
- **watermarks** (`update_watermark`): live per-channel high-water
  marks — device state, executor temp, KV cache, checkpoint host
  staging — each update records a `memory`-channel counter sample
  (Chrome counter track via `tracing.record_counter`) and backs the
  `ptpu_memory_*` gauges in `metrics.default_registry()`, so one
  /metrics scrape and a flight-recorder dossier both carry the memory
  board.
- **MFU** (`note_mfu`): `costs.predict` flops over measured step time
  as the `ptpu_mfu` gauge — the utilization signal the planner search
  trusts its cost model against (TVM-style measured feedback,
  PAPERS.md).

The ledger's accounting identity over all of this lives in
`observability/ledger.py` (`check_memory_identity`), held per builder
by tests/test_memory_obs.py.
"""

from __future__ import annotations

import threading
from typing import Any, Dict, Optional, Sequence

import numpy as np

from ..core.enforce import InvalidArgumentError, enforce

#: the watermark channels (fixed set: a typo'd channel raises instead of
#: minting a gauge no scrape ever finds)
CHANNELS = ("device_state_bytes", "executor_temp_bytes",
            "kv_cache_bytes", "kv_cache_used_bytes",
            "host_staging_bytes", "host_kv_bytes",
            "host_optimizer_bytes")

_lock = threading.Lock()
_marks: Dict[str, Dict[str, float]] = {
    c: {"current": 0.0, "peak": 0.0} for c in CHANNELS}
_mfu = {"value": 0.0, "flops": 0.0, "step_s": 0.0}
_metrics = None


def memory_metrics():
    """The memory/utilization series, registered (idempotently) into
    `metrics.default_registry()` — `ptpu_memory_<channel>` (current
    level), `ptpu_memory_watermark_bytes{channel=...}` (high-water), and
    `ptpu_mfu`. One /metrics scrape sees them next to `ptpu_ckpt_*` and
    `ptpu_train_*` (the r16 unified-registry discipline)."""
    global _metrics
    if _metrics is None:
        from . import metrics as m
        r = m.default_registry()
        out: Dict[str, Any] = {}
        for c in CHANNELS:
            out[c] = m.get_or_create(
                r, "gauge", f"ptpu_memory_{c}",
                f"Current {c.replace('_', ' ')} (memory census channel).",
                fn=(lambda c=c: _marks[c]["current"]))
            out[f"{c}_peak"] = m.get_or_create(
                r, "gauge", "ptpu_memory_watermark_bytes",
                "Per-channel high-water mark of the memory census.",
                labels={"channel": c},
                fn=(lambda c=c: _marks[c]["peak"]))
        out["mfu"] = m.get_or_create(
            r, "gauge", "ptpu_mfu",
            "Model-flops utilization: predicted step flops over measured "
            "step time, fraction of the hardware peak.",
            fn=(lambda: _mfu["value"]))
        _metrics = out
    return _metrics


def update_watermark(channel: str, value: float):
    """Set a channel's current level; the high-water mark ratchets.
    When tracing is enabled the sample also lands on the ring as a
    `memory/<channel>` counter event (Chrome counter track,
    tools/trace_merge.py gives it a per-rank lane). This is the
    executor's per-step hot path — no eager f-strings, one dict probe
    for the channel check."""
    m = _marks.get(channel)
    if m is None:
        raise InvalidArgumentError(
            f"unknown memory channel {channel!r}; known: "
            f"{list(CHANNELS)}")
    if _metrics is None:
        memory_metrics()
    v = float(value)
    with _lock:
        m["current"] = v
        if v > m["peak"]:
            m["peak"] = v
    from . import tracing as _tracing
    if _tracing.enabled():
        _tracing.record_counter("memory/" + channel, v)


def note_mfu(flops: float, step_s: float,
             peak_flops: Optional[float] = None):
    """One measured step: predicted flops over wall seconds -> the
    `ptpu_mfu` gauge (+ a `memory/mfu` counter sample when tracing).
    Callers measure step_s across a dispatch window; under donated-state
    backpressure successive dispatches track true step time.

    The peak is the computing device's own (`costs.device_peaks` by
    `device_kind`) unless passed explicitly. On a device the table lacks —
    the CPU included — NO sample is recorded: a fraction of some other
    chip's peak is not a utilization."""
    from ..framework import costs as _costs
    if peak_flops is None:
        import jax
        peaks = _costs.device_peaks(jax.devices()[0].device_kind)
        if peaks is None:
            return
        peak_flops = peaks["peak_flops"]
    memory_metrics()
    with _lock:
        _mfu["flops"] = float(flops)
        _mfu["step_s"] = float(step_s)
        _mfu["value"] = _costs.mfu(flops, step_s, peak_flops)
    from . import tracing as _tracing
    _tracing.record_counter("memory/mfu", _mfu["value"])


def watermark_board() -> Dict[str, Dict[str, float]]:
    """{channel: {current, peak}} + the last MFU reading — what
    /healthz and the flight-recorder dossier embed as the memory
    board."""
    with _lock:
        out: Dict[str, Any] = {c: dict(v) for c, v in _marks.items()}
        out["mfu"] = dict(_mfu)
    return out


def reset_watermarks():
    """Test isolation: zero every channel and the MFU reading."""
    with _lock:
        for v in _marks.values():
            v["current"] = v["peak"] = 0.0
        _mfu.update(value=0.0, flops=0.0, step_s=0.0)


# ---------------------------------------------------------------------------
# measured census
# ---------------------------------------------------------------------------


def per_device_bytes(val) -> float:
    """Per-device bytes of one array: committed bytes over the array's
    own shard count (replicated on N devices: N copies / N = one; dim-0
    sharded: total / N). Host/numpy values count their nbytes whole —
    they live on the one local device once placed."""
    shards = getattr(val, "addressable_shards", None)
    if shards:
        return sum(s.data.nbytes for s in shards) / len(shards)
    return float(getattr(val, "nbytes", 0) or 0)


def _var_category(v, name: str, kv_names) -> str:
    # kv_cache is a census-side refinement of other_state (the static
    # walk cannot know which persistables are slot caches); everything
    # else goes through the ONE classifier shared with the predicted
    # walk (costs.state_category), so the ledger's exact per-category
    # checks cannot fail from classifier drift
    if name in kv_names:
        return "kv_cache"
    from ..framework.costs import state_category
    return state_category(v, name)


def state_census(scope, program, names: Sequence[str],
                 kv_names: Sequence[str] = ()) -> Dict:
    """Measured per-device state bytes by category for the named scope
    vars (a compiled step's ro + rw lists): params / params_quantized /
    optimizer_state / ef_residual / kv_cache / other_state, each from the
    ACTUAL device arrays via `per_device_bytes`. `kv_names` marks the
    serving engine's slot-cache vars (they are plain persistables to the
    program)."""
    kv = set(kv_names)
    cats: Dict[str, float] = {"params": 0.0, "params_quantized": 0.0,
                              "params_draft": 0.0,
                              "optimizer_state": 0.0, "ef_residual": 0.0,
                              "kv_cache": 0.0, "other_state": 0.0}
    per_var: Dict[str, Dict] = {}
    for name in names:
        if not scope.has_var(name):
            continue
        val = scope.get(name)
        nb = per_device_bytes(val)
        v = None
        for b in program.blocks:
            if b.has_var(name):
                v = b.var(name)
                break
        cat = _var_category(v, name, kv) if v is not None else "other_state"
        cats[cat] += nb
        per_var[name] = {"category": cat, "per_device_bytes": nb}
    cats["state_total"] = sum(cats[c] for c in
                              ("params", "params_quantized",
                               "params_draft", "optimizer_state",
                               "ef_residual", "kv_cache", "other_state"))
    return {"categories": cats, "per_var": per_var}


def live_array_census(scope=None, tracked_names: Sequence[str] = ()) -> Dict:
    """The host-side truth: every live jax array in the process
    (`jax.live_arrays()`), split into scope-tracked vs untracked bytes.
    Untracked bytes are real device residency the program's state walk
    cannot see (donation ghosts, caller-held fetches, prefetch staging) —
    exactly what an OOM post-mortem needs named."""
    import jax
    tracked_ids = set()
    if scope is not None:
        for name in (tracked_names or scope.local_var_names()):
            if scope.has_var(name):
                tracked_ids.add(id(scope.get(name)))
    total = tracked = 0.0
    n = 0
    for a in jax.live_arrays():
        try:
            nb = sum(s.data.nbytes for s in a.addressable_shards)
        except Exception:
            nb = getattr(a, "nbytes", 0) or 0
        total += nb
        n += 1
        if id(a) in tracked_ids:
            tracked += nb
    return {"live_arrays": n, "committed_bytes": total,
            "tracked_bytes": tracked,
            "untracked_bytes": total - tracked}


def executable_memory(aot) -> Dict:
    """Per-device memory of one AOT-compiled executable from XLA's
    buffer assignment (`memory_analysis()`): argument / output / temp /
    alias / generated-code bytes. Falls back to the documented HLO
    liveness walk for the temp figure when the backend reports 0 on a
    program with intermediate values (`temp_source` names which side
    produced the number)."""
    from ..framework import costs as _costs
    ma = aot.memory_analysis()
    ma = ma[0] if isinstance(ma, (list, tuple)) else ma
    out = {
        "argument_bytes": int(ma.argument_size_in_bytes),
        "output_bytes": int(ma.output_size_in_bytes),
        "temp_bytes": int(ma.temp_size_in_bytes),
        "alias_bytes": int(ma.alias_size_in_bytes),
        "generated_code_bytes": int(ma.generated_code_size_in_bytes),
        "temp_source": "xla",
    }
    if out["temp_bytes"] == 0:
        walked = int(_costs.hlo_liveness_temp_bytes(aot.as_text()))
        if walked:
            out["temp_bytes"] = walked
            out["temp_source"] = "hlo_liveness_walk"
    return out


def device_memory_census(executor, feed: Dict[str, Any], scope, *,
                         program=None, compiled=None, dp: int = 1,
                         kv_names: Sequence[str] = ()) -> Dict:
    """The full measured memory census for one compiled step (the
    ledger's measured side; run the step once first so the compile
    cache is warm):

      state     per-device category bytes of the step's ro+rw scope vars
                (`state_census`, actual arrays)
      feeds     per-device bytes of the actual feed arrays — batch-led
                feeds split rows over dp, fixed-shape aux feeds
                replicated (the manual-mode placement rule)
      seed      the uint32 step seed (4)
      xla       `executable_memory` of the SAME executable (argument /
                output / temp / alias; `args balance` in the ledger
                cross-checks state+feeds+seed against argument_bytes)
      live      `live_array_census` process-wide sweep
      peak_bytes   argument + temp + non-aliased output bytes — the
                per-device live-step footprint the residual bound is
                measured against

    Updates the `device_state_bytes` and `executor_temp_bytes`
    watermarks with what it measured."""
    program = program or getattr(executor, "main_program", None)
    if program is None:
        from ..framework.program import default_main_program
        program = default_main_program()
    rewritten = executor._prepare_program(program, scope)
    if compiled is None:
        enforce(len(executor._cache) > 0,
                "device_memory_census: the executor has no compiled step "
                "yet — run the step once first (the census measures the "
                "executable the runs actually use)",
                exc=InvalidArgumentError)
        compiled = list(executor._cache.values())[-1]
    st = state_census(scope, rewritten,
                      sorted(set(compiled.ro_names)
                             | set(compiled.rw_names)),
                      kv_names=kv_names)
    import jax
    feed_bytes = 0.0
    per_feed = {}
    for name in compiled.feed_names:
        if name not in feed:
            # the bench convention Executor._aot_compiled supports:
            # feed names absent from the dict resolve to scope values —
            # real XLA arguments that memory_args_balance must see, so
            # count the placed array itself
            if scope is not None and scope.has_var(name):
                nb = per_device_bytes(scope.get(name))
                per_feed[name] = {"per_device_bytes": nb,
                                  "batch_led": False,
                                  "from_scope": True}
                feed_bytes += nb
            continue
        val = np.asarray(feed[name])
        # count CANONICAL dtypes: the device buffer is what jnp.asarray
        # makes of the host value (int64 -> int32 with x64 disabled), so
        # host nbytes would overcount exactly the narrowed feeds
        itemsize = np.dtype(
            jax.dtypes.canonicalize_dtype(val.dtype)).itemsize
        nb = float(val.size * itemsize)
        shape = None
        for b in rewritten.blocks:
            if b.has_var(name):
                shape = getattr(b.var(name), "shape", None)
                break
        batch_led = shape is None or (bool(shape) and shape[0] == -1)
        if batch_led and dp > 1:
            nb /= dp
        per_feed[name] = {"per_device_bytes": nb, "batch_led": batch_led}
        feed_bytes += nb
    aot = executor._aot_compiled(compiled, feed, scope)
    xla = executable_memory(aot)
    peak = (xla["argument_bytes"] + xla["temp_bytes"]
            + max(0, xla["output_bytes"] - xla["alias_bytes"]))
    update_watermark("device_state_bytes", st["categories"]["state_total"])
    update_watermark("executor_temp_bytes", xla["temp_bytes"])
    from ..framework import offload as _offload
    return {
        "state": st,
        "feeds": {"per_device_bytes": feed_bytes, "per_feed": per_feed,
                  "dp": dp},
        "seed_bytes": 4,
        "xla": xla,
        "live": live_array_census(scope),
        "peak_bytes": peak,
        # the second tier, from the ONE host-byte ledger (r23): the same
        # rows the host_*_bytes watermark channels publish, so a dossier
        # and /healthz cannot disagree about host residency
        "host_tier": _offload.shared_host_pool().rows(),
    }
