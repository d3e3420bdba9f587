"""Global typed flag registry.

Capability equivalent of the reference's gflags surface (DEFINE_bool/int/double in
C++, e.g. FLAGS_benchmark / FLAGS_check_nan_inf at reference
paddle/fluid/framework/executor.cc:27 and operator.cc:726) plus the Python env
bridge (`read_env_flags` in reference python/paddle/fluid/__init__.py:121-137).

Flags are typed, documented, and can be set from the environment with the
``PTPU_`` prefix, e.g. ``PTPU_CHECK_NAN_INF=1``.
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Any, Callable, Dict

from .enforce import AlreadyExistsError, NotFoundError


@dataclass
class _FlagSpec:
    name: str
    default: Any
    parser: Callable[[str], Any]
    help: str
    value: Any


_REGISTRY: Dict[str, _FlagSpec] = {}

_ENV_PREFIX = "PTPU_"


def _parse_bool(s: str) -> bool:
    return s.strip().lower() in ("1", "true", "yes", "on")


def _define(name: str, default: Any, parser, help: str) -> None:
    if name in _REGISTRY:
        raise AlreadyExistsError(f"flag {name!r} already defined")
    value = default
    env = os.environ.get(_ENV_PREFIX + name.upper())
    if env is not None:
        value = parser(env)
    _REGISTRY[name] = _FlagSpec(name, default, parser, help, value)


def define_bool(name: str, default: bool, help: str = "") -> None:
    _define(name, default, _parse_bool, help)


def define_int(name: str, default: int, help: str = "") -> None:
    _define(name, default, int, help)


def define_float(name: str, default: float, help: str = "") -> None:
    _define(name, default, float, help)


def define_string(name: str, default: str, help: str = "") -> None:
    _define(name, default, str, help)


def get_flag(name: str) -> Any:
    spec = _REGISTRY.get(name)
    if spec is None:
        raise NotFoundError(f"unknown flag {name!r}")
    return spec.value


def set_flag(name: str, value: Any) -> None:
    spec = _REGISTRY.get(name)
    if spec is None:
        raise NotFoundError(f"unknown flag {name!r}")
    spec.value = value


def set_flags(mapping: Dict[str, Any]) -> None:
    for k, v in mapping.items():
        set_flag(k, v)


def all_flags() -> Dict[str, Any]:
    return {k: v.value for k, v in _REGISTRY.items()}


def vlog(level: int, msg: str, *args) -> None:
    """Verbose logging gated on the `vlog` flag (≙ glog VLOG(level) used
    throughout the reference's C++; enable with PTPU_VLOG=N)."""
    if get_flag("vlog") >= level:
        import sys
        print(f"[VLOG{level}] " + (msg % args if args else msg),
              file=sys.stderr)


# --- Core framework flags (≙ the reference's gflags config surface, SURVEY §5) ---
define_bool("check_nan_inf", False,
            "Scan every op's outputs for NaN/Inf during execution "
            "(≙ FLAGS_check_nan_inf, reference operator.cc:726-736).")
define_bool("benchmark", False,
            "Block on device after each program run and log timings "
            "(≙ FLAGS_benchmark, reference executor.cc:27).")
define_int("vlog", 0, "Verbose logging level (≙ glog VLOG).")
define_bool("use_bf16_matmul", True,
            "Prefer bfloat16 MXU matmul precision where layers opt in.")
define_bool("disable_pallas", False,
            "Force XLA-composite lowerings for ops that default to Pallas "
            "kernels on TPU (escape hatch: PTPU_DISABLE_PALLAS=1).")
define_bool("fuse_recurrent_cells", True,
            "Executor-compile-time fuse_recurrent_cell_pass: rewrite "
            "dynamic_lstm/dynamic_gru to the fused whole-sequence cell "
            "kernels (paddle_tpu/fusion/recurrent.py — one Pallas kernel "
            "for the entire recurrence on TPU). Numerically equivalent "
            "fwd+grad; kill switch PTPU_FUSE_RECURRENT_CELLS=0.")
define_bool("fuse_decode_attention", True,
            "Executor-compile-time fuse_decode_attention_pass: rewrite the "
            "cached-decode QK^T->+bias->softmax->V op chain into one "
            "fused_decode_attention kernel per tick "
            "(paddle_tpu/fusion/decode_attention.py). Kill switch "
            "PTPU_FUSE_DECODE_ATTENTION=0.")
define_bool("pipeline", True,
            "Allow the program-level pipeline-parallel executor mode when "
            "the BuildStrategy requests it (pipeline_stages >= 2). Kill "
            "switch: PTPU_PIPELINE=0 runs the program unpartitioned (plain "
            "SPMD, replicated over the pp axis) — the escape hatch if "
            "partitioning ever misbehaves in production. Part of the "
            "executor's compile cache key (framework/executor.py "
            "_fusion_flags_key; resolved by parallel/pipeline.py "
            "pipeline_config).")
define_bool("tp_shard", True,
            "Allow the static sharding-propagation rewrite (framework/"
            "sharding.py tp_shard_pass) that makes tp-annotated parameters "
            "executable under the full-manual execution modes (explicit "
            "dp comm / pipeline). Kill switch: PTPU_TP_SHARD=0 restores "
            "the old enforce gate — tp-sharded programs are then rejected "
            "by the manual modes instead of rewritten. Part of the "
            "executor's compile cache key.")
define_bool("memory_plan", True,
            "Allow the static memory planner (framework/memory_plan.py) "
            "when the BuildStrategy requests it (memory_plan=True) or a "
            "caller applies memory_plan_pass: liveness-minimizing op "
            "scheduling, interference-graph buffer-slot coloring (verified "
            "race-free by the r13 buffer-reuse detectors on every apply), "
            "and the remat-vs-stash search that segments the backward "
            "region under jax.checkpoint. Kill switch: PTPU_MEMORY_PLAN=0 "
            "runs every program unplanned — the escape hatch if a plan "
            "ever misbehaves in production. Part of the executor's "
            "compile cache key (framework/executor.py _fusion_flags_key).")
define_bool("auto_parallel", True,
            "Allow the auto-parallel planner (framework/auto_parallel.py) "
            "when the BuildStrategy requests it (auto_parallel=True): "
            "cost-model-guided search over the dp x pp x tp strategy "
            "space that chooses the executor's BuildStrategy knobs and "
            "mesh factorization, and re-plans on elastic restore to a "
            "changed world size. Kill switch: PTPU_AUTO_PARALLEL=0 runs "
            "the user's strategy and mesh untouched — the escape hatch "
            "if a plan ever misbehaves in production. Part of the "
            "executor's compile cache key (framework/executor.py "
            "_fusion_flags_key).")
define_bool("kv_sanitize", False,
            "Shadow-state KV sanitizer (serving/sanitizer.py): mirror "
            "every BlockPool/KVPager/host-tier mutation into the abstract "
            "ownership model (framework/ownership.py) and raise "
            "SanitizerDivergence naming the op, block, and invariant on "
            "the first drift. Off by default in production (the shadow "
            "bookkeeping costs a few percent of the host tick loop); "
            "pinned ON for the whole test suite via PTPU_KV_SANITIZE=1 "
            "in tests/conftest.py, same discipline as PTPU_VERIFY_PASSES. "
            "Read at KVPager construction (attach-or-None), and part of "
            "the executor's compile cache key so a mid-process toggle "
            "never shares cached state with its instrumented twin.")
define_bool("quant_comm", True,
            "Allow quantized gradient collectives when the BuildStrategy "
            "requests them (quant_comm='int8'/'bf16'). Kill switch: "
            "PTPU_QUANT_COMM=0 forces fp32 gradient transfers everywhere "
            "while keeping the explicit reduce-scatter pipeline — the "
            "escape hatch if quantization ever hurts a model's "
            "convergence in production (parallel/grad_comm.py).")
define_bool("quant_params", True,
            "Allow weight-only quantized serving when an engine requests it "
            "(quant='int8'/'int4'): quantize_params_pass rewrites a serving "
            "program's persistable f32 weights into block-scaled (payload, "
            "scales) pairs consumed by qmatmul/qlookup (framework/passes.py, "
            "parallel/collective.py quantize_blocks_2d). Kill switch: "
            "PTPU_QUANT_PARAMS=0 serves full f32 weights — the escape hatch "
            "if quantization ever hurts decode quality in production. Part "
            "of the executor's compile cache key (framework/executor.py "
            "_fusion_flags_key).")
define_bool("trace", True,
            "Structured step tracing (observability/tracing.py): typed "
            "nested spans (compile/step/tick/pass/dp_comm/pp_tick/"
            "admission/feed_fetch) recorded into the in-process ring "
            "buffer, exportable as Chrome trace / aggregate tables and "
            "joined with analytic predictions by observability/ledger.py. "
            "Kill switch: PTPU_TRACE=0 makes every span a no-op (span "
            "enter/exit cost drops below the 0.5%%-of-step budget asserted "
            "in tests/test_observability.py).")
define_int("trace_ring", 262144,
           "Capacity of the span ring buffer (observability/tracing.py). "
           "Oldest spans are overwritten; the buffer is preallocated so "
           "recording never allocates on the hot path. A served tick "
           "records seven or eight live spans, and a benchmark window about "
           "fourteen records a tick: the default holds half a minute of a "
           "2 ms tick (a full ring keeps ~60 MB of span records alive).")
# (num_iteration_per_drop_scope lives on ExecutionStrategy for API parity;
# the functional executor has no per-iteration kid scopes to drop)
define_int("sparse_dense_apply_max_bytes", 1 << 30,
           "Lazy sparse optimizer updates (adam) switch from the "
           "merged-rows path (sort + row gather/scatter, O(batch*dim) "
           "touched) to a dense-MASKED apply (full-table elementwise, "
           "identical lazy semantics) when the table is at most this many "
           "bytes: on TPU the 160k-id sort alone costs ~12 ms while "
           "elementwise passes over a <=1 GB table cost ~1-4 ms. Set 0 to "
           "force the row path regardless of size (EP-scale tables).")
define_int("_reserved_num_iteration_per_drop_scope", 1,
           "Iterations between temporary-scope cleanups "
           "(≙ ExecutionStrategy::num_iteration_per_drop_scope_).")
