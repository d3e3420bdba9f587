"""Op registry: op type → jax lowering rule.

Capability equivalent of the reference's operator registry + kernel dispatch
(reference: paddle/fluid/framework/op_registry.h:185-236, op_kernel_type.h:27,
operator.cc:657-737). Where the reference dispatches at *runtime* to a
(place, dtype, layout, library) kernel per op, here each op registers ONE
lowering rule that emits jax/XLA operations at *trace* time; XLA then does the
per-backend kernel selection, layout assignment, and fusion. Pallas kernels
plug in as alternative lowerings gated on backend.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

from ..core.enforce import AlreadyExistsError, NotFoundError

# A lowering takes (ctx, ins, attrs) where ins: slot -> list of jax values, and
# returns outs: slot -> list of jax values.
LowerFn = Callable[["LowerCtx", Dict[str, List[Any]], Dict[str, Any]],
                   Dict[str, List[Any]]]

# An infer_spec takes (ctx, in_shapes, in_dtypes, attrs) where in_shapes /
# in_dtypes mirror the lowering's ins layout (slot -> list of shape tuples /
# numpy dtypes) and returns outs: slot -> list of (shape, dtype) pairs.
# `ctx` is an analysis.InferCtx (declared-shape lookups, mesh axis sizes).
# Most ops don't need one: the analyzer derives shapes by abstract-evaluating
# the lowering itself (jax.eval_shape), so the kernel IS the shape function
# and the two can never drift. An explicit spec is only registered where the
# lowering cannot be abstractly evaluated standalone (collectives that need a
# mesh axis, region pseudo-ops, sub-block control flow).
InferFn = Callable[[Any, Dict[str, List[tuple]], Dict[str, List[Any]],
                    Dict[str, Any]], Dict[str, List[tuple]]]


@dataclass
class OpDef:
    type: str
    lower: LowerFn
    # ops whose outputs must never be differentiated through (metrics, prints)
    stop_gradient: bool = False
    # extra metadata for passes/inspection
    tags: tuple = ()
    # optional explicit shape/dtype rule (see InferFn above); None = derive
    # from the lowering via jax.eval_shape (framework/analysis.py)
    infer_spec: Optional[InferFn] = None


_OPS: Dict[str, OpDef] = {}


def dim_prod(dims) -> Any:
    """Product of shape dims WITHOUT int() coercion: under jax.export a
    leading dim may be symbolic, and int() on it raises. Use this in any
    lowering that flattens leading dims."""
    out = 1
    for d in dims:
        out = out * d
    return out


def register_op(op_type: str, *, stop_gradient: bool = False, tags=(),
                infer_spec: Optional[InferFn] = None):
    """Decorator registering a lowering rule (≙ REGISTER_OPERATOR +
    REGISTER_OP_*_KERNEL, reference op_registry.h:185-217)."""

    def deco(fn: LowerFn) -> LowerFn:
        if op_type in _OPS:
            raise AlreadyExistsError(f"op {op_type!r} already registered")
        _OPS[op_type] = OpDef(op_type, fn, stop_gradient=stop_gradient,
                              tags=tuple(tags), infer_spec=infer_spec)
        return fn

    return deco


def register_infer_spec(op_type: str):
    """Decorator attaching an explicit shape/dtype inference rule to an
    already-registered op (≙ the reference's InferShape functions living
    next to each OpMaker, framework/operator.h InferShapeContext) — used
    where the analyzer cannot abstract-evaluate the lowering itself."""

    def deco(fn: InferFn) -> InferFn:
        op = _OPS.get(op_type)
        if op is None:
            raise NotFoundError(
                f"cannot attach infer_spec: op {op_type!r} not registered")
        if op.infer_spec is not None:
            raise AlreadyExistsError(
                f"op {op_type!r} already has an infer_spec")
        op.infer_spec = fn
        return fn

    return deco


# A shard-propagation rule mirrors infer_spec at the sharding layer
# (framework/sharding.py): (ShardCtx, in_specs, attrs) -> out_specs, where a
# spec is a per-dim tuple of mesh-axis-or-None. Rules are registered in a
# side table (not on OpDef) so sharding rules for generic ops can be
# declared without forcing the op module import graph; lookup falls back to
# the default replicated rule in framework/sharding.py.
_SHARD_RULES: Dict[str, Any] = {}


def register_shard_spec(op_type: str):
    """Decorator registering the sharding-propagation rule for `op_type`
    (lives alongside register_infer_spec: same per-op contract, one layer
    up — how shardings flow through the op instead of shapes)."""

    def deco(fn):
        if op_type in _SHARD_RULES:
            raise AlreadyExistsError(
                f"op {op_type!r} already has a shard-propagation rule")
        _SHARD_RULES[op_type] = fn
        return fn

    return deco


def lookup_shard_rule(op_type: str):
    """The registered shard-propagation rule for `op_type`, or None."""
    return _SHARD_RULES.get(op_type)


# An effect rule refines the dataflow effect set of one op
# (framework/dataflow.py): (op) -> dict with any of the keys
#   collective_axes: tuple of mesh axis names the op communicates over
#                    (a collective both orders execution across shards of
#                    those axes AND makes its outputs axis-consistent),
#   rng:             True when the op draws per-step randomness (per-shard
#                    decorrelated seeds on the dp axis),
#   inplace:         ((in_name, out_name), ...) aliased buffer pairs beyond
#                    the same-name read+write default.
# reads/writes always derive from op.inputs/op.outputs; rules only ADD the
# semantics the slot lists cannot express. Registered in a side table like
# _SHARD_RULES so parallel modules can declare effects without forcing the
# op module import graph.
_EFFECT_RULES: Dict[str, Any] = {}


def register_effects(op_type: str):
    """Decorator registering the dataflow effect rule for `op_type` (lives
    alongside register_infer_spec/register_shard_spec: same per-op
    contract, one layer up — what the op DOES to buffers and mesh axes
    instead of what shapes/shardings it emits)."""

    def deco(fn):
        if op_type in _EFFECT_RULES:
            raise AlreadyExistsError(
                f"op {op_type!r} already has an effect rule")
        _EFFECT_RULES[op_type] = fn
        return fn

    return deco


def lookup_effect_rule(op_type: str):
    """The registered effect rule for `op_type`, or None (pure compute:
    reads its inputs, writes its outputs, no collectives, no rng)."""
    return _EFFECT_RULES.get(op_type)


def lookup_op(op_type: str) -> OpDef:
    op = _OPS.get(op_type)
    if op is None:
        # Make sure all builtin op modules are imported (they self-register).
        _ensure_builtin_ops()
        op = _OPS.get(op_type)
    if op is None:
        raise NotFoundError(f"no op registered with type {op_type!r}; "
                            f"known ops: {sorted(_OPS)[:20]}...")
    return op


def registered_ops() -> List[str]:
    _ensure_builtin_ops()
    return sorted(_OPS)


_builtins_loaded = False


def _ensure_builtin_ops():
    global _builtins_loaded
    if _builtins_loaded:
        return
    _builtins_loaded = True
    # import for registration side effects
    from ..ops import (elementwise, nn_ops, tensor_ops, reduce_ops,  # noqa: F401
                       optimizer_ops, random_ops, sequence_ops, metric_ops,
                       control_ops, loss_ops, sequence_label_ops,
                       beam_search_ops, detection_ops, pallas_kernels)
    from ..fusion import (decode_attention, paged_attention,  # noqa: F401
                          recurrent, latent_attention, moe,
                          short_conv, ssm, kda)


@dataclass
class LowerCtx:
    """Per-trace context handed to lowerings (≙ ExecutionContext,
    reference framework/operator.h ExecutionContext).

    rng_key: base PRNG key for this step; ops take fresh keys via next_key().
    is_test: inference mode (dropout/batch-norm behave accordingly).
    mesh / axis info is used by parallel-aware lowerings.
    """
    rng_key: Any = None
    is_test: bool = False
    mesh: Any = None
    _rng_counter: int = 0
    extras: dict = field(default_factory=dict)

    def next_key(self):
        import jax
        self._rng_counter += 1
        return jax.random.fold_in(self.rng_key, self._rng_counter)
