"""Block → jax function tracing.

This replaces the reference's op-by-op interpreting Executor hot loop
(reference: paddle/fluid/framework/executor.cc:321-340 "for op in ctx->ops_:
op->Run(scope, place)") with a single trace of the whole block into one jax
function, which XLA compiles and fuses. The `vjp_region` pseudo-op (appended by
backward.append_backward) is executed via jax.vjp — compiler-native source
transformation replacing the reference's per-op GradOpDescMaker pipeline
(reference python/paddle/fluid/backward.py:469).
"""

from __future__ import annotations

from typing import Any, Dict, List, Sequence, Set

import jax
import jax.numpy as jnp

from ..core import flags
from ..core.enforce import EnforceError, NotFoundError
from .program import Block, Operator
from .registry import LowerCtx, lookup_op

SEQLEN_SUFFIX = "@SEQLEN"
GRAD_SUFFIX = "@GRAD"

# Region op type -> runner(region_op, seg_indices, env, block, ctx). A
# region op consumes a recorded segment of forward ops (attrs["fwd_ops"])
# and executes it specially: vjp_region under jax.vjp (below);
# pp_pipeline_region under the pipeline schedule engine (registered by
# parallel/pipeline.py at import).
REGION_RUNNERS: Dict[str, Any] = {}


def grad_var_name(name: str) -> str:
    return name + GRAD_SUFFIX


def _gather_inputs(op: Operator, env: Dict[str, Any]) -> Dict[str, List[Any]]:
    ins = {}
    for slot, names in op.inputs.items():
        vals = []
        for n in names:
            if n not in env:
                raise NotFoundError(
                    f"op {op.type!r} reads variable {n!r} (slot {slot!r}) "
                    f"which is not initialized — run the startup program or "
                    f"feed it")
            vals.append(env[n])
        ins[slot] = vals
    return ins


def _scatter_outputs(op: Operator, outs: Dict[str, List[Any]],
                     env: Dict[str, Any], block: Block):
    check_nan = flags.get_flag("check_nan_inf")
    for slot, names in op.outputs.items():
        vals = outs.get(slot)
        if vals is None:
            continue
        for n, v in zip(names, vals):
            if v is None:
                continue
            try:
                var = block.var(n)
                if var.stop_gradient and not var.persistable:
                    v = jax.lax.stop_gradient(v)
            except NotFoundError:
                pass
            if check_nan and hasattr(v, "dtype") and jnp.issubdtype(
                    v.dtype, jnp.floating):
                _nan_guard(op.type, n, v)
            env[n] = v


def _nan_guard(op_type: str, name: str, value):
    """Debug-mode NaN/Inf scan (≙ FLAGS_check_nan_inf + CheckTensorNANOrInf,
    reference framework/operator.cc:651,726-736). Host callbacks are a
    CPU-debug facility — a per-op host round trip has no place on the
    accelerator's hot path, so the guard no-ops off-CPU (the executor's
    fetch-time sweep covers the chip; rerun under JAX_PLATFORMS=cpu to
    localize)."""
    if jax.default_backend() != "cpu":
        from ..ops.tensor_ops import _warn_guards_inactive
        _warn_guards_inactive()
        return
    bad = jnp.logical_not(jnp.all(jnp.isfinite(value)))

    def _report(bad_flag, op_type=op_type, name=name):
        if bool(bad_flag):
            raise FloatingPointError(
                f"NaN/Inf detected in output {name!r} of op {op_type!r}")

    jax.debug.callback(_report, bad)


def run_op(op: Operator, env: Dict[str, Any], block: Block, ctx: LowerCtx):
    opdef = lookup_op(op.type)
    ins = _gather_inputs(op, env)
    try:
        outs = opdef.lower(ctx, ins, op.attrs)
    except EnforceError:
        raise
    except Exception as e:  # re-raise with op context, keep traceback
        raise type(e)(f"[while lowering op {op.type!r} "
                      f"{op.inputs} -> {op.outputs}] {e}") from e
    _scatter_outputs(op, outs or {}, env, block)


def _ancestor_op_indices(block: Block, upto: int, roots: Set[str]) -> List[int]:
    """Indices (< upto) of ops needed to compute vars in `roots`
    (≙ _find_op_path_, reference python/paddle/fluid/backward.py:645)."""
    needed = set(roots)
    keep = []
    for i in range(upto - 1, -1, -1):
        op = block.ops[i]
        if needed & set(op.output_names()):
            keep.append(i)
            needed |= set(op.input_names())
    keep.reverse()
    return keep


def build_plan(block: Block):
    """Pre-scan the block into an execution plan.

    Ops consumed by a vjp_region execute *inside* jax.vjp; the region runs at
    the position of its earliest forward op so downstream consumers (metric
    ops etc.) see the forward values.
    """
    regions: Dict[int, list] = {}  # first_fwd_index -> [(region_op, seg), ...]
    consumed: Set[int] = set()
    region_ops: Set[int] = set()
    for i, op in enumerate(block.ops):
        if op.type in REGION_RUNNERS:
            seg = op.attrs["fwd_ops"]
            if not seg:
                continue
            # multiple regions may share the earliest forward op (two losses
            # over one trunk) — keep them all, in program order
            regions.setdefault(min(seg), []).append((op, list(seg)))
            consumed |= set(seg)
            region_ops.add(i)

    plan = []
    for i, op in enumerate(block.ops):
        for region_op, seg in regions.get(i, ()):
            plan.append(("region", region_op, seg))
        if i in consumed or i in region_ops:
            continue
        plan.append(("op", op))
    return plan


# Optimizer ops with a SelectedRows (sparse) apply branch — the only
# sanctioned consumers of a sparse grad (≙ the reference's SelectedRows
# optimizer kernels, adam_op.h / math/selected_rows_functor.cc).
SPARSE_CAPABLE_OPT_OPS = frozenset({"sgd", "momentum", "adam"})


def _find_sparse_embedding_specs(seg_ops, target_names, env, block, ctx):
    """Params whose gradient can ship as (rows, values) instead of a dense
    [vocab, dim] array: an is_sparse lookup_table param, read exactly once in
    the segment, ids available before the region, every block-level consumer
    of its @GRAD a sparse-capable optimizer op, and the grad not fetched."""
    fetches = set(ctx.extras.get("fetch_names", ()))
    specs = []
    for op in seg_ops:
        if op.type != "lookup_table" or not op.attrs.get("is_sparse"):
            continue
        w = op.inputs["W"][0]
        gname = grad_var_name(w)
        if w not in target_names or gname in fetches:
            continue
        ids_name = op.inputs["Ids"][0]
        if ids_name not in env:
            continue  # ids computed inside the region: dense fallback
        reads = sum(n == w for o in seg_ops
                    for ns in o.inputs.values() for n in ns)
        if reads != 1:
            continue  # table also read elsewhere: grads would be partial
        consumers = [o.type for o in block.ops
                     if gname in {n for ns in o.inputs.values() for n in ns}]
        if not consumers or any(t not in SPARSE_CAPABLE_OPT_OPS
                                for t in consumers):
            continue
        specs.append((w, op.outputs["Out"][0], ids_name,
                      op.attrs.get("padding_idx", None)))
    return specs


def remat_boundaries(seg_op_lists, out_need: Set[str]):
    """Per-segment carried-out name lists for a segmented-remat region:
    segment i's boundary = names produced at/before segment i that a
    LATER segment reads, or that the region must publish (`out_need` —
    the narrowed live-out set plus the loss). Everything else a segment
    produces is recomputed from its boundary input during the backward
    (jax.checkpoint per segment). The ONE copy shared by the executing
    runner below and the planner's predicted-peak model
    (framework/memory_plan.py) — prediction and execution cannot drift."""
    reads_after = []
    acc: Set[str] = set()
    for ops in reversed(seg_op_lists):
        reads_after.insert(0, set(acc))
        for op in ops:
            acc |= set(op.input_names())
    boundaries = []
    avail: Set[str] = set()
    for i, ops in enumerate(seg_op_lists):
        for op in ops:
            avail |= set(op.output_names())
        boundaries.append(sorted((reads_after[i] | out_need) & avail))
    return boundaries


def _run_vjp_region_segmented(region_op, seg_indices, env, block, ctx,
                              segments):
    """Segmented-remat execution of a vjp_region (attrs set by the memory
    planner, framework/memory_plan.py): the forward runs as a chain of
    jax.checkpoint'd segment functions, so the backward of segment i
    recomputes ONLY segment i's activations from its carried boundary —
    the executable form of the remat-vs-stash plan (Checkmate-style
    segmentation; the pipeline engine's stage-granular checkpointing is
    the same idea at stage boundaries). attrs consulted:
      remat_segments     list of block-op-index lists partitioning fwd_ops
      remat_policy       optional jax.checkpoint_policies name per segment
      remat_prevent_cse  default True (real recompute); False lets XLA CSE
                         recomputation back into the forward where that
                         wins wall-clock (documented tradeoff)
    """
    attrs = region_op.attrs
    target_names: List[str] = attrs["targets"]
    loss_name: str = attrs["loss"]
    seg_ops_all = [block.ops[i] for i in seg_indices]
    produced: List[str] = []
    for op in seg_ops_all:
        for n in op.output_names():
            if n not in produced:
                produced.append(n)
    live_out = attrs.get("live_out")
    if live_out is not None:
        live = set(live_out) | set(ctx.extras.get("fetch_names", ()))
        produced = [n for n in produced if n in live]
    base_env = {k: v for k, v in env.items()}
    dense_names = list(target_names)
    seg_op_lists = [[block.ops[i] for i in seg] for seg in segments]
    # boundaries computed at TRACE time (not plan time) so run-specific
    # fetch targets are carried out of their producing segment
    boundaries = remat_boundaries(seg_op_lists,
                                  set(produced) | {loss_name})
    policy_name = attrs.get("remat_policy")
    policy = (getattr(jax.checkpoint_policies, policy_name)
              if policy_name else None)
    prevent_cse = bool(attrs.get("remat_prevent_cse", True))

    def fwd(dense_vals, perturb_vals):
        carried_names: List[str] = []
        carried_vals = ()
        for i, ops in enumerate(seg_op_lists):
            bn = boundaries[i]

            def seg_fn(dv, cv, _ops=ops, _cn=list(carried_names), _bn=bn):
                e = dict(base_env)
                e.update(zip(dense_names, dv))
                e.update(zip(_cn, cv))
                for op in _ops:
                    run_op(op, e, block, ctx)
                return tuple(e[n] for n in _bn)

            seg_fn = jax.checkpoint(seg_fn, policy=policy,
                                    prevent_cse=prevent_cse)
            carried_vals = seg_fn(dense_vals, carried_vals)
            carried_names = bn
        e = dict(zip(carried_names, carried_vals))
        loss = e[loss_name]
        aux = tuple(e[n] for n in produced)
        return loss, aux

    missing = [n for n in dense_names if n not in env]
    if missing:
        raise NotFoundError(
            f"vjp_region differentiates wrt {missing} which are not "
            f"initialized — run the startup program or feed them")
    dense_vals = tuple(env[n] for n in dense_names)
    loss_val, vjp_fn, aux = jax.vjp(fwd, dense_vals, (), has_aux=True)
    seed = jnp.ones_like(loss_val)
    dgrads, _ = vjp_fn(seed)
    env.update(zip(produced, aux))
    env[grad_var_name(loss_name)] = seed
    for name, g in zip(dense_names, dgrads):
        env[grad_var_name(name)] = g


def run_vjp_region(region_op: Operator, seg_indices: Sequence[int],
                   env: Dict[str, Any], block: Block, ctx: LowerCtx):
    """Execute a forward segment under jax.vjp, producing forward vars AND
    gradients (≙ append_backward's emitted grad-op chain, reference
    backward.py:315-469, executed by the compiler instead)."""
    attrs = region_op.attrs
    segments = attrs.get("remat_segments")
    if segments:
        # the planner refuses to segment regions with sparse-capable
        # embedding lookups (the perturbation trick below needs the
        # un-segmented trace); re-check here so a hand-set attr degrades
        # to the plain path instead of mis-training
        sparse_free = not any(
            block.ops[i].type == "lookup_table"
            and block.ops[i].attrs.get("is_sparse")
            for i in seg_indices)
        if sparse_free and sorted(i for s in segments for i in s) == \
                sorted(seg_indices):
            return _run_vjp_region_segmented(region_op, seg_indices, env,
                                             block, ctx, segments)
    target_names: List[str] = attrs["targets"]        # vars to differentiate wrt
    loss_name: str = attrs["loss"]
    seg_ops = [block.ops[i] for i in seg_indices]
    produced: List[str] = []
    for op in seg_ops:
        for n in op.output_names():
            if n not in produced:
                produced.append(n)

    # memory_optimize (transpiler/memory_optimization.py) narrows the forward
    # vars published out of the region to the live-out set it computed, plus
    # whatever this run actually fetches (liveness can't see fetch lists).
    live_out = attrs.get("live_out")
    if live_out is not None:
        live = set(live_out) | set(ctx.extras.get("fetch_names", ()))
        produced = [n for n in produced if n in live]

    # Snapshot of everything the segment may read, minus the diff targets.
    base_env = {k: v for k, v in env.items()}

    # Sparse embedding grads: differentiate wrt a zero perturbation ADDED to
    # the lookup output instead of wrt the [vocab, dim] table — the
    # perturbation's cotangent IS the per-row gradient values, and the rows
    # are the ids. The table never takes a dense gradient.
    sparse_specs = _find_sparse_embedding_specs(seg_ops, target_names, env,
                                                block, ctx)
    sparse_names = {w for w, _, _, _ in sparse_specs}
    dense_names = [n for n in target_names if n not in sparse_names]
    perturb_for = {out: i for i, (_, out, _, _) in enumerate(sparse_specs)}
    perturbs = []
    for w, _, ids_name, _ in sparse_specs:
        wval, ids = env[w], env[ids_name]
        idshape = (ids.shape[:-1] if ids.ndim >= 2 and ids.shape[-1] == 1
                   else ids.shape)
        perturbs.append(jnp.zeros(idshape + (wval.shape[1],),
                                  dtype=wval.dtype))

    def fwd(dense_vals, perturb_vals):
        env2 = dict(base_env)
        env2.update(zip(dense_names, dense_vals))
        for op in seg_ops:
            run_op(op, env2, block, ctx)
            for n in op.output_names():
                i = perturb_for.get(n)
                if i is not None:
                    env2[n] = env2[n] + perturb_vals[i]
        loss = env2[loss_name]
        aux = tuple(env2[n] for n in produced)
        return loss, aux

    # Rematerialization (set by transpiler.memory_optimize ≙ the reference's
    # memory_optimization_transpiler): trade FLOPs for HBM by recomputing the
    # forward in the backward pass under the chosen checkpoint policy.
    if attrs.get("remat"):
        policy_name = attrs.get("remat_policy")
        policy = (getattr(jax.checkpoint_policies, policy_name)
                  if policy_name else None)
        fwd = jax.checkpoint(fwd, policy=policy)

    missing = [n for n in dense_names if n not in env]
    if missing:
        raise NotFoundError(
            f"vjp_region differentiates wrt {missing} which are not "
            f"initialized — run the startup program or feed them")
    dense_vals = tuple(env[n] for n in dense_names)
    loss_val, vjp_fn, aux = jax.vjp(fwd, dense_vals, tuple(perturbs),
                                    has_aux=True)
    seed = jnp.ones_like(loss_val)  # ≙ fill_constant loss@GRAD=1 (backward.py:566)
    dgrads, pgrads = vjp_fn(seed)
    env.update(zip(produced, aux))
    env[grad_var_name(loss_name)] = seed
    for name, g in zip(dense_names, dgrads):
        env[grad_var_name(name)] = g
    if sparse_specs:
        from .selected_rows import TracedSelectedRows
        for (w, _, ids_name, padding_idx), pg in zip(sparse_specs, pgrads):
            ids = env[ids_name]
            if ids.ndim >= 2 and ids.shape[-1] == 1:
                ids = jnp.squeeze(ids, axis=-1)
            rows = ids.reshape(-1)
            vals = pg.reshape((-1, pg.shape[-1]))
            if padding_idx is not None:
                pad = (padding_idx if padding_idx >= 0
                       else padding_idx + env[w].shape[0])
                vals = vals * (rows != pad)[:, None].astype(vals.dtype)
            env[grad_var_name(w)] = TracedSelectedRows(
                rows, vals, env[w].shape[0])


REGION_RUNNERS["vjp_region"] = run_vjp_region


from .registry import register_op  # noqa: E402


@register_op("vjp_region", stop_gradient=True)
def _vjp_region_stub(ctx, ins, attrs):
    # Never lowered directly — handled by build_plan/run_vjp_region. Appears in
    # the registry so Operator construction validates (≙ OpInfoMap entry).
    raise RuntimeError("vjp_region must be executed via the block planner")


def run_plan(plan, env: Dict[str, Any], block: Block, ctx: LowerCtx):
    for step in plan:
        if step[0] == "op":
            run_op(step[1], env, block, ctx)
        else:
            _, region_op, seg = step
            REGION_RUNNERS[region_op.type](region_op, seg, env, block, ctx)
    return env
