"""Pipeline parallelism over the `pp` mesh axis.

Two layers live here:

1. `pipeline_apply` — the original GPipe-style ring for UNIFORM stages
   (stage parameters stacked with a leading [num_stages] dim sharded over
   `pp`; reverse-mode AD re-runs the ring backwards). Kept for callers that
   hand-stack per-stage params.

2. The program-level executor mode (≙ the reference's `pipeline_trainer` /
   program section splitting): `framework/passes.py:pipeline_partition_pass`
   cuts the op DAG into K contiguous stages and splices explicit
   `pp_send`/`pp_recv` ops at the cuts; the `pp_pipeline_region` engine in
   this module then runs a STATIC tick schedule — GPipe or non-interleaved
   1F1B (warmup / 1-forward-1-backward steady state / drain) — as one
   `lax.scan`, moving boundary activations and boundary gradients with one
   `ppermute` each per tick (GDP frames the placement as cost-modeled graph
   partitioning, arXiv 1910.01578; keeping stage transfers as explicit,
   census-able collectives follows arXiv 2112.01075 — the same discipline as
   the r08 dp_grad_comm pipeline). The backward per microbatch recomputes
   the stage forward from a stashed boundary input (activation
   checkpointing at stage granularity) and accumulates parameter gradients
   across microbatches; 1F1B's whole point is the bounded stash
   (≤ num_stages in-flight microbatches vs GPipe's num_microbatches).
   The schedule is a host-side table (`build_schedule`), so the measured
   bubble census (`schedule_census`) reads the SAME
   tables the device executes.
"""

from __future__ import annotations

from typing import Callable, Dict, List, Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from jax.tree_util import tree_map

from ..core.enforce import InvalidArgumentError, enforce
from .collective import ring_perm
from .mesh import PIPELINE_AXIS, DeviceMesh


def _pipeline_body(stage_fn: Callable, axis_name: str):
    """Returns the per-device pipeline function: (stage_params, x) -> y where
    stage_params is this device's stage (leading stacked dim already split
    away by shard_map), x: [M, mb, ...] microbatched input (replicated)."""

    def body(params, x):
        params = tree_map(lambda p: p[0], params)  # drop the stage dim slice
        n = jax.lax.psum(1, axis_name)
        idx = jax.lax.axis_index(axis_name)
        m = x.shape[0]
        ticks = m + n - 1
        perm = ring_perm(n)

        state = jnp.zeros(x.shape[1:], x.dtype)       # in-flight activation
        y = jnp.zeros(x.shape, x.dtype)               # outputs (last stage)
        # the scan carry is device-varying (each stage holds different
        # activations) — mark the initial zeros as such for shard_map's
        # varying-axis type system
        state = jax.lax.pcast(state, (axis_name,), to="varying")
        y = jax.lax.pcast(y, (axis_name,), to="varying")

        def tick(carry, t):
            state, y = carry
            # stage 0 ingests microbatch t (if any); others take the ring
            feed = x[jnp.clip(t, 0, m - 1)]
            inp = jnp.where(idx == 0, feed, state)
            out = stage_fn(params, inp)
            # last stage emits microbatch t-(n-1)
            ot = jnp.clip(t - (n - 1), 0, m - 1)
            emit = (idx == n - 1) & (t >= n - 1)
            y = jnp.where(emit, y.at[ot].set(out), y)
            state = jax.lax.ppermute(out, axis_name, perm)
            return (state, y), None

        (state, y), _ = jax.lax.scan(tick, (state, y), jnp.arange(ticks))
        # only the last device holds real outputs; share them over the ring
        y = jax.lax.psum(jnp.where(idx == n - 1, y, jnp.zeros_like(y)),
                         axis_name)
        return y

    return body


def pipeline_apply(mesh: DeviceMesh, stage_fn: Callable, stacked_params, x,
                   num_microbatches: int, axis_name: str = PIPELINE_AXIS):
    """Run `stage_fn(params_i, x) -> y` as a pipeline over the pp axis.

    stacked_params: pytree whose leaves have leading dim == pp axis size.
    x: [B, ...] global batch; it is reshaped to [M, B/M, ...] microbatches.
    Returns y: [B, ...] (same trailing shape as stage output).
    """
    n = mesh.axis_size(axis_name)
    b = x.shape[0]
    enforce(num_microbatches >= 1,
            f"num_microbatches must be >= 1, got {num_microbatches}",
            exc=InvalidArgumentError)
    enforce(b % num_microbatches == 0,
            f"pipeline_apply: batch size {b} is not divisible by "
            f"num_microbatches {num_microbatches}; every microbatch must be "
            f"equal-sized (the schedule averages per-microbatch losses and "
            f"an uneven tail would be silently re-weighted). Pad the batch "
            f"or pick a divisor of {b}",
            exc=InvalidArgumentError)
    xm = x.reshape((num_microbatches, b // num_microbatches) + x.shape[1:])

    # The ring buffer requires stage output shape/dtype == input (activation
    # flows through identical stages). Fail fast with a clear message.
    import jax as _jax
    from jax.tree_util import tree_map as _tm
    probe_params = _tm(lambda p: jax.ShapeDtypeStruct(p.shape[1:], p.dtype),
                      stacked_params)
    probe_x = jax.ShapeDtypeStruct(xm.shape[1:], xm.dtype)
    out_shape = _jax.eval_shape(lambda p, h: stage_fn(p, h), probe_params,
                                probe_x)
    if (out_shape.shape, out_shape.dtype) != (probe_x.shape, probe_x.dtype):
        raise ValueError(
            f"pipeline stage must map activations to the same shape/dtype "
            f"(got {probe_x.shape}/{probe_x.dtype} -> "
            f"{out_shape.shape}/{out_shape.dtype}); wrap shape-changing "
            f"layers into the first/last stage outside the pipeline")

    param_specs = tree_map(
        lambda p: P(*([axis_name] + [None] * (p.ndim - 1))), stacked_params)
    body = _pipeline_body(stage_fn, axis_name)
    f = jax.shard_map(body, mesh=mesh.jax_mesh,
                      in_specs=(param_specs, P()), out_specs=P())
    ym = f(stacked_params, xm)
    return ym.reshape((b,) + ym.shape[2:])


# ===========================================================================
# program-level pipeline execution (pp_pipeline_region)
# ===========================================================================

PP_REGION_TYPE = "pp_pipeline_region"
PIPELINE_SCHEDULES = ("gpipe", "1f1b")

# The executor's shard_map wrapper publishes the traced pp stage index here
# (same mechanism and rationale as grad_comm._CURRENT_DP_INDEX: inside the
# full-manual region a dp/pp-sharded arange sliced to the local entry is the
# index form every jax/XLA version accepts).
_CURRENT_PP_INDEX: List = []


class pp_index_scope:
    """Context manager binding the traced pp stage index for the region."""

    def __init__(self, idx):
        self.idx = idx

    def __enter__(self):
        _CURRENT_PP_INDEX.append(self.idx)

    def __exit__(self, *a):
        _CURRENT_PP_INDEX.pop()


def current_pp_index(axis_name: str):
    if _CURRENT_PP_INDEX:
        return _CURRENT_PP_INDEX[-1]
    return jax.lax.axis_index(axis_name)


def pipeline_config(strategy) -> Optional[Dict]:
    """None when the strategy does not ask for program-level pipelining (or
    the PTPU_PIPELINE=0 kill switch is down); otherwise the resolved config.
    Resolved at prepare time so a runtime kill-switch flip recompiles (the
    flag rides the executor's compile cache key)."""
    from ..core import flags
    stages = int(getattr(strategy, "pipeline_stages", 0) or 0)
    if stages <= 1 or not flags.get_flag("pipeline"):
        return None
    sched = getattr(strategy, "pipeline_schedule", "1f1b")
    enforce(sched in PIPELINE_SCHEDULES,
            f"BuildStrategy.pipeline_schedule must be one of "
            f"{PIPELINE_SCHEDULES}, got {sched!r}",
            exc=InvalidArgumentError)
    m = int(getattr(strategy, "num_microbatches", 1) or 1)
    enforce(m >= 1,
            f"BuildStrategy.num_microbatches must be >= 1, got {m}",
            exc=InvalidArgumentError)
    return {"stages": stages, "microbatches": m, "schedule": sched}


# ---------------------------------------------------------------------------
# schedule tables: host-side slot-synchronous simulation
# ---------------------------------------------------------------------------

class PipelineSchedule:
    """Static tick tables driving the region scan. Slot model: each tick a
    stage performs ONE forward or ONE backward (or idles — a bubble);
    boundary activations/gradients shifted at END of tick arrive for the
    next tick. Tables are [ticks, num_stages] int arrays of microbatch
    indices, -1 = none."""

    def __init__(self, name, num_microbatches, num_stages, fwd_mb, bwd_mb,
                 fwd_slot, bwd_slot):
        self.name = name
        self.num_microbatches = num_microbatches
        self.num_stages = num_stages
        self.fwd_mb = fwd_mb                      # [T, K]
        self.bwd_mb = bwd_mb                      # [T, K]
        self.ticks = fwd_mb.shape[0]
        self._fwd_slot = fwd_slot                 # [K][M] completion slots
        self._bwd_slot = bwd_slot
        K, T = num_stages, self.ticks
        # arrival tables: what lands on stage k's stash at END of tick t
        self.arr_act = np.full((T, K), -1, np.int32)
        self.arr_act[:, 1:] = fwd_mb[:, :-1]
        self.arr_grad = np.full((T, K), -1, np.int32)
        self.arr_grad[:, :-1] = bwd_mb[:, 1:]
        # stash depths: peak count of microbatches live (arrived, backward
        # not yet done) — the live window is contiguous in mb index (fwd and
        # bwd both issue in order), so `mb % depth` is collision-free
        self.act_stash_depth = max(1, max(
            self._peak_live(k, arrival="act") for k in range(K)))
        self.grad_stash_depth = max(1, max(
            self._peak_live(k, arrival="grad") for k in range(K)))

    def _peak_live(self, k, arrival):
        """Peak occupancy of stage k's stash: live interval of microbatch m
        is (arrival_slot, bwd_slot] — arrival is the upstream fwd (act) or
        downstream bwd (grad); edge stages (0 for act, K-1 for grad) own
        the value locally (no stash needed), counted from local issue."""
        M, K = self.num_microbatches, self.num_stages
        if arrival == "act":
            arr = (self._fwd_slot[k - 1] if k > 0 else self._fwd_slot[k])
        else:
            if k == K - 1:
                return 0
            arr = self._bwd_slot[k + 1]
        done = self._bwd_slot[k]
        peak = 0
        for t in range(self.ticks + 1):
            live = sum(1 for m in range(M) if arr[m] < t <= done[m])
            peak = max(peak, live)
        return peak

    def stash_census(self):
        """Per-stage peak stashed-microbatch count (activation liveness):
        for stage k, the max number of microbatches whose forward input is
        held for a pending backward. This is DERIVED from the executed
        tables, not assumed — the tests read it."""
        M, K = self.num_microbatches, self.num_stages
        return [self._peak_live(k, "act") for k in range(K)]

    def bubble_census(self):
        M, K, T = self.num_microbatches, self.num_stages, self.ticks
        idle = [int(T - (self.fwd_mb[:, k] >= 0).sum()
                    - (self.bwd_mb[:, k] >= 0).sum()) for k in range(K)]
        return {
            "ticks": T,
            "work_slots_per_stage": 2 * M,
            "idle_slots_per_stage": idle,
            "bubble_fraction_per_stage": [i / T for i in idle],
            "bubble_fraction": (T - 2 * M) / T,
            "analytic_bubble_fraction": (K - 1) / (M + K - 1),
        }


def build_schedule(name: str, num_microbatches: int,
                   num_stages: int) -> PipelineSchedule:
    """Simulate the slot-synchronous schedule and emit its tick tables.

    One simulator, one knob: the per-stage in-flight limit. GPipe allows M
    microbatches in flight (all forwards first, flush at the end); 1F1B
    caps stage k at min(K - k, M) — after its warmup a stage must retire a
    backward before admitting the next forward, which is exactly the
    1-forward-1-backward steady state and the bounded activation stash.

    Recorded as a "pp_tick" span (schedule/M/K provenance): the tick
    tables are THE pipeline control artifact, so their construction cost
    and config land in the trace next to the compile they feed."""
    from ..observability import tracing as _tracing
    with _tracing.span("pp_tick", "pipeline/build_schedule",
                       schedule=str(name), microbatches=int(num_microbatches),
                       stages=int(num_stages)):
        return _build_schedule_impl(name, num_microbatches, num_stages)


def _build_schedule_impl(name: str, num_microbatches: int,
                         num_stages: int) -> PipelineSchedule:
    M, K = int(num_microbatches), int(num_stages)
    enforce(name in PIPELINE_SCHEDULES,
            f"unknown pipeline schedule {name!r}; known: "
            f"{PIPELINE_SCHEDULES}", exc=InvalidArgumentError)
    enforce(M >= 1 and K >= 1, f"need M >= 1, K >= 1 (got M={M}, K={K})",
            exc=InvalidArgumentError)
    limit = [M] * K if name == "gpipe" else [min(K - k, M) for k in range(K)]
    fwd_slot = [[None] * M for _ in range(K)]
    bwd_slot = [[None] * M for _ in range(K)]
    next_f, next_b = [0] * K, [0] * K
    rows_f, rows_b = [], []
    cap = 4 * (M + K) + 8
    t = 0
    while any(nb < M for nb in next_b):
        enforce(t < cap, f"pipeline schedule simulation did not converge "
                f"(schedule={name}, M={M}, K={K}) — scheduler bug",
                exc=InvalidArgumentError)
        row_f, row_b = [-1] * K, [-1] * K
        for k in range(K):
            nf, nb = next_f[k], next_b[k]
            f_avail = nf < M and (
                k == 0 or (fwd_slot[k - 1][nf] is not None
                           and fwd_slot[k - 1][nf] < t))
            b_avail = (nb < M and nb < nf and fwd_slot[k][nb] < t
                       and (k == K - 1 or (bwd_slot[k + 1][nb] is not None
                                           and bwd_slot[k + 1][nb] < t)))
            in_flight = nf - nb
            if b_avail and (in_flight >= limit[k] or nf >= M
                            or not f_avail):
                row_b[k] = nb
                bwd_slot[k][nb] = t
                next_b[k] += 1
            elif f_avail and in_flight < limit[k]:
                row_f[k] = nf
                fwd_slot[k][nf] = t
                next_f[k] += 1
        rows_f.append(row_f)
        rows_b.append(row_b)
        t += 1
    return PipelineSchedule(name, M, K,
                            np.asarray(rows_f, np.int32),
                            np.asarray(rows_b, np.int32),
                            fwd_slot, bwd_slot)


def schedule_census(name: str, num_microbatches: int,
                    num_stages: int) -> Dict:
    """The bubble + activation-liveness census of one schedule, from the
    same tables the region executes. `bubble_fraction` counts a stage's
    idle slots out of total ticks; for both schedules it lands exactly on
    the analytic (K-1)/(M+K-1)."""
    s = build_schedule(name, num_microbatches, num_stages)
    out = {"schedule": name, "num_microbatches": s.num_microbatches,
           "num_stages": s.num_stages}
    out.update(s.bubble_census())
    stash = s.stash_census()
    out["peak_stash_per_stage"] = stash
    out["peak_stash"] = max(stash)
    out["act_stash_depth"] = s.act_stash_depth
    out["grad_stash_depth"] = s.grad_stash_depth
    return out


# ---------------------------------------------------------------------------
# op stubs: constructed by pipeline_partition_pass, executed by the engine
# ---------------------------------------------------------------------------

from ..framework.registry import LowerCtx, register_op  # noqa: E402


@register_op("pp_send", stop_gradient=True)
def _pp_send_stub(ctx, ins, attrs):
    raise RuntimeError(
        "pp_send marks a pipeline stage boundary; it is executed by the "
        "pp_pipeline_region scheduler, never lowered directly")


@register_op("pp_recv", stop_gradient=True)
def _pp_recv_stub(ctx, ins, attrs):
    raise RuntimeError(
        "pp_recv marks a pipeline stage boundary; it is executed by the "
        "pp_pipeline_region scheduler, never lowered directly")


@register_op(PP_REGION_TYPE, stop_gradient=True)
def _pp_region_stub(ctx, ins, attrs):
    raise RuntimeError(
        "pp_pipeline_region must be executed via the block planner "
        "(framework/lowering.py REGION_RUNNERS)")


# static-analysis infer specs (framework/analysis.py): the boundary ops are
# executed by the region scheduler, never lowered, so the analyzer needs
# their shape contract stated explicitly. pp_pipeline_region itself is
# engine-interpreted (Grads mirror the diff targets), like vjp_region.

from ..framework.registry import register_infer_spec  # noqa: E402


@register_infer_spec("pp_send")
def _infer_pp_send(ictx, in_shapes, in_dtypes, attrs):
    # Out is a zero-size token tying the cut into the DAG; the real
    # transfer is the scheduler's packed f32 buffer
    import numpy as _np
    return {"Out": [((0,), _np.dtype("float32"))]}


@register_infer_spec("pp_recv")
def _infer_pp_recv(ictx, in_shapes, in_dtypes, attrs):
    # re-binds the crossing activations on the consuming stage: shapes are
    # exactly the declared shapes of the names it re-binds
    outs = []
    for name in ictx.op.outputs["Out"]:
        decl = ictx.declared(name)
        if decl is None:
            raise NotImplementedError(
                f"pp_recv output {name!r} has no declared shape")
        outs.append(decl)
    return {"Out": outs}


# dataflow effect sets (framework/dataflow.py): the boundary ops move a
# value between pp shards (one ppermute each per tick) — a transfer, not a
# reduction, so they neither resolve nor shard any axis's consistency; the
# region op runs the whole schedule's collectives over pp (plus the dp
# grad pmean when it owns the dp reduction, i.e. reduce_dp).

from ..framework.registry import register_effects  # noqa: E402


@register_effects("pp_send")
def _eff_pp_send(op):
    return {"collective_axes": (PIPELINE_AXIS,)}


@register_effects("pp_recv")
def _eff_pp_recv(op):
    return {"collective_axes": (PIPELINE_AXIS,)}


@register_effects(PP_REGION_TYPE)
def _eff_pp_region(op):
    axes = [op.attrs.get("axis") or PIPELINE_AXIS]
    if op.attrs.get("reduce_dp") and op.attrs.get("dp_axis"):
        axes.append(op.attrs["dp_axis"])
    return {"collective_axes": tuple(axes)}


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------

def _resolve_cuts(block, stage_ops):
    """[(cut names tuple)] for cuts 0..K-2, read off the spliced pp_send
    ops — the program IS the source of truth for what crosses each
    boundary."""
    from ..framework.analysis import op_loc
    cuts = []
    for k, ops in enumerate(stage_ops[:-1]):
        send = [op for op in ops if op.type == "pp_send"]
        if len(send) != 1:
            desc = (op_loc(block, block.ops.index(ops[0]), ops[0])
                    if ops else "<empty stage>")
            enforce(False,
                    f"stage {k} ({desc} ...) must end in exactly one "
                    f"pp_send, found {len(send)} — program not produced by "
                    f"pipeline_partition_pass?", exc=InvalidArgumentError)
        cuts.append(tuple(send[0].inputs["X"]))
    return cuts


def run_pp_region(region_op, seg_indices, env, block, ctx):
    """Execute a pp_pipeline_region: the microbatched 1F1B/GPipe schedule
    over the pp mesh axis, inside the executor's full-manual shard_map.

    Publishes into `env`: the loss (mean over all microbatches, LOCAL to
    the dp shard), loss@GRAD (ones), and every target's @GRAD — the
    gradient of the microbatch-mean loss, summed over pipeline stages
    (psum over pp) and, when `reduce_dp`, averaged over the dp axis.
    Forward activations are deliberately NOT published: they only ever
    exist per-microbatch on their stage's device."""
    from ..framework.lowering import grad_var_name, run_op

    attrs = region_op.attrs
    K = int(attrs["num_stages"])
    M = int(attrs["num_microbatches"])
    axis = attrs["axis"]
    dp_axis = attrs.get("dp_axis") or None
    target_names: List[str] = list(attrs["targets"])
    loss_name: str = attrs["loss"]
    batch_led = set(attrs["batch_led"])
    stage_ops = [[block.ops[i] for i in idxs] for idxs in attrs["stages"]]
    cut_names = _resolve_cuts(block, stage_ops)
    pp_idx = current_pp_index(axis)
    f32 = jnp.float32

    missing = [n for n in target_names if n not in env]
    if missing:
        from ..core.enforce import NotFoundError
        raise NotFoundError(
            f"pp_pipeline_region differentiates wrt {missing} which are "
            f"not initialized — run the startup program or feed them")
    params = tuple(env[n] for n in target_names)

    # -- classify external inputs: microbatched vs replicated-static ------
    ext_names = [n for n in attrs["x_names"] if n not in set(target_names)]
    statics, stacked = {}, {}
    b = None
    for n in ext_names:
        v = env.get(n)
        if v is None:
            continue
        if n in batch_led and hasattr(v, "ndim") and v.ndim >= 1:
            if b is None:
                b = v.shape[0]
            enforce(v.shape[0] == b,
                    f"pipeline feeds disagree on the batch dim: {n!r} has "
                    f"{v.shape[0]}, expected {b}", exc=InvalidArgumentError)
            stacked[n] = v
        else:
            statics[n] = v
    enforce(b is not None,
            "pipeline mode needs at least one batch-led feed to microbatch",
            exc=InvalidArgumentError)
    enforce(b % M == 0,
            f"pipeline mode: per-shard batch {b} is not divisible by "
            f"num_microbatches {M}; the schedule averages EQUAL-sized "
            f"microbatch losses, so feed a batch divisible by "
            f"dp * num_microbatches", exc=InvalidArgumentError)
    mb = b // M
    stacked = {n: v.reshape((M, mb) + v.shape[1:])
               for n, v in stacked.items()}

    # -- stage execution (shared by layout pass, forward, and backward) ---
    def _mb_env(mb_i):
        e = dict(statics)
        for n, v in stacked.items():
            e[n] = jax.lax.dynamic_index_in_dim(v, mb_i, axis=0,
                                                keepdims=False)
        return e

    def _stage_ctx(k, mb_i):
        # decorrelate randomness per (microbatch, stage) and make the
        # backward RECOMPUTE replay the forward's exact stream (same fold)
        return LowerCtx(rng_key=jax.random.fold_in(ctx.rng_key,
                                                   mb_i * K + k),
                        is_test=ctx.is_test, mesh=ctx.mesh,
                        extras=ctx.extras)

    def _run_stage(k, env2, bin_by_name, ctx2):
        """Run stage k's spliced op list; returns crossing out values (or
        None for the last stage). Boundary ops record "collective" spans
        carrying their cut's corr_id (trace-time provenance: the spliced
        send/recv pair shares the id, so a merged timeline pairs the
        producing and consuming stage lanes)."""
        from ..observability import tracing as _tracing
        out_vals = None
        for op in stage_ops[k]:
            if op.type == "pp_recv":
                with _tracing.span(
                        "collective", f"pp_recv/{op.attrs['cut']}",
                        stage=k, cut=op.attrs["cut"],
                        corr_id=op.attrs.get("corr_id", "")):
                    for n in op.outputs["Out"]:
                        env2[n] = bin_by_name[n]
            elif op.type == "pp_send":
                with _tracing.span(
                        "collective", f"pp_send/{op.attrs['cut']}",
                        stage=k, cut=op.attrs["cut"],
                        corr_id=op.attrs.get("corr_id", "")):
                    out_vals = [env2[n] for n in op.inputs["X"]]
            else:
                run_op(op, env2, block, ctx2)
        return out_vals

    # -- boundary layouts: abstract-interpret stages in order -------------
    layouts = []     # per cut: [(name, shape, dtype, offset, numel)]
    loss_aval = [None]
    cut_avals: Dict[str, jax.ShapeDtypeStruct] = {}
    for k in range(K):
        in_names = list(cut_names[k - 1]) if k > 0 else []
        in_avals = [cut_avals[n] for n in in_names]
        p_avals = [jax.ShapeDtypeStruct(p.shape, p.dtype) for p in params]
        mb_avals = [jax.ShapeDtypeStruct(v.shape[1:], v.dtype)
                    for v in stacked.values()]
        mb_keys = list(stacked.keys())

        def _abs(pv, bv, cv, _k=k, _in=in_names):
            env2 = dict(statics)
            env2.update(zip(mb_keys, bv))
            env2.update(zip(target_names, pv))
            ctx2 = LowerCtx(rng_key=jax.random.PRNGKey(0),
                            is_test=ctx.is_test, mesh=ctx.mesh,
                            extras=ctx.extras)
            outs = _run_stage(_k, env2, dict(zip(_in, cv)), ctx2)
            if _k == K - 1:
                return (env2[loss_name],)
            return tuple(outs)
        res = jax.eval_shape(_abs, tuple(p_avals), tuple(mb_avals),
                             tuple(in_avals))
        if k == K - 1:
            loss_aval[0] = res[0]
        else:
            off = 0
            lay = []
            for n, av in zip(cut_names[k], res):
                enforce(jnp.issubdtype(av.dtype, jnp.floating),
                        f"pipeline boundary var {n!r} has non-float dtype "
                        f"{av.dtype}; only floating activations may cross "
                        f"a stage cut", exc=InvalidArgumentError)
                numel = int(np.prod(av.shape)) if av.shape else 1
                lay.append((n, av.shape, av.dtype, off, numel))
                cut_avals[n] = av
                off += numel
            layouts.append(lay)
    S = max(1, max((lay[-1][3] + lay[-1][4] for lay in layouts),
                   default=1))

    def _pack(vals):
        # packing order == the send op's input order == the cut layout
        flat = jnp.concatenate(
            [v.astype(f32).reshape(-1) for v in vals]) if vals else \
            jnp.zeros((0,), f32)
        return jnp.pad(flat, (0, S - flat.shape[0]))

    def _unpack(buf, lay):
        return {n: buf[off:off + numel].reshape(shape).astype(dtype)
                for n, shape, dtype, off, numel in lay}

    # -- per-stage forward as a pure fn of (params, boundary-in) ----------
    def _stage_fwd(k, pvals, bin_flat, mb_i):
        env2 = _mb_env(mb_i)
        env2.update(zip(target_names, pvals))
        ctx2 = _stage_ctx(k, mb_i)
        bin_by_name = _unpack(bin_flat, layouts[k - 1]) if k > 0 else {}
        out_vals = _run_stage(k, env2, bin_by_name, ctx2)
        if k == K - 1:
            return (jnp.zeros((S,), f32),
                    jnp.asarray(env2[loss_name], f32).reshape(()))
        return _pack(out_vals), jnp.zeros((), f32)

    zero_params = tuple(jnp.zeros(p.shape, p.dtype) for p in params)
    zero_buf = jnp.zeros((S,), f32)
    zero_loss = jnp.zeros((), f32)

    def _fwd_branch(k):
        def br(pvals, bin_f, bin_b, gin, fm, bm):
            bout, loss = _stage_fwd(k, pvals, bin_f, fm)
            return bout, loss, zero_buf, zero_params
        return br

    def _bwd_branch(k):
        def br(pvals, bin_f, bin_b, gin, fm, bm):
            # recompute stage k's forward for microbatch bm from the
            # stashed boundary input, then pull the incoming boundary
            # gradient (the 1/M loss seed on the last stage) back through
            def f(pv, bf):
                return _stage_fwd(k, pv, bf, bm)
            _, vjp_fn = jax.vjp(f, pvals, bin_b)
            ct_bout = gin if k < K - 1 else zero_buf
            ct_loss = (jnp.full((), 1.0 / M, f32) if k == K - 1
                       else zero_loss)
            gp, gbin = vjp_fn((ct_bout, ct_loss))
            return zero_buf, zero_loss, gbin, gp
        return br

    def _idle_branch(pvals, bin_f, bin_b, gin, fm, bm):
        return zero_buf, zero_loss, zero_buf, zero_params

    branches = ([_fwd_branch(k) for k in range(K)]
                + [_bwd_branch(k) for k in range(K)]
                + [_idle_branch])

    # -- the tick scan ----------------------------------------------------
    sched = build_schedule(attrs["schedule"], M, K)
    T = sched.ticks
    d_a, d_g = sched.act_stash_depth, sched.grad_stash_depth
    fwd_tbl = jnp.asarray(sched.fwd_mb)
    bwd_tbl = jnp.asarray(sched.bwd_mb)
    arr_a_tbl = jnp.asarray(sched.arr_act)
    arr_g_tbl = jnp.asarray(sched.arr_grad)
    perm_fwd = [(i, i + 1) for i in range(K - 1)]
    perm_bwd = [(i, i - 1) for i in range(1, K)]

    def tick(carry, t):
        stash_a, stash_g, loss_sum, gacc = carry
        fm = fwd_tbl[t, pp_idx]
        bm = bwd_tbl[t, pp_idx]
        fi = jnp.clip(fm, 0, M - 1)
        bi = jnp.clip(bm, 0, M - 1)
        bin_f = stash_a[jnp.mod(fi, d_a)]
        bin_b = stash_a[jnp.mod(bi, d_a)]
        gin = stash_g[jnp.mod(bi, d_g)]
        idx = jnp.where(fm >= 0, pp_idx,
                        jnp.where(bm >= 0, K + pp_idx, 2 * K))
        bout, loss_c, gbin, gp = jax.lax.switch(
            idx, branches, params, bin_f, bin_b, gin, fi, bi)
        # one boundary-activation shift + one boundary-gradient shift per
        # tick (the "one send/recv pair per boundary per tick" the HLO
        # census asserts)
        act_in = jax.lax.ppermute(bout, axis, perm_fwd)
        grad_in = jax.lax.ppermute(gbin, axis, perm_bwd)
        am = arr_a_tbl[t, pp_idx]
        gm = arr_g_tbl[t, pp_idx]
        ai = jnp.mod(jnp.clip(am, 0, None), d_a)
        stash_a = stash_a.at[ai].set(
            jnp.where(am >= 0, act_in, stash_a[ai]))
        gi = jnp.mod(jnp.clip(gm, 0, None), d_g)
        stash_g = stash_g.at[gi].set(
            jnp.where(gm >= 0, grad_in, stash_g[gi]))
        return (stash_a, stash_g, loss_sum + loss_c,
                tuple(a + g for a, g in zip(gacc, gp))), None

    init = (jnp.zeros((d_a, S), f32), jnp.zeros((d_g, S), f32),
            zero_loss, zero_params)
    (s_a, s_g, loss_sum, gacc), _ = jax.lax.scan(
        tick, init, jnp.arange(T, dtype=jnp.int32))

    # only the last stage accumulated loss; each stage holds its own
    # params' grad contributions — psum over pp gives every stage the
    # totals (zeros elsewhere), keeping the replicated optimizer exact
    loss_total = jax.lax.psum(loss_sum, axis) / M
    grads = jax.lax.psum(gacc, axis)
    if attrs.get("reduce_dp") and dp_axis:
        grads = jax.lax.pmean(grads, dp_axis)
    loss_val = loss_total.astype(loss_aval[0].dtype).reshape(
        loss_aval[0].shape)
    env[loss_name] = loss_val
    env[grad_var_name(loss_name)] = jnp.ones_like(loss_val)
    for n, g in zip(target_names, grads):
        env[grad_var_name(n)] = g


def pp_boundary_wire_bytes(program, microbatch_rows: int) -> Optional[Dict]:
    """Per-device interconnect bytes per STEP of a pipeline-partitioned
    program's boundary transfers — the analytic side the HLO census is
    checked against (tests/test_pipeline_parallel.py), same ring-accounting
    discipline as grad_comm.analytic_wire_bytes. The engine moves one
    activation buffer and one gradient buffer of S f32 (the max cut size)
    through a collective-permute EVERY tick, idle or not — so per step:
    2 * ticks * S * 4 bytes. None for non-partitioned programs."""
    if not getattr(program, "_pp_applied", False):
        return None
    block = program.global_block()
    region = next((op for op in block.ops if op.type == PP_REGION_TYPE),
                  None)
    if region is None:
        return None
    cut_numels = []
    for op in block.ops:
        if op.type != "pp_send":
            continue
        total = 0
        for n in op.inputs["X"]:
            v = block.var(n)
            shape = list(v.shape or ())
            numel = 1
            for d in shape:
                numel *= (microbatch_rows if d == -1 else int(d))
            total += numel
        cut_numels.append(total)
    if not cut_numels:
        return None
    s = max(cut_numels)
    sched = build_schedule(region.attrs["schedule"],
                           region.attrs["num_microbatches"],
                           region.attrs["num_stages"])
    per_tick = 2 * s * 4                       # act shift + grad shift
    return {"buffer_numel": s,
            "cut_numels": cut_numels,
            "ticks_per_step": sched.ticks,
            "pp_boundary_bytes": per_tick * sched.ticks}


# register the region runner with the block planner
from ..framework import lowering as _lowering  # noqa: E402

_lowering.REGION_RUNNERS[PP_REGION_TYPE] = run_pp_region
