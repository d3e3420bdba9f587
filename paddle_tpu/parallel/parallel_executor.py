"""ParallelExecutor: SPMD data-parallel program execution.

≙ reference framework/parallel_executor.cc:119 + python/paddle/fluid/
parallel_executor.py:32. The reference replicates block-0 onto every GPU,
inserts NCCL all-reduce op handles per gradient, and schedules the SSA graph
with a thread pool. The TPU-native design compiles the SAME single-device
program once under `jax.jit` with sharding annotations:

- feed tensors are sharded along dim 0 over the mesh's data axis
  (≙ FeedAndSplitTensorIntoLocalScopes / SplitLoDTensor,
  parallel_executor.cc:333);
- parameters are replicated (≙ BCastParamsToDevices, :210);
- XLA's SPMD partitioner then emits the per-gradient all-reduce on ICI that
  the reference builds explicitly (multi_devices_graph_pass.cc:419-425);
- with `ReduceStrategy.Reduce`, optimizer accumulators are sharded across
  the data axis instead — XLA lowers the update to reduce-scatter + sharded
  optimizer math + all-gather, the ZeRO-1 formulation of the reference's
  reduce-to-one-owner-then-broadcast mode (:412-418,445-453).

Because the mean loss is computed over the *global* (sharded) batch, loss
scaling by 1/num_devices (≙ ScaleLossGradOpHandle) is implicit.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from ..core.enforce import InvalidArgumentError, enforce
from ..framework.executor import Executor
from ..framework.program import Program, Variable, default_main_program
from ..framework.scope import Scope, global_scope
from . import grad_comm as _grad_comm
from . import pipeline as _pipeline
from . import tensor_parallel as _tensor_parallel
from .mesh import (DATA_AXIS, MODEL_AXIS, PIPELINE_AXIS, SEQUENCE_AXIS,
                   DeviceMesh, get_default_mesh)
from .strategy import (BuildStrategy, ExecutionStrategy,
                       GradientScaleStrategy, ReduceStrategy)


class ParallelExecutor(Executor):
    """Drop-in multi-device executor (≙ fluid.ParallelExecutor)."""

    def __init__(self,
                 use_tpu: bool = True,
                 loss_name: Optional[str] = None,
                 main_program: Optional[Program] = None,
                 share_vars_from: Optional["ParallelExecutor"] = None,
                 exec_strategy: Optional[ExecutionStrategy] = None,
                 build_strategy: Optional[BuildStrategy] = None,
                 num_trainers: int = 1,
                 trainer_id: int = 0,
                 scope: Optional[Scope] = None,
                 mesh: Optional[DeviceMesh] = None):
        super().__init__()
        self.mesh = mesh or get_default_mesh()
        self.loss_name = loss_name
        self.main_program = main_program
        self.exec_strategy = exec_strategy or ExecutionStrategy()
        self.build_strategy = build_strategy or BuildStrategy()
        self.scope = scope or global_scope()
        if share_vars_from is not None:
            self.scope = share_vars_from.scope
        self._dp = self.mesh.axis_size(DATA_AXIS)
        self._feed_shapes: Dict[str, tuple] = {}
        self._comm_cache: Dict[Any, Program] = {}
        self._pp_cache: Dict[Any, Program] = {}
        self._tp_cache: Dict[Any, Program] = {}
        if (_grad_comm.explicit_comm_config(self.build_strategy) is not None):
            enforce(DATA_AXIS in self.mesh.axes,
                    f"the explicit gradient pipeline (ReduceScatter / "
                    f"quant_comm) needs a {DATA_AXIS!r} axis in the mesh, "
                    f"got axes {self.mesh.axis_names}",
                    exc=InvalidArgumentError)
        if (self.build_strategy.gradient_scale_strategy
                == GradientScaleStrategy.CoeffNumDevice):
            raise NotImplementedError(
                "GradientScaleStrategy.CoeffNumDevice is not implemented: "
                "under SPMD the global-batch `mean` already scales the loss "
                "gradient; build the program with a mean-reduced loss "
                "(GradientScaleStrategy.One) instead")

    # -- sharding assignment ---------------------------------------------
    def _find_var(self, program: Program, name: str) -> Optional[Variable]:
        for b in program.blocks:
            if b.has_var(name):
                return b.var(name)
        return None

    def _state_sharding(self, program: Program, name: str) -> NamedSharding:
        v = self._find_var(program, name)
        spec = getattr(v, "sharding_spec", None) if v is not None else None
        manual = (getattr(program, "_dp_comm_applied", False)
                  or getattr(program, "_pp_applied", False))
        if spec is not None and not (
                manual and v is not None
                and (getattr(v, "dp_shard_update", False)
                     or getattr(v, "dp_replica_state", False)
                     or getattr(v, "tp_spec", None))):
            # explicit TP/EP placement from ParamAttr(sharding_spec=...) or
            # parallel.auto_shard annotation; mesh.sharding drops axis names
            # not present in this mesh (replicated there). In the MANUAL
            # modes a var the rewrite passes marked is placed by its
            # markers below instead: optimizer.py copies the param's
            # sharding_spec onto same-shaped accumulators, and an
            # annotation-only placement would drop the ZeRO dim-0/dp
            # component a dp_shard_update accumulator needs (caught by
            # the r19 planner sweep: tp-annotated transformer + Adam +
            # sharded update crashed the per-shard optimizer math on a
            # tp-only moment slice).
            return self.mesh.sharding(*spec)
        if manual:
            # manual (explicit-comm and/or pipeline) modes: placement
            # follows the rewrite passes' markers — tp_shard_pass marks
            # tensor-parallel state with `tp_spec` (lives split over tp);
            # sharded-update accumulators and per-replica error-feedback
            # state live split on dim 0 over dp, composing with tp as
            # tp-major on a shared dim (dp_shard_slice slices WITHIN the
            # tp-local block). Everything else is replicated (the Reduce
            # heuristic below must NOT apply: an accumulator left on the
            # full-update path is consumed whole per shard).
            if v is None or not v.shape:
                return self.mesh.replicated()
            rank = len(v.shape)
            tp_spec = list(getattr(v, "tp_spec", None) or ())
            tp_spec += [None] * (rank - len(tp_spec))
            entries: List[Any] = [MODEL_AXIS if s == MODEL_AXIS else None
                                  for s in tp_spec[:rank]]
            if (getattr(v, "dp_shard_update", False)
                    or getattr(v, "dp_replica_state", False)):
                entries[0] = ((MODEL_AXIS, DATA_AXIS)
                              if entries[0] == MODEL_AXIS else DATA_AXIS)
            if not any(e is not None for e in entries):
                return self.mesh.replicated()
            return self.mesh.sharding(*entries)
        if (self.build_strategy.reduce_strategy == ReduceStrategy.Reduce
                and v is not None
                and getattr(v, "is_optimizer_state", False)
                and v.shape and len(v.shape) >= 1
                and v.shape[0] >= self._dp and v.shape[0] % self._dp == 0):
            # ZeRO-1: shard the accumulator's dim 0 across the data axis.
            return self.mesh.sharding(DATA_AXIS,
                                      *([None] * (len(v.shape) - 1)))
        return self.mesh.replicated()

    def _batch_led_feed(self, program: Program, name: str) -> bool:
        """A feed DECLARED batch-led ([-1, ...]) — or undeclared (sidecars
        like @SEQLEN, batch-led by construction). Shared rule with
        _pad_for_dp."""
        v = self._find_var(program, name)
        shape = getattr(v, "shape", None) if v is not None else None
        return shape is None or (bool(shape) and shape[0] == -1)

    def _feed_sharding(self, program: Program, name: str,
                       shape) -> NamedSharding:
        if not shape:  # scalar feed
            return self.mesh.replicated()
        if ((getattr(program, "_dp_comm_applied", False)
             or getattr(program, "_pp_applied", False))
                and not self._batch_led_feed(program, name)):
            # manual modes: the per-shard step consumes a fixed-shape
            # auxiliary feed WHOLE — splitting it would hand each shard a
            # fragment (the SPMD partitioner can split it safely; manual
            # per-shard code cannot)
            return self.mesh.replicated()
        if (self.build_strategy.enable_sequence_parallel and len(shape) >= 2):
            v = self._find_var(program, name)
            if v is not None and getattr(v, "lod_level", 0) > 0:
                # sequence feed [B, T, ...]: split T over the sequence axis
                # too (context parallelism; ring attention consumes this
                # layout — parallel/ring_attention.py).
                return self.mesh.sharding(DATA_AXIS, SEQUENCE_AXIS,
                                          *([None] * (len(shape) - 2)))
        return self.mesh.sharding(DATA_AXIS, *([None] * (len(shape) - 1)))

    # -- compile with shardings ------------------------------------------
    def _step_shardings(self, program, feed_names, fetch_names, ro, rw,
                        state_out_names):
        """The ONE place per-name placement policy lives: shardings for a
        single step's (feeds, ro, rw, seed) inputs and (fetches, state)
        outputs — both the single-step compile and the scan-fused
        run_steps derive from it."""
        feed_shard = tuple(self._feed_sharding(program, n,
                                               self._feed_shapes.get(n))
                           for n in feed_names)
        ro_shard = tuple(self._state_sharding(program, n) for n in ro)
        rw_shard = tuple(self._state_sharding(program, n) for n in rw)
        repl = self.mesh.replicated()
        fetch_shard = tuple(repl for _ in fetch_names)
        state_out_shard = tuple(self._state_sharding(program, n)
                                for n in state_out_names)
        return ((feed_shard, ro_shard, rw_shard, repl),
                (fetch_shard, state_out_shard))

    def _compile(self, program: Program, scope: Scope, feed_names, fetch_names,
                 in_shardings=None, out_shardings=None, analysis=None,
                 name=None):
        program = self._prepare_program(program, scope)
        analysis = analysis or self._analyze_state(program, scope, feed_names,
                                                   fetch_names)
        ro, rw, out_only = analysis
        state_out_names = sorted(set(rw) | set(out_only))
        in_sh, out_sh = self._step_shardings(program, feed_names,
                                             fetch_names, ro, rw,
                                             state_out_names)
        return super()._compile(
            program, scope, feed_names, fetch_names,
            in_shardings=in_sh, out_shardings=out_sh, analysis=analysis,
            name=name)

    # -- explicit gradient-comm pipeline (parallel/grad_comm.py) ----------
    def _gate_manual_mode(self, program: Program, what: str):
        """Gates for the full-manual execution modes (explicit dp comm,
        pipeline), naming exactly the combinations that remain
        unsupported. tp-sharded parameters are NOT gated anymore: the
        tp_shard_pass (framework/sharding.py) rewrites them into explicit
        tp collectives before this gate runs (r11). Still rejected:

          1. sequence-parallel feed splitting (enable_sequence_parallel):
             the manual step consumes whole per-shard sequences, so an
             sp-split feed — with or without TP — would hand each shard a
             sequence fragment. Use the SPMD AllReduce/Reduce strategies
             for sp programs.
          2. parameters sharded over a NON-tp mesh axis (dp/sp-sharded
             annotations): no rewrite pass owns those placements in the
             manual modes.
          3. tp-sharded parameters while the PTPU_TP_SHARD=0 kill switch
             is down (the pass that makes them executable is disabled)."""
        enforce(not self.build_strategy.enable_sequence_parallel,
                f"{what} runs the step manually over the whole mesh and "
                f"consumes each dp shard's sequences WHOLE, so "
                f"sequence-parallel feed splitting "
                f"(enable_sequence_parallel) cannot compose with it (with "
                f"or without TP). Use the SPMD AllReduce/Reduce "
                f"strategies for sp programs; tp-sharded params compose "
                f"with {what} via the tp_shard_pass path",
                exc=InvalidArgumentError)
        from ..core import flags
        from ..framework.sharding import tp_component
        for b in program.blocks:
            for v in b.vars.values():
                spec = getattr(v, "sharding_spec", None)
                # only a spec that still names a LIVE axis on this mesh is
                # truly sharded — an annotation resolving to all-None
                # (general-mesh annotation run on a dp-only mesh) is
                # replicated and composes fine
                if not v.persistable or spec is None:
                    continue
                axes = set()
                for s in self.mesh.pspec(*spec):
                    if isinstance(s, (tuple, list)):
                        axes.update(s)
                    elif s is not None:
                        axes.add(s)
                non_tp = sorted(axes - {MODEL_AXIS})
                if non_tp:
                    raise InvalidArgumentError(
                        f"parameter {v.name!r} is sharded over mesh "
                        f"axes {non_tp} — {what} runs the step manually "
                        f"and only the tp axis has a rewrite pass "
                        f"(tp_shard_pass) that splices the needed "
                        f"collectives. Shard parameters over {MODEL_AXIS!r} "
                        f"only, or use the SPMD AllReduce/Reduce "
                        f"strategies for {non_tp}-sharded placements")
                if axes and not getattr(program, "_tp_applied", False):
                    if not flags.get_flag("tp_shard"):
                        hint = ("the PTPU_TP_SHARD=0 kill switch disabled "
                                "the tp_shard_pass rewrite; flip it back "
                                "to 1")
                    elif v.name not in program.global_block().vars:
                        hint = ("the annotation sits on a SUB-BLOCK "
                                "parameter; the sharding subsystem "
                                "propagates over the global block only — "
                                "hoist the parameter to block 0 or drop "
                                "its annotation")
                    else:
                        hint = "tp_shard_pass did not run — executor bug"
                    raise InvalidArgumentError(
                        f"parameter {v.name!r} is tp-sharded "
                        f"({tp_component(spec)}) but the program was not "
                        f"rewritten for manual tp execution: {hint}. "
                        f"Without the rewrite {what} would compute "
                        f"partial tensor-parallel products without their "
                        f"collectives; the SPMD AllReduce/Reduce "
                        f"strategies also run tp-sharded programs")

    def _apply_tp_shard(self, program: Program) -> Program:
        """Apply tp_shard_pass (cached) when the manual modes will run a
        tp-annotated program on a mesh with a live tp axis: the pass
        splices the explicit tp collectives that make the per-shard step
        compute exactly the single-device math. Kill switch
        PTPU_TP_SHARD=0 skips the rewrite (the gate then rejects)."""
        from ..core import flags
        tpn = self.mesh.axis_size(MODEL_AXIS)
        if (tpn <= 1 or not flags.get_flag("tp_shard")
                or getattr(program, "_tp_applied", False)):
            return program
        from ..framework.sharding import has_tp_annotations
        if not has_tp_annotations(program):
            return program
        key = (id(program), program._version, tpn)
        rewritten = self._tp_cache.get(key)
        if rewritten is None:
            from ..framework.passes import get_pass
            rewritten = get_pass("tp_shard_pass", tp=tpn)(program)
            self._tp_cache[key] = rewritten
        return rewritten

    def _maybe_auto_plan(self, program: Program):
        """BuildStrategy.auto_parallel: run the cost-model-guided planner
        (framework/auto_parallel.py) once per (program version, device
        count, batch) and ADOPT its choice — the chosen BuildStrategy
        knobs and the chosen mesh factorization over this executor's own
        devices. Planning always starts from the USER's base strategy
        (knobs that change numerics — quant_comm, error feedback — are
        pinned to it), so repeated prepares converge instead of
        compounding. Kill switch PTPU_AUTO_PARALLEL=0 (in the compile
        cache key) reverts to the user's own strategy/mesh, so a runtime
        flip recompiles the un-planned configuration."""
        from ..core import flags
        if not getattr(self.build_strategy, "auto_parallel", False):
            return
        if getattr(self, "_auto_plan_suspended", False):
            # replan_on_restore prices the KEPT side through
            # prepare_program; planning here would adopt mid-pricing
            return
        if not flags.get_flag("auto_parallel"):
            orig = getattr(self, "_auto_orig", None)
            if orig is not None and getattr(self, "_auto_adopted", False):
                self.build_strategy, self.mesh = orig
                self._dp = self.mesh.axis_size(DATA_AXIS)
                self._auto_adopted = False
                # forget the plan: flipping the switch back on must
                # RE-plan and re-adopt, and auto_plan_report() must not
                # keep describing a strategy that is no longer executing
                self._auto_plan = None
                self._auto_plan_keys = set()
            return
        if (getattr(program, "_dp_comm_applied", False)
                or getattr(program, "_pp_applied", False)
                or getattr(program, "_memory_plan_applied", False)):
            return   # already-rewritten view: the decision was made
        batch = max((s[0] for s in (self._feed_shapes or {}).values()
                     if len(s) >= 1), default=8)
        key = (id(program), program._version, self.mesh.num_devices,
               int(batch))
        done = getattr(self, "_auto_plan_keys", None)
        if done is None:
            done = self._auto_plan_keys = set()
        # batch None = an elastic-restore decision covering ANY batch
        # (auto_parallel.replan_on_restore priced it against the
        # one-time reshard cost; re-planning here would override it
        # without that price)
        if key in done or key[:3] + (None,) in done:
            return
        from ..framework import auto_parallel as _auto
        if not hasattr(self, "_auto_orig"):
            self._auto_orig = (self.build_strategy, self.mesh)
        base = self._auto_orig[0]
        result = _auto.plan(
            program, self.mesh.num_devices, nominal_batch=int(batch),
            strategy_base=base,
            space=_auto.numerics_preserving_space(base))
        done.add(key)
        self._auto_plan = result
        self.build_strategy = result.strategy
        if dict(result.mesh_axes) != dict(self.mesh.axes):
            devices = list(self.mesh.jax_mesh.devices.flat)
            self.mesh = DeviceMesh(devices, result.mesh_axes)
        self._dp = self.mesh.axis_size(DATA_AXIS)
        self._auto_adopted = True

    def auto_plan_report(self):
        """The adopted PlanResult of the auto-parallel planner — None
        until a prepare ran with BuildStrategy.auto_parallel=True (and
        the PTPU_AUTO_PARALLEL kill switch up)."""
        return getattr(self, "_auto_plan", None)

    def _prepare_program(self, program: Program, scope: Scope) -> Program:
        """BuildStrategy-driven program rewrite, four ordered passes, each
        cached per (program, version, resolved config) and idempotent (the
        base Executor calls this again inside _compile):

        1. tp sharding (tp-annotated params on a tp mesh, manual modes
           only): framework/sharding.py tp_shard_pass splices explicit tp
           collectives so per-shard execution is exact;
        2. explicit gradient comm (ReduceScatter / quant_comm):
           grad_comm.comm_optimize_pass + zero-init of per-replica
           error-feedback state (tp-aware: plans over tp-LOCAL shapes,
           optimizer slices sharded over dp per tp shard);
        3. pipeline partitioning (pipeline_stages >= 2, PTPU_PIPELINE=1):
           passes.pipeline_partition_pass on the (possibly comm-rewritten)
           program — the pp_pipeline_region leaves gradients as LOCAL dp
           partials when dp_grad_comm owns the dp reduction, and pmeans
           them itself otherwise;
        4. static memory plan (memory_plan=True, PTPU_MEMORY_PLAN=1):
           framework/memory_plan.py memory_plan_pass over the program AS
           REWRITTEN — scheduling/coloring/remat decisions are made
           against the ops the step actually runs, and the sanitized
           apply re-verifies the colored program with the r13
           buffer-reuse detectors.

        Step 0, before any of them: the auto-parallel planner
        (BuildStrategy.auto_parallel) may first REPLACE the strategy and
        mesh this executor rewrites FOR (framework/auto_parallel.py)."""
        self._maybe_auto_plan(program)
        return self._apply_memory_plan(
            self._prepare_parallel(program, scope))

    def _apply_memory_plan(self, program: Program) -> Program:
        from ..core import flags
        if (not getattr(self.build_strategy, "memory_plan", False)
                or not flags.get_flag("memory_plan")
                or getattr(program, "_memory_plan_applied", False)):
            return program
        cache = getattr(self, "_plan_cache", None)
        if cache is None:
            cache = self._plan_cache = {}
        batch = max((s[0] for s in (self._feed_shapes or {}).values()
                     if len(s) >= 1), default=8)
        budget_s = float(getattr(self.build_strategy,
                                 "memory_plan_time_budget_s", 0.0) or 0.0)
        prevent_cse = bool(getattr(self.build_strategy,
                                   "memory_plan_prevent_cse", False))
        time_frac = float(getattr(self.build_strategy,
                                  "memory_plan_time_frac", 0.02))
        stash_host = bool(getattr(self.build_strategy,
                                  "memory_plan_stash_to_host", False))
        # every strategy field the plan reads is in the key: BuildStrategy
        # is a mutable dataclass, and a knob flipped between runs must
        # re-plan instead of silently serving the stale plan
        key = (id(program), program._version, int(batch), budget_s,
               prevent_cse, time_frac, stash_host)
        planned = cache.get(key)
        if planned is None:
            from ..framework.passes import get_pass
            planned = get_pass(
                "memory_plan_pass",
                nominal_batch=int(batch),
                time_budget_s=(budget_s or None),
                time_budget_frac=time_frac,
                remat_prevent_cse=prevent_cse,
                stash_to_host=stash_host,
            )(program)
            cache[key] = planned
        return planned

    def _prepare_parallel(self, program: Program, scope: Scope) -> Program:
        if getattr(program, "_pp_applied", False):
            return program
        cfg = _grad_comm.explicit_comm_config(self.build_strategy)
        pcfg = _pipeline.pipeline_config(self.build_strategy)
        if not getattr(program, "_dp_comm_applied", False):
            if cfg is None and pcfg is None:
                # still reconcile: a PREVIOUS explicit-mode config may have
                # left sharded state behind (kill-switch flip back to SPMD)
                self._reconcile_state_placement(program, scope, None)
                return program
            program = self._apply_tp_shard(program)
            if cfg is not None:
                self._gate_manual_mode(
                    program, "the explicit gradient pipeline "
                    "(ReduceScatter / quant_comm)")
                key = (id(program), program._version,
                       tuple(sorted(cfg.items())))
                rewritten = self._comm_cache.get(key)
                if rewritten is None:
                    rewritten = _grad_comm.comm_optimize_pass(
                        program, self._dp, cfg)
                    self._comm_cache[key] = rewritten
                for v in rewritten.global_block().vars.values():
                    if getattr(v, "dp_replica_state", False) \
                            and not scope.has_var(v.name):
                        scope.set_var(v.name, jax.device_put(
                            np.zeros(v.shape, np.float32),
                            self._state_sharding(rewritten, v.name)))
                program = rewritten
        if pcfg is not None:
            program = self._apply_pipeline(program, pcfg)
        marker = ((tuple(sorted(cfg.items())) if cfg else None),
                  (tuple(sorted(pcfg.items())) if pcfg else None),
                  (self.mesh.axis_size(MODEL_AXIS)
                   if getattr(program, "_tp_applied", False) else None))
        self._reconcile_state_placement(
            program, scope,
            marker if marker != (None, None, None) else None)
        return program

    # public views for the elastic checkpoint runtime (parallel/elastic.py)
    # and tooling: the program AS THIS EXECUTOR RUNS IT and the placement
    # its policy assigns a state var — snapshot contents (sharded ZeRO-1
    # accumulators, error-feedback state) and restore-time re-placement
    # must both follow the REWRITTEN view, not the user's program.
    def prepare_program(self, program: Optional[Program] = None,
                        scope: Optional[Scope] = None) -> Program:
        return self._prepare_program(
            program or self.main_program or default_main_program(),
            scope or self.scope)

    def state_sharding(self, program: Program, name: str) -> NamedSharding:
        return self._state_sharding(program, name)

    def _apply_pipeline(self, program: Program, pcfg: Dict) -> Program:
        """Apply pipeline_partition_pass (cached) for the resolved pipeline
        config; validates the mesh carries a pp axis of the right size."""
        enforce(PIPELINE_AXIS in self.mesh.axes
                and self.mesh.axis_size(PIPELINE_AXIS) == pcfg["stages"],
                f"BuildStrategy.pipeline_stages={pcfg['stages']} needs a "
                f"{PIPELINE_AXIS!r} mesh axis of exactly that size; this "
                f"mesh has axes {dict(self.mesh.axes)}",
                exc=InvalidArgumentError)
        self._gate_manual_mode(program, "pipeline-parallel execution")
        key = (id(program), program._version, tuple(sorted(pcfg.items())))
        rewritten = self._pp_cache.get(key)
        if rewritten is None:
            from ..framework.passes import get_pass
            has_dp = DATA_AXIS in self.mesh.axes
            rewritten = get_pass(
                "pipeline_partition_pass",
                num_stages=pcfg["stages"],
                num_microbatches=pcfg["microbatches"],
                schedule=pcfg["schedule"],
                dp_axis=DATA_AXIS if has_dp else "",
                # dp_grad_comm owns the dp reduction when the comm pass ran
                reduce_dp=(has_dp and
                           not getattr(program, "_dp_comm_applied", False)),
            )(program)
            self._pp_cache[key] = rewritten
        return rewritten

    def _reconcile_state_placement(self, program: Program, scope: Scope,
                                   cfg_key):
        """Live state placed under a DIFFERENT comm config (the
        PTPU_QUANT_COMM kill switch flipped, or the strategy's pipeline
        toggled between executors sharing a scope) may sit sharded where
        the new compile expects replicated or vice versa — jit would then
        reject the arg/sharding mismatch. On config change, re-place every
        fully-addressable persistable to the placement this program
        expects. Cross-process arrays are left alone (resharding them is a
        collective; flip the switch before process start in that world)."""
        marks = getattr(self, "_scope_cfg", None)
        if marks is None:
            marks = self._scope_cfg = {}
        if marks.get(id(scope), "<unset>") == cfg_key:
            return
        from ..observability import tracing as _tracing
        with _tracing.span("collective", "parallel/reconcile_state_placement",
                           cfg=str(cfg_key)) as sp:
            moved = 0
            for b in program.blocks:
                for v in b.vars.values():
                    if not v.persistable or not scope.has_var(v.name):
                        continue
                    val = scope.get(v.name)
                    sh = getattr(val, "sharding", None)
                    if sh is None or not getattr(val, "is_fully_addressable",
                                                 True):
                        continue
                    want = self._state_sharding(program, v.name)
                    if not sh.is_equivalent_to(want, getattr(val, "ndim", 0)):
                        scope.set_var(v.name, jax.device_put(val, want))
                        moved += 1
            sp.attrs["moved"] = moved
        marks[id(scope)] = cfg_key

    def _store_marks(self):
        # everything of THIS executor that a trace of its step reads and
        # the prepared program does not carry: the strategies (dataclasses
        # of enums and scalars: their repr is their value) and the mesh
        return super()._store_marks() + [
            repr(self.build_strategy), repr(self.exec_strategy),
            self.mesh.axes,
            [d.id for d in self.mesh.jax_mesh.devices.flat]]

    def _lowering_mesh(self, program: Program):
        # the manual modes already run the step per shard (shard_map
        # below): a lowering must not map its kernel over the mesh again
        if (getattr(program, "_dp_comm_applied", False)
                or getattr(program, "_pp_applied", False)):
            return None
        return self.mesh

    def _build_step_fn(self, program, feed_names, fetch_names, ro, rw,
                       state_out_names):
        """Manual modes: run the whole step as per-shard SPMD code —
        shard_map full-manual over the mesh — so the dp_grad_comm /
        dp_shard_* ops the comm pass spliced in (r08) and/or the
        pp_pipeline_region schedule engine (r09) can issue their own
        collectives. Feeds arrive as the local dp batch slice, replicated
        over pp; gradients leave the vjp/pipeline region as LOCAL partials
        and cross the wire only through dp_grad_comm (or the region's psum
        when no explicit comm pipeline is configured)."""
        step = super()._build_step_fn(program, feed_names, fetch_names,
                                      ro, rw, state_out_names)
        dp_mode = getattr(program, "_dp_comm_applied", False)
        pp_mode = getattr(program, "_pp_applied", False)
        if not (dp_mode or pp_mode):
            return step
        if pp_mode:
            hidden = getattr(program, "_pp_hidden", frozenset())
            for name in fetch_names:
                enforce(name not in hidden,
                        f"fetch target {name!r} is a forward activation "
                        f"(or a value derived from one — e.g. a pruned "
                        f"metric head) computed inside the pipeline "
                        f"region: its values only ever exist "
                        f"per-microbatch on their stage's device, so "
                        f"pipeline mode can fetch only the loss (and "
                        f"values computed outside the region). Drop the "
                        f"fetch or run without pipeline_stages",
                        exc=InvalidArgumentError)
        has_dp = DATA_AXIS in self.mesh.axes
        has_pp = PIPELINE_AXIS in self.mesh.axes
        has_tp = (MODEL_AXIS in self.mesh.axes
                  and getattr(program, "_tp_applied", False))
        manual_axes = {DATA_AXIS} | ({MODEL_AXIS} if has_tp else set())

        def manual_only(ns: NamedSharding) -> PartitionSpec:
            # manual specs may only name manual axes: keep the dp (and,
            # for tp-rewritten programs, tp) components; everything else
            # becomes None. The r11 full-manual mesh covers dp x pp x tp —
            # sp remains gated out of the manual modes.
            cleaned = []
            for s in ns.spec:
                names = s if isinstance(s, (tuple, list)) else (s,)
                kept = tuple(a for a in names if a in manual_axes)
                if len(kept) == 1:
                    cleaned.append(kept[0])
                elif kept:
                    cleaned.append(kept)
                else:
                    cleaned.append(None)
            return PartitionSpec(*cleaned)

        feed_specs = tuple(manual_only(self._feed_sharding(
            program, n, self._feed_shapes.get(n))) for n in feed_names)
        ro_specs = tuple(manual_only(self._state_sharding(program, n))
                         for n in ro)
        rw_specs = tuple(manual_only(self._state_sharding(program, n))
                         for n in rw)
        state_specs = tuple(manual_only(self._state_sharding(program, n))
                            for n in state_out_names)
        batch_led = self._batch_led_fetches(program, fetch_names)
        fetch_specs = tuple(PartitionSpec(DATA_AXIS) if (led and has_dp)
                            else PartitionSpec() for led in batch_led)
        # fetch contract: non-batch-led fetches come back pmean'd — exact
        # for batch-mean statistics (loss, accuracy), WRONG by 1/dp for a
        # batch sum. Reject the directly-detectable sum fetches instead of
        # silently rescaling them (docs/data_parallel.md).
        if has_dp:
            producers = {n: op.type for blk in program.blocks
                         for op in blk.ops for n in op.output_names()}
            for name, led in zip(fetch_names, batch_led):
                if led:
                    continue
                enforce(producers.get(name) not in ("reduce_sum", "sum"),
                        f"fetch {name!r} is a sum reduction: manual-mode "
                        f"execution returns non-batch-led fetches as "
                        f"the MEAN over data shards, which would silently "
                        f"divide a batch sum by {self._dp}. Fetch a "
                        f"mean-form statistic (or the per-row tensor) "
                        f"instead, or use the SPMD AllReduce/Reduce "
                        f"strategies", exc=InvalidArgumentError)

        def shard_step(dp_idx, pp_idx, tp_idx, feed_vals, ro_vals, rw_vals,
                       seed):
            # dp_idx/pp_idx/tp_idx: local slices of axis-sharded aranges —
            # the shard's indices without a PartitionId instruction
            # (lax.axis_index is rejected by the partitioner inside
            # partial-manual regions)
            idx = dp_idx[0]
            # decorrelate per-shard randomness across dp (dropout masks
            # must differ across batch shards like they do across rows in
            # SPMD mode); pp stages share the seed — the pipeline region
            # re-folds per (microbatch, stage); tp shards ALSO share the
            # seed (they jointly compute ONE logical value)
            seed = seed + idx.astype(jnp.uint32) * np.uint32(2654435761)
            with _grad_comm.dp_index_scope(idx), \
                    _pipeline.pp_index_scope(pp_idx[0]), \
                    _tensor_parallel.tp_index_scope(tp_idx[0]):
                fetches, new_state = step(feed_vals, ro_vals, rw_vals, seed)
            merged = []
            for f, led in zip(fetches, batch_led):
                if led:
                    merged.append(f)   # local rows; out_spec dp reassembles
                elif (has_dp and hasattr(f, "dtype")
                        and jnp.issubdtype(f.dtype, jnp.inexact)):
                    # scalar/statistic fetches are batch means (loss,
                    # accuracy): mean of equal-size shard means == the
                    # global-batch mean. Replicated values pass through
                    # unchanged (pmean of identical copies).
                    merged.append(jax.lax.pmean(f, DATA_AXIS))
                else:
                    merged.append(f)
            return tuple(merged), new_state

        # FULL-manual over every mesh axis. dp/pp partition the batch and
        # the stage chain; tp partitions weights when the tp_shard_pass
        # rewrote the program (its spliced tp_* collectives are the ONLY
        # cross-shard traffic on that axis) and is replicated otherwise;
        # sp stays gated out of the manual modes. Partial-manual (auto=sp)
        # would be the composable form, but this jax/XLA rejects
        # PartitionId and trips manual-subgroup checks inside
        # partial-manual regions.
        dp_spec = PartitionSpec(DATA_AXIS) if has_dp else PartitionSpec()
        pp_spec = PartitionSpec(PIPELINE_AXIS) if has_pp else PartitionSpec()
        tp_spec = (PartitionSpec(MODEL_AXIS)
                   if MODEL_AXIS in self.mesh.axes else PartitionSpec())
        mapped = jax.shard_map(
            shard_step, mesh=self.mesh.jax_mesh,
            in_specs=(dp_spec, pp_spec, tp_spec, feed_specs, ro_specs,
                      rw_specs, PartitionSpec()),
            out_specs=(fetch_specs, state_specs), check_vma=False)
        dp = self._dp
        ppn = self.mesh.axis_size(PIPELINE_AXIS)
        tpn = self.mesh.axis_size(MODEL_AXIS)

        def wrapped(feed_vals, ro_vals, rw_vals, seed):
            return mapped(jnp.arange(dp, dtype=jnp.int32),
                          jnp.arange(ppn, dtype=jnp.int32),
                          jnp.arange(tpn, dtype=jnp.int32),
                          feed_vals, ro_vals, rw_vals, seed)

        return wrapped

    def _pad_for_dp(self, program, feed):
        """Make a partial batch runnable: pad every batch-dim feed up to the
        next dp multiple by wrapping real rows (in-distribution values — no
        NaN bait), and zero the padded rows of the batch-row mask so a
        mask-weighted loss counts real rows only (≙ reference
        details/data_balance_op_handle.cc redistributing uneven reader
        batches). Returns (feed, real_rows, padded_rows) — real==padded
        means the feed was already divisible and untouched."""
        from ..framework.program import BATCH_ROW_MASK_NAME

        def _batch_led(name):
            # pad ONLY feeds DECLARED batch-led ([-1, ...]): a fixed-shape
            # auxiliary feed whose dim0 merely equals the batch size must
            # not be wrapped (mirrors _batch_led_fetches on the fetch
            # side). Undeclared feeds (sidecars like @SEQLEN) are batch-led
            # by construction.
            return self._batch_led_feed(program, name)

        sizes = {np.shape(v)[0] for n, v in feed.items()
                 if np.ndim(v) >= 1 and _batch_led(n)}
        if not sizes:
            return feed, None, None
        enforce(len(sizes) == 1,
                f"feed batch dims disagree across vars: {sorted(sizes)} "
                f"(≙ SplitLoDTensor batch split needs one batch size)",
                exc=InvalidArgumentError)
        b = sizes.pop()
        m = getattr(program, "_pp_microbatches", 0)
        if m:
            enforce(b % (self._dp * m) == 0,
                    f"feed batch size {b} is not divisible by "
                    f"dp * num_microbatches = {self._dp} * {m}: the "
                    f"pipeline schedule derives the global-mean loss from "
                    f"EQUAL microbatches on EQUAL dp shards, so "
                    f"wrap-padding would bias it. Feed divisible batches "
                    f"in pipeline mode", exc=InvalidArgumentError)
        if b % self._dp == 0:
            return feed, b, b
        enforce(_grad_comm.explicit_comm_config(self.build_strategy) is None,
                f"feed batch size {b} is not divisible by data-parallel "
                f"degree {self._dp}: the explicit gradient pipeline "
                f"(ReduceScatter / quant_comm) derives the global-mean "
                f"gradient from EQUAL per-shard batches, so wrap-padding "
                f"would bias it. Feed dp-divisible batches in this mode",
                exc=InvalidArgumentError)
        enforce(BATCH_ROW_MASK_NAME in program.global_block().vars,
                f"feed batch size {b} is not divisible by data-parallel "
                f"degree {self._dp}, and the program does not declare "
                f"layers.batch_row_mask() — padding without a mask would "
                f"silently bias an unweighted mean loss (wrapped rows "
                f"counted twice). Either make the batch dp-divisible or "
                f"declare the mask and weight the loss by it "
                f"(loss = reduce_sum(per_ex*mask)/reduce_sum(mask))",
                exc=InvalidArgumentError)
        p = ((b + self._dp - 1) // self._dp) * self._dp
        idx = np.arange(p) % b
        out = {}
        for name, val in feed.items():
            if (np.ndim(val) >= 1 and np.shape(val)[0] == b
                    and _batch_led(name)):
                out[name] = np.take(np.asarray(val), idx, axis=0)
            else:
                out[name] = val
        # a caller-fed mask was wrap-padded above — keep its real-row
        # weights and only zero the rows WE added; synthesize 1/0 otherwise
        if BATCH_ROW_MASK_NAME in out:
            mask = np.asarray(out[BATCH_ROW_MASK_NAME],
                              np.float32).copy()
        else:
            mask = np.ones((p,), np.float32)
        mask[b:] = 0.0
        out[BATCH_ROW_MASK_NAME] = mask
        return out, b, p

    def _batch_led_fetches(self, program, fetch_list):
        """Which fetch targets are declared batch-led ([-1, ...] leading
        dim)? Only those get pad rows stripped — a fetch whose CONCRETE
        leading dim merely coincides with the padded size (e.g. a [16, k]
        parameter) must come back whole."""
        out = []
        for f in fetch_list or []:
            name = f.name if isinstance(f, Variable) else f
            v = self._find_var(program, name)
            shape = getattr(v, "shape", None) if v is not None else None
            out.append(bool(shape) and shape[0] == -1)
        return out

    def _slice_padded_fetches(self, fetches, batch_led, real, stacked=False):
        """Strip pad rows from per-row fetch outputs. `stacked`: run_steps
        fetches carry a leading K (steps) axis; the batch axis is axis 1."""
        out = []
        for f, led in zip(fetches, batch_led):
            if led and hasattr(f, "ndim") and f.ndim >= (2 if stacked else 1):
                out.append(f[:, :real] if stacked else f[:real])
            else:
                out.append(f)
        return out

    # -- scan-fused multi-step loop (run_steps) ---------------------------
    def _shift_scan_axis(self, ns: NamedSharding) -> NamedSharding:
        """Per-step sharding -> stacked sharding: replicated leading K
        (steps) axis. The ONE place the scan-axis placement lives."""
        return NamedSharding(self.mesh.jax_mesh,
                             PartitionSpec(None, *ns.spec))

    def _scan_shardings(self, program, feed_names, fetch_names, ro, rw,
                        state_out_names):
        """Shardings for the run_steps executable: the single-step policy
        (_step_shardings) with the scan axis shifted onto the stacked
        feeds/fetches."""
        ((feed_sh, ro_sh, rw_sh, seed_sh),
         (fetch_sh, state_out_sh)) = self._step_shardings(
            program, feed_names, fetch_names, ro, rw, state_out_names)
        shift = self._shift_scan_axis
        return ((tuple(shift(f) for f in feed_sh), ro_sh, rw_sh, seed_sh),
                (tuple(shift(f) for f in fetch_sh), state_out_sh))

    def run_steps(self, feed_list, fetch_list=None, program=None,
                  scope=None, return_numpy=True):
        """Scan-fused K-step loop over the mesh (see Executor.run_steps);
        each step's feed batch is dp-sharded exactly as in run(). Works
        across processes too: state is globalized first and each stacked
        feed (the K global batches, identical on every process) is placed
        with its scan sharding, each process materializing only its
        addressable shards."""
        program = program or self.main_program or default_main_program()
        scope = scope or self.scope
        if feed_list and feed_list[0]:
            self._feed_shapes = {n: np.shape(v)
                                 for n, v in feed_list[0].items()}
        # rewrite for the explicit gradient pipeline BEFORE any placement
        # decision: _globalize_state/_place_feed_stack consult the
        # rewritten program's markers (sharded accumulators, error state,
        # replicated aux feeds), and the base run_steps would rewrite
        # anyway — doing it here keeps both views identical
        program = self._prepare_program(program, scope)
        enforce(len(feed_list) >= 1, "run_steps needs at least one feed",
                exc=InvalidArgumentError)
        padded_list = []
        real_b = padded_b = None
        for f in feed_list:
            f2, rb, pb = self._pad_for_dp(program, dict(f))
            padded_list.append(f2)
            real_b, padded_b = rb, pb  # uniform: signatures must match
        feed_list = padded_list
        self._feed_shapes = {n: np.shape(v)
                             for n, v in feed_list[0].items()}
        if self._spans_processes():
            self._globalize_state(program, scope)
        fetches = super().run_steps(feed_list, fetch_list=fetch_list,
                                    program=program, scope=scope,
                                    return_numpy=return_numpy)
        if real_b is not None and padded_b != real_b:
            # stacked fetches are [K, batch, ...]: strip pad rows on axis 1
            fetches = self._slice_padded_fetches(
                fetches, self._batch_led_fetches(program, fetch_list),
                real_b, stacked=True)
        return fetches

    def _place_feed_stack(self, program, name, vals):
        """Stack K per-step feed values; in a cross-process world place the
        (identical-on-every-process) host stack with its scan sharding so
        each process materializes only its addressable shards. Local runs
        keep the base (device-side) stacking — no host round trip."""
        if not self._spans_processes():
            return super()._place_feed_stack(program, name, vals)
        for v in vals:
            sh = getattr(v, "sharding", None)
            if sh is not None and not sh.is_fully_addressable:
                raise NotImplementedError(
                    f"run_steps feed {name!r} is already a global array; "
                    f"feed host values (the global batch, identical on "
                    f"every process) or use per-step run() for "
                    f"pre-placed feeds")
        stack = np.stack([np.asarray(v) for v in vals])
        return jax.device_put(
            stack,
            self._shift_scan_axis(self._feed_sharding(
                program, name, self._feed_shapes.get(name))))

    # -- multi-process state/feed placement -------------------------------
    def _spans_processes(self) -> bool:
        return jax.process_count() > 1

    def _globalize_state(self, program: Program, scope: Scope):
        """Place persistable state onto the global mesh (≙
        BCastParamsToDevices, reference parallel_executor.cc:210): after a
        plain Executor ran the startup program, state lives as
        process-local arrays; a cross-process mesh needs it as global
        arrays. Every process computed IDENTICAL host values (seeded
        startup program), so placement is a device_put of the host value
        with the state's global sharding — each process materializes only
        its addressable shards. Runs once per (program version, scope):
        afterwards every state output of the compiled step is already
        global."""
        from ..io import _is_persistable, _select_vars
        from ..observability import tracing as _tracing
        key = (id(program), program._version, id(scope))
        if key in getattr(self, "_globalized", ()):
            return
        with _tracing.span("collective", "parallel/globalize_state"):
            for v in _select_vars(program, _is_persistable):
                if not scope.has_var(v.name):
                    continue
                val = scope.get(v.name)
                sh = getattr(val, "sharding", None)
                if sh is not None and not sh.is_fully_addressable:
                    continue  # already a global array
                target = self._state_sharding(program, v.name)
                scope.set_var(v.name, jax.device_put(np.asarray(val), target))
            self._globalized = getattr(self, "_globalized", set()) | {key}

    # -- host-offload optimizer state (framework/offload.py) ---------------
    def _host_optimizer_state(self, program, scope):
        """Lazily build (and cache per program/scope identity) the
        HostOptimizerState for this step, or None when the knob is off,
        the PTPU_OFFLOAD=0 kill switch is up, or the program carries no
        optimizer accumulators yet (eval/startup programs)."""
        import os
        if not getattr(self.build_strategy, "offload_optimizer_state",
                       False):
            return None
        if os.environ.get("PTPU_OFFLOAD", "1") == "0":
            return None
        from ..framework import offload as _offload
        key = (id(program), getattr(program, "_version", 0), id(scope))
        if getattr(self, "_host_opt_key", None) == key:
            return self._host_opt
        names = _offload.optimizer_state_names(program, scope)
        if not names:
            return None
        prev = getattr(self, "_host_opt", None)
        if prev is not None:
            # program/scope changed under us: bring the old shards home
            # and return their buffers before re-keying
            prev.restore()
            prev.release()
        self._host_opt = _offload.HostOptimizerState(scope, names)
        self._host_opt_key = key
        return self._host_opt

    # -- run --------------------------------------------------------------
    def run(self,
            fetch_list: Optional[Sequence[Union[str, Variable]]] = None,
            feed: Optional[Dict[str, Any]] = None,
            program: Optional[Program] = None,
            scope: Optional[Scope] = None,
            return_numpy: bool = True):
        """≙ ParallelExecutor.run (reference parallel_executor.py:168).
        Argument order follows the reference (fetch_list first)."""
        from ..observability import tracing as _tracing
        program = program or self.main_program or default_main_program()
        scope = scope or self.scope
        with _tracing.span("collective", "parallel/prepare"):
            # provisional feed shapes BEFORE the rewrite: the memory
            # planner's nominal batch reads them (padded shapes re-stash
            # below)
            if feed:
                self._feed_shapes = {n: np.shape(v) for n, v in feed.items()}
            # see run_steps: placement below must read the REWRITTEN program
            program = self._prepare_program(program, scope)
            # ZeRO-offload: the accumulator shards live on the host between
            # steps — h2d them back BEFORE placement/dispatch, d2h them out
            # after the fetches return (the d2h overlaps whatever the host
            # does next; costs.predict's `offload` section prices whether
            # the round-trip hides behind the step)
            host_opt = self._host_optimizer_state(program, scope)
            if host_opt is not None:
                host_opt.restore()
            feed, real_b, padded_b = self._pad_for_dp(program,
                                                      dict(feed or {}))
            padded = real_b is not None and padded_b != real_b
            # synthesize the batch-row mask BEFORE multi-process placement:
            # the base Executor would otherwise inject a host numpy array
            # after the _place loop, which jit cannot auto-place onto a
            # non-addressable global sharding
            feed = self._synthesize_batch_mask(program, feed)
            # stash shapes so _compile can build feed shardings without
            # re-plumbing the Executor.run signature.
            self._feed_shapes = {n: np.shape(v) for n, v in feed.items()}
            if self._spans_processes():
                self._globalize_state(program, scope)
                # feeds carry the GLOBAL batch (identical on every process —
                # the reference's nccl2-mode trainers likewise each
                # construct their portion deterministically); device_put
                # materializes each process's addressable shards of the dp
                # split. Values that are ALREADY global jax arrays (e.g.
                # built with make_array_from_process_local_data for
                # per-process-distinct data) pass through untouched.
                def _place(n, v):
                    sh = getattr(v, "sharding", None)
                    if sh is not None and not sh.is_fully_addressable:
                        return v
                    return jax.device_put(
                        np.asarray(v),
                        self._feed_sharding(program, n, np.shape(v)))
                feed = {n: _place(n, v) for n, v in feed.items()}
        fetches = super().run(program=program, feed=feed,
                              fetch_list=fetch_list, scope=scope,
                              return_numpy=return_numpy)
        if host_opt is None and not padded:
            return fetches
        with _tracing.span("collective", "parallel/finish"):
            if host_opt is not None:
                host_opt.offload()
            if padded:
                fetches = self._slice_padded_fetches(
                    fetches, self._batch_led_fetches(program, fetch_list),
                    real_b)
        return fetches

    def cost_report(self, program: Optional[Program] = None,
                    scope: Optional[Scope] = None,
                    nominal_batch: int = 8) -> Dict:
        """framework.costs.predict() over the program AS THIS EXECUTOR
        RUNS IT (after the tp/dp-comm/pipeline rewrites), with the mesh's
        dp/tp degrees filled in — the prediction side of the r12 cost
        ledger (observability/ledger.py)."""
        from ..framework import costs as _costs
        program = program or self.main_program or default_main_program()
        scope = scope or self.scope
        rewritten = self._prepare_program(program, scope)
        return _costs.predict(rewritten, self.build_strategy,
                              dp=self._dp,
                              tp=self.mesh.axis_size(MODEL_AXIS),
                              nominal_batch=nominal_batch)

    def memory_report(self, feed, program: Optional[Program] = None,
                      scope: Optional[Scope] = None,
                      nominal_batch: int = 8) -> Dict:
        """Predicted + measured memory for the program AS RUN, in one
        dict — the memory half of the r17 sensor pair (ROADMAP items 1
        and 2 read both sides):

          predicted  cost_report()["memory"] — the static estimate plus
                     the per-device category buckets
                     (costs.memory_categories at this mesh's dp/tp)
          measured   Executor.memory_census() — actual scope arrays, the
                     XLA executable's buffer-assignment figures, the
                     live-array sweep

        Run the step once first (the census measures the executable the
        runs actually use); observability/ledger.py
        check_memory_identity reconciles the two sides with the
        accounting identity."""
        report = self.cost_report(program=program, scope=scope,
                                  nominal_batch=nominal_batch)
        census = self.memory_census(feed, program=program, scope=scope)
        return {"predicted": report["memory"], "measured": census}

    @property
    def device_count(self) -> int:
        return self.mesh.num_devices
