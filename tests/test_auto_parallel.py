"""Auto-parallel planner tests (framework/auto_parallel.py +
costs.strategy_is_feasible, ISSUE 15).

Five disciplines:
1. one unit test per NAMED rejection branch of strategy_is_feasible —
   the compile-free twin of every executor/pass gate;
2. planner properties — deterministic for a fixed seed, every emitted
   strategy is in the feasible set (representative builders in tier-1,
   the full MODEL_BUILDERS x mesh sweep slow-marked), HBM budget
   rejection, pinned-mesh planning;
3. plan-aware memory pricing (costs.predict with strategy.memory_plan)
   and the ledger identity staying green on a planned cell;
4. executor adoption — BuildStrategy.auto_parallel chooses strategy +
   mesh with fixed-seed parity vs single device, PTPU_AUTO_PARALLEL
   kill switch reverts to the user's config;
5. re-plan on elastic resize — dp2 -> dp4 restore re-plans
   deterministically, prices both restore layouts, and keeps fixed-seed
   parity vs BOTH the kept-strategy restore and the uninterrupted run.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax

import paddle_tpu as pt
from paddle_tpu import layers
from paddle_tpu.core import flags as _flags
from paddle_tpu.core.enforce import InvalidArgumentError
from paddle_tpu.framework import auto_parallel, costs
from paddle_tpu.framework.auto_parallel import (StrategyPoint,
                                                mesh_factorizations)
from paddle_tpu.parallel import ParallelExecutor, annotate_tp, elastic
from paddle_tpu.parallel.mesh import DeviceMesh
from paddle_tpu.parallel.strategy import (BuildStrategy,
                                          GradientScaleStrategy,
                                          ReduceStrategy)

import test_static_analysis as _tsa  # pytest puts tests/ on sys.path

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _mlp_program(in_dim=64):
    x = layers.data("x", shape=[in_dim])
    label = layers.data("label", shape=[1], dtype="int64")
    h = layers.fc(x, size=128, act="relu")
    loss = layers.mean(layers.softmax_with_cross_entropy(
        layers.fc(h, size=10), label))
    pt.optimizer.MomentumOptimizer(0.1, momentum=0.9).minimize(loss)
    return pt.default_main_program(), loss


def _rs(**kw):
    bst = BuildStrategy(**kw)
    bst.reduce_strategy = ReduceStrategy.ReduceScatter
    return bst


def _codes(feas):
    return feas.reason_codes()


# ---------------------------------------------------------------------------
# 1. named rejection branches
# ---------------------------------------------------------------------------


class TestFeasibilityRejections:
    def test_feasible_deep_returns_rewritten_program(self):
        prog, _ = _mlp_program()
        f = costs.strategy_is_feasible(prog, _rs(), mesh_axes={"dp": 4},
                                       nominal_batch=16)
        assert f.ok and not f.reasons
        assert getattr(f.program, "_dp_comm_applied", False)
        # the input program is untouched
        assert not getattr(prog, "_dp_comm_applied", False)

    def test_shallow_check_skips_the_rewrites(self):
        prog, _ = _mlp_program()
        f = costs.strategy_is_feasible(prog, _rs(), mesh_axes={"dp": 4},
                                       nominal_batch=16, deep=False)
        assert f.ok and f.program is None

    def test_quant_invalid(self):
        prog, _ = _mlp_program()
        bst = BuildStrategy()
        bst.quant_comm = "fp4"
        f = costs.strategy_is_feasible(prog, bst, mesh_axes={"dp": 2})
        assert _codes(f) == ["quant-invalid"]

    def test_gradient_scale_unsupported(self):
        prog, _ = _mlp_program()
        bst = BuildStrategy(
            gradient_scale_strategy=GradientScaleStrategy.CoeffNumDevice)
        f = costs.strategy_is_feasible(prog, bst, mesh_axes={"dp": 2})
        assert "gradient-scale-unsupported" in _codes(f)

    def test_mesh_mismatch_pp_axis(self):
        prog, _ = _mlp_program()
        f = costs.strategy_is_feasible(
            prog, BuildStrategy(pipeline_stages=2, num_microbatches=4),
            mesh_axes={"dp": 4}, nominal_batch=16)
        assert "mesh-mismatch" in _codes(f)
        # and the inverse: a pp axis the strategy does not ask for
        f2 = costs.strategy_is_feasible(prog, BuildStrategy(),
                                        mesh_axes={"dp": 2, "pp": 2})
        assert "mesh-mismatch" in _codes(f2)

    def test_batch_indivisible_explicit(self):
        prog, _ = _mlp_program()
        f = costs.strategy_is_feasible(prog, _rs(), mesh_axes={"dp": 4},
                                       nominal_batch=6)
        assert _codes(f) == ["batch-indivisible"]

    def test_batch_indivisible_pipeline(self):
        prog, _ = _mlp_program()
        f = costs.strategy_is_feasible(
            prog, BuildStrategy(pipeline_stages=2, num_microbatches=4),
            mesh_axes={"dp": 2, "pp": 2}, nominal_batch=12)
        assert _codes(f) == ["batch-indivisible"]

    def test_batch_norm(self):
        x = layers.data("x", shape=[8])
        h = layers.batch_norm(layers.fc(x, size=8))
        loss = layers.mean(h)
        pt.optimizer.SGD(learning_rate=0.1).minimize(loss)
        f = costs.strategy_is_feasible(pt.default_main_program(), _rs(),
                                       mesh_axes={"dp": 2})
        assert _codes(f) == ["batch-norm"]

    def test_non_mean_loss(self):
        x = layers.data("x", shape=[8])
        loss = layers.reduce_sum(layers.fc(x, size=4))
        pt.optimizer.SGD(learning_rate=0.1).minimize(loss)
        f = costs.strategy_is_feasible(pt.default_main_program(), _rs(),
                                       mesh_axes={"dp": 2})
        assert _codes(f) == ["non-mean-loss"]

    def test_sp_manual_conflict(self):
        prog, _ = _mlp_program()
        bst = _rs(enable_sequence_parallel=True)
        f = costs.strategy_is_feasible(prog, bst, mesh_axes={"dp": 2})
        assert _codes(f) == ["sp-manual-conflict"]

    def test_multi_region(self):
        x = layers.data("x", shape=[8])
        l1 = layers.mean(layers.fc(x, size=4))
        l2 = layers.mean(layers.fc(x, size=4))
        pt.optimizer.SGD(learning_rate=0.1).minimize(l1)
        pt.optimizer.SGD(learning_rate=0.1).minimize(l2)
        f = costs.strategy_is_feasible(
            pt.default_main_program(),
            BuildStrategy(pipeline_stages=2, num_microbatches=4),
            mesh_axes={"dp": 1, "pp": 2}, nominal_batch=16)
        assert "multi-region" in _codes(f)

    def test_pp_too_few_ops(self):
        x = layers.data("x", shape=[8])
        loss = layers.mean(layers.fc(x, size=4))
        pt.optimizer.SGD(learning_rate=0.1).minimize(loss)
        f = costs.strategy_is_feasible(
            pt.default_main_program(),
            BuildStrategy(pipeline_stages=4, num_microbatches=4),
            mesh_axes={"dp": 1, "pp": 4}, nominal_batch=16)
        assert "pp-too-few-ops" in _codes(f)

    def test_narrow_cut(self):
        """Twenty parallel branches all read at the end: the balanced
        partition's cut crosses more than max_boundary_vars activations
        — the DEEP check maps pipeline_partition_pass's narrow-cut
        enforce to its named reason."""
        x = layers.data("x", shape=[16])
        branches = [layers.fc(x, size=4, act="relu") for _ in range(20)]
        acc = branches[0]
        for b in branches[1:]:
            acc = layers.elementwise_add(acc, b)
        loss = layers.mean(acc)
        pt.optimizer.SGD(learning_rate=0.1).minimize(loss)
        f = costs.strategy_is_feasible(
            pt.default_main_program(),
            BuildStrategy(pipeline_stages=2, num_microbatches=4),
            mesh_axes={"dp": 1, "pp": 2}, nominal_batch=16)
        assert not f.ok
        assert set(_codes(f)) <= {"narrow-cut", "pp-gate"}
        assert "narrow-cut" in _codes(f)

    def test_tp_unannotated(self):
        prog, _ = _mlp_program()
        f = costs.strategy_is_feasible(prog, _rs(),
                                       mesh_axes={"dp": 2, "tp": 2})
        assert _codes(f) == ["tp-unannotated"]

    def test_tp_indivisible(self):
        x = layers.data("x", shape=[6])
        h = layers.fc(x, size=6, act="relu")
        loss = layers.mean(layers.fc(h, size=3))
        pt.optimizer.SGD(learning_rate=0.1).minimize(loss)
        prog = pt.default_main_program()
        # annotate a weight whose sharded dim does not divide by tp=4
        for b in prog.blocks:
            for v in b.vars.values():
                if getattr(v, "trainable", False) and v.shape == (6, 6):
                    v.sharding_spec = (None, "tp")
        f = costs.strategy_is_feasible(prog, _rs(),
                                       mesh_axes={"dp": 1, "tp": 4})
        assert "tp-indivisible" in _codes(f)

    def test_non_tp_sharded_param(self):
        prog, _ = _mlp_program()
        for b in prog.blocks:
            for v in b.vars.values():
                if getattr(v, "trainable", False) and v.shape and \
                        len(v.shape) == 2:
                    v.sharding_spec = ("dp", None)
                    break
        f = costs.strategy_is_feasible(prog, _rs(), mesh_axes={"dp": 2})
        assert "non-tp-sharded-param" in _codes(f)


# ---------------------------------------------------------------------------
# 2./3. step model + plan-aware memory pricing
# ---------------------------------------------------------------------------


def _transformer_program(tp_annotate=False):
    from paddle_tpu.models import transformer
    loss, _ = transformer.transformer_lm(
        vocab=128, max_len=32, d_model=64, d_inner=128, num_heads=4,
        num_layers=2, dropout=0.0, mean_loss=True)
    if tp_annotate:
        assert annotate_tp()
    pt.optimizer.AdamOptimizer(1e-3).minimize(loss)
    return pt.default_main_program(), loss


class TestStepModel:
    def test_breakdown_sections(self):
        prog, _ = _mlp_program()
        f = costs.strategy_is_feasible(prog, _rs(), mesh_axes={"dp": 4},
                                       nominal_batch=16)
        rep = costs.predict(f.program, _rs(), dp=4, nominal_batch=16)
        sec = costs.predicted_step_seconds(rep, mesh_axes={"dp": 4},
                                           strategy=_rs())
        assert sec["total_s"] > 0
        assert sec["compute_s"] > 0 and sec["dp_comm_s"] > 0
        assert sec["launch_s"] > 0 and sec["bubble_s"] == 0
        assert sec["total_s"] == pytest.approx(sum(
            v for k, v in sec.items()
            if k.endswith("_s") and k != "total_s"))

    def test_pipeline_bubble_priced(self):
        prog, _ = _mlp_program()
        bst = BuildStrategy(pipeline_stages=2, num_microbatches=4)
        f = costs.strategy_is_feasible(prog, bst,
                                       mesh_axes={"dp": 2, "pp": 2},
                                       nominal_batch=16)
        rep = costs.predict(f.program, bst, dp=2, nominal_batch=16)
        sec = costs.predicted_step_seconds(
            rep, mesh_axes={"dp": 2, "pp": 2}, strategy=bst)
        assert sec["bubble_s"] > 0 and sec["pp_comm_s"] > 0
        # (M+K-1)/M with M=4, K=2: bubble = compute * 0.25
        assert sec["bubble_s"] == pytest.approx(sec["compute_s"] * 0.25)

    def test_unsharded_tp_axis_not_credited(self):
        """A tp mesh axis the rewrite shards nothing over must not
        divide predicted compute (the dp1-tp4 'free lunch' loophole)."""
        prog, _ = _mlp_program()
        rep = costs.predict(prog, None, dp=1, nominal_batch=16)
        sec4 = costs.predicted_step_seconds(rep,
                                            mesh_axes={"dp": 1, "tp": 4})
        sec1 = costs.predicted_step_seconds(rep, mesh_axes={"dp": 1})
        assert sec4["compute_s"] == sec1["compute_s"]

    def test_quant_priced_against_hbm(self):
        prog, _ = _mlp_program()
        q = _rs()
        q.quant_comm = "int8"
        f = costs.strategy_is_feasible(prog, q, mesh_axes={"dp": 4},
                                       nominal_batch=16)
        rep = costs.predict(f.program, q, dp=4, nominal_batch=16)
        sec = costs.predicted_step_seconds(rep, mesh_axes={"dp": 4},
                                           strategy=q)
        assert sec["quant_s"] > 0

    def test_spmd_zero1_wire_costs_more_than_allreduce(self):
        """The Reduce mode's XLA lowering all-gathers the sharded-update
        params ON TOP of the gradient all-reduce (census-measured) — the
        planner must not price it as plain allreduce."""
        prog, _ = _mlp_program()
        bst_r = BuildStrategy(reduce_strategy=ReduceStrategy.Reduce)
        rep_r = costs.predict(prog, bst_r, dp=4, nominal_batch=16)
        rep_ar = costs.predict(prog, BuildStrategy(), dp=4,
                               nominal_batch=16)
        assert rep_r["dp_comm"]["wire_bytes"] > \
            rep_ar["dp_comm"]["wire_bytes"]
        assert rep_r["dp_comm"].get("exact") is False


class TestPlanAwareMemoryPricing:
    def test_predict_prices_the_plan_when_strategy_sets_it(self):
        prog, _ = _transformer_program()
        bst = _rs(memory_plan=True)
        f = costs.strategy_is_feasible(prog, bst, mesh_axes={"dp": 2},
                                       nominal_batch=32)
        assert f.ok and getattr(f.program, "_memory_plan_applied", False)
        rep = costs.predict(f.program, bst, dp=2, nominal_batch=32)
        per_dev = rep["memory"]["per_device"]
        assert "transient_peak_planned" in per_dev
        # this transformer's remat plan frees real stash (the run_ci
        # memory-plan stanza pins the measured reduction on the same
        # shape) — the PLANNED transient must be strictly below
        assert per_dev["transient_peak_planned"] < \
            per_dev["transient_peak"]
        assert rep["memory"]["planned_peak_total_bytes"] < \
            rep["memory"]["peak_total_bytes"]
        assert costs.predicted_device_bytes(rep, planned=True) < \
            costs.predicted_device_bytes(rep, planned=False)

    def test_unplanned_predict_has_no_planned_keys(self):
        prog, _ = _transformer_program()
        bst = _rs()
        f = costs.strategy_is_feasible(prog, bst, mesh_axes={"dp": 2},
                                       nominal_batch=32)
        rep = costs.predict(f.program, bst, dp=2, nominal_batch=32)
        assert "transient_peak_planned" not in rep["memory"]["per_device"]
        assert "planned_peak_total_bytes" not in rep["memory"]

    def test_ledger_identity_stays_green_on_planned_cell(self):
        """The planned pricing rides NEW keys only: the ledger's exact
        per-category checks and residual bound must hold unchanged on an
        executed memory-planned dp2 cell."""
        from paddle_tpu.observability.ledger import CostLedger
        rng = np.random.RandomState(0)
        _, loss = _mlp_program()
        bst = _rs(memory_plan=True)
        bst.memory_plan_time_budget_s = 1.0
        exe = ParallelExecutor(loss_name=loss.name, build_strategy=bst,
                               mesh=DeviceMesh(jax.devices()[:2],
                                               {"dp": 2}))
        pt.Executor().run(pt.default_startup_program())
        feed = {"x": rng.rand(16, 64).astype("float32"),
                "label": rng.randint(0, 10, (16, 1)).astype("int64")}
        jax.block_until_ready(exe.run(feed=feed, fetch_list=[loss],
                                      return_numpy=False))
        row = CostLedger("t").row("mnist_dp2_planned")
        row.set_prediction(exe.cost_report(nominal_batch=16))
        row.set_memory_census(exe.memory_census(feed=feed))
        row.check_memory_identity(residual_frac=0.10)
        assert row.ok, [c for c in row.checks if not c["ok"]]


# ---------------------------------------------------------------------------
# planner properties
# ---------------------------------------------------------------------------


class TestPlanner:
    def test_mesh_factorizations(self):
        f8 = mesh_factorizations(8)
        assert (8, 1, 1) == f8[0]
        assert (2, 2, 2) in f8 and (1, 8, 1) in f8 and (1, 1, 8) in f8
        assert all(dp * pp * tp == 8 for dp, pp, tp in f8)

    def test_canonicalization_dedupes_irrelevant_knobs(self):
        a = StrategyPoint(dp=4, microbatches=8, schedule="gpipe")
        assert a.canonical() == StrategyPoint(dp=4)
        b = StrategyPoint(dp=4, reduce="allreduce", quant="",
                          bucket_bytes=1 << 20)
        assert b.canonical().bucket_bytes == 4 << 20

    def test_plan_is_deterministic_for_fixed_seed(self):
        prog, _ = _mlp_program()
        r1 = auto_parallel.plan(prog, 4, nominal_batch=16, seed=3)
        r2 = auto_parallel.plan(prog, 4, nominal_batch=16, seed=3)
        assert r1.point == r2.point
        assert [row["point"] for row in r1.ranking] == \
            [row["point"] for row in r2.ranking]

    def test_chosen_strategy_is_feasible_and_adoptable(self):
        prog, _ = _mlp_program()
        r = auto_parallel.plan(prog, 4, nominal_batch=16)
        f = costs.strategy_is_feasible(prog, r.strategy,
                                       mesh_axes=r.mesh_axes,
                                       nominal_batch=16)
        assert f.ok
        assert r.n_feasible > 0 and r.predicted_step_s > 0
        assert r.rank_of(r.point) == 1

    def test_hbm_budget_rejects_everything_when_tiny(self):
        prog, _ = _mlp_program()
        with pytest.raises(InvalidArgumentError) as ei:
            auto_parallel.plan(prog, 4, nominal_batch=16, hbm_bytes=1)
        assert "hbm-budget" in str(ei.value)

    def test_pinned_mesh_dict_searches_only_the_other_knobs(self):
        prog, _ = _mlp_program()
        r = auto_parallel.plan(prog, {"dp": 2, "pp": 2},
                               nominal_batch=16)
        assert r.point.dp == 2 and r.point.pp == 2
        assert r.strategy.pipeline_stages == 2

    def test_numerics_preserving_space_pins_quant(self):
        base = _rs()
        base.quant_comm = "int8"
        sp = auto_parallel.numerics_preserving_space(base)
        assert sp.quant_modes == ("int8",)
        assert auto_parallel.numerics_preserving_space(
            BuildStrategy()).quant_modes == ("",)

    def test_pinned_quant_space_never_emits_unquantized_points(self):
        """A numerics-preserving space pinned to int8 must hold across
        the WHOLE search — grid and annealer both: an unquantized point
        would silently change the training numerics the pin exists to
        preserve (and vice versa for a pinned-'' base)."""
        prog, _ = _mlp_program()
        base = _rs()
        base.quant_comm = "int8"
        r = auto_parallel.plan(
            prog, 4, nominal_batch=16, strategy_base=base,
            space=auto_parallel.numerics_preserving_space(base))
        assert all(row["point"].quant == "int8" for row in r.ranking), \
            [row["point"].describe() for row in r.ranking[:6]]
        assert r.strategy.quant_comm == "int8"
        r2 = auto_parallel.plan(
            prog, 4, nominal_batch=16,
            space=auto_parallel.numerics_preserving_space(
                BuildStrategy()))
        assert all(row["point"].quant == "" for row in r2.ranking)

    #: representative builders for the tier-1 property: a plain mlp, a
    #: batch-norm model (manual modes gate-rejected), a recurrent net, a
    #: sparse-embedding recommender, and the tp-annotated transformer
    REPRESENTATIVE = ("mnist_mlp", "resnet_cifar10", "stacked_lstm",
                      "deepfm", "transformer_lm_tp")

    @pytest.mark.parametrize("name", REPRESENTATIVE)
    def test_planner_emits_feasible_strategies(self, name):
        loss = _tsa.MODEL_BUILDERS[name]()
        if loss is not None:
            pt.optimizer.SGD(learning_rate=0.1).minimize(loss)
        prog = pt.default_main_program()
        r = auto_parallel.plan(prog, 4, nominal_batch=16,
                               anneal_iters=8)
        f = costs.strategy_is_feasible(prog, r.strategy,
                                       mesh_axes=r.mesh_axes,
                                       nominal_batch=16)
        assert f.ok, (name, r.point, f.reasons)

    @pytest.mark.slow
    @pytest.mark.parametrize("name", sorted(_tsa.MODEL_BUILDERS))
    @pytest.mark.parametrize("n_devices", (2, 4, 8))
    def test_planner_emits_feasible_strategies_full_sweep(self, name,
                                                          n_devices):
        loss = _tsa.MODEL_BUILDERS[name]()
        if loss is not None:
            pt.optimizer.SGD(learning_rate=0.1).minimize(loss)
        prog = pt.default_main_program()
        r = auto_parallel.plan(prog, n_devices, nominal_batch=16,
                               anneal_iters=16)
        f = costs.strategy_is_feasible(prog, r.strategy,
                                       mesh_axes=r.mesh_axes,
                                       nominal_batch=16)
        assert f.ok, (name, n_devices, r.point, f.reasons)


# ---------------------------------------------------------------------------
# 4. executor adoption + kill switch
# ---------------------------------------------------------------------------


def _feeds(n, batch=16, cols=64):
    rng = np.random.RandomState(0)
    return [{"x": rng.rand(batch, cols).astype("float32"),
             "label": rng.randint(0, 10, (batch, 1)).astype("int64")}
            for _ in range(n)]


def _fresh_mlp():
    pt.reset_default_programs()
    pt.reset_global_scope()
    with pt.core.unique_name.guard():
        _, loss = _mlp_program()
    return loss


class TestExecutorAdoption:
    def test_auto_parallel_adopts_and_keeps_parity(self):
        feeds = _feeds(3)
        loss = _fresh_mlp()
        exe = pt.Executor()
        exe.run(pt.default_startup_program())
        base = [float(exe.run(feed=f, fetch_list=[loss])[0])
                for f in feeds]
        loss = _fresh_mlp()
        pexe = ParallelExecutor(
            loss_name=loss.name,
            build_strategy=BuildStrategy(auto_parallel=True),
            mesh=DeviceMesh(jax.devices()[:4], {"dp": 4}))
        pt.Executor().run(pt.default_startup_program())
        got = [float(pexe.run(feed=f, fetch_list=[loss])[0])
               for f in feeds]
        assert max(abs(a - b) for a, b in zip(base, got)) <= 1e-5
        rep = pexe.auto_plan_report()
        assert rep is not None and rep.point.describe()
        # the adopted strategy never flips lossy wire on implicitly
        assert pexe.build_strategy.quant_comm == ""
        # the adopted mesh is a factorization of the SAME devices
        assert pexe.mesh.num_devices == 4

    def test_kill_switch_reverts_to_user_config(self):
        feeds = _feeds(1)
        loss = _fresh_mlp()
        pexe = ParallelExecutor(
            loss_name=loss.name,
            build_strategy=BuildStrategy(auto_parallel=True),
            mesh=DeviceMesh(jax.devices()[:4], {"dp": 4}))
        pt.Executor().run(pt.default_startup_program())
        pexe.run(feed=feeds[0], fetch_list=[loss])
        adopted = pexe.build_strategy
        assert pexe.auto_plan_report() is not None
        old = _flags.get_flag("auto_parallel")
        try:
            _flags.set_flag("auto_parallel", False)
            pexe.run(feed=feeds[0], fetch_list=[loss])
            # reverted: the user's own strategy/mesh are live again
            assert dict(pexe.mesh.axes) == {"dp": 4}
            assert pexe.build_strategy is not adopted
            assert pexe.build_strategy.reduce_strategy == \
                ReduceStrategy.AllReduce
        finally:
            _flags.set_flag("auto_parallel", old)

    def test_kill_switch_is_in_compile_cache_key(self):
        from paddle_tpu.framework.executor import _fusion_flags_key
        old = _flags.get_flag("auto_parallel")
        try:
            _flags.set_flag("auto_parallel", True)
            on = _fusion_flags_key()
            _flags.set_flag("auto_parallel", False)
            off = _fusion_flags_key()
            assert on != off
        finally:
            _flags.set_flag("auto_parallel", old)

    def test_plain_executor_without_auto_is_untouched(self):
        loss = _fresh_mlp()
        pexe = ParallelExecutor(
            loss_name=loss.name, build_strategy=BuildStrategy(),
            mesh=DeviceMesh(jax.devices()[:4], {"dp": 4}))
        pt.Executor().run(pt.default_startup_program())
        pexe.run(feed=_feeds(1)[0], fetch_list=[loss])
        assert pexe.auto_plan_report() is None
        assert dict(pexe.mesh.axes) == {"dp": 4}


# ---------------------------------------------------------------------------
# 5. re-plan on elastic resize (ISSUE property c)
# ---------------------------------------------------------------------------


def _elastic_world(dp, auto=False):
    pt.reset_default_programs()
    pt.reset_global_scope()
    with pt.core.unique_name.guard():
        x = layers.data("x", shape=[16])
        label = layers.data("label", shape=[1], dtype="int64")
        h = layers.fc(x, size=32, act="relu")
        loss = layers.mean(layers.softmax_with_cross_entropy(
            layers.fc(h, size=4), label))
        pt.optimizer.MomentumOptimizer(0.1, momentum=0.9).minimize(loss)
    bst = BuildStrategy(auto_parallel=auto)
    bst.reduce_strategy = ReduceStrategy.ReduceScatter
    pexe = ParallelExecutor(loss_name=loss.name, build_strategy=bst,
                            mesh=DeviceMesh(jax.devices()[:dp],
                                            {"dp": dp}))
    pt.Executor().run(pt.default_startup_program())
    return loss, pexe


def _elastic_feeds(n, batch=8):
    rng = np.random.RandomState(0)
    return [{"x": rng.rand(batch, 16).astype("float32"),
             "label": rng.randint(0, 4, (batch, 1)).astype("int64")}
            for _ in range(n)]


class TestReplanOnResize:
    def _save_dp2(self, root, feeds):
        loss, pexe = _elastic_world(2)
        ref = []
        for i, f in enumerate(feeds):
            ref.append(float(pexe.run(feed=f, fetch_list=[loss])[0]))
            if i == 2:
                elastic.save_train_state(root, executor=pexe, step=3)
        return ref

    def test_replan_prices_and_keeps_parity(self, tmp_path):
        feeds = _elastic_feeds(6)
        ref = self._save_dp2(str(tmp_path), feeds)

        loss, kept4 = _elastic_world(4)
        elastic.restore_train_state(str(tmp_path), executor=kept4)
        kept = [float(kept4.run(feed=f, fetch_list=[loss])[0])
                for f in feeds[3:]]

        loss, auto4 = _elastic_world(4, auto=True)
        meta = elastic.restore_train_state(str(tmp_path), executor=auto4)
        rp = meta["replan"]
        assert set(rp) >= {"replanned", "kept", "chosen",
                           "gain_s_per_step"}
        # both restore layouts are PRICED: predicted step seconds and
        # the redistribution wire bytes of each side
        assert rp["kept"]["predicted_step_s"] > 0
        assert rp["kept"]["reshard_wire_bytes"] is not None
        assert rp["chosen"]["predicted_step_s"] > 0
        if rp["replanned"]:
            assert rp["chosen"]["reshard_wire_bytes"] is not None
            assert rp["gain_s_per_step"] > 0
        got = [float(auto4.run(feed=f, fetch_list=[loss])[0])
               for f in feeds[3:]]
        assert max(abs(a - b) for a, b in zip(kept, got)) <= 1e-5
        assert max(abs(a - b) for a, b in zip(ref[3:], got)) <= 1e-5

    def test_replan_is_deterministic(self, tmp_path):
        feeds = _elastic_feeds(4)
        self._save_dp2(str(tmp_path), feeds)
        choices = []
        for _ in range(2):
            loss, auto4 = _elastic_world(4, auto=True)
            meta = elastic.restore_train_state(str(tmp_path),
                                               executor=auto4)
            choices.append((meta["replan"]["chosen"]["point"],
                            tuple(sorted(dict(auto4.mesh.axes).items()))))
        assert choices[0] == choices[1]

    def test_replan_false_suppresses_the_resize_replan(self, tmp_path):
        """replan=False: no resize re-plan record/pricing. (The
        executor's OWN prepare-time planning still runs for an
        auto_parallel strategy — it is what the flag asks for — but the
        elastic decision record must be absent and the restore must
        still land at parity.)"""
        feeds = _elastic_feeds(6)
        ref = self._save_dp2(str(tmp_path), feeds)
        loss, auto4 = _elastic_world(4, auto=True)
        meta = elastic.restore_train_state(str(tmp_path), executor=auto4,
                                           replan=False)
        assert "replan" not in meta
        got = [float(auto4.run(feed=f, fetch_list=[loss])[0])
               for f in feeds[3:]]
        assert max(abs(a - b) for a, b in zip(ref[3:], got)) <= 1e-5

    def test_restore_decision_pins_later_prepares(self, tmp_path):
        """The restore-time decision was priced against the one-time
        reshard cost at a batch the restore could not know; a later
        prepare with the REAL feed batch must honor it instead of
        re-planning batch-keyed and silently overriding it."""
        feeds = _elastic_feeds(4)
        self._save_dp2(str(tmp_path), feeds)
        loss, auto4 = _elastic_world(4, auto=True)
        elastic.restore_train_state(str(tmp_path), executor=auto4)
        decided = auto4.build_strategy
        decided_axes = dict(auto4.mesh.axes)
        # a different batch size than the restore's nominal default
        rng = np.random.RandomState(1)
        big = {"x": rng.rand(16, 16).astype("float32"),
               "label": rng.randint(0, 4, (16, 1)).astype("int64")}
        auto4.run(feed=big, fetch_list=[loss])
        assert auto4.build_strategy is decided
        assert dict(auto4.mesh.axes) == decided_axes

    def test_same_world_restore_never_replans(self, tmp_path):
        feeds = _elastic_feeds(4)
        self._save_dp2(str(tmp_path), feeds)
        loss, auto2 = _elastic_world(2, auto=True)
        meta = elastic.restore_train_state(str(tmp_path), executor=auto2)
        assert "replan" not in meta


# ---------------------------------------------------------------------------
# lint_program --strategy CLI (the named-reasons surface)
# ---------------------------------------------------------------------------


@pytest.mark.slow
class TestLintStrategyCLI:
    def _lint(self, strategy_json):
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        return subprocess.run(
            [sys.executable, os.path.join(REPO, "tools",
                                          "lint_program.py"),
             "--model", "mnist", "--json", "--strategy", strategy_json],
            capture_output=True, text=True, env=env)

    def test_feasible_strategy_lints_clean(self):
        p = self._lint('{"dp": 2, "reduce": "reduce_scatter"}')
        assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
        rep = json.loads(p.stdout)[0]
        assert rep["strategy_feasible"]["ok"]
        assert rep["errors"] == 0

    def test_infeasible_strategy_exits_2_with_named_reason(self):
        p = self._lint('{"dp": 2, "tp": 2, "reduce": "reduce_scatter"}')
        assert p.returncode == 2, p.stdout[-2000:] + p.stderr[-2000:]
        rep = json.loads(p.stdout)[0]
        codes = [r["code"] for r in rep["strategy_feasible"]["reasons"]]
        assert codes == ["tp-unannotated"]
        assert rep["gate_rejected"]
