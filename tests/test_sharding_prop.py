"""Unit tests for the static sharding-propagation subsystem
(framework/sharding.py): per-op propagation rules, conflict diagnostics
with op provenance, the tp_shard_pass rewrite structure, the analyzer
integration (mutation tests), and the manual-mode gate branches in
ParallelExecutor.

The executor-level half (fixed-seed parity on tp2 / dp2xtp2 / dp2xpp2xtp2
meshes, HLO census, kill switch) lives in tests/test_ztp_exec.py — same
split as test_pipeline_parallel.py vs test_zpipeline_exec.py.
"""

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers
from paddle_tpu.core import flags
from paddle_tpu.core.enforce import InvalidArgumentError
from paddle_tpu.framework import analysis, sharding
from paddle_tpu.framework.passes import get_pass
from paddle_tpu.framework.sharding import (TP_AXIS, ProgramAnalysisError,
                                           propagate_sharding,
                                           tp_analytic_wire_bytes,
                                           tp_component, tp_local_shape)
from paddle_tpu.param_attr import ParamAttr


# ---------------------------------------------------------------------------
# helpers: tiny hand-built programs
# ---------------------------------------------------------------------------


def _col_row_mlp(d_in=8, d_h=8, col=True, row=True, nclass=4):
    """The Megatron pair: column-parallel fc1 -> row-parallel fc2."""
    x = layers.data("x", shape=[d_in])
    label = layers.data("label", shape=[1], dtype="int64")
    h = layers.fc(x, size=d_h, act="relu", name="fc1",
                  param_attr=ParamAttr(
                      name="fc1.w",
                      sharding_spec=(None, TP_AXIS) if col else None),
                  bias_attr=ParamAttr(
                      name="fc1.b",
                      sharding_spec=(TP_AXIS,) if col else None))
    h = layers.fc(h, size=nclass, name="fc2",
                  param_attr=ParamAttr(
                      name="fc2.w",
                      sharding_spec=(TP_AXIS, None) if row else None))
    loss = layers.mean(layers.softmax_with_cross_entropy(h, label))
    pt.optimizer.SGDOptimizer(0.1).minimize(loss)
    return loss


def _tp_transformer(vocab=64, d_model=32, heads=4, dropout=None):
    from paddle_tpu.models import transformer
    from paddle_tpu.parallel import annotate_tp
    kw = {} if dropout is None else {"dropout": dropout}
    loss, _ = transformer.transformer_lm(
        vocab=vocab, max_len=8, d_model=d_model, d_inner=2 * d_model,
        num_heads=heads, num_layers=2, mean_loss=True, **kw)
    pt.optimizer.Adam(learning_rate=1e-3).minimize(loss)
    return loss, annotate_tp()


# ---------------------------------------------------------------------------
# spec helpers
# ---------------------------------------------------------------------------


class TestSpecHelpers:
    def test_tp_component(self):
        assert tp_component(None) is None
        assert tp_component((None, None)) is None
        assert tp_component((None, "tp")) == (None, "tp")
        # general specs naming other axes / axis tuples reduce to tp-only
        assert tp_component(("dp", "tp")) == (None, "tp")
        assert tp_component((("tp", "dp"), None)) == ("tp", None)
        assert tp_component(("dp", None)) is None

    def test_tp_local_shape(self):
        assert tp_local_shape((8, 6), (None, "tp"), 2) == (8, 3)
        assert tp_local_shape((8, 6), ("tp", None), 2) == (4, 6)
        assert tp_local_shape((8, 6), None, 2) == (8, 6)
        assert tp_local_shape((-1, 6), ("tp", "tp"), 2) == (-1, 3)


# ---------------------------------------------------------------------------
# propagation: the Megatron column -> row recipe
# ---------------------------------------------------------------------------


class TestPropagation:
    def test_column_row_pair_propagates_clean(self):
        _col_row_mlp()
        res = propagate_sharding(pt.default_main_program(), tp_size=2)
        assert not res.errors, [str(d) for d in res.errors]
        sharded = res.sharded_vars()
        assert sharded["fc1.w"] == (None, "tp")
        assert sharded["fc2.w"] == ("tp", None)
        # the activation between them is feature-sharded; the row output
        # (pre-psum) is replicated in the propagated env
        assert any(s == (None, "tp") for n, s in sharded.items()
                   if n.startswith("fc1"))
        # exactly one partial-sum output (the row-parallel matmul), one
        # ident (column input), zero splits (x arrives sharded from fc1)
        kinds = {"psums": 0, "idents": 0, "splits": 0, "gathers": 0}
        for a in res.actions:
            for k in kinds:
                kinds[k] += len(getattr(a, k))
        assert kinds["psums"] == 1
        assert kinds["idents"] >= 1
        assert kinds["splits"] == 0

    def test_row_alone_splits_input(self):
        _col_row_mlp(col=False, row=True)
        res = propagate_sharding(pt.default_main_program(), tp_size=2)
        assert not res.errors, [str(d) for d in res.errors]
        # replicated activation into a row-parallel weight: local slice
        assert sum(len(a.splits) for a in res.actions) == 1
        assert sum(len(a.psums) for a in res.actions) == 1

    def test_accumulators_inherit_param_sharding(self):
        x = layers.data("x", shape=[8])
        label = layers.data("label", shape=[1], dtype="int64")
        h = layers.fc(x, size=8, name="cfc",
                      param_attr=ParamAttr(name="cfc.w",
                                           sharding_spec=(None, TP_AXIS)),
                      bias_attr=False)
        loss = layers.mean(layers.softmax_with_cross_entropy(
            layers.fc(h, size=4), label))
        pt.optimizer.Adam(learning_rate=0.1).minimize(loss)
        res = propagate_sharding(pt.default_main_program(), tp_size=2)
        assert not res.errors, [str(d) for d in res.errors]
        acc = [n for n, s in res.sharded_vars().items()
               if "moment" in n and s == (None, "tp")]
        assert len(acc) == 2, res.sharded_vars()

    def test_divisibility_diagnostic(self):
        x = layers.data("x", shape=[8])
        layers.fc(x, size=6, name="odd",
                  param_attr=ParamAttr(name="odd.w",
                                       sharding_spec=(None, TP_AXIS)),
                  bias_attr=False)
        res = propagate_sharding(pt.default_main_program(), tp_size=4)
        assert any(d.code == "shard-divisibility" for d in res.diagnostics)
        # size-agnostic verification skips the check
        res2 = propagate_sharding(pt.default_main_program(), tp_size=None)
        assert not [d for d in res2.diagnostics
                    if d.code == "shard-divisibility"]

    def test_ruleless_op_falls_back_to_gather_with_warning(self):
        x = layers.data("x", shape=[8])
        h = layers.fc(x, size=8, name="gfc",
                      param_attr=ParamAttr(name="gfc.w",
                                           sharding_spec=(None, TP_AXIS)),
                      bias_attr=False)
        layers.topk(h, k=2)  # top_k has no sharding rule
        res = propagate_sharding(pt.default_main_program(), tp_size=2)
        assert not res.errors
        warns = [d for d in res.diagnostics if d.code == "shard-reshard"]
        assert warns and "all-gathered" in warns[0].message
        assert sum(len(a.gathers) for a in res.actions) >= 1

    def test_size1_x_broadcast_into_sharded_y_gets_ident(self):
        """A replicated size-1 X dim broadcasting into a tp-sharded Y dim
        is the mirror of the bias case: X's backward cotangent sums over
        the sharded dim, so X must be tp_ident-wrapped too."""
        x = layers.data("x", shape=[8])
        h = layers.fc(x, size=8, name="xb",
                      param_attr=ParamAttr(name="xb.w",
                                           sharding_spec=(None, TP_AXIS)),
                      bias_attr=False)
        g = layers.reduce_sum(x, dim=[1], keep_dim=True)  # [B, 1]
        layers.elementwise_mul(g, h)
        res = propagate_sharding(pt.default_main_program(), tp_size=2)
        assert not res.errors, [str(d) for d in res.errors]
        block = pt.default_main_program().global_block()
        idents = [(block.ops[a.op_idx].type, slot)
                  for a in res.actions for slot, _ in a.idents]
        assert ("elementwise_mul", "X") in idents, idents

    def test_transformer_annotation_propagates_clean(self):
        loss, ann = _tp_transformer()
        assert len(ann) >= 10
        res = propagate_sharding(pt.default_main_program(), tp_size=2)
        assert not res.errors, [str(d) for d in res.errors]
        sharded = res.sharded_vars()
        # head-sharded attention: q, k, v and the context stay [B, T, H*D]
        # through the fused op, their heads (the last dim) over tp
        block = pt.default_main_program().global_block()
        fused = [op for op in block.ops if op.type == "fused_attention"]
        assert fused
        for op in fused:
            for name in (op.inputs["Q"][0], op.outputs["Out"][0]):
                assert tuple(sharded[name]) == (None, None, "tp"), name


# ---------------------------------------------------------------------------
# conflicts: provenance-carrying diagnostics
# ---------------------------------------------------------------------------


class TestConflicts:
    def _diag_codes(self, tp=2):
        res = propagate_sharding(pt.default_main_program(), tp_size=tp)
        return res

    def test_weight_sharded_both_dims(self):
        x = layers.data("x", shape=[8])
        layers.fc(x, size=8, name="bad",
                  param_attr=ParamAttr(name="bad.w",
                                       sharding_spec=(TP_AXIS, TP_AXIS)),
                  bias_attr=False)
        res = self._diag_codes()
        errs = [d for d in res.errors if d.code == "shard-conflict"]
        assert errs and "BOTH" in errs[0].message
        # provenance: block/op#/op.type, the analyzer's op_loc format
        assert "block 0 op#" in errs[0].loc and "'mul'" in errs[0].loc

    def test_sharded_bias_on_replicated_activation(self):
        """The classic annotation bug: a tp-sharded bias added to a
        replicated activation (no column-parallel weight upstream)."""
        x = layers.data("x", shape=[8])
        layers.fc(x, size=8, name="bb",
                  param_attr=ParamAttr(name="bb.w"),
                  bias_attr=ParamAttr(name="bb.b",
                                      sharding_spec=(TP_AXIS,)))
        res = self._diag_codes()
        errs = [d for d in res.errors if d.code == "shard-conflict"]
        assert errs, [str(d) for d in res.diagnostics]
        assert "elementwise_add" in errs[0].loc

    def test_spec_arity_mismatch(self):
        x = layers.data("x", shape=[8])
        layers.fc(x, size=8, name="ar",
                  param_attr=ParamAttr(name="ar.w",
                                       sharding_spec=(TP_AXIS,)),
                  bias_attr=False)
        res = self._diag_codes()
        assert any(d.code == "shard-spec-arity" for d in res.diagnostics)

    def test_analyzer_folds_in_sharding_diagnostics(self):
        """analyze_program surfaces a propagation conflict as a
        provenance-carrying diagnostic (the acceptance-bar mutation test:
        corrupt a clean annotation, assert the specific diagnostic)."""
        loss, ann = _tp_transformer()
        prog = pt.default_main_program()
        diags = analysis.analyze_program(prog, tp_size=2)
        assert not [d for d in diags if d.severity == "error"
                    and d.code.startswith("shard")]
        # mutation: lie about the lm-head bias — shard a rank-1 bias that
        # adds to the (replicated, post-psum) logits
        prog.global_block().var("lm_head.w_1").sharding_spec = (TP_AXIS,)
        diags = analysis.analyze_program(prog, tp_size=2)
        errs = [d for d in diags if d.severity == "error"
                and d.code == "shard-conflict"]
        assert errs, "mutated annotation produced no conflict"
        assert any("block 0 op#" in d.loc for d in errs), \
            [str(d) for d in errs]

    def test_control_flow_consuming_sharded_value_conflicts(self):
        x = layers.data("x", shape=[8])
        h = layers.fc(x, size=8, name="cf",
                      param_attr=ParamAttr(name="cf.w",
                                           sharding_spec=(None, TP_AXIS)),
                      bias_attr=False)
        cond = layers.fill_constant([1], "bool", True)
        layers.cond(cond, lambda: layers.scale(h, scale=2.0),
                    lambda: h)
        res = self._diag_codes()
        assert any("control-flow" in d.message for d in res.errors)


# ---------------------------------------------------------------------------
# tp_shard_pass: rewrite structure
# ---------------------------------------------------------------------------


class TestTpShardPass:
    def test_splices_collectives_and_marks_vars(self):
        _col_row_mlp()
        prog = pt.default_main_program()
        out = get_pass("tp_shard_pass", tp=2)(prog)
        assert out is not prog and out._tp_applied and out._tp_size == 2
        ops = [op.type for op in out.global_block().ops]
        assert "tp_allreduce" in ops and "tp_ident" in ops
        # the partial-sum output was renamed and restored
        ar = next(op for op in out.global_block().ops
                  if op.type == "tp_allreduce")
        assert ar.inputs["X"][0].endswith("@TPPART")
        # sharded vars (params AND their grads) carry tp_spec
        b = out.global_block()
        assert b.var("fc1.w").tp_spec == (None, "tp")
        assert b.var("fc2.w").tp_spec == ("tp", None)
        assert b.var("fc2.w@GRAD").tp_spec == ("tp", None)
        # source program untouched
        assert not any(op.type.startswith("tp_")
                       for op in prog.global_block().ops)

    def test_idempotent_and_noop_without_annotations(self):
        _col_row_mlp(col=False, row=False)
        prog = pt.default_main_program()
        assert get_pass("tp_shard_pass", tp=2)(prog) is prog
        _ = None
        pt.reset_default_programs()
        with pt.core.unique_name.guard():
            _col_row_mlp()
        prog = pt.default_main_program()
        out = get_pass("tp_shard_pass", tp=2)(prog)
        assert get_pass("tp_shard_pass", tp=2)(out) is out

    def test_conflict_raises_with_provenance(self):
        x = layers.data("x", shape=[8])
        layers.fc(x, size=8, name="bad2",
                  param_attr=ParamAttr(name="bad2.w",
                                       sharding_spec=(TP_AXIS, TP_AXIS)),
                  bias_attr=False)
        with pytest.raises(ProgramAnalysisError) as ei:
            get_pass("tp_shard_pass", tp=2)(pt.default_main_program())
        assert "block 0 op#" in str(ei.value)

    def test_pass_sanitizer_clean_on_transformer(self):
        """PTPU_VERIFY_PASSES=1 (conftest) runs verify-before/after around
        every pass apply; a sanitizer violation would raise here. Also
        assert the rewritten program re-analyzes clean at tp-local shapes."""
        assert flags.get_flag("verify_passes")
        _tp_transformer()
        out = get_pass("tp_shard_pass", tp=2)(pt.default_main_program())
        diags = analysis.analyze_program(out, tp_size=2)
        errs = [d for d in diags if d.severity == "error"]
        assert not errs, [str(d) for d in errs]

    def test_vocab_lookup_rewritten(self):
        _tp_transformer()
        out = get_pass("tp_shard_pass", tp=2)(pt.default_main_program())
        ops = [op.type for op in out.global_block().ops]
        assert "tp_vocab_lookup" in ops
        op = next(o for o in out.global_block().ops
                  if o.type == "tp_vocab_lookup")
        assert op.attrs["parts"] == 2 and op.attrs["vocab"] == 64

    def test_reshape_attrs_localized(self):
        """Head-split reshape targets divide by tp (the [B,T,D@tp] ->
        [B,T,nh/tp,dh] case), on the path that needs the attention
        weights (dropout on them); the fused op's `num_heads` likewise."""
        _tp_transformer(d_model=32, heads=4, dropout=0.1)
        out = get_pass("tp_shard_pass", tp=2)(pt.default_main_program())
        head_splits = [op for op in out.global_block().ops
                       if op.type == "reshape"
                       and len(op.attrs.get("shape", ())) == 4]
        assert head_splits
        for op in head_splits:
            assert op.attrs["shape"][2] == 2  # 4 heads / tp2

    def test_fused_attention_heads_localized(self):
        """The fused op on [B,T,H*D@tp] sees H/tp whole heads a shard."""
        _tp_transformer(d_model=32, heads=4)
        prog = pt.default_main_program()
        out = get_pass("tp_shard_pass", tp=2)(prog)
        fused = [op for op in out.global_block().ops
                 if op.type == "fused_attention"]
        assert fused and all(op.attrs["num_heads"] == 2 for op in fused)
        assert all(op.attrs["num_heads"] == 4
                   for op in prog.global_block().ops
                   if op.type == "fused_attention")

    def test_analytic_wire_bytes(self):
        _col_row_mlp()
        prog = pt.default_main_program()
        assert tp_analytic_wire_bytes(prog, 2) is None  # not rewritten
        out = get_pass("tp_shard_pass", tp=2)(prog)
        w = tp_analytic_wire_bytes(out, 2, nominal_batch=8)
        assert w["tp_op_counts"]["tp_allreduce"] == 1
        assert w["tp_op_counts"]["tp_ident"] >= 1
        # fwd psum of the [8, 4] row output: ring all-reduce 2n(tp-1)/tp
        assert w["tp_allreduce_wire_bytes"] >= int(2 * 8 * 4 * 4 * 0.5)
        assert w["tp_wire_bytes"] == (w["tp_allreduce_wire_bytes"]
                                      + w["tp_allgather_wire_bytes"])


# ---------------------------------------------------------------------------
# the manual-mode gate: one test per branch (satellite #1)
# ---------------------------------------------------------------------------


class TestManualModeGate:
    def _exe(self, mesh_axes, **bst_kw):
        import jax
        from paddle_tpu.parallel import ParallelExecutor
        from paddle_tpu.parallel.mesh import DeviceMesh
        from paddle_tpu.parallel.strategy import BuildStrategy, \
            ReduceStrategy
        n = int(np.prod(list(mesh_axes.values())))
        bst = BuildStrategy(**bst_kw)
        bst.reduce_strategy = ReduceStrategy.ReduceScatter
        mesh = DeviceMesh(jax.devices()[:n], mesh_axes)
        return ParallelExecutor(mesh=mesh, build_strategy=bst)

    def test_sp_feed_splitting_rejected_with_or_without_tp(self):
        _col_row_mlp()
        exe = self._exe({"dp": 2, "sp": 2}, enable_sequence_parallel=True)
        with pytest.raises(InvalidArgumentError, match="WHOLE"):
            exe._prepare_program(pt.default_main_program(),
                                 pt.global_scope())

    def test_non_tp_axis_sharded_param_rejected(self):
        x = layers.data("x", shape=[8])
        label = layers.data("label", shape=[1], dtype="int64")
        h = layers.fc(x, size=8, name="dpw",
                      param_attr=ParamAttr(name="dpw.w",
                                           sharding_spec=(None, "sp")),
                      bias_attr=False)
        loss = layers.mean(layers.softmax_with_cross_entropy(
            layers.fc(h, size=4), label))
        pt.optimizer.SGDOptimizer(0.1).minimize(loss)
        exe = self._exe({"dp": 2, "sp": 2})
        with pytest.raises(InvalidArgumentError,
                           match=r"sharded over mesh\s+axes \['sp'\]"):
            exe._prepare_program(pt.default_main_program(),
                                 pt.global_scope())

    def test_kill_switch_branch_names_the_flag(self):
        _col_row_mlp()
        exe = self._exe({"dp": 2, "tp": 2})
        old = flags.get_flag("tp_shard")
        try:
            flags.set_flag("tp_shard", False)
            with pytest.raises(InvalidArgumentError,
                               match="PTPU_TP_SHARD"):
                exe._prepare_program(pt.default_main_program(),
                                     pt.global_scope())
        finally:
            flags.set_flag("tp_shard", old)

    def test_tp_sharded_param_now_passes_the_gate(self):
        """The r11 lift: the exact configuration the old blanket gate
        rejected — tp-sharded params + explicit dp pipeline — prepares
        cleanly (the tp_shard_pass rewrite runs first)."""
        _col_row_mlp()
        exe = self._exe({"dp": 2, "tp": 2})
        prog = exe._prepare_program(pt.default_main_program(),
                                    pt.global_scope())
        assert prog._tp_applied and prog._dp_comm_applied
        ops = [op.type for op in prog.global_block().ops]
        assert "tp_allreduce" in ops and "dp_grad_comm" in ops

    def test_annotation_on_tp_less_mesh_composes(self):
        """A tp annotation resolved on a mesh WITHOUT a tp axis is
        replicated and rides the manual modes untouched (no rewrite)."""
        _col_row_mlp()
        exe = self._exe({"dp": 2})
        prog = exe._prepare_program(pt.default_main_program(),
                                    pt.global_scope())
        assert not getattr(prog, "_tp_applied", False)
        assert prog._dp_comm_applied
