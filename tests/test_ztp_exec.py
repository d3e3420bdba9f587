"""Executor-level tensor-parallel suite: fixed-seed parity of the
tp_shard_pass + full-manual shard_map path vs the single-device baseline
on tp2 / dp2xtp2 / dp2xpp2xtp2 CPU meshes (ReduceScatter mode), the HLO
tp-collective census asserted against the analytic ring model, quantized
composition, and the PTPU_TP_SHARD kill switch.

(Named test_ztp_* so the heavyweight compiles sort after the whole suite —
the same discipline as test_zero_comm.py / test_zpipeline_exec.py; the
fast propagation/pass/gate unit half lives in tests/test_sharding_prop.py.)
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.core import flags
from paddle_tpu.core.enforce import InvalidArgumentError
from paddle_tpu.framework.costs import collective_census
from paddle_tpu.framework.sharding import tp_analytic_wire_bytes
from paddle_tpu.parallel import ParallelExecutor, annotate_tp
from paddle_tpu.parallel.mesh import DeviceMesh
from paddle_tpu.parallel.strategy import BuildStrategy, ReduceStrategy

VOCAB, T, D, HEADS, LAYERS = 64, 8, 32, 4, 2


def _build(mean_loss=True):
    from paddle_tpu.models import transformer
    loss, _ = transformer.transformer_lm(
        vocab=VOCAB, max_len=T, d_model=D, d_inner=2 * D,
        num_heads=HEADS, num_layers=LAYERS, mean_loss=mean_loss)
    pt.optimizer.Adam(learning_rate=1e-3).minimize(loss)
    return loss


def _feeds(n=3, bs=8):
    rng = np.random.RandomState(7)
    out = []
    for _ in range(n):
        out.append({
            "tokens": rng.randint(0, VOCAB, (bs, T)).astype("int64"),
            "tokens@SEQLEN": np.full((bs,), T, dtype="int32"),
            "targets": rng.randint(0, VOCAB, (bs, T)).astype("int64")})
    return out


@pytest.fixture(autouse=True)
def _f32_matmuls():
    """Parity runs compare f32-exact: splitting a bf16 contraction over tp
    changes its rounding, which is precision noise, not a sharding bug."""
    old = flags.get_flag("use_bf16_matmul")
    flags.set_flag("use_bf16_matmul", False)
    yield
    flags.set_flag("use_bf16_matmul", old)


def _baseline(feeds):
    pt.reset_default_programs()
    pt.reset_global_scope()
    with pt.core.unique_name.guard():
        loss = _build()
    exe = pt.Executor()
    exe.run(pt.default_startup_program())
    return [float(exe.run(feed=f, fetch_list=[loss])[0]) for f in feeds]


def _tp_run(feeds, axes, stages=0, micro=0, quant="", use_steps=False):
    pt.reset_default_programs()
    pt.reset_global_scope()
    with pt.core.unique_name.guard():
        loss = _build()
    annotated = annotate_tp()
    assert annotated
    exe = pt.Executor()
    exe.run(pt.default_startup_program())
    n = int(np.prod(list(axes.values())))
    kw = {}
    if stages:
        kw = dict(pipeline_stages=stages, num_microbatches=micro)
    bst = BuildStrategy(**kw)
    bst.reduce_strategy = ReduceStrategy.ReduceScatter
    bst.quant_comm = quant
    mesh = DeviceMesh(jax.devices()[:n], axes)
    pe = ParallelExecutor(loss_name=loss.name, mesh=mesh,
                          build_strategy=bst)
    if use_steps:
        out = pe.run_steps(feeds, fetch_list=[loss])
        losses = [float(v) for v in np.asarray(out[0]).ravel()]
    else:
        losses = [float(pe.run(feed=f, fetch_list=[loss])[0])
                  for f in feeds]
    return losses, pe, loss


def _compiled_hlo(exe, feed):
    scope = pt.global_scope()
    cs = list(exe._cache.values())[-1]
    feed_vals = tuple(jnp.asarray(feed[n]) for n in cs.feed_names)
    ro = tuple(scope.get(n) for n in cs.ro_names)
    rw = tuple(scope.get(n) for n in cs.rw_names)
    return cs.fn.lower(feed_vals, ro, rw, np.uint32(0)).compile().as_text()


# ---------------------------------------------------------------------------
# fixed-seed parity vs the single-device baseline (the acceptance bar)
# ---------------------------------------------------------------------------


class TestTpParity:
    @pytest.mark.quick
    def test_tp2_parity(self):
        feeds = _feeds()
        base = _baseline(feeds)
        got, exe, _ = _tp_run(feeds, {"dp": 1, "tp": 2})
        np.testing.assert_allclose(got, base, rtol=0, atol=1e-5)
        prog = exe._prepare_program(pt.default_main_program(),
                                    pt.global_scope())
        assert prog._tp_applied and prog._tp_size == 2

    def test_dp2_tp2_parity(self):
        feeds = _feeds()
        base = _baseline(feeds)
        got, _, _ = _tp_run(feeds, {"dp": 2, "tp": 2})
        np.testing.assert_allclose(got, base, rtol=0, atol=1e-5)

    def test_dp2_pp2_tp2_parity_3d_mesh(self):
        """The full 3D composition: explicit dp reduce-scatter pipeline +
        1F1B pipeline schedule + tp collectives on one dp x pp x tp mesh."""
        feeds = _feeds()
        base = _baseline(feeds)
        got, exe, _ = _tp_run(feeds, {"dp": 2, "pp": 2, "tp": 2},
                              stages=2, micro=4)
        np.testing.assert_allclose(got, base, rtol=0, atol=1e-5)
        prog = exe._prepare_program(pt.default_main_program(),
                                    pt.global_scope())
        assert prog._tp_applied and prog._dp_comm_applied \
            and prog._pp_applied

    def test_run_steps_scan_fused_tp(self):
        feeds = _feeds()
        base = _baseline(feeds)
        got, _, _ = _tp_run(feeds, {"dp": 2, "tp": 2}, use_steps=True)
        np.testing.assert_allclose(got, base, rtol=0, atol=1e-5)


# ---------------------------------------------------------------------------
# census: the compiled step's tp collectives == the analytic plan
# ---------------------------------------------------------------------------


class TestTpCensus:
    def test_allreduce_census_matches_analytic(self):
        """On a tp-only mesh (dp=1) every >=8-byte all-reduce in the
        compiled HLO is a tp collective the pass spliced (fwd psums +
        tp_ident backward psums + vocab-lookup psums): their total output
        bytes must equal the analytic model's psum'd bytes exactly."""
        feeds = _feeds(1)
        got, exe, _ = _tp_run(feeds, {"dp": 1, "tp": 2})
        prog = exe._prepare_program(pt.default_main_program(),
                                    pt.global_scope())
        w = tp_analytic_wire_bytes(prog, 2, nominal_batch=8)
        assert w is not None and w["tp_wire_bytes"] > 0
        census = collective_census(_compiled_hlo(exe, feeds[0]))
        ar_census = sum(b for b, _ in census.get("all-reduce", [])
                        if b >= 8)
        # analytic all-reduce wire = 2 n (tp-1)/tp over psum'd bytes n:
        # invert the ring factor to compare OUTPUT bytes with the census
        tp = 2
        ar_analytic = w["tp_allreduce_wire_bytes"] / (2 * (tp - 1) / tp)
        assert ar_census == int(ar_analytic), (
            ar_census, ar_analytic, {k: len(v) for k, v in census.items()})

    def test_counts_and_kinds(self):
        feeds = _feeds(1)
        _, exe, _ = _tp_run(feeds, {"dp": 1, "tp": 2})
        prog = exe._prepare_program(pt.default_main_program(),
                                    pt.global_scope())
        w = tp_analytic_wire_bytes(prog, 2, nominal_batch=8)
        counts = w["tp_op_counts"]
        # the Megatron recipe on a 2-layer decoder: one fwd psum per
        # attention out-proj + per ffn down-proj + the lm head row matmul,
        # plus the vocab-sharded embedding lookup
        assert counts["tp_allreduce"] == 2 * LAYERS + 1
        assert counts["tp_vocab_lookup"] == 1
        assert counts["tp_ident"] >= LAYERS  # deduped per variable
        # the lm head is Megatron's row entry: its (replicated,
        # post-layernorm) input is locally sliced, backward all-gathers
        assert counts["tp_split"] == 1
        ops = [op.type for op in prog.global_block().ops]
        assert ops.count("tp_vocab_lookup") == 1


# ---------------------------------------------------------------------------
# quantized-dp composition
# ---------------------------------------------------------------------------


class TestQuantComposition:
    def test_dp2_tp2_quant_bf16_runs_close(self):
        """bf16 wire quantization under tp: not bit-exact (gradients lose
        mantissa on the wire) but the 3-step trajectory stays within wire-
        precision distance of the exact run, and the error-feedback state
        is laid out per (dp x tp) coordinate."""
        feeds = _feeds()
        base = _baseline(feeds)
        bst_losses, exe, _ = _tp_run(feeds, {"dp": 2, "tp": 2},
                                     quant="bf16")
        np.testing.assert_allclose(bst_losses, base, rtol=0, atol=5e-2)
        assert all(np.isfinite(v) for v in bst_losses)

    def test_error_feedback_state_covers_dp_x_tp(self):
        pt.reset_default_programs()
        pt.reset_global_scope()
        with pt.core.unique_name.guard():
            loss = _build()
        annotate_tp()
        pt.Executor().run(pt.default_startup_program())
        bst = BuildStrategy()
        bst.reduce_strategy = ReduceStrategy.ReduceScatter
        bst.quant_comm = "int8"
        bst.comm_error_feedback = True
        mesh = DeviceMesh(jax.devices()[:4], {"dp": 2, "tp": 2})
        pe = ParallelExecutor(loss_name=loss.name, mesh=mesh,
                              build_strategy=bst)
        prog = pe._prepare_program(pt.default_main_program(),
                                   pt.global_scope())
        errs = [v for v in prog.global_block().vars.values()
                if getattr(v, "dp_replica_state", False)]
        assert errs
        for v in errs:
            assert v.shape[0] == 4  # dp * tp coordinates
            assert getattr(v, "tp_spec", None) == ("tp", None)


# ---------------------------------------------------------------------------
# kill switch
# ---------------------------------------------------------------------------


class TestKillSwitch:
    def test_tp_shard_off_restores_the_gate(self):
        pt.reset_default_programs()
        pt.reset_global_scope()
        with pt.core.unique_name.guard():
            loss = _build()
        annotate_tp()
        pt.Executor().run(pt.default_startup_program())
        bst = BuildStrategy()
        bst.reduce_strategy = ReduceStrategy.ReduceScatter
        mesh = DeviceMesh(jax.devices()[:2], {"dp": 1, "tp": 2})
        pe = ParallelExecutor(loss_name=loss.name, mesh=mesh,
                              build_strategy=bst)
        old = flags.get_flag("tp_shard")
        try:
            flags.set_flag("tp_shard", False)
            with pytest.raises(InvalidArgumentError,
                               match="PTPU_TP_SHARD"):
                pe.run(feed=_feeds(1)[0], fetch_list=[loss])
        finally:
            flags.set_flag("tp_shard", old)
