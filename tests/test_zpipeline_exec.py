"""Executor-level pipeline-parallel suite: fixed-seed parity, dp x pp
composition (incl. the r08 ReduceScatter pipeline), HLO boundary census,
and the kill switch.

(Named test_zpipeline_* so the heavyweight compiles in this file sort
after the whole suite — the same discipline as tests/test_zero_comm.py;
the fast unit half lives in tests/test_pipeline_parallel.py.)
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers
from paddle_tpu.core import flags
from paddle_tpu.framework.costs import collective_census
from paddle_tpu.parallel import ParallelExecutor
from paddle_tpu.parallel.mesh import DeviceMesh
from paddle_tpu.parallel.strategy import BuildStrategy, ReduceStrategy

from test_pipeline_parallel import (_baseline, _build_conv,  # noqa: E402
                                    _build_mlp, _compiled_hlo, _conv_feed,
                                    _mlp_feed, _pipeline_run)


# ---------------------------------------------------------------------------
# fixed-seed parity vs the single-device baseline
# ---------------------------------------------------------------------------

class TestPipelineParity:
    @pytest.mark.quick
    def test_mlp_parity_both_schedules(self):
        feeds = [_mlp_feed(i) for i in range(3)]
        base = _baseline(_build_mlp, feeds)
        for sched in ("gpipe", "1f1b"):
            got, _, _ = _pipeline_run(_build_mlp, feeds, {"pp": 2}, 2, 4,
                                      sched)
            np.testing.assert_allclose(got, base, rtol=0, atol=1e-5)

    def test_conv_parity(self):
        feeds = [_conv_feed(i) for i in range(3)]
        base = _baseline(_build_conv, feeds)
        for sched in ("gpipe", "1f1b"):
            got, _, _ = _pipeline_run(_build_conv, feeds, {"pp": 2}, 2, 4,
                                      sched)
            np.testing.assert_allclose(got, base, rtol=0, atol=1e-5)

    def test_four_stage_parity(self):
        feeds = [_mlp_feed(i) for i in range(2)]
        base = _baseline(lambda: _build_mlp(depth=6), feeds)
        got, _, _ = _pipeline_run(lambda: _build_mlp(depth=6), feeds,
                                  {"pp": 4}, 4, 8, "1f1b")
        np.testing.assert_allclose(got, base, rtol=0, atol=1e-5)


class TestDpPpComposition:
    def test_dp2_pp2_parity_allreduce_and_reduce_scatter(self):
        """dp=2 x pp=2 train step == single device, including the r08
        explicit reduce-scatter gradient pipeline under pipeline mode."""
        feeds = [_mlp_feed(i) for i in range(3)]
        base = _baseline(_build_mlp, feeds)
        for rs in (ReduceStrategy.AllReduce, ReduceStrategy.ReduceScatter):
            got, exe, _ = _pipeline_run(_build_mlp, feeds,
                                        {"dp": 2, "pp": 2}, 2, 4, "1f1b",
                                        reduce_strategy=rs)
            np.testing.assert_allclose(got, base, rtol=0, atol=1e-5)
        # ReduceScatter under pipeline keeps its structural contract: the
        # explicit dp pipeline engaged (reduce-scatter present on the wire)
        census = collective_census(_compiled_hlo(exe, feeds[-1]))
        assert "reduce-scatter" in census, census.keys()

    def test_run_steps_scan_fused_window(self):
        feeds = [_mlp_feed(i) for i in range(3)]
        base = _baseline(_build_mlp, feeds)
        pt.reset_default_programs()
        pt.reset_global_scope()
        with pt.core.unique_name.guard():
            loss = _build_mlp()
        bst = BuildStrategy(pipeline_stages=2, num_microbatches=4)
        mesh = DeviceMesh(jax.devices()[:4], {"dp": 2, "pp": 2})
        exe = ParallelExecutor(loss_name=loss.name, mesh=mesh,
                               build_strategy=bst)
        pt.Executor().run(pt.default_startup_program())
        out = exe.run_steps(feeds, fetch_list=[loss])
        np.testing.assert_allclose(np.asarray(out[0]).ravel(), base,
                                   rtol=0, atol=1e-5)


# ---------------------------------------------------------------------------
# HLO census: boundary comm structure of the compiled step
# ---------------------------------------------------------------------------

class TestHLOCensus:
    def test_one_boundary_permute_pair_per_tick(self):
        """The scan body carries exactly ONE boundary-activation shift and
        ONE boundary-gradient shift per tick — two collective-permute
        instructions in the whole compiled step, no matter how many
        microbatches run through it."""
        feeds = [_mlp_feed(0)]
        for m in (2, 8):
            got, exe, _ = _pipeline_run(_build_mlp, feeds, {"pp": 2}, 2, m,
                                        "1f1b")
            census = collective_census(_compiled_hlo(exe, feeds[0]))
            assert len(census.get("collective-permute", [])) == 2, {
                k: len(v) for k, v in census.items()}


class TestKillSwitch:
    def _exe(self, loss, stages=2, m=4):
        bst = BuildStrategy(pipeline_stages=stages, num_microbatches=m)
        mesh = DeviceMesh(jax.devices()[:stages], {"pp": stages})
        return ParallelExecutor(loss_name=loss.name, mesh=mesh,
                                build_strategy=bst)

    def test_kill_switch_runs_unpartitioned_spmd(self):
        feeds = [_mlp_feed(i) for i in range(2)]
        base = _baseline(_build_mlp, feeds)
        pt.reset_default_programs()
        pt.reset_global_scope()
        with pt.core.unique_name.guard():
            loss = _build_mlp()
        exe = self._exe(loss)
        pt.Executor().run(pt.default_startup_program())
        old = flags.get_flag("pipeline")
        try:
            flags.set_flag("pipeline", False)
            got = [float(exe.run(feed=f, fetch_list=[loss])[0])
                   for f in feeds]
            np.testing.assert_allclose(got, base, rtol=0, atol=1e-5)
            # no pipeline region compiled: the plain SPMD path ran
            prog = exe._prepare_program(pt.default_main_program(),
                                        pt.global_scope())
            assert not getattr(prog, "_pp_applied", False)
        finally:
            flags.set_flag("pipeline", old)


