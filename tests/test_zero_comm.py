"""Executor-level tests for the explicit (ZeRO-style) gradient-comm
pipeline: ReduceStrategy.ReduceScatter + BuildStrategy.quant_comm.

Census assertions follow tests/test_comm_structure.py's discipline — byte
counts parsed from the partitioned optimized HLO, balanced against the
analytic formula EXACTLY — plus loss parity against the SPMD baseline,
error-feedback statefulness across steps and through the run_steps carry,
the PTPU_QUANT_COMM kill switch, and the 3-axis-mesh regression confirming
quantization only engages on the dp axis.

(Named test_zero_* so the heavyweight compiles in this file sort after the
whole suite; the fast unit half lives in tests/test_grad_comm.py.)
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers
from paddle_tpu.core import flags
from paddle_tpu.core.enforce import InvalidArgumentError
from paddle_tpu.framework.costs import (census_wire_bytes, collective_census,
                                        collective_wire_bytes)
from paddle_tpu.parallel import ParallelExecutor
from paddle_tpu.parallel.mesh import DeviceMesh
from paddle_tpu.parallel.strategy import BuildStrategy, ReduceStrategy

DP = 8
# fc(64->128) + fc(128->10): w1/b1/w2 ride the sharded path (dim0 % 8 == 0),
# b2 [10] rides the bucket (padded to 16 f32 = 64 bytes)
GRAD_BYTES = (64 * 128 + 128 + 128 * 10 + 10) * 4
SHARDED_BYTES = (64 * 128 + 128 + 128 * 10) * 4
BUCKET_PAD_BYTES = 16 * 4


def _build_mlp(optimizer="momentum"):
    x = layers.data("x", shape=[64])
    label = layers.data("label", shape=[1], dtype="int64")
    h = layers.fc(x, size=128, act="relu")
    logits = layers.fc(h, size=10)
    loss = layers.mean(layers.softmax_with_cross_entropy(logits, label))
    if optimizer == "momentum":
        pt.optimizer.MomentumOptimizer(0.1, momentum=0.9).minimize(loss)
    else:
        pt.optimizer.AdamOptimizer(0.01).minimize(loss)
    return loss


def _feed(rng, bs=32):
    return {"x": rng.rand(bs, 64).astype("float32"),
            "label": rng.randint(0, 10, (bs, 1)).astype("int64")}


def _exe(loss, mode, quant="", ef=False, axes=None, bucket=None):
    bst = BuildStrategy()
    bst.reduce_strategy = mode
    bst.quant_comm = quant
    bst.comm_error_feedback = ef
    if bucket is not None:
        bst.comm_bucket_bytes = bucket
    mesh = DeviceMesh(jax.devices(), axes or {"dp": DP})
    return ParallelExecutor(loss_name=loss.name, mesh=mesh,
                            build_strategy=bst)


def _compiled_hlo(exe, feed):
    scope = pt.global_scope()
    cs = list(exe._cache.values())[-1]
    feed_vals = tuple(jnp.asarray(feed[n]) for n in cs.feed_names)
    ro = tuple(scope.get(n) for n in cs.ro_names)
    rw = tuple(scope.get(n) for n in cs.rw_names)
    return cs.fn.lower(feed_vals, ro, rw, np.uint32(0)).compile().as_text()


def _run_modes(rng, modes, steps=3, optimizer="momentum"):
    """Run the same training trajectory under each mode; returns
    {name: (losses, census)}. Fresh program/scope per mode."""
    feeds = [_feed(np.random.RandomState(1000 + i)) for i in range(steps)]
    out = {}
    for name, (mode, quant, ef) in modes.items():
        pt.reset_default_programs()
        pt.reset_global_scope()
        with pt.core.unique_name.guard():
            loss = _build_mlp(optimizer)
        exe = _exe(loss, mode, quant=quant, ef=ef)
        pt.Executor().run(pt.default_startup_program())
        losses = [float(exe.run(feed=f, fetch_list=[loss])[0])
                  for f in feeds]
        out[name] = (losses, collective_census(_compiled_hlo(exe, feeds[-1])))
    return out


class TestReduceScatterStructure:
    def test_census_no_gradient_allreduce_exact_balance(self, rng):
        res = _run_modes(rng, {
            "allreduce": (ReduceStrategy.AllReduce, "", False),
            "rs": (ReduceStrategy.ReduceScatter, "", False)})
        _, base_census = res["allreduce"]
        losses, census = res["rs"]

        # 1. no all-reduce carries gradient bytes: every surviving
        #    all-reduce is a scalar (loss pmean)
        for b, line in census.get("all-reduce", []):
            assert b <= 64, (b, line[:120])

        # 2. exact analytic balance. reduce-scatter: each sharded gradient
        #    leaves a 1/8 chunk, the bucket (b2 padded to 16 f32) too.
        rs_bytes = sum(b for b, _ in census.get("reduce-scatter", []))
        assert rs_bytes == SHARDED_BYTES // DP + BUCKET_PAD_BYTES // DP, \
            census.get("reduce-scatter")
        # all-gather: the three updated parameters come back whole, plus
        # the bucket's gathered gradient
        ag_bytes = sum(b for b, _ in census.get("all-gather", []))
        assert ag_bytes == SHARDED_BYTES + BUCKET_PAD_BYTES, \
            census.get("all-gather")

        # 3. ring identity, EXACT: an all-reduce costs its reduce-scatter +
        #    all-gather decomposition, so total wire bytes differ between
        #    the modes by precisely the bucket's pad-to-16-f32 slack
        #    (min_bytes=8 drops only the 4-byte scalar loss pmean both
        #    modes share). The GRADIENT share of the wire halves — the
        #    other half became parameter bytes (overlappable with the next
        #    forward pass, which an all-reduce's gather half is not).
        ar_wire = census_wire_bytes(base_census, DP, min_bytes=8)
        rs_wire = census_wire_bytes(census, DP, min_bytes=8)
        pad_bytes = BUCKET_PAD_BYTES - 10 * 4
        pad_wire = (collective_wire_bytes("reduce-scatter",
                                          pad_bytes // DP, DP)
                    + collective_wire_bytes("all-gather", pad_bytes, DP))
        assert rs_wire - ar_wire == pad_wire, (rs_wire, ar_wire, pad_wire)
        grad_wire = (collective_wire_bytes("reduce-scatter", rs_bytes, DP)
                     + collective_wire_bytes("all-gather", BUCKET_PAD_BYTES,
                                             DP))
        assert grad_wire < 0.51 * ar_wire, (grad_wire, ar_wire)

    def test_quantized_census_wire_ratio(self, rng):
        res = _run_modes(rng, {
            "allreduce": (ReduceStrategy.AllReduce, "", False),
            "quant": (ReduceStrategy.AllReduce, "int8", False)})
        _, base_census = res["allreduce"]
        losses, census = res["quant"]
        # int8 payload on the wire, fp32 nowhere except scalars
        assert any("s8[" in line for items in census.values()
                   for _, line in items), census
        base_wire = census_wire_bytes(base_census, DP, min_bytes=1024)
        q_wire = census_wire_bytes(census, DP, min_bytes=1024)
        ratio = base_wire / q_wire
        assert ratio >= 3.5, (base_wire, q_wire, ratio)
        # exact accounting of the quantized transfer: one bucket of all
        # 9610 grad values, padded to 9616 (dp) then per-chunk to 1280
        # (block 256): 8 destinations x (1280 int8 + 5 f32 scales)
        a2a = sum(b for b, _ in census.get("all-to-all", []))
        assert a2a == 8 * (1280 + 5 * 4), census.get("all-to-all")
        ag = sum(b for b, _ in census.get("all-gather", []))
        assert ag == 8 * (1280 + 5 * 4), census.get("all-gather")


class TestExplicitParity:
    def test_reduce_scatter_parity(self, rng):
        res = _run_modes(rng, {
            "allreduce": (ReduceStrategy.AllReduce, "", False),
            "rs": (ReduceStrategy.ReduceScatter, "", False)})
        base, _ = res["allreduce"]
        rs, _ = res["rs"]
        np.testing.assert_allclose(rs, base, rtol=0, atol=1e-5)

    def test_quantized_parity_with_error_feedback(self, rng):
        res = _run_modes(rng, {
            "allreduce": (ReduceStrategy.AllReduce, "", False),
            "q": (ReduceStrategy.ReduceScatter, "int8", True)},
            optimizer="adam")
        base, _ = res["allreduce"]
        q, _ = res["q"]
        np.testing.assert_allclose(q, base, rtol=0, atol=5e-3)


class TestErrorFeedback:
    def test_state_is_sharded_persistent_and_advances(self, rng):
        loss = _build_mlp()
        exe = _exe(loss, ReduceStrategy.ReduceScatter, quant="int8", ef=True)
        pt.Executor().run(pt.default_startup_program())
        exe.run(feed=_feed(rng), fetch_list=[loss])
        scope = pt.global_scope()
        err_names = [n for n in scope.local_var_names()
                     if n.startswith("dp_comm_err")]
        assert err_names, "error-feedback state vars missing from scope"
        first = {n: np.asarray(scope.get(n)).copy() for n in err_names}
        for n in err_names:
            v = first[n]
            assert v.shape[0] == DP, v.shape      # one residual per replica
            assert np.abs(v).sum() > 0            # quantization left residue
        exe.run(feed=_feed(np.random.RandomState(7)), fetch_list=[loss])
        changed = any(not np.array_equal(first[n],
                                         np.asarray(scope.get(n)))
                      for n in err_names)
        assert changed, "error state did not advance across steps"

    def test_run_steps_carries_error_state(self, rng):
        loss = _build_mlp()
        exe = _exe(loss, ReduceStrategy.ReduceScatter, quant="int8", ef=True)
        pt.Executor().run(pt.default_startup_program())
        feeds = [_feed(np.random.RandomState(i)) for i in range(3)]
        out = exe.run_steps(feeds, fetch_list=[loss])
        assert np.asarray(out[0]).shape[0] == 3   # stacked loss curve
        scope = pt.global_scope()
        err_names = [n for n in scope.local_var_names()
                     if n.startswith("dp_comm_err")]
        assert err_names
        assert np.abs(np.asarray(scope.get(err_names[0]))).sum() > 0


class TestGatesAndKillSwitch:
    def test_non_divisible_batch_rejected(self, rng):
        loss = _build_mlp()
        exe = _exe(loss, ReduceStrategy.ReduceScatter)
        pt.Executor().run(pt.default_startup_program())
        with pytest.raises(InvalidArgumentError, match="divisible"):
            exe.run(feed=_feed(rng, bs=30), fetch_list=[loss])

    def test_kill_switch_forces_fp32_wire(self, rng):
        loss = _build_mlp()
        exe = _exe(loss, ReduceStrategy.ReduceScatter, quant="int8")
        pt.Executor().run(pt.default_startup_program())
        old = flags.get_flag("quant_comm")
        try:
            flags.set_flag("quant_comm", False)
            feed = _feed(rng)
            exe.run(feed=feed, fetch_list=[loss])
            census = collective_census(_compiled_hlo(exe, feed))
            assert not any("s8[" in line for items in census.values()
                           for _, line in items), census
            # still the explicit pipeline: reduce-scatter present
            assert "reduce-scatter" in census, census.keys()
        finally:
            flags.set_flag("quant_comm", old)

    def test_sum_fetch_rejected_mean_fetch_ok(self, rng):
        x = layers.data("x", shape=[16])
        label = layers.data("label", shape=[1], dtype="int64")
        per_row = layers.softmax_with_cross_entropy(
            layers.fc(x, size=4), label)
        total = layers.reduce_sum(per_row)
        loss = layers.mean(per_row)
        pt.optimizer.SGDOptimizer(0.1).minimize(loss)
        exe = _exe(loss, ReduceStrategy.ReduceScatter)
        pt.Executor().run(pt.default_startup_program())
        feed = {"x": np.random.RandomState(0).rand(16, 16).astype("f4"),
                "label": np.zeros((16, 1), np.int64)}
        # a sum fetch would come back /dp — rejected, not silently scaled
        with pytest.raises(InvalidArgumentError, match="sum reduction"):
            exe.run(feed=feed, fetch_list=[loss, total])
        out = exe.run(feed=feed, fetch_list=[loss])   # mean fetch fine
        assert np.isfinite(float(out[0]))

    def test_general_mesh_annotation_replicated_here_is_allowed(self, rng):
        # a param annotated for a bigger mesh (tp axis) resolves to
        # all-None = replicated on this dp-only mesh: must NOT trip the
        # TP gate (mesh.pspec drops absent axes by design)
        loss = _build_mlp()
        prog = pt.default_main_program()
        w = next(v for v in prog.global_block().vars.values()
                 if getattr(v, "trainable", False) and len(v.shape) == 2)
        w.sharding_spec = ("tp", None)
        exe = _exe(loss, ReduceStrategy.ReduceScatter)
        pt.Executor().run(pt.default_startup_program())
        out = exe.run(feed=_feed(rng), fetch_list=[loss])
        assert np.isfinite(float(out[0]))

    def test_batch_global_op_rejected(self, rng):
        x = layers.data("img", shape=[16])
        h = layers.fc(x, size=16)
        h = layers.batch_norm(h)
        label = layers.data("label", shape=[1], dtype="int64")
        loss = layers.mean(layers.softmax_with_cross_entropy(
            layers.fc(h, size=4), label))
        pt.optimizer.SGDOptimizer(0.1).minimize(loss)
        exe = _exe(loss, ReduceStrategy.ReduceScatter)
        pt.Executor().run(pt.default_startup_program())
        with pytest.raises(InvalidArgumentError, match="batch_norm"):
            exe.run(feed={"img": np.zeros((16, 16), np.float32),
                          "label": np.zeros((16, 1), np.int64)},
                    fetch_list=[loss])


class TestThreeAxisMesh:
    def test_quantization_only_on_dp_axis(self, rng):
        """Regression: on a dp=2 x tp=2 x sp=2 mesh, every quantized
        collective must group dp siblings only — devices {i, i+4} for the
        (dp, tp, sp) axis order — and the numerics must match the SPMD
        baseline run on the same mesh."""
        feeds = [_feed(np.random.RandomState(50 + i), bs=16)
                 for i in range(2)]
        axes = {"dp": 2, "tp": 2, "sp": 2}

        pt.reset_default_programs()
        pt.reset_global_scope()
        with pt.core.unique_name.guard():
            loss = _build_mlp()
        exe = _exe(loss, ReduceStrategy.AllReduce, axes=axes)
        pt.Executor().run(pt.default_startup_program())
        base = [float(exe.run(feed=f, fetch_list=[loss])[0]) for f in feeds]

        pt.reset_default_programs()
        pt.reset_global_scope()
        with pt.core.unique_name.guard():
            loss = _build_mlp()
        exe = _exe(loss, ReduceStrategy.ReduceScatter, quant="int8",
                   axes=axes)
        pt.Executor().run(pt.default_startup_program())
        got = [float(exe.run(feed=f, fetch_list=[loss])[0]) for f in feeds]
        np.testing.assert_allclose(got, base, rtol=0, atol=1e-3)

        census = collective_census(_compiled_hlo(exe, feeds[-1]))
        dp_groups = {frozenset({i, i + 4}) for i in range(4)}
        quant_lines = [line for items in census.values()
                       for _, line in items if "s8[" in line]
        assert quant_lines, census
        for line in quant_lines:
            m = re.search(r"replica_groups=\{(\{[\d,]+\}(?:,\{[\d,]+\})*)\}",
                          line)
            assert m, line[:160]
            groups = {frozenset(int(x) for x in g.split(","))
                      for g in re.findall(r"\{([\d,]+)\}", m.group(1))}
            assert groups <= dp_groups, (groups, line[:160])
