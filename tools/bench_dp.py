#!/usr/bin/env python
"""Data-parallel gradient-path A/B (ISSUE r8): allreduce vs reduce-scatter
vs quantized on the virtual device mesh, plus quantized-vs-fp32
convergence parity.

Produces BENCH_DP_r08.json. For each model config and reduce mode:

  - per-step latency, >=3 independent runs (fresh executor each), spreads;
  - collective_cost_ms_per_step = dp8 step minus the dp1-equivalent step
    (same per-device batch, no collectives) — the absolute per-step cost
    this host pays for the gradient exchange, the same reading
    tools/benchmark.py multiproc reports (a REAL multi-process world needs
    jaxlib >= 0.5; this container's 0.4.x CPU backend cannot form one, so
    the mesh is 8 single-process host devices and the caveat is stated);
  - grad_bytes_on_wire: analytic ring model AND the HLO census — the two
    must agree exactly (tests/test_zero_comm.py pins this balance).

Convergence: 100 steps, fixed seeds and feed stream, fp32-SPMD vs int8
(with and without error feedback) on the flagship-adjacent MLP and
stacked-LSTM configs; the artifact commits the sampled loss curves and
max |delta|.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        python tools/bench_dp.py | tee BENCH_DP_r08.json
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

if "XLA_FLAGS" not in os.environ:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

from probe_common import census_wire_bytes, collective_census  # noqa: E402

DP = 8
ITERS = 15
RUNS = 3
CONV_STEPS = 100


def _build(config):
    import paddle_tpu as pt
    from paddle_tpu import layers

    pt.reset_default_programs()
    pt.reset_global_scope()
    with pt.core.unique_name.guard():
        if config == "mlp":
            # comm-bound: 2.7 MB of gradients over ~0.4 MFLOP of compute
            x = layers.data("img", shape=[784])
            h = layers.fc(x, size=784, act="relu")
            logits = layers.fc(h, size=10)
            label = layers.data("label", shape=[1], dtype="int64")
            loss = layers.mean(layers.softmax_with_cross_entropy(
                logits, label))
        else:                                  # stacked_lstm
            from paddle_tpu.models import stacked_lstm
            loss = stacked_lstm.stacked_lstm_net(
                dict_dim=10000, emb_dim=256, hid_dim=256, max_len=32)[0]
        pt.optimizer.MomentumOptimizer(0.05, momentum=0.9).minimize(loss)
    return loss


def _feed(config, rng, bs):
    if config == "mlp":
        return {"img": rng.rand(bs, 784).astype("float32"),
                "label": rng.randint(0, 10, (bs, 1)).astype("int64")}
    seq = 32
    return {"words": rng.randint(0, 10000, (bs, seq)).astype("int64"),
            "words@SEQLEN": np.full((bs,), seq, dtype="int32"),
            "label": rng.randint(0, 2, (bs, 1)).astype("int64")}


def _strategy(mode, ef=False):
    from paddle_tpu.parallel.strategy import BuildStrategy, ReduceStrategy
    bst = BuildStrategy()
    bst.reduce_strategy = {"allreduce": ReduceStrategy.AllReduce,
                           "reduce_scatter": ReduceStrategy.ReduceScatter,
                           "quantized": ReduceStrategy.ReduceScatter,
                           }[mode]
    if mode == "quantized":
        bst.quant_comm = "int8"
        bst.comm_error_feedback = ef
    return bst


def _time_steps(run_step, iters=ITERS):
    out = run_step()
    float(np.asarray(out[0]).ravel()[0])           # compile + drain
    t0 = time.time()
    outs = [run_step() for _ in range(iters)]
    float(np.asarray(outs[-1]).ravel()[0])
    return (time.time() - t0) / iters * 1e3


def measure_mode(config, mode, bs):
    """One independent run: fresh program + executor. Returns
    (latency_ms, comm_fields or None)."""
    import jax.numpy as jnp
    import paddle_tpu as pt
    from paddle_tpu.parallel import ParallelExecutor, grad_comm

    loss = _build(config)
    exe = ParallelExecutor(loss_name=loss.name, build_strategy=_strategy(mode))
    pt.Executor().run(pt.default_startup_program())
    feed = _feed(config, np.random.RandomState(0), bs)
    lat = _time_steps(lambda: exe.run(feed=feed, fetch_list=[loss],
                                      return_numpy=False))
    prog, scope = pt.default_main_program(), pt.global_scope()
    rewritten = exe._prepare_program(prog, scope)
    analytic = (grad_comm.analytic_wire_bytes(rewritten, DP)
                or grad_comm.spmd_allreduce_wire_bytes(prog, DP))
    cs = list(exe._cache.values())[-1]
    hlo = cs.fn.lower(
        tuple(jnp.asarray(feed[n]) for n in cs.feed_names),
        tuple(scope.get(n) for n in cs.ro_names),
        tuple(scope.get(n) for n in cs.rw_names),
        np.uint32(0)).compile().as_text()
    census = collective_census(hlo)
    fields = {
        "grad_bytes_on_wire": analytic["grad_wire_bytes"],
        "param_allgather_bytes_on_wire":
            analytic["param_allgather_wire_bytes"],
        "wire_bytes_per_step_analytic": analytic["wire_bytes"],
        "wire_bytes_per_step_census": int(census_wire_bytes(
            census, DP, min_bytes=8)),
        "census_collectives": {k: len(v) for k, v in census.items()},
        "gradient_allreduce_instructions": sum(
            1 for b, _ in census.get("all-reduce", []) if b > 64),
    }
    return lat, fields


def measure_dp1(config, bs):
    """The no-collective yardstick: plain single-device executor on the
    per-shard batch (bs/DP) — identical per-device compute, zero comm."""
    import paddle_tpu as pt

    loss = _build(config)
    exe = pt.Executor()
    exe.run(pt.default_startup_program())
    feed = _feed(config, np.random.RandomState(0), bs // DP)
    return _time_steps(lambda: exe.run(feed=feed, fetch_list=[loss],
                                       return_numpy=False))


def bench_config(config, bs):
    dp1 = [round(measure_dp1(config, bs), 3) for _ in range(RUNS)]
    row = {"config": config, "global_batch": bs, "dp": DP,
           "iters_per_run": ITERS, "runs": RUNS,
           "dp1_equiv_latency_ms": {"runs": dp1, "best": min(dp1)}}
    for mode in ("allreduce", "reduce_scatter", "quantized"):
        lats, fields = [], None
        for _ in range(RUNS):
            lat, fields = measure_mode(config, mode, bs)
            lats.append(round(lat, 3))
        row[mode] = {
            "latency_ms_runs": lats,
            "latency_ms": min(lats),
            "latency_ms_spread": [min(lats), max(lats)],
            "collective_cost_ms_per_step": round(min(lats) - min(dp1), 3),
            **fields,
        }
    ar, rs, q = (row[m] for m in ("allreduce", "reduce_scatter",
                                  "quantized"))
    row["grad_wire_reduction_rs_vs_allreduce"] = round(
        ar["grad_bytes_on_wire"] / rs["grad_bytes_on_wire"], 2)
    row["grad_wire_reduction_quant_vs_rs"] = round(
        rs["grad_bytes_on_wire"] / q["grad_bytes_on_wire"], 2)
    row["grad_wire_reduction_quant_vs_allreduce"] = round(
        ar["grad_bytes_on_wire"] / q["grad_bytes_on_wire"], 2)
    return row


def convergence(config, bs):
    """100 fixed-seed steps: fp32 SPMD vs int8 (+-error feedback)."""
    import paddle_tpu as pt
    from paddle_tpu.parallel import ParallelExecutor

    def run(mode, ef):
        loss = _build(config)
        exe = ParallelExecutor(loss_name=loss.name,
                               build_strategy=_strategy(mode, ef=ef))
        pt.Executor().run(pt.default_startup_program())
        losses = []
        for i in range(CONV_STEPS):
            feed = _feed(config, np.random.RandomState(10_000 + i), bs)
            losses.append(float(exe.run(feed=feed, fetch_list=[loss])[0]))
        return losses

    fp32 = run("allreduce", False)
    q_ef = run("quantized", True)
    q_raw = run("quantized", False)

    def delta(a):
        return float(max(abs(x - y) for x, y in zip(a, fp32)))

    sample = list(range(0, CONV_STEPS, 10)) + [CONV_STEPS - 1]
    return {
        "config": config, "steps": CONV_STEPS, "global_batch": bs,
        "seeds": "feed stream RandomState(10000+i); program seed 0",
        "loss_curve_sampled": {
            "step": sample,
            "fp32": [round(fp32[i], 5) for i in sample],
            "int8_error_feedback": [round(q_ef[i], 5) for i in sample],
            "int8_no_feedback": [round(q_raw[i], 5) for i in sample],
        },
        "final_loss": {"fp32": round(fp32[-1], 5),
                       "int8_error_feedback": round(q_ef[-1], 5),
                       "int8_no_feedback": round(q_raw[-1], 5)},
        "max_abs_delta_vs_fp32": {
            "int8_error_feedback": round(delta(q_ef), 5),
            "int8_no_feedback": round(delta(q_raw), 5)},
    }


def main():
    t0 = time.time()
    rows = [bench_config("mlp", 64), bench_config("stacked_lstm", 16)]
    conv = [convergence("mlp", 64), convergence("stacked_lstm", 16)]
    print(json.dumps({
        "bench": "data-parallel gradient path A/B (ISSUE r8)",
        "mesh": f"{DP} virtual CPU devices, single process "
                f"(tools/benchmark.py --update_method multiproc carries "
                f"the same reduce_mode/byte fields over a real N-process "
                f"world)",
        "rows": rows,
        "convergence": conv,
        "reading": {
            "grad_bytes_on_wire": "per device per step, ring model "
                "(probe_common.collective_wire_bytes). For the explicit "
                "modes (reduce_scatter/quantized) analytic == census to "
                "rounding (<= tens of bytes: per-instruction float "
                "(N-1)/N terms + the 4-byte scalar loss pmean) — WE emit "
                "those collectives; tests/test_zero_comm.py pins the "
                "balance exactly on the MLP. For SPMD allreduce the "
                "analytic row is the dense-gradient formula and XLA owns "
                "the instructions — it may restructure small collectives "
                "(0.04% delta on the LSTM row, committed side by side)",
            "collective_cost_ms_per_step": "mode latency minus the "
                "dp1-equivalent (same per-device batch, no collectives)",
        },
        "caveats": [
            "wall-clock on this mesh crosses a memcpy-speed interconnect "
            "shared by 8 host threads on 2 cores: byte fields are the "
            "TPU-transferable claim; ms fields are a this-host census "
            "(quantized mode trades wire bytes for quant/dequant compute, "
            "which a CPU mesh pays but free ICI does not reward)",
        ],
        "wall_s": round(time.time() - t0, 1),
    }, indent=1))


if __name__ == "__main__":
    main()
