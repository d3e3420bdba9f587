#!/bin/bash
# CI driver (≙ reference paddle/scripts/paddle_build.sh: build + test +
# API check + smokes). Runs on the virtual 8-device CPU mesh.
#
#   tools/run_ci.sh          full tier (suite measured at ~40 min on this
#                            2-core box single-process — budget an hour)
#   tools/run_ci.sh quick    smoke tier (~5 min): build + API check +
#                            `-m quick`-marked tests + the smokes below
set -e
cd "$(dirname "$0")/.."
TIER="${1:-full}"

echo "== build native runtime =="
PTPU_BUILD_PREDICT=1 sh paddle_tpu/native/build.sh || \
    sh paddle_tpu/native/build.sh   # predictor needs TF libs; lib alone if absent

echo "== API surface check =="
JAX_PLATFORMS=cpu python tools/print_signatures.py | sort > /tmp/api_current.txt
sort API.spec > /tmp/api_golden.txt
diff /tmp/api_golden.txt /tmp/api_current.txt || {
    echo "API surface drifted — review and run tools/print_signatures.py --update"; exit 1; }

echo "== static program lint (analyzer over the flagship builders) =="
# whole-program shape/dtype inference + structural/parallel/dataflow
# verification (framework/analysis.py + framework/dataflow.py) over the
# flagship builders AND the serving-engine programs; exit 1 on any
# error-severity diagnostic. docs/static_analysis.md has the catalog.
JAX_PLATFORMS=cpu python tools/lint_program.py --model mnist
JAX_PLATFORMS=cpu python tools/lint_program.py --model transformer_lm
# the serving path: the engine's compiled decode tick + the prefill/
# generate program must be analyzer-clean too (docs/serving.md)
JAX_PLATFORMS=cpu python tools/lint_program.py \
    --model transformer_lm_decode_tick
JAX_PLATFORMS=cpu python tools/lint_program.py \
    --model transformer_lm_paged_decode_tick
JAX_PLATFORMS=cpu python tools/lint_program.py \
    --model transformer_lm_quant_decode_tick
# the r22 speculative-decoding programs: draft tick + both verify
# forwards (serving/speculative.py builds exactly these shapes)
JAX_PLATFORMS=cpu python tools/lint_program.py \
    --model transformer_lm_draft_tick
JAX_PLATFORMS=cpu python tools/lint_program.py \
    --model transformer_lm_spec_verify_tick
JAX_PLATFORMS=cpu python tools/lint_program.py \
    --model transformer_lm_paged_spec_verify_tick
JAX_PLATFORMS=cpu python tools/lint_program.py --model transformer_lm_prefill
# tp lint: tp-annotated transformer through tp_shard_pass at tp=2; prints
# the propagated sharding-spec table and fails on any propagation conflict
# (docs/tensor_parallel.md has the rule catalog)
JAX_PLATFORMS=cpu python tools/lint_program.py --model transformer_lm_tp \
    --tp 2

if [ "$TIER" != "quick" ]; then
    echo "== lint-all sweep: every builder x {plain, dp2, pp2, tp2} =="
    # the zero-false-positive acceptance gate: every model builder, under
    # every parallelism rewrite its gates admit, must produce zero
    # error-severity diagnostics. --json is the contract (machine-readable
    # code/severity/op_loc rows; documented exit codes in
    # tools/lint_program.py) — no table scraping. Pass gates rejecting a
    # (model, config) pair are expected sweep noise (--allow_gate_rejects).
    # the r18 planned variants ride the same sweep: every (model, config)
    # pair is ALSO linted through memory_plan_pass — the planner's
    # scheduling/coloring/remat must introduce zero error diagnostics on
    # every program the detectors accept unplanned
    rm -f /tmp/lint_sweep_*.json
    i=0
    for flags in "" "--dp 2" "--pipeline_stages 2 --num_microbatches 4" \
                 "--tp 2" "--memory_plan" "--dp 2 --memory_plan" \
                 "--pipeline_stages 2 --num_microbatches 4 --memory_plan" \
                 "--tp 2 --memory_plan"; do
        # don't let set -e kill the sweep on a lint exit(1): the Python
        # aggregator below owns the gating AND prints which model/config/
        # code failed (a hard crash leaves truncated JSON, which the
        # aggregator's json.load turns into a failure too)
        JAX_PLATFORMS=cpu python tools/lint_program.py --all --json \
            --allow_gate_rejects $flags > /tmp/lint_sweep_$i.json || true
        i=$((i+1))
    done
    python - <<'PY'
import glob, json
rows = [r for f in sorted(glob.glob("/tmp/lint_sweep_*.json"))
        for r in json.load(open(f))]
bad = [r for r in rows if r["errors"]]
gated = [r for r in rows if r["gate_rejected"]]
for r in bad:
    for d in r["diagnostics"]:
        if d["severity"] == "error":
            print(f"{r['model']} {r['config']}: [{d['code']}] "
                  f"{d['loc']}: {d['message']}")
assert not bad, f"{len(bad)} builder/config pair(s) with error diagnostics"
print(f"lint-all sweep OK: {len(rows) - len(gated)} program(s) clean, "
      f"{len(gated)} gate-skipped across {len(rows)} (model, config) pairs")
PY
fi

if [ "$TIER" = "quick" ]; then
    echo "== quick test tier (~5 min) =="
    # the fusion numeric-parity tests (tests/test_fusion.py) ride this
    # tier via their `quick` marks — the fuse passes are default-on, so
    # every smoke must see them verified. PTPU_VERIFY_PASSES=1 keeps the
    # pass sanitizer active, so every pass test doubles as a sanitizer
    # test (it is also the default; the env pins it).
    PTPU_VERIFY_PASSES=1 \
    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        python -m pytest tests/ -q -x -m quick
else
    echo "== full test pyramid (~29 min on 2 cores with -n 2; measured) =="
    # tier-1 selection: everything but the slow-marked tests
    PTPU_VERIFY_PASSES=1 \
    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        python -m pytest tests/ -q -n 2 --dist load -m 'not slow'
fi

echo "== dp-comm smoke (reduce-scatter + quantized collectives) =="
# the explicit gradient pipeline end to end on the 8-virtual-device mesh:
# reduce-scatter mode must leave no gradient all-reduce in the compiled
# step, quantized mode must put int8 on the wire, and both must train.
# (A REAL 2-process world needs jaxlib >= 0.5 — the CPU backend below
# that cannot run multi-process collectives; tests/test_dist_multiproc.py
# carries the same skip. This smoke pins the structure, which is
# process-count-invariant.)
XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
python - <<'PY'
import numpy as np, jax
import paddle_tpu as pt
from paddle_tpu import layers
from paddle_tpu.parallel import ParallelExecutor
from paddle_tpu.parallel.strategy import BuildStrategy, ReduceStrategy
from paddle_tpu.framework.costs import collective_census

for quant in ("", "int8"):
    pt.reset_default_programs(); pt.reset_global_scope()
    with pt.core.unique_name.guard():
        x = layers.data("x", shape=[64])
        label = layers.data("label", shape=[1], dtype="int64")
        h = layers.fc(x, size=128, act="relu")
        loss = layers.mean(layers.softmax_with_cross_entropy(
            layers.fc(h, size=10), label))
        pt.optimizer.MomentumOptimizer(0.1, momentum=0.9).minimize(loss)
    bst = BuildStrategy(); bst.reduce_strategy = ReduceStrategy.ReduceScatter
    bst.quant_comm = quant; bst.comm_error_feedback = bool(quant)
    exe = ParallelExecutor(loss_name=loss.name, build_strategy=bst)
    pt.Executor().run(pt.default_startup_program())
    rng = np.random.RandomState(0)
    feed = {"x": rng.rand(32, 64).astype("float32"),
            "label": rng.randint(0, 10, (32, 1)).astype("int64")}
    l0 = float(exe.run(feed=feed, fetch_list=[loss])[0])
    l1 = float(exe.run(feed=feed, fetch_list=[loss])[0])
    assert l1 < l0, (quant, l0, l1)          # it actually trains
    import jax.numpy as jnp
    cs = list(exe._cache.values())[-1]
    scope = pt.global_scope()
    hlo = cs.fn.lower(tuple(jnp.asarray(feed[n]) for n in cs.feed_names),
                      tuple(scope.get(n) for n in cs.ro_names),
                      tuple(scope.get(n) for n in cs.rw_names),
                      np.uint32(0)).compile().as_text()
    census = collective_census(hlo)
    assert all(b <= 64 for b, _ in census.get("all-reduce", [])), \
        "gradient all-reduce leaked into reduce-scatter mode"
    if quant:
        assert any("s8[" in l for v in census.values() for _, l in v), \
            "quantized mode has no int8 on the wire"
print("dp-comm smoke OK")
PY

echo "== tensor-parallel smoke (tp2 parity through tp_shard_pass) =="
# the static sharding subsystem end to end: annotate_tp + tp_shard_pass +
# the full-manual shard_map executor must reproduce the single-device
# fixed-seed loss curve on a dp1 x tp2 mesh in ReduceScatter mode
# (f32 matmuls: splitting a bf16 contraction changes its rounding).
XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
python - <<'PY'
import numpy as np, jax
import paddle_tpu as pt
from paddle_tpu.core import flags
from paddle_tpu.parallel import ParallelExecutor, annotate_tp
from paddle_tpu.parallel.mesh import DeviceMesh
from paddle_tpu.parallel.strategy import BuildStrategy, ReduceStrategy

flags.set_flag("use_bf16_matmul", False)

def build():
    from paddle_tpu.models import transformer
    loss, _ = transformer.transformer_lm(
        vocab=64, max_len=8, d_model=32, d_inner=64, num_heads=4,
        num_layers=2, mean_loss=True)
    pt.optimizer.Adam(learning_rate=1e-3).minimize(loss)
    return loss

rng = np.random.RandomState(7)
feeds = [{"tokens": rng.randint(0, 64, (8, 8)).astype("int64"),
          "tokens@SEQLEN": np.full((8,), 8, "int32"),
          "targets": rng.randint(0, 64, (8, 8)).astype("int64")}
         for _ in range(3)]
pt.reset_default_programs(); pt.reset_global_scope()
with pt.core.unique_name.guard():
    loss = build()
exe = pt.Executor(); exe.run(pt.default_startup_program())
base = [float(exe.run(feed=f, fetch_list=[loss])[0]) for f in feeds]
pt.reset_default_programs(); pt.reset_global_scope()
with pt.core.unique_name.guard():
    loss = build()
assert annotate_tp()
bst = BuildStrategy(); bst.reduce_strategy = ReduceStrategy.ReduceScatter
mesh = DeviceMesh(jax.devices()[:2], {"dp": 1, "tp": 2})
pexe = ParallelExecutor(loss_name=loss.name, mesh=mesh,
                        build_strategy=bst)
pt.Executor().run(pt.default_startup_program())
got = [float(pexe.run(feed=f, fetch_list=[loss])[0]) for f in feeds]
assert max(abs(a - b) for a, b in zip(base, got)) <= 1e-5, (base, got)
prog = pexe._prepare_program(pt.default_main_program(), pt.global_scope())
assert getattr(prog, "_tp_applied", False)
print("tensor-parallel smoke OK")
PY

echo "== pipeline-parallel smoke (gpipe + 1f1b parity, pp=2, M=4) =="
# the program-level pipeline executor end to end: partition pass + both
# schedules must reproduce the single-device fixed-seed loss curve, and
# the compiled step must carry exactly one boundary-activation + one
# boundary-gradient collective-permute per tick.
XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
python - <<'PY'
import numpy as np, jax
import paddle_tpu as pt
from paddle_tpu import layers
from paddle_tpu.parallel import ParallelExecutor
from paddle_tpu.parallel.mesh import DeviceMesh
from paddle_tpu.parallel.strategy import BuildStrategy
from paddle_tpu.framework.costs import collective_census

def build():
    x = layers.data("x", shape=[32])
    label = layers.data("label", shape=[1], dtype="int64")
    h = layers.fc(x, size=64, act="relu")
    h = layers.fc(h, size=64, act="relu")
    loss = layers.mean(layers.softmax_with_cross_entropy(
        layers.fc(h, size=10), label))
    pt.optimizer.MomentumOptimizer(0.1, momentum=0.9).minimize(loss)
    return loss

rng = np.random.RandomState(0)
feeds = [{"x": np.random.RandomState(50 + i).rand(16, 32).astype("f4"),
          "label": np.random.RandomState(60 + i)
          .randint(0, 10, (16, 1)).astype("i8")} for i in range(3)]
pt.reset_default_programs(); pt.reset_global_scope()
with pt.core.unique_name.guard():
    loss = build()
exe = pt.Executor(); exe.run(pt.default_startup_program())
base = [float(exe.run(feed=f, fetch_list=[loss])[0]) for f in feeds]
for sched in ("gpipe", "1f1b"):
    pt.reset_default_programs(); pt.reset_global_scope()
    with pt.core.unique_name.guard():
        loss = build()
    bst = BuildStrategy(pipeline_stages=2, num_microbatches=4,
                        pipeline_schedule=sched)
    mesh = DeviceMesh(jax.devices()[:2], {"pp": 2})
    pexe = ParallelExecutor(loss_name=loss.name, mesh=mesh,
                            build_strategy=bst)
    pt.Executor().run(pt.default_startup_program())
    got = [float(pexe.run(feed=f, fetch_list=[loss])[0]) for f in feeds]
    assert max(abs(a - b) for a, b in zip(base, got)) <= 1e-5, (sched,
                                                                base, got)
    import jax.numpy as jnp
    cs = list(pexe._cache.values())[-1]
    scope = pt.global_scope()
    hlo = cs.fn.lower(tuple(jnp.asarray(feeds[-1][n])
                            for n in cs.feed_names),
                      tuple(scope.get(n) for n in cs.ro_names),
                      tuple(scope.get(n) for n in cs.rw_names),
                      np.uint32(0)).compile().as_text()
    census = collective_census(hlo)
    n_perm = len(census.get("collective-permute", []))
    assert n_perm == 2, (sched, n_perm)
print("pipeline smoke OK")
PY

echo "== observability smoke (spans + ledger + /metrics) =="
# the r12 layer end to end: a traced 3-step mnist run must record the
# executor's compile/step/feed_fetch spans, the cost ledger's predicted
# wire bytes must equal the HLO census EXACTLY on a dp2 reduce-scatter
# step, and one Prometheus scrape of a live EngineServer must carry the
# serving telemetry (docs/observability.md).
XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
python - <<'PY'
import numpy as np, jax
import jax.numpy as jnp
import paddle_tpu as pt
from paddle_tpu import layers
from paddle_tpu.framework.costs import collective_census
from paddle_tpu.observability import tracing
from paddle_tpu.observability.ledger import CostLedger
from paddle_tpu.parallel import ParallelExecutor
from paddle_tpu.parallel.strategy import BuildStrategy, ReduceStrategy
from paddle_tpu.parallel.mesh import DeviceMesh

pt.reset_default_programs(); pt.reset_global_scope()
with pt.core.unique_name.guard():
    x = layers.data("x", shape=[64])
    label = layers.data("label", shape=[1], dtype="int64")
    h = layers.fc(x, size=128, act="relu")
    loss = layers.mean(layers.softmax_with_cross_entropy(
        layers.fc(h, size=10), label))
    pt.optimizer.MomentumOptimizer(0.1, momentum=0.9).minimize(loss)
bst = BuildStrategy(); bst.reduce_strategy = ReduceStrategy.ReduceScatter
mesh = DeviceMesh(jax.devices()[:2], {"dp": 2})
exe = ParallelExecutor(loss_name=loss.name, build_strategy=bst, mesh=mesh)
pt.Executor().run(pt.default_startup_program())
rng = np.random.RandomState(0)
feed = {"x": rng.rand(16, 64).astype("float32"),
        "label": rng.randint(0, 10, (16, 1)).astype("int64")}
mark = tracing.mark()
for _ in range(3):                                   # traced 3-step run
    exe.run(feed=feed, fetch_list=[loss])
kinds = {(s.kind, s.name) for s in tracing.spans_since(mark)}
assert ("step", "executor/run") in kinds, kinds
assert ("feed_fetch", "executor/feed") in kinds, kinds

cs = list(exe._cache.values())[-1]
scope = pt.global_scope()
hlo = cs.fn.lower(tuple(jnp.asarray(feed[n]) for n in cs.feed_names),
                  tuple(scope.get(n) for n in cs.ro_names),
                  tuple(scope.get(n) for n in cs.rw_names),
                  np.uint32(0)).compile().as_text()
row = CostLedger("ci").row("mnist_dp2_rs")
row.set_prediction(exe.cost_report(nominal_batch=16))
row.set_census(collective_census(hlo), 2, min_bytes=8)
chk = row.check_wire_bytes_exact()
assert chk["ok"], chk                     # predicted == census, exactly

from paddle_tpu.serving_engine import (ContinuousBatchingEngine,
                                       EngineClient, EngineServer,
                                       scrape_healthz, scrape_metrics)
eng = ContinuousBatchingEngine(n_slots=2, vocab=100, max_len=16,
                               d_model=32, d_inner=64, num_heads=4,
                               num_layers=2)
with EngineServer(eng) as srv:
    host, port = srv.address
    with EngineClient(host, port) as c:
        c.send_gen([3], max_new=2, request_id="ci-req")
        c.recv_done()
    text = scrape_metrics(*srv.metrics_address)
    health = scrape_healthz(*srv.metrics_address)
assert "ptpu_engine_tokens_total 2" in text, text[:400]
assert "ptpu_engine_tick_latency_seconds_count" in text
# r16: the per-request latency decomposition series are on the scrape,
# for all four phases, and one scrape carries the checkpoint + training
# series too (unified registries)
for phase in ("queue_wait", "prefill", "decode", "transport"):
    assert f'ptpu_request_latency_seconds_count{{phase="{phase}"}}' \
        in text, phase
assert "ptpu_request_e2e_seconds_count" in text
assert "ptpu_ckpt_saves_total" in text and "ptpu_train_steps_total" in text
# r17: ONE scrape also carries the memory board + the MFU sensor
for series in ("ptpu_mfu", "ptpu_memory_device_state_bytes",
               "ptpu_memory_kv_cache_bytes",
               "ptpu_memory_watermark_bytes"):
    assert series in text, series
# r16: /healthz is live on the same listener
assert health["status"] == "serving", health
assert health["engine"]["last_tick_age_s"] is not None
assert health["checkpoints"]["pending_async"] == 0
# r17: /healthz embeds the same memory board the dossiers carry
assert health["memory"]["kv_cache_bytes"]["current"] > 0, health
print("observability smoke OK")
PY

echo "== memory-observability smoke (census + ledger identity + MFU) =="
# the r17 memory sensor end to end (docs/observability.md): a traced
# mnist dp2 step must reconcile its measured memory census against
# costs.predict's per-device categories under the accounting identity
# (state/feed categories EXACT, unattributed residual <= 10% of the
# measured peak), stamp the ptpu_memory_* watermarks + ptpu_mfu, and
# emit memory COUNTER events into the Chrome trace export.
XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
python - <<'PY'
import json, numpy as np, jax
import paddle_tpu as pt
from paddle_tpu import layers
from paddle_tpu.observability import memory as obs_memory
from paddle_tpu.observability import metrics as obs_metrics
from paddle_tpu.observability import tracing
from paddle_tpu.observability.ledger import CostLedger
from paddle_tpu.parallel import ParallelExecutor
from paddle_tpu.parallel.mesh import DeviceMesh
from paddle_tpu.parallel.strategy import BuildStrategy, ReduceStrategy

pt.reset_default_programs(); pt.reset_global_scope()
with pt.core.unique_name.guard():
    x = layers.data("x", shape=[64])
    label = layers.data("label", shape=[1], dtype="int64")
    h = layers.fc(x, size=128, act="relu")
    loss = layers.mean(layers.softmax_with_cross_entropy(
        layers.fc(h, size=10), label))
    pt.optimizer.MomentumOptimizer(0.1, momentum=0.9).minimize(loss)
bst = BuildStrategy(); bst.reduce_strategy = ReduceStrategy.ReduceScatter
exe = ParallelExecutor(loss_name=loss.name, build_strategy=bst,
                       mesh=DeviceMesh(jax.devices()[:2], {"dp": 2}))
pt.Executor().run(pt.default_startup_program())
rng = np.random.RandomState(0)
feed = {"x": rng.rand(16, 64).astype("float32"),
        "label": rng.randint(0, 10, (16, 1)).astype("int64")}
for _ in range(3):   # traced steps (first is the MFU warm-up window)
    exe.run(feed=feed, fetch_list=[loss])

row = CostLedger("ci").row("mnist_dp2_mem")
row.set_prediction(exe.cost_report(nominal_batch=16))
row.set_memory_census(exe.memory_census(feed=feed))
rec = row.check_memory_identity()
assert row.ok, [c for c in row.checks if not c["ok"]]

text = obs_metrics.default_registry().expose()
assert "ptpu_memory_device_state_bytes" in text
assert "ptpu_memory_executor_temp_bytes" in text
mfu = [l for l in text.splitlines() if l.startswith("ptpu_mfu ")][0]
assert float(mfu.split()[-1]) > 0, mfu

tracing.export_chrome_trace("/tmp/ptpu_mem_trace_ci.json")
evs = json.load(open("/tmp/ptpu_mem_trace_ci.json"))["traceEvents"]
counters = {e["name"] for e in evs if e.get("ph") == "C"}
assert any(n.startswith("memory/") for n in counters), counters
print("memory-observability smoke OK:", json.dumps(rec["buckets"]))
PY
rm -f /tmp/ptpu_mem_trace_ci.json

echo "== memory-plan smoke (planner + detectors + measured reduction) =="
# the r18 static memory planner end to end (docs/static_analysis.md):
# (1) plan mnist dp2 through BuildStrategy.memory_plan — the sanitized
#     memory_plan_pass apply must stay lint-clean (the r13 buffer-reuse
#     detectors are the soundness gate) and the r17 ledger identity must
#     still hold on the planned cell; the mnist plan is a no-op by
#     SEARCH (nothing to free on the mlp) and its census must not
#     regress;
# (2) the activation-heavy transformer cell: the searched remat plan's
#     memory_census peak must land STRICTLY below the unplanned twin.
XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
    python - <<'PY'
import numpy as np, jax
import paddle_tpu as pt
from paddle_tpu.core import flags as _flags
from paddle_tpu.framework import analysis, costs as _costs
from paddle_tpu.framework.passes import get_pass
from paddle_tpu.observability.ledger import CostLedger
from paddle_tpu.parallel import ParallelExecutor
from paddle_tpu.parallel.mesh import DeviceMesh
from paddle_tpu.parallel.strategy import BuildStrategy, ReduceStrategy
_flags.set_flag("use_bf16_matmul", False)
led = CostLedger("ci-memplan")

# (1) mnist dp2 behind BuildStrategy.memory_plan
rng = np.random.RandomState(7)
from paddle_tpu import layers
x = layers.data("x", shape=[64]); label = layers.data("label", shape=[1], dtype="int64")
h = layers.fc(x, size=128, act="relu")
loss = layers.mean(layers.softmax_with_cross_entropy(layers.fc(h, size=10), label))
pt.optimizer.MomentumOptimizer(0.1, momentum=0.9).minimize(loss)
bst = BuildStrategy(); bst.reduce_strategy = ReduceStrategy.ReduceScatter
bst.memory_plan = True; bst.memory_plan_time_budget_s = 1.0
exe = ParallelExecutor(loss_name=loss.name, build_strategy=bst,
                       mesh=DeviceMesh(jax.devices()[:2], {"dp": 2}))
pt.Executor().run(pt.default_startup_program())
feed = {"x": rng.rand(16, 64).astype("float32"),
        "label": rng.randint(0, 10, (16, 1)).astype("int64")}
jax.block_until_ready(exe.run(feed=feed, fetch_list=[loss], return_numpy=False))
planned = exe.prepare_program()
assert getattr(planned, "_memory_plan_applied", False)
errs = [d for d in analysis.verify_program(planned) if d.severity == "error"]
assert not errs, errs
row = led.row("mnist_dp2_planned")
row.set_prediction(exe.cost_report(nominal_batch=16))
row.set_memory_census(exe.memory_census(feed=feed))
rec = row.check_memory_identity(residual_frac=0.10)
assert row.ok, [c for c in row.checks if not c["ok"]]

# (2) transformer: planned census peak strictly below unplanned
def build():
    pt.reset_default_programs(); pt.reset_global_scope()
    with pt.core.unique_name.guard():
        from paddle_tpu.models import transformer
        loss, _ = transformer.transformer_lm(
            vocab=128, max_len=32, d_model=64, d_inner=128, num_heads=4,
            num_layers=2, dropout=0.0, mean_loss=True)
        pt.optimizer.AdamOptimizer(1e-3).minimize(loss)
    r = np.random.RandomState(7)
    feed = {"tokens": r.randint(0, 128, (32, 32)).astype("int64"),
            "tokens@SEQLEN": np.full((32,), 32, "int32"),
            "targets": r.randint(0, 128, (32, 32)).astype("int64")}
    return loss, feed

def peak(prog, loss, feed):
    e = pt.Executor()
    pt.Executor().run(pt.default_startup_program())
    jax.block_until_ready(e.run(program=prog, feed=feed,
                                fetch_list=[loss], return_numpy=False))
    c = e.memory_census(feed=feed, program=prog)
    return c["peak_bytes"], c

loss, feed = build()
p_base, _ = peak(pt.default_main_program(), loss, feed)
loss, feed = build()
prog = get_pass("memory_plan_pass", nominal_batch=32,
                time_budget_s=1.0)(pt.default_main_program())
assert not [d for d in analysis.verify_program(prog)
            if d.severity == "error"]
p_plan, census = peak(prog, loss, feed)
assert p_plan < p_base, (p_plan, p_base)
prow = led.row("transformer_planned")
prow.set_prediction(_costs.predict(prog, dp=1, nominal_batch=32))
prow.set_memory_census(census)
prow.check_memory_identity(residual_frac=0.10)
assert prow.ok, [c for c in prow.checks if not c["ok"]]
import json
print("memory-plan smoke OK:", json.dumps({
    "transformer_peak_unplanned": round(p_base),
    "transformer_peak_planned": round(p_plan),
    "reduction": round(1 - p_plan / p_base, 4)}))
PY

echo "== auto-parallel smoke (planner choice: feasible + lint-clean + exact wire) =="
# the r19 auto-parallel planner end to end (docs/auto_parallel.md): plan
# mnist over a 4-device mesh; the chosen strategy must (1) be in the
# feasible set per the SAME compile-free gates the executor raises
# (costs.strategy_is_feasible), (2) leave the rewritten program
# analyzer-clean, and (3) balance its predicted per-step wire bytes
# against the executed HLO census EXACTLY (the r12 ledger discipline on
# a strategy the framework picked for itself). Then the lint surface:
# a feasible --strategy lints clean, an infeasible one exits 2 naming
# the reason.
XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
python - <<'PY'
import numpy as np, jax
import jax.numpy as jnp
import paddle_tpu as pt
from paddle_tpu import layers
from paddle_tpu.framework import analysis, auto_parallel, costs
from paddle_tpu.observability.ledger import CostLedger
from paddle_tpu.parallel import ParallelExecutor
from paddle_tpu.parallel.mesh import DeviceMesh

pt.reset_default_programs(); pt.reset_global_scope()
with pt.core.unique_name.guard():
    x = layers.data("x", shape=[64])
    label = layers.data("label", shape=[1], dtype="int64")
    h = layers.fc(x, size=128, act="relu")
    loss = layers.mean(layers.softmax_with_cross_entropy(
        layers.fc(h, size=10), label))
    pt.optimizer.MomentumOptimizer(0.1, momentum=0.9).minimize(loss)
prog = pt.default_main_program()
result = auto_parallel.plan(prog, 4, nominal_batch=16)
feas = costs.strategy_is_feasible(prog, result.strategy,
                                  mesh_axes=result.mesh_axes,
                                  nominal_batch=16)
assert feas.ok, feas.reasons                       # (1) feasible
errs = [d for d in analysis.verify_program(feas.program)
        if d.severity == "error"]
assert not errs, errs                              # (2) lint-clean

exe = ParallelExecutor(loss_name=loss.name, build_strategy=result.strategy,
                       mesh=DeviceMesh(jax.devices()[:4],
                                       result.mesh_axes))
pt.Executor().run(pt.default_startup_program())
rng = np.random.RandomState(0)
feed = {"x": rng.rand(16, 64).astype("float32"),
        "label": rng.randint(0, 10, (16, 1)).astype("int64")}
l0 = float(exe.run(feed=feed, fetch_list=[loss])[0])
l1 = float(exe.run(feed=feed, fetch_list=[loss])[0])
assert l1 < l0, (l0, l1)                           # it actually trains
cs = list(exe._cache.values())[-1]
scope = pt.global_scope()
hlo = cs.fn.lower(tuple(jnp.asarray(feed[n]) for n in cs.feed_names),
                  tuple(scope.get(n) for n in cs.ro_names),
                  tuple(scope.get(n) for n in cs.rw_names),
                  np.uint32(0)).compile().as_text()
row = CostLedger("ci").row("auto_parallel_choice")
row.set_prediction(exe.cost_report(nominal_batch=16))
row.set_census(costs.collective_census(hlo),
               exe.mesh.axis_size("dp"), min_bytes=8)
chk = row.check_wire_bytes_exact()
assert chk["ok"], chk                              # (3) exact balance
import json
print("auto-parallel smoke OK:", json.dumps({
    "chosen": result.point.describe(),
    "predicted_wire": chk["predicted"], "census": chk["measured"]}))
PY
JAX_PLATFORMS=cpu python tools/lint_program.py --model mnist \
    --strategy '{"dp": 2, "pp": 2, "microbatches": 4, "reduce": "reduce_scatter"}'
if JAX_PLATFORMS=cpu python tools/lint_program.py --model mnist \
    --strategy '{"dp": 2, "tp": 2, "reduce": "reduce_scatter"}'; then
    echo "lint accepted an INFEASIBLE strategy"; exit 1
fi

echo "== flight-recorder smoke (SIGKILL mid-barrier -> dossier + post-mortem) =="
# the distributed flight recorder end to end (observability/
# flight_recorder.py, docs/fault_tolerance.md): a 4-rank world-atomic
# child is SIGKILLed at a NON-CHIEF rank's ack phase via the existing
# PTPU_FAULT_INJECT crash_rank hook; the beacons written before the kill
# must name exactly that rank and phase, and the post-mortem synthesis
# must commit the verdict. (The merged-timeline path, trace_merge.py, is
# pinned by tests/test_observability.py.)
rm -rf /tmp/ptpu_flightrec_ci
XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
PTPU_FAULT_INJECT=crash_rank:2@ack \
    python tools/recovery_smoke.py --world-atomic-child --world 4 \
    --root /tmp/ptpu_flightrec_ci && { \
    echo "child survived a crash_rank directive"; exit 1; } || true
JAX_PLATFORMS=cpu python - <<'PY'
import json
from paddle_tpu.observability import flight_recorder as fr
d = "/tmp/ptpu_flightrec_ci/dossiers"
verdict = fr.analyze(d)
assert verdict["dead_rank"] == 2, verdict
assert verdict["dead_phase"] == "ack", verdict
assert verdict["cause"] == "crash_rank SIGKILL", verdict
pm = fr.write_post_mortem(d, incarnation=1)
doc = json.load(open(pm))
assert doc["dead_rank"] == 2 and doc["dead_phase"] == "ack"
print(f"flight-recorder smoke OK: {pm} names rank 2 @ ack")
PY
rm -rf /tmp/ptpu_flightrec_ci

echo "== recovery smoke (kill -9 mid-run, dp resize, fixed-seed parity) =="
# the elastic fault-tolerance runtime end to end (parallel/elastic.py,
# docs/fault_tolerance.md): a supervised child SIGKILLs itself mid-run and
# resumes BITWISE-exact from the latest committed snapshot; a second crashed
# run restarts with dp resized 2 -> 4 and matches the uninterrupted
# fixed-seed loss trajectory within the fp32 parity band; a kill DURING a
# snapshot write leaves only an uncommitted dir that restore skips. Then
# lint the restored program's sharded-state placement against the resized
# snapshot (exit 1 on any restore-* or verify_program diagnostic).
XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
    python tools/recovery_smoke.py --keep_root /tmp/ptpu_recovery_ci
JAX_PLATFORMS=cpu python tools/lint_program.py --model mnist \
    --optimizer momentum --dp 4 --restore_dir /tmp/ptpu_recovery_ci/b
rm -rf /tmp/ptpu_recovery_ci

echo "== multi-rank recovery (chief-commits barrier, kill -9 mid-barrier) =="
# the chief-commits multi-writer protocol end to end (parallel/elastic.py +
# parallel/process_world.py): training dp=4 snapshots through a 4-rank
# simulated world; a non-chief rank is SIGKILLed mid-barrier (nothing may
# commit) and the chief is SIGKILLed mid-COMMIT (a VISIBLE but uncommitted
# snapshot dir remains); both restarts resume from the last committed
# barrier snapshot with BITWISE fixed-seed loss parity vs the uninterrupted
# run. Then lint_program --restore_dir must ACCEPT every committed barrier
# snapshot (exit 0) and REJECT the uncommitted leftover (exit 1).
XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
    python tools/recovery_smoke.py --world 4 \
    --keep_root /tmp/ptpu_recovery_world_ci
JAX_PLATFORMS=cpu python tools/lint_program.py --model mnist \
    --optimizer momentum --dp 4 \
    --restore_dir /tmp/ptpu_recovery_world_ci/d
JAX_PLATFORMS=cpu python tools/lint_program.py --model mnist \
    --optimizer momentum --dp 4 \
    --restore_dir /tmp/ptpu_recovery_world_ci/e
uncommitted=$(ls -d /tmp/ptpu_recovery_world_ci/e/snapshot-* | while read d; do \
    [ ! -f "$d/COMMIT" ] && echo "$d"; done | head -1)
test -n "$uncommitted"
if JAX_PLATFORMS=cpu python tools/lint_program.py --model mnist \
    --optimizer momentum --restore_dir "$uncommitted"; then
    echo "lint accepted an UNCOMMITTED snapshot dir"; exit 1
fi
rm -rf /tmp/ptpu_recovery_world_ci

echo "== serving-engine smoke =="
# continuous-batching engine end to end: submit through the RPC server,
# decode over the slot cache, check a mid-batch join completes (fast:
# tiny LM, ~15 s including compile)
JAX_PLATFORMS=cpu python - <<'PY'
from paddle_tpu.serving_engine import (ContinuousBatchingEngine,
                                       EngineClient, EngineServer)
eng = ContinuousBatchingEngine(n_slots=2, vocab=100, max_len=16,
                               d_model=32, d_inner=64, num_heads=4,
                               num_layers=2)
with EngineServer(eng) as srv:
    host, port = srv.address
    with EngineClient(host, port) as c:
        long_tag = c.send_gen([3], max_new=8)
        short_tag = c.send_gen([5], max_new=2)      # joins mid-batch
        done = dict((t, toks) for t, toks, _ in
                    (c.recv_done(), c.recv_done()))
        assert len(done[long_tag]) == 8 and len(done[short_tag]) == 2
print("serving-engine smoke OK")
PY

echo "== paged-serving smoke (r20: block-table KV + prefix sharing) =="
# slot vs paged decode identity on a shared-prefix mix (same scope =
# same weights), prefix-cache hits on the second wave, and the census
# used-vs-reserved reconciliation (used + free == reserved, exactly)
JAX_PLATFORMS=cpu python - <<'PY'
import paddle_tpu as pt
from paddle_tpu.observability.memory import watermark_board
from paddle_tpu.serving import ContinuousBatchingEngine, PagedKVEngine
DIMS = dict(vocab=100, max_len=16, d_model=32, d_inner=64, num_heads=4,
            num_layers=2)
scope = pt.global_scope()
slot = ContinuousBatchingEngine(n_slots=3, scope=scope, **DIMS)
paged = PagedKVEngine(n_slots=3, block_size=4, scope=scope, **DIMS)
pre = [2, 7, 1, 9, 4, 8, 5, 6]
waves = [[pre + [3]], [pre + [11], pre + [12, 13], [6, 5, 4]]]
for wave in waves:
    a = [slot.submit(p, max_new=5) for p in wave]
    slot.run_until_idle()
    b = [paged.submit(p, max_new=5) for p in wave]
    paged.run_until_idle()
    assert [r.tokens for r in a] == [r.tokens for r in b], \
        "paged decode diverged from slot engine"
assert paged.pager.prefix_hits >= 2, paged.pager.stats()
pool = paged.pager.pool
pool.check()
assert pool.n_used + pool.n_free == paged.n_blocks - 1
paged._stamp_kv_watermarks({})
board = watermark_board()
per_block = paged._kv_bytes_static / paged.n_blocks
assert board["kv_cache_bytes"]["current"] == paged._kv_bytes_static
assert board["kv_cache_used_bytes"]["current"] == pool.n_used * per_block
print("paged-serving smoke OK")
PY

echo "== quantized-serving smoke (r21: weight-only int8 + zero-dispatch tick) =="
# quantize an mnist-scale LM tick in place: census ledger identity must
# be EXACT (predicted params_quantized == measured, byte for byte),
# int8 greedy decode must be token-identical to f32 on the shared
# weights at this vocab, and the steady-state tick must be genuinely
# zero-dispatch: the engine emits `dispatch` spans and the bound tick's
# per-tick Python allocation stays under a pinned budget
JAX_PLATFORMS=cpu python - <<'PY'
import tracemalloc
import numpy as np
import paddle_tpu as pt
from paddle_tpu.core import flags
from paddle_tpu.framework.costs import memory_categories
from paddle_tpu.observability import tracing
from paddle_tpu.observability.memory import state_census
from paddle_tpu.serving import ContinuousBatchingEngine

DIMS = dict(vocab=50, max_len=16, d_model=32, d_inner=64, num_heads=4,
            num_layers=2)
scope = pt.global_scope()
f32 = ContinuousBatchingEngine(n_slots=3, scope=scope, **DIMS)
q8 = ContinuousBatchingEngine(n_slots=3, scope=scope, quant="int8",
                              **DIMS)
assert q8.quant == "int8" and q8.quant_freed_bytes > 0
assert f32.params_bytes_f32 / q8._param_bytes() >= 2.0, \
    (f32.params_bytes_f32, q8._param_bytes())

# ledger identity: predicted category == measured census, exactly
pred = memory_categories(q8._program)
names = [n for n, v in q8._program.current_block().vars.items()
         if v.persistable]
meas = state_census(scope, q8._program, names)["categories"]
assert int(pred["params_quantized"]) == int(meas["params_quantized"]) \
    > 0, (pred, meas)

# decode smoke: int8 tokens == f32 tokens on the shared weights
prompts = [[7], [3, 9], [11, 2, 5]]
a = [f32.submit(p, max_new=5) for p in prompts]
f32.run_until_idle()
flags.set_flag("trace", True)
try:
    mark = tracing.mark()
    b = [q8.submit(p, max_new=5) for p in prompts]
    q8.run_until_idle()
    spans = [s for s in tracing.spans_since(mark)
             if (s.kind, s.name) == ("dispatch", "engine/dispatch")]
finally:
    flags.set_flag("trace", False)
assert [r.tokens for r in a] == [r.tokens for r in b], \
    "int8 greedy decode diverged from f32"
assert spans and q8._m_dispatch.count > 0

# zero-dispatch: the bound tick allocates (almost) nothing per tick
step = q8._step
step.run_bound()
tracemalloc.start()
s0 = tracemalloc.take_snapshot()
for _ in range(50):
    out = step.run_bound()
np.asarray(out[0])
s1 = tracemalloc.take_snapshot()
tracemalloc.stop()
per_tick = sum(max(d.size_diff, 0)
               for d in s1.compare_to(s0, "filename")) / 50
assert per_tick < 2048, f"bound tick allocates {per_tick:.0f} B/tick"
print(f"quantized-serving smoke OK ({per_tick:.0f} B/tick)")
PY

echo "== speculative-decoding smoke (r22: draft propose + one-forward verify) =="
# γ=4 greedy speculation on the paged engine: decode must be
# TOKEN-IDENTICAL to the target-only twin on shared weights (the accept
# rule is structural), the acceptance gauge must be live on the engine
# registry, and the block pool must reconcile with per-round checks on
# (rollbacks included).
JAX_PLATFORMS=cpu PTPU_SPEC_POOL_CHECK=1 python - <<'PY'
import numpy as np
import paddle_tpu as pt
from paddle_tpu.serving import PagedKVEngine, SpecConfig

DIMS = dict(vocab=100, max_len=16, d_model=32, d_inner=64, num_heads=4,
            num_layers=2)
scope = pt.global_scope()
base = PagedKVEngine(n_slots=3, block_size=4, scope=scope, **DIMS)
spec = PagedKVEngine(n_slots=3, block_size=4, scope=scope,
                     speculative=SpecConfig(gamma=4, draft="int8"), **DIMS)
rng = np.random.RandomState(0)
prompts = [rng.randint(1, 100, size=rng.randint(2, 6)).tolist()
           for _ in range(5)]
a = [base.submit(p, max_new=6) for p in prompts]
base.run_until_idle()
b = [spec.submit(p, max_new=6) for p in prompts]
spec.run_until_idle()
assert [r.tokens for r in a] == [r.tokens for r in b], \
    "speculative decode diverged from the target-only twin"
s = spec.spec.stats()
assert s["rounds"] > 0 and 0.0 <= s["acceptance_rate"] <= 1.0
assert spec.target_forwards < base.target_forwards, \
    (spec.target_forwards, base.target_forwards)
text = spec.metrics_registry.expose()
for series in ("ptpu_engine_spec_acceptance_rate",
               "ptpu_engine_spec_tokens_per_target_forward",
               "ptpu_engine_spec_rolled_back_blocks"):
    assert series in text, series
pool = spec.pager.pool
pool.check()
assert pool.n_used + pool.n_free == pool.n_blocks - 1
print(f"speculative smoke OK (acceptance={s['acceptance_rate']:.3f}, "
      f"{spec.tokens_out / spec.target_forwards:.2f} tok/target-fwd "
      f"vs 1.0 plain)")
PY

echo "== two-tier host-offload smoke (r23: spill + prefetch + exact census) =="
# a paged engine at a deliberately tight device pool with the host tier
# on: decode must be TOKEN-IDENTICAL to an unconstrained-pool twin,
# real spills must have happened, the wire-byte census must reconcile
# EXACTLY (eviction/reload counters x per-block bytes == the transfer
# stream's measured bytes), and the two-pool accounting identity must
# hold. The offload schedule lint must pass on the shipped prefetch
# policy.
JAX_PLATFORMS=cpu python - <<'PY'
import numpy as np
import paddle_tpu as pt
from paddle_tpu.framework import offload as ofl
from paddle_tpu.serving import HostTierConfig, PagedKVEngine

DIMS = dict(vocab=100, max_len=16, d_model=32, d_inner=64, num_heads=4,
            num_layers=2)
scope = pt.global_scope()
rng = np.random.RandomState(0)
prompts = [rng.randint(1, 100, size=rng.randint(3, 9)).tolist()
           for _ in range(8)]
base = PagedKVEngine(n_slots=6, block_size=4, scope=scope, **DIMS)
a = [base.submit(p, max_new=6) for p in prompts]
base.run_until_idle()
two = PagedKVEngine(n_slots=6, block_size=4, n_blocks=9, scope=scope,
                    host_tier=HostTierConfig(host_blocks=32,
                                             prefetch_distance=2,
                                             rotate_quantum=4), **DIMS)
b = [two.submit(p, max_new=6) for p in prompts]
two.run_until_idle()
assert [r.tokens for r in a] == [r.tokens for r in b], \
    "two-tier decode diverged from the unconstrained twin"
assert two.pager.host_evictions > 0, "no spill pressure — smoke is dead"
per = two._ht_per_block_bytes
assert two.ht_d2h_bytes == two.pager.host_evictions * per, \
    (two.ht_d2h_bytes, two.pager.host_evictions, per)
assert two.ht_h2d_bytes == two.pager.host_reloads * per, \
    (two.ht_h2d_bytes, two.pager.host_reloads, per)
two.pager.check_two_tier()
events = ofl.kv_prefetch_events({"r%d" % t: t for t in range(2, 6)}, 2)
assert ofl.check_schedule(events) == [], "shipped prefetch policy lints dirty"
print(f"offload smoke OK ({two.pager.host_evictions} spills, "
      f"{two.ht_d2h_bytes} B d2h == census, hit_rate="
      f"{two.pager.stats()['host_tier']['prefetch_hit_rate']:.2f})")
PY

echo "== lint_program --offload (named diagnostic: offload-use-before-arrival) =="
JAX_PLATFORMS=cpu python tools/lint_program.py --model mnist --offload > /dev/null
JAX_PLATFORMS=cpu python tools/lint_program.py \
    --model transformer_lm_paged_decode_tick --offload > /dev/null
echo "lint --offload OK"

echo "== serving ownership verifier (r24: model check + seeded mutation + lint contract) =="
# the block-lifetime model checker must exhaustively clear the shipped
# pager protocol at its default scope (the state count is the proof of
# coverage), and a seeded protocol mutation must be caught BY NAME —
# both halves of the static_analysis.md §5 contract
JAX_PLATFORMS=cpu python - <<'PY'
from paddle_tpu.framework.ownership import ModelChecker, MUTATIONS

res = ModelChecker().run()
assert res.ok, res.violations
assert res.states_explored == 238 and res.transitions == 686, \
    (res.states_explored, res.transitions)
mut = ModelChecker(mutation="leaked-release").run()
assert not mut.ok and MUTATIONS["leaked-release"] in mut.codes(), \
    mut.codes()
print(f"ownership model check OK ({res.states_explored} states / "
      f"{res.transitions} transitions clean; seeded leaked-release "
      f"caught as {MUTATIONS['leaked-release']})")
PY

# lint --serving: clean on the shipped paged tick builder (exit 0, the
# report's serving section populated) and the --json exit-code contract
JAX_PLATFORMS=cpu python tools/lint_program.py \
    --model transformer_lm_paged_decode_tick --serving > /dev/null
JAX_PLATFORMS=cpu python tools/lint_program.py \
    --model transformer_lm_paged_decode_tick --serving --json \
    | python -c '
import json, sys
reports = json.load(sys.stdin)
sv = reports[0]["serving"]
mc = sv["model_check"]
assert mc["violations"] == 0 and mc["states_explored"] == 238, mc
assert sv["violations"] == 0, sv["violations"]
print("lint --serving OK (json contract, model check "
      "%d states)" % mc["states_explored"])
'

echo "CI OK"
