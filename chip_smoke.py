"""chip_smoke.py — the quickest proof that the system still starts on the chip.

    python3 chip_smoke.py        # on a machine with a TPU; one process

Drives the main path once through the entry points a user would call, at the
full width of the largest dense model the repo builds (transformer LM,
12 layers x 1024, vocab 32000): trained for a few steps by `Executor`, then
served by `PagedKVEngine` behind `EngineServer` from the SAME scope. Then a
ResNet-50 train step, every Pallas kernel the package selects by default on a
TPU against its own composite, and — with four or more devices — the same LM
under `ParallelExecutor` on a dp2 x tp2 mesh plus ring attention on sp4.

Each phase prints one JSON line. The last line of stdout is
`{"ok": true, "device": {...}}` and the exit code is 0 only when every phase
passed. Without an accelerator (or on a device the repo has no peaks for) the
`device` phase fails: there is no size, flag or environment variable that
lets this script pass on a CPU. Weights are random, from fixed seeds; nothing
is read from disk or network; no child process is started (a chip belongs to
one process).

The phase bodies are plain functions of their sizes so that
tests/test_chip_smoke.py can run them tiny on the virtual CPU mesh.
"""

from __future__ import annotations

import gc
import json
import os
import re
import sys
import threading
import time
import traceback

import numpy as np

# whole-run deadline: the contract is exit within 1200 s, compilation included
DEADLINE_S = 1140.0

# Tolerances, as a fraction of max|reference|.
# bf16 kernels: inputs are bf16 (8 mantissa bits, ulp 2^-8 relative); the
# kernel rounds P to bf16 before the PV matmul and the output to bf16, the
# f32 reference does neither — a few bf16 ulps at the output's scale.
TOL_BF16 = 2.0 ** -6
# f32 kernels on the VPU (decode attention): same math as the composite in a
# different reduction order.
TOL_F32 = 1e-5
# f32 recurrent cells: kernel and composite both feed the MXU XLA's default
# f32 precision (one bf16 pass), and a rounding difference in one step's
# recurrent matmul carries through the remaining T-1 steps.
TOL_F32_MXU = 2.0 ** -9

_PHASE = ["start"]


class SmokeFailure(Exception):
    """A phase's check failed."""


def _check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def _device_facts():
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "device_kind": devs[0].device_kind,
            "n_devices": len(devs)}


def _emit(phase, facts):
    line = {"phase": phase}
    line.update(_device_facts())
    line.update(facts)
    print(json.dumps(line), flush=True)


def _rel_err(got, ref):
    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    _check(got.shape == ref.shape, f"shape {got.shape} != {ref.shape}")
    _check(np.isfinite(got).all(), "non-finite values")
    return float(np.max(np.abs(got - ref)) / max(np.max(np.abs(ref)), 1e-30))


# how a Mosaic (Pallas TPU) kernel appears in optimized HLO text
_MOSAIC_CALL = 'custom_call_target="tpu_custom_call"'


def _n_custom_calls(hlo: str) -> int:
    return hlo.count(_MOSAIC_CALL)


def _executables():
    """How many executables this process has compiled or loaded so far, by
    the program's own record (`tracing.compile_spans()`: each first run's
    span and the `jax/unscoped` records; docs/observability.md)."""
    from paddle_tpu.observability import tracing
    return sum(s.attrs.get("executables", 0) for s in tracing.compile_spans())


def _free_device_memory():
    import paddle_tpu as pt
    pt.reset_default_programs()
    pt.reset_global_scope()
    gc.collect()


def _lm_feed(batch, seq_len, vocab, seed=0):
    rng = np.random.RandomState(seed)
    toks = rng.randint(0, vocab, (batch, seq_len + 1)).astype("int64")
    return {"tokens": toks[:, :-1].copy(),
            "tokens@SEQLEN": np.full((batch,), seq_len, "int32"),
            "targets": toks[:, 1:].copy()}


def _build_lm_train(vocab, seq_len, d_model, d_inner, num_heads, num_layers,
                    attn_backend=None):
    """layers.* -> optimizer.minimize; returns the loss var. `attn_backend`
    pins the fused_attention ops' backend (the CPU tests run the kernels
    through the Pallas interpreter); None leaves the default selection."""
    import paddle_tpu as pt
    from paddle_tpu.models import transformer

    _free_device_memory()
    with pt.core.unique_name.guard():
        loss, _ = transformer.transformer_lm(
            vocab=vocab, max_len=seq_len, d_model=d_model, d_inner=d_inner,
            num_heads=num_heads, num_layers=num_layers, dropout=0.0)
        pt.optimizer.AdamOptimizer(learning_rate=1e-4).minimize(loss)
    if attn_backend is not None:
        for op in pt.default_main_program().global_block().ops:
            if op.type == "fused_attention":
                op.attrs["backend"] = attn_backend
    return loss


def _run_steps(run, steps, decreasing=True):
    """run() -> loss; first call timed as compile, the rest as run."""
    t0 = time.time()
    losses = [float(run())]
    compile_s = time.time() - t0
    t0 = time.time()
    losses += [float(run()) for _ in range(steps - 1)]
    run_s = time.time() - t0
    _check(all(np.isfinite(losses)), f"non-finite loss: {losses}")
    _check(not decreasing or all(b < a for a, b in zip(losses, losses[1:])),
           f"loss not decreasing on a fixed batch: {losses}")
    return losses, compile_s, run_s


# ---------------------------------------------------------------- phases


def phase_device():
    """A TPU of a kind the repo has peaks for — or the run ends here."""
    import jax
    from paddle_tpu.framework.costs import device_peaks

    facts = _device_facts()
    _check(facts["platform"] == "tpu",
           f"no chip: JAX found platform {facts['platform']!r} "
           f"({facts['device_kind']}); chip_smoke.py needs a TPU and never "
           f"runs smaller on a CPU")
    peaks = device_peaks(facts["device_kind"])
    _check(peaks is not None,
           f"device_kind {facts['device_kind']!r} is not in the repo's "
           f"peaks table (paddle_tpu/framework/costs.py DEVICE_PEAKS)")
    from importlib import metadata
    return {"compile_s": 0.0, "run_s": 0.0,
            "peak_bf16_tflops": peaks["peak_flops"] / 1e12,
            "hbm_gbps": peaks["hbm_bps"] / 1e9,
            "jax": jax.__version__, "libtpu": metadata.version("libtpu")}


def phase_train_lm(vocab=32000, seq_len=1024, d_model=1024, d_inner=4096,
                   num_heads=16, num_layers=12, batch=8, steps=4,
                   flash_calls_per_layer=2):
    """Train the LM for a few steps on one fixed batch through Executor.run.
    Leaves the trained weights in the global scope for `phase_serve_lm`."""
    import paddle_tpu as pt

    loss = _build_lm_train(vocab, seq_len, d_model, d_inner, num_heads,
                           num_layers)
    exe = pt.Executor()
    exe.run(pt.default_startup_program())
    feed = _lm_feed(batch, seq_len, vocab)
    losses, compile_s, run_s = _run_steps(
        lambda: exe.run(feed=feed, fetch_list=[loss])[0], steps)
    # the step's first run is on record as what it was: a compile or a load
    from paddle_tpu.observability import tracing
    first = [s.attrs for s in tracing.compile_spans()
             if s.name == "executor/compile_or_load"
             and s.attrs["program"] == "train_step"]
    _check(len(first) == 1 and first[0]["executables"] >= 1,
           f"the training step's first run left {first} as its "
           f"executor/compile_or_load spans; expected one, with an "
           f"executable compiled or loaded")
    t0 = time.time()
    n_calls = _n_custom_calls(exe.compiled_hlo(feed=feed, fetch_list=[loss]))
    hlo_s = time.time() - t0
    # flash forward + the one-pass backward kernel, per layer: the step ran
    # the Mosaic kernels, not the composite
    _check(n_calls == flash_calls_per_layer * num_layers,
           f"{n_calls} tpu_custom_calls in the compiled train step, expected "
           f"{flash_calls_per_layer} x {num_layers} layers")
    return {"compile_s": round(compile_s, 2), "run_s": round(run_s, 2),
            "hlo_s": round(hlo_s, 2), "steps": steps,
            "losses": [round(x, 4) for x in losses],
            "first_run": {k: round(first[0][k], 3) for k in (
                "trace_s", "lower_s", "compile_s", "cache_load_s")},
            "tpu_custom_calls": n_calls, "batch_tokens": batch * seq_len}


def phase_serve_lm(scope, vocab=32000, max_len=1024, d_model=1024,
                   d_inner=4096, num_heads=16, num_layers=12, n_slots=16,
                   n_requests=8, min_prompt=16, max_prompt=64, max_new=32,
                   deadline_s=420.0, expect_lowering="kernel"):
    """Serve the weights `scope` holds (shared by name with the train graph)
    through PagedKVEngine behind EngineServer, an EngineClient in a thread of
    this process. `expect_lowering` is what the tick's cache read must
    compile to: on a chip the paged kernel, one Mosaic call a layer
    (fusion/paged_attention.py); "composite" is for a run off the chip."""
    from paddle_tpu.serving import EngineClient, EngineServer, PagedKVEngine

    trained = {n: scope.get(n) for n in ("tok_emb", "lm_head.w_0")
               if scope.has_var(n)}
    _check(len(trained) == 2, "the scope holds no trained LM weights")
    t0 = time.time()
    eng = PagedKVEngine(n_slots=n_slots, vocab=vocab, max_len=max_len,
                        d_model=d_model, d_inner=d_inner,
                        num_heads=num_heads, num_layers=num_layers,
                        scope=scope)
    _check(all(scope.get(n) is v for n, v in trained.items()),
           "the engine re-initialized weights the scope already held")
    _check(eng.stats()["prefill"] == "chunked",
           f"the engine consumes prompts {eng.stats()['prefill']!r}, not "
           f"in chunks through the mixed tick's lanes")
    built = _executables()

    rng = np.random.RandomState(1)
    prompts = [rng.randint(0, vocab, (int(n),)).tolist()
               for n in rng.randint(min_prompt, max_prompt + 1, n_requests)]
    result = {}

    def client(address):
        try:
            with EngineClient(*address) as c:
                c.generate([1], max_new=1)        # compiles the tick
                t1 = time.time()
                tags = [c.send_gen(p, max_new=max_new) for p in prompts]
                done = dict((tag, toks) for tag, toks, _ in
                            (c.recv_done() for _ in prompts))
                result["tokens"] = [done[t] for t in tags]
                hits = eng.pager.stats()["prefix_hits"]
                # the first prompt again, after its first answer completed
                result["repeat"] = c.generate(prompts[0], max_new=max_new)
                result["prefix_hits"] = (eng.pager.stats()["prefix_hits"]
                                         - hits)
                result["run_s"] = time.time() - t1
        except Exception as e:     # reported by the phase, which then fails
            result["error"] = f"{type(e).__name__}: {e}"

    with EngineServer(eng, host="127.0.0.1") as srv:
        th = threading.Thread(target=client, args=(srv.address,),
                              daemon=True)
        th.start()
        th.join(deadline_s)
        health = srv.health()
    _check("run_s" in result,
           f"client: {result.get('error', 'no answer')} after "
           f"{time.time() - t0:.0f} s of a {deadline_s:.0f} s deadline "
           f"(server health: status={health['status']}, "
           f"error={health['error']})")
    compile_s = time.time() - t0 - result["run_s"]
    _check(all(len(t) == max_new for t in result["tokens"]),
           f"token counts {[len(t) for t in result['tokens']]} != {max_new}")
    _check(result["repeat"] == result["tokens"][0],
           "the repeated prompt did not decode token-identically")
    _check(result["prefix_hits"] >= 1,
           "the repeated prompt did not hit the prefix cache")

    # serving compiled the engine's two tick programs (the mixed tick on the
    # first prompt, the decode tick on the first tick without one) and
    # nothing else: every later shape of traffic runs one of the two
    n_programs = _executables() - built
    _check(n_programs == 2,
           f"serving compiled {n_programs} programs; the engine has two "
           f"(the decode tick and the mixed tick)")
    stats = eng.stats()
    lowering = stats["paged_attention_lowering"]
    n_calls = _n_custom_calls(eng.tick_hlo())
    n_mixed = _n_custom_calls(eng.mixed_tick_hlo())
    want_calls = num_layers if expect_lowering == "kernel" else 0
    # the mixed tick: the decode rows' kernel and the lanes' chunk kernel
    _check((lowering, n_calls, n_mixed)
           == (expect_lowering, want_calls, 2 * want_calls),
           f"the engine reports its cache read as {lowering!r}, the "
           f"compiled tick holds {n_calls} tpu_custom_calls and the mixed "
           f"tick {n_mixed}; expected {expect_lowering!r} with "
           f"{want_calls} and {2 * want_calls}")
    # a launch of either tick hands the device ONE host array: the feeds
    # and the seed, packed (PreparedStep.bind), `tick_from_last` among them
    dispatch = dict(stats["dispatch"])
    late_reads = dispatch.pop("late_reads")
    run_ahead = dispatch.pop("run_ahead")
    copies_found = dispatch.pop("copies_found")
    host_args = {n: d["host_args"] for n, d in dispatch.items()}
    _check(host_args == {"main": 1, "mixed": 1},
           f"a launch hands over {host_args} host arrays; expected one for "
           f"the decode tick and one for the mixed tick")
    # most ticks left their ids on the device, where the next tick's decode
    # rows took them, and the host read them a launch late (engine.py
    # `_plain_tick`): the same requests through the same engine, every tick
    # read at once and the prompts prefilled anew, emit the same tokens
    _check(0 < late_reads < stats["ticks"],
           f"{late_reads} of {stats['ticks']} ticks were read a launch late")
    eng.pager.index.evict_all(eng.pager.pool)
    eng._late_ok = False
    eager = [eng.submit(p, max_new) for p in prompts]
    eng.run_until_idle()
    _check([r.tokens for r in eager] == result["tokens"],
           "the ticks read a launch late did not emit the eager order's "
           "tokens")
    _check(eng.stats()["dispatch"]["late_reads"] == late_reads,
           "an engine made to read every tick at once read one late")
    return {"compile_s": round(compile_s, 2),
            "run_s": round(result["run_s"], 2),
            "host_args": host_args, "late_reads": late_reads,
            "run_ahead": run_ahead, "copies_found": copies_found,
            "requests": n_requests + 1, "max_new": max_new,
            "prompt_lens": [len(p) for p in prompts],
            "ticks": stats["ticks"], "tokens_out": stats["tokens_out"],
            "prefix_hits": result["prefix_hits"],
            "paged_attention_lowering": lowering,
            "prefill": stats["prefill"], "programs_compiled": n_programs,
            "tpu_custom_calls": n_calls, "mixed_tpu_custom_calls": n_mixed,
            "block_size": eng.block_size, "n_blocks": eng.n_blocks}


def phase_serve_hybrid(vocab=65536, d_model=2048, d_inner=7168, num_heads=32,
                       num_kv_heads=8, d_expert=1792, n_experts=32, top_k=4,
                       kinds=("conv", "conv", "attention", "conv"),
                       n_slots=16, block_size=64, n_blocks=128, max_len=1024,
                       preamble=256, turns=(40, 150), max_new=24,
                       expect_lowering="kernel", decode_read=None):
    """A model with a kind a layer through the same PagedKVEngine: gated
    short convolutions with a per-request state beside grouped-query rotary
    attention over bfloat16 pools of the key/value heads, routed experts all
    held under a bias-corrected top-k, the embedding as the head, at the
    published widths of benchmark/configs/lfm2-8b-a1b.json and four layers.
    Requests that start from a shared preamble (K/V blocks AND the conv
    state's snapshot from the prefix cache) must emit the tokens an engine
    without prefix sharing emits, which prefills the preamble itself.
    Beside it the tick's grouped decode read alone, at the widths of
    benchmark/cells/lfm2-8b-a1b_serve_assistant.json (64 slots, a quarter of
    them live, a table of 48 blocks; `decode_read` overrides
    `_check_grouped_decode`'s arguments), against the composite."""
    from paddle_tpu.models.decoder_spec import DecoderSpec, MoESpec, RopeSpec
    from paddle_tpu.serving import PagedKVEngine
    import paddle_tpu as pt

    spec = DecoderSpec.conv_gqa_moe(
        vocab, d_model, d_inner, num_heads, num_kv_heads, kinds,
        RopeSpec(dim=d_model // num_heads, theta=1e6),
        MoESpec(n_routed=n_experts, top_k=top_k, d_expert=d_expert,
                held=tuple(range(n_experts)), n_shared=0, first_dense=1,
                topk_method="bias", norm_eps=1e-6))
    t0 = time.time()
    scope = pt.Scope()
    engines = [PagedKVEngine(n_slots=n_slots, max_len=max_len,
                             block_size=block_size, n_blocks=n_blocks,
                             scope=scope, model=spec, prefix_sharing=share)
               for share in (True, False)]
    rng = np.random.RandomState(3)
    head = rng.randint(0, vocab, (preamble,)).tolist()
    prompts = [head + rng.randint(0, vocab, (n,)).tolist() for n in turns]
    tokens = []
    for eng in engines:
        first = eng.submit(prompts[0], max_new)
        eng.run_until_idle()            # the preamble's blocks are cached
        rest = [eng.submit(p, max_new) for p in prompts[1:]]
        eng.run_until_idle()
        _check(all(r.done and r.error is None for r in [first] + rest),
               "a request of the hybrid engine did not finish")
        tokens.append([r.tokens for r in [first] + rest])
    run_s = time.time() - t0
    shared, alone = engines
    st = shared.stats()
    _check(tokens[0] == tokens[1],
           "a request that resumed from the prefix cache (K/V blocks and conv "
           "state snapshot) emitted other tokens than its self-prefilled twin")
    conv = st["conv_state"]
    _check(conv["restores"] == len(turns) - 1
           and alone.stats()["conv_state"]["restores"] == 0,
           f"conv state restores {conv}: every prefix hit resumes from a "
           f"block's snapshot")
    n_attn = list(kinds).count("attention")
    n_moe = len(kinds) - 1
    want = (n_attn + n_moe) if expect_lowering == "kernel" else 0
    n_calls = _n_custom_calls(shared.tick_hlo())
    n_mixed = _n_custom_calls(shared.mixed_tick_hlo())
    _check((st["paged_attention_lowering"], n_calls, n_mixed)
           == (expect_lowering, want, want + (n_attn if want else 0)),
           f"the hybrid engine reports its cache read as "
           f"{st['paged_attention_lowering']!r}, its ticks hold {n_calls} "
           f"and {n_mixed} tpu_custom_calls; expected {expect_lowering!r} "
           f"with {want} (a read an attention layer, a product a routed "
           f"layer) and {n_attn} more for the lanes")
    # the comparison above has to refuse a restore that is broken: with the
    # snapshots zeroed, a request that starts two tokens after the shared
    # preamble reads a zero state where its twin reads the preamble's
    import jax.numpy as jnp
    name = shared._cache_prefix + "_conv_block"
    scope.set_var(name, jnp.zeros_like(scope.get(name)))
    probe = head + rng.randint(0, vocab, (2,)).tolist()
    pair = [eng.submit(probe, max_new) for eng in engines]
    for eng in engines:
        eng.run_until_idle()
    _check(pair[0].shared_len == preamble and pair[1].shared_len == 0
           and pair[0].tokens != pair[1].tokens,
           "a request that resumed from a ZEROED conv state snapshot emitted "
           "its self-prefilled twin's tokens: the twins' comparison does not "
           "see the state")
    read_err = _check_grouped_decode(**{
        "n_slots": 64, "n_blocks": 1024, "block_size": block_size,
        "num_heads": num_heads, "num_kv_heads": num_kv_heads,
        "d_head": d_model // num_heads, "blocks_per_req": 48,
        **(decode_read or {})})
    return {"compile_s": 0.0, "run_s": round(run_s, 2),
            "tokens_out": sum(len(t) for t in tokens[0]),
            "decode_read_max_rel_err": float("%.2e" % read_err),
            "zeroed_state_differs_at": next(
                i for i, (a, b) in enumerate(zip(*(r.tokens for r in pair)))
                if a != b),
            "conv_state": conv, "block_bytes": st["block_bytes"],
            "experts_touched": int(np.count_nonzero(shared.expert_rows)),
            "paged_attention_lowering": st["paged_attention_lowering"],
            "tpu_custom_calls": n_calls, "mixed_tpu_custom_calls": n_mixed}


def phase_serve_ssm(vocab=32768, d_model=4096, num_heads=32, num_kv_heads=2,
                    d_head=128, ssm=(128, 64, 8, 128), latent=1024,
                    d_expert=2688, d_shared=5376, n_routed=512, n_held=16,
                    top_k=22, kinds=("ssm", "moe", "ssm", "attention"),
                    n_slots=16, block_size=64, n_blocks=128, n_snapshots=4,
                    max_len=1024, preamble=256, turns=(40, 150), max_new=24,
                    expect_lowering="kernel"):
    """A model of one sublayer a layer through the same PagedKVEngine:
    Mamba-2 mixers whose float32 state a decode row updates in place and a
    prefix hit restores from the snapshot POOL, attention over two
    key/value heads without positions, latent two-matrix experts of which a
    share is held, at the published widths of
    benchmark/configs/nemotron3-super-ep4.json, four layers and 16 held
    experts. Requests that start from a shared preamble (K/V blocks from the
    prefix cache and the state after them from the pool) must emit the tokens
    an engine without prefix sharing emits, which prefills the preamble
    itself; with the pool's entries swapped they must not."""
    from paddle_tpu.models.decoder_spec import DecoderSpec, MoESpec, SsmSpec
    from paddle_tpu.serving import PagedKVEngine
    import paddle_tpu as pt

    spec = DecoderSpec.ssm_gqa_moe(
        vocab, d_model, num_heads, num_kv_heads, d_head, kinds, SsmSpec(*ssm),
        MoESpec(n_routed=n_routed, top_k=top_k, d_expert=d_expert,
                held=tuple(range(n_held)), n_shared=1, first_dense=0,
                scaling=5.0, topk_method="bias", norm_eps=1e-20,
                activation="relu2", latent=latent, d_shared=d_shared))
    t0 = time.time()
    scope = pt.Scope()
    engines = [PagedKVEngine(n_slots=n_slots, max_len=max_len,
                             block_size=block_size, n_blocks=n_blocks,
                             n_snapshots=n_snapshots, scope=scope, model=spec,
                             prefix_sharing=share)
               for share in (True, False)]
    # the startup program leaves A_log, dt_bias and D as it leaves a matrix;
    # a state that neither explodes nor forgets at once needs A < 0 of order
    # one and a dt well under one
    import jax.numpy as jnp
    for name in list(scope.local_var_names()):
        if name.endswith("_a_log"):
            scope.set_var(name, jnp.zeros_like(scope.get(name)))
        elif name.endswith("_dt_bias"):
            scope.set_var(name, jnp.full_like(scope.get(name), -3.0))
    rng = np.random.RandomState(3)
    head = rng.randint(0, vocab, (preamble,)).tolist()
    other = rng.randint(0, vocab, (preamble,)).tolist()
    prompts = [head + rng.randint(0, vocab, (n,)).tolist() for n in turns]
    tokens = []
    for eng in engines:
        warm = [eng.submit(p, 2) for p in (head, other)]
        eng.run_until_idle()        # both preambles' blocks and snapshots
        rest = [eng.submit(p, max_new) for p in prompts]
        eng.run_until_idle()
        _check(all(r.done and r.error is None for r in warm + rest),
               "a request of the state-space engine did not finish")
        tokens.append([r.tokens for r in rest])
    run_s = time.time() - t0
    shared, alone = engines
    st = shared.stats()
    _check(tokens[0] == tokens[1],
           "a request that resumed from the prefix cache (K/V blocks and the "
           "state-space snapshot) emitted other tokens than its "
           "self-prefilled twin")
    pool = st["ssm_state"]
    _check(pool["restores"] == len(turns)
           and alone.stats()["ssm_state"]["restores"] == 0,
           f"state-space restores {pool}: every prefix hit resumes from an "
           f"entry of the snapshot pool")
    n_calls = _n_custom_calls(shared.tick_hlo())
    n_mixed = _n_custom_calls(shared.mixed_tick_hlo())
    per = {k: list(kinds).count(k) for k in ("ssm", "moe", "attention")}
    want = sum(per.values()) if expect_lowering == "kernel" else 0
    _check((st["paged_attention_lowering"], n_calls, n_mixed)
           == (expect_lowering, want,
               want + (per["attention"] if want else 0)),
           f"the state-space engine reports its cache read as "
           f"{st['paged_attention_lowering']!r}, its ticks hold {n_calls} "
           f"and {n_mixed} tpu_custom_calls; expected {expect_lowering!r} "
           f"with {want} (a state update a mixer, a product a routed layer, "
           f"a read an attention layer) and one more a lanes' read")
    # the comparison above has to refuse a restore that is broken: with the
    # two preambles' snapshots swapped, a request that starts two tokens
    # after `head` reads the state after `other`
    for j in range(per["ssm"]):
        for part in ("h", "conv"):
            name = f"{shared._cache_prefix}_ssm_snap_{part}{j}"
            snap = scope.get(name)
            scope.set_var(name, snap.at[0].set(snap[1]).at[1].set(snap[0]))
    probe = head + rng.randint(0, vocab, (2,)).tolist()
    pair = [eng.submit(probe, max_new) for eng in engines]
    for eng in engines:
        eng.run_until_idle()
    _check(pair[0].shared_len == preamble and pair[1].shared_len == 0
           and pair[0].tokens != pair[1].tokens,
           "a request that resumed from ANOTHER prompt's snapshot emitted its "
           "self-prefilled twin's tokens: the twins' comparison does not see "
           "the state")
    return {"compile_s": 0.0, "run_s": round(run_s, 2),
            "tokens_out": sum(len(t) for t in tokens[0]),
            "swapped_state_differs_at": next(
                i for i, (a, b) in enumerate(zip(*(r.tokens for r in pair)))
                if a != b),
            "ssm_state": pool, "block_bytes": st["block_bytes"],
            "experts_touched": int(np.count_nonzero(shared.expert_rows)),
            "paged_attention_lowering": st["paged_attention_lowering"],
            "tpu_custom_calls": n_calls, "mixed_tpu_custom_calls": n_mixed}


def phase_serve_kda(vocab=39296, d_model=2560, d_inner=6144, num_heads=32,
                    head_dim=128, kv_lora_rank=512, rope_dim=64, d_expert=768,
                    n_routed=512, n_held=64, top_k=8, n_group=8, topk_group=4,
                    kinds=("kda", "kda", "attention", "kda"), n_slots=16,
                    block_size=64, n_blocks=128, n_snapshots=4, max_len=1024,
                    preamble=256, turns=(40, 150), max_new=24,
                    expect_lowering="kernel"):
    """A model whose layers are channel-wise gated delta-rule (kda) mixers or
    latent attention by kind, through the same PagedKVEngine: a float32
    MATRIX state a head that a decode row updates in place (the kda kernel)
    and a prefix hit restores from the snapshot POOL, beside ONE latent pool
    with a gate a head and no query bottleneck, group-limited routed experts
    of which one whole group is held, at the published widths of
    benchmark/configs/ling3-flash-ep4.json and four layers. Requests that
    start from a shared preamble (latent blocks from the prefix cache and the
    state after them from the pool) must emit the tokens an engine without
    prefix sharing emits, which prefills the preamble itself; with the pool's
    entries swapped they must not, nor with the entries zeroed."""
    from paddle_tpu.models.decoder_spec import (DecoderSpec, KdaSpec,
                                                LatentSpec, MoESpec, RopeSpec)
    from paddle_tpu.serving import PagedKVEngine
    import jax.numpy as jnp
    import paddle_tpu as pt

    spec = DecoderSpec.kda_latent_moe(
        vocab, d_model, d_inner, num_heads, kinds,
        KdaSpec(heads=num_heads, head_dim=head_dim),
        LatentSpec(q_lora_rank=None, kv_lora_rank=kv_lora_rank,
                   qk_nope_head_dim=head_dim, v_head_dim=head_dim,
                   rope=RopeSpec(dim=rope_dim, theta=6e6), gate="head"),
        MoESpec(n_routed=n_routed, top_k=top_k, d_expert=d_expert,
                held=tuple(range(n_held)), n_shared=1, first_dense=1,
                scaling=2.5, topk_method="group_bias", norm_eps=1e-20,
                n_group=n_group, topk_group=topk_group))
    t0 = time.time()
    scope = pt.Scope()
    sizes = dict(max_len=max_len, block_size=block_size, scope=scope,
                 model=spec)
    # the startup program leaves A_log and dt_bias as it leaves a matrix; a
    # state that remembers across a turn needs slow channels (rate 1, a bias
    # of -4: a decay of exp(-5 sigmoid(f - 4)) ~ 0.9 a step). A bound step
    # pins the weights it was built over, so they are made by an engine that
    # never runs and set BEFORE the two that do are built
    PagedKVEngine(n_slots=1, n_blocks=max_len // block_size + 1,
                  n_snapshots=1, **sizes)
    for name in list(scope.local_var_names()):
        if name.endswith("_a_log"):
            scope.set_var(name, jnp.zeros_like(scope.get(name)))
        elif name.endswith("_dt_bias"):
            scope.set_var(name, jnp.full_like(scope.get(name), -4.0))
    engines = [PagedKVEngine(n_slots=n_slots, n_blocks=n_blocks,
                             n_snapshots=n_snapshots, prefix_sharing=share,
                             **sizes)
               for share in (True, False)]
    rng = np.random.RandomState(3)
    head = rng.randint(0, vocab, (preamble,)).tolist()
    other = rng.randint(0, vocab, (preamble,)).tolist()
    prompts = [head + rng.randint(0, vocab, (n,)).tolist() for n in turns]
    tokens = []
    for eng in engines:
        warm = [eng.submit(p, 2) for p in (head, other)]
        eng.run_until_idle()        # both preambles' blocks and snapshots
        rest = [eng.submit(p, max_new) for p in prompts]
        eng.run_until_idle()
        _check(all(r.done and r.error is None for r in warm + rest),
               "a request of the kda engine did not finish")
        tokens.append([r.tokens for r in rest])
    run_s = time.time() - t0
    shared, alone = engines
    st = shared.stats()
    _check(tokens[0] == tokens[1],
           "a request that resumed from the prefix cache (latent blocks and "
           "the delta-rule snapshot) emitted other tokens than its "
           "self-prefilled twin")
    pool = st["ssm_state"]
    _check(pool["restores"] == len(turns)
           and alone.stats()["ssm_state"]["restores"] == 0,
           f"delta-rule restores {pool}: every prefix hit resumes from an "
           f"entry of the snapshot pool")
    n_calls = _n_custom_calls(shared.tick_hlo())
    n_mixed = _n_custom_calls(shared.mixed_tick_hlo())
    per = {k: list(kinds).count(k) for k in ("kda", "attention")}
    n_moe = len(kinds) - 1
    want = per["kda"] + per["attention"] + n_moe \
        if expect_lowering == "kernel" else 0
    _check((st["paged_attention_lowering"], n_calls, n_mixed)
           == (expect_lowering, want,
               want + (per["attention"] if want else 0)),
           f"the kda engine reports its cache read as "
           f"{st['paged_attention_lowering']!r}, its ticks hold {n_calls} "
           f"and {n_mixed} tpu_custom_calls; expected {expect_lowering!r} "
           f"with {want} (a state update a kda layer, a product a routed "
           f"layer, a read a latent layer) and one more a lanes' read")

    def snapshots(change):
        for j in range(per["kda"]):
            for part in ("h", "conv"):
                name = f"{shared._cache_prefix}_kda_snap_{part}{j}"
                scope.set_var(name, change(scope.get(name)))

    def differs_at(what):
        """A request two tokens behind `head` on both engines: where the hit
        and its self-prefilled twin part ways."""
        probe = head + rng.randint(0, vocab, (2,)).tolist()
        pair = [eng.submit(probe, max_new) for eng in engines]
        for eng in engines:
            eng.run_until_idle()
        _check(pair[0].shared_len == preamble and pair[1].shared_len == 0
               and pair[0].tokens != pair[1].tokens,
               f"a request that resumed from {what} emitted its "
               "self-prefilled twin's tokens: the twins' comparison does not "
               "see the state")
        return next(i for i, (a, b) in enumerate(zip(*(r.tokens
                                                       for r in pair)))
                    if a != b)

    # the comparison above has to refuse a restore that is broken: with the
    # two preambles' snapshots swapped a request that starts two tokens after
    # `head` reads the state after `other`; with the entries zeroed, nothing
    snapshots(lambda s: s.at[0].set(s[1]).at[1].set(s[0]))
    swapped = differs_at("ANOTHER prompt's snapshot")
    snapshots(jnp.zeros_like)
    zeroed = differs_at("a snapshot that holds zeros")
    return {"compile_s": 0.0, "run_s": round(run_s, 2),
            "tokens_out": sum(len(t) for t in tokens[0]),
            "swapped_state_differs_at": swapped,
            "zeroed_state_differs_at": zeroed,
            "ssm_state": pool, "block_bytes": st["block_bytes"],
            "experts_touched": int(np.count_nonzero(shared.expert_rows)),
            "paged_attention_lowering": st["paged_attention_lowering"],
            "tpu_custom_calls": n_calls, "mixed_tpu_custom_calls": n_mixed}


def phase_serve_dsa(vocab=19360, d_model=4096, d_inner=12288, num_heads=64,
                    head_dim=128, q_lora_rank=1536, kv_lora_rank=512,
                    latent_head_dim=256, index=(32, 128, 256, 4, 64),
                    d_expert=2048, n_routed=288, n_held=8, top_k=8,
                    kinds=("kda", "attention", "kda"), n_slots=16,
                    block_size=64, n_blocks=96, n_snapshots=4, max_len=2048,
                    preamble=1024, turns=(40, 150), max_new=24,
                    expect_lowering="kernel"):
    """A model whose residual is FOUR streams mixed through Sinkhorn around
    every sub-layer, whose layers are kda mixers with low-rank gate pairs or
    ONE sparse NoPE latent layer (an indexer of `index` = (heads, dim, topk,
    kpool, rotated) scores pooled keys in a second pool under the same block
    table and the read attends the best groups and the tail), clamped gated
    pairs, through the same PagedKVEngine at the published widths of
    benchmark/configs/glm53-flash-ep8.json and three layers. The preamble
    holds four times the positions `index_topk` keeps, so every row behind it
    drops groups. Requests that start from a shared preamble (latent blocks
    AND pooled keys from the prefix cache, the state from the pool) must emit
    the tokens an engine without prefix sharing emits; with the shared
    blocks' pooled keys zeroed they must not."""
    from paddle_tpu.models.decoder_spec import (DecoderSpec, HyperSpec,
                                                IndexerSpec, KdaSpec,
                                                LatentSpec, MoESpec, RopeSpec)
    from paddle_tpu.serving import PagedKVEngine
    import jax.numpy as jnp
    import paddle_tpu as pt

    ih, idim, topk, kpool, rot = index
    spec = DecoderSpec.kda_latent_moe(
        vocab, d_model, d_inner, num_heads, kinds,
        KdaSpec(heads=num_heads, head_dim=head_dim, gate_rank=head_dim),
        LatentSpec(q_lora_rank=q_lora_rank, kv_lora_rank=kv_lora_rank,
                   qk_nope_head_dim=latent_head_dim,
                   v_head_dim=latent_head_dim, rope=None),
        MoESpec(n_routed=n_routed, top_k=top_k, d_expert=d_expert,
                held=tuple(range(n_held)), n_shared=1, first_dense=1,
                scaling=2.5, topk_method="bias", norm_eps=1e-20,
                swiglu_limit=10.0),
        norm_eps=1e-5,
        indexer=IndexerSpec(ih, idim, topk, kpool, RopeSpec(rot, 1e6)),
        hyper=HyperSpec())
    t0 = time.time()
    scope = pt.Scope()
    sizes = dict(max_len=max_len, block_size=block_size, scope=scope,
                 model=spec)
    # slow channels and stream maps of the benchmark's making, set BEFORE the
    # engines that run are built (a bound step pins the weights it was built
    # over): see `phase_serve_kda`; the maps' scalars 1, their bias 2 on
    # H_res's diagonal
    PagedKVEngine(n_slots=1, n_blocks=max_len // block_size + 1,
                  n_snapshots=1, **sizes)
    n = spec.hyper.mult
    for name in list(scope.local_var_names()):
        var = scope.get(name)
        if name.endswith("_a_log"):
            scope.set_var(name, jnp.zeros_like(var))
        elif name.endswith("_dt_bias"):
            scope.set_var(name, jnp.full_like(var, -4.0))
        elif "_hc" in name and name.endswith("_a"):
            scope.set_var(name, jnp.ones_like(var))
        elif "_hc" in name and name.endswith("_b"):
            scope.set_var(name, jnp.concatenate(
                [jnp.zeros((2 * n,), var.dtype),
                 2.0 * jnp.eye(n, dtype=var.dtype).reshape(-1)]))
    engines = [PagedKVEngine(n_slots=n_slots, n_blocks=n_blocks,
                             n_snapshots=n_snapshots, prefix_sharing=share,
                             **sizes)
               for share in (True, False)]
    rng = np.random.RandomState(3)
    head = rng.randint(0, vocab, (preamble,)).tolist()
    prompts = [head + rng.randint(0, vocab, (n,)).tolist() for n in turns]
    tokens = []
    for eng in engines:
        warm = eng.submit(head, 2)
        eng.run_until_idle()
        rest = [eng.submit(p, max_new) for p in prompts]
        eng.run_until_idle()
        _check(all(r.done and r.error is None for r in [warm] + rest),
               "a request of the sparse engine did not finish")
        tokens.append([r.tokens for r in rest])
    run_s = time.time() - t0
    shared, alone = engines
    st = shared.stats()
    _check(tokens[0] == tokens[1],
           "a request that resumed from the prefix cache (latent blocks, "
           "pooled index keys and the delta-rule snapshot) emitted other "
           "tokens than its self-prefilled twin")
    n_calls = _n_custom_calls(shared.tick_hlo())
    n_mixed = _n_custom_calls(shared.mixed_tick_hlo())
    per = {k: list(kinds).count(k) for k in ("kda", "attention")}
    want = per["kda"] + per["attention"] + len(kinds) - 1 \
        if expect_lowering == "kernel" else 0
    _check((st["paged_attention_lowering"], n_calls, n_mixed)
           == (expect_lowering, want, want),
           f"the sparse engine reports its cache read as "
           f"{st['paged_attention_lowering']!r}, its ticks hold {n_calls} "
           f"and {n_mixed} tpu_custom_calls; expected {expect_lowering!r} "
           f"with {want} in both (a state update a kda layer, a product a "
           "routed layer, ONE read a sparse layer: lanes and decode rows are "
           "one batch of rows)")
    # the comparison has to refuse a broken second pool: with every pooled
    # key zeroed a hit selects by index scores of zero (the first groups)
    index_pools = [c for c in shared.cache_names if "_ci" in c]
    _check(len(index_pools) == per["attention"],
           f"index pools {index_pools}: one a sparse layer")
    for name in index_pools:
        scope.set_var(name, jnp.zeros_like(scope.get(name)))
    probe = head + rng.randint(0, vocab, (2,)).tolist()
    pair = [eng.submit(probe, max_new) for eng in engines]
    for eng in engines:
        eng.run_until_idle()
    _check(pair[0].shared_len == preamble and pair[1].shared_len == 0
           and pair[0].tokens != pair[1].tokens,
           "a request that resumed from zeroed pooled keys emitted its "
           "self-prefilled twin's tokens: the comparison does not see the "
           "index pool")
    zeroed = next(i for i, (a, b) in enumerate(zip(*(r.tokens for r in pair)))
                  if a != b)
    return {"compile_s": 0.0, "run_s": round(run_s, 2),
            "tokens_out": sum(len(t) for t in tokens[0]),
            "zeroed_index_differs_at": zeroed,
            "ssm_state": st["ssm_state"], "block_bytes": st["block_bytes"],
            "paged_attention_lowering": st["paged_attention_lowering"],
            "tpu_custom_calls": n_calls, "mixed_tpu_custom_calls": n_mixed}


def phase_serve_parallel(vocab=261120, d_model=5120, d_inner=21504,
                         num_heads=20, num_kv_heads=4, d_head=128,
                         ssm=(32, 128, 2, 256), num_layers=4, n_slots=4,
                         block_size=64, n_blocks=96, n_snapshots=4,
                         max_len=1024, preamble=256, turns=(40, 150),
                         max_new=24, expect_lowering="kernel"):
    """A model of TWO mixers a layer through the same PagedKVEngine: in every
    layer a Mamba-2 mixer (heads of 128 x 256 over 2 groups, a float32 state
    a decode row updates in place and a prefix hit restores from the snapshot
    pool) and rotary grouped-query attention with FIVE query heads a
    key/value head, summed, under the family's multipliers, over the whole
    261,120-row vocabulary: the published widths of
    benchmark/configs/falcon-h1-34b-pp12.json, four layers. Every matrix
    is seeded N(0, 1 / (fan-in x multiplier^2)), larger by the inverse of the
    multiplier that follows it (as the configuration's seeded weights are),
    or both mixers would vanish beside the residual and the comparison below
    would see neither. Requests
    that start from a shared preamble (K/V blocks AND the state after them)
    must emit the tokens an engine without prefix sharing emits; with the
    pool's entries swapped they must not."""
    from paddle_tpu.models.decoder_spec import (DecoderSpec, Multipliers,
                                                RopeSpec, SsmSpec)
    from paddle_tpu.serving import PagedKVEngine
    import jax
    import jax.numpy as jnp
    import paddle_tpu as pt

    by = Multipliers(
        embedding=5.656854249492381, attention_out=0.0375,
        key=0.011048543456039804, ssm_in=0.25, ssm_out=0.08838834764831845,
        ssm=(0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
             0.3535533905932738),
        mlp=(0.1767766952966369, 0.011160714285714284), lm_head=0.0078125)
    spec = DecoderSpec.parallel_ssm_gqa(
        vocab, d_model, d_inner, num_heads, num_kv_heads, d_head, num_layers,
        SsmSpec(*ssm), RopeSpec(dim=d_head, theta=1e11), by)
    t0 = time.time()
    scope = pt.Scope()
    sizes = dict(n_slots=n_slots, max_len=max_len, block_size=block_size,
                 n_blocks=n_blocks, n_snapshots=n_snapshots, scope=scope,
                 model=spec)
    # a bound step pins the weights it was built over, so they are in the
    # scope BEFORE the engines are built (an engine's start-up makes only
    # what it does not find): the tick program, built and not run, says
    # which parameters there are. The state-space output is four times its
    # unit scale and has no skip term: the twins' comparison reads tokens,
    # and has to see the state in them
    from paddle_tpu.core import unique_name
    from paddle_tpu.framework.program import Program, program_guard
    from paddle_tpu.models import transformer
    declared = Program()
    with program_guard(declared, Program()), unique_name.guard():
        transformer.transformer_lm_paged_decode_tick(
            n_slots, n_blocks, block_size, max_len // block_size, model=spec,
            n_snapshots=n_snapshots, cache_prefix="declared")
    gains = {"tok_emb": 1 / by.embedding, "_ssm_out.w_0": 4 / by.ssm_out,
             "_ssm_taps": 1.0, "_ssm_in.w_0": 1 / (by.ssm_in * by.ssm[1]),
             "_attn_k.w_0": 1.6 / by.key, "_attn_q.w_0": 1.6,
             "_attn_o.w_0": 1 / by.attention_out,
             "_ffn_gate.w_0": 1 / by.mlp[0], "_ffn_down.w_0": 1 / by.mlp[1],
             "lm_head.w_0": 1 / by.lm_head}
    key = jax.random.key(5)
    seeded = jax.jit(lambda k, shape, dtype, std: jax.random.normal(
        k, shape, dtype) * std, static_argnums=(1, 2))
    params = sorted((v.name, tuple(v.shape), jnp.dtype(str(v.dtype)))
                    for v in declared.global_block().vars.values()
                    if v.persistable and not v.name.startswith("declared"))
    for k, (name, shape, dtype) in enumerate(params):
        if len(shape) == 2:
            # N(0, gain^2 / fan-in), a unit row for the embedding (a conv
            # channel's fan-in is its taps)
            gain = next((g for end, g in gains.items() if name.endswith(end)),
                        1.0)
            fan_in = shape[1] if name.endswith("_ssm_taps") else shape[0]
            std = gain * (1.0 if name == "tok_emb" else fan_in ** -0.5)
            value = seeded(jax.random.fold_in(key, k), shape, dtype,
                           jnp.asarray(std, dtype))
        elif name.endswith(".scale"):
            value = jnp.ones(shape, dtype)
        elif name.endswith("_dt_bias"):
            # with A = -1 (A_log 0): a state that neither explodes nor
            # forgets at once
            value = jnp.full(shape, -3.0, dtype)
        else:                       # A_log, the conv bias, the skip term D
            value = jnp.zeros(shape, dtype)
        scope.set_var(name, value)
    engines = [PagedKVEngine(prefix_sharing=share, **sizes)
               for share in (True, False)]
    rng = np.random.RandomState(5)
    head = rng.randint(0, vocab, (preamble,)).tolist()
    other = rng.randint(0, vocab, (preamble,)).tolist()
    prompts = [head + rng.randint(0, vocab, (n,)).tolist() for n in turns]
    tokens = []
    for eng in engines:
        warm = [eng.submit(p, 2) for p in (head, other)]
        eng.run_until_idle()        # both preambles' blocks and snapshots
        rest = [eng.submit(p, max_new) for p in prompts]
        eng.run_until_idle()
        _check(all(r.done and r.error is None for r in warm + rest),
               "a request of the two-mixer engine did not finish")
        tokens.append([r.tokens for r in rest])
    run_s = time.time() - t0
    shared, alone = engines
    st = shared.stats()
    _check(tokens[0] == tokens[1],
           "a request that resumed from the prefix cache (K/V blocks and the "
           "state-space snapshot of the same layers) emitted other tokens "
           "than its self-prefilled twin")
    pool = st["ssm_state"]
    _check(pool["restores"] == len(turns) and pool["layers"] == num_layers
           == pool["layers_with_kv"],
           f"state-space restores {pool}: every prefix hit resumes from an "
           f"entry of the snapshot pool, in every layer")
    n_calls = _n_custom_calls(shared.tick_hlo())
    n_mixed = _n_custom_calls(shared.mixed_tick_hlo())
    want = 2 * num_layers if expect_lowering == "kernel" else 0
    _check((st["paged_attention_lowering"], n_calls, n_mixed)
           == (expect_lowering, want, want + (num_layers if want else 0)),
           f"the two-mixer engine reports its cache read as "
           f"{st['paged_attention_lowering']!r}, its ticks hold {n_calls} "
           f"and {n_mixed} tpu_custom_calls; expected {expect_lowering!r} "
           f"with {want} (a state update and a read a layer) and one more a "
           f"layer for the lanes' read")
    for j in range(num_layers):
        for part in ("h", "conv"):
            name = f"{shared._cache_prefix}_ssm_snap_{part}{j}"
            snap = scope.get(name)
            scope.set_var(name, snap.at[0].set(snap[1]).at[1].set(snap[0]))
    probe = head + rng.randint(0, vocab, (2,)).tolist()
    pair = [eng.submit(probe, max_new) for eng in engines]
    for eng in engines:
        eng.run_until_idle()
    _check(pair[0].shared_len == preamble and pair[1].shared_len == 0
           and pair[0].tokens != pair[1].tokens,
           "a request that resumed from ANOTHER prompt's snapshot emitted its "
           "self-prefilled twin's tokens: the twins' comparison does not see "
           "the state")
    return {"compile_s": 0.0, "run_s": round(run_s, 2),
            "tokens_out": sum(len(t) for t in tokens[0]),
            "swapped_state_differs_at": next(
                i for i, (a, b) in enumerate(zip(*(r.tokens for r in pair)))
                if a != b),
            "ssm_state": pool, "block_bytes": st["block_bytes"],
            "paged_attention_lowering": st["paged_attention_lowering"],
            "tpu_custom_calls": n_calls, "mixed_tpu_custom_calls": n_mixed}


def phase_window_read(n_slots=32, n_blocks=512, block_size=64, num_heads=64,
                      num_kv_heads=8, d_head=128, blocks_per_req=16,
                      window=128, n_lanes=2, chunk=128, backend=None):
    """The sliding-window read of k-exaone-ep8_serve_long_sessions at its
    published widths (64 query heads over 8 key/value heads of 128, bfloat16
    pools in blocks of 64, of a table's blocks a row maps the two or three
    its window lies in): the decode rows' kernel and the lanes' chunk kernel,
    each against the composite, and the same two reads with no window as the
    full layer takes them. The table is 16 blocks wide, not the cell's 272:
    the composite gathers the whole table for every query head in float32,
    18 GB at 272 (the cell's own runs read through the kernels at 272, and
    `tests/test_pallas_tpu_lowering.py` compiles them there)."""
    shape = dict(n_blocks=n_blocks, block_size=block_size,
                 num_heads=num_heads, num_kv_heads=num_kv_heads,
                 d_head=d_head, blocks_per_req=blocks_per_req,
                 backend=backend)
    t0 = time.time()
    errs = {
        "window_decode": _check_grouped_decode(n_slots, window=window,
                                               **shape),
        "window_chunk": _check_grouped_decode(n_lanes, window=window,
                                              rows=chunk, **shape),
        "full_decode": _check_grouped_decode(n_slots, **shape),
        "full_chunk": _check_grouped_decode(n_lanes, rows=chunk, **shape)}
    return {"compile_s": 0.0, "run_s": round(time.time() - t0, 2),
            "max_rel_err": {k: float("%.2e" % v) for k, v in errs.items()}}


def phase_train_resnet50(batch=256, steps=2, depth=50, image=224):
    """The image training graph: ResNet-50 NHWC bf16, uint8 staging
    declared (and fed), Momentum."""
    import paddle_tpu as pt
    from paddle_tpu import models

    _free_device_memory()
    with pt.core.unique_name.guard():
        img = pt.layers.data(name="img", shape=[image, image, 3],
                             staging_dtype="uint8")
        loss, _, _ = models.resnet.resnet_imagenet(
            img=img, depth=depth, is_test=False, data_format="NHWC",
            use_bf16=True)
        pt.optimizer.MomentumOptimizer(learning_rate=3e-3,
                                       momentum=0.9).minimize(loss)
    exe = pt.Executor()
    exe.run(pt.default_startup_program())
    scope = pt.global_scope()
    pname = pt.default_main_program().global_block().all_parameters()[0].name
    before = np.asarray(scope.get(pname)).copy()
    rng = np.random.RandomState(2)
    feed = {"img": rng.randint(0, 256, (batch, image, image, 3), "uint8"),
            "label": rng.randint(0, 1000, (batch, 1)).astype("int64")}
    losses, compile_s, run_s = _run_steps(
        lambda: exe.run(feed=feed, fetch_list=[loss])[0], steps,
        decreasing=False)
    after = np.asarray(scope.get(pname))
    _check(np.isfinite(after).all() and not np.array_equal(before, after),
           f"parameter {pname} did not change")
    return {"compile_s": round(compile_s, 2), "run_s": round(run_s, 2),
            "steps": steps, "batch": batch,
            "losses": [round(x, 4) for x in losses], "param_changed": pname}


def _timed_first(f, *args):
    import jax
    t0 = time.time()
    out = jax.block_until_ready(f(*args))
    return out, time.time() - t0


def _check_flash(shape, causal, with_segments, backend, timing,
                 token_major=False):
    """Flash fwd + bwd (one jit) against the composite evaluated in f32 at
    the highest matmul precision, head by head so a long sequence's [T, T]
    scores never need more than one head of HBM. `token_major`: the same
    numbers handed over as [B, T, H*D], the layout a training step's
    projections leave, through `_attend`."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import pallas_kernels as pk

    B, H, T, D = shape
    rng = np.random.RandomState(3)
    q, k, v, do = (jnp.asarray(rng.randn(*shape), jnp.bfloat16)
                   for _ in range(4))
    ids = None
    if with_segments:      # four packed segments per row, ids 1..4
        cuts = np.sort(rng.randint(1, T, (B, 3)), axis=1)
        ids = jnp.asarray(1 + (np.arange(T)[None, :, None]
                               >= cuts[:, None, :]).sum(-1), jnp.int32)

    def fwd_bwd(be, q, k, v, do):
        out, vjp = jax.vjp(lambda q, k, v: pk.flash_attention(
            q, k, v, causal=causal, backend=be, segment_ids=ids), q, k, v)
        return (out,) + vjp(do.astype(out.dtype))

    def fwd_bwd_token_major(q, k, v, do):
        def rows(x):        # [B, H, T, D] -> [B, T, H*D]
            return jnp.swapaxes(x, 1, 2).reshape(B, T, H * D)
        out, vjp = jax.vjp(lambda q, k, v: pk._attend(
            q, k, v, None if ids is None else (ids, ids), D ** -0.5, causal,
            backend, H),
            rows(q), rows(k), rows(v))
        return tuple(jnp.swapaxes(x.reshape(B, T, H, D), 1, 2)
                     for x in (out,) + vjp(rows(do)))

    got, c = _timed_first(
        jax.jit(fwd_bwd_token_major if token_major
                else lambda *a: fwd_bwd(backend, *a)), q, k, v, do)
    timing["compile_s"] += c
    t0 = time.time()

    @jax.jit
    def ref_head(q, k, v, do):
        return fwd_bwd("xla", *(a.astype(jnp.float32) for a in (q, k, v, do)))
    errs = [0.0] * 4
    with jax.default_matmul_precision("highest"):
        for h in range(H):
            sl = slice(h, h + 1)
            ref = ref_head(q[:, sl], k[:, sl], v[:, sl], do[:, sl])
            for i in range(4):
                errs[i] = max(errs[i], _rel_err(got[i][:, sl], ref[i]))
    timing["run_s"] += time.time() - t0
    _check(max(errs) <= TOL_BF16,
           f"flash {shape} causal={causal} segments={with_segments}: "
           f"out/dq/dk/dv errors {errs} exceed {TOL_BF16}")
    return max(errs)


def _check_decode(num_heads, d_head, span, rows, backend, timing):
    import jax
    import jax.numpy as jnp
    from paddle_tpu.fusion import decode_attention as da

    _check(da._pallas_fits(num_heads, span, d_head)
           and not da._pallas_fits(num_heads, span + 128, d_head),
           f"T={span} is not the largest span the decode kernel's gate "
           f"admits at {num_heads} heads x {d_head}")
    rng = np.random.RandomState(4)
    q = jnp.asarray(rng.randn(rows, 1, num_heads, 1, d_head), jnp.float32)
    k, v = (jnp.asarray(rng.randn(rows, 1, num_heads, span, d_head),
                        jnp.float32) for _ in range(2))
    lens = rng.randint(1, span + 1, (rows,))
    bias = jnp.asarray(np.where(np.arange(span)[None] < lens[:, None],
                                0.0, -1e9).reshape(rows, 1, 1, 1, span),
                       jnp.float32)
    scale = d_head ** -0.5

    def run(be):
        return jax.jit(lambda *a: da.fused_decode_attention(
            *a, scale=scale, backend=be))
    got, c = _timed_first(run(backend), q, k, v, bias)
    timing["compile_s"] += c
    t0 = time.time()
    with jax.default_matmul_precision("highest"):
        ref = run("xla")(q, k, v, bias)
    err = _rel_err(got, ref)
    timing["run_s"] += time.time() - t0
    _check(err <= TOL_F32, f"decode attention T={span}: error {err}")
    return err


def _check_paged(n_slots, n_blocks, block_size, num_heads, d_head,
                 blocks_per_req, backend, timing):
    """The paged decode-attention kernel against its composite: ragged
    positions (an idle slot on the null block, a block's last row, the
    whole span among them), a permuted table."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.fusion import (paged_attention_lowering,
                                   paged_decode_attention)
    from paddle_tpu.ops.tensor_ops import pool_block_shape

    rng = np.random.RandomState(7)
    span = blocks_per_req * block_size
    # the pools as the tick declares them (lane-dense at these widths)
    shape = (n_blocks,) + pool_block_shape(num_heads, block_size, d_head)
    _check(paged_attention_lowering("float32", shape[-1], 1, d_head, False,
                                    backend=backend) == "kernel",
           f"pools {shape} do not take the paged kernel")
    k_pool, v_pool = (jnp.asarray(rng.randn(*shape), jnp.float32)
                      for _ in range(2))
    q = jnp.asarray(rng.randn(n_slots, 1, num_heads * d_head), jnp.float32)
    pos = rng.randint(0, span, (n_slots,))
    pos[:3] = 0, block_size - 1, span - 1
    btab = np.zeros((n_slots, blocks_per_req), np.int32)
    ids = iter(np.resize(rng.permutation(np.arange(1, n_blocks)),
                         n_slots * blocks_per_req))
    for s in range(1, n_slots):              # slot 0 idle: the null block
        for j in range(pos[s] // block_size + 1):
            btab[s, j] = next(ids)
    args = (q, k_pool, v_pool, jnp.asarray(btab),
            jnp.asarray(pos, jnp.float32))

    def run(be):
        return jax.jit(lambda *a: paged_decode_attention(
            *a, num_heads, scale=d_head ** -0.5, backend=be))
    got, c = _timed_first(run(backend), *args)
    timing["compile_s"] += c
    t0 = time.time()
    with jax.default_matmul_precision("highest"):
        ref = run("xla")(*args)
    err = _rel_err(got, ref)
    timing["run_s"] += time.time() - t0
    _check(err <= TOL_F32, f"paged decode attention: error {err}")
    return err, float(np.max(np.abs(np.asarray(got) - np.asarray(ref))))


def _check_grouped_decode(n_slots, n_blocks, block_size, num_heads,
                          num_kv_heads, d_head, blocks_per_req, backend=None,
                          window=0, rows=1):
    """The grouped decode read (one position a slot over bfloat16 pools of
    the key/value heads: fusion/paged_attention.py `_decode_kernel`) against
    its composite: every fourth slot live and the others idle on the null
    block, ragged positions (a block's first and last row and the whole
    span among them), a permuted table. With `window` the read is a
    sliding-window layer's: the table maps only the blocks a position of the
    window lies in (the pager has released the others), and the positions
    lie to either side of the window's edge; with `rows` > 1 it is a lane's
    chunk of that many positions (`_chunk_kernel`), every slot live."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.fusion import (paged_attention_lowering,
                                   paged_decode_attention)
    from paddle_tpu.ops.tensor_ops import pool_block_shape

    rng = np.random.RandomState(13)
    span = blocks_per_req * block_size
    shape = (n_blocks,) + pool_block_shape(num_kv_heads, block_size, d_head)
    _check(paged_attention_lowering("bfloat16", shape[-1], 1, d_head, False,
                                    backend=backend) == "kernel",
           f"bfloat16 pools {shape} do not take the grouped decode kernel")
    k_pool, v_pool = (jnp.asarray(rng.randn(*shape), jnp.bfloat16)
                      for _ in range(2))
    q = jnp.asarray(rng.randn(n_slots, rows, num_heads * d_head),
                    jnp.float32)
    live = np.arange(n_slots) % 4 == 1 if rows == 1 else \
        np.ones(n_slots, bool)
    pos = np.where(live, rng.randint(0, span // 2, (n_slots,)), 0)
    if rows > 1:
        pos = pos // block_size * block_size    # a chunk starts a block
    edges = np.flatnonzero(live)[:5]
    pos[edges] = ((block_size, block_size - 1, span - rows,
                   max(window - 1, 0), window) if rows == 1
                  else (0, span - rows))[:len(edges)]
    ids = iter(np.resize(rng.permutation(np.arange(1, n_blocks)),
                         n_slots * blocks_per_req))
    btab = np.zeros((n_slots, blocks_per_req), np.int32)
    for s in np.flatnonzero(live):
        first = max(pos[s] - (window - 1), 0) // block_size if window else 0
        for j in range(first, (pos[s] + rows - 1) // block_size + 1):
            btab[s, j] = next(ids)
    args = (q, k_pool, v_pool, jnp.asarray(btab), jnp.asarray(pos, jnp.int32))

    def run(be):
        return jax.jit(lambda *a: paged_decode_attention(
            *a, num_heads, scale=d_head ** -0.5, backend=be, window=window))
    got = run(backend)(*args)
    with jax.default_matmul_precision("highest"):
        ref = run("xla")(*args)
    _check(bool(jnp.isfinite(got).all()), "grouped decode read: not finite")
    err = _rel_err(got[live], ref[live])
    # bf16 operands on the MXU against float32 at the highest precision
    _check(err <= TOL_BF16, f"grouped decode read: error {err}")
    return err


def _check_experts(gated, n_held, d_model, d_expert, rows, backend, timing):
    """The serving expert product (fusion/moe.py `experts`: the packed walk
    over the touched experts, bfloat16 stacks) against its composite over
    every held expert, with a scattered touched set (every third expert and
    the last), with every expert touched and with none. Returns the largest
    error."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.fusion import moe

    key = jax.random.PRNGKey(5)
    shapes = ([(n_held, d_model, d_expert)] * (2 if gated else 1)
              + [(n_held, d_expert, d_model)])
    stacks = [(jax.random.normal(jax.random.fold_in(key, i), s, jnp.float32)
               * s[1] ** -0.5).astype(jnp.bfloat16)
              for i, s in enumerate(shapes)]
    x = jax.random.normal(jax.random.fold_in(key, 7), (rows, d_model),
                          jnp.bfloat16)
    rng = np.random.RandomState(5)
    _check(moe.experts_lowering(rows, d_model, d_expert, backend,
                                moe.experts_tile(rows, d_model, d_expert, 2,
                                                 len(stacks))) == moe.KERNEL,
           f"no kernel serves {rows} rows x [{d_model}, {d_expert}]")

    def run(be):
        return jax.jit(lambda x, w, n, *m: moe.experts(
            x, w, n, *(m if gated else (None,) + m), backend=be))
    kernel, composite = run(backend), run("xla")
    worst = 0.0
    scattered = np.arange(n_held) % 3 == 0
    scattered[-1] = True
    # ... and with none (an idle engine's tick): zeros out of one step
    for on in (scattered, np.ones(n_held, bool), np.zeros(n_held, bool)):
        picked = on[:, None, None] & (rng.rand(n_held, rows, 1) < 0.3)
        picked[on, 0] = True                 # a touched expert has a row
        w = jnp.asarray(picked * rng.uniform(0.05, 0.5, picked.shape),
                        jnp.float32)
        counts = jnp.asarray(picked.sum(axis=(1, 2)), jnp.int32)
        args = (x, w, counts, *stacks)
        got, c = _timed_first(kernel, *args)
        timing["compile_s"] += c
        t0 = time.time()
        err = _rel_err(got, composite(*args))
        timing["run_s"] += time.time() - t0
        _check(err <= TOL_BF16,
               f"expert product ({'gated' if gated else 'two-matrix'}, "
               f"{int(on.sum())} of {n_held} touched): error {err}")
        worst = max(worst, err)
    return worst


def _check_latent_decode(n_slots, n_live, n_blocks, block_size, num_heads,
                         row_lanes, v_width, blocks_per_req, backend, timing):
    """The latent decode read (one position a slot, every head on the slot's
    latent rows in a bfloat16 pool: fusion/latent_attention.py
    `_latent_decode_kernel`) against its composite: `n_live` slots spread
    over the grid, each near the end of its table (the first one on its last
    row), the others idle on the null block; a permuted table. Returns the
    error and, on a TPU, the milliseconds of one call."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.fusion import (latent_attention_lowering,
                                   latent_paged_attention)

    rng = np.random.RandomState(17)
    span = blocks_per_req * block_size
    _check(latent_attention_lowering(row_lanes, v_width, num_heads, 1,
                                     backend) == "kernel",
           f"rows of {row_lanes} lanes do not take the latent kernel")
    pool = jnp.asarray(rng.randn(n_blocks, 1, block_size, row_lanes) * 0.5,
                       jnp.bfloat16)
    q = jnp.asarray(rng.randn(n_slots, 1, num_heads * row_lanes),
                    jnp.bfloat16)
    live = np.linspace(1, n_slots - 1, n_live).astype(int)
    pos = np.zeros((n_slots,), np.int32)
    pos[live] = rng.randint(span - 2 * block_size, span, (n_live,))
    pos[live[0]] = span - 1
    btab = np.zeros((n_slots, blocks_per_req), np.int32)
    for s in live:
        btab[s] = rng.randint(1, n_blocks, blocks_per_req)
    btab, pos = jnp.asarray(btab), jnp.asarray(pos)

    def run(be):
        return jax.jit(lambda q, t, p: latent_paged_attention(
            q, pool, t, p, num_heads, v_width, row_lanes ** -0.5, backend=be))
    read = run(backend)
    got, c = _timed_first(read, q, btab, pos)
    timing["compile_s"] += c
    t0 = time.time()
    with jax.default_matmul_precision("highest"):
        ref = run("xla")(q[live], btab[live], pos[live])
    _check(bool(jnp.isfinite(got).all()), "latent decode read: not finite")
    err = _rel_err(got[live], ref)
    call_ms = None
    if jax.default_backend() == "tpu":
        t1 = time.perf_counter()
        for _ in range(20):
            out = read(q, btab, pos)
        out.block_until_ready()
        call_ms = (time.perf_counter() - t1) / 20 * 1e3
    timing["run_s"] += time.time() - t0
    # bf16 operands on the MXU against float32 at the highest precision
    _check(err <= TOL_BF16, f"latent decode read: error {err}")
    return err, call_ms


def _check_paged_chunk(n_lanes, chunk, n_blocks, block_size, num_heads,
                       d_head, blocks_per_req, backend, timing):
    """The chunk-attention kernel (a prefill lane's read of the mixed tick)
    against its composite, through a permuted table: a whole chunk behind a
    four-block prefix beside a short last chunk, then a few rows from
    position 0 beside an idle lane. Only a lane's real rows are compared;
    everything returned must be finite."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.fusion import (paged_attention_lowering,
                                   paged_decode_attention)
    from paddle_tpu.ops.tensor_ops import pool_block_shape

    rng = np.random.RandomState(11)
    shape = (n_blocks,) + pool_block_shape(num_heads, block_size, d_head)
    _check(paged_attention_lowering("float32", shape[-1], chunk, d_head,
                                    False, backend=backend) == "kernel",
           f"pools {shape} with {chunk} query rows do not take the chunk "
           f"kernel")
    k_pool, v_pool = (jnp.asarray(rng.randn(*shape), jnp.float32)
                      for _ in range(2))
    perm = rng.permutation(np.arange(1, n_blocks))

    def run(be):
        return jax.jit(lambda q, b, p, r: paged_decode_attention(
            q, k_pool, v_pool, b, p, num_heads, scale=d_head ** -0.5,
            backend=be, rows=r))
    worst = 0.0
    for pos, rows in (((4 * block_size, 2 * chunk), (chunk, chunk // 3 + 1)),
                      ((0, 0), (5, 0))):
        pos, rows = pos[:n_lanes], rows[:n_lanes]
        btab = np.zeros((n_lanes, blocks_per_req), np.int32)
        for lane in range(n_lanes):
            n = -(-(pos[lane] + rows[lane]) // block_size) if rows[lane] else 0
            btab[lane, :n] = perm[lane * blocks_per_req:][:n]
        q = jnp.asarray(rng.randn(n_lanes, chunk, num_heads * d_head),
                        jnp.float32)
        args = (q, jnp.asarray(btab), jnp.asarray(pos, jnp.int32),
                jnp.asarray(rows, jnp.int32))
        got, c = _timed_first(run(backend), *args)
        timing["compile_s"] += c
        t0 = time.time()
        with jax.default_matmul_precision("highest"):
            ref = run("xla")(*args)
        timing["run_s"] += time.time() - t0
        _check(bool(jnp.isfinite(got).all()), "chunk attention: not finite")
        for lane in range(n_lanes):
            if rows[lane]:
                worst = max(worst, _rel_err(got[lane, :rows[lane]],
                                            ref[lane, :rows[lane]]))
    # bf16 operands on the MXU against float32 at the highest precision
    _check(worst <= TOL_BF16, f"paged chunk attention: error {worst}")
    return worst


def _check_recurrent(kind, batch, steps, hidden, backend, timing):
    import jax
    import jax.numpy as jnp
    from paddle_tpu.fusion import fused_gru_sequence, fused_lstm_sequence

    gates = 4 if kind == "lstm" else 3
    rng = np.random.RandomState(5)
    x = jnp.asarray(rng.randn(batch, steps, gates * hidden) * 0.5,
                    jnp.float32)
    w = jnp.asarray(rng.randn(hidden, gates * hidden) * hidden ** -0.5,
                    jnp.float32)
    states = [jnp.asarray(rng.randn(batch, hidden) * 0.1, jnp.float32)
              for _ in range(2 if kind == "lstm" else 1)]
    seqlen = jnp.asarray(rng.randint(steps // 2, steps + 1, (batch,)),
                         jnp.int32)

    def fwd_grad(be, x, w, *states):
        def loss(x, w):
            if kind == "lstm":
                hs, cs = fused_lstm_sequence(x, *states, w, seqlen,
                                             backend=be)
                return jnp.sum(hs * hs) + jnp.sum(cs), hs
            hs = fused_gru_sequence(x, *states, w, seqlen, backend=be)
            return jnp.sum(hs * hs), hs
        (_, hs), (dx, dw) = jax.value_and_grad(loss, argnums=(0, 1),
                                               has_aux=True)(x, w)
        return hs, dx, dw

    got, c = _timed_first(jax.jit(lambda *a: fwd_grad(backend, *a)),
                          x, w, *states)
    timing["compile_s"] += c
    t0 = time.time()
    ref = jax.jit(lambda *a: fwd_grad("xla", *a))(x, w, *states)
    errs = [_rel_err(g, r) for g, r in zip(got, ref)]
    timing["run_s"] += time.time() - t0
    _check(max(errs) <= TOL_F32_MXU,
           f"fused {kind} B{batch} T{steps} H{hidden}: hs/dx/dw errors "
           f"{errs} exceed {TOL_F32_MXU}")
    return max(errs)


def _check_train_experts(rows, top_k, d_model, d_expert, n_held, n_routed,
                         backend, timing):
    """The routed TRAINING layer (fusion/moe.py `train_experts`: the row
    gather, megablox's products, the elementwise step between them and the
    sum back, bfloat16 operands) against its composite (`jax.lax.ragged_dot`,
    `x[index]`), the layer and all five gradients, with a quarter of the
    pairs held (an even routing's share), 72% (what the training cell's lone
    rank comes to hold) and all of them. On the chip the rows behind the held
    pairs are whatever memory held. Returns the largest error."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.fusion import moe

    key = jax.random.PRNGKey(11)
    x, probe = (jax.random.normal(jax.random.fold_in(key, i),
                                  (rows, d_model), jnp.float32)
                for i in (0, 1))
    gate, up = (jax.random.normal(jax.random.fold_in(key, i),
                                  (n_held, d_model, d_expert), jnp.float32)
                * d_model ** -0.5 for i in (2, 3))
    down = jax.random.normal(jax.random.fold_in(key, 4),
                             (n_held, d_expert, d_model),
                             jnp.float32) * d_expert ** -0.5
    w = jax.random.uniform(jax.random.fold_in(key, 5), (rows, top_k),
                           jnp.float32, 0.05, 0.5)
    _check(moe.rows_lowering(x, rows * top_k, jnp.bfloat16, backend)
           == moe.KERNEL, f"no row gather serves {rows} rows of {d_model}")
    rng = np.random.RandomState(11)

    def run(be):
        def layer(idx, x, w, gate, up, down):
            out, sizes = moe.train_experts(x, idx, w, tuple(range(n_held)),
                                           n_routed, gate, up, down,
                                           backend=be)
            return jnp.sum(out * probe), (out, sizes)
        return jax.jit(jax.value_and_grad(layer, argnums=(1, 2, 3, 4, 5),
                                          has_aux=True))
    kernel, composite = run(backend), run("xla")
    worst = 0.0
    for share in (0.25, 0.72, 1.0):
        # a row selects its held experts first, then the others
        mine = rng.binomial(min(top_k, n_held), share, rows) if share < 1 \
            else np.full(rows, min(top_k, n_held))
        idx = np.stack([np.concatenate([
            rng.permutation(n_held)[:n],
            n_held + rng.permutation(n_routed - n_held)[:top_k - n]])
            for n in mine]).astype(np.int32)
        args = (jnp.asarray(idx), x, w, gate, up, down)
        ((_, (got, sizes)), got_grads), c = _timed_first(kernel, *args)
        timing["compile_s"] += c
        t0 = time.time()
        (_, (want, _)), want_grads = composite(*args)
        _check(int(sizes.sum()) == int(mine.sum()), "a held pair was dropped")
        err = max([_rel_err(got, want)] + [
            _rel_err(a, b) for a, b in zip(got_grads, want_grads)])
        timing["run_s"] += time.time() - t0
        _check(err <= TOL_BF16,
               f"routed training layer, {share:.0%} of the pairs held: "
               f"max rel err {err:.3e} > {TOL_BF16:.1e}")
        worst = max(worst, err)
    return worst


def phase_kernels(backend="pallas",
                  flash_shapes=(((8, 16, 1024, 64), True),
                                ((64, 16, 128, 64), True),
                                ((64, 16, 128, 64), False),
                                ((1, 8, 8192, 128), True)),
                  decode=(16, 64, 640, 16), recurrent=(64, 64, 256),
                  paged=(16, 1024, 16, 16, 64, 64), chunk=(2, 128),
                  latent=(32, 5, 2048, 64, 64, 640, 512, 272),
                  experts=((True, 32, 2048, 1792, 64),
                           (False, 128, 1024, 2688, 64)),
                  train_experts=(8192, 8, 2304, 896, 16, 64)):
    """Every Pallas kernel the package selects by default on a TPU, called
    directly, compiled by Mosaic, run, and compared with its own composite.
    flash_shapes = ([B, H, T, D], causal): the training cells' shapes (the
    LM's, which is also its dp4 shard's; the NMT decoder's; its encoder's and
    cross attention's) and a head that streams, each with and without
    segment ids, head-major as `flash_attention` takes them and token-major
    (`_tm`) as a training step hands them over, under the plan each shape
    gives (`flash_plans`);
    decode = (heads, d_head, span, rows); recurrent = (batch, steps, hidden);
    paged = (slots, pool blocks, block size, heads, d_head, blocks a
    request): the serving benchmark's tick; chunk = (lanes, tokens a
    lane) of its mixed tick, over the same pools; latent = (slots, live
    slots, pool blocks, block size, heads, row lanes, value lanes, blocks a
    request): the document cell's latent decode read at its published
    widths, five slots live at 17k positions; experts = (gated, held experts,
    d_model, d_expert, rows) a case: the assistant cell's gated product and
    the bursts cell's two-matrix one at their decode shapes, each with a
    scattered touched set, with every expert touched and with none;
    train_experts = (rows, top-k, d_model, d_expert, held experts, routed
    experts): the routed layer of the 8k training cell, forward and
    backward, with a quarter, 72% and all of its pairs held."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas_kernels import _plan_for
    timing = {"compile_s": 0.0, "run_s": 0.0}
    errs, plans = {}, {}
    for shape, causal in flash_shapes:
        B, H, T, D = shape
        qkv = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
        rows = jax.ShapeDtypeStruct((B, T, H * D), jnp.bfloat16)
        for seg in (False, True):
            name = ("flash_" + "x".join(map(str, shape))
                    + ("" if causal else "_full") + ("_seg" * seg))
            errs[name] = _check_flash(shape, causal, seg, backend, timing)
            plans[name] = _plan_for(qkv, qkv, seg).scopes()
            errs[name + "_tm"] = _check_flash(shape, causal, seg, backend,
                                              timing, token_major=True)
            plans[name + "_tm"] = _plan_for(rows, rows, seg,
                                            num_heads=H).scopes()
    errs["decode_T%d" % decode[2]] = _check_decode(*decode, backend, timing)
    errs["paged_decode"], paged_abs = _check_paged(*paged, backend, timing)
    errs["paged_chunk"] = _check_paged_chunk(*chunk, *paged[1:], backend,
                                             timing)
    errs["latent_decode"], latent_ms = _check_latent_decode(*latent, backend,
                                                            timing)
    for case in experts:
        errs["experts_gated" if case[0] else "experts_two_matrix"] = \
            _check_experts(*case, backend, timing)
    errs["train_experts"] = _check_train_experts(*train_experts, backend,
                                                 timing)
    for kind in ("lstm", "gru"):
        errs["fused_" + kind] = _check_recurrent(kind, *recurrent, backend,
                                                 timing)
    out = {"compile_s": round(timing["compile_s"], 2),
           "run_s": round(timing["run_s"], 2), "backend": backend,
           "max_rel_err": {k: float("%.2e" % v) for k, v in errs.items()},
           "flash_plans": plans,
           "paged_decode_max_abs_diff": float("%.2e" % paged_abs)}
    if latent_ms is not None:       # a TPU's: a call with its dispatch
        out["latent_decode_call_ms"] = round(latent_ms, 4)
    return out


def _multichip_ring(devices, ring_shape):
    """Ring attention over sp=4, causal, fwd + grad (backend auto), against
    flash attention on one chip. ring_shape = [B, T, H, D]."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas_kernels import flash_attention
    from paddle_tpu.parallel import DeviceMesh
    from paddle_tpu.parallel.ring_attention import ring_attention_sharded

    t0 = time.time()
    rng = np.random.RandomState(6)
    q, k, v, do = (jnp.asarray(rng.randn(*ring_shape), jnp.bfloat16)
                   for _ in range(4))
    sp_mesh = DeviceMesh(devices, {"sp": 4})

    def fwd_bwd(attend, q, k, v, do):
        out, vjp = jax.vjp(attend, q, k, v)
        return (out,) + vjp(do)

    def ring(q, k, v):
        return ring_attention_sharded(sp_mesh, q, k, v, causal=True)

    def flash(q, k, v):        # [B, T, H, D] -> the kernel's [B, H, T, D]
        def t(a):
            return jnp.transpose(a, (0, 2, 1, 3))
        return t(flash_attention(t(q), t(k), t(v), causal=True))

    got = jax.block_until_ready(
        jax.jit(lambda *a: fwd_bwd(ring, *a))(q, k, v, do))
    ref = jax.jit(lambda *a: fwd_bwd(flash, *a))(q, k, v, do)
    errs = [_rel_err(g, r) for g, r in zip(got, ref)]
    # both sides are bf16 kernels; the ring merges four partial softmaxes
    _check(max(errs) <= TOL_BF16,
           f"ring attention vs flash: out/dq/dk/dv errors {errs}")
    return {"mesh": {"sp": 4}, "shape": list(ring_shape),
            "seconds": round(time.time() - t0, 2),
            "max_rel_err": float("%.2e" % max(errs))}


def _shapes(text):
    """[(dims...), ...] of every typed array shape in an HLO fragment."""
    return [tuple(int(d) for d in m.split(","))
            for m in re.findall(r"\b[a-z]+[0-9]*\[([0-9,]+)\]", text)]


def phase_multichip(ref_first_loss=None, vocab=32000, seq_len=1024,
                    d_model=1024, d_inner=4096, num_heads=16, num_layers=12,
                    batch=8, steps=3, flash_calls_per_layer=2,
                    attn_backend=None, ring_shape=(1, 8192, 8, 128),
                    loss_tol=5e-3, min_bytes_in_use=1 << 20):
    """The train_lm model under ParallelExecutor on a dp2 x tp2 mesh
    (annotate_tp + ZeRO-1 Reduce), then ring attention on sp4 against
    single-chip flash. One process drives the four chips."""
    import jax
    import paddle_tpu as pt
    from paddle_tpu.parallel import (BuildStrategy, DeviceMesh,
                                     ParallelExecutor, ReduceStrategy,
                                     annotate_tp)

    devices = jax.devices()[:4]
    loss = _build_lm_train(vocab, seq_len, d_model, d_inner, num_heads,
                           num_layers, attn_backend)
    annotate_tp()
    pt.Executor().run(pt.default_startup_program())
    mesh = DeviceMesh(devices, {"dp": 2, "tp": 2})
    pe = ParallelExecutor(
        loss_name=loss.name, mesh=mesh,
        build_strategy=BuildStrategy(reduce_strategy=ReduceStrategy.Reduce))
    feed = _lm_feed(batch, seq_len, vocab)
    losses, compile_s, run_s = _run_steps(
        lambda: pe.run(fetch_list=[loss], feed=feed)[0], steps)
    if ref_first_loss is not None:
        # same seeds, same batch: only the reduction order differs
        _check(abs(losses[0] - ref_first_loss)
               <= loss_tol * abs(ref_first_loss),
               f"first-step loss {losses[0]} on the mesh vs "
               f"{ref_first_loss} on one chip")

    # every device holds shards of the state, and real memory
    scope = pt.global_scope()
    holders = set()
    for name in scope.local_var_names():
        val = scope.get(name)
        if hasattr(val, "addressable_shards"):
            holders |= {s.device for s in val.addressable_shards}
    _check(set(devices) <= holders,
           f"devices without a shard: {set(devices) - holders}")
    in_use = []
    for d in devices:
        stats = d.memory_stats()
        if stats is not None:          # the CPU backend reports none
            in_use.append(int(stats["bytes_in_use"]))
            _check(in_use[-1] >= min_bytes_in_use,
                   f"{d} holds {in_use[-1]} bytes")

    # the flash kernels run per shard: [B/dp, T, H/tp * D] operands (head-major
    # [B/dp * H/tp, T, D], as the residuals' rows, where the shard's heads
    # fill no whole lane tile), and no all-gather rebuilds a full-size q/k/v
    # in front of them
    n_calls = 0
    if flash_calls_per_layer:
        hlo = pe.compiled_hlo(feed=feed, fetch_list=[loss])
        calls = [ln for ln in hlo.splitlines() if _MOSAIC_CALL in ln]
        n_calls = len(calls)
        _check(n_calls == flash_calls_per_layer * num_layers,
               f"{n_calls} tpu_custom_calls in the sharded step")
        rows = (batch // 2) * (num_heads // 2)
        d_head = d_model // num_heads
        for ln in calls:
            lead = {s[0] for s in _shapes(ln) if len(s) == 3}
            _check(rows in lead and lead <= {rows, batch // 2},
                   f"flash call with leading dims {lead}, expected per-shard "
                   f"{rows} or {batch // 2}: {ln[:200]}")
        for ln in hlo.splitlines():
            if " all-gather(" in ln:
                result = _shapes(ln.split(" all-gather(")[0])
                _check(not any(s[-2:] in ((seq_len, d_head),
                                          (seq_len, d_model))
                               for s in result),
                       f"q/k/v all-gather: {ln[:200]}")
    facts = {"compile_s": round(compile_s, 2), "run_s": round(run_s, 2),
             "mesh": {"dp": 2, "tp": 2}, "steps": steps,
             "losses": [round(x, 4) for x in losses],
             "ref_first_loss": ref_first_loss,
             "tpu_custom_calls": n_calls, "bytes_in_use": in_use}
    _free_device_memory()
    facts["ring"] = _multichip_ring(devices, ring_shape)
    return facts


# ------------------------------------------------------------------ driver


def _expire():
    print(f"chip_smoke: deadline of {DEADLINE_S:.0f} s expired in phase "
          f"{_PHASE[0]}", file=sys.stderr, flush=True)
    os._exit(4)


def _run():
    import jax
    import paddle_tpu as pt

    def phase(name, fn, *args, **kw):
        _PHASE[0] = name
        facts = fn(*args, **kw)
        _emit(name, facts)
        return facts

    phase("device", phase_device)
    train = phase("train_lm", phase_train_lm)
    phase("serve_lm", phase_serve_lm, pt.global_scope())
    phase("train_resnet50", phase_train_resnet50)
    _free_device_memory()
    phase("serve_hybrid", phase_serve_hybrid)
    phase("serve_ssm", phase_serve_ssm)
    _free_device_memory()
    phase("serve_parallel", phase_serve_parallel)
    _free_device_memory()
    phase("serve_kda", phase_serve_kda)
    phase("serve_dsa", phase_serve_dsa)
    _free_device_memory()
    phase("window_read", phase_window_read)
    phase("kernels", phase_kernels)
    if len(jax.devices()) >= 4:
        phase("multichip", phase_multichip, train["losses"][0])
    else:
        _PHASE[0] = "multichip"
        _emit("multichip", {"compile_s": 0.0, "run_s": 0.0,
                            "skipped": f"{len(jax.devices())} device"})
    d = _device_facts()
    return {"platform": d["platform"], "kind": d["device_kind"],
            "count": d["n_devices"]}


def main():
    watchdog = threading.Timer(DEADLINE_S, _expire)
    watchdog.daemon = True
    watchdog.start()
    t0 = time.time()
    try:
        device = _run()
    except Exception:
        traceback.print_exc()
        print(f"chip_smoke: FAILED in phase {_PHASE[0]} after "
              f"{time.time() - t0:.0f} s", file=sys.stderr, flush=True)
        return 1
    print(f"chip_smoke: all phases passed in {time.time() - t0:.0f} s",
          file=sys.stderr, flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
