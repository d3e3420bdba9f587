"""Pallas -> Mosaic lowering for the TPU platform, checked without a chip.

`jax.jit(f).trace(*args).lower(lowering_platforms=("tpu",))` runs the whole
Pallas TPU lowering (block-shape legality, layout rules) from a CPU-only
process; only Mosaic's own compile needs the device. Interpret mode checks
none of this: the batch-major blocks of the r06 recurrent kernel passed every
interpret-mode test and were refused here ("last two dimensions of your block
shape are divisible by 8 and 128"). Every kernel chip_smoke.py's `kernels`
phase runs is lowered at the smoke's shapes, plus the program-level steps
whose kernel selection depends on shape gates.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.fusion import (fused_decode_attention, fused_gru_sequence,
                               fused_lstm_sequence)
from paddle_tpu.ops import pallas_kernels
from paddle_tpu.ops.pallas_kernels import flash_attention

S = jax.ShapeDtypeStruct
F32, BF16, I32 = jnp.float32, jnp.bfloat16, jnp.int32


def _tpu_text(f, *args):
    return jax.jit(f).trace(*args).lower(lowering_platforms=("tpu",)).as_text()


def _n_calls(text):
    return text.count("tpu_custom_call")


def _flash_fwd_bwd(causal=True, segments=False):
    def fwd_bwd(q, k, v, do, ids):
        out, vjp = jax.vjp(lambda q, k, v: flash_attention(
            q, k, v, causal=causal, backend="pallas",
            segment_ids=ids if segments else None), q, k, v)
        return (out,) + vjp(do)
    return fwd_bwd


def _flash_args(shape):
    return [S(shape, BF16)] * 4 + [S((shape[0], shape[2]), I32)]


def _flash_fwd_bwd_token_major(shape, causal=True, segments=False):
    """The same call on q, k, v as a training step's projections leave them,
    [B, T, H*D]: the op's own path (`_attend`)."""
    B, H, T, D = shape

    def fwd_bwd(q, k, v, do, ids):
        out, vjp = jax.vjp(lambda q, k, v: pallas_kernels._attend(
            q, k, v, (ids, ids) if segments else None, D ** -0.5, causal,
            "pallas", H), q, k, v)
        return (out,) + vjp(do)
    return fwd_bwd, [S((B, T, H * D), BF16)] * 4 + [S((B, T), I32)]


# [B, H, T, D], causal, kernels: the cells' shapes take the resident plan (a
# forward and ONE backward), a head over the VMEM budget streams (dq and
# dk / dv in two passes)
_FLASH_SHAPES = [
    pytest.param((8, 16, 1024, 64), True, 2, id="lm-big_train_1chip"),
    pytest.param((32 // 4, 16, 1024, 64), True, 2, id="lm-big_train_dp4-shard"),
    pytest.param((64, 16, 128, 64), True, 2, id="nmt-big-decoder"),
    pytest.param((64, 16, 128, 64), False, 2, id="nmt-big-encoder-cross"),
    pytest.param((1, 8, 8192, 128), True, 3, id="long-context-streams"),
]


@pytest.mark.parametrize("shape, causal, kernels", _FLASH_SHAPES)
@pytest.mark.parametrize("segments", [False, True])
def test_flash_fwd_bwd_lowers(shape, causal, kernels, segments):
    text = _tpu_text(_flash_fwd_bwd(causal, segments), *_flash_args(shape))
    assert _n_calls(text) == kernels


@pytest.mark.parametrize("shape, causal, kernels", _FLASH_SHAPES)
@pytest.mark.parametrize("segments", [False, True])
def test_flash_fwd_bwd_lowers_token_major(shape, causal, kernels, segments):
    """The cells' shapes as the training steps hand them over; the head that
    streams goes back to the head-major kernels between two transposes."""
    f, args = _flash_fwd_bwd_token_major(shape, causal, segments)
    text = jax.jit(f).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text(debug_info=True)
    assert _n_calls(text) == kernels
    names = set(re.findall(r'loc\("(flash_[^"]*)/pallas_call"', text))
    assert len(names) == kernels
    assert all(n.endswith("_tm") == (kernels == 2) for n in names), names


@pytest.fixture(scope="module")
def one_chip():
    """A described (not attached) v5e chip: the TPU compiler runs Mosaic's
    own compile for it, which is what refuses a kernel for its VMEM."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("shape, causal, kernels", _FLASH_SHAPES)
def test_flash_fwd_bwd_compiles_for_v5e(one_chip, shape, causal, kernels):
    """Lowering checks block shapes; only Mosaic's compile checks that a
    plan's blocks, scratch and tiles fit the 16 MiB of VMEM a kernel gets."""
    args = [S(a.shape, a.dtype, sharding=one_chip)
            for a in _flash_args(shape)]
    compiled = jax.jit(_flash_fwd_bwd(causal)).lower(*args).compile()
    assert compiled.as_text().count("tpu_custom_call") == kernels


# [B, H, T, D] handed over as [B, T, H*D]: the cells' shapes, then what else
# the rule sends token-major (two heads of 128, a pair of six heads, four
# heads of 32, keys of another length, a length that pads)
_TOKEN_MAJOR_SHAPES = [p for p in _FLASH_SHAPES[:4]] + [
    pytest.param((4, 16, 1024, 128), True, 2, id="D128-rows2"),
    pytest.param((4, 6, 1024, 64), True, 2, id="pair-of-6-heads"),
    pytest.param((4, 8, 512, 32), False, 2, id="D32-rows8"),
    pytest.param((4, 16, 2048, 64), True, 2, id="T2048-rows2"),
    pytest.param((8, 16, 200, 64), True, 2, id="T200-padded"),
]


@pytest.mark.parametrize("shape, causal, kernels", _TOKEN_MAJOR_SHAPES)
def test_flash_token_major_compiles_for_v5e(one_chip, shape, causal, kernels):
    """Mosaic's own compile of the token-major kernels: the heads' lane
    slices, the q-side heads held over the key loop and the transposes that
    write the results must fit VMEM beside the blocks."""
    f, args = _flash_fwd_bwd_token_major(shape, causal)
    args = [S(a.shape, a.dtype, sharding=one_chip) for a in args]
    text = jax.jit(f).lower(*args).compile().as_text()
    assert text.count("tpu_custom_call") == kernels
    assert text.count("_tm") >= kernels


@pytest.mark.parametrize("shape, scope", [
    ((2, 4, 256, 64), "flash_fwd_resident_q256_k256_rows4"),
    ((2, 4, 256, 64), "flash_bwd_resident_q256_k256_rows4"),
    ((1, 2, 8192, 128), "flash_fwd_streamed_q1024_k1024"),
    ((1, 2, 8192, 128), "flash_bwd_dq_streamed_q1024_k1024"),
    ((1, 2, 8192, 128), "flash_bwd_dkv_streamed_q1024_k1024"),
])
def test_flash_kernels_carry_their_names(shape, scope):
    """Each flash kernel's pallas_call sits in a named scope that spells its
    plan: XLA names the custom call after it, which is how a device trace
    tells the kernels apart and says which plan a cell ran (PERF.md,
    `device_ops`)."""
    lowered = jax.jit(_flash_fwd_bwd()).trace(*_flash_args(shape)).lower(
        lowering_platforms=("tpu",))
    # the call lies in the kernel's own jitted function (traced once a
    # step), so its location starts at the scope
    names = re.findall(r'loc\("([^"]*)/pallas_call"',
                       lowered.as_text(debug_info=True))
    assert sum(n == scope for n in set(names)) == 1, names


def test_decode_attention_lowers_at_the_gate_edge():
    rows, nh, dh, span = 16, 16, 64, 640
    text = _tpu_text(
        lambda q, k, v, b: fused_decode_attention(
            q, k, v, b, scale=dh ** -0.5, backend="pallas"),
        S((rows, 1, nh, 1, dh), F32), S((rows, 1, nh, span, dh), F32),
        S((rows, 1, nh, span, dh), F32), S((rows, 1, 1, 1, span), F32))
    assert _n_calls(text) == 1
    # past the gate the same call is the composite: the choice is by shape
    text = _tpu_text(
        lambda q, k, v, b: fused_decode_attention(
            q, k, v, b, scale=dh ** -0.5, backend="pallas"),
        S((rows, 1, nh, 1, dh), F32), S((rows, 1, nh, 1024, dh), F32),
        S((rows, 1, nh, 1024, dh), F32), S((rows, 1, 1, 1, 1024), F32))
    assert _n_calls(text) == 0


@pytest.mark.parametrize("kind", ["lstm", "gru"])
def test_fused_recurrent_fwd_grad_lowers(kind):
    B, T, H = 64, 64, 256
    gates = 4 if kind == "lstm" else 3

    def fwd_grad(x, w, sl, *states):
        def loss(x, w):
            if kind == "lstm":
                hs, cs = fused_lstm_sequence(x, *states, w, sl,
                                             backend="pallas")
                return jnp.sum(hs) + jnp.sum(cs)
            return jnp.sum(fused_gru_sequence(x, *states, w, sl,
                                              backend="pallas"))
        return jax.grad(loss, argnums=(0, 1))(x, w)

    states = [S((B, H), F32)] * (2 if kind == "lstm" else 1)
    text = _tpu_text(fwd_grad, S((B, T, gates * H), F32),
                     S((H, gates * H), F32), S((B,), I32), *states)
    assert _n_calls(text) == 1     # the forward recurrence; bwd is a scan


# -- program-level steps, with the backend selection a TPU would make -------


@pytest.fixture
def as_on_tpu(monkeypatch):
    """Kernel selection as on a TPU (`_auto_backend` reads the default
    backend, which is the CPU here); every shape gate stays live."""
    monkeypatch.setattr(pallas_kernels, "_auto_backend", lambda: "pallas")


def _step_tpu_text(compiled, feed, scope):
    args = (tuple(jnp.asarray(feed[n]) for n in compiled.feed_names),
            tuple(scope.get(n) for n in compiled.ro_names),
            tuple(scope.get(n) for n in compiled.rw_names), np.uint32(0))
    return compiled.fn.trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()


def _tick_tpu_text(step):
    """A bound step as the engine launches it: the packed host array cut
    apart (slices, a bitcast for the float spans), then the program."""
    return step.lower(lowering_platforms=("tpu",)).as_text()


def test_stacked_lstm_train_step_lowers_with_default_flags(as_on_tpu):
    """bs64 / T64 / H256: the model with chip history that the batch-major
    kernel made un-lowerable for a TPU under default flags."""
    from paddle_tpu.models import stacked_lstm
    b, t = 64, 64
    loss, _, _ = stacked_lstm.stacked_lstm_net(
        dict_dim=10000, emb_dim=256, hid_dim=256, max_len=t)
    pt.optimizer.AdamOptimizer(learning_rate=5e-4).minimize(loss)
    exe = pt.Executor()
    exe.run(pt.default_startup_program())
    feed = {"words": np.zeros((b, t), "int64"),
            "words@SEQLEN": np.full((b,), t, "int32"),
            "label": np.zeros((b, 1), "int64")}
    compiled = exe._compile(pt.default_main_program(), pt.global_scope(),
                            list(feed), [loss.name])
    text = _step_tpu_text(compiled, feed, pt.global_scope())
    n_lstm = sum(op.type == "dynamic_lstm"
                 for op in pt.default_main_program().global_block().ops)
    assert n_lstm >= 2 and _n_calls(text) == n_lstm


@pytest.mark.parametrize("max_len", [256, 1024])
def test_paged_tick_reads_the_pool_through_the_paged_kernel(as_on_tpu,
                                                            max_len):
    """16 heads x 64, 16-token blocks: the tick's cache read is the paged
    decode kernel, one custom call per layer, at a 256-token span and at
    the benchmark's 1024 alike (the slot kernel's VMEM gate, which sent the
    1024 span to the composite, plays no part: a step holds a few blocks,
    never the span). chip_smoke.py's serve_lm phase asserts the same."""
    from paddle_tpu.serving import PagedKVEngine
    n_layers = 2
    eng = PagedKVEngine(n_slots=2, vocab=64, max_len=max_len, d_model=1024,
                        d_inner=64, num_heads=16, num_layers=n_layers,
                        block_size=16)
    assert eng.stats()["paged_attention_lowering"] == "kernel"
    text = _tick_tpu_text(eng._step)
    # the read is one jitted function: lowered to Mosaic once, called by
    # every layer (the compiled tick inlines it: a custom call a layer)
    assert _n_calls(text) == 1
    assert len(re.findall(r"call @_paged_pallas\b", text)) == n_layers


def test_paged_decode_kernel_lowers_at_the_benchmark_shapes():
    from paddle_tpu.fusion import paged_decode_attention
    slots, blocks, heads, dh, per_req = 16, 1024, 16, 64, 64
    text = _tpu_text(
        lambda q, k, v, t, p: paged_decode_attention(
            q, k, v, t, p, heads, scale=dh ** -0.5, backend="pallas"),
        S((slots, 1, heads * dh), F32), S((blocks, heads, 8, 128), F32),
        S((blocks, heads, 8, 128), F32), S((slots, per_req), I32),
        S((slots, 1, 1), F32))
    assert _n_calls(text) == 1


def test_paged_chunk_kernel_lowers_at_the_benchmark_shapes():
    """Two prefill lanes of 128 rows over the serving cell's pools."""
    from paddle_tpu.fusion import paged_decode_attention
    lanes, chunk, blocks, heads, dh, per_req = 2, 128, 1024, 16, 64, 64
    text = _tpu_text(
        lambda q, k, v, t, p, r: paged_decode_attention(
            q, k, v, t, p, heads, scale=dh ** -0.5, backend="pallas",
            rows=r),
        S((lanes, chunk, heads * dh), F32), S((blocks, heads, 8, 128), F32),
        S((blocks, heads, 8, 128), F32), S((lanes, per_req), I32),
        S((lanes, 1, 1), F32), S((lanes,), I32))
    assert _n_calls(text) == 1


def test_mixed_tick_reads_the_pool_through_both_kernels(as_on_tpu):
    """The mixed tick at the benchmark's widths and span: a layer's decode
    rows take the decode kernel and its lanes the chunk kernel (128 rows a
    lane), each lowered to Mosaic once; 16 + 2 * 128 rows share the
    matmuls; the decode tick beside it holds what it held."""
    from paddle_tpu.serving import PagedKVEngine
    n_layers = 2
    eng = PagedKVEngine(n_slots=16, vocab=64, max_len=1024, d_model=1024,
                        d_inner=64, num_heads=16, num_layers=n_layers,
                        block_size=16)
    assert (eng.n_lanes, eng.chunk_tokens) == (2, 128)
    text = _tick_tpu_text(eng._mixed_step)
    assert _n_calls(text) == 2
    assert len(re.findall(r"call @_paged_pallas\b", text)) == n_layers
    assert len(re.findall(r"call @_chunk_pallas\b", text)) == n_layers
    assert "tensor<272x1x1024xf32>" in text
    decode = _tick_tpu_text(eng._step)
    # either launch takes ONE small host argument: the seed and the feeds
    assert "tensor<%dxi32>" % eng._mixed_step._buf.size in text
    assert "tensor<%dxi32>" % eng._step._buf.size in decode
    assert _n_calls(decode) == 1 and "_chunk_pallas" not in decode


def test_sharded_train_step_runs_flash_per_shard(as_on_tpu):
    """Under ParallelExecutor's SPMD mode the flash calls sit inside a
    shard_map: their operands are the per-shard [B/dp * H/tp, T, D], not
    the full batch behind an all-gather."""
    from paddle_tpu import models
    from paddle_tpu.parallel import (BuildStrategy, DeviceMesh,
                                     ParallelExecutor, ReduceStrategy,
                                     annotate_tp)
    b, t, nh, dh, n_layers = 8, 128, 4, 16, 2
    loss, _ = models.transformer.transformer_lm(
        vocab=128, max_len=t, d_model=nh * dh, d_inner=128, num_heads=nh,
        num_layers=n_layers, dropout=0.0)
    pt.optimizer.AdamOptimizer(learning_rate=1e-3).minimize(loss)
    annotate_tp()
    pt.Executor().run(pt.default_startup_program())
    mesh = DeviceMesh(jax.devices()[:4], {"dp": 2, "tp": 2})
    pe = ParallelExecutor(
        loss_name=loss.name, mesh=mesh,
        build_strategy=BuildStrategy(reduce_strategy=ReduceStrategy.Reduce))
    feed = {"tokens": np.zeros((b, t), "int64"),
            "tokens@SEQLEN": np.full((b,), t, "int32"),
            "targets": np.zeros((b, t), "int64")}
    pe._feed_shapes = {n: np.shape(v) for n, v in feed.items()}
    compiled = pe._compile(pt.default_main_program(), pt.global_scope(),
                           list(feed), [loss.name])
    text = _step_tpu_text(compiled, feed, pt.global_scope())
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    # a forward and ONE backward, each lowered once for all the layers; two
    # heads of 16 fill no 128-lane tile, so the shards run head-major
    assert len(calls) == 2
    per_shard = f"tensor<{(b // 2) * (nh // 2)}x{t}x{dh}xbf16>"
    full = f"tensor<{b * nh}x{t}x{dh}xbf16>"
    for ln in calls:
        assert per_shard in ln and full not in ln, ln[:300]
    assert re.search(r"sdy\.manual_computation|shard_map", text)


# -- a step holds each flash body once, and no head-major copy ---------------


def _flash_counters(mark):
    from paddle_tpu.observability import tracing
    spans = [s.name for s in tracing.spans_since(mark)]
    return spans.count("flash/body_traced"), spans.count("flash/call")


def _lm_step(n_layers, b=2, t=256, nh=4, dh=64):
    loss, _ = pt.models.transformer.transformer_lm(
        vocab=128, max_len=t, d_model=nh * dh, d_inner=128, num_heads=nh,
        num_layers=n_layers, dropout=0.0)
    feed = {"tokens": np.zeros((b, t), "int64"),
            "tokens@SEQLEN": np.full((b,), t, "int32"),
            "targets": np.zeros((b, t), "int64")}
    return loss, feed


def _nmt_step(n_layers, b=2, t=128, nh=4, dh=64):
    loss, _ = pt.models.transformer.transformer(
        src_vocab=64, tgt_vocab=64, max_len=t, d_model=nh * dh, d_inner=128,
        num_heads=nh, num_layers=n_layers, dropout=0.0)
    feed = {"src": np.zeros((b, t), "int64"),
            "src@SEQLEN": np.full((b,), t, "int32"),
            "tgt": np.zeros((b, t), "int64"),
            "tgt@SEQLEN": np.full((b,), t, "int32"),
            "lbl": np.zeros((b, t), "int64")}
    return loss, feed


_HEAD_MAJOR_COPY = re.compile(r"stablehlo\.transpose[^\n]*dims = \[0, 2, 1, 3\]")


@pytest.mark.parametrize("build, n_layers, calls, bodies, scopes", [
    # a causal forward and its backward, whatever the depth
    pytest.param(_lm_step, 2, 4, 2, {"rows4_tm"}, id="lm-2-layers"),
    pytest.param(_lm_step, 3, 6, 2, {"rows4_tm"}, id="lm-3-layers"),
    # encoder and cross attention share the full bodies (Tk = T), the
    # decoder's self attention has the causal ones
    pytest.param(_nmt_step, 2, 12, 4, {"rows4_tm"}, id="nmt-2+2-layers"),
])
@pytest.mark.parametrize("mesh_of_four", [False, True],
                         ids=["one-chip", "dp4"])
def test_a_step_lowers_each_flash_body_once(as_on_tpu, build, n_layers,
                                            calls, bodies, scopes,
                                            mesh_of_four):
    """A training step traces and lowers a flash body once, not once a
    layer: the module holds `bodies` Mosaic calls for `calls` call sites and
    the counters say the same; the kernels take the projections' [B, T, H*D]
    (`_tm`), so no head-major copy stands between a projection and a call.
    `dp4`: the same inside the shard_map of a data-parallel mesh of four."""
    from paddle_tpu.observability import tracing
    from paddle_tpu.parallel import DeviceMesh, ParallelExecutor
    loss, feed = build(n_layers)
    pt.optimizer.AdamOptimizer(learning_rate=1e-3).minimize(loss)
    exe = pt.Executor()
    exe.run(pt.default_startup_program())
    if mesh_of_four:
        feed = {n: np.concatenate([v] * 4) for n, v in feed.items()}
        exe = ParallelExecutor(
            loss_name=loss.name,
            mesh=DeviceMesh(jax.devices()[:4], {"dp": 4}))
        exe._feed_shapes = {n: np.shape(v) for n, v in feed.items()}
    compiled = exe._compile(pt.default_main_program(), pt.global_scope(),
                            list(feed), [loss.name])
    jax.clear_caches()      # a body an earlier test traced is not traced again
    mark = tracing.mark()
    args = (tuple(jnp.asarray(feed[n]) for n in compiled.feed_names),
            tuple(pt.global_scope().get(n) for n in compiled.ro_names),
            tuple(pt.global_scope().get(n) for n in compiled.rw_names),
            np.uint32(0))
    text = compiled.fn.trace(*args).lower(
        lowering_platforms=("tpu",)).as_text(debug_info=True)
    assert _n_calls(text) == bodies
    assert _flash_counters(mark) == (bodies, calls)
    names = set(re.findall(r'loc\("(flash_[^"]*)/pallas_call"', text))
    assert {n.split("_", 5)[-1] for n in names} == scopes, names
    assert not _HEAD_MAJOR_COPY.search(text)
    if mesh_of_four:
        assert re.search(r"sdy\.manual_computation|shard_map", text)
        b = feed[compiled.feed_names[0]].shape[0] // 4
        for ln in text.splitlines():
            if "tpu_custom_call" in ln:
                assert f"tensor<{b}x" in ln, ln[:300]


def _latent_read(slots, positions):
    from paddle_tpu.fusion import latent_paged_attention
    return (lambda q, pool, t, p, r: latent_paged_attention(
                q, pool, t, p, 64, 512, 0.13, rows=r, backend="pallas"),
            [S((slots, positions, 64 * 640), BF16),
             S((2048, 1, 64, 640), BF16), S((slots, 272), I32),
             S((slots, 1, 1), F32), S((slots,), I32)])


@pytest.mark.parametrize("slots, positions, body, result", [
    (32, 1, "_latent_decode_kernel", "tensor<32x64x512xbf16>"),
    (2, 128, "_latent_kernel", "tensor<2x8192x512xbf16>")])
def test_latent_attention_kernel_lowers_at_the_benchmark_shapes(
        slots, positions, body, result):
    """The latent read of axk1-ep16_serve_docqa: 32 decode rows through the
    decode body, and two prefill lanes of 128 positions through the lanes'
    body, 64 heads over rows of 640 lanes in blocks of 64, a table of 272
    blocks. Each is ONE Mosaic call in the scope `latent_paged_attention`
    whose result is `bf16[slots, rows, 512]`: what benchmark/kernel_ops.py
    finds the decode read by."""
    f, args = _latent_read(slots, positions)
    text = jax.jit(f).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text(debug_info=True)
    (call,) = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert f"-> {result}" in call
    (loc,) = re.findall(r"loc\((#loc\d+)\)", call)
    scope = re.search(re.escape(loc) + r' = loc\("([^"]+)"', text).group(1)
    assert scope.split("/")[-2:] == ["latent_paged_attention", "pallas_call"]
    assert f'kernel_name = "{body}"' in call


@pytest.mark.parametrize("slots, positions", [(32, 1), (2, 128)])
def test_latent_attention_kernel_compiles_for_v5e(one_chip, slots, positions):
    """Both bodies at those widths through the TPU compiler for a v5e: the
    decode body's two 1024-row buffers and its written-out copies, the
    lanes' body as it was."""
    f, args = _latent_read(slots, positions)
    args = [S(a.shape, a.dtype, sharding=one_chip) for a in args]
    compiled = jax.jit(f).lower(*args).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1
    if positions == 1:
        assert "bf16[32,64,512]" in compiled.as_text()


@pytest.mark.parametrize("rows", [32, 288])
@pytest.mark.parametrize("held, width", [(12, 7168), (16, 6144)],
                         ids=["document", "long_sessions"])
def test_expert_product_kernel_lowers_at_the_benchmark_shapes(one_chip, held,
                                                              width, rows):
    """The grouped expert product of the decode tick (32 rows) and the mixed
    tick (32 + 2 * 128): twelve held experts of 7168 x 2048 (the document
    cell), sixteen of 6144 x 2048 (the long sessions). The packed walk over
    the touched experts (PR 51) is ONE Mosaic call whose expert axis ends
    with the count, at the tile the shape gives (256 columns; 512 under the
    long sessions' decode rows), and the TPU compiler takes it for a v5e."""
    from paddle_tpu.fusion import moe
    assert moe.experts_tile(rows, width, 2048, 2) == (
        512 if (rows, width) == (32, 6144) else 256)
    f = lambda x, w, n, g, u, d: moe.experts(x, w, n, g, u, d,   # noqa: E731
                                             backend="pallas")
    args = [S((rows, width), BF16), S((held, rows, 1), F32), S((held,), I32),
            S((held, width, 2048), BF16), S((held, width, 2048), BF16),
            S((held, 2048, width), BF16)]
    assert _n_calls(_tpu_text(f, *args)) == 1
    args = [S(a.shape, a.dtype, sharding=one_chip) for a in args]
    text = jax.jit(f).lower(*args).compile().as_text()
    assert text.count("tpu_custom_call") == 1
    assert f"f32[{rows},{width}]" in text


# -- grouped queries over bfloat16 pools, and all 32 experts held -----------

_GQA = dict(nh=32, nkv=8, dh=64, bs=64, n_blocks=2048, table=48)


def _gqa_read(slots, positions):
    from paddle_tpu.fusion import paged_decode_attention
    g = _GQA
    pool = S((g["n_blocks"], g["nkv"], g["bs"] * g["dh"] // 128, 128), BF16)
    return (lambda q, k, v, t, p, r: paged_decode_attention(
                q, k, v, t, p, g["nh"], scale=g["dh"] ** -0.5, rows=r,
                backend="pallas"),
            [S((slots, positions, g["nh"] * g["dh"]), BF16), pool, pool,
             S((slots, g["table"]), I32), S((slots, 1, 1), F32),
             S((slots,), I32)])


@pytest.mark.parametrize("slots, positions", [(64, 1), (2, 128)])
def test_grouped_paged_kernel_compiles_for_v5e(one_chip, slots, positions):
    """The paged read of lfm2-8b-a1b_serve_assistant at its published widths:
    64 decode rows, and two prefill lanes of 128 positions, 32 query heads
    over 8 key/value heads of 64 in bfloat16 blocks of 64, a table of 48
    blocks: ONE Mosaic call, which the TPU compiler takes for a v5e."""
    f, args = _gqa_read(slots, positions)
    assert _n_calls(_tpu_text(f, *args)) == 1
    args = [S(a.shape, a.dtype, sharding=one_chip) for a in args]
    compiled = jax.jit(f).lower(*args).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1


@pytest.mark.parametrize("rows", [64, 320])
def test_all_held_expert_product_compiles_for_v5e(one_chip, rows):
    """The grouped expert product of that cell's decode tick (64 rows) and
    mixed tick (64 + 2 * 128): all 32 experts of 2048 x 1792 held, seven
    steps of 256 columns a touched expert (PR 51: the tile is the shape's)."""
    from paddle_tpu.fusion import moe
    args = [S((rows, 2048), BF16), S((32, rows, 1), F32), S((32,), I32),
            S((32, 2048, 1792), BF16), S((32, 2048, 1792), BF16),
            S((32, 1792, 2048), BF16)]
    f = lambda x, w, n, g, u, d: moe.experts(x, w, n, g, u, d,   # noqa: E731
                                             backend="pallas")
    assert _n_calls(_tpu_text(f, *args)) == 1
    args = [S(a.shape, a.dtype, sharding=one_chip) for a in args]
    assert jax.jit(f).lower(*args).compile().as_text().count(
        "tpu_custom_call") == 1


def test_equal_heads_float32_pools_keep_their_kernels():
    """The classic float32 pools with as many key/value heads as query heads
    still take `_paged_kernel` (one position) and the chunk kernel under its
    old name: the grouped path is taken by the pool's heads and dtype."""
    from paddle_tpu.fusion import paged_decode_attention
    pool = S((1024, 16, 8, 128), F32)
    for positions, scope in ((1, "paged_decode_attention"),
                             (128, "paged_chunk_attention")):
        text = _tpu_text(
            lambda q, k, v, t, p: paged_decode_attention(
                q, k, v, t, p, 16, scale=0.125, backend="pallas"),
            S((2, positions, 1024), F32), pool, pool, S((2, 18), I32),
            S((2, 1, 1), F32))
        assert _n_calls(text) == 1 and "paged_gqa_attention" not in text


# -- a sliding window beside full layers: heads of 128, two pools ------------

_WIN = dict(nh=64, nkv=8, dh=128, bs=64, n_blocks=512, table=272, window=128)


def _window_read(slots, positions, window):
    from paddle_tpu.fusion import paged_decode_attention
    g = _WIN
    pool = S((g["n_blocks"], g["nkv"], g["bs"] * g["dh"] // 128, 128), BF16)
    return (lambda q, k, v, t, p, r: paged_decode_attention(
                q, k, v, t, p, g["nh"], scale=g["dh"] ** -0.5, rows=r,
                backend="pallas", window=window),
            [S((slots, positions, g["nh"] * g["dh"]), BF16), pool, pool,
             S((slots, g["table"]), I32), S((slots, 1, 1), F32),
             S((slots,), I32)])


@pytest.mark.parametrize("slots, positions", [(32, 1), (2, 128)])
@pytest.mark.parametrize("window", [128, 0])
def test_window_paged_kernel_compiles_for_v5e(one_chip, slots, positions,
                                              window):
    """The paged reads of k-exaone-ep8_serve_long_sessions at its published
    widths: 32 decode rows, and two prefill lanes of 128 positions, 64 query
    heads over 8 key/value heads of 128 in bfloat16 blocks of 64, a table of
    272 blocks; bounded by the window of 128 (the four sliding layers) and
    unbounded (the full layer): ONE Mosaic call each, which the TPU compiler
    takes for a v5e, and the two carry different names in the trace."""
    f, args = _window_read(slots, positions, window)
    lowered = jax.jit(f).trace(*args).lower(lowering_platforms=("tpu",))
    assert _n_calls(lowered.as_text()) == 1
    named = lowered.as_text(debug_info=True)
    assert ("paged_window_attention" in named) == bool(window)
    assert ("paged_gqa_attention" in named) == (not window)
    args = [S(a.shape, a.dtype, sharding=one_chip) for a in args]
    compiled = jax.jit(f).lower(*args).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1


def test_a_windowless_read_lowers_to_the_text_it_had():
    """`window` 0 is no argument: the lowered text of the grouped read is the
    same with and without it."""
    f0, args = _window_read(32, 1, 0)
    from paddle_tpu.fusion import paged_decode_attention
    g = _WIN
    plain = lambda q, k, v, t, p, r: paged_decode_attention(   # noqa: E731
        q, k, v, t, p, g["nh"], scale=g["dh"] ** -0.5, rows=r,
        backend="pallas")
    assert _tpu_text(f0, *args) == _tpu_text(plain, *args)


# -- the state-space decode update, latent experts, two key/value heads ------

def _ssm_update(slots=64):
    from paddle_tpu.fusion import ssm
    h, p, g, n = 128, 64, 8, 128
    args = [S((slots, h, p, n), F32), S((slots,), F32), S((slots, h, p), BF16),
            S((slots, g, n), BF16), S((slots, g, n), BF16), S((slots, h), F32),
            S((slots, h), F32)]
    return (lambda st, live, x, b, c, dt, dec: ssm.ssm_decode_update(
        st, live, x, b, c, dt, dec, backend="pallas")), args


def test_ssm_decode_update_compiles_for_v5e_in_place(one_chip):
    """The decode update at the published widths (64 slots of 128 heads x 64
    x 128 float32, 4 MB a slot: a whole slot a grid step, 17 MB of VMEM
    double-buffered) through the TPU compiler for a v5e, the state aliased
    onto its input."""
    f, args = _ssm_update()
    text = _tpu_text(f, *args)
    assert _n_calls(text) == 1 and "ssm_decode_update" in jax.jit(f).trace(
        *args).lower(lowering_platforms=("tpu",)).as_text(debug_info=True)
    assert "output_operand_aliases" in text or "operand_index" in text
    args = [S(a.shape, a.dtype, sharding=one_chip) for a in args]
    compiled = jax.jit(f, donate_argnums=0).lower(*args).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1
    assert "f32[64,128,1,64]" in compiled.as_text()


def test_ssm_decode_update_compiles_for_v5e_at_heads_of_128_by_256(one_chip):
    """The decode update at Falcon-H1's widths (ISSUE 54: 16 slots of 32
    heads x 128 x 256 float32 over 2 groups, 4 MB a slot like the heads of
    64 x 128 above) through the TPU compiler for a v5e, in place."""
    from paddle_tpu.fusion import ssm
    slots, h, p, g, n = 16, 32, 128, 2, 256
    args = [S((slots, h, p, n), F32), S((slots,), F32), S((slots, h, p), BF16),
            S((slots, g, n), BF16), S((slots, g, n), BF16), S((slots, h), F32),
            S((slots, h), F32)]
    f = lambda st, live, x, b, c, dt, dec: ssm.ssm_decode_update(  # noqa: E731
        st, live, x, b, c, dt, dec, backend="pallas")
    assert _n_calls(_tpu_text(f, *args)) == 1
    args = [S(a.shape, a.dtype, sharding=one_chip) for a in args]
    compiled = jax.jit(f, donate_argnums=0).lower(*args).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1
    assert "f32[16,32,1,128]" in compiled.as_text()


@pytest.mark.parametrize("slots, positions", [(16, 1), (2, 128)])
def test_grouped_paged_kernel_compiles_at_five_queries_a_kv_head(
        one_chip, slots, positions):
    """The paged read of falcon-h1-34b-pp12_serve_long_prompts at its
    published widths (ISSUE 54): 16 decode rows, and two prefill lanes of 128
    positions, 20 query heads over 4 key/value heads of 128 (FIVE a group,
    not a power of two: padded to a sublane tile in the decode read, 640 rows
    a lane in the chunk read) in bfloat16 blocks of 64, a table of 200
    blocks: ONE Mosaic call, which the TPU compiler takes for a v5e."""
    from paddle_tpu.fusion import paged_decode_attention
    nh, nkv, dh = 20, 4, 128
    pool = S((3264, nkv, 64 * dh // 128, 128), BF16)
    f = lambda q, k, v, t, p, r: paged_decode_attention(  # noqa: E731
        q, k, v, t, p, nh, scale=dh ** -0.5, rows=r, backend="pallas")
    args = [S((slots, positions, nh * dh), BF16), pool, pool,
            S((slots, 200), I32), S((slots, 1, 1), F32), S((slots,), I32)]
    assert _n_calls(_tpu_text(f, *args)) == 1
    args = [S(a.shape, a.dtype, sharding=one_chip) for a in args]
    text = jax.jit(f).lower(*args).compile().as_text()
    assert text.count("tpu_custom_call") == 1
    if positions == 1:          # the shape gqa_decode_roofline keys
        assert "f32[16,4,8,128]" in text


@pytest.mark.parametrize("rows", [64, 320])
def test_latent_expert_product_compiles_for_v5e(one_chip, rows):
    """128 held experts of 1024 x 2688 and 2688 x 1024, seven steps of 384
    columns a touched expert (PR 51; a whole expert a step before it), under
    the decode tick's 64 rows and the mixed tick's 64 + 2 * 128."""
    from paddle_tpu.fusion import moe
    args = [S((rows, 1024), BF16), S((128, rows, 1), F32), S((128,), I32),
            S((128, 1024, 2688), BF16), S((128, 2688, 1024), BF16)]
    f = lambda x, w, n, u, d: moe.experts(x, w, n, None, u, d,  # noqa: E731
                                          backend="pallas")
    assert _n_calls(_tpu_text(f, *args)) == 1
    args = [S(a.shape, a.dtype, sharding=one_chip) for a in args]
    text = jax.jit(f).lower(*args).compile().as_text()
    assert text.count("tpu_custom_call") == 1
    assert f"f32[{rows},1024]" in text


@pytest.mark.parametrize("slots, positions", [(64, 1), (2, 128)])
def test_two_kv_head_read_compiles_for_v5e(one_chip, slots, positions):
    """32 query heads over 2 key/value heads of 128: a head is a whole
    128-lane pool row and a group of 16 is two sublane tiles of query rows
    (the assistant cell's heads are halves of a row in groups of 4)."""
    from paddle_tpu.fusion import paged_decode_attention
    pool = S((2048, 2, 64, 128), BF16)
    args = [S((slots, positions, 4096), BF16), pool, pool,
            S((slots, 24), I32), S((slots, 1, 1), F32), S((slots,), I32)]
    f = lambda q, k, v, t, p, r: paged_decode_attention(  # noqa: E731
        q, k, v, t, p, 32, scale=128 ** -0.5, backend="pallas", rows=r)
    args = [S(a.shape, a.dtype, sharding=one_chip) for a in args]
    assert jax.jit(f).lower(*args).compile().as_text().count(
        "tpu_custom_call") == 1


# -- the training cell of grouped heads, a window and routed experts ----------

@pytest.mark.parametrize("window, scope", [
    (1024, "streamed_q512_k512_g8_w1024"), (0, "streamed_q1024_k1024_g8")])
def test_grouped_window_flash_compiles_for_v5e(one_chip, window, scope):
    """The flash forward and backward at the 8k training cell's shape: 32
    query heads of 128 over 4 key/value heads, token-major operands as the
    projections leave them (the op transposes: the plan is streamed), under
    the window of 1,024 and without: three Mosaic kernels each, named by
    their plan."""
    B, H, KV, T, D = 1, 32, 4, 8192, 128

    def fwd_bwd(q, k, v, do):
        out, vjp = jax.vjp(
            lambda q, k, v: pallas_kernels._attend(
                q, k, v, None, D ** -0.5, True, "pallas", H, window), q, k, v)
        return (out,) + vjp(do)

    q = S((B, T, H * D), jnp.bfloat16, sharding=one_chip)
    k = S((B, T, KV * D), jnp.bfloat16, sharding=one_chip)
    text = jax.jit(fwd_bwd).lower(q, k, k, q).compile().as_text()
    assert text.count("tpu_custom_call") == 3
    for kernel in ("fwd", "bwd_dq", "bwd_dkv"):
        assert f"flash_{kernel}_{scope}" in text


def test_training_expert_product_compiles_for_v5e(one_chip):
    """The routed layer's training kernels at the 8k training cell's shape
    (8,192 rows of 2,304, top-8 of 64, 16 held experts of width 896, ONE
    pair buffer of all m = 65,536 pairs), forward and backward: megablox's
    six products under fusion/moe.py's tilings fit the scoped VMEM PR 50's
    other tilings were refused at (17 MiB); the row gather and the sum
    back, which hold their float32 side whole in VMEM, and the elementwise
    step and its derivative compile beside them under limits of their
    own."""
    from paddle_tpu.fusion import moe
    N, D, F, E, H, K = 8192, 2304, 896, 64, 16, 8
    assert moe.rows_lowering(S((N, D), jnp.float32), N * K, jnp.bfloat16,
                             "pallas") == moe.KERNEL

    def fwd_bwd(x, idx, w, gate, up, down):
        def layer(x, w, gate, up, down):
            return moe.train_experts(x, idx, w, tuple(range(H)), E, gate, up,
                                     down, backend="pallas")[0]
        out, vjp = jax.vjp(layer, x, w, gate, up, down)
        return (out,) + vjp(out)

    args = [S((N, D), jnp.float32), S((N, K), jnp.int32),
            S((N, K), jnp.float32), S((H, D, F), jnp.float32),
            S((H, D, F), jnp.float32), S((H, F, D), jnp.float32)]
    args = [S(a.shape, a.dtype, sharding=one_chip) for a in args]
    text = jax.jit(fwd_bwd).lower(*args).compile().as_text()
    assert moe.total_lowering(S((N * K, D), jnp.bfloat16), N,
                              "pallas") == moe.KERNEL
    # forward: the gather, 2 products, the step between them, the sum back;
    # backward: the rows' and the cotangent's gathers, the step's
    # derivative, 2 transposed products, 2 weight gradients, the sum back
    assert text.count("tpu_custom_call") == 5 + 8
    for scope in ("rows", "total", "gate", "gate_bwd", "in", "out", "in_t",
                  "out_t", "in_w", "out_w"):
        assert f"moe_train_{scope}/" in text, scope


def _kda_update(slots=96, h=32, d=128):
    from paddle_tpu.fusion import kda
    args = [S((slots, h, d, d), F32), S((slots,), F32), S((slots, h, d), F32),
            S((slots, h, d), F32), S((slots, h, d), BF16),
            S((slots, h, d), F32), S((slots, h), F32)]
    return (lambda st, live, q, k, v, g, beta: kda.kda_decode_update(
        st, live, q, k, v, g, beta, backend="pallas")), args


def test_kda_decode_update_compiles_for_v5e_in_place(one_chip):
    """The delta-rule decode update at the published widths (ISSUE 59: 96
    slots of 32 heads x 128 x 128 float32, 2 MB a slot: a whole slot a grid
    step) through the TPU compiler for a v5e, the state aliased onto its
    input; the name `kda_decode_roofline` keys is the result's shape."""
    f, args = _kda_update()
    text = _tpu_text(f, *args)
    assert _n_calls(text) == 1 and "kda_decode" in jax.jit(f).trace(
        *args).lower(lowering_platforms=("tpu",)).as_text(debug_info=True)
    assert "output_operand_aliases" in text or "operand_index" in text
    args = [S(a.shape, a.dtype, sharding=one_chip) for a in args]
    compiled = jax.jit(f, donate_argnums=0).lower(*args).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1
    assert "f32[96,32,128]" in compiled.as_text()


def test_kda_scan_compiles_for_v5e_at_the_lanes_shape(one_chip):
    """One kda layer of the MIXED tick at the published widths (96 decode
    rows and two lanes of 128 rows: the decode kernel and the chunked form,
    its sub-blocks of 16 rows, the snapshot pool of 32) through the TPU
    compiler for a v5e: one Mosaic call, the rest XLA."""
    from paddle_tpu.fusion import kda
    slots, lanes, chunk, h, d, snaps = 96, 2, 128, 32, 128, 32
    n = slots + lanes * chunk
    args = [S((n, 3 * h * d), BF16), S((n, h * d), BF16), S((n, h), BF16),
            S((3 * h * d, 4), BF16), S((h,), F32), S((h * d,), F32),
            S((slots, h, d, d), F32), S((slots, 3, 3 * h * d), BF16),
            S((slots,), F32), S((snaps, h, d, d), F32),
            S((snaps, 3, 3 * h * d), BF16)]
    args += [S((lanes,), I32)] * 6

    def f(qkv, fr, br, taps, a_log, dt_bias, slot_s, slot_conv, live, snap_s,
          snap_conv, lpos, lrows, lslot, src, dst, snap_rows):
        return kda.kda_scan(
            qkv, fr, br, taps, a_log, dt_bias, slot_s, slot_conv, live,
            (h, d, -5.0), (snap_s, snap_conv, lpos, lrows, lslot, src, dst,
                           snap_rows, chunk), backend="pallas")
    assert _n_calls(_tpu_text(f, *args)) == 1
    args = [S(a.shape, a.dtype, sharding=one_chip) for a in args]
    compiled = jax.jit(f, donate_argnums=(6, 7, 9, 10)).lower(*args).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1


def test_kda_decode_update_compiles_for_v5e_at_64_heads(one_chip):
    """ISSUE 61: 64 slots of 64 heads x 128 x 128 float32, 4.19 MB a slot
    and grid step, twice the reasoning cell's."""
    f, args = _kda_update(slots=64, h=64)
    args = [S(a.shape, a.dtype, sharding=one_chip) for a in args]
    compiled = jax.jit(f, donate_argnums=0).lower(*args).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1
    assert "f32[64,64,128]" in compiled.as_text()


def _sparse_read(slots, lanes, chunk=128, n_blocks=1024, nlb=552, bs=64,
                 nh=64, c=512, ni=32, di=128, top=512, kpool=4):
    from paddle_tpu.fusion import sparse_latent_attention as sla
    n = slots + lanes * chunk
    args = [S((n, 1, nh * c), BF16), S((n_blocks, 1, bs, c), BF16),
            S((n_blocks, 1, bs // kpool, di), BF16), S((n, ni * di), BF16),
            S((n, di), BF16), S((n, ni), F32), S((n, 1, 1), F32),
            S((nlb * bs, 64), F32), S((slots, nlb), I32), S((slots,), I32),
            S((slots,), I32)]
    if lanes:
        args += [S((lanes, nlb), I32), S((lanes * chunk // bs,), I32),
                 S((lanes,), I32)]

    def f(q, pool, ipool, qi, ki, wi, pos, table, btab, wblock, woff,
          *lane):
        return sla.sparse_latent_attention(
            q, pool, ipool, qi, ki, wi, pos, table, btab, wblock, woff,
            (*lane, chunk) if lane else None, num_heads=nh, v_width=c,
            scale=256 ** -0.5, index_heads=ni, top_groups=top, kpool=kpool,
            backend="pallas")
    return f, args


@pytest.mark.parametrize("slots, lanes", [(64, 0), (64, 2)])
def test_sparse_latent_read_compiles_for_v5e(one_chip, slots, lanes):
    """The sparse latent read at the repository cell's widths (ISSUE 61: 64
    decode rows, and with them two lanes of 128; 64 heads over rows of 512
    values, 32 index heads of 128 over 8,832 pooled keys a request, the best
    512 groups of 4 and the tail) through the TPU compiler for a v5e: the
    index pool written in place, ONE Mosaic call (ISSUE 62: the fetch kernel,
    `sparse_fetch`, a DMA a picked group's 8-row chunk from the pool as it
    lies, decode rows and lanes alike), the rest XLA. And NO instruction
    regroups the pool (1,024 blocks here: `bf16[16384,4,512]`) or a scratch
    of picked groups (`bf16[<any>,4,512]`), or gathers one by blocks: PR 61
    copied 537 MB on every tick and 692 MB more on a mixed one that way.
    ISSUE 64: the selection's ONE sort is of 8 rows and stands in the body
    of a `while` under `dsa_index` whose trip count the device takes from the
    live rows (no sort over all 64 or 320 rows is left), as do the index
    pool's gather and the scores; the fetch kernel stands outside, once."""
    f, args = _sparse_read(slots, lanes)
    assert _n_calls(_tpu_text(f, *args)) == 1
    args = [S(a.shape, a.dtype, sharding=one_chip) for a in args]
    compiled = jax.jit(f, donate_argnums=2).lower(*args).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    n = slots + lanes * 128
    assert f"bf16[{n},64,512]" in text
    assert "sparse_fetch" in text and "latent_paged_attention" not in text
    assert not re.search(r"bf16\[\d+,4,512\]", text)
    assert f"bf16[{1 + n * 33},1,64,512]" not in text
    assert "bf16[65536,512]{1,0:T(8,128)(2,1)} bitcast(" in text
    loops = re.findall(r" while\(.*op_name=\"[^\"]*dsa_index/", text)
    assert len(loops) == (3 if lanes else 2)
    sorts = re.findall(
        r"= \(f32\[(\d+),8832\].* sort\(.*op_name=\"([^\"]*)\"", text)
    assert [int(rows) for rows, _ in sorts] == [8]
    assert "dsa_index/while/body" in sorts[0][1]
    assert not re.search(r"bf16\[(64|320),8832,128\]", text)


def test_clamped_expert_walk_compiles_for_v5e(one_chip):
    """The walk's gated body with `swiglu_limit` 10 at the repository cell's
    widths: 36 held experts of 4096 x 2048, the mixed tick's 320 rows."""
    from paddle_tpu.fusion import moe
    held, d, f_, rows = 36, 4096, 2048, 320
    args = [S((rows, d), BF16), S((held, rows, 1), F32), S((held,), I32),
            S((held, d, f_), BF16), S((held, d, f_), BF16),
            S((held, f_, d), BF16)]

    def f(x, w, n, gate, up, down):
        return moe.experts(x, w, n, gate, up, down, backend="pallas",
                           limit=10.0)
    assert _n_calls(_tpu_text(f, *args)) == 1
    args = [S(a.shape, a.dtype, sharding=one_chip) for a in args]
    assert jax.jit(f).lower(*args).compile().as_text().count(
        "tpu_custom_call") == 1
