"""Fused decode-attention step: one KV-cache tick's QK^T·softmax·V in one
kernel.

≙ reference attention_lstm_fuse_pass.cc's fused attention step — the
reference fuses the decoder's per-step attention chain into one op; here
the chain is the cached-decode hot path (`models/transformer.py
_attend_cached`): matmul(q, K^T, alpha=scale) → +bias → softmax →
matmul(·, V), four kernels per tick per layer with the [.., 1, T]
score/weight tensors round-tripping HBM between them. The fused kernel
reads the cache ONCE and keeps scores/weights in VMEM. The cache WRITE
side stays on the existing `cache_write` dynamic-update-slice op — this
kernel only fuses the read side.

The query has exactly one position (the decode tick), so the score matrix
is [heads, T]: heads ride the sublane axis, cache positions the lane axis,
and the whole per-(batch·beam) computation is VPU element-wise + lane
reductions — decode attention is memory-bound, so the win is the single
pass over the cache, not MXU utilization.

Gradients (decode graphs are inference-only, but the op is registered
without `stop_gradient` for completeness): `jax.custom_vjp` whose backward
differentiates the identical XLA composite — exact.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..framework.registry import register_op

_NEG_INF = -1e30


def _auto_backend():
    from ..ops.pallas_kernels import _auto_backend as _ab
    return _ab()


def _round_up(n, m):
    return -(-n // m) * m


def _decode_xla(q4, k4, v4, bias4, scale):
    """Normalized-shape composite: q4 [R, nh, G, dh], k4/v4 [R, nh, T, dh],
    bias4 [R, nh, G, T]. G is 1 for the plain decode tick and γ+1 for a
    speculative verify forward. Replicates the unfused op chain's math
    exactly (matmul in f32 preferred type, alpha after, softmax last-axis).
    """
    s = jnp.matmul(q4, jnp.swapaxes(k4, -1, -2),
                   preferred_element_type=jnp.float32).astype(q4.dtype)
    s = s * scale + bias4
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.matmul(w, v4, preferred_element_type=jnp.float32)
    return out.astype(q4.dtype)


def _decode_step_kernel(q_ref, k_ref, v_ref, b_ref, o_ref, *, scale):
    q = q_ref[0].astype(jnp.float32)                 # [nh, 1, dh] -> [nh, dh]
    q = q[:, 0, :]
    k = k_ref[0].astype(jnp.float32)                 # [nh, T, dh]
    v = v_ref[0].astype(jnp.float32)
    bias = b_ref[0]                                  # [nh, T]
    s = jnp.sum(q[:, None, :] * k, axis=-1) * scale + bias       # [nh, T]
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    w = p / l
    o_ref[0] = jnp.sum(w[:, :, None] * v, axis=1)[:, None, :].astype(
        o_ref.dtype)


def _decode_pallas(q4, k4, v4, bias3, scale, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    r, nh, _, dh = q4.shape
    t = k4.shape[2]
    nhp = _round_up(nh, 8)
    tp = _round_up(t, 128)

    def pad(a, axis, target, value=0.0):
        if a.shape[axis] == target:
            return a
        spec = [(0, 0)] * a.ndim
        spec[axis] = (0, target - a.shape[axis])
        return jnp.pad(a, spec, constant_values=value)

    qf = pad(q4, 1, nhp)
    kf = pad(pad(k4, 1, nhp), 2, tp)
    vf = pad(pad(v4, 1, nhp), 2, tp)
    # padded cache columns must be dead under softmax
    bf = pad(pad(bias3, 1, nhp), 2, tp, value=_NEG_INF)

    out = pl.pallas_call(
        functools.partial(_decode_step_kernel, scale=scale),
        grid=(r,),
        in_specs=[
            pl.BlockSpec((1, nhp, 1, dh), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((1, nhp, tp, dh), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((1, nhp, tp, dh), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((1, nhp, tp), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, nhp, 1, dh), lambda i: (i, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((r, nhp, 1, dh), q4.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_vmem_limit_bytes(nhp, tp, dh)),
        interpret=interpret,
    )(qf, kf, vf, bf)
    return out[:, :nh]


# shape gate: logical bytes of one grid step's K/V/bias blocks
_VMEM_BUDGET_BYTES = 6 << 20


def _vmem_limit_bytes(nhp, tp, dh):
    """Scoped-VMEM limit the kernel asks Mosaic for. A [nhp, tp, dh] f32
    block is lane-padded to 128 in VMEM; the K and V blocks are each
    double-buffered and the two broadcast-multiply products are block-sized
    temporaries — six padded blocks, plus room for the [nhp, tp] score
    tiles. At the gate's largest shapes that is ~40 MiB of a v5e core's
    128 MiB; the compiler's 16 MiB default refuses anything past
    nh=16, T=512, dh=64."""
    return 6 * nhp * tp * max(dh, 128) * 4 + (4 << 20)


def _pallas_fits(nh, t, dh):
    """Mosaic-path gate: the K/V/bias blocks must fit the VMEM budget and
    dh (the lane axis of every block) must be sublane-packable — dh % 8,
    matching the flash kernels' proven D=64 tiling. Anything else takes
    the identical XLA composite (same policy as recurrent._pallas_ok)."""
    nhp = _round_up(nh, 8)
    tp = _round_up(t, 128)
    return (dh % 8 == 0
            and nhp * tp * (2 * dh + 1) * 4 <= _VMEM_BUDGET_BYTES)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _decode_attention(q4, k4, v4, bias4, scale, backend):
    if backend != "xla" and (
            q4.shape[2] != 1   # Mosaic kernel is single-position only —
                               # verify widening (G>1) takes the composite
            or not _pallas_fits(q4.shape[1], k4.shape[2], q4.shape[3])):
        backend = "xla"   # cache block would blow the VMEM budget
    if backend == "xla":
        return _decode_xla(q4, k4, v4, bias4, scale)
    return _decode_pallas(q4, k4, v4, bias4[:, :, 0, :], scale,
                          interpret=(backend == "pallas_interpret"))


def _decode_attention_fwd(q4, k4, v4, bias4, scale, backend):
    return (_decode_attention(q4, k4, v4, bias4, scale, backend),
            (q4, k4, v4, bias4))


def _decode_attention_bwd(scale, backend, res, g):
    q4, k4, v4, bias4 = res
    _, vjp = jax.vjp(
        lambda q_, k_, v_, b_: _decode_xla(q_, k_, v_, b_, scale),
        q4, k4, v4, bias4)
    return vjp(g)


_decode_attention.defvjp(_decode_attention_fwd, _decode_attention_bwd)


QUANT_KV_BLOCK_T = 8   # time-axis tile: one f32 scale per <=8 cache steps


def _fit_time_block(t, block):
    b = min(block, t)
    while t % b:
        b -= 1
    return b


def quantize_kv_time_blocks(kv, block=QUANT_KV_BLOCK_T):
    """Symmetric int8 quantization of a KV cache along the time axis.

    kv [..., T, dh] → (payload int8 [..., T, dh], scales f32 [..., T//bt])
    where bt is the largest divisor of T that is <= block, so the payload
    keeps the exact cache shape (no padding bytes). One scale covers a
    [bt, dh] tile per leading index — the time-local amax tracks the
    cache's per-step magnitude drift, which is what makes int8 caches
    viable for decode attention (same rationale as the gradient path's
    `quantize_blocks`, specialised to the cache layout)."""
    t, dh = kv.shape[-2], kv.shape[-1]
    bt = _fit_time_block(t, block)
    lead = kv.shape[:-2]
    tiles = jnp.asarray(kv, jnp.float32).reshape(lead + (t // bt, bt, dh))
    amax = jnp.max(jnp.abs(tiles), axis=(-1, -2), keepdims=True)
    sc = jnp.where(amax > 0, amax / 127.0, 1.0).astype(jnp.float32)
    q = jnp.clip(jnp.round(tiles / sc), -127, 127).astype(jnp.int8)
    return q.reshape(kv.shape), sc.reshape(lead + (t // bt,))


def dequantize_kv_time_blocks(q, scales, dtype=jnp.float32):
    """Inverse of `quantize_kv_time_blocks`: payload int8 [..., T, dh] +
    scales [..., T//bt] → dequantized [..., T, dh] in `dtype`."""
    t, dh = q.shape[-2], q.shape[-1]
    nb = scales.shape[-1]
    bt = t // nb
    lead = q.shape[:-2]
    tiles = q.astype(jnp.float32).reshape(lead + (nb, bt, dh))
    out = tiles * scales[..., :, None, None]
    return out.reshape(q.shape).astype(dtype)


def fused_decode_attention(q, k, v, bias, scale=1.0, backend=None,
                           k_scale=None, v_scale=None):
    """One decode tick of cached attention in one kernel.

    q [..., nh, G, dh] (G query positions: 1 for the plain decode tick,
    γ+1 for a speculative verify forward), k/v [..., nh, T, dh] (the KV
    cache), bias broadcastable to [..., nh, G, T] (additive mask hiding
    cache positions beyond each query's tick — causal within the verify
    window). Returns [..., nh, G, dh]. Equals matmul(q, k^T)*scale + bias
    → softmax → matmul(·, v) exactly. G == 1 may take the Pallas kernel;
    G > 1 always lowers through the identical XLA composite.

    Quantized variant: pass int8 k/v payloads plus `k_scale`/`v_scale`
    from `quantize_kv_time_blocks` (f32 [..., nh, T//bt]); the caches are
    dequantized per time block inside the lowering before the math —
    XLA fuses the rescale into the single cache read, so the HBM traffic
    is the int8 payload, not the f32 cache.
    """
    backend = backend or _auto_backend()
    if k_scale is not None:
        k = dequantize_kv_time_blocks(k, k_scale, dtype=q.dtype)
    if v_scale is not None:
        v = dequantize_kv_time_blocks(v, v_scale, dtype=q.dtype)
    lead = q.shape[:-3]
    nh, g, dh = q.shape[-3], q.shape[-2], q.shape[-1]
    t = k.shape[-2]
    r = 1
    for d in lead:
        r *= d
    q4 = q.reshape((r, nh, g, dh))
    k4 = jnp.broadcast_to(k, lead + k.shape[-3:]).reshape((r, nh, t, dh))
    v4 = jnp.broadcast_to(v, lead + v.shape[-3:]).reshape((r, nh, t, dh))
    bias4 = jnp.broadcast_to(
        bias, lead + (nh, g, t)).reshape((r, nh, g, t)).astype(jnp.float32)
    out = _decode_attention(q4, k4, v4, bias4, float(scale), backend)
    return out.reshape(lead + (nh, g, dh))


@register_op("fused_decode_attention")
def _fused_decode_attention_op(ctx, ins, attrs):
    """Fused Q·K^T+bias→softmax→·V over a KV cache for a single-position
    query (emitted by `fuse_decode_attention_pass` from the 4-op decode
    chain)."""
    q, k, v = ins["Q"][0], ins["K"][0], ins["V"][0]
    bias = ins["Bias"][0]
    ks = ins.get("KScale")
    vs = ins.get("VScale")
    backend = attrs.get("backend") or _auto_backend()
    out = fused_decode_attention(q, k, v, bias,
                                 scale=attrs.get("scale", 1.0),
                                 backend=backend,
                                 k_scale=ks[0] if ks else None,
                                 v_scale=vs[0] if vs else None)
    return {"Out": [out]}
