"""Collective primitives over mesh axes.

≙ reference operators/nccl_op.cc:24-93 (raw AllReduce/Reduce/Bcast ops) and
platform/nccl_helper.h — except on TPU these are *compiled into* the program
as XLA HLO collectives riding the ICI, not runtime library calls. These
wrappers exist so higher layers (tensor_parallel, pipeline, ring_attention)
speak one vocabulary; inside `shard_map` they lower to psum/all_gather/
ppermute HLOs.
"""

from __future__ import annotations

import functools
from typing import Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..core.enforce import InvalidArgumentError, enforce
from .mesh import DeviceMesh


def axis_size(axis_name: str) -> int:
    """Concrete size of a named mesh axis, valid inside shard_map/pmap
    (psum of the literal 1 constant-folds to the axis size at trace time)."""
    return jax.lax.psum(1, axis_name)


def all_reduce(x, axis_name: str):
    """Sum across an axis (≙ ncclAllReduce, all_reduce_op_handle.cc)."""
    return jax.lax.psum(x, axis_name)


def all_reduce_mean(x, axis_name: str):
    return jax.lax.pmean(x, axis_name)


def reduce_scatter(x, axis_name: str, scatter_dim: int = 0):
    """≙ the Reduce-to-owner half of ReduceOpHandle (reduce_op_handle.h:34),
    generalized: every shard owns a slice of the reduction."""
    n = axis_size(axis_name)
    # guards raise with full context but build their message only on the
    # failing path — these run inside traced hot loops (same de-f-string
    # discipline as memory.update_watermark)
    if not 0 <= scatter_dim < x.ndim:
        raise InvalidArgumentError(
            f"reduce_scatter: scatter_dim {scatter_dim} out of range for "
            f"rank-{x.ndim} input")
    if x.shape[scatter_dim] % n != 0:
        raise InvalidArgumentError(
            f"reduce_scatter: dim {scatter_dim} of shape {tuple(x.shape)} is "
            f"not divisible by the {axis_name!r} axis size {n}; pad the "
            f"scattered dimension to a multiple of {n} (each shard owns an "
            f"equal slice of the reduction) or scatter a different dim")
    return jax.lax.psum_scatter(x, axis_name, scatter_dimension=scatter_dim,
                                tiled=True)


def all_gather(x, axis_name: str, gather_dim: int = 0):
    """≙ BroadcastOpHandle capability (broadcast_op_handle.h:35)."""
    return jax.lax.all_gather(x, axis_name, axis=gather_dim, tiled=True)


def all_to_all(x, axis_name: str, split_dim: int, concat_dim: int):
    return jax.lax.all_to_all(x, axis_name, split_axis=split_dim,
                              concat_axis=concat_dim, tiled=True)


def ppermute(x, axis_name: str, perm: Sequence[tuple]):
    return jax.lax.ppermute(x, axis_name, perm=perm)


def ring_perm(axis_size: int) -> list:
    """The forward ring permutation shard i -> (i+1) % n — the one schedule
    shared by ring attention and the pipeline."""
    return [(i, (i + 1) % axis_size) for i in range(axis_size)]


def shift_right(x, axis_name: str, axis_size: int):
    """Ring shift: shard i -> shard (i+1) % n. Building block for ring
    attention and pipelining."""
    return jax.lax.ppermute(x, axis_name, perm=ring_perm(axis_size))


def shift_left(x, axis_name: str, axis_size: int):
    perm = [((i + 1) % axis_size, i) for i in range(axis_size)]
    return jax.lax.ppermute(x, axis_name, perm=perm)


def axis_index(axis_name: str):
    return jax.lax.axis_index(axis_name)


def sharded(mesh: DeviceMesh, in_specs, out_specs,
            check_vma: bool = False) -> Callable:
    """Decorator: run fn as per-shard SPMD code over `mesh` (shard_map).

    This is the escape hatch from the "annotate & let XLA partition" world
    into explicit per-device code — used where the collective schedule IS the
    algorithm (ring attention, pipeline), mirroring how the reference drops
    from graph building into hand-written op handles.
    """
    def deco(fn):
        smapped = jax.shard_map(fn, mesh=mesh.jax_mesh, in_specs=in_specs,
                                out_specs=out_specs, check_vma=check_vma)
        return functools.wraps(fn)(smapped)
    return deco


# ---------------------------------------------------------------------------
# Quantized collectives (block-scaled compress -> collective -> decompress).
#
# ≙ EQuARX (PAPERS.md): on the wire a gradient travels as int8 payload plus
# one f32 scale per block instead of f32 — ~4x fewer bytes with block-local
# dynamic range. The cross-replica SUM is decomposed into the same two
# phases XLA uses for a ring all-reduce (reduce-scatter, then all-gather),
# but each phase's transfer is quantized by US before it hits the wire:
#
#   phase 1: every shard splits its local partial into `axis` chunks,
#            quantizes each destination chunk independently, all_to_all's
#            the (payload, scales) pair, and dequant-sums what it received
#            -> shard i owns the fully reduced chunk i, fp32.
#   phase 2: the owner re-quantizes its reduced chunk and all_gather's it.
#
# The fp32 accumulation in phase 1 keeps the sum exact given the quantized
# contributions (no int overflow, no precision loss across `axis` adds);
# the only approximation is the two quantization steps, which the optional
# error-feedback state (grad_comm.py) compensates across steps.
# ---------------------------------------------------------------------------

QUANT_BLOCK = 256           # default block: one f32 scale per 256 values
_QUANT_WIRE_DTYPES = ("int8", "bf16")


def quantize_blocks(flat, block: int = QUANT_BLOCK):
    """Block-scaled symmetric int8 quantization of a flat f32 vector whose
    length is a multiple of `block`. Returns (q int8 [n//block, block],
    scales f32 [n//block, 1]); zero blocks get scale 1 so they stay exact."""
    if flat.ndim != 1 or flat.shape[0] % block != 0:
        raise InvalidArgumentError(
            f"quantize_blocks wants a flat block-multiple vector, got shape "
            f"{tuple(flat.shape)} for block {block}")
    xb = flat.reshape(-1, block)
    amax = jnp.max(jnp.abs(xb), axis=1, keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0).astype(jnp.float32)
    q = jnp.clip(jnp.round(xb / scale), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_blocks(q, scale):
    """Inverse of quantize_blocks: flat f32 vector."""
    return (q.astype(jnp.float32) * scale).reshape(-1)


# ---------------------------------------------------------------------------
# 2-D block quantization for weights-at-rest (r21 weight-only serving).
#
# The wire path above scales per contiguous 1-D run; weights want per-tile
# scales so a single outlier row does not flatten a whole matrix. Tiles are
# (br, bc) sub-blocks of the 2-D weight; each tile gets one f32 scale.
# Int4 halves the payload again by packing two nibbles per int8 byte along
# the column axis (column count must be even).
# ---------------------------------------------------------------------------

QUANT_BLOCK_2D = 64         # default tile edge: one f32 scale per <=64x64 tile


def block_dims_2d(shape, block: int = QUANT_BLOCK_2D):
    """Largest tile dims <= `block` that divide each axis of `shape` exactly
    (falls back toward 1, which always divides), so payloads keep the exact
    declared weight shape — no padding bytes to reconcile in the census."""
    def fit(n):
        b = min(block, n)
        while n % b:
            b -= 1
        return b
    return fit(shape[0]), fit(shape[1])


def quantize_blocks_2d(w, bits: int = 8, block: int = QUANT_BLOCK_2D):
    """Tile-scaled symmetric quantization of a 2-D f32 matrix.

    Returns (payload int8 [R, C] — or [R, C//2] nibble-packed when bits=4 —
    and scales f32 [R//br, C//bc]). Zero tiles get scale 1 so they stay
    exact; int4 clips to [-7, 7] before packing.
    """
    if w.ndim != 2:
        raise InvalidArgumentError(
            f"quantize_blocks_2d wants a 2-D matrix, got shape "
            f"{tuple(w.shape)}")
    if bits not in (8, 4):
        raise InvalidArgumentError(
            f"quantize_blocks_2d supports bits in (8, 4), got {bits}")
    r, c = w.shape
    if bits == 4 and c % 2 != 0:
        raise InvalidArgumentError(
            f"int4 packing needs an even column count, got shape "
            f"{tuple(w.shape)}")
    br, bc = block_dims_2d(w.shape, block)
    t = jnp.asarray(w, jnp.float32).reshape(r // br, br, c // bc, bc)
    amax = jnp.max(jnp.abs(t), axis=(1, 3), keepdims=True)
    qmax = 127.0 if bits == 8 else 7.0
    scale = jnp.where(amax > 0, amax / qmax, 1.0).astype(jnp.float32)
    q = jnp.clip(jnp.round(t / scale), -qmax, qmax).astype(jnp.int8)
    q = q.reshape(r, c)
    if bits == 4:
        q = pack_int4(q)
    return q, scale.reshape(r // br, c // bc)


def dequantize_blocks_2d(q, scales, bits: int = 8):
    """Inverse of quantize_blocks_2d: f32 matrix [R, C]. `scales` carries the
    tile grid [R//br, C//bc]; the payload is nibble-unpacked when bits=4."""
    if bits == 4:
        q = unpack_int4(q)
    r, c = q.shape
    nr, nc = scales.shape
    t = q.astype(jnp.float32).reshape(nr, r // nr, nc, c // nc)
    return (t * scales[:, None, :, None]).reshape(r, c)


def pack_int4(q):
    """Pack an int8 matrix with values in [-7, 7] into nibbles: columns
    (2k, 2k+1) share byte k as (low, high). Returns int8 [R, C//2]."""
    lo = q[:, 0::2]
    hi = q[:, 1::2]
    return ((lo & jnp.int8(0x0F)) | (hi << 4)).astype(jnp.int8)


def unpack_int4(p):
    """Inverse of pack_int4: int8 [R, C2] -> int8 [R, 2*C2]. Sign-extends
    each nibble via arithmetic shifts (two's complement)."""
    lo = ((p << 4).astype(jnp.int8) >> 4).astype(jnp.int8)
    hi = (p >> 4).astype(jnp.int8)
    return jnp.stack([lo, hi], axis=-1).reshape(p.shape[0], -1)


def _compress(flat, wire_dtype: str, block: int):
    """flat f32 -> (payload, scales-or-None) in the wire dtype."""
    if wire_dtype == "int8":
        return quantize_blocks(flat, block)
    if wire_dtype == "bf16":
        return flat.astype(jnp.bfloat16), None
    raise InvalidArgumentError(
        f"unknown comm wire dtype {wire_dtype!r}; "
        f"expected one of {_QUANT_WIRE_DTYPES}")


def _decompress(payload, scales):
    if scales is None:
        return payload.astype(jnp.float32).reshape(-1)
    return dequantize_blocks(payload, scales)


def _pin_wire(payload, scales):
    """Best-effort pin of the COMPRESSED dtype on the wire. The bf16
    path is an exact round-trip (the f32 -> bf16 -> f32 widening loses
    nothing the narrowing didn't already drop), so a simplifier may
    legally commute the widening convert across the collective; the
    optimization barriers keep each convert on its own side of the
    transfer on backends whose collectives carry bf16 natively (TPU).
    KNOWN LIMIT, census-measured (r19 planner bench): this container's
    jaxlib-0.4.x CPU backend promotes the bf16 collective payload to
    f32 REGARDLESS (it inserts its own converts and elides the
    barriers), so on the CPU mesh the bf16 wire census reads exactly 2x
    the analytic model — which is why the auto-parallel planner's
    DEFAULT space searches int8 but not bf16 (auto_parallel.
    SearchSpace); the bf16 claim stays a TPU re-measure item. int8
    needs no pin: its dequant multiplies by per-block scales, which
    nothing can hoist."""
    if scales is None:
        payload = jax.lax.optimization_barrier(payload)
    return payload


def compressed_size_ratio(wire_dtype: str, block: int = QUANT_BLOCK) -> float:
    """Analytic bytes-on-wire ratio vs f32 for one compressed transfer."""
    if wire_dtype == "int8":
        return (1.0 + 4.0 / block) / 4.0
    if wire_dtype == "bf16":
        return 0.5
    return 1.0


def quantized_reduce_scatter_flat(flat, axis_name: str, *,
                                  wire_dtype: str = "int8",
                                  block: int = QUANT_BLOCK,
                                  mean: bool = False):
    """Phase 1 of the quantized all-reduce: each shard contributes its local
    partial `flat` (length divisible by the axis size) and receives the fully
    reduced chunk it owns, fp32, length len(flat)//axis_size. Each
    destination chunk is compressed independently (block padding included) so
    the chunk boundary never splits a scale block."""
    n = axis_size(axis_name)
    if flat.ndim != 1 or flat.shape[0] % n != 0:
        raise InvalidArgumentError(
            f"quantized_reduce_scatter_flat wants a flat vector divisible by "
            f"the {axis_name!r} axis size {n}, got {tuple(flat.shape)}")
    chunk = flat.shape[0] // n
    cpad = -(-chunk // block) * block
    xb = flat.reshape(n, chunk)
    xb = jnp.pad(xb, ((0, 0), (0, cpad - chunk)))
    payload, scales = _compress(xb.reshape(-1), wire_dtype, block)
    # all_to_all the per-destination compressed chunks: shard i ends up
    # holding every peer's compressed version of chunk i
    payload = _pin_wire(payload, scales)
    payload = payload.reshape(n, -1, *payload.shape[1:])
    payload = jax.lax.all_to_all(payload, axis_name, split_axis=0,
                                 concat_axis=0, tiled=True)
    payload = _pin_wire(payload, scales)
    if scales is not None:
        scales = scales.reshape(n, -1, *scales.shape[1:])
        scales = jax.lax.all_to_all(scales, axis_name, split_axis=0,
                                    concat_axis=0, tiled=True)
        part = (payload.astype(jnp.float32) * scales)
    else:
        part = payload.astype(jnp.float32)
    part = part.reshape(n, cpad).sum(axis=0)[:chunk]
    if mean:
        part = part / n
    return part


def quantization_residual_flat(flat, n: int, *, wire_dtype: str = "int8",
                               block: int = QUANT_BLOCK):
    """What phase 1 loses for THIS shard's contribution: flat minus the
    dequantized form of its compressed transfer, under the exact
    per-destination-chunk padded block layout quantized_reduce_scatter_flat
    puts on the wire. This is the error-feedback accumulator's update."""
    chunk = flat.shape[0] // n
    cpad = -(-chunk // block) * block
    xb = jnp.pad(flat.reshape(n, chunk), ((0, 0), (0, cpad - chunk)))
    payload, scales = _compress(xb.reshape(-1), wire_dtype, block)
    deq = _decompress(payload, scales).reshape(n, cpad)[:, :chunk]
    return flat - deq.reshape(-1)


def quantized_all_gather_flat(chunk, axis_name: str, *,
                              wire_dtype: str = "int8",
                              block: int = QUANT_BLOCK):
    """Phase 2: compress the owned chunk, all_gather, decompress. Returns the
    concatenation over shards, fp32, length len(chunk) * axis_size."""
    n = axis_size(axis_name)
    c = chunk.shape[0]
    cpad = -(-c // block) * block
    padded = jnp.pad(chunk, (0, cpad - c))
    payload, scales = _compress(padded, wire_dtype, block)
    payload = _pin_wire(payload, scales)
    payload = jax.lax.all_gather(payload, axis_name, axis=0, tiled=True)
    payload = _pin_wire(payload, scales)
    if scales is not None:
        scales = jax.lax.all_gather(scales, axis_name, axis=0, tiled=True)
    full = _decompress(payload, scales).reshape(n, cpad)[:, :c]
    return full.reshape(-1)


def quantized_all_reduce_flat(flat, axis_name: str, *,
                              wire_dtype: str = "int8",
                              block: int = QUANT_BLOCK,
                              mean: bool = False):
    """Block-scaled quantized all-reduce of a flat vector (length divisible
    by the axis size): quantized reduce-scatter + quantized all-gather.
    Wire bytes ~= 2 * len(flat) * (1 + 4/block) for int8 vs 8 * len(flat)
    for the fp32 ring equivalent."""
    part = quantized_reduce_scatter_flat(flat, axis_name,
                                         wire_dtype=wire_dtype, block=block,
                                         mean=mean)
    return quantized_all_gather_flat(part, axis_name, wire_dtype=wire_dtype,
                                     block=block)


