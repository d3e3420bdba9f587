"""Decode attention over the PAGED KV pool, read through the block table.

The paged tick (`models/transformer.py transformer_lm_paged_decode_tick`)
keeps its K/V in one pool per layer and a slot sees its cache through a row
of the block table. Until this op the read rebuilt the slot tick's dense
`[S, nh, T, dh]` view (gather → transpose → reshape) every layer of every
tick: a pass over as many bytes as the whole pool, live or not.
`paged_decode_attention` is the read as ONE op with two lowerings of one
algorithm:

- the kernel (a TPU, float32 pools whose block is lane-dense, one query
  position): a Pallas kernel with the block table and the positions
  scalar-prefetched. One grid step a slot; the slot's LIVE blocks
  (`pos // block_size + 1` of them) are DMA'd straight from the pool in
  HBM, double-buffered, the next block (or the next slot's first) in
  flight while this one is scored; blocks past the position cost nothing;
  online softmax in float32 across the blocks; the tail of the last block
  is masked by position. Nothing of pool shape is read or written. (A
  BlockSpec pipeline over (slot, logical block) with the dead blocks
  clamped to one index computes the same, and spends 0.1 ms a call on the
  1024 dead steps of an idle tick where this spends 0.008: PERF.md,
  PR 25.)
- the composite (everything else: a CPU, int8 pools with their scale pools,
  a verify window of G > 1 positions): gather the table view and run
  `decode_attention._decode_xla`, the math the slot tick runs.

Which one a shape takes is `paged_attention_lowering`; on a TPU the composite
is never a fallback for a shape the kernel serves (it raises).

**The pool's shape.** A pool holds, per physical block (axis 0) and head,
`block_size` rows of `dh` values. Declared `[NB, nh, BS, dh]` with dh = 64 it
is stored by XLA on a TPU with the BLOCK axis minor-most (layout
`{0,3,2,1:T(8,128)}`: no padding of 64 to 128 lanes that way), and then no
block is contiguous anywhere: every Mosaic call pays a relayout of the whole
pool and a row write scatters 4-byte elements over a thousand tiles.
So a pool whose head rows pack whole 128-lane rows is DECLARED that way,
`[NB, nh, BS*dh/128, 128]` (`pool_block_shape`): the same bytes in the same
row-major order, 128 // dh tokens to a row, which XLA stores as declared.
Every reader and writer takes either shape; axis 0 is the physical block in
both.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..framework.registry import register_op
from ..ops.tensor_ops import POOL_LANES as _LANES
from .decode_attention import _auto_backend, _decode_xla

_MASKED = -1e9     # the additive bias `_mask_to_bias` gives a hidden key
_M_INIT = -1e30    # running max before the first block (finite: no inf-inf)

KERNEL, COMPOSITE = "kernel", "composite"


def paged_attention_lowering(pool_dtype, pool_lanes, n_query, d_head,
                             quantized, backend=None, platform=None):
    """Which lowering `paged_decode_attention` takes: "kernel" or
    "composite". Decided from what the op can see: the pool's dtype and
    minor dimension (128 with whole positions to a row: lane-dense,
    `pool_block_shape`), the number of query positions, the head size,
    and the backend — `None` for the choice a program gets
    (`_auto_backend()`: "pallas" on a TPU), a name for a caller that asks
    for one (a test, the smoke's reference). On a TPU a shape the kernel
    serves never takes the composite unasked: that is an error, not a
    fallback."""
    asked = backend is not None
    backend = backend or _auto_backend()
    platform = platform or jax.default_backend()
    served = (jnp.dtype(pool_dtype) == jnp.float32 and not quantized
              and n_query == 1 and pool_lanes == _LANES
              and _LANES % d_head == 0)
    if served and backend != "xla":
        return KERNEL
    if served and platform == "tpu" and not asked:
        raise RuntimeError(
            "paged_decode_attention: float32 pools with one query position "
            f"(d_head {d_head}) take the Pallas kernel on a TPU, but the "
            "backend selected is 'xla' (PTPU_DISABLE_PALLAS?); the "
            "composite rebuilds the whole pool per layer per tick and is "
            "not a fallback here")
    return COMPOSITE


def _table_view(pool, btab, d_head, scales=None):
    """The slot tick's dense cache view `[S, nh, T, dh]` of `pool`
    `[NB, nh, R, L]` (either `pool_block_shape`) through the block table
    `[S, NLB]` (T = NLB * BS), dequantized against `scales`
    `[NB, nh, BS, 1]` when the pool is int8."""
    g = pool[btab]                                     # [S,NLB,nh,R,L]
    s, nlb, nh = g.shape[:3]
    g = g.reshape(s, nlb, nh, -1, d_head)              # [S,NLB,nh,BS,dh]
    if scales is not None:
        g = g.astype(jnp.float32) * scales[btab]
    return g.transpose(0, 2, 1, 3, 4).reshape(s, nh, -1, d_head)


def _paged_composite(q4, k_pool, v_pool, btab, pos, scale, k_scale, v_scale):
    """q4 [S, nh, G, dh]; query row g of slot s sits at position pos[s] + g
    and attends the cache positions t <= pos[s] + g."""
    dh = q4.shape[-1]
    k4 = _table_view(k_pool, btab, dh, k_scale)
    v4 = _table_view(v_pool, btab, dh, v_scale)
    g, t = q4.shape[2], k4.shape[2]
    posg = pos[:, None].astype(jnp.float32) + jnp.arange(g, dtype=jnp.float32)
    valid = jnp.arange(t, dtype=jnp.float32) < posg[:, :, None] + 1.0
    bias4 = jnp.where(valid, 0.0, _MASKED).astype(jnp.float32)[:, None]
    return _decode_xla(q4, k4.astype(q4.dtype), v4.astype(q4.dtype), bias4,
                       scale)


def _paged_kernel(btab_ref, pos_ref, q_ref, k_hbm, v_hbm, o_ref,
                  kbuf, vbuf, sem, parity_ref, *, scale, n_slots, n_logical,
                  block_size, d_head):
    """One grid step = one slot; its LIVE blocks only, each DMA'd whole from
    the pool in HBM into one of two VMEM buffers while the block before it
    is scored (the slot's last block overlaps the next slot's first, so a
    tick of idle slots is sixteen 64 KB reads and nothing else). A block is
    [nh, R, 128]: row ρ holds the in-block positions ρ*per_row ..
    ρ*per_row+per_row-1, dh lanes each (per_row = 128 // dh), and stays so
    from the DMA to the reductions (heads leading, rows on sublanes): a
    position's score is the sum over its lane segment, scores are
    [nh, R, 1] a segment, the running max and sum [nh, 1, 1]. The context
    accumulates per lane, [nh, 1, 128]: segment g holds the share of the
    positions of segment g, and the caller adds the segments."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    s = pl.program_id(0)
    per_row = _LANES // d_head
    n_rows = block_size // per_row

    def copies(slot, j, buf):
        blk = btab_ref[slot * n_logical + j]
        return (pltpu.make_async_copy(k_hbm.at[blk], kbuf.at[buf],
                                      sem.at[0, buf]),
                pltpu.make_async_copy(v_hbm.at[blk], vbuf.at[buf],
                                      sem.at[1, buf]))

    @pl.when(s == 0)
    def _():
        parity_ref[0] = 0
        for c in copies(0, 0, 0):
            c.start()

    base = parity_ref[0]          # the buffer this slot's block 0 lands in
    pos = pos_ref[s]
    n_live = jax.lax.div(pos, block_size) + 1
    q = q_ref[0].astype(jnp.float32) * scale   # [nh, 1, 128]: q per segment
    nh = q.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, (1, n_rows, 1), 1)
    seg = jax.lax.broadcasted_iota(jnp.int32, (1, 1, _LANES), 2) // d_head

    def body(j, carry):
        m, l, acc = carry
        buf = jax.lax.rem(base + j, 2)
        last = j + 1 >= n_live
        nxt_slot = jnp.where(last, s + 1, s)

        @pl.when(nxt_slot < n_slots)     # the next block, or the next slot's
        def _():
            for c in copies(nxt_slot, jnp.where(last, 0, j + 1), 1 - buf):
                c.start()

        for c in copies(s, j, buf):
            c.wait()
        prod = q * kbuf[buf]                                  # [nh, R, 128]
        scores = []
        for g in range(per_row):
            sg = jnp.sum(jnp.where(seg == g, prod, 0.0), axis=-1,
                         keepdims=True)                       # [nh, R, 1]
            scores.append(jnp.where(
                j * block_size + row * per_row + g <= pos, sg, _MASKED))
        m_new = m
        for sg in scores:
            m_new = jnp.maximum(m_new, jnp.max(sg, axis=1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        weights = [jnp.exp(sg - m_new) for sg in scores]
        l = alpha * l
        for p in weights:
            l = l + jnp.sum(p, axis=1, keepdims=True)
        p_lane = weights[-1]             # [nh, R, 1] → per lane [nh, R, 128]
        for g in range(per_row - 1):
            p_lane = jnp.where(seg == g, weights[g], p_lane)
        acc = alpha * acc + jnp.sum(p_lane * vbuf[buf], axis=1,
                                    keepdims=True)
        return m_new, l, acc

    _, l, acc = jax.lax.fori_loop(
        0, n_live, body, (jnp.full((nh, 1, 1), _M_INIT, jnp.float32),
                          jnp.zeros((nh, 1, 1), jnp.float32),
                          jnp.zeros((nh, 1, _LANES), jnp.float32)))
    o_ref[0] = (acc / l).astype(o_ref.dtype)
    parity_ref[0] = jax.lax.rem(base + n_live, 2)


# jitted: every layer's read is the same function of the same shapes, so a
# tick program traces the kernel and lowers it to Mosaic once, not once a
# layer (seconds of set-up at 12 layers)
@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def _paged_pallas(q4, k_pool, v_pool, btab, pos, scale, interpret):
    """q4 [S, nh, 1, dh] float32, pools [NB, nh, R, 128] → [S, nh, 1, dh].
    The pools stay in HBM (`pl.ANY`); the kernel fetches blocks itself."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n_slots, nh, _, dh = q4.shape
    n_rows = k_pool.shape[2]
    per_row = _LANES // dh
    qspec = pl.BlockSpec((1, nh, 1, _LANES), lambda s, *_: (s, 0, 0, 0))
    with jax.named_scope("paged_decode_attention"):
        out = pl.pallas_call(
            functools.partial(_paged_kernel, scale=scale, n_slots=n_slots,
                              n_logical=btab.shape[1],
                              block_size=n_rows * per_row, d_head=dh),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(n_slots,),
                in_specs=[qspec, pl.BlockSpec(memory_space=pl.ANY),
                          pl.BlockSpec(memory_space=pl.ANY)],
                out_specs=qspec,
                scratch_shapes=[
                    pltpu.VMEM((2, nh, n_rows, _LANES), k_pool.dtype),
                    pltpu.VMEM((2, nh, n_rows, _LANES), v_pool.dtype),
                    pltpu.SemaphoreType.DMA((2, 2)),
                    pltpu.SMEM((1,), jnp.int32)]),
            out_shape=jax.ShapeDtypeStruct((n_slots, nh, 1, _LANES),
                                           q4.dtype),
            # slots run in order: a slot's first block is fetched while the
            # slot before it scores its last
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)),
            interpret=interpret,
        )(btab.reshape(-1), pos, jnp.tile(q4, (1, 1, 1, per_row)),
          k_pool, v_pool)
    return out.reshape(n_slots, nh, per_row, dh).sum(axis=2, keepdims=True)


def paged_decode_attention(q, k_pool, v_pool, btab, pos, num_heads,
                           scale=1.0, backend=None, k_scale=None,
                           v_scale=None):
    """Attention of each slot's G query positions over its paged cache.

    q [S, G, nh*dh]; k_pool / v_pool [NB, nh, R, L], either
    `pool_block_shape` (float32, or int8 with `k_scale` / `v_scale`
    [NB, nh, BS, 1]); btab [S, NLB] physical block of each logical block;
    pos [S] (any shape of S elements) the position of each slot's FIRST
    query row, which attends cache positions 0..pos (row g attends
    0..pos+g: the rows written earlier in the same forward included).
    Every logical block up to position pos + G - 1 must be mapped; blocks
    beyond it and the rows beyond the position are never attended,
    whatever they hold. Returns [S, G, nh*dh]."""
    s, g, h = q.shape
    dh = h // num_heads
    btab = btab.astype(jnp.int32)
    pos = pos.reshape(-1).astype(jnp.int32)
    lowering = paged_attention_lowering(
        k_pool.dtype, k_pool.shape[-1], g, dh, k_scale is not None,
        backend=backend)
    q4 = q.reshape(s, g, num_heads, dh).transpose(0, 2, 1, 3)
    if lowering == KERNEL:
        out = _paged_pallas(q4, k_pool, v_pool, btab, pos, float(scale),
                            interpret=(backend == "pallas_interpret"))
    else:
        out = _paged_composite(q4, k_pool, v_pool, btab, pos, float(scale),
                               k_scale, v_scale)
    return out.transpose(0, 2, 1, 3).reshape(s, g, h)


@register_op("paged_decode_attention", stop_gradient=True)
def _paged_decode_attention_op(ctx, ins, attrs):
    """The paged ticks' cache read (`layers.paged_decode_attention`)."""
    ks, vs = ins.get("KScale"), ins.get("VScale")
    out = paged_decode_attention(
        ins["Q"][0], ins["KPool"][0], ins["VPool"][0],
        ins["BlockTable"][0], ins["Pos"][0], attrs["num_heads"],
        scale=attrs.get("scale", 1.0), backend=attrs.get("backend"),
        k_scale=ks[0] if ks else None, v_scale=vs[0] if vs else None)
    return {"Out": [out]}
