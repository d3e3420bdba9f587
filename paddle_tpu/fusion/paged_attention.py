"""Decode attention over the PAGED KV pool, read through the block table.

The paged tick (`models/transformer.py transformer_lm_paged_decode_tick`)
keeps its K/V in one pool per layer and a slot sees its cache through a row
of the block table. Until this op the read rebuilt the slot tick's dense
`[S, nh, T, dh]` view (gather → transpose → reshape) every layer of every
tick: a pass over as many bytes as the whole pool, live or not.
`paged_decode_attention` is the read as ONE op: three kernels and a
composite of one algorithm, chosen by what the op can see (the number of
query positions, the pool's dtype, the head counts) and nothing else:

| query positions a slot | pools                          | lowering         |
| ---------------------- | ------------------------------ | ---------------- |
| 1                      | float32, as many heads as q    | `_paged_kernel`  |
| 1                      | grouped heads, or bfloat16     | `_decode_kernel` |
| a multiple of 8        | float32 or bfloat16, any group | `_chunk_kernel`  |
| anything else; int8 pools; a CPU                        | the composite    |

- the decode kernel of equal heads (a TPU, float32 pools whose block is
  lane-dense, one query position): a Pallas kernel with the block table
  and the positions scalar-prefetched. One grid step a slot; the slot's
  LIVE blocks (`pos // block_size + 1` of them) are DMA'd straight from the
  pool in HBM, double-buffered, the next block (or the next slot's first)
  in flight while this one is scored on the VPU in float32; blocks past
  the position cost nothing; online softmax in float32 across the blocks;
  the tail of the last block is masked by position. Nothing of pool shape
  is read or written. (A BlockSpec pipeline over (slot, logical block)
  with the dead blocks clamped to one index computes the same, and spends
  0.1 ms a call on the 1024 dead steps of an idle tick where this spends
  0.008: PERF.md, PR 25.)
- the decode kernel of grouped queries (fewer key/value heads than query
  heads: the pool's head axis is the key/value heads', `grp` = query heads
  / key/value heads) and of bfloat16 pools, one query position: one grid
  step a slot, the slot's live blocks `_DECODE_KEY_ROWS` pool rows a step,
  double-buffered, ALL key/value heads of a step in one batched product on
  the MXU (bf16 operands, float32 accumulation, float32 online softmax).
  **The block-diagonal query layout**: a pool row holds per_row = 128 // dh
  positions, one a lane segment; the `grp` query heads of a key/value head
  are padded to `rp` rows (a sublane tile) and laid `[per_row * rp, 128]`,
  rows g*rp .. g*rp+rp-1 carrying the queries on the lanes of segment g
  and zeros elsewhere, so ONE product scores every position of every row
  (no lane is sliced, no tile goes through the MXU twice) and ONE product
  weighs V; the result `[nkv, rp, 128]` holds segment g's share of the
  context on segment g's lanes and the caller adds the segments.
  **The cross-slot prefetch**: during a slot's last step the first step of
  the next LIVE slot is in flight (a parity scratch says which buffer it
  lands in); an idle slot (no real row, or position 0 on the null block,
  where the pager points an idle slot) fetches nothing, returns zeros and
  costs a grid step. (Before PR 40 these rows took the chunk kernel, eight
  rows a head in a loop over the heads: a tenth of the read's roofline.)
- the chunk kernel (G query rows a slot with G a multiple of 8: a prefill
  lane of the mixed tick feeds a chunk of its prompt): one grid step a
  lane; the lane's live blocks, up to position pos + rows - 1, are DMA'd
  128 key rows a step, double-buffered, and scored on the MXU a head at a
  time (bf16 operands, float32 accumulation, float32 online softmax); row
  g attends positions 0..pos+g, so the shared prefix's blocks, earlier
  chunks and the chunk itself are one causal read. A lane with no rows
  fetches nothing and returns zeros. Under grouped queries the `grp` query
  heads of a key/value head are ROWS of its products: a lane's C positions
  are C * grp rows a key/value head (row r at position pos + r // grp); a
  block's K and V are fetched once for the whole group.
- the composite (everything else: a CPU, int8 pools with their scale pools,
  a verify window of a few positions): gather the table view (a key/value
  head repeated over its group) and run `decode_attention._decode_xla`, the
  math the slot tick runs.

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
from ..ops.pallas_kernels import _NN, _NT    # [heads, ., .] a @ b, a @ b^T
from ..ops.tensor_ops import POOL_LANES as _LANES
from .decode_attention import _auto_backend, _decode_xla

_MASKED = -1e9     # the additive bias `_mask_to_bias` gives a hidden key
_M_INIT = -1e30    # running max before the first block (finite: no inf-inf)

KERNEL, COMPOSITE = "kernel", "composite"
_CHUNK_ROWS = 8    # the chunk kernel's query rows come in whole sublane tiles
_DECODE_KEY_ROWS = 256   # pool rows the grouped decode kernel scores a step
#: the scope (and so the device trace's name) of a read bounded by a window:
#: a window layer's read and a full layer's are told apart by it
_WINDOW_SCOPE = "paged_window_attention"


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
    served = (jnp.dtype(pool_dtype) in (jnp.float32, jnp.bfloat16)
              and not quantized
              and (n_query == 1 or n_query % _CHUNK_ROWS == 0)
              and pool_lanes == _LANES and _LANES % d_head == 0)
    if served and backend != "xla":
        return KERNEL
    if served and platform == "tpu" and not asked:
        raise RuntimeError(
            f"paged_decode_attention: {jnp.dtype(pool_dtype).name} pools "
            f"with {n_query} query "
            f"position(s) (d_head {d_head}) take a Pallas kernel on a TPU, "
            "but the backend selected is 'xla' (PTPU_DISABLE_PALLAS?); the "
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


def _paged_composite(q4, k_pool, v_pool, btab, pos, scale, k_scale, v_scale,
                     window=0):
    """q4 [S, nh, G, dh]; query row g of slot s sits at position pos[s] + g
    and attends the cache positions t <= pos[s] + g (with `window`, the last
    `window` of them: pos[s] + g - window < t)."""
    dh = q4.shape[-1]
    k4 = _table_view(k_pool, btab, dh, k_scale)
    v4 = _table_view(v_pool, btab, dh, v_scale)
    grp = q4.shape[1] // k4.shape[1]
    if grp > 1:         # query head i reads key/value head i // grp
        k4, v4 = (jnp.repeat(t, grp, axis=1) for t in (k4, v4))
    g, t = q4.shape[2], k4.shape[2]
    posg = pos[:, None].astype(jnp.float32) + jnp.arange(g, dtype=jnp.float32)
    keys = jnp.arange(t, dtype=jnp.float32)
    valid = keys < posg[:, :, None] + 1.0
    if window:
        valid &= keys > posg[:, :, None] - float(window)
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


def _chunk_kernel(btab_ref, pos_ref, rows_ref, q_ref, k_hbm, v_hbm, o_ref,
                  kbuf, vbuf, sem, m_ref, l_ref, acc_ref, *, n_logical,
                  block_size, d_head, group, mxu_dtype, rows_per_pos=1,
                  window=0):
    """One grid step = one lane: C query rows at positions pos..pos+C-1, of
    which the first `rows` are real. The lane's live blocks (those holding
    a position up to pos + rows - 1) come `group` at a time: each DMA'd
    whole from the pool into its rows of one of two VMEM buffers, the next
    group in flight while this one is scored. A block stays [nh, R, 128]
    as in `_paged_kernel` (row ρ of a block holds per_row positions, dh
    lanes each); the keys of lane segment g are scored by the query tiled
    over the segments and zeroed outside segment g, so no lane is ever
    sliced: scores [C, group*R] a segment, a head at a time on the MXU.
    The context accumulates per lane, [nh, C, 128], segment g holding the
    share of the keys of segment g; the caller adds the segments. Every
    block of a live group is fetched (a dead one is the null block or an
    unwritten block of the request: finite, and masked by position): p = 0
    times a stale VMEM row could be NaN. With `rows_per_pos` > 1 (grouped
    queries: the pool's heads are the key/value heads) `rows_per_pos`
    consecutive query rows share a position: row r sits at pos + r //
    rows_per_pos, and `rows` still counts positions. With `window` a row
    attends the last `window` positions up to its own: the walk starts at the
    block that holds position pos - window + 1 (`first`), nothing below it is
    fetched, and that block's head is masked row by row."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    lane = pl.program_id(0)
    per_row = _LANES // d_head
    n_rows = block_size // per_row
    nh, c = q_ref.shape[1], q_ref.shape[2]
    key_rows = group * n_rows
    pos = pos_ref[lane]
    rows = rows_ref[lane]
    n_live = jnp.where(rows > 0,
                       jax.lax.div(pos + rows - 1, block_size) + 1, 0)
    first = 0
    if window:
        first = jax.lax.div(jnp.maximum(pos - (window - 1), 0), block_size)
        n_live = jnp.maximum(n_live - first, 0)
    n_steps = jax.lax.div(n_live + group - 1, group)

    def fetch(step, buf, wait):
        """Start (or wait for) the DMAs of the `group` blocks of `step` into
        buffer `buf`: a loop, not `group` unrolled descriptors, so that the
        kernel's text stays short (it is lowered at every set-up)."""
        def one(g, carry):
            j = jnp.minimum(first + step * group + g, n_logical - 1)
            blk = btab_ref[lane * n_logical + j]
            dst = pl.ds(pl.multiple_of(g * n_rows, n_rows), n_rows)
            for hbm, vmem, kv in ((k_hbm, kbuf, 0), (v_hbm, vbuf, 1)):
                cp = pltpu.make_async_copy(
                    hbm.at[blk], vmem.at[buf, :, dst, :], sem.at[kv, buf, g])
                cp.wait() if wait else cp.start()
            return carry
        jax.lax.fori_loop(0, group, one, 0)

    m_ref[...] = jnp.full(m_ref.shape, _M_INIT, jnp.float32)
    l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
    acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    @pl.when(n_steps > 0)
    def _():
        fetch(0, 0, wait=False)

    seg = jax.lax.broadcasted_iota(jnp.int32, (1, _LANES), 1) // d_head
    q_row = jax.lax.broadcasted_iota(jnp.int32, (c, key_rows), 0)
    q_pos = pos + (q_row if rows_per_pos == 1 else q_row // rows_per_pos)
    key_row = jax.lax.broadcasted_iota(jnp.int32, (c, key_rows), 1)

    def step_body(step, carry):
        buf = jax.lax.rem(step, 2)

        @pl.when(step + 1 < n_steps)
        def _():
            fetch(step + 1, 1 - buf, wait=False)

        fetch(step, buf, wait=True)
        key0 = (first * block_size if window else 0) \
            + step * (group * block_size)

        def head_body(h, carry):
            q = q_ref[0, h]                                    # [C, 128]
            k = kbuf[buf, h].astype(mxu_dtype)                 # [keys/per_row, 128]
            v = vbuf[buf, h].astype(mxu_dtype)
            scores = []
            for g in range(per_row):
                qg = q if per_row == 1 else jnp.where(seg == g, q, 0.0)
                sg = jax.lax.dot_general(
                    qg.astype(mxu_dtype), k, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)        # [C, key_rows]
                key_pos = key0 + key_row * per_row + g
                seen = key_pos <= q_pos
                if window:
                    seen &= key_pos > q_pos - window
                scores.append(jnp.where(seen, sg, _MASKED))
            m_prev = m_ref[h]                                  # [C, 1]
            m_new = m_prev
            for sg in scores:
                m_new = jnp.maximum(m_new, jnp.max(sg, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            l_new = alpha * l_ref[h]
            ctx = None
            for g, sg in enumerate(scores):
                p = jnp.exp(sg - m_new)
                l_new = l_new + jnp.sum(p, axis=1, keepdims=True)
                og = jax.lax.dot_general(
                    p.astype(mxu_dtype), v, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)        # [C, 128]
                if per_row > 1:
                    og = jnp.where(seg == g, og, 0.0)
                ctx = og if ctx is None else ctx + og
            acc_ref[h] = alpha * acc_ref[h] + ctx
            m_ref[h] = m_new
            l_ref[h] = l_new
            return carry

        return jax.lax.fori_loop(0, nh, head_body, carry)

    jax.lax.fori_loop(0, n_steps, step_body, 0)
    l = l_ref[...]
    o_ref[0] = (acc_ref[...] / jnp.where(l > 0.0, l, 1.0)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "interpret",
                                             "rows_per_pos", "window"))
def _chunk_pallas(q4, k_pool, v_pool, btab, pos, rows, scale, interpret,
                  rows_per_pos=1, window=0):
    """q4 [L, nh, C, dh] float32, pools [NB, nh, R, 128] → [L, nh, C, dh].
    On the chip the MXU takes bf16 operands (what XLA's default precision
    gives the composite's float32 matmuls there); interpreted, the
    operands stay float32 and the composite is matched to rounding. With
    `rows_per_pos` (grouped queries) the C rows are `rows_per_pos` a
    position and nh is the key/value heads'."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n_lanes, nh, c, dh = q4.shape
    n_rows = k_pool.shape[2]
    per_row = _LANES // dh
    n_logical = btab.shape[1]
    group = max(1, min(_LANES // n_rows, n_logical))
    qspec = pl.BlockSpec((1, nh, c, _LANES), lambda i, *_: (i, 0, 0, 0))
    with jax.named_scope(_WINDOW_SCOPE if window
                         else "paged_chunk_attention" if rows_per_pos == 1
                         else "paged_gqa_attention"):
        out = pl.pallas_call(
            functools.partial(
                _chunk_kernel, n_logical=n_logical,
                block_size=n_rows * per_row, d_head=dh, group=group,
                mxu_dtype=jnp.float32 if interpret else jnp.bfloat16,
                rows_per_pos=rows_per_pos, window=window),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3,
                grid=(n_lanes,),
                in_specs=[qspec, pl.BlockSpec(memory_space=pl.ANY),
                          pl.BlockSpec(memory_space=pl.ANY)],
                out_specs=qspec,
                scratch_shapes=[
                    pltpu.VMEM((2, nh, group * n_rows, _LANES), k_pool.dtype),
                    pltpu.VMEM((2, nh, group * n_rows, _LANES), v_pool.dtype),
                    pltpu.SemaphoreType.DMA((2, 2, group)),
                    pltpu.VMEM((nh, c, 1), jnp.float32),
                    pltpu.VMEM((nh, c, 1), jnp.float32),
                    pltpu.VMEM((nh, c, _LANES), jnp.float32)]),
            out_shape=jax.ShapeDtypeStruct((n_lanes, nh, c, _LANES),
                                           q4.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=32 * 1024 * 1024),
            interpret=interpret,
        )(btab.reshape(-1), pos, rows,
          jnp.tile(q4 * scale, (1, 1, 1, per_row)), k_pool, v_pool)
    return out.reshape(n_lanes, nh, c, per_row, dh).sum(axis=3)


def _decode_kernel(btab_ref, pos_ref, rows_ref, q_ref, k_hbm, v_hbm, o_ref,
                   kbuf, vbuf, sem, parity_ref, *, n_slots, n_logical,
                   block_size, d_head, group, mxu_dtype, window=0):
    """One grid step = one slot's ONE query position under grouped queries
    (or bfloat16 pools). The slot's live blocks come `group` at a time into
    one of two VMEM buffers, live blocks only, the next group in flight
    while this one is scored; during a slot's last group the first group of
    the next LIVE slot is in flight (`parity_ref` says which buffer it lands
    in). A slot is idle when it has no real row, or sits at position 0 on the
    null block (physical block 0: where the pager points an idle slot); it
    fetches nothing, returns zeros and costs a grid step.

    A group is [nkv, group*R, 128] as fetched: row ρ holds per_row
    positions, dh lanes each. The queries are [nkv, per_row*rp, 128],
    block-diagonal: rows g*rp .. g*rp+rp-1 carry the rp query rows of a
    key/value head on the lanes of segment g and zeros elsewhere, so ONE
    product batched over the key/value heads scores every position of every
    row (query row g*rp+r against key row ρ is position ρ*per_row+g), one
    softmax update runs on the [nkv, per_row*rp, group*R] scores, and one
    product weighs V: rows g*rp.. of the context are right on the lanes of
    segment g (the other lanes are dropped at the end). m, l and the context
    are loop values; the segments' running maxima differ and are reconciled
    once a slot. The result is [nkv, rp, 128], segment g holding the share
    of the positions of segment g: the caller adds the segments. The buffers
    are zeroed at the first step, so the rows past a slot's last live block
    hold zeros or an earlier group's rows: finite, and masked by position.

    With `window` the slot attends its last `window` positions: its live
    blocks start at the one that holds position pos - window + 1
    (`first_block`; the table's entries below it may be unmapped and are
    never looked at), and that block's head is masked."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    s = pl.program_id(0)
    per_row = _LANES // d_head
    n_rows = block_size // per_row
    nkv, q_rows = q_ref.shape[1], q_ref.shape[2]
    rp = q_rows // per_row
    key_rows = group * n_rows

    def first_block(slot):
        if not window:
            return 0
        return jax.lax.div(jnp.maximum(pos_ref[slot] - (window - 1), 0),
                           block_size)

    def live_blocks(slot):
        pos = pos_ref[slot]
        idle = (rows_ref[slot] <= 0) | (
            (pos == 0) & (btab_ref[slot * n_logical] == 0))
        return jnp.where(idle, 0, jax.lax.div(pos, block_size) + 1
                         - first_block(slot))

    def first_live(slot):
        """The first live slot at or after `slot`; n_slots when none is."""
        return jax.lax.while_loop(
            lambda t: (t < n_slots)
            & (live_blocks(jnp.minimum(t, n_slots - 1)) == 0),
            lambda t: t + 1, slot)

    def fetch(slot, step, buf, wait):
        """Start (or wait for) the DMAs of the live blocks of group `step`
        of `slot` into buffer `buf`."""
        def one(g, carry):
            blk = btab_ref[slot * n_logical + first_block(slot)
                           + step * group + g]
            dst = pl.ds(pl.multiple_of(g * n_rows, n_rows), n_rows)
            for hbm, vmem, kv in ((k_hbm, kbuf, 0), (v_hbm, vbuf, 1)):
                cp = pltpu.make_async_copy(
                    hbm.at[blk], vmem.at[buf, :, dst, :], sem.at[kv, buf, g])
                cp.wait() if wait else cp.start()
            return carry
        jax.lax.fori_loop(
            0, jnp.minimum(group, live_blocks(slot) - step * group), one, 0)

    @pl.when(s == 0)
    def _():
        kbuf[...] = jnp.zeros(kbuf.shape, kbuf.dtype)
        vbuf[...] = jnp.zeros(vbuf.shape, vbuf.dtype)
        parity_ref[0] = 0
        first = first_live(0)

        @pl.when(first < n_slots)
        def _():
            fetch(first, 0, 0, wait=False)

    n_live = live_blocks(s)

    @pl.when(n_live == 0)
    def _():
        o_ref[0] = jnp.zeros(o_ref.shape[1:], o_ref.dtype)

    @pl.when(n_live > 0)
    def _():
        base = parity_ref[0]      # the buffer this slot's first group is in
        # the slot's position, counted from its first live block
        pos = pos_ref[s] - first_block(s) * block_size
        n_steps = jax.lax.div(n_live + group - 1, group)
        nxt_slot = first_live(s + 1)
        q = q_ref[0]                                 # [nkv, q_rows, 128]
        # query row i scores the positions of segment i // rp of a key row
        key_off = (jax.lax.broadcasted_iota(
            jnp.int32, (1, q_rows, key_rows), 2) * per_row
            + jax.lax.broadcasted_iota(
                jnp.int32, (1, q_rows, key_rows), 1) // rp)

        def body(j, carry):
            m, l, acc = carry
            buf = jax.lax.rem(base + j, 2)
            last = j + 1 >= n_steps
            to_slot = jnp.where(last, nxt_slot, s)

            @pl.when(to_slot < n_slots)  # the next group, or the next slot's
            def _():
                fetch(to_slot, jnp.where(last, 0, j + 1), 1 - buf, wait=False)

            fetch(s, j, buf, wait=True)
            sc = jax.lax.dot_general(
                q, kbuf[buf].astype(mxu_dtype), _NT,
                preferred_element_type=jnp.float32)  # [nkv, q_rows, keys]
            seen = key_off <= pos - j * (group * block_size)
            if window:
                seen &= key_off > pos - j * (group * block_size) - window
            sc = jnp.where(seen, sc, _MASKED)
            m_new = jnp.maximum(m, jnp.max(sc, axis=2, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(sc - m_new)
            l = alpha * l + jnp.sum(p, axis=2, keepdims=True)
            acc = alpha * acc + jax.lax.dot_general(
                p.astype(mxu_dtype), vbuf[buf].astype(mxu_dtype), _NN,
                preferred_element_type=jnp.float32)  # [nkv, q_rows, 128]
            return m_new, l, acc

        m, l, acc = jax.lax.fori_loop(
            0, n_steps, body,
            (jnp.full((nkv, q_rows, 1), _M_INIT, jnp.float32),
             jnp.zeros((nkv, q_rows, 1), jnp.float32),
             jnp.zeros((nkv, q_rows, _LANES), jnp.float32)))
        parity_ref[0] = jax.lax.rem(base + n_steps, 2)
        # a segment with no visible position has m = _MASKED: weight 0
        segs = [slice(g * rp, (g + 1) * rp) for g in range(per_row)]
        m_all = functools.reduce(jnp.maximum, (m[:, sl] for sl in segs))
        w = [jnp.exp(m[:, sl] - m_all) for sl in segs]
        l_all = sum(l[:, sl] * wg for sl, wg in zip(segs, w))
        seg = jax.lax.broadcasted_iota(jnp.int32, (1, 1, _LANES), 2) // d_head
        ctx = acc[:, segs[-1]] * w[-1]
        for g in range(per_row - 1):
            ctx = jnp.where(seg == g, acc[:, segs[g]] * w[g], ctx)
        o_ref[0] = (ctx / l_all).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("d_head", "interpret", "window"))
def _decode_pallas(q2, k_pool, v_pool, btab, pos, rows, d_head, interpret,
                   window=0):
    """q2 [S, nkv, per_row*rp, 128] float32, scaled and block-diagonal
    (`_grouped_decode`), pools [NB, nkv, R, 128] → [S, nkv, rp, 128]
    float32, the context's share a lane segment. The operands of both
    products are bf16 on the chip and float32 interpreted, as the chunk
    kernel's."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n_slots, nkv, q_rows, _ = q2.shape
    n_rows = k_pool.shape[2]
    per_row = _LANES // d_head
    n_logical = btab.shape[1]
    group = max(1, min(_DECODE_KEY_ROWS // n_rows, n_logical))
    mxu_dtype = jnp.float32 if interpret else jnp.bfloat16
    if window:      # never more blocks a step than a window spans
        group = min(group, -(-(window - 1) // (n_rows * per_row)) + 1)
    with jax.named_scope(_WINDOW_SCOPE if window else "paged_gqa_attention"):
        return pl.pallas_call(
            functools.partial(
                _decode_kernel, n_slots=n_slots, n_logical=n_logical,
                block_size=n_rows * per_row, d_head=d_head, group=group,
                mxu_dtype=mxu_dtype, window=window),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3,
                grid=(n_slots,),
                in_specs=[pl.BlockSpec((1, nkv, q_rows, _LANES),
                                       lambda i, *_: (i, 0, 0, 0)),
                          pl.BlockSpec(memory_space=pl.ANY),
                          pl.BlockSpec(memory_space=pl.ANY)],
                out_specs=pl.BlockSpec((1, nkv, q_rows // per_row, _LANES),
                                       lambda i, *_: (i, 0, 0, 0)),
                scratch_shapes=[
                    pltpu.VMEM((2, nkv, group * n_rows, _LANES), k_pool.dtype),
                    pltpu.VMEM((2, nkv, group * n_rows, _LANES), v_pool.dtype),
                    pltpu.SemaphoreType.DMA((2, 2, group)),
                    pltpu.SMEM((1,), jnp.int32)]),
            out_shape=jax.ShapeDtypeStruct(
                (n_slots, nkv, q_rows // per_row, _LANES), jnp.float32),
            # slots run in order: a slot's first group is fetched while the
            # live slot before it scores its last
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=32 * 1024 * 1024),
            interpret=interpret,
        )(btab.reshape(-1), pos, rows, q2.astype(mxu_dtype), k_pool, v_pool)


def paged_decode_attention(q, k_pool, v_pool, btab, pos, num_heads,
                           scale=1.0, backend=None, k_scale=None,
                           v_scale=None, rows=None, window=0):
    """Attention of each slot's G query positions over its paged cache.

    q [S, G, nh*dh]; k_pool / v_pool [NB, nkv, R, L], either
    `pool_block_shape` (float32 or bfloat16, or int8 with `k_scale` /
    `v_scale` [NB, nh, BS, 1]; nkv the key/value heads, nh or a divisor of
    it: query head i reads key/value head i // (nh / nkv)); btab [S, NLB]
    physical block of each logical block;
    pos [S] (any shape of S elements) the position of each slot's FIRST
    query row, which attends cache positions 0..pos (row g attends
    0..pos+g: the rows written earlier in the same forward included).
    Every logical block up to position pos + G - 1 must be mapped; blocks
    beyond it and the rows beyond the position are never attended,
    whatever they hold. `rows` [S] (optional; G when absent) is how many of
    a slot's G rows are real: the others, and every row of a slot with
    none, return values nobody reads (finite), and only the blocks up to
    position pos + rows - 1 need be mapped. Physical block 0 is the pager's
    null block, never a request's: a slot at position 0 whose first logical
    block is block 0 is an idle slot, and the grouped decode kernel returns
    it zeros without fetching. `window` > 0 (a sliding-window layer): row g
    attends positions pos + g - window + 1 .. pos + g only; the walk starts
    at the block that holds the first of them, and the table's entries below
    that block are never read (the pager has released them:
    serving/kv_pager.py). The two grouped kernels and the composite take it;
    with 0 every call lowers as it did without the argument.
    Returns [S, G, nh*dh]."""
    window = int(window)
    s, g, h = q.shape
    dh = h // num_heads
    btab = btab.astype(jnp.int32)
    pos = pos.reshape(-1).astype(jnp.int32)
    lowering = paged_attention_lowering(
        k_pool.dtype, k_pool.shape[-1], g, dh, k_scale is not None,
        backend=backend)
    interpret = backend == "pallas_interpret"
    nkv = k_pool.shape[1]
    if lowering == KERNEL and (nkv != num_heads or window
                               or k_pool.dtype != jnp.float32):
        grouped = _grouped_decode if g == 1 else _grouped_lanes
        return grouped(q, k_pool, v_pool, btab, pos, rows, num_heads,
                       float(scale), interpret, window)
    q4 = q.reshape(s, g, num_heads, dh).transpose(0, 2, 1, 3)
    if lowering == KERNEL and g == 1:
        out = _paged_pallas(q4, k_pool, v_pool, btab, pos, float(scale),
                            interpret=interpret)
    elif lowering == KERNEL:
        out = _chunk_pallas(q4, k_pool, v_pool, btab, pos,
                            _real_rows(rows, s, g), float(scale),
                            interpret=interpret)
    else:
        out = _paged_composite(q4, k_pool, v_pool, btab, pos, float(scale),
                               k_scale, v_scale, window)
    return out.transpose(0, 2, 1, 3).reshape(s, g, h)


def _real_rows(rows, s, g):
    """`rows` as the kernels take it: [S] int32, all `g` real when absent."""
    return (jnp.full((s,), g, jnp.int32) if rows is None
            else rows.reshape(-1).astype(jnp.int32))


def _grouped_lanes(q, k_pool, v_pool, btab, pos, rows, num_heads, scale,
                   interpret, window=0):
    """The chunk kernel under grouped queries (and bfloat16 pools): the
    `grp` query heads of a key/value head, position-major, as the rows of
    one product."""
    s, g, h = q.shape
    nkv = k_pool.shape[1]
    grp, dh = num_heads // nkv, h // num_heads
    q4 = (q.reshape(s, g, nkv, grp, dh).astype(jnp.float32)
          .transpose(0, 2, 1, 3, 4).reshape(s, nkv, g * grp, dh))
    out = _chunk_pallas(q4, k_pool, v_pool, btab, pos, _real_rows(rows, s, g),
                        scale, interpret=interpret, rows_per_pos=grp,
                        window=window)
    out = out.reshape(s, nkv, g, grp, dh)
    return out.transpose(0, 2, 1, 3, 4).reshape(s, g, h).astype(q.dtype)


def _grouped_decode(q, k_pool, v_pool, btab, pos, rows, num_heads, scale,
                    interpret, window=0):
    """The decode kernel of grouped queries (and bfloat16 pools): a slot's
    one position, the `grp` query heads of a key/value head padded to a
    sublane tile and laid block-diagonally over the lane segments
    (`_decode_kernel`); the kernel's result holds each segment's share of
    the context on that segment's lanes, added here."""
    s, _, h = q.shape
    nkv = k_pool.shape[1]
    grp, dh = num_heads // nkv, h // num_heads
    per_row = _LANES // dh
    rp = -(-grp // _CHUNK_ROWS) * _CHUNK_ROWS
    q4 = q.reshape(s, nkv, grp, dh).astype(jnp.float32) * scale
    q4 = jnp.pad(q4, ((0, 0), (0, 0), (0, rp - grp), (0, 0)))
    # rows g*rp .. g*rp+rp-1 hold the queries on the lanes of segment g and
    # zeros elsewhere: one product scores every position of a pool row
    q2 = (q4[:, :, None, :, None, :]
          * jnp.eye(per_row, dtype=q4.dtype)[:, None, :, None])
    q2 = q2.reshape(s, nkv, per_row * rp, _LANES)
    out = _decode_pallas(q2, k_pool, v_pool, btab, pos, _real_rows(rows, s, 1),
                         d_head=dh, interpret=interpret,
                         window=window)                    # [S, nkv, rp, 128]
    out = out.reshape(s, nkv, rp, per_row, dh).sum(axis=3)[:, :, :grp]
    return out.reshape(s, 1, h).astype(q.dtype)


@register_op("paged_decode_attention", stop_gradient=True)
def _paged_decode_attention_op(ctx, ins, attrs):
    """The paged ticks' cache read (`layers.paged_decode_attention`)."""
    ks, vs = ins.get("KScale"), ins.get("VScale")
    out = paged_decode_attention(
        ins["Q"][0], ins["KPool"][0], ins["VPool"][0],
        ins["BlockTable"][0], ins["Pos"][0], attrs["num_heads"],
        scale=attrs.get("scale", 1.0), backend=attrs.get("backend"),
        k_scale=ks[0] if ks else None, v_scale=vs[0] if vs else None,
        rows=ins["Rows"][0] if ins.get("Rows") else None,
        window=attrs.get("window", 0))
    return {"Out": [out]}
