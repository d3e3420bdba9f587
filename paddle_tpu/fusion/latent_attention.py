"""Attention on the latent row itself, read through the block table.

Latent attention (MLA) caches ONE row a token a layer: the normalised
`c_kv` (`v_width` values), the rotated `k_pe`, and zeros up to a whole
number of 128-lane rows (`LatentSpec.row_lanes`: 576 -> 640). Every head
shares the row. With the key half of `kv_b_proj` absorbed into the query
(`q~_h = W_k_h^T q_nope_h`) a head's score is ONE dot product of its padded
query row `[q~_h, q_pe_h, 0]` with the cache row, and the context is the
softmax-weighted sum of the rows' first `v_width` values; the value half of
`kv_b_proj` is applied after (`layers.latent_head_proj`). K and V are never
expanded: a position costs `row_lanes` stored values, not
`num_heads * (qk + v)`.

`latent_paged_attention` is that read as one op with two lowerings:

- the kernel (a TPU): ONE Mosaic call under the scope
  `latent_paged_attention`, with two bodies chosen by the number of query
  positions a slot and nothing else (`latent_attention_body`). They are two
  because their regimes conflict: a decode row is 64 matmul rows against
  16k cache rows, bound by the pool's bytes, over 32 slots most of which
  are idle; a lane's tile is 512 rows against the same bytes, bound by the
  MXU, over 2 lanes, and masks more than one group.
  - `_latent_kernel` (whole tiles of `_TILE_POSITIONS` positions: the
    prefill lanes): grid (slot, query tile), a tile `tq` positions x
    `num_heads` heads, one matmul row each. The slot's live blocks, up to
    the tile's last position, are DMA'd from the pool in HBM `group` blocks
    a step, double-buffered; scores and the weighted sum run on the MXU
    (bfloat16 operands, float32 accumulation), the online softmax in
    float32, masked every step.
  - `_latent_decode_kernel` (one position: the decode rows; ISSUE 42): grid
    (slot,). A slot's live blocks come `_DECODE_STEP_KEYS` rows a group
    into one of two buffers, live blocks only. Every group but the slot's
    last is scored unmasked, in a loop body that is one block of code: the
    next group's copies are written out one by one under their own
    conditions, so the compiler lays them beside the products instead of
    running a loop of them first, and m, l and the context are loop values.
    The last group alone is masked by position, and while it is scored the
    first group of the next LIVE slot is in flight: the DMA does not see a
    slot boundary. An idle slot (no real row, or position 0 on the null
    block) fetches nothing, returns zeros and costs a grid step (0.6 us on
    a v5e). Same arithmetic as the lanes' body. Timed alone at the document
    cell's widths it reads the pool at 87% of the chip's bandwidth: the
    bytes bound it (PERF.md section 6, PR 42).
- the composite (a CPU, or asked for): gather the table's view and compute
  the same in `jax.numpy`.

Arithmetic of the chunk path, written down (ISSUE 36): a lane of C = 128
positions against T = 16.5k cached rows, 64 heads. Absorbed: 2 * 64C * T *
(640 + 512) = 312 GFLOP a layer, and no intermediate. Expanded through
`kv_b_proj` for the chunk's context: 2 * T * 512 * 16384 = 277 GFLOP to
make K and V (541 MB of them, a lane a layer) plus 2 * 64C * T * (192 + 128)
= 87 GFLOP of attention: 364 GFLOP. The absorbed form is cheaper at this
chunk and needs no second kernel, so the lanes take it too.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..framework.registry import register_op
from .decode_attention import _auto_backend

_MASKED = -1e9
_M_INIT = -1e30
_LANES = 128
KERNEL, COMPOSITE = "kernel", "composite"
_TILE_POSITIONS = 8          # query positions a tile of a chunk holds
_STEP_KEYS = 512             # cache rows a step of a lane's tile scores
_DECODE_STEP_KEYS = 1024     # cache rows a group of the decode read scores


def latent_attention_lowering(row_lanes, v_width, num_heads, n_query,
                              backend=None):
    """Which lowering the read takes. The kernel serves rows of whole
    128-lane rows whose value part is whole lanes too, `num_heads` a
    multiple of 8, and one query position or whole tiles of them."""
    backend = backend or _auto_backend()
    served = (row_lanes % _LANES == 0 and v_width % _LANES == 0
              and v_width <= row_lanes and num_heads % 8 == 0
              and (n_query == 1 or n_query % _TILE_POSITIONS == 0))
    if served and backend != "xla":
        return KERNEL
    if jax.default_backend() == "tpu" and backend != "xla":
        raise RuntimeError(
            f"latent_paged_attention: a row of {row_lanes} lanes "
            f"({v_width} of values), {num_heads} heads and {n_query} query "
            "position(s) has no kernel, and the composite gathers every "
            "slot's whole table: not a fallback on a TPU")
    return COMPOSITE


def latent_attention_body(n_query):
    """Which body of the kernel serves `n_query` positions a slot: the
    decode body one position, the lanes' body whole tiles of them."""
    return _latent_decode_pallas if n_query == 1 else _latent_pallas


def _latent_composite(q, pool, btab, pos, num_heads, v_width, scale):
    s, g, _ = q.shape
    w = pool.shape[-1]
    view = pool[btab].reshape(s, -1, w).astype(jnp.float32)     # [S,T,W]
    q4 = q.reshape(s, g, num_heads, w).astype(jnp.float32)
    sc = jnp.einsum("sghw,stw->sght", q4, view) * scale
    posg = pos[:, None] + jnp.arange(g, dtype=jnp.int32)        # [S,G]
    valid = jnp.arange(view.shape[1])[None, None, :] <= posg[:, :, None]
    sc = jnp.where(valid[:, :, None, :], sc, _MASKED)
    p = jax.nn.softmax(sc, axis=-1)
    out = jnp.einsum("sght,stv->sghv", p, view[..., :v_width])
    return out.reshape(s, g, num_heads * v_width).astype(q.dtype)


def _latent_kernel(btab_ref, pos_ref, rows_ref, q_ref, pool_hbm, o_ref,
                   kbuf, sem, m_ref, l_ref, acc_ref, *, n_logical,
                   block_size, group, num_heads, tq, v_width, scale):
    """One grid step = one tile of one slot: `tq` query positions x
    `num_heads` heads as rows [R, W] (row r is position r // num_heads)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    s, t = pl.program_id(0), pl.program_id(1)
    pos, rows = pos_ref[s], rows_ref[s]
    g0 = t * tq
    live_q = jnp.minimum(rows - g0, tq)
    n_live = jnp.where(live_q > 0,
                       jax.lax.div(pos + g0 + live_q - 1, block_size) + 1, 0)
    n_steps = jax.lax.div(n_live + group - 1, group)
    keys = group * block_size
    n_rows = q_ref.shape[1]

    def fetch(step, buf, wait):
        def one(g, carry):
            j = jnp.minimum(step * group + g, n_logical - 1)
            blk = btab_ref[s * n_logical + j]
            dst = pl.ds(pl.multiple_of(g * block_size, block_size),
                        block_size)
            cp = pltpu.make_async_copy(pool_hbm.at[blk],
                                       kbuf.at[buf, :, dst, :],
                                       sem.at[buf, g])
            cp.wait() if wait else cp.start()
            return carry
        jax.lax.fori_loop(0, group, one, 0)

    m_ref[...] = jnp.full(m_ref.shape, _M_INIT, jnp.float32)
    l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
    acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    @pl.when(n_steps > 0)
    def _():
        fetch(0, 0, wait=False)

    row = jax.lax.broadcasted_iota(jnp.int32, (n_rows, keys), 0)
    q_pos = pos + g0 + jax.lax.div(row, num_heads)
    key_col = jax.lax.broadcasted_iota(jnp.int32, (n_rows, keys), 1)

    def step_body(step, carry):
        buf = jax.lax.rem(step, 2)

        @pl.when(step + 1 < n_steps)
        def _():
            fetch(step + 1, 1 - buf, wait=False)

        fetch(step, buf, wait=True)
        k = kbuf[buf, 0]                                       # [keys, W]
        sc = jax.lax.dot_general(
            q_ref[0], k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale        # [R, keys]
        sc = jnp.where(step * keys + key_col <= q_pos, sc, _MASKED)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(sc - m_new)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + jax.lax.dot_general(
            p.astype(k.dtype), kbuf[buf, 0, :, :v_width],
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[...] = m_new
        return carry

    jax.lax.fori_loop(0, n_steps, step_body, 0)
    l = l_ref[...]
    o_ref[0] = (acc_ref[...] / jnp.where(l > 0.0, l, 1.0)).astype(o_ref.dtype)


def _latent_decode_kernel(btab_ref, pos_ref, rows_ref, q_ref, pool_hbm, o_ref,
                          kbuf, sem, parity_ref, *, n_slots, n_logical,
                          block_size, group, v_width, scale):
    """One grid step = one slot's ONE query position: `num_heads` rows
    [R, W] against the slot's live latent rows.

    The live blocks come `group` at a time into one of two VMEM buffers,
    live blocks only, the next group in flight while this one is scored;
    during a slot's last group the first group of the next LIVE slot is in
    flight (`parity_ref` says which buffer it lands in). A slot is idle when
    it has no real row, or sits at position 0 on the null block (physical
    block 0: where the pager points an idle slot); it fetches nothing,
    returns zeros and costs a grid step.

    Every group but the slot's last is wholly live and is scored with no
    mask, in a loop whose body is ONE block of code: its copies are written
    out one by one (scalar work the compiler lays beside the products, not
    loops before them), and m, l and the context are loop values. The last
    group alone is masked by position, its copies started and waited for in
    loops: once a slot, and an unrolled copy is traced at every set-up.
    The buffers are zeroed at the first step, so rows past a slot's last
    live block hold zeros or an earlier group's rows: finite, and masked."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    s = pl.program_id(0)
    keys = group * block_size
    n_rows = q_ref.shape[1]

    def live_blocks(slot):
        pos = pos_ref[slot]
        idle = (rows_ref[slot] <= 0) | (
            (pos == 0) & (btab_ref[slot * n_logical] == 0))
        return jnp.where(idle, 0, jax.lax.div(pos, block_size) + 1)

    def first_live(slot):
        """The first live slot at or after `slot`; n_slots when none is."""
        return jax.lax.while_loop(
            lambda t: (t < n_slots)
            & (live_blocks(jnp.minimum(t, n_slots - 1)) == 0),
            lambda t: t + 1, slot)

    def copy(slot, step, buf, g):
        """The DMA of block g of group `step` of `slot` into buffer `buf`."""
        blk = btab_ref[slot * n_logical + step * group + g]
        row = g * block_size
        if not isinstance(g, int):
            row = pl.multiple_of(row, block_size)
        return pltpu.make_async_copy(
            pool_hbm.at[blk], kbuf.at[buf, :, pl.ds(row, block_size), :],
            sem.at[buf, g])

    def fetch(slot, step, buf, count, wait):
        """Start (or wait for) the DMAs of the first `count` blocks of group
        `step` of `slot` into buffer `buf`, one after the other."""
        def one(g, carry):
            cp = copy(slot, step, buf, g)
            cp.wait() if wait else cp.start()
            return carry
        jax.lax.fori_loop(0, jnp.minimum(group, count), one, 0)

    @pl.when(s == 0)
    def _():
        kbuf[...] = jnp.zeros(kbuf.shape, kbuf.dtype)
        parity_ref[0] = 0
        first = jnp.minimum(first_live(0), n_slots - 1)
        fetch(first, 0, 0, live_blocks(first), wait=False)

    n_live = live_blocks(s)

    @pl.when(n_live == 0)
    def _():
        o_ref[0] = jnp.zeros(o_ref.shape[1:], o_ref.dtype)

    @pl.when(n_live > 0)
    def _():
        base = parity_ref[0]      # the buffer this slot's first group is in
        n_steps = jax.lax.div(n_live + group - 1, group)
        nxt_slot = jnp.minimum(first_live(s + 1), n_slots - 1)
        nxt_blocks = jnp.where(nxt_slot > s, live_blocks(nxt_slot), 0)
        q = q_ref[0]                                          # [R, W]

        def scored(carry, k, limit=None):
            """The online softmax over the rows `k` [keys, W]; keys past
            `limit` (an index into `k`) are dead."""
            m, l, acc = carry
            sc = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale   # [R, keys]
            if limit is not None:
                col = jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1)
                sc = jnp.where(col <= limit, sc, _MASKED)
            m_new = jnp.maximum(m, jnp.max(sc, axis=1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(sc - m_new)
            l = alpha * l + jnp.sum(p, axis=1, keepdims=True)
            acc = alpha * acc + jax.lax.dot_general(
                p.astype(k.dtype), k[:, :v_width],
                (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
            return m_new, l, acc

        def whole_group(j, carry):
            """Group j, not the slot's last: all of it live, and the group
            after it ours. Its copies are written out, the starts each under
            its own condition (the next group may be the short last one), so
            that the body is one block of code and the compiler lays their
            scalar work beside the products."""
            buf = jax.lax.rem(base + j, 2)
            left = n_live - (j + 1) * group
            for g in range(group):
                @pl.when(g < left)
                def _():
                    copy(s, j + 1, 1 - buf, g).start()
            for g in range(group):
                copy(s, j, buf, g).wait()
            return scored(carry, kbuf[buf, 0])

        carry = jax.lax.fori_loop(
            0, n_steps - 1, whole_group,
            (jnp.full((n_rows, 1), _M_INIT, jnp.float32),
             jnp.zeros((n_rows, 1), jnp.float32),
             jnp.zeros((n_rows, v_width), jnp.float32)))
        # the last group, masked by position, the next live slot's first
        # group in flight meanwhile
        last = n_steps - 1
        buf = jax.lax.rem(base + last, 2)
        fetch(nxt_slot, 0, 1 - buf, nxt_blocks, wait=False)
        fetch(s, last, buf, n_live - last * group, wait=True)
        _, l, acc = scored(carry, kbuf[buf, 0],
                           limit=pos_ref[s] - last * keys)
        parity_ref[0] = jax.lax.rem(base + n_steps, 2)
        o_ref[0] = (acc / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("num_heads", "v_width", "scale",
                                             "interpret"))
def _latent_decode_pallas(q, pool, btab, pos, rows, num_heads, v_width, scale,
                          interpret):
    """q [S, 1, nh*W]; pool [NB, 1, BS, W]; -> [S, 1, nh*v_width]."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    s = q.shape[0]
    _, _, block_size, w = pool.shape
    n_logical = btab.shape[1]
    group = max(1, min(_DECODE_STEP_KEYS // block_size, n_logical))
    with jax.named_scope("latent_paged_attention"):
        out = pl.pallas_call(
            functools.partial(
                _latent_decode_kernel, n_slots=s, n_logical=n_logical,
                block_size=block_size, group=group, v_width=v_width,
                scale=scale),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3,
                grid=(s,),
                in_specs=[pl.BlockSpec((1, num_heads, w),
                                       lambda i, *_: (i, 0, 0)),
                          pl.BlockSpec(memory_space=pl.ANY)],
                out_specs=pl.BlockSpec((1, num_heads, v_width),
                                       lambda i, *_: (i, 0, 0)),
                scratch_shapes=[
                    pltpu.VMEM((2, 1, group * block_size, w), pool.dtype),
                    pltpu.SemaphoreType.DMA((2, group)),
                    pltpu.SMEM((1,), jnp.int32)]),
            out_shape=jax.ShapeDtypeStruct((s, num_heads, v_width), q.dtype),
            # slots run in order: a slot's first group is fetched while the
            # live slot before it scores its last
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=64 * 1024 * 1024),
            interpret=interpret,
        )(btab.reshape(-1), pos, rows,
          q.reshape(s, num_heads, w).astype(pool.dtype), pool)
    return out.reshape(s, 1, num_heads * v_width)


@functools.partial(jax.jit, static_argnames=("num_heads", "v_width", "scale",
                                             "interpret"))
def _latent_pallas(q, pool, btab, pos, rows, num_heads, v_width, scale,
                   interpret):
    """q [S, G, nh*W], G whole tiles; pool [NB, 1, BS, W];
    -> [S, G, nh*v_width]."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    s, g, _ = q.shape
    _, _, block_size, w = pool.shape
    n_logical = btab.shape[1]
    tq = _TILE_POSITIONS
    n_rows = tq * num_heads
    group = max(1, min(_STEP_KEYS // block_size, n_logical))
    with jax.named_scope("latent_paged_attention"):
        out = pl.pallas_call(
            functools.partial(
                _latent_kernel, n_logical=n_logical, block_size=block_size,
                group=group, num_heads=num_heads, tq=tq, v_width=v_width,
                scale=scale),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3,
                grid=(s, g // tq),
                in_specs=[pl.BlockSpec((1, n_rows, w),
                                       lambda i, t, *_: (i, t, 0)),
                          pl.BlockSpec(memory_space=pl.ANY)],
                out_specs=pl.BlockSpec((1, n_rows, v_width),
                                       lambda i, t, *_: (i, t, 0)),
                scratch_shapes=[
                    pltpu.VMEM((2, 1, group * block_size, w), pool.dtype),
                    pltpu.SemaphoreType.DMA((2, group)),
                    pltpu.VMEM((n_rows, 1), jnp.float32),
                    pltpu.VMEM((n_rows, 1), jnp.float32),
                    pltpu.VMEM((n_rows, v_width), jnp.float32)]),
            out_shape=jax.ShapeDtypeStruct((s, g * num_heads, v_width),
                                           q.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary"),
                vmem_limit_bytes=64 * 1024 * 1024),
            interpret=interpret,
        )(btab.reshape(-1), pos, rows,
          q.reshape(s, g * num_heads, w).astype(pool.dtype), pool)
    return out.reshape(s, g, num_heads * v_width)


def latent_paged_attention(q, pool, btab, pos, num_heads, v_width, scale,
                           rows=None, backend=None):
    """q [S, G, nh*W]: each head's padded query row `[q~_h, q_pe_h, 0]` at
    positions pos..pos+G-1 of slot s; pool [NB, 1, BS, W] the latent rows;
    btab [S, NLB]; pos [S] (any shape of S elements); `rows` [S] how many of
    a slot's G positions are real (G when absent). Row g attends cache
    positions 0..pos+g. Returns [S, G, nh*v_width]: per head the weighted
    sum of the rows' first `v_width` values."""
    s, g, _ = q.shape
    btab = btab.astype(jnp.int32)
    pos = pos.reshape(-1).astype(jnp.int32)
    rows = (jnp.full((s,), g, jnp.int32) if rows is None
            else rows.reshape(-1).astype(jnp.int32))
    lowering = latent_attention_lowering(pool.shape[-1], v_width, num_heads,
                                         g, backend)
    if lowering == KERNEL:
        return latent_attention_body(g)(
            q, pool, btab, pos, rows, num_heads, v_width, float(scale),
            interpret=backend == "pallas_interpret")
    # rows beyond a slot's real ones return values nobody reads
    return _latent_composite(q, pool, btab, pos, num_heads, v_width,
                             float(scale))


@register_op("latent_paged_attention", stop_gradient=True)
def _latent_paged_attention_op(ctx, ins, attrs):
    out = latent_paged_attention(
        ins["Q"][0], ins["Pool"][0], ins["BlockTable"][0], ins["Pos"][0],
        attrs["num_heads"], attrs["v_width"], attrs["scale"],
        rows=ins["Rows"][0] if ins.get("Rows") else None,
        backend=attrs.get("backend"))
    return {"Out": [out]}


@register_op("latent_head_proj", stop_gradient=True)
def _latent_head_proj_op(ctx, ins, attrs):
    """The two halves of `kv_b_proj` [c, nh*(dk+dv)] (a head's columns:
    dk of keys, then dv of values), applied around the latent read:
    mode "absorb_q": X [.., nh*dk] -> [.., nh*c]  (q~_h = W_k_h q_nope_h)
    mode "expand_v": X [.., nh*c]  -> [.., nh*dv] (o_h = W_v_h^T ctx_h)."""
    x, w = ins["X"][0], ins["W"][0]
    nh, dk, dv = attrs["num_heads"], attrs["k_dim"], attrs["v_dim"]
    c = w.shape[0]
    w3 = w.reshape(c, nh, dk + dv)
    lead = x.shape[:-1]
    if attrs["mode"] == "absorb_q":
        out = jnp.einsum("nhd,chd->nhc", x.reshape(-1, nh, dk),
                         w3[:, :, :dk], preferred_element_type=jnp.float32)
    elif attrs["mode"] == "expand_v":
        out = jnp.einsum("nhc,chd->nhd", x.reshape(-1, nh, c),
                         w3[:, :, dk:], preferred_element_type=jnp.float32)
    else:
        raise ValueError(f"latent_head_proj: unknown mode {attrs['mode']!r}")
    return {"Out": [out.reshape(lead + (-1,)).astype(x.dtype)]}
