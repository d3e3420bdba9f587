"""A latent read that SELECTS the cached rows it attends (DeepSeek sparse
attention over pooled index keys), through the block table.

Beside a layer's latent rows (`fusion/latent_attention.py`: ONE row `c` a
position, shared by all heads) stands a second pool, the INDEX pool: one row
of `index_dim` values a GROUP of `kpool` consecutive positions, the mean of
the group's index keys. A row of the tick at position t (a decode row, or
row c of a lane's chunk) reads:

    qI_j = the row's index queries, j over `index_heads`; kI_s the index key
           of position s; the first `table` width of both rotated at the
           token's own position (rotate-half)
    KI_b = mean over s in group b of kI_s              (group b: kpool b ..)
    I_b  = sum_j w_j relu(qI_j . KI_b)   over the WHOLE groups b < (t+1)//kpool
    the selection: the `top_groups` largest I_b (all of them where there are
    no more), and the TAIL: the positions kpool ((t+1)//kpool) .. t
    out_h = softmax over the selected positions s of (q_h . c_s * scale) c_s

Up to `top_groups` whole groups the read IS the dense latent read. The
selection is a property of the ROW, so lanes and decode rows are one batch
of N rows here: each scores the pooled rows of its own table.

The pooled row is written by the tick that visits the group. A lane's chunk
starts on a block boundary (groups are whole; the rows past a short chunk's
last real row count as zero), so it writes its blocks' pooled rows outright.
A decode row at position t ADDS kI_t / kpool to its group's row (t % kpool
== 0: it starts the row), so the half-full group of a request lives in the
pool itself between its ticks, as a running sum that nothing reads before
the group is whole: no state beside the pools.

`sparse_latent_attention` is ONE op with two lowerings that share the
selection (`tick_selection`: index write, scores, the sort: `ids` [N, G] the
picked PHYSICAL groups, the valid ones first, and `count` the selected
positions).

The selection runs for the rows that HOLD A TOKEN (ISSUE 64). A tick's shapes
are static (64 slots, two lanes of 128), its live rows are few (7 decode rows
at the repository cell's median tick, one busy lane of two, a short last
chunk), and the sort costs the same for a row whatever the row holds. So what
grows with the rows (the index pool gathered through the decode rows' tables,
their scores, a lane's scores, the sort over all N rows) runs
`over_live_rows`: `_STEP` rows at a time over the order that puts the live
rows first, in a loop whose trip count the device takes from the live count
(`rung`: the rows it reaches), the results put back to the rows' own places.
A sort of 8 rows costs what 8 rows of the parent's one sort cost, so the loop
is ONE small sort program where a ladder of row counts under a switch (built
first and timed: PERF.md section 6, PR 64) was a program a rung. A live row
gets the scores, the stable sort, the ids in the order and the count it got
when every row was sorted; an idle row reads ids 0 and count 1, which the
kernel never fetches (`n_fetch` 0) and the composite attends as the null
block. A tick with no live row of a kind (a mixed tick with no decode row:
the contexts' prefill) runs no step of it. One algorithm at the row count it
observes: no option chooses. The pager counts the rows it reaches
(`engine/tick` `dsa_scored_rows`, by `rung` itself).

Neither lowering copies a pool: the latent pool is read as the engine keeps
it, [NB, 1, BS, W], or seen as [NB * BS, W], which is a bitcast (a block is
whole tiles).

- the composite gathers the picked groups' SINGLE rows of that view into a
  dense [N, G * kpool, W] and attends it in `jax.numpy`;
- the kernel (a TPU, ISSUE 62) is a body of its own, `_sparse_fetch_kernel`,
  with the latent decode body's structure (`latent_attention.
  _latent_decode_kernel`: double-buffered DMAs from the pool in HBM, online
  softmax, idle rows skipped, the next live row's first step in flight during
  a row's last) and the row's PICKED groups as its table: one DMA a picked
  group, of the 8 rows of the pool the group lies in (a DMA may slice a
  bfloat16 pool by whole (8, 128) tiles and no finer: Mosaic refuses 4 rows),
  the 4 rows beside it masked by a bias the XLA side builds from `ids % 2`;
  two picked groups of one chunk are fetched twice and each masked to its
  own. No scratch in HBM, no fetch for an idle row or for a slot past a row's
  selection. One form for decode rows and lanes: timed alone at the
  repository cell's shapes it beat XLA's gather (by groups of a regrouped
  pool, PR 61's, and by single rows) at 11 and 25 live decode rows and at the
  mixed tick's 320 (PERF.md section 6, PR 62). Where a group is no whole
  number of such chunks (`fetch_chunk`) the read is the composite's.

Scopes: `dsa_index` (write, scores, sort: `tick_selection`, the bodies of
its loops within) and `sparse_latent_attention` (the composite's gather
and attend; the Mosaic call `sparse_fetch` inside it).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..framework.registry import register_op
from ..ops.tensor_ops import _write_pool_blocks, _write_pool_rows
from .latent_attention import (KERNEL, _M_INIT, _MASKED,
                               latent_attention_lowering)

_HEAD_BLOCK = 8         # index heads scored at a time (bounds the scratch)
_FETCH_ROWS = 8         # rows of the pool a DMA may slice (its HBM tiling)
_FETCH_STEP_KEYS = 512  # fetched rows a step of the fetch kernel scores
# rows the selection takes at a time: a sort of 8 rows of 8,832 scores costs
# what 8 rows of a larger sort cost (~0.1 ms; PERF.md section 6, PR 64)
_STEP = 8


def rotate_first(x, pos, table):
    """x [N, h, d]: the first `table.shape[1]` values of each head rotated
    (rotate-half pairs) by row `pos[n]` of `table` [T, r] (cos | sin);
    float32."""
    r = table.shape[-1]
    half = r // 2
    row = table[pos].astype(jnp.float32)
    cos, sin = row[:, None, :half], row[:, None, half:]
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., :half], xf[..., half:r]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            xf[..., r:]], axis=-1)


def write_index(ipool, ki, wblock, woff, kpool, lanes=None):
    """The tick's index keys `ki` [N, d] (rotated, float32) into the index
    pool [NB, 1, BS / kpool, d]: a lane's chunk as whole blocks of group
    means, a decode row added to its group's running sum."""
    dtype, f32 = ipool.dtype, jnp.float32
    s = wblock.shape[0]
    if lanes is not None:
        lwblocks, lrows, chunk = lanes
        kl = ki[s:].reshape(lrows.shape[0], chunk, -1)
        real = jnp.arange(chunk)[None, :] < lrows[:, None]
        kl = jnp.where(real[..., None], kl, 0.0)
        pooled = jnp.mean(kl.reshape(kl.shape[0], chunk // kpool, kpool, -1),
                          axis=2)
        ipool = _write_pool_blocks(ipool, pooled.astype(dtype), lwblocks,
                                   new_heads=1)
    group, first = woff // kpool, woff % kpool == 0
    held = ipool[wblock, 0, group].astype(f32)                   # [S, d]
    new = jnp.where(first[:, None], 0.0, held) + ki[:s] / kpool
    return _write_pool_rows(ipool, new.astype(dtype)[:, None, :], wblock,
                            group)


def index_scores(qi, wi, pooled, head_block=_HEAD_BLOCK):
    """qi [R, Q, h, d], wi [R, Q, h] float32, pooled [R, G, d] -> I [R, Q, G]
    float32: sum_j w_j relu(qI_j . KI_g), `head_block` heads at a time (each
    pass reads `pooled` again: a lane's chunk shares its pooled rows among Q
    = 128 queries and bounds the [Q, heads, G] scores by the block; a decode
    row, Q = 1, takes every head in ONE pass)."""
    out = 0.0
    for lo in range(0, qi.shape[2], head_block):
        hi = lo + head_block
        dots = jnp.einsum("rqhd,rgd->rqhg", qi[:, :, lo:hi], pooled,
                          preferred_element_type=jnp.float32)
        out = out + jnp.sum(jax.nn.relu(dots) * wi[:, :, lo:hi, None], axis=2)
    return out


def select(scores, pos, tab, kpool, top_groups, groups_per_block, n_scratch):
    """scores [N, G] float32 over a row's logical groups; pos [N]; tab
    [N, NLB] the row's block table -> (ids [N, n_scratch] int32: the PHYSICAL
    groups whose rows the read attends, the valid ones first, then the tail's
    group, then the null block's; count [N]: the valid ROWS of the scratch).
    The physical ids ride through the sort as its payload: looked up after a
    `top_k` they were a gather of N * top_groups scalars, 10 ns each on a
    v5e (PERF.md section 6, PR 61)."""
    n, g = scores.shape
    n_whole = (pos + 1) // kpool
    eligible = jnp.arange(g)[None, :] < n_whole[:, None]
    phys = (jnp.repeat(tab, groups_per_block, axis=1) * groups_per_block
            + jnp.tile(jnp.arange(groups_per_block, dtype=tab.dtype),
                       tab.shape[1])[None, :]).astype(jnp.int32)
    k = min(top_groups, g)
    # ascending in -score, stable: among equals the lower group first
    _, picked = jax.lax.sort((jnp.where(eligible, -scores, jnp.inf), phys),
                             num_keys=1)
    n_valid = jnp.minimum(n_whole, k)
    tail = jnp.take_along_axis(
        phys, jnp.minimum(n_whole, g - 1)[:, None], axis=1)
    j = jnp.arange(n_scratch)[None, :]
    ids = jnp.where(j < n_valid[:, None],
                    jnp.pad(picked[:, :k], ((0, 0), (0, n_scratch - k))),
                    jnp.where(j == n_valid[:, None], tail, 0))
    return ids, n_valid * kpool + (pos + 1) % kpool


def rung(count, n_rows, step=_STEP):
    """The rows the selection runs over where `count` of `n_rows` rows hold
    a token: whole steps of `step`. Plain integers: the pager counts
    `dsa_scored_rows` by it."""
    return min(-(-count // step) * step, n_rows)


def over_live_rows(fn, live, rows, fill, step=_STEP):
    """`fn(*rows)` (arrays [n, ...] in, a tree of arrays [n, ...] out, row by
    row) run `step` rows at a time over the order that puts the rows `live`
    [n] flags first, each kind in its own order, for as many steps as hold
    the live rows: a loop whose trip count the DEVICE takes from the flags
    (no live row: no step). The results go back to the rows' own places; a
    row no step reached reads `fill` (a tree like `fn`'s result). A live row
    gets what `fn` over all rows gave it."""
    n = live.shape[0]
    i32 = jnp.int32
    n_live = jnp.sum(live, dtype=i32)
    # where a row stands in that order, and the order itself: the inverse
    # permutation by a compare of n x n (no sort, no scatter), padded to
    # whole steps with rows nothing reads back
    place = jnp.where(live, jnp.cumsum(live, dtype=i32) - 1,
                      n_live + jnp.cumsum(~live, dtype=i32) - 1)
    at = jnp.arange(n, dtype=i32)
    order = jnp.pad(jnp.sum(jnp.where(place[:, None] == at[None, :],
                                      at[:, None], 0), axis=0), (0, -n % step))
    shapes = jax.eval_shape(fn, *(jax.ShapeDtypeStruct(
        (step,) + a.shape[1:], a.dtype) for a in rows))

    def one_step(i, outs):
        taken = jax.lax.dynamic_slice(order, (i * step,), (step,))
        new = fn(*(a[taken] for a in rows))
        return jax.tree.map(
            lambda out, part: jax.lax.dynamic_update_slice(
                out, part, (i * step,) + (0,) * (part.ndim - 1)), outs, new)

    outs = jax.lax.fori_loop(
        0, (n_live + step - 1) // step, one_step,
        jax.tree.map(lambda shape, value: jnp.full(
            (order.shape[0],) + shape.shape[1:], value, shape.dtype),
            shapes, fill))
    return jax.tree.map(lambda out: out[place], outs)


def _attend_composite(q, scratch, count, num_heads, v_width, scale):
    n, t, w = scratch.shape
    f32 = jnp.float32
    rows = scratch.astype(f32)
    sc = jnp.einsum("nhw,ntw->nht", q.reshape(n, num_heads, w).astype(f32),
                    rows) * scale
    sc = jnp.where(jnp.arange(t)[None, None, :] < count[:, None, None], sc,
                   _MASKED)
    p = jax.nn.softmax(sc, axis=-1)
    out = jnp.einsum("nht,ntv->nhv", p, rows[..., :v_width])
    return out.reshape(n, 1, num_heads * v_width).astype(q.dtype)


def scratch_rows(top_groups, kpool, block_size, n_logical):
    """Rows of a tick row's selection: the picked groups and the tail's,
    rounded up to whole blocks."""
    k = min(top_groups, n_logical * block_size // kpool)
    return -(-(k + 1) * kpool // block_size) * block_size


def picked_rows(ids, kpool):
    """ids [..., G] physical groups -> [..., G * kpool] the rows of the pool
    seen as [NB * BS, W] they hold."""
    rows = ids[..., None] * kpool + jnp.arange(kpool, dtype=ids.dtype)
    return rows.reshape(ids.shape[:-1] + (-1,))


def fetch_chunk(kpool, block_size):
    """Rows of ONE DMA of the fetch kernel: the `_FETCH_ROWS` rows of the pool
    a picked group lies in (a DMA slices whole tiles of the pool as it lies in
    HBM, (8, 128) of them), or the group itself where it is whole tiles.
    None where neither holds: the read is then the composite's."""
    chunk = max(kpool, _FETCH_ROWS)
    if chunk % kpool or chunk % _FETCH_ROWS or block_size % chunk:
        return None
    return chunk


def _sparse_fetch_kernel(fetch_ref, src_ref, nxt_ref, q_ref, bias_ref,
                         pool_hbm, o_ref, kbuf, sem, parity_ref, *,
                         step_chunks, chunk, v_width, scale):
    """One grid step = one row of the tick: `num_heads` rows [R, W] against
    the row's PICKED latent rows, fetched from the pool as it lies.

    `src_ref` [1, 1, G] (SMEM, this row's; `nxt_ref` the next row's) the pool
    row each picked group's chunk starts on, `fetch_ref[r]` how many of them
    hold a selected position (0: an idle row, which fetches nothing and
    returns zeros). They come `step_chunks` DMAs of `chunk` rows a step into
    one of two buffers, the next step in flight while this one is scored, and
    during a row's last step the first step of the row after it, where that
    row is live (`parity_ref` says which buffer it lands in). `bias_ref`
    [1, steps, keys] masks what a chunk holds beside its group, the tail's
    rows past the row's position, and the slots not fetched (the buffers are
    zeroed at the first step: finite). Two picked groups of one chunk are two
    fetches, each masked to its own group.

    Every step but a row's last is whole, and its body is ONE block of code
    as the latent decode body's is: the next step's starts written out, each
    under its own condition (the next step may be the short last one), and
    this step's waits, so that the compiler lays their scalar work beside the
    products; 528 DMAs a row are what bounds the read (PERF.md section 6, PR
    62). The last step's copies run in loops. Same arithmetic as the latent
    decode body."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    r, n_tick = pl.program_id(0), pl.num_programs(0)
    n_fetch = fetch_ref[r]
    n_rows = q_ref.shape[1]

    def copy(ref, step, buf, g):
        """The DMA of slot g of step `step` of the row `ref` lists."""
        src = pl.multiple_of(ref[0, 0, step * step_chunks + g], chunk)
        dst = g * chunk
        if not isinstance(g, int):
            dst = pl.multiple_of(dst, chunk)
        return pltpu.make_async_copy(
            pool_hbm.at[pl.ds(src, chunk), :],
            kbuf.at[buf, pl.ds(dst, chunk), :], sem.at[buf])

    def fetch(ref, step, buf, count, wait):
        """Start (or wait for) the first `count` DMAs of a step, in a loop."""
        def one(g, carry):
            cp = copy(ref, step, buf, g)
            cp.wait() if wait else cp.start()
            return carry
        jax.lax.fori_loop(0, jnp.minimum(count, step_chunks), one, 0)

    @pl.when(r == 0)
    def _():
        kbuf[...] = jnp.zeros(kbuf.shape, kbuf.dtype)
        parity_ref[0] = 0

    @pl.when(n_fetch == 0)
    def _():
        o_ref[0] = jnp.zeros(o_ref.shape[1:], o_ref.dtype)

    @pl.when(n_fetch > 0)
    def _():
        base = parity_ref[0]      # the buffer this row's first step is in
        n_steps = jax.lax.div(n_fetch + step_chunks - 1, step_chunks)
        q = q_ref[0]                                          # [R, W]

        # the live row before this one started this row's first step
        @pl.when((r == 0) | (fetch_ref[jnp.maximum(r - 1, 0)] == 0))
        def _():
            fetch(src_ref, 0, base, n_fetch, wait=False)

        def scored(carry, j, buf):
            m, l, acc = carry
            k = kbuf[buf]                                     # [keys, W]
            sc = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale \
                + bias_ref[0, pl.ds(j, 1), :]                 # [R, keys]
            m_new = jnp.maximum(m, jnp.max(sc, axis=1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(sc - m_new)
            l = alpha * l + jnp.sum(p, axis=1, keepdims=True)
            acc = alpha * acc + jax.lax.dot_general(
                p.astype(k.dtype), k[:, :v_width],
                (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
            return m_new, l, acc

        def whole_step(j, carry):
            buf = jax.lax.rem(base + j, 2)
            left = n_fetch - (j + 1) * step_chunks
            for g in range(step_chunks):
                @pl.when(g < left)
                def _():
                    copy(src_ref, j + 1, 1 - buf, g).start()
            for g in range(step_chunks):
                copy(src_ref, j, buf, g).wait()
            return scored(carry, j, buf)

        carry = jax.lax.fori_loop(
            0, n_steps - 1, whole_step,
            (jnp.full((n_rows, 1), _M_INIT, jnp.float32),
             jnp.zeros((n_rows, 1), jnp.float32),
             jnp.zeros((n_rows, v_width), jnp.float32)))
        last = n_steps - 1
        buf = jax.lax.rem(base + last, 2)
        fetch(nxt_ref, 0, 1 - buf,
              jnp.where(r + 1 < n_tick,
                        fetch_ref[jnp.minimum(r + 1, n_tick - 1)], 0),
              wait=False)
        fetch(src_ref, last, buf, n_fetch - last * step_chunks, wait=True)
        _, l, acc = scored(carry, last, buf)
        parity_ref[0] = jax.lax.rem(base + n_steps, 2)
        o_ref[0] = (acc / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("num_heads", "v_width", "scale",
                                             "kpool", "chunk", "interpret"))
def _sparse_fetch_pallas(q, pool, ids, count, live, num_heads, v_width, scale,
                         kpool, chunk, interpret):
    """q [N, 1, nh*W]; pool [NB, 1, BS, W]; ids [N, G] the picked physical
    groups, the valid ones first; count [N] the selected positions; live [N]
    -> [N, 1, nh*v_width]. ONE Mosaic call, `sparse_fetch`, made inside the
    scope `sparse_latent_attention`."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, g = ids.shape
    w = pool.shape[-1]
    i32 = jnp.int32
    per = chunk // kpool                    # groups a chunk holds
    step_chunks = min(max(1, _FETCH_STEP_KEYS // chunk), g)
    n_steps = -(-g // step_chunks)
    keys = step_chunks * chunk
    ids = jnp.pad(ids, ((0, 0), (0, n_steps * step_chunks - g)))
    c = jnp.arange(chunk, dtype=i32)
    slot = jnp.arange(ids.shape[1], dtype=i32)
    valid = ((c // kpool == (ids % per)[:, :, None])
             & (slot[None, :, None] * kpool + c % kpool
                < count[:, None, None]))
    bias = jnp.where(valid, 0.0, _MASKED).astype(jnp.float32) \
        .reshape(n, n_steps, keys)
    n_fetch = jnp.where(live > 0, -(-count // kpool), 0).astype(i32)
    src = ((ids // per) * chunk)[:, None, :]
    with jax.named_scope("sparse_fetch"):
        out = pl.pallas_call(
            functools.partial(
                _sparse_fetch_kernel, step_chunks=step_chunks, chunk=chunk,
                v_width=v_width, scale=scale),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(n,),
                in_specs=[pl.BlockSpec((1, 1, ids.shape[1]),
                                       lambda i, *_: (i, 0, 0),
                                       memory_space=pltpu.SMEM),
                          pl.BlockSpec((1, 1, ids.shape[1]),
                                       lambda i, *_: (
                                           jnp.minimum(i + 1, n - 1), 0, 0),
                                       memory_space=pltpu.SMEM),
                          pl.BlockSpec((1, num_heads, w),
                                       lambda i, *_: (i, 0, 0)),
                          pl.BlockSpec((1, n_steps, keys),
                                       lambda i, *_: (i, 0, 0)),
                          pl.BlockSpec(memory_space=pl.ANY)],
                out_specs=pl.BlockSpec((1, num_heads, v_width),
                                       lambda i, *_: (i, 0, 0)),
                scratch_shapes=[pltpu.VMEM((2, keys, w), pool.dtype),
                                pltpu.SemaphoreType.DMA((2,)),
                                pltpu.SMEM((1,), jnp.int32)]),
            out_shape=jax.ShapeDtypeStruct((n, num_heads, v_width), q.dtype),
            # rows run in order: a row's first step is fetched while the
            # live row before it scores its last
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=64 * 1024 * 1024),
            interpret=interpret,
        )(n_fetch, src, src, q.reshape(n, num_heads, w).astype(pool.dtype),
          bias, pool.reshape(-1, w))
    return out.reshape(n, 1, num_heads * v_width)


def tick_selection(ipool, qi, ki, wi, pos, table, btab, wblock, woff, lanes,
                   *, dtype, index_heads, top_groups, kpool):
    """The selection of a tick's N rows (the arguments of
    `sparse_latent_attention`, the integers int32, `dtype` the activations'):
    the rows' index keys written into `ipool`, then ids [N, G], count [N]
    (`select`'s) of the rows that hold a token, `live` [N]. What grows with
    the rows (the index pool's gather through the table, the scores, the
    sort) runs `over_live_rows`, 8 rows at a time; an idle row reads ids 0 and count 1, which
    nothing attends."""
    n, s, n_logical = qi.shape[0], btab.shape[0], btab.shape[1]
    d, gpb = ki.shape[-1], ipool.shape[2]
    f32 = jnp.float32
    live = wblock > 0
    # the rotated index rows, rounded as the activations are
    qi = rotate_first(qi.reshape(n, index_heads, d), pos, table).astype(dtype)
    ki = rotate_first(ki.reshape(n, 1, d), pos, table)[:, 0] \
        .astype(dtype).astype(f32)
    wi = wi.reshape(n, index_heads).astype(f32)
    ipool = write_index(ipool, ki, wblock, woff, kpool, lanes and lanes[1:])

    def pooled(tab):    # the index pool's rows of each row of `tab`
        return ipool[tab].reshape(tab.shape[0], n_logical * gpb, d)

    scores = over_live_rows(
        lambda qi, wi, tab: index_scores(
            qi[:, None], wi[:, None], pooled(tab),
            head_block=index_heads)[:, 0],
        live, (qi[:s], wi[:s], btab), 0.0)
    tab = btab
    if lanes is not None:
        lbtab, _, lrows, chunk = lanes
        n_lanes = lbtab.shape[0]
        # a lane's queries share its pooled rows: a lane a step, an idle
        # LANE none
        sl = over_live_rows(
            lambda qi, wi, tab: index_scores(qi, wi, pooled(tab)),
            lrows > 0,
            (qi[s:].reshape(n_lanes, chunk, index_heads, d),
             wi[s:].reshape(n_lanes, chunk, index_heads), lbtab), 0.0,
            step=1)
        scores = jnp.concatenate([scores, sl.reshape(n - s, -1)], axis=0)
        tab = jnp.concatenate([btab, jnp.repeat(lbtab, chunk, axis=0)])
        live = jnp.concatenate(
            [live, (jnp.arange(chunk)[None, :] < lrows[:, None]).reshape(-1)])
    n_scratch = scratch_rows(top_groups, kpool, gpb * kpool, n_logical) \
        // kpool
    ids, count = over_live_rows(
        lambda scores, pos, tab: select(scores, pos, tab, kpool, top_groups,
                                        gpb, n_scratch),
        live, (scores, pos, tab), (0, 1))
    return ids, jnp.maximum(count, 1), live, ipool


def sparse_latent_attention(q, pool, ipool, qi, ki, wi, positions, table,
                            btab, wblock, woff, lanes=None, *, num_heads,
                            v_width, scale, index_heads, top_groups, kpool,
                            backend=None):
    """q [N, 1, nh*W] the padded per-head query rows of the tick's N rows (S
    decode rows, then L lanes of C); pool [NB, 1, BS, W] the latent rows, the
    tick's own written; ipool [NB, 1, BS/kpool, d] the index pool, NOT yet
    written; qi [N, index_heads*d], ki [N, d], wi [N, index_heads] the rows'
    index queries, key and head weights; positions [N]; table [T, r]; btab
    [S, NLB], wblock, woff [S] the decode rows' table and write target;
    `lanes` (lbtab [L, NLB], lwblocks [L*C/BS], lrows [L], chunk).
    Returns (out [N, 1, nh*v_width], ipool written)."""
    _, _, block_size, w = pool.shape
    i32 = jnp.int32
    flat = lambda t: t.reshape(-1).astype(i32)  # noqa: E731
    if lanes is not None:
        lbtab, lwblocks, lrows, chunk = lanes
        lanes = (lbtab.astype(i32), flat(lwblocks), flat(lrows), chunk)
    with jax.named_scope("dsa_index"):
        ids, count, live, ipool = tick_selection(
            ipool, qi, ki, wi, flat(positions), table, btab.astype(i32),
            flat(wblock), flat(woff), lanes, dtype=q.dtype,
            index_heads=index_heads, top_groups=top_groups, kpool=kpool)
    with jax.named_scope("sparse_latent_attention"):
        lowering = latent_attention_lowering(w, v_width, num_heads, 1, backend)
        fetched = fetch_chunk(kpool, block_size)
        if lowering == KERNEL and fetched is not None:
            out = _sparse_fetch_pallas(
                q, pool, ids, count, live.astype(i32), num_heads, v_width,
                float(scale), kpool, fetched,
                interpret=backend == "pallas_interpret")
        else:
            # single rows of the pool seen as [NB * BS, W]: a bitcast, blocks
            # are whole tiles
            rows = pool.reshape(-1, w)[picked_rows(ids, kpool)]
            out = _attend_composite(q, rows, count, num_heads, v_width,
                                    float(scale))
    return out, ipool


@register_op("sparse_latent_attention", stop_gradient=True)
def _sparse_latent_attention_op(ctx, ins, attrs):
    flat = lambda t: t.reshape(-1, t.shape[-1])  # noqa: E731
    lanes = None
    if ins.get("LaneBlockTable"):
        lanes = (ins["LaneBlockTable"][0], ins["LaneWBlocks"][0],
                 ins["LaneRows"][0], attrs["chunk"])
    out, ipool = sparse_latent_attention(
        ins["Q"][0], ins["Pool"][0], ins["IndexPool"][0], flat(ins["QI"][0]),
        flat(ins["KI"][0]), flat(ins["WI"][0]), ins["Positions"][0],
        ins["Table"][0], ins["BlockTable"][0], ins["WBlock"][0],
        ins["WOff"][0], lanes, num_heads=attrs["num_heads"],
        v_width=attrs["v_width"], scale=attrs["scale"],
        index_heads=attrs["index_heads"], top_groups=attrs["top_groups"],
        kpool=attrs["kpool"], backend=attrs.get("backend"))
    return {"Out": [out], "IndexPoolOut": [ipool]}
