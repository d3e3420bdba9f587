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
selection (index write, scores, `top_k`, and the gather of the picked groups'
rows of `c` into a dense scratch [N, top_groups * kpool + a block, W], the
valid rows first, `count` of them): the composite attends the scratch in
`jax.numpy`; the kernel (a TPU) is the latent read's DECODE body
(`latent_attention._latent_decode_pallas`) over the scratch as a pool of its
own, a row of the tick a "slot" of one query position. Scopes: `dsa_index`
(write, scores, top-k) and `sparse_latent_attention` (gather, attend).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..framework.registry import register_op
from ..ops.tensor_ops import _write_pool_blocks, _write_pool_rows
from .latent_attention import (KERNEL, _MASKED, _latent_decode_pallas,
                               latent_attention_lowering)

_HEAD_BLOCK = 8         # index heads scored at a time (bounds the scratch)


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
    """Rows of a tick row's scratch: the selection and the tail's group,
    rounded up to whole blocks."""
    k = min(top_groups, n_logical * block_size // kpool)
    return -(-(k + 1) * kpool // block_size) * block_size


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
    n, s = q.shape[0], btab.shape[0]
    _, _, block_size, w = pool.shape
    n_logical = btab.shape[1]
    gpb = block_size // kpool
    f32, i32 = jnp.float32, jnp.int32
    pos = positions.reshape(-1).astype(i32)
    btab, wblock, woff = (t.astype(i32) for t in (btab, wblock.reshape(-1),
                                                  woff.reshape(-1)))
    live = wblock > 0
    with jax.named_scope("dsa_index"):
        d = ki.shape[-1]
        # the rotated index rows, rounded as the activations are
        qi = rotate_first(qi.reshape(n, index_heads, d), pos, table) \
            .astype(q.dtype)
        ki = rotate_first(ki.reshape(n, 1, d), pos, table)[:, 0] \
            .astype(q.dtype).astype(f32)
        wi = wi.reshape(n, index_heads).astype(f32)
        chunk_lanes = None
        if lanes is not None:
            lbtab, lwblocks, lrows, chunk = lanes
            lbtab, lrows = lbtab.astype(i32), lrows.reshape(-1).astype(i32)
            chunk_lanes = (lwblocks.reshape(-1).astype(i32), lrows, chunk)
        ipool = write_index(ipool, ki, wblock, woff, kpool, chunk_lanes)
        scores = index_scores(
            qi[:s, None], wi[:s, None],
            ipool[btab].reshape(s, n_logical * gpb, d),
            head_block=index_heads)[:, 0]
        tab = btab
        if lanes is not None:
            n_lanes = lbtab.shape[0]
            sl = index_scores(
                qi[s:].reshape(n_lanes, chunk, index_heads, d),
                wi[s:].reshape(n_lanes, chunk, index_heads),
                ipool[lbtab].reshape(n_lanes, n_logical * gpb, d))
            scores = jnp.concatenate([scores, sl.reshape(n - s, -1)], axis=0)
            tab = jnp.concatenate([btab, jnp.repeat(lbtab, chunk, axis=0)])
            live = jnp.concatenate(
                [live, (jnp.arange(chunk)[None, :] < lrows[:, None])
                 .reshape(-1)])
        t_rows = scratch_rows(top_groups, kpool, block_size, n_logical)
        ids, count = select(scores, pos, tab, kpool, top_groups, gpb,
                            t_rows // kpool)
        count = jnp.maximum(count, 1)
    with jax.named_scope("sparse_latent_attention"):
        groups = pool.reshape(-1, kpool, w)
        lowering = latent_attention_lowering(w, v_width, num_heads, 1, backend)
        if lowering != KERNEL:
            out = _attend_composite(q, groups[ids].reshape(n, t_rows, w),
                                    count, num_heads, v_width, float(scale))
            return out, ipool
        # the scratch as a pool of its own behind a null block (the decode
        # body reads a slot at position 0 on block 0 as idle), a row of the
        # tick a slot whose table is its own blocks in order
        nb = t_rows // block_size
        ids = jnp.concatenate([jnp.zeros((gpb,), i32), ids.reshape(-1)])
        scratch = groups[ids].reshape(1 + n * nb, 1, block_size, w)
    # the decode body opens the scope `latent_paged_attention` itself; the
    # reader of this read sums the three scopes
    out = _latent_decode_pallas(
        q, scratch, 1 + jnp.arange(n * nb, dtype=i32).reshape(n, nb),
        count - 1, live.astype(i32), num_heads, v_width, float(scale),
        interpret=backend == "pallas_interpret")
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
