"""Tensor-manipulation op lowerings.

≙ reference paddle/fluid/operators/{reshape,transpose,concat,split,slice,
gather,scatter,stack,squeeze,unsqueeze,flatten,expand,pad,one_hot,cast,
fill_constant,fill_zeros_like,assign,shape,reverse,multiplex,crop,
label_smooth,lookup_table}_op.cc (SURVEY §2.2 tensor-manip family).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..core.dtypes import convert_dtype
from ..framework.registry import dim_prod, register_op


@register_op("reshape")
def _reshape(ctx, ins, attrs):
    x = ins["X"][0]
    shape = list(attrs["shape"])
    # reference reshape semantics: 0 means copy dim from input, -1 inferred
    for i, d in enumerate(shape):
        if d == 0:
            shape[i] = x.shape[i]
    return {"Out": [jnp.reshape(x, shape)]}


@register_op("transpose")
def _transpose(ctx, ins, attrs):
    return {"Out": [jnp.transpose(ins["X"][0], attrs["axis"])]}


@register_op("concat")
def _concat(ctx, ins, attrs):
    return {"Out": [jnp.concatenate(ins["X"], axis=attrs.get("axis", 0))]}


@register_op("split")
def _split(ctx, ins, attrs):
    x = ins["X"][0]
    axis = attrs.get("axis", 0)
    if attrs.get("sections"):
        idx = np.cumsum(attrs["sections"])[:-1]
        outs = jnp.split(x, idx, axis=axis)
    else:
        outs = jnp.split(x, attrs["num"], axis=axis)
    return {"Out": list(outs)}


@register_op("slice")
def _slice(ctx, ins, attrs):
    x = ins["X"][0]
    axes, starts, ends = attrs["axes"], attrs["starts"], attrs["ends"]
    idx = [slice(None)] * x.ndim
    for ax, s, e in zip(axes, starts, ends):
        idx[ax] = slice(s, e)
    return {"Out": [x[tuple(idx)]]}


@register_op("gather")
def _gather(ctx, ins, attrs):
    return {"Out": [jnp.take(ins["X"][0], ins["Index"][0], axis=0)]}


@register_op("scatter")
def _scatter(ctx, ins, attrs):
    x, index, updates = ins["X"][0], ins["Ids"][0], ins["Updates"][0]
    if attrs.get("overwrite", True):
        return {"Out": [x.at[index].set(updates)]}
    return {"Out": [x.at[index].add(updates)]}


@register_op("stack")
def _stack(ctx, ins, attrs):
    return {"Y": [jnp.stack(ins["X"], axis=attrs.get("axis", 0))]}


@register_op("unstack")
def _unstack(ctx, ins, attrs):
    x = ins["X"][0]
    axis = attrs.get("axis", 0)
    n = x.shape[axis]
    return {"Y": [jnp.squeeze(s, axis=axis)
                  for s in jnp.split(x, n, axis=axis)]}


@register_op("squeeze")
def _squeeze(ctx, ins, attrs):
    axes = attrs.get("axes") or None
    return {"Out": [jnp.squeeze(ins["X"][0],
                                axis=tuple(axes) if axes else None)]}


@register_op("unsqueeze")
def _unsqueeze(ctx, ins, attrs):
    return {"Out": [jnp.expand_dims(ins["X"][0], axis=tuple(attrs["axes"]))]}


@register_op("flatten")
def _flatten(ctx, ins, attrs):
    x = ins["X"][0]
    ax = attrs.get("axis", 1)
    lead = dim_prod(x.shape[:ax]) if ax > 0 else 1
    return {"Out": [jnp.reshape(x, (lead, -1))]}


@register_op("expand")
def _expand(ctx, ins, attrs):
    x = ins["X"][0]
    times = attrs["expand_times"]
    return {"Out": [jnp.tile(x, times)]}


@register_op("expand_as")
def _expand_as(ctx, ins, attrs):
    x, y = ins["X"][0], ins["Y"][0]
    return {"Out": [jnp.broadcast_to(x, y.shape)]}


@register_op("pad")
def _pad(ctx, ins, attrs):
    x = ins["X"][0]
    p = attrs["paddings"]  # flat [before0, after0, before1, after1, ...]
    pads = [(p[2 * i], p[2 * i + 1]) for i in range(x.ndim)]
    return {"Out": [jnp.pad(x, pads, constant_values=attrs.get("pad_value", 0.0))]}


@register_op("pad_constant_like")
def _pad_constant_like(ctx, ins, attrs):
    x, y = ins["X"][0], ins["Y"][0]
    pads = [(0, xd - yd) for xd, yd in zip(x.shape, y.shape)]
    return {"Out": [jnp.pad(y, pads, constant_values=attrs.get("pad_value", 0.0))]}


def _uniform_pos_guard(pos_flat):
    """cache_write's contract: ONE scalar position for the whole batch
    (`Pos.reshape(-1)[0]` is what gets used). A caller feeding per-row
    positions (ragged prompt lengths) would silently have every row
    written at row 0's position — enforce instead (ADVICE r5 #3). Host
    callbacks are a CPU-debug facility (see _nan_guard): the check is
    active on CPU — where the whole test tier runs — and a no-op on an
    accelerator, whose hot path takes no host round trips."""
    if pos_flat.shape[0] <= 1 or jax.default_backend() != "cpu":
        return
    lo = jnp.min(pos_flat)
    hi = jnp.max(pos_flat)

    def _report(lo_v, hi_v):
        if int(lo_v) != int(hi_v):
            raise ValueError(
                f"cache_write requires a uniform position across rows "
                f"(contract: Pos is one scalar broadcast to the batch), "
                f"got per-row positions spanning [{int(lo_v)}, "
                f"{int(hi_v)}]; write ragged rows via separate "
                f"cache_write calls or a vmapped update")

    jax.debug.callback(_report, lo, hi)


@register_op("cache_write", stop_gradient=True)
def _cache_write(ctx, ins, attrs):
    """Write `New` (size-1 on `axis`) into `Cache` at position `Pos` along
    `axis` via dynamic_update_slice — the KV-cache decode idiom. Inside a
    scan carry XLA performs the update in place, so the per-step cache
    cost is one row write + the attention read, not a full read+rewrite of
    the cache (the one-hot outer-product formulation's cost). No reference
    analogue: the reference's while_op decoder re-runs attention over
    growing LoD tensors instead of caching.

    Two position modes, selected by the `batch_axis` attr:

    - batch_axis None (default): `Pos` must be UNIFORM — a single
      position (any tensor; every element equal). Non-uniform per-row
      positions raise on CPU (enforced via host callback — inactive on
      TPU, where host send/recv is unavailable).
    - batch_axis set: `Pos` holds ONE position PER ROW of `Cache` along
      `batch_axis` (`Pos.reshape(-1)` length == that dim) and each row is
      written at its own position — the slot-indexed KV cache the
      continuous-batching serving engine needs (a slot mid-prompt and a
      slot mid-generation share one compiled tick). Lowers to a vmapped
      dynamic_update_slice over the batch axis."""
    cache = ins["Cache"][0]
    new = ins["New"][0].astype(cache.dtype)
    pos_flat = ins["Pos"][0].reshape(-1)
    axis = attrs["axis"] % cache.ndim
    batch_axis = attrs.get("batch_axis", None)
    if batch_axis is None:
        _uniform_pos_guard(pos_flat)
        pos = pos_flat[0].astype(jnp.int32)
        starts = [jnp.int32(0)] * cache.ndim
        starts[axis] = pos
        return {"Out": [jax.lax.dynamic_update_slice(cache, new,
                                                     tuple(starts))]}
    ba = batch_axis % cache.ndim
    if ba == axis:
        raise ValueError("cache_write: batch_axis must differ from axis")
    if pos_flat.shape[0] != cache.shape[ba]:
        raise ValueError(
            f"cache_write: per-slot Pos has {pos_flat.shape[0]} entries "
            f"but Cache dim {ba} is {cache.shape[ba]}")
    pos = pos_flat.astype(jnp.int32)
    row_axis = axis - (1 if axis > ba else 0)

    def _write_row(c, n, p):
        starts = [jnp.int32(0)] * c.ndim
        starts[row_axis] = p
        return jax.lax.dynamic_update_slice(c, n, tuple(starts))

    out = jax.vmap(_write_row, in_axes=(ba, ba, 0),
                   out_axes=ba)(cache, new, pos)
    return {"Out": [out]}


@register_op("paged_cache_write", stop_gradient=True)
def _paged_cache_write(ctx, ins, attrs):
    """Block-granular KV write for the paged cache (serving/kv_pager.py):
    scatter one new token row per slot into a device-resident block POOL
    instead of a per-slot cache row. `Cache` is the pool
    [n_blocks, nh, block_size, dh] (or its lane-dense declaration
    [n_blocks, nh, block_size*dh/128, 128]: `pool_block_shape`); `New` is
    [S, nh, dh] (one row per tick slot); `BlockIds`/`Offsets` are [S] —
    slot s lands at pool[BlockIds[s], :, Offsets[s], :] of the
    [.., block_size, dh] view. Inactive slots are steered at
    the reserved null block 0 (never mapped by a live block table), so
    one fixed-shape compiled tick serves any mix of live/idle slots —
    the same trick the slot tick plays with its zeroed feeds. Duplicate
    (block, offset) targets are only ever the null block, where any
    write order is acceptable. Lowers to one `dynamic_update_slice` per
    row (`_write_pool_rows`): inside the executor's donated-state path the
    pool updates in place and nothing of pool shape is computed.

    The mixed tick's prefill lanes ride the same op (a pool keeps ONE
    writer a layer): optional `Chunk` [L, C, nh*dh] holds C consecutive
    token rows a lane, starting on a block boundary, and `ChunkBlockIds`
    [L * C/block_size] the physical block of each whole block of them
    (0, the null block, for the blocks a short chunk leaves unused). Each
    lands as ONE whole-block update (`_write_pool_blocks`), not
    block_size row writes."""
    pool = ins["Cache"][0]
    if ins.get("Chunk"):
        pool = _write_pool_blocks(
            pool, ins["Chunk"][0].astype(pool.dtype),
            ins["ChunkBlockIds"][0].reshape(-1).astype(jnp.int32),
            new_heads=ins["New"][0].shape[-2])
    new = ins["New"][0].astype(pool.dtype)
    blocks = ins["BlockIds"][0].reshape(-1).astype(jnp.int32)
    offs = ins["Offsets"][0].reshape(-1).astype(jnp.int32)
    if new.ndim != pool.ndim - 1:
        raise ValueError(
            f"paged_cache_write: New must drop exactly the pool's "
            f"block-size axis (pool {pool.shape}, New {new.shape})")
    if blocks.shape != offs.shape:
        raise ValueError(
            f"paged_cache_write: BlockIds {blocks.shape} and Offsets "
            f"{offs.shape} must agree")
    return {"Out": [_write_pool_rows(pool, new, blocks, offs)]}


POOL_LANES = 128


def pool_block_shape(num_heads, block_size, d_head):
    """The shape of one block of a paged K or V pool, `[nh, R, L]`:
    lane-dense `[nh, block_size * d_head / 128, 128]` when a head's rows
    pack whole 128-lane rows, `[nh, block_size, d_head]` if not (tiny test
    widths). Row-major, the two hold the same values in the same order;
    why a pool is declared lane-dense: fusion/paged_attention.py."""
    if POOL_LANES % d_head == 0 and (block_size * d_head) % POOL_LANES == 0:
        return (num_heads, block_size * d_head // POOL_LANES, POOL_LANES)
    return (num_heads, block_size, d_head)


# jitted: every layer's K and V write is the same function of the same
# shapes, traced and lowered once a tick program, not 24 times
@jax.jit
def _write_pool_rows(pool, rows, blocks, offs):
    """Row i (`rows[i]`, [nh, w]) becomes the w values of in-block position
    `offs[i]` of block `blocks[i]` in every head's `[R, L]` plane of `pool`
    [NB, nh, R, L] (either `pool_block_shape`; w = dh, or 1 for a scale
    pool): one in-place `dynamic_update_slice` of [1, nh, 1, w] a row, in
    order (a later duplicate target wins: only the null block has any). A
    scatter says the same; XLA on a TPU made it a pass over the whole pool
    (64 MB a pool at the benchmark's widths, to write 16 rows of 4 KB),
    and a row update is what the donated buffer takes in place for sure."""
    width, lanes = rows.shape[-1], pool.shape[-1]
    zero = jnp.int32(0)
    for i in range(rows.shape[0]):
        lin = offs[i] * width
        pool = jax.lax.dynamic_update_slice(
            pool, rows[i][None, :, None, :],
            (blocks[i], zero, lin // lanes, lin % lanes))
    return pool


@functools.partial(jax.jit, static_argnames=("new_heads",))
def _write_pool_blocks(pool, chunk, block_ids, new_heads):
    """`chunk` [L, C, nh*dh]: lane l's C consecutive token rows, the first
    on a block boundary. Its whole blocks ([nh, block_size, dh], laid out
    as the pool declares a block) go to `pool[block_ids[i]]`, i over the
    L * C/block_size blocks in order: one in-place `dynamic_update_slice`
    of a whole contiguous block each (the row writes' lowering, a block at
    a time). A later duplicate target wins: only the null block has any."""
    block_shape = pool.shape[1:]
    n = block_ids.shape[0]
    d_head = chunk.shape[-1] // new_heads
    blocks = chunk.reshape(n, -1, new_heads, d_head).transpose(0, 2, 1, 3)
    blocks = blocks.reshape((n,) + block_shape)
    zero = jnp.int32(0)
    for i in range(n):      # lax slices: a traced jnp index costs a millisecond
        pool = jax.lax.dynamic_update_slice(
            pool, jax.lax.slice_in_dim(blocks, i, i + 1),
            (jax.lax.index_in_dim(block_ids, i, keepdims=False),
             zero, zero, zero))
    return pool


@register_op("paged_cache_write_quant", stop_gradient=True)
def _paged_cache_write_quant(ctx, ins, attrs):
    """int8 variant of `paged_cache_write`: the pool stores int8 payloads
    plus a per-row f32 scale pool (`Scales`, [n_blocks, nh, block_size, 1])
    and each incoming f32 row is quantized symmetrically over its dh
    vector on the way in — amax/127 scale per (slot, head) row, zero rows
    pinned to scale 1.0 so dequantization is exact for them. The payload
    write and the scale write are the same in-place row writes as the
    f32 write; the engine-side win is the pool's RESIDENT bytes
    (f32 -> int8 + one scale per dh row), which the pager hands back as
    extra admitted blocks. Same null-block steering contract as
    `paged_cache_write`."""
    pool = ins["Cache"][0]
    scales = ins["Scales"][0]
    new = jnp.asarray(ins["New"][0], jnp.float32)
    blocks = ins["BlockIds"][0].reshape(-1).astype(jnp.int32)
    offs = ins["Offsets"][0].reshape(-1).astype(jnp.int32)
    if new.ndim != pool.ndim - 1:
        raise ValueError(
            f"paged_cache_write_quant: New must drop exactly the pool's "
            f"block-size axis (pool {pool.shape}, New {new.shape})")
    if blocks.shape != offs.shape:
        raise ValueError(
            f"paged_cache_write_quant: BlockIds {blocks.shape} and "
            f"Offsets {offs.shape} must agree")
    amax = jnp.max(jnp.abs(new), axis=-1, keepdims=True)
    sc = jnp.where(amax > 0, amax / 127.0, 1.0).astype(jnp.float32)
    q = jnp.clip(jnp.round(new / sc), -127, 127).astype(jnp.int8)
    return {"Out": [_write_pool_rows(pool, q, blocks, offs)],
            "ScalesOut": [_write_pool_rows(scales, sc, blocks, offs)]}


@register_op("one_hot", stop_gradient=True)
def _one_hot(ctx, ins, attrs):
    x = ins["X"][0]
    depth = attrs["depth"]
    if x.ndim >= 2 and x.shape[-1] == 1:
        x = jnp.squeeze(x, axis=-1)
    return {"Out": [jax.nn.one_hot(x, depth, dtype=jnp.float32)]}


@register_op("cast")
def _cast(ctx, ins, attrs):
    dtype = convert_dtype(attrs["out_dtype"])
    return {"Out": [ins["X"][0].astype(dtype)]}


@register_op("fill_constant", stop_gradient=True)
def _fill_constant(ctx, ins, attrs):
    dtype = convert_dtype(attrs.get("dtype", "float32"))
    shape = attrs["shape"]
    return {"Out": [jnp.full(shape, attrs["value"], dtype=dtype)]}


@register_op("fill_constant_batch_size_like", stop_gradient=True)
def _fill_constant_bsl(ctx, ins, attrs):
    ref = ins["Input"][0]
    shape = list(attrs["shape"])
    in_idx = attrs.get("input_dim_idx", 0)
    out_idx = attrs.get("output_dim_idx", 0)
    shape[out_idx] = ref.shape[in_idx]
    dtype = convert_dtype(attrs.get("dtype", "float32"))
    return {"Out": [jnp.full(shape, attrs["value"], dtype=dtype)]}


@register_op("fill_zeros_like", stop_gradient=True)
def _fill_zeros_like(ctx, ins, attrs):
    return {"Out": [jnp.zeros_like(ins["X"][0])]}


@register_op("assign")
def _assign(ctx, ins, attrs):
    return {"Out": [ins["X"][0]]}


@register_op("assign_value", stop_gradient=True)
def _assign_value(ctx, ins, attrs):
    values = np.asarray(attrs["values"], dtype=convert_dtype(attrs["dtype"]))
    return {"Out": [jnp.asarray(values.reshape(attrs["shape"]))]}


@register_op("shape", stop_gradient=True)
def _shape(ctx, ins, attrs):
    return {"Out": [jnp.asarray(ins["Input"][0].shape, dtype=jnp.int64)]}


@register_op("reverse")
def _reverse(ctx, ins, attrs):
    return {"Out": [jnp.flip(ins["X"][0], axis=tuple(attrs["axis"]))]}


@register_op("multiplex")
def _multiplex(ctx, ins, attrs):
    ids = ins["Ids"][0].reshape(-1)
    stacked = jnp.stack(ins["X"], axis=0)  # [n_candidates, batch, ...]
    return {"Out": [stacked[ids, jnp.arange(stacked.shape[1])]]}


@register_op("crop")
def _crop(ctx, ins, attrs):
    x = ins["X"][0]
    offsets = attrs["offsets"]
    shape = attrs["shape"]
    idx = tuple(slice(o, o + s) for o, s in zip(offsets, shape))
    return {"Out": [x[idx]]}


@register_op("label_smooth")
def _label_smooth(ctx, ins, attrs):
    x = ins["X"][0]
    eps = attrs.get("epsilon", 0.0)
    if "PriorDist" in ins and ins["PriorDist"]:
        prior = ins["PriorDist"][0]
        return {"Out": [(1 - eps) * x + eps * prior]}
    return {"Out": [(1 - eps) * x + eps / x.shape[-1]]}


@register_op("lookup_table")
def _lookup_table(ctx, ins, attrs):
    """Embedding lookup (≙ lookup_table_op.cc:21). `is_sparse`/`is_distributed`
    attrs are accepted for parity; on TPU the table is a dense sharded array
    and sparse gradient aggregation is handled by XLA scatter-add in the VJP."""
    w = ins["W"][0]
    ids = ins["Ids"][0]
    if ids.ndim >= 2 and ids.shape[-1] == 1:
        ids = jnp.squeeze(ids, axis=-1)
    padding_idx = attrs.get("padding_idx", None)
    out = jnp.take(w, ids, axis=0)
    if padding_idx is not None:
        if padding_idx < 0:  # negative indexes from the end, as in reference
            padding_idx += w.shape[0]
        mask = (ids != padding_idx)[..., None]
        out = out * mask.astype(out.dtype)
    return {"Out": [out]}


@register_op("qlookup")
def _qlookup(ctx, ins, attrs):
    """Weight-only quantized embedding lookup (quantize_params_pass rewrite
    of `lookup_table`): gathers int8/int4 payload ROWS plus their row-block
    scales and dequantizes only the gathered rows — the full f32 table is
    never materialized on device."""
    qw, scales, ids = ins["QW"][0], ins["Scales"][0], ins["Ids"][0]
    if ids.ndim >= 2 and ids.shape[-1] == 1:
        ids = jnp.squeeze(ids, axis=-1)
    rows = jnp.take(qw, ids, axis=0)
    if attrs.get("bits", 8) == 4:
        from ..parallel.collective import unpack_int4
        lead, c2 = rows.shape[:-1], rows.shape[-1]
        rows = unpack_int4(rows.reshape(-1, c2)).reshape(lead + (2 * c2,))
    nr, nc = scales.shape
    br = qw.shape[0] // nr
    bc = rows.shape[-1] // nc
    s = jnp.take(scales, ids // br, axis=0)          # [..., nc]
    out = (rows.astype(jnp.float32).reshape(rows.shape[:-1] + (nc, bc))
           * s[..., :, None]).reshape(rows.shape)
    padding_idx = attrs.get("padding_idx", None)
    if padding_idx is not None:
        if padding_idx < 0:
            padding_idx += qw.shape[0]
        mask = (ids != padding_idx)[..., None]
        out = out * mask.astype(out.dtype)
    return {"Out": [out]}


@register_op("increment")
def _increment(ctx, ins, attrs):
    x = ins["X"][0]
    # keep x's dtype: int counters must not promote to float (the carry of a
    # lax.while_loop requires stable dtypes)
    return {"Out": [x + jnp.asarray(attrs.get("step", 1.0), x.dtype)]}


@register_op("print", stop_gradient=True)
def _print(ctx, ins, attrs):
    # ≙ print_op (debug tensor dump, reference layers/control_flow.py:147)
    x = ins["In"][0]
    jax.debug.print(attrs.get("message", "print_op") + ": {}", x)
    return {"Out": [x]}


@register_op("arange", stop_gradient=True)
def _arange(ctx, ins, attrs):
    return {"Out": [jnp.arange(attrs["start"], attrs["end"], attrs["step"],
                               dtype=convert_dtype(attrs.get("dtype", "int64")))]}


@register_op("cumsum")
def _cumsum(ctx, ins, attrs):
    x = ins["X"][0]
    axis = attrs.get("axis", -1)
    if attrs.get("reverse", False):
        x = jnp.flip(x, axis=axis)
    out = jnp.cumsum(x, axis=axis)
    if attrs.get("exclusive", False):
        pad = [(0, 0)] * x.ndim
        pad[axis] = (1, 0)
        sl = [slice(None)] * x.ndim
        sl[axis] = slice(0, x.shape[axis])
        out = jnp.pad(out, pad)[tuple(sl)]
    if attrs.get("reverse", False):
        out = jnp.flip(out, axis=axis)
    return {"Out": [out]}


@register_op("piecewise_decay", stop_gradient=True)
def _piecewise_decay(ctx, ins, attrs):
    # branch-free piecewise-constant LR lookup (≙ reference
    # learning_rate_scheduler.py piecewise_decay's Switch construct)
    step = ins["Step"][0]
    boundaries = jnp.asarray(attrs["boundaries"], dtype=step.dtype)
    values = jnp.asarray(attrs["values"], dtype=jnp.float32)
    idx = jnp.searchsorted(boundaries, step.reshape(()), side="right")
    return {"Out": [values[idx].reshape(1)]}


_guards_warned = []


def _warn_guards_inactive():
    if not _guards_warned:
        import warnings
        warnings.warn(
            "check_nan_inf runtime guards are a CPU-debug facility; they "
            "are INACTIVE on this backend (debug host callbacks stay off "
            "the accelerator's hot path). Rerun under JAX_PLATFORMS=cpu to "
            "localize the failure.")
        _guards_warned.append(True)


def _as_id32(ids):
    """Ids live in the int32 space (the framework runs without x64). Under
    jax_enable_x64 an id beyond int32 range is mapped to the INVALID
    sentinel (negative) instead of silently wrapping into someone else's
    row: lookups return zero rows and dispatch routes it to the padded
    class, so corruption is visible rather than plausible."""
    if ids.dtype == jnp.int64:   # only possible with x64 enabled
        ids = jnp.where(jnp.abs(ids) > 2**31 - 1, -(2**31 - 1), ids)
    return ids.astype(jnp.int32)


def _array_bounds_guard(i, cap, what):
    """XLA clamps out-of-range dynamic indices; under the debug flag
    (PTPU_CHECK_NAN_INF — the framework's runtime-guards mode) report them
    instead of silently reading/writing the last slot. Host callbacks are a
    CPU-debug facility: a host round trip per indexed op has no place on
    the accelerator's hot path, so the guard is a no-op there (run the
    repro under JAX_PLATFORMS=cpu)."""
    from ..core import flags as _flags
    if not _flags.get_flag("check_nan_inf"):
        return
    if jax.default_backend() != "cpu":
        _warn_guards_inactive()
        return
    bad = (i < 0) | (i >= cap)

    def _report(bad_flag, i_val, what=what, cap=cap):
        if bool(bad_flag):
            raise IndexError(
                f"{what} index {int(i_val)} outside preallocated "
                f"capacity {cap}")

    jax.debug.callback(_report, bad, i)


@register_op("array_write")
def _array_write(ctx, ins, attrs):
    """≙ tensor_array_read_write.cc WriteToArray: functional index write
    into a preallocated [max_len, ...] array (the static-shape translation
    of the reference's dynamically-growing LoDTensorArray). NOTE: XLA
    clamps an out-of-range index to the last slot; enable the
    check_nan_inf debug flag to fail loudly instead."""
    arr = ins["Array"][0]
    x = ins["X"][0]
    i = ins["I"][0].reshape(()).astype(jnp.int32)
    _array_bounds_guard(i, arr.shape[0], "array_write")
    return {"Out": [jax.lax.dynamic_update_index_in_dim(
        arr, x.astype(arr.dtype), i, axis=0)]}


@register_op("array_read")
def _array_read(ctx, ins, attrs):
    """≙ ReadFromArray: dynamic index read (same clamping caveat as
    array_write; debug flag reports out-of-range)."""
    arr = ins["Array"][0]
    i = ins["I"][0].reshape(()).astype(jnp.int32)
    _array_bounds_guard(i, arr.shape[0], "array_read")
    return {"Out": [jax.lax.dynamic_index_in_dim(arr, i, axis=0,
                                                 keepdims=False)]}


@register_op("array_length", stop_gradient=True)
def _array_length(ctx, ins, attrs):
    """≙ lod_array_length_op: the array's capacity (static translation —
    preallocated arrays have fixed leading extent)."""
    return {"Out": [jnp.asarray(ins["X"][0].shape[0], jnp.int64)]}


# ---------------------------------------------------------------------------
# sparse/dist helpers (≙ split_ids_op / merge_ids_op /
# lookup_sparse_table_op / split_selected_rows_op — the pserver row-dispatch
# family, SURVEY.md §2.2 "Sparse/dist helpers"). Static-shape translation:
# shard membership is a mask, outputs are padded to the input length with
# sentinel -1 ids and zero rows; counts come back alongside.
# ---------------------------------------------------------------------------

@register_op("split_ids", stop_gradient=True)
def _split_ids(ctx, ins, attrs):
    """Partition ids across `num_shards` by modulo (the reference's hash
    dispatch). Out: one [N] padded id tensor per shard + [num_shards]
    counts; order within a shard is preserved."""
    # int32 id space (the framework runs without x64; ids >= 2**31 are
    # outside the supported vocab range)
    ids = _as_id32(ins["Ids"][0].reshape(-1))
    n = attrs["num_shards"]
    outs, counts = [], []
    for s in range(n):
        mask = (ids % n) == s
        cnt = jnp.sum(mask.astype(jnp.int32))
        pos = jnp.cumsum(mask.astype(jnp.int32)) - 1
        scatter_pos = jnp.where(mask, pos, ids.shape[0])
        buf = jnp.full((ids.shape[0] + 1,), -1, jnp.int32)
        buf = buf.at[scatter_pos].set(ids)
        outs.append(buf[:-1])
        counts.append(cnt)
    return {"Out": outs, "Count": [jnp.stack(counts)]}


@register_op("merge_ids", stop_gradient=True)
def _merge_ids(ctx, ins, attrs):
    """≙ merge_ids_op: route per-shard row values back to the original id
    order. Ids [N] (the original query), per-shard padded ids + rows as
    produced by split_ids + a sharded lookup."""
    ids = _as_id32(ins["Ids"][0].reshape(-1))
    shard_ids = ins["X"]            # list of [N] padded id tensors
    shard_rows = ins["Rows"]        # list of [N, D] row values
    n = len(shard_ids)
    d = shard_rows[0].shape[-1]
    out = jnp.zeros((ids.shape[0], d), shard_rows[0].dtype)
    for s in range(n):
        mask = (ids % n) == s
        pos = jnp.cumsum(mask.astype(jnp.int32)) - 1   # index into shard
        gathered = shard_rows[s][jnp.maximum(pos, 0)]
        out = jnp.where(mask[:, None], gathered, out)
    return {"Out": [out]}


@register_op("lookup_sparse_table", stop_gradient=True)
def _lookup_sparse_table(ctx, ins, attrs):
    """≙ lookup_sparse_table_op: gather rows by id from a table shard;
    padded (-1) ids yield zero rows (the reference auto-grows unseen rows —
    static translation returns the init value 0)."""
    w = ins["W"][0]
    ids = _as_id32(ins["Ids"][0].reshape(-1))
    valid = ids >= 0
    safe = jnp.where(valid, ids, 0)
    rows = w[safe]
    return {"Out": [jnp.where(valid[:, None], rows, 0.0)]}
