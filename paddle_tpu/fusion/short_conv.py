"""The gated short convolution's causal part, over a tick's rows, with the
state a request carries beside its per-token cache rows.

The operator (`models/transformer.py _short_conv`) is `[B, C, z] = x W_in`,
`u = B * z`, `c_t = sum_j k[:, j] * u_{t-(K-1)+j}` (depthwise, K taps, no
bias), `out = (C * c) W_out`. Everything but `c` is row-wise; `c_t` needs
the K-1 rows of u before row t, zero before position 0. That is a request's
STATE: a fixed few rows a conv layer, whatever the request's length, where
attention keeps a row a position.

Two persistable arrays hold it (`_ConvState` declares them, all conv layers
in one array each):

- `slot_state` [n_slots, n_conv, K-1, D]: the state of the request in each
  tick slot, AFTER the last position it fed. A decode row reads its slot's
  rows and writes them back shifted by one; a prefill lane leaves there the
  state after its chunk's last real row.
- `block_state` [n_blocks, n_conv, K-1, D]: for a pool block, the state
  AFTER the block's last position, written by the lane whose chunk fills the
  block. It lives and dies with the block (shared, reference-counted,
  evicted by `serving/kv_pager.py` like the block's K/V). A lane whose chunk
  starts at position p > 0 starts from the snapshot of the block that ends
  at p - 1 in ITS table, whether an earlier chunk of the same request wrote
  that block or the prefix cache handed it over: a prefix hit resumes from
  the state the shared span ends in, bit for bit what the request would have
  computed, and nothing is copied at admission. Only prompt blocks are ever
  shared (`KVPager.note_block_filled`) and every prompt token goes through a
  lane, so decode rows write no snapshot.

`short_conv` is one layer's `c` over the tick's rows (S decode rows, then
L lanes of C rows) plus what the layer adds to the state; `conv_state_commit`
writes every layer's additions into the two arrays at the end of the tick,
in place. Plain `jax.numpy`: K = 3 shifted multiply-adds a row are fused by
XLA with the gates around them; nothing here is a kernel.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..framework.registry import register_op


def conv_rows(ext, taps, n_out):
    """ext [.., K-1 + n_out, D] (the state's rows, then the new ones), taps
    [D, K] -> c [.., n_out, D] float32: c_t = sum_j taps[:, j] * ext_{t+j}."""
    k = taps.shape[1]
    tf = taps.astype(jnp.float32)
    return sum(tf[:, j] * jax.lax.slice_in_dim(ext, j, j + n_out, axis=-2)
               .astype(jnp.float32) for j in range(k))


def short_conv(u, taps, slot_state, layer, n_slots, lanes=None):
    """One conv layer over a tick's rows.

    u [S + L*C, D]; taps [D, K]; `layer` the layer's index among the conv
    layers; `lanes` None (a decode tick) or (block_state, lbtab [L, NLB],
    lpos [L], lrows [L], chunk C, block_size). Returns (c [S + L*C, D] in u's dtype,
    decode rows' new state [S, K-1, D], and with lanes: the state after each
    whole block of each chunk [L * C/BS, K-1, D], the state after each
    lane's last real row [L, K-1, D])."""
    s, r = n_slots, taps.shape[1] - 1
    ud = u[:s]
    ext_d = jnp.concatenate([slot_state[:, layer], ud[:, None]], axis=1)
    c = conv_rows(ext_d, taps, 1)[:, 0].astype(u.dtype)
    new_d = ext_d[:, 1:]
    if lanes is None:
        return c, new_d, None, None
    block_state, lbtab, lpos, lrows, chunk, bs = lanes
    n_lanes = lbtab.shape[0]
    lpos = lpos.reshape(-1).astype(jnp.int32)
    lrows = lrows.reshape(-1).astype(jnp.int32)
    ul = u[s:].reshape(n_lanes, chunk, -1)
    # the state the chunk starts from: the snapshot of the block before it
    # in the lane's own table; zeros at position 0
    prev = jnp.take_along_axis(
        lbtab.astype(jnp.int32),
        jnp.maximum(lpos // bs - 1, 0)[:, None], axis=1)[:, 0]
    start = jnp.where((lpos > 0)[:, None, None], block_state[prev, layer],
                      jnp.zeros((), u.dtype))
    ext_l = jnp.concatenate([start.astype(u.dtype), ul], axis=1)
    c_l = conv_rows(ext_l, taps, chunk).astype(u.dtype)
    # after the block that ends at chunk row (b+1)*bs - 1: ext rows
    # (b+1)*bs .. (b+1)*bs + r - 1
    snaps = jnp.stack([ext_l[:, (b + 1) * bs:(b + 1) * bs + r]
                       for b in range(chunk // bs)], axis=1)
    last = jax.vmap(lambda e, n: jax.lax.dynamic_slice_in_dim(e, n, r, 0))(
        ext_l, lrows)
    return (jnp.concatenate([c, c_l.reshape(n_lanes * chunk, -1)], axis=0),
            new_d, snaps.reshape((-1,) + snaps.shape[2:]), last)


def commit(slot_state, new_d, live, lanes=None):
    """The tick's additions, every conv layer's stacked on axis 1, into the
    two arrays -> (slot_state, block_state or None). new_d
    [S, n_conv, K-1, D]; live [S] (> 0: the slot fed a decode row); `lanes`
    None or (block_state, snaps [L*C/BS, n_conv, K-1, D], last
    [L, n_conv, K-1, D], lwblocks [L*C/BS], lrows [L], lslot [L],
    block_size). A block's snapshot is written only where the chunk filled
    the block (else to the null block, 0, which nothing reads); a lane
    without rows leaves its slot's state as it is."""
    alive = (live.reshape(-1) > 0)[:, None, None, None]
    slot_state = jnp.where(alive, new_d.astype(slot_state.dtype), slot_state)
    if lanes is None:
        return slot_state, None
    block_state, snaps, last, lwblocks, lrows, lslot, bs = lanes
    lwblocks = lwblocks.reshape(-1).astype(jnp.int32)
    lrows = lrows.reshape(-1).astype(jnp.int32)
    lslot = lslot.reshape(-1).astype(jnp.int32)
    per_lane = lwblocks.shape[0] // lrows.shape[0]
    zero = jnp.int32(0)
    for i in range(lwblocks.shape[0]):
        lane, b = divmod(i, per_lane)
        full = lrows[lane] >= (b + 1) * bs
        block_state = jax.lax.dynamic_update_slice(
            block_state, snaps[i:i + 1].astype(block_state.dtype),
            (jnp.where(full, lwblocks[i], 0), zero, zero, zero))
    for lane in range(lrows.shape[0]):
        at = (lslot[lane], zero, zero, zero)
        old = jax.lax.dynamic_slice(slot_state, at, (1,) + slot_state.shape[1:])
        new = jnp.where(lrows[lane] > 0,
                        last[lane:lane + 1].astype(slot_state.dtype), old)
        slot_state = jax.lax.dynamic_update_slice(slot_state, new, at)
    return slot_state, block_state


def _lanes_of(ins, attrs):
    if not ins.get("LaneBlockTable"):
        return None
    return (ins["BlockState"][0], ins["LaneBlockTable"][0],
            ins["LanePos"][0], ins["LaneRows"][0], attrs["chunk"],
            attrs["block_size"])


@register_op("short_conv", stop_gradient=True)
def _short_conv_op(ctx, ins, attrs):
    u = ins["U"][0]
    c, new_d, snaps, last = short_conv(
        u.reshape(-1, u.shape[-1]), ins["Taps"][0], ins["SlotState"][0],
        attrs["layer"], attrs["n_slots"], _lanes_of(ins, attrs))
    out = {"Out": [c.reshape(u.shape)], "DecodeState": [new_d]}
    if snaps is not None:
        out["LaneSnaps"], out["LaneState"] = [snaps], [last]
    return out


@register_op("conv_state_commit", stop_gradient=True)
def _conv_state_commit_op(ctx, ins, attrs):
    lanes = None
    if ins.get("LaneSnaps"):
        lanes = (ins["BlockState"][0], jnp.stack(ins["LaneSnaps"], axis=1),
                 jnp.stack(ins["LaneState"], axis=1),
                 ins["LaneWriteBlocks"][0], ins["LaneRows"][0],
                 ins["LaneSlot"][0], attrs["block_size"])
    slot_state, block_state = commit(
        ins["SlotState"][0], jnp.stack(ins["DecodeState"], axis=1),
        ins["Live"][0], lanes)
    out = {"SlotStateOut": [slot_state]}
    if block_state is not None:
        out["BlockStateOut"] = [block_state]
    return out
