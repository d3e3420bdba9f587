"""Pallas TPU kernels for the hot ops.

SURVEY.md §7 stage 4: "Pallas kernels only where XLA underperforms". The
first such op is fused attention — XLA materializes the [T, T] score matrix
in HBM for a naive composite, while the flash kernel keeps per-tile scores
in VMEM with an online softmax (O(T) memory), which is the difference
between fitting long sequences on-chip or not (reference analogue: the
hand-written CUDA kernels under operators/math/, e.g. lstm/gru_compute —
the places the reference dropped below its framework abstractions for
speed).

Backend selection: on TPU the kernel compiles via Mosaic; elsewhere the
mathematically-identical jnp composite runs (tests additionally exercise
the kernel itself in pallas interpret mode to pin the tiling logic).
"""

from __future__ import annotations

import dataclasses
import functools
import inspect
import math

import jax
import jax.numpy as jnp

_NEG_INF = -1e30


def _auto_backend():
    from ..core import flags as _flags
    if _flags.get_flag("disable_pallas"):
        return "xla"
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def _dims(q, k, num_heads=None):
    """(B, H, T, Tk, D) of a call on q [B, H, T, D] and k [B, H, Tk, D] or,
    with `num_heads`, on token-major q [B, T, H * D] and k [B, Tk, H * D]."""
    if num_heads:
        B, T, HD = q.shape
        return B, num_heads, T, k.shape[1], HD // num_heads
    B, H, T, D = q.shape
    return B, H, T, k.shape[2], D


def _normalize_segment_ids(segment_ids, q, k, num_heads=None):
    """Accept a single [B, Tq] array (self-attention; Tq must equal Tk) or
    a (q_ids [B, Tq], kv_ids [B, Tk]) pair. Returns (q_ids, kv_ids) int32
    or (None, None). Same semantics as parallel.ring_attention: a query
    attends a key iff their ids are equal — the static-shape translation
    of the reference's LoD ragged batches (SURVEY §5 long-context row).
    q, k: [B, H, T, D], or with `num_heads` [B, T, H * D]."""
    if segment_ids is None:
        return None, None
    if isinstance(segment_ids, (tuple, list)):
        q_ids, kv_ids = segment_ids
    else:
        q_ids = kv_ids = segment_ids
    q_ids = jnp.asarray(q_ids, jnp.int32)
    kv_ids = jnp.asarray(kv_ids, jnp.int32)
    B, _, Tq, Tk, _ = _dims(q, k, num_heads)
    if q_ids.shape != (B, Tq) or kv_ids.shape != (B, Tk):
        raise ValueError(
            f"segment_ids shapes {q_ids.shape}/{kv_ids.shape} do not match "
            f"q [B={B}, Tq={Tq}] / k [B={B}, Tk={Tk}]")
    return q_ids, kv_ids


def _attention_reference(q, k, v, scale, causal, segment_ids=None, window=0):
    """Naive composite (the XLA fallback path). q: [B, H, T, D]; k, v: [B,
    KV, Tk, D], KV a divisor of H (query head i reads key/value head
    i // (H / KV)). `window` > 0: a query sees the last `window` keys the
    causal mask leaves it, itself among them.
    Causal masking is bottom-right aligned (query i sees keys up to
    i + Tk - Tq — the incremental-decode convention). A query row with NO
    visible keys (causal T > Tk head rows, or a segment id matching no
    key) outputs zeros — the flash kernels' semantics — rather than
    softmax's uniform-weights artifact, so every backend computes
    identical values and gradients."""
    q_ids, kv_ids = _normalize_segment_ids(segment_ids, q, k)
    group = q.shape[1] // k.shape[1]
    if group > 1:
        k, v = (jnp.repeat(x, group, axis=1) for x in (k, v))
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    tq, tk = s.shape[-2], s.shape[-1]
    mask = jnp.ones((1, tq, tk), bool)
    if causal:
        mask &= jnp.tril(jnp.ones((tq, tk), bool), tk - tq)[None]
    if window:
        mask &= ~jnp.tril(jnp.ones((tq, tk), bool), tk - tq - window)[None]
    if q_ids is not None:
        mask &= q_ids[:, :, None] == kv_ids[:, None, :]      # [B, tq, tk]
    if causal or q_ids is not None:
        s = jnp.where(mask[:, None], s, _NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        any_key = jnp.any(mask, axis=-1)                     # [B?, tq]
        p = jnp.where(any_key[:, None, :, None], p, 0.0)
    else:
        p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


_LANES = 128


def _clamp_block(block, t):
    """Block size actually used for length t when the caller NAMES one: the
    requested block, clamped to t rounded UP to a 128 multiple. Keeps every
    block shape Mosaic-legal (128 | bq, bk) for ANY sequence length — the
    sequence is padded up to the block multiple and the padding
    masked/sliced — and guarantees the segment-id tiling precondition
    (128 | bk) by construction."""
    return min(block, -(-t // _LANES) * _LANES)


def _fit_block(t, target):
    """Block size chosen for length t: the largest 128 multiple up to
    `target` that divides t rounded up to 128, so the sequence is padded to
    the lane width and no further."""
    pieces = -(-t // _LANES)
    return _LANES * max(d for d in range(1, target // _LANES + 1)
                        if pieces % d == 0)


def _pad_to(x, axis, target):
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, target - x.shape[axis])
    return jnp.pad(x, pad) if target != x.shape[axis] else x


# ---------------------------------------------------------------------------
# the plan: how one flash call tiles the shape it is given
# ---------------------------------------------------------------------------

# VMEM a resident plan may fill with what grows with the key length, of the
# 16 MiB Mosaic grants a kernel by default (the score tiles and the
# query-side blocks take the rest): K and V and, in the backward, dK and dV,
# each double-buffered by the pipeline, plus the backward's two float32
# accumulators
_VMEM_BUDGET = 8 << 20
# score entries of one tile over all the heads of a step (1 MiB of float32):
# what a v5e overlaps best at the benchmark's shapes — 4 heads of 256 x 256
# at T = 1024, 16 heads of 128 x 128 at T = 128 (PERF.md section 6, PR 38)
_TILE_SCORES = 1 << 18


@dataclasses.dataclass(frozen=True)
class FlashPlan:
    """Tiling of one flash call. `resident`: a grid step holds the whole K
    and V of `rows` heads in VMEM and loops over their key blocks inside the
    kernel, stopping at the causal diagonal; otherwise key blocks stream
    through the grid, one head a step. Either way a tile is `block_k` x
    `block_q` scores a head, and a step's heads are one batch of it.
    `token_major`: the operands are [B, T, H * D] as the projections leave
    them and a step's heads are `rows * D` lanes of one batch row, a whole
    number of 128-lane tiles; otherwise [B * H, T, D], a head a row.
    `group` > 1: that many query heads read one key/value head (k and v come
    [B * H / group, Tk, D]); `window` > 0: a query sees its last `window`
    keys. Both are streamed plans: the key axis of the grid is then as long
    as the blocks ONE q-block can see, and starts at its first live block."""
    resident: bool
    block_q: int
    block_k: int
    rows: int
    token_major: bool = False
    group: int = 1
    window: int = 0

    def scope(self, kernel):
        """The kernel's name in a device trace: XLA names the custom call
        after its `jax.named_scope`, so `device_ops` spells the plan, `_tm`
        last where the operands are token-major, `_g<group>` where the heads
        are grouped and `_w<window>` where the keys are windowed."""
        tiles = f"q{self.block_q}_k{self.block_k}"
        if self.resident:
            return (f"flash_{kernel}_resident_{tiles}_rows{self.rows}"
                    + "_tm" * self.token_major)
        return (f"flash_{kernel}_streamed_{tiles}"
                + f"_g{self.group}" * (self.group > 1)
                + f"_w{self.window}" * (self.window > 0))

    def scopes(self):
        """Every kernel a forward and backward under this plan runs: a
        resident head's backward is one pass, a streamed one's two."""
        backward = ("bwd",) if self.resident else ("bwd_dq", "bwd_dkv")
        return [self.scope(k) for k in ("fwd",) + backward]


# the side of a tile of a windowed call: a q-block of 512 under a window of
# 1,024 visits three key blocks of 512 for two blocks' worth of live pairs
_WINDOW_SIDE = 512


def _flash_plan(T, Tk, D, itemsize, heads, block_q=None, block_k=None,
                num_heads=None, group=1, window=0):
    """The plan for q [.., T, D] against k, v [.., Tk, D]: a pure function
    of the shape, for the forward and the backward alike. `heads`: how many
    heads may share a grid step (B*H; H when segment ids are given, whose
    row a step's heads must share). An explicit block size is honoured
    (clamped to the padded length). `num_heads`: the H of an operand that
    comes token-major, [B, T, H * D]; None for [B, H, T, D].

    A head whose keys fit the VMEM budget is resident, in tiles of 256 (the
    diagonal stop then skips 3/8 of a causal square at T = 1024), as many
    heads a step as fill `_TILE_SCORES` and divide `heads`. A longer head
    streams 1024-wide blocks through the grid.

    A token-major operand keeps its layout where the plan is resident and
    some such number of heads divides H and fills whole 128-lane tiles
    (`rows` even at D = 64): the largest that does is taken. Every other
    shape gets the head-major plan, and its caller transposes.

    Grouped heads (`group` query heads a key/value head) and a `window` take
    the streamed plan at any length: its grid walks the live key blocks of a
    q-block alone, and in the dK / dV pass the q-blocks of the group's heads
    that see a key block, summing over them in VMEM."""
    head_bytes = -(-Tk // _LANES) * _LANES * D * (8 * itemsize + 8)
    resident = head_bytes <= _VMEM_BUDGET and group == 1 and not window
    side = 256 if resident else _WINDOW_SIDE if window else 1024
    bq = _clamp_block(block_q, T) if block_q else _fit_block(T, side)
    bk = _clamp_block(block_k, Tk) if block_k else _fit_block(Tk, side)
    if not resident:
        return FlashPlan(False, bq, bk, 1, group=group, window=window)
    most = max(1, min(_VMEM_BUDGET // head_bytes, _TILE_SCORES // (bq * bk)))
    whole_tiles = [r for r in range(1, most + 1)
                   if num_heads and num_heads % r == 0
                   and r * D % _LANES == 0]
    if whole_tiles:
        return FlashPlan(True, bq, bk, max(whole_tiles), token_major=True)
    rows = max(r for r in range(1, most + 1) if heads % r == 0)
    return FlashPlan(True, bq, bk, rows)


def _plan_for(q, k, segments, block_q=None, block_k=None, num_heads=None,
              window=0):
    """The plan of a call on q [B, H, T, D] and k [B, KV, Tk, D] or, with
    `num_heads`, on q [B, T, H * D] and k [B, Tk, KV * D] (arrays or shapes
    with a dtype), with segment ids or without."""
    B, H, T, Tk, D = _dims(q, k, num_heads)
    kv_heads = k.shape[2] // D if num_heads else k.shape[1]
    if H % kv_heads:
        raise ValueError(f"{H} query heads over {kv_heads} key/value heads")
    return _flash_plan(T, Tk, D, jnp.dtype(q.dtype).itemsize,
                       H if segments else B * H, block_q, block_k,
                       num_heads=num_heads, group=H // kv_heads,
                       window=int(window))


# ---------------------------------------------------------------------------
# pieces the forward and backward kernels share
# ---------------------------------------------------------------------------

def _segment_mask(major_ids, minor_ids):
    """[n, m] equality mask of a score tile from its staged segment ids.

    Layout (mirrors jax's own TPU flash kernel): the ids of the tile's ROWS
    (keys) ride broadcast over 128 lanes as [n, 128], the ids of its COLUMNS
    (queries) ride broadcast over 8 sublanes as [8, m] — Mosaic-legal
    tilings for what are logically 1-D vectors."""
    m = minor_ids.shape[1]
    if m <= _LANES:
        rows = major_ids[:, :m]                    # lane slice
    else:
        repeats, rem = divmod(m, _LANES)
        if rem:
            raise NotImplementedError("blocks must be multiples of 128 "
                                      "when segment ids are used")
        rows = jnp.tile(major_ids, (1, repeats))
    return rows == minor_ids[:1]


def _ranges_overlap(a_ids, b_ids):
    """Can ANY id of block a equal one of block b? Exact as a "no pair can
    match" test for any id assignment (ranges disjoint => no equality),
    merely conservative when ranges overlap without an exact match; the
    per-element mask still zeroes those."""
    return ((jnp.max(a_ids) >= jnp.min(b_ids))
            & (jnp.min(a_ids) <= jnp.max(b_ids)))


@dataclasses.dataclass(frozen=True)
class _Visible:
    """What decides which keys a query sees, beside segment ids: fixed for a
    call. `offset` = Tk - T aligns the causal diagonal bottom-right (query i
    sees keys up to i + offset: matches _attention_reference for Tq != Tk);
    keys from `true_tk` on are padding. `window` > 0: query i sees keys
    above i + offset - window. `k_steps` / `q_steps`: how long the key axis
    (forward, dQ pass) and the q axis (dK / dV pass) of a streamed grid are:
    all the blocks, or with a window those one block of the other side can
    see (`_first_key_block`, `_first_q_block` say where they start)."""
    causal: bool
    offset: int
    true_tk: int
    num_k_blocks: int
    num_q_blocks: int = 0
    window: int = 0
    k_steps: int = 0
    q_steps: int = 0


def _floor_div0(x, d):
    """max(x, 0) // d for a Python int or a traced one."""
    return (max(x, 0) if isinstance(x, int) else jnp.maximum(x, 0)) // d


def _first_key_block(plan, see, qi):
    """The first key block some query of q-block `qi` sees."""
    if not see.window:
        return 0
    return _floor_div0(qi * plan.block_q + see.offset - see.window + 1,
                       plan.block_k)


def _last_key_block(plan, see, qi):
    """The last key block some query of q-block `qi` sees (causal)."""
    last = see.num_k_blocks - 1
    if not see.causal:
        return last
    seen = _floor_div0((qi + 1) * plan.block_q - 1 + see.offset, plan.block_k)
    return min(seen, last) if isinstance(seen, int) else \
        jnp.minimum(seen, last)


def _first_q_block(plan, see, j):
    """The first q-block some query of which sees key block `j` (causal)."""
    if not see.causal:
        return 0
    return _floor_div0(j * plan.block_k - see.offset, plan.block_q)


def _last_q_block(plan, see, j):
    """The last q-block some query of which sees key block `j`."""
    last = see.num_q_blocks - 1
    if not see.window:
        return last
    seen = _floor_div0((j + 1) * plan.block_k - 1 - see.offset
                       + see.window - 1, plan.block_q)
    return min(seen, last)


def _step_q_block(plan, see, j, r):
    """The q-block step `r` of the dK / dV pass's q axis is at, under key
    block `j`: the axis runs over the `q_steps` q-blocks of each head of the
    group in turn, from the first that sees the key block where a window
    bounds them."""
    first = _first_q_block(plan, see, j) if see.window else 0
    return first + r % see.q_steps


def _visible(plan, causal, T, Tk, nq, nk, window=0):
    """The `_Visible` of a call, with the streamed grid's axis lengths."""
    see = _Visible(causal, Tk - T, Tk, nk, nq, window)
    if not window:
        return dataclasses.replace(see, k_steps=nk, q_steps=nq)
    k_steps = max(_last_key_block(plan, see, i)
                  - _first_key_block(plan, see, i) + 1 for i in range(nq))
    q_steps = max(_last_q_block(plan, see, j)
                  - _first_q_block(plan, see, j) + 1 for j in range(nk))
    return dataclasses.replace(see, k_steps=max(k_steps, 1),
                               q_steps=max(q_steps, 1))


class _KeyBlocks:
    """The key blocks q-block `qi` has to see, inside a kernel. Blocks
    [n_first, n_live) hold a live pair; of them [n_inside, n_full) are
    visible whole to every query of the block: no mask is paid. The others
    hold some masked pair (the causal diagonal; the window's far edge; padded
    keys; any block under segment ids) and pay the per-element mask. Blocks
    outside [n_first, n_live) hold no live pair and are not visited: at
    T = 32768 causal that halves the issued FLOPs, and under a window of
    1,024 at T = 8,192 it leaves 3 key blocks of 512 a q-block of the 16.
    Without a window n_first and n_inside are 0. `j`: the key block a
    streamed step is at (a resident plan loops over them)."""

    def __init__(self, plan, see, qi, j, major_ids_ref, minor_ids_ref):
        self.plan, self.see, self.qi, self.j = plan, see, qi, j
        self.major_ids_ref, self.minor_ids_ref = major_ids_ref, minor_ids_ref
        self.segments = major_ids_ref is not None
        bq, bk = plan.block_q, plan.block_k
        # whether the call can meet a masked tile at all (else none is traced)
        self.any_masked = (see.causal or self.segments
                           or see.true_tk % bk != 0)
        self.n_live = jnp.int32(see.num_k_blocks)
        self.n_full = jnp.int32(0 if self.segments else see.true_tk // bk)
        self.n_first = self.n_inside = jnp.int32(0)
        if see.causal:
            first = qi * bq + see.offset + 1    # keys the FIRST query sees
            last = first + bq - 1               # keys the LAST query sees
            self.n_full = jnp.minimum(self.n_full,
                                      jnp.maximum(first, 0) // bk)
            self.n_live = jnp.minimum(
                self.n_live, (jnp.maximum(last, 0) + bk - 1) // bk)
        if see.window:
            low = qi * bq + see.offset - see.window + 1  # first query's first
            self.n_first = jnp.maximum(low, 0) // bk
            self.n_inside = (jnp.maximum(low + bq - 1, 0) + bk - 1) // bk
            # a q-block past the last one (the dK / dV pass's q axis starts
            # at a key block's first q-block and may run over) sees nothing
            alive = qi < see.num_q_blocks
            self.n_live = jnp.where(alive, self.n_live, 0)
            self.n_full = jnp.where(alive, self.n_full, 0)

    def _ids(self, j):
        return (self.major_ids_ref[0, _key_rows(self.plan, j), :],
                self.minor_ids_ref[0, 0])

    def mask(self, j):
        """[1, bk, bq]: which entries of masked tile j are alive."""
        bq, bk = self.plan.block_q, self.plan.block_k
        q0, k0 = self.qi * bq, j * bk
        ids = self._ids(j) if self.segments else None
        k_pos = k0 + jax.lax.broadcasted_iota(jnp.int32, (bk, bq), 0)
        ok = k_pos < self.see.true_tk
        if self.see.causal:
            q_pos = q0 + jax.lax.broadcasted_iota(jnp.int32, (bk, bq), 1)
            ok &= q_pos + self.see.offset >= k_pos
            if self.see.window:
                ok &= q_pos + self.see.offset - self.see.window < k_pos
        if ids is not None:
            ok &= _segment_mask(*ids)
        return ok[None]

    def visit(self, tile):
        """Run `tile(j, masked)` over the blocks to see. A resident plan
        loops inside the kernel; a streamed one is at key block `j` and runs
        it or not. Under segment ids a block whose id range misses the
        queries' is skipped too."""
        from jax.experimental import pallas as pl

        def masked(j):
            if self.segments:
                pl.when(_ranges_overlap(*self._ids(j)))(
                    lambda: tile(j, True))
            else:
                tile(j, True)

        if self.plan.resident:
            jax.lax.fori_loop(0, self.n_full,
                              lambda j, c: tile(j, False) or c, 0)
            if self.any_masked:
                jax.lax.fori_loop(self.n_full, self.n_live,
                                  lambda j, c: masked(j) or c, 0)
        else:
            j = self.j
            whole = (j >= self.n_inside) & (j < self.n_full)
            pl.when(whole)(lambda: tile(j, False))
            if self.any_masked:
                pl.when((j >= self.n_first) & (j < self.n_live)
                        & jnp.logical_not(whole))(lambda: masked(j))


def _key_rows(plan, j):
    """Where key block j lies in a step's key-side blocks: a slice of the
    resident heads, or all of the block the grid streamed in."""
    from jax.experimental import pallas as pl
    if not plan.resident:
        return slice(None)
    return pl.ds(pl.multiple_of(j * plan.block_k, plan.block_k), plan.block_k)


def _row_stat(ref, qi, block_q):
    """A q-block's per-row residual (lse, delta) as [rows, 1, block_q]. The
    array holds a head's T values as [T / 128, 128], so 1,024 floats are one
    (8, 128) tile in HBM and not 128 copies a row."""
    from jax.experimental import pallas as pl
    pieces = block_q // ref.shape[-1]
    return jnp.concatenate(
        [ref[:, pl.ds(qi * pieces + c, 1), :] for c in range(pieces)], axis=2)


def _store_row_stat(ref, qi, row):
    """Write a q-block's [rows, 1, block_q] into the `_row_stat` layout."""
    from jax.experimental import pallas as pl
    lanes = ref.shape[-1]
    pieces = row.shape[2] // lanes
    for c in range(pieces):
        ref[:, pl.ds(qi * pieces + c, 1), :] = (
            row[:, :, c * lanes:(c + 1) * lanes])


# dot_general dimension numbers over [rows, ., .] operands, heads batched
_NT = (((2,), (2,)), ((0,), (0,)))      # a @ b^T
_NN = (((2,), (1,)), ((0,), (0,)))      # a @ b
_TN = (((1,), (1,)), ((0,), (0,)))      # a^T @ b


def _out_struct(shape, dtype, *refs):
    """ShapeDtypeStruct for a pallas_call output, carrying the union of the
    inputs' device-varying axes — required when the kernel runs inside
    shard_map (ring attention) where check_vma demands explicit vma."""
    vma = set()
    for r in refs:
        vma |= set(getattr(getattr(r, "aval", None), "vma", ()) or ())
    if vma:
        return jax.ShapeDtypeStruct(shape, dtype, vma=frozenset(vma))
    return jax.ShapeDtypeStruct(shape, dtype)


def _named_call(kernel, scope, grid, ins, outs, scratch, interpret):
    """pallas_call with refs handed to `kernel` by NAME (pallas passes them
    positionally: inputs, outputs, scratch): an optional ref the call does
    not stage — segment ids, lse — is simply absent, and the kernel's
    default None stands. ins: {name: (array, spec)}; outs: {name: (struct,
    spec)}; scratch: {name: VMEM shape}. Returns {name: array}. The scope is
    the kernel's stable name in a device trace, whatever wraps the call."""
    from jax.experimental import pallas as pl
    names = [*ins, *outs, *scratch]

    def body(*refs):
        kernel(**dict(zip(names, refs)))

    with jax.named_scope(scope):
        res = pl.pallas_call(
            body, grid=grid,
            in_specs=[spec for _, spec in ins.values()],
            out_specs=[spec for _, spec in outs.values()],
            out_shape=[struct for struct, _ in outs.values()],
            scratch_shapes=list(scratch.values()),
            interpret=interpret,
        )(*[x for x, _ in ins.values()])
    return dict(zip(outs, res))


class _Tiling:
    """The padded operands and BlockSpecs of one flash call under a plan, by
    name. The grid runs (head group, q block) for a resident plan and
    (head group, q block, key block) for a streamed one — key blocks BEFORE
    q blocks with `keys_outer`, the streamed dK / dV pass, which accumulates
    over the q blocks. A head group is `plan.rows` heads: rows of the
    head-major [B * H, T, D], or `rows * D` lanes of one batch row of the
    token-major [B, T, H * D]. The per-row residuals lie [B * H, T / 128,
    128] in either form, and so do the head groups' numbers."""

    def __init__(self, plan, q, k, num_heads, q_ids, kv_ids, causal,
                 keys_outer=False):
        from jax.experimental import pallas as pl
        self.plan = plan
        B, H, T, Tk, D = _dims(q, k, num_heads)
        self.B, self.H, self.T, self.Tk, self.D = B, H, T, Tk, D
        self.KV = H // plan.group
        bq, bk, rows = plan.block_q, plan.block_k, plan.rows
        self.Tp, self.Tkp = -(-T // bq) * bq, -(-Tk // bk) * bk
        self.nq, self.nk = self.Tp // bq, self.Tkp // bk
        self.lanes = math.gcd(bq, _LANES)
        self.q_ids, self.kv_ids = q_ids, kv_ids
        self.see = see = _visible(plan, causal, T, Tk, self.nq, self.nk,
                                  plan.window)
        if plan.resident:
            self.grid = (B * H // rows, self.nq)
        elif keys_outer:
            self.grid = (B * self.KV, self.nk, plan.group * see.q_steps)
        else:
            self.grid = (B * H, self.nq, see.k_steps)
        # the keys a step holds: the head's, or one streamed block
        keys = self.Tkp if plan.resident else bk

        def where(g, a, b=0):
            """(query head, key/value head, q-block, key block) a grid step
            stages. A streamed step that sees nothing (`_KeyBlocks`) stages
            the nearest block that some step does: the pipeline fetches a
            block again only when its index changes."""
            if plan.resident:
                return g, g, a, 0
            if keys_outer:
                i = _step_q_block(plan, see, a, b)
                i = jnp.clip(i, _first_q_block(plan, see, a), self.nq - 1)
                return g * plan.group + b // see.q_steps, g, i, a
            j = _first_key_block(plan, see, a) + b
            j = jnp.minimum(j, _last_key_block(plan, see, a))
            return g, g // plan.group, a, j

        def spec(block, index):
            return pl.BlockSpec(block, lambda *ids: index(*where(*ids)))

        def batch(h):        # the batch row a step's query heads lie in
            return h * rows // H

        if plan.token_major:
            groups = H // rows
            self.q_spec = spec((1, bq, rows * D),
                               lambda h, _, i, j: (h // groups, i, h % groups))
            self.k_spec = spec((1, keys, rows * D),
                               lambda h, _, i, j: (h // groups, j, h % groups))
        else:
            self.q_spec = spec((rows, bq, D), lambda h, _, i, j: (h, i, 0))
            self.k_spec = spec((rows, keys, D), lambda _, h, i, j: (h, j, 0))
        self.stat_spec = spec((rows, self.Tp // self.lanes, self.lanes),
                              lambda h, _, i, j: (h, 0, 0))
        # segment ids of a tile's rows (keys) / columns (queries): see
        # _segment_mask
        self.major_ids_spec = spec((1, keys, _LANES),
                                   lambda h, _, i, j: (batch(h), j, 0))
        self.minor_ids_spec = spec((1, 1, 8, bq),
                                   lambda h, _, i, j: (batch(h), i, 0, 0))

    def operand(self, x, length):
        """A caller's q, k, v or do as the kernel takes it, its sequence
        zero-padded to `length`: [B, H, t, D] -> [B * H, length, D]; a
        token-major [B, t, H * D] stays as it lies."""
        if not self.plan.token_major:
            x = x.reshape(-1, *x.shape[2:])
        return _pad_to(x, 1, length)

    def shape(self, length, heads=None):
        """The shape `operand` gives at that length (`heads`: of a
        key/value-side operand whose heads are fewer)."""
        if self.plan.token_major:
            return (self.B, length, self.H * self.D)
        return (self.B * (heads or self.H), length, self.D)

    def result(self, y, t, heads=None):
        """A kernel's output as the caller's layout has it, `t` long."""
        y = y[:, :t]
        if self.plan.token_major:
            return y
        return y.reshape(self.B, heads or self.H, t, self.D)

    def stat(self, x):
        """[B, H, T] per-row residual -> the `_row_stat` layout."""
        x = _pad_to(x.reshape(-1, x.shape[-1]), 1, self.Tp)
        return x.reshape(x.shape[0], self.Tp // self.lanes, self.lanes)

    def segment_inputs(self):
        """{name: (ids, spec)} of the staged segment ids, or {}. Padding
        carries id 0, which is harmless: padded keys are killed by the
        true_tk guard, padded queries are sliced off (forward) or carry
        do = 0 (backward) regardless of id."""
        if self.q_ids is None:
            return {}
        bq = self.plan.block_q
        B = self.q_ids.shape[0]
        keys = _pad_to(self.kv_ids, 1, self.Tkp)
        keys = jnp.broadcast_to(keys[:, :, None], (B, self.Tkp, _LANES))
        queries = _pad_to(self.q_ids, 1, self.Tp).reshape(B, self.nq, 1, bq)
        queries = jnp.broadcast_to(queries, (B, self.nq, 8, bq))
        return {"major_ids_ref": (keys, self.major_ids_spec),
                "minor_ids_ref": (queries, self.minor_ids_spec)}


def _heads_of(plan, ref, rows=slice(None)):
    """[plan.rows, n, D]: rows `rows` of every head of a step's block. A
    head-major block is that already; in a token-major one, [1, n, rows * D],
    a head is D lanes at a static offset."""
    if not plan.token_major:
        return ref[:, rows, :]
    D = ref.shape[2] // plan.rows
    return jnp.stack([ref[0, rows, r * D:(r + 1) * D]
                      for r in range(plan.rows)])


def _held_heads(plan, ref):
    """The heads of a q-side block for every tile of a grid step, as a
    function to call where they are used. Token-major the lane slices are
    taken ONCE a step and held over the key loop; head-major every use reads
    the ref, which is the block as it lies: held, a step of the LM's shape is
    4% slower and a streamed one 5% (PERF.md section 6, PR 46-47)."""
    if not plan.token_major:
        return lambda: ref[:]
    held = _heads_of(plan, ref)
    return lambda: held


def _put_heads_transposed(plan, ref, x):
    """Write [plan.rows, D, n], a head's rows its D, as the block of `ref`:
    head-major a transpose a head; token-major the heads' rows are one
    matrix [rows * D, n] and its transpose is the block."""
    if not plan.token_major:
        ref[:] = jnp.swapaxes(x, 1, 2).astype(ref.dtype)
    else:
        rows, D, n = x.shape
        ref[0] = x.reshape(rows * D, n).T.astype(ref.dtype)


# ---------------------------------------------------------------------------
# one trace a step: equal calls share one jaxpr and one lowered function
# ---------------------------------------------------------------------------

def _traced_once(fn):
    """`fn(*arrays, **what_is_not_an_array)` behind ONE jitted callable, every
    keyword static. A step calls a kernel once a layer with the same shapes
    and the same keywords: jit's cache then hands every call after the first
    the first one's jaxpr, and the step's module holds the kernel's body once
    and calls it, where each call site used to trace the body and lower it to
    Mosaic again (24 times in a 12-layer step; PERF.md section 6, PR 46-47).
    It holds under `jax.vjp`, `jax.checkpoint` and `jax.shard_map`: the
    operands' varying axes are part of the cache's key."""
    names = tuple(p.name for p in inspect.signature(fn).parameters.values()
                  if p.kind is p.KEYWORD_ONLY)
    return jax.jit(fn, static_argnames=names)


def _count(name, plan, backward=False):
    """A set-up counter (a compiled step records nothing), once a kernel of
    a call: `flash/call`, a call of a flash kernel at a call site, and
    `flash/body_traced`, a trace of its body, `scope` the kernel's name under
    `plan`; the second over the first is the share of the calls that paid a
    trace."""
    from ..observability import tracing
    for scope in plan.scopes()[1:] if backward else plan.scopes()[:1]:
        tracing.record_counter(name, 1, scope=scope)


# ---------------------------------------------------------------------------
# flash forward
# ---------------------------------------------------------------------------

def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, m_ref=None, l_ref=None,
                      acc_ref=None, lse_ref=None, major_ids_ref=None,
                      minor_ids_ref=None, *, plan, see, single, scale):
    """One grid step of flash attention: the q-block program_id(1) of
    `plan.rows` heads against the key blocks it can see, by an online softmax
    whose m / l / acc live in VMEM scratch (across the streamed plan's
    sequential key steps: TPU grid semantics). The heads of a step are one
    batch of every product and every elementwise pass: independent chains
    the scheduler overlaps, where one short head alone waits out each
    product's latency. A tile is laid [keys, queries], as the backward's: the
    per-query m and l are then [1, bq] rows, eight to a vreg row and not one,
    the accumulator [D, bq] fills its lanes at D = 64, and lse leaves in the
    layout the backward reads; the output is transposed once a q-block.
    Scores, softmax and accumulators are float32; p is cast to v's type for
    the second product; a row with no visible key gives 0."""
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    step = None if plan.resident else pl.program_id(2)
    blocks = _KeyBlocks(
        plan, see, qi,
        None if plan.resident else _first_key_block(plan, see, qi) + step,
        major_ids_ref, minor_ids_ref)

    def init():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    q = _held_heads(plan, q_ref)                   # [rows, bq, D]

    def visit(j, masked):
        keys = _key_rows(plan, j)
        v = _heads_of(plan, v_ref, keys)           # [rows, bk, D]
        s = jax.lax.dot_general(
            _heads_of(plan, k_ref, keys), q(), _NT,
            preferred_element_type=jnp.float32) * scale  # [rows, bk, bq]
        if masked:
            ok = blocks.mask(j)
            s = jnp.where(ok, s, _NEG_INF)
        m_new = jnp.max(s, axis=1, keepdims=True)  # [rows, 1, bq]
        if not single:
            m_prev = m_ref[:]
            m_new = jnp.maximum(m_prev, m_new)
            alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                     # [rows, bk, bq]
        if masked:
            # a fully-masked row has m == s == NEG_INF, making
            # exp(s - m) == 1 for every DEAD entry — zero them so such rows
            # output 0, not mean(v)
            p = jnp.where(ok, p, 0.0)
        l = jnp.sum(p, axis=1, keepdims=True)
        acc = jax.lax.dot_general(v, p.astype(v.dtype), _TN,
                                  preferred_element_type=jnp.float32)
        if single:
            finalize(m_new, l, acc)
        else:
            l_ref[:] = l_ref[:] * alpha + l
            acc_ref[:] = acc_ref[:] * alpha + acc  # [rows, D, bq]
            m_ref[:] = m_new

    def finalize(m, l, acc):
        l = jnp.maximum(l, 1e-30)
        _put_heads_transposed(plan, o_ref, acc / l)
        if lse_ref is not None:
            # logsumexp per query row — the backward kernels' residual
            _store_row_stat(lse_ref, qi, m + jnp.log(l))

    def finalize_state():
        finalize(m_ref[:], l_ref[:], acc_ref[:])

    if single:
        visit(0, blocks.any_masked)
    elif plan.resident:
        init()
        blocks.visit(visit)
        finalize_state()
    else:
        pl.when(step == 0)(init)
        blocks.visit(visit)
        pl.when(step == see.k_steps - 1)(finalize_state)


def _checked_window(window, causal):
    if window and not causal:
        raise ValueError("a window bounds the keys a CAUSAL query sees: "
                         "window > 0 comes with causal=True")
    return int(window or 0)


def _flash_attention_pallas(q, k, v, scale, causal, block_q=None,
                            block_k=None, interpret=False, with_lse=False,
                            segment_ids=None, num_heads=None, window=0):
    """The flash forward on q [B, H, T, D] and k, v [B, KV, Tk, D]: the
    context, and with `with_lse` the rows' logsumexp [B, H, T] beside it.
    With `num_heads` the operands and the context are token-major, [B, T,
    H * D], for a shape whose plan is (`_flash_plan`). `window`: a query
    sees its last `window` keys (the plan carries it, and the head group)."""
    plan = _plan_for(q, k, segment_ids is not None, block_q, block_k,
                     num_heads, _checked_window(window, causal))
    _count("flash/call", plan)
    return _flash_fwd(q, k, v, segment_ids, scale=float(scale),
                      causal=bool(causal), plan=plan,
                      interpret=bool(interpret), with_lse=bool(with_lse),
                      num_heads=num_heads)


@_traced_once
def _flash_fwd(q, k, v, segment_ids, *, scale, causal, plan, interpret,
               with_lse, num_heads):
    from jax.experimental.pallas import tpu as pltpu

    _count("flash/body_traced", plan)
    q_ids, kv_ids = _normalize_segment_ids(segment_ids, q, k, num_heads)
    t = _Tiling(plan, q, k, num_heads, q_ids, kv_ids, causal)
    B, H, T, Tk, D = t.B, t.H, t.T, t.Tk, t.D
    bq = plan.block_q
    # sequence lengths are rounded up to block multiples: padded queries are
    # sliced off, padded keys are masked dead inside the kernel
    ins = {"q_ref": (t.operand(q, t.Tp), t.q_spec),
           "k_ref": (t.operand(k, t.Tkp), t.k_spec),
           "v_ref": (t.operand(v, t.Tkp), t.k_spec),
           **t.segment_inputs()}
    outs = {"o_ref": (_out_struct(t.shape(t.Tp), q.dtype, q, k, v),
                      t.q_spec)}
    if with_lse:
        outs["lse_ref"] = (
            _out_struct((B * H, t.Tp // t.lanes, t.lanes), jnp.float32,
                        q, k, v), t.stat_spec)
    # a resident head that is ONE key block wide needs no running softmax:
    # no state is kept, rescaled or revisited (q-blocks no query of which
    # sees a key, causal with T > Tk, keep the general path: it writes 0)
    single = plan.resident and t.nk == 1 and (not causal or Tk >= T)
    kernel = functools.partial(
        _flash_fwd_kernel, plan=plan, see=t.see, single=single, scale=scale)
    state = {} if single else {
        "m_ref": pltpu.VMEM((plan.rows, 1, bq), jnp.float32),
        "l_ref": pltpu.VMEM((plan.rows, 1, bq), jnp.float32),
        "acc_ref": pltpu.VMEM((plan.rows, D, bq), jnp.float32)}
    res = _named_call(kernel, plan.scope("fwd"), t.grid, ins, outs, state,
                      interpret)
    out = t.result(res["o_ref"], T)
    if with_lse:
        return out, res["lse_ref"].reshape(B * H, t.Tp)[:, :T].reshape(B, H, T)
    return out


# ---------------------------------------------------------------------------
# flash backward (FlashAttention-2 style): recompute P tiles from (q, k,
# lse) in VMEM — no [T, T] materialization in HBM on the backward either
# ---------------------------------------------------------------------------

_TT = (((1,), (2,)), ((0,), (0,)))      # a^T @ b^T


def _bwd_tile(q, k, v, do, lse, delta, ok, scale):
    """One [rows, keys, queries] tile of the backward: (dq [rows, D, bq],
    dk, dv [rows, D, bk]) partial sums in float32, dq and dk still to be
    multiplied by `scale` (once, where they are written, and not an entry of
    ds at a time). The tile is laid keys-major so that the per-query
    residuals lse / delta broadcast as [rows, 1, bq] rows; the sums come with
    D before the positions, so that the small operand of each of their
    products is the transposed one, an accumulator fills its lanes at D = 64
    and a block is written by one transpose."""
    f32 = jnp.float32
    s = jax.lax.dot_general(k, q, _NT, preferred_element_type=f32) * scale
    p = jnp.exp(s - lse)                           # [rows, bk, bq]
    if ok is not None:
        p = jnp.where(ok, p, 0.0)
    dp = jax.lax.dot_general(v, do, _NT, preferred_element_type=f32)
    ds = p * (dp - delta)                          # [rows, bk, bq]
    dv = jax.lax.dot_general(do, p.astype(do.dtype), _TT,
                             preferred_element_type=f32)
    dk = jax.lax.dot_general(q, ds.astype(q.dtype), _TT,
                             preferred_element_type=f32)
    dq = jax.lax.dot_general(k, ds.astype(k.dtype), _TN,
                             preferred_element_type=f32)
    return dq, dk, dv


def _flash_bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      major_ids_ref=None, minor_ids_ref=None, dq_ref=None,
                      dk_ref=None, dv_ref=None, dq_acc=None, dk_acc=None,
                      dv_acc=None, *, plan, see, keys_outer, single, scale):
    """One grid step of the flash backward.

    Resident plan: dq, dk and dv come from ONE pass that recomputes s and p
    once a tile — the q-block program_id(1) of `plan.rows` heads loops over
    the key blocks it can see, dq accumulating over that loop and dk / dv in
    VMEM over the q-blocks of the head. Streamed plan (a head's keys do not
    fit): the same tile in two passes, because one of dq and dk / dv has to
    accumulate over a grid axis the other is blocked along — a dq pass
    (grid g, i, j; dk_ref None) and a dk / dv pass (grid g, j, i; dq_ref
    None). Either way the three sums and their accumulators are [rows, D,
    positions], and a block is written by one transpose (`_bwd_tile`,
    `_put_heads_transposed`)."""
    from jax.experimental import pallas as pl

    if plan.resident:
        qi, j, step = pl.program_id(1), None, None
    elif keys_outer:
        j, step = pl.program_id(1), pl.program_id(2)
        qi = _step_q_block(plan, see, j, step)
    else:
        qi, step = pl.program_id(1), pl.program_id(2)
        j = _first_key_block(plan, see, qi) + step
    blocks = _KeyBlocks(plan, see, qi, j, major_ids_ref, minor_ids_ref)
    q, do = _held_heads(plan, q_ref), _held_heads(plan, do_ref)

    def tile(j, masked):
        keys = _key_rows(plan, j)
        ok = blocks.mask(j) if masked else None
        return _bwd_tile(
            q(), _heads_of(plan, k_ref, keys), _heads_of(plan, v_ref, keys),
            do(),
            _row_stat(lse_ref, qi, plan.block_q),
            _row_stat(delta_ref, qi, plan.block_q), ok, scale)

    def visit(j, masked):
        keys = _key_rows(plan, j)
        dq, dk, dv = tile(j, masked)
        if dq_ref is not None:
            dq_acc[:] += dq
        if dk_ref is not None:
            dk_acc[:, :, keys] += dk
            dv_acc[:, :, keys] += dv

    def zero_dq():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    def write(dq=None, dk=None, dv=None):
        if dq is not None:
            _put_heads_transposed(plan, dq_ref, dq * scale)
        if dk is not None:
            _put_heads_transposed(plan, dk_ref, dk * scale)
            _put_heads_transposed(plan, dv_ref, dv)

    def zero_dkv():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def write_dkv():
        write(dk=dk_acc[:], dv=dv_acc[:])

    if single:       # one tile is the whole head: nothing to accumulate
        write(*tile(0, blocks.any_masked))
    elif plan.resident:
        pl.when(qi == 0)(zero_dkv)
        zero_dq()
        blocks.visit(visit)
        write(dq=dq_acc[:])
        pl.when(qi == see.num_q_blocks - 1)(write_dkv)
    elif dq_ref is not None:
        pl.when(step == 0)(zero_dq)
        blocks.visit(visit)
        pl.when(step == see.k_steps - 1)(lambda: write(dq=dq_acc[:]))
    else:
        # over the q-blocks of every query head of the key/value head's group
        pl.when(step == 0)(zero_dkv)
        blocks.visit(visit)
        pl.when(step == plan.group * see.q_steps - 1)(write_dkv)


def _flash_attention_bwd_pallas(q, k, v, o, lse, do, scale, causal,
                                block_q=None, block_k=None, interpret=False,
                                segment_ids=None, delta=None, num_heads=None,
                                window=0):
    """The flash backward: (dq, dk, dv) from the forward's operands, its
    context `o` and logsumexp, and the context's cotangent `do`, in the
    operands' layout (`num_heads`: as the forward's). Ring attention passes
    the global `delta` in (`o` may then be None)."""
    plan = _plan_for(q, k, segment_ids is not None, block_q, block_k,
                     num_heads, _checked_window(window, causal))
    _count("flash/call", plan, backward=True)
    return _flash_bwd(q, k, v, o, lse, do, segment_ids, delta,
                      scale=float(scale), causal=bool(causal), plan=plan,
                      interpret=bool(interpret), num_heads=num_heads)


@_traced_once
def _flash_bwd(q, k, v, o, lse, do, segment_ids, delta, *, scale, causal,
               plan, interpret, num_heads):
    from jax.experimental.pallas import tpu as pltpu

    _count("flash/body_traced", plan, backward=True)
    q_ids, kv_ids = _normalize_segment_ids(segment_ids, q, k, num_heads)
    if delta is None:
        # delta_i = sum_d do*o — recomputed here on the single-device path;
        # ring attention passes the global delta in (o may then be None)
        delta = do.astype(jnp.float32) * o.astype(jnp.float32)
        if plan.token_major:                       # [B, T, H * D]
            delta = jnp.swapaxes(jnp.sum(
                delta.reshape(*delta.shape[:2], num_heads, -1), axis=-1),
                1, 2)
        else:
            delta = jnp.sum(delta, axis=-1)        # [B, H, T]
    f32 = jnp.float32
    bq, bk, rows = plan.block_q, plan.block_k, plan.rows

    def call(scope, want_dq, want_dkv):
        keys_outer = not want_dq
        t = _Tiling(plan, q, k, num_heads, q_ids, kv_ids, causal, keys_outer)
        T, Tk, D = t.T, t.Tk, t.D
        # padded queries carry do = 0 and delta = 0 (and a finite lse), so
        # they add nothing to dk and dv whatever they see
        ins = {"q_ref": (t.operand(q, t.Tp), t.q_spec),
               "k_ref": (t.operand(k, t.Tkp), t.k_spec),
               "v_ref": (t.operand(v, t.Tkp), t.k_spec),
               "do_ref": (t.operand(do, t.Tp), t.q_spec),
               "lse_ref": (t.stat(lse), t.stat_spec),
               "delta_ref": (t.stat(delta), t.stat_spec),
               **t.segment_inputs()}
        outs, scratch = {}, {}
        keys = t.Tkp if plan.resident else bk
        single = plan.resident and t.nq == 1 and t.nk == 1
        if want_dq:
            outs["dq_ref"] = (_out_struct(t.shape(t.Tp), q.dtype,
                                          q, k, v, do), t.q_spec)
            scratch["dq_acc"] = pltpu.VMEM((rows, D, bq), f32)
        if want_dkv:
            for name, x in (("dk", k), ("dv", v)):
                outs[name + "_ref"] = (_out_struct(
                    t.shape(t.Tkp, t.KV), x.dtype, q, k, v, do), t.k_spec)
                scratch[name + "_acc"] = pltpu.VMEM((rows, D, keys), f32)
        kernel = functools.partial(
            _flash_bwd_kernel, plan=plan, see=t.see, keys_outer=keys_outer,
            single=single, scale=scale)
        res = _named_call(kernel, plan.scope(scope), t.grid, ins, outs,
                          {} if single else scratch, interpret)
        return {name: (t.result(x, T) if name == "dq_ref"
                       else t.result(x, Tk, t.KV))
                for name, x in res.items()}

    if plan.resident:
        res = call("bwd", True, True)
    else:
        res = {**call("bwd_dq", True, False), **call("bwd_dkv", False, True)}
    return res["dq_ref"], res["dk_ref"], res["dv_ref"]


def flash_attention(q, k, v, scale=None, causal=False, block_q=None,
                    block_k=None, backend=None, segment_ids=None, window=0):
    """Fused multi-head attention. q: [B, H, T, D]; k, v: [B, KV, Tk, D],
    KV a divisor of H (grouped heads: query head i reads key/value head
    i // (H / KV)). `window` > 0 (causal only): a query sees its last
    `window` keys, itself among them.

    backend: None = auto (pallas on TPU, XLA composite elsewhere);
    "pallas_interpret" forces the kernel through the pallas interpreter
    (CPU-testable); "xla" forces the composite.

    segment_ids: packed-batch masking (the LoD translation, SURVEY §5) —
    a [B, T] int array (self-attention) or a (q_ids, kv_ids) pair; a query
    attends a key iff their ids are equal, matching
    parallel.ring_attention's semantics. Composes with `causal`.

    block_q / block_k: None = the kernels take their tiling from the shape
    (`_flash_plan`); a value names the tile's sides.
    """
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    if backend is None:
        backend = _auto_backend()
    return _fused_attention(q, k, v, segment_ids, scale, causal, backend,
                            block_q, block_k, None,
                            _checked_window(window, causal))


# ---------------------------------------------------------------------------
# differentiable wrapper + op registration
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9, 10))
def _fused_attention(q, k, v, segment_ids, scale, causal, backend,
                     block_q=None, block_k=None, num_heads=None, window=0):
    """Differentiable attention on [B, H, T, D] operands (k, v may have
    fewer heads: grouped) or, with `num_heads`, on token-major [B, T, H * D]
    ones whose plan is token-major (`_attend` sees to that)."""
    if backend == "xla":
        return _attention_reference(q, k, v, scale, causal, segment_ids,
                                    window)
    return _flash_attention_pallas(q, k, v, scale, causal, block_q, block_k,
                                   interpret=(backend == "pallas_interpret"),
                                   segment_ids=segment_ids,
                                   num_heads=num_heads, window=window)


def _fused_attention_fwd(q, k, v, segment_ids, scale, causal, backend,
                         block_q=None, block_k=None, num_heads=None,
                         window=0):
    if backend == "xla":
        out = _attention_reference(q, k, v, scale, causal, segment_ids,
                                   window)
        return out, (q, k, v, segment_ids, None, None)
    out, lse = _flash_attention_pallas(
        q, k, v, scale, causal, block_q, block_k,
        interpret=(backend == "pallas_interpret"), with_lse=True,
        segment_ids=segment_ids, num_heads=num_heads, window=window)
    return out, (q, k, v, segment_ids, out, lse)


def _fused_attention_bwd(scale, causal, backend, block_q, block_k, num_heads,
                         window, res, g):
    q, k, v, segment_ids, o, lse = res
    if backend == "xla":
        _, vjp = jax.vjp(
            lambda q_, k_, v_: _attention_reference(q_, k_, v_, scale,
                                                    causal, segment_ids,
                                                    window),
            q, k, v)
        return vjp(g) + (None,)
    # flash backward: recompute P tiles from (q, k, lse) in VMEM — the
    # [T, T] score matrix never exists in HBM in either direction
    return _flash_attention_bwd_pallas(
        q, k, v, o, lse, g, scale, causal, block_q, block_k,
        interpret=(backend == "pallas_interpret"),
        segment_ids=segment_ids, num_heads=num_heads,
        window=window) + (None,)


_fused_attention.defvjp(_fused_attention_fwd, _fused_attention_bwd)


def _attend(q, k, v, segment_ids, scale, causal, backend, num_heads=None,
            window=0):
    """The op on either layout. With `num_heads`: attention of q [B, T,
    H * D] over k, v [B, Tk, KV * D], the layout the projections leave, and
    the context [B, T, H * D]: the kernels take a shape whose plan is
    token-major as it lies; every other shape (grouped heads and a window
    among them), and the composite, goes head-major between two transposes,
    as every call did before PR 47. Without: q [B, H, T, D] and k, v
    [B, KV, Tk, D], a rank-4 caller's."""
    window = _checked_window(window, causal)
    if not num_heads:
        return _fused_attention(q, k, v, segment_ids, scale, causal, backend,
                                None, None, None, window)
    if backend != "xla" and _plan_for(q, k, segment_ids is not None,
                                      num_heads=num_heads,
                                      window=window).token_major:
        return _fused_attention(q, k, v, segment_ids, scale, causal, backend,
                                None, None, num_heads)
    d_head = q.shape[-1] // num_heads

    def heads(x):
        return jnp.swapaxes(x.reshape(*x.shape[:2], -1, d_head), 1, 2)

    out = _fused_attention(heads(q), heads(k), heads(v), segment_ids, scale,
                           causal, backend, None, None, None, window)
    return jnp.swapaxes(out, 1, 2).reshape(q.shape)


def _attention_over_mesh(mesh, q, k, v, segment_ids, scale, causal, backend,
                         num_heads=None, window=0):
    """The flash kernels inside an SPMD-partitioned step (ParallelExecutor).

    The partitioner cannot see into a Mosaic custom call: left bare, the
    kernel runs replicated at the FULL batch on every chip behind an
    all-gather of q/k/v. Attention is independent across batch rows and
    heads, so the call is mapped over the mesh instead: batch over the data
    axis, heads over the model axis (each only where it divides), sequence
    and head_dim whole — every chip runs the kernel on its own
    [B/dp, H/tp, T, D] shard (token-major, `num_heads` given: [B/dp, T,
    H/tp * D]) and no collective is needed."""
    from jax.sharding import PartitionSpec as P

    from ..parallel.mesh import DATA_AXIS, MODEL_AXIS

    def axis_for(name, n):
        size = mesh.axis_size(name)
        return name if size > 1 and n % size == 0 else None

    b_ax = axis_for(DATA_AXIS, q.shape[0])
    # the heads split where the key/value heads (the fewer) divide
    kv_heads = (k.shape[2] * num_heads // q.shape[2] if num_heads
                else k.shape[1])
    h_ax = axis_for(MODEL_AXIS, kv_heads)
    if num_heads and h_ax:
        num_heads //= mesh.axis_size(h_ax)

    def attend(q, k, v, seg):
        return _attend(q, k, v, seg, scale, causal, backend, num_heads,
                       window)

    if b_ax is None and h_ax is None:
        return attend(q, k, v, segment_ids)
    qkv = P(b_ax, None, h_ax) if num_heads else P(b_ax, h_ax, None, None)
    args, specs = [q, k, v], [qkv, qkv, qkv]
    if segment_ids is not None:
        args += list(segment_ids)
        specs += [P(b_ax, None), P(b_ax, None)]

    def per_shard(q, k, v, *seg):
        return attend(q, k, v, seg or None)

    # same exemption as ring attention: the pallas INTERPRETER's discharge
    # path trips the varying-axes check; the compiled kernel keeps it
    return jax.shard_map(per_shard, mesh=mesh.jax_mesh, in_specs=tuple(specs),
                         out_specs=qkv,
                         check_vma=backend != "pallas_interpret")(*args)


def _register():
    from ..framework.registry import register_op

    @register_op("fused_attention")
    def _fused_attention_op(ctx, ins, attrs):
        """Fused scaled-dot-product attention (≙ the composite
        nets.py:332 scaled_dot_product_attention upgraded to a flash
        kernel). Lowering picks the backend per device — the TPU-native
        translation of the reference's (place, dtype, ...) kernel
        dispatch (op_registry.h:214). Q, K, V: [B, H, T, D], or with the
        attr `num_heads` token-major, [B, T, H * D]; Out is as Q. K and V
        may have fewer heads than Q (grouped heads); the attr `window`
        bounds the keys a causal query sees."""
        q, k, v = ins["Q"][0], ins["K"][0], ins["V"][0]
        num_heads = attrs.get("num_heads")
        d_head = q.shape[-1] // (num_heads or 1)
        scale = attrs.get("scale") or 1.0 / (d_head ** 0.5)
        backend = attrs.get("backend") or _auto_backend()
        seg = None
        if ins.get("QSeg"):
            q_ids = ins["QSeg"][0]
            kv_ids = ins["KVSeg"][0] if ins.get("KVSeg") else q_ids
            seg = (q_ids, kv_ids)
        causal = attrs.get("causal", False)
        window = attrs.get("window", 0)
        mesh = getattr(ctx, "mesh", None)
        if backend != "xla" and mesh is not None:
            out = _attention_over_mesh(mesh, q, k, v, seg, scale, causal,
                                       backend, num_heads, window)
        else:
            out = _attend(q, k, v, seg, scale, causal, backend, num_heads,
                          window)
        return {"Out": [out]}


_register()
