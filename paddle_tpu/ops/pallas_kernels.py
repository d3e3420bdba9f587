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

import functools

import jax
import jax.numpy as jnp

_NEG_INF = -1e30


def _auto_backend():
    from ..core import flags as _flags
    if _flags.get_flag("disable_pallas"):
        return "xla"
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def _normalize_segment_ids(segment_ids, q, k):
    """Accept a single [B, Tq] array (self-attention; Tq must equal Tk) or
    a (q_ids [B, Tq], kv_ids [B, Tk]) pair. Returns (q_ids, kv_ids) int32
    or (None, None). Same semantics as parallel.ring_attention: a query
    attends a key iff their ids are equal — the static-shape translation
    of the reference's LoD ragged batches (SURVEY §5 long-context row)."""
    if segment_ids is None:
        return None, None
    if isinstance(segment_ids, (tuple, list)):
        q_ids, kv_ids = segment_ids
    else:
        q_ids = kv_ids = segment_ids
    q_ids = jnp.asarray(q_ids, jnp.int32)
    kv_ids = jnp.asarray(kv_ids, jnp.int32)
    B, _, Tq, _ = q.shape
    Tk = k.shape[2]
    if q_ids.shape != (B, Tq) or kv_ids.shape != (B, Tk):
        raise ValueError(
            f"segment_ids shapes {q_ids.shape}/{kv_ids.shape} do not match "
            f"q [B={B}, Tq={Tq}] / k [B={B}, Tk={Tk}]")
    return q_ids, kv_ids


def _attention_reference(q, k, v, scale, causal, segment_ids=None):
    """Naive composite (the XLA fallback path). q/k/v: [B, H, T, D].
    Causal masking is bottom-right aligned (query i sees keys up to
    i + Tk - Tq — the incremental-decode convention). A query row with NO
    visible keys (causal T > Tk head rows, or a segment id matching no
    key) outputs zeros — the flash kernels' semantics — rather than
    softmax's uniform-weights artifact, so every backend computes
    identical values and gradients."""
    q_ids, kv_ids = _normalize_segment_ids(segment_ids, q, k)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    tq, tk = s.shape[-2], s.shape[-1]
    mask = jnp.ones((1, tq, tk), bool)
    if causal:
        mask &= jnp.tril(jnp.ones((tq, tk), bool), tk - tq)[None]
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


def _segment_mask(qseg_ref, kvseg_ref, block_k):
    """[bq, bk] equality mask from the staged segment-id blocks.

    Layout (mirrors jax's own TPU flash kernel): q ids ride broadcast over
    128 lanes as a [bq, 128] block, kv ids ride broadcast over 8 sublanes
    as an [8, bk] block — Mosaic-legal tilings for what are logically 1-D
    vectors."""
    if block_k <= 128:
        q_ids = qseg_ref[0][:, :block_k]           # [bq, bk] (lane slice)
    else:
        repeats, rem = divmod(block_k, 128)
        if rem:
            raise NotImplementedError("block_k must be a multiple of 128 "
                                      "when segment ids are used")
        q_ids = jnp.tile(qseg_ref[0], (1, repeats))  # [bq, bk]
    kv_ids = kvseg_ref[0][:1]                      # [1, bk]
    return q_ids == kv_ids


def _block_alive(q_blk_idx, k_blk_idx, block_q, block_k, causal,
                 causal_offset, qseg_ref, kvseg_ref):
    """Cheap scalar predicate: can ANY (query, key) pair in this
    (q-block, k-block) tile be unmasked? False → the whole tile's matmuls,
    exp and accumulator updates are skipped (pl.when), which at T=32768
    causal halves the issued FLOPs and on packed batches skips most
    cross-segment tiles. Two safe over-approximations compose:

    - causal: alive iff the LAST query row of the block can see the FIRST
      key column (bottom-right alignment).
    - segments: alive iff the blocks' id RANGES overlap — exact as a
      "no-pair-can-match" test for any id assignment (ranges disjoint ⇒ no
      equality), merely conservative when ranges overlap without an exact
      match; the per-element mask still zeroes those.
    Returns None when nothing can be skipped (no causal, no segments)."""
    alive = None
    if causal:
        alive = ((q_blk_idx + 1) * block_q - 1 + causal_offset
                 >= k_blk_idx * block_k)
    if qseg_ref is not None:
        q_ids = qseg_ref[0]
        kv_ids = kvseg_ref[0]
        seg_alive = ((jnp.max(q_ids) >= jnp.min(kv_ids))
                     & (jnp.min(q_ids) <= jnp.max(kv_ids)))
        alive = seg_alive if alive is None else alive & seg_alive
    return alive


def _flash_kernel(q_ref, k_ref, v_ref, qseg_ref, kvseg_ref, o_ref, lse_ref,
                  m_ref, l_ref, acc_ref, *, scale, causal, block_q, block_k,
                  num_k_blocks, causal_offset, true_tk):
    """One (batch·head, q-block, k-block) grid step of flash attention.

    Grid iterates the k dimension innermost; m/l/acc scratch persists
    across those sequential iterations (TPU grid semantics), implementing
    the online softmax. Fully-masked tiles are skipped (_block_alive).
    """
    from jax.experimental import pallas as pl

    j = pl.program_id(2)
    qi = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def _compute():
        q = q_ref[0]                               # [bq, D]
        k = k_ref[0]                               # [bk, D]
        v = v_ref[0]                               # [bk, D]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [bq, bk]

        k_pos = j * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        # padded key columns (from rounding Tk up to the block size) are
        # dead
        s = jnp.where(k_pos < true_tk, s, _NEG_INF)
        if qseg_ref is not None:
            s = jnp.where(_segment_mask(qseg_ref, kvseg_ref, block_k), s,
                          _NEG_INF)
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            # bottom-right alignment: matches _attention_reference for
            # Tq != Tk
            s = jnp.where(q_pos + causal_offset >= k_pos, s, _NEG_INF)

        m_prev = m_ref[:]                          # [bq, 1]
        l_prev = l_ref[:]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                     # [bq, bk]
        # a fully-masked row has m == s == NEG_INF, making exp(s - m) == 1
        # for every DEAD entry — zero them so such rows output 0, not
        # mean(v)
        p = jnp.where(s > _NEG_INF / 2, p, 0.0)
        l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:] = m_new
        l_ref[:] = l_new

    alive = _block_alive(qi, j, block_q, block_k, causal, causal_offset,
                         qseg_ref, kvseg_ref)
    if alive is None:
        _compute()
    else:
        pl.when(alive)(_compute)

    @pl.when(j == num_k_blocks - 1)
    def _finalize():
        o_ref[0] = (acc_ref[:] /
                    jnp.maximum(l_ref[:], 1e-30)).astype(o_ref.dtype)
        if lse_ref is not None:
            # logsumexp per query row — the backward kernels' residual.
            # Stored broadcast over 128 lanes: Mosaic requires the last two
            # block dims to be (8k, 128m)-tileable, so a [bq] vector output
            # is illegal on real TPU (same layout as jax's own tpu
            # flash_attention lse).
            lse = m_ref[:] + jnp.log(jnp.maximum(l_ref[:], 1e-30))  # [bq,1]
            lse_ref[0] = jnp.broadcast_to(lse, lse_ref[0].shape)



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


def _clamp_block(block, t):
    """Block size actually used for length t: the requested block, clamped
    to t rounded UP to a 128 multiple. Keeps every block shape
    Mosaic-legal (128 | bq, bk) for ANY sequence length — the sequence is
    padded up to the block multiple and the padding masked/sliced — and
    guarantees the segment-id tiling precondition (128 | bk) by
    construction."""
    return min(block, -(-t // 128) * 128)


def _pad_to(x, axis, target):
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, target - x.shape[axis])
    return jnp.pad(x, pad) if target != x.shape[axis] else x


def _stage_segment_ids(q_ids, kv_ids, H, Tp, Tkp, bq, bk):
    """Broadcast + pad segment ids into their Mosaic-legal layouts and
    build (inputs, specs) for a grid whose leading dim is B*H. Padding
    rows/columns carry id 0, which is harmless: padded key columns are
    killed by the true_tk position guard and padded query rows are sliced
    off (fwd) / killed by the true_tq guard (bwd) regardless of id."""
    from jax.experimental import pallas as pl

    B = q_ids.shape[0]
    qseg = jnp.broadcast_to(
        _pad_to(q_ids, 1, Tp)[:, :, None], (B, Tp, 128))
    kvseg = jnp.broadcast_to(
        _pad_to(kv_ids, 1, Tkp)[:, None, :], (B, 8, Tkp))
    qseg_spec = pl.BlockSpec((1, bq, 128), lambda b, i, j, H=H: (b // H, i, 0))
    kvseg_spec = pl.BlockSpec((1, 8, bk), lambda b, i, j, H=H: (b // H, 0, j))
    return (qseg, kvseg), (qseg_spec, kvseg_spec)


def _flash_attention_pallas(q, k, v, scale, causal, block_q, block_k,
                            interpret, with_lse=False, segment_ids=None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    q_ids, kv_ids = _normalize_segment_ids(segment_ids, q, k)
    B, H, T, D = q.shape
    Tk = k.shape[2]
    bq = _clamp_block(block_q, T)
    bk = _clamp_block(block_k, Tk)
    # round sequence lengths up to block multiples: padded queries are
    # sliced off, padded keys are masked dead inside the kernel
    Tp = -(-T // bq) * bq
    Tkp = -(-Tk // bk) * bk
    qf = _pad_to(q.reshape(B * H, T, D), 1, Tp)
    kf = _pad_to(k.reshape(B * H, Tk, D), 1, Tkp)
    vf = _pad_to(v.reshape(B * H, Tk, D), 1, Tkp)
    nq, nk = Tp // bq, Tkp // bk

    inputs = [qf, kf, vf]
    in_specs = [
        pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0)),
        pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0)),
    ]
    has_seg = q_ids is not None
    if has_seg:
        seg_inputs, seg_specs = _stage_segment_ids(
            q_ids, kv_ids, H, Tp, Tkp, bq, bk)
        inputs += list(seg_inputs)
        in_specs += list(seg_specs)

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, block_q=bq, block_k=bk,
        num_k_blocks=nk, causal_offset=Tk - T, true_tk=Tk)
    out_specs = [pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0))]
    out_shape = [_out_struct((B * H, Tp, D), q.dtype, q, k, v)]
    if with_lse:
        out_specs.append(
            pl.BlockSpec((1, bq, 128), lambda b, i, j: (b, i, 0)))
        out_shape.append(
            _out_struct((B * H, Tp, 128), jnp.float32, q, k, v))
    # adapt the kernel's (fixed) signature to the optional refs actually
    # staged: segment refs when packed, lse only on the training path.
    # pallas passes refs positionally (inputs, outputs, scratch), so one
    # generic splicer covers every combination.
    n_in, n_out = len(in_specs), len(out_specs)

    def body(*refs, _k=kernel):
        ins, outs = refs[:n_in], refs[n_in:n_in + n_out]
        scratch = refs[n_in + n_out:]
        qs_ref, ks_ref = (ins[3], ins[4]) if has_seg else (None, None)
        lse_ref = outs[1] if with_lse else None
        _k(ins[0], ins[1], ins[2], qs_ref, ks_ref, outs[0], lse_ref,
           *scratch)
    # the scope is the kernel's stable name in a device trace: XLA names the
    # custom call after it (`jvp_flash_fwd_...`), whatever wraps the call
    with jax.named_scope("flash_fwd"):
        res = pl.pallas_call(
            body,
            grid=(B * H, nq, nk),
            in_specs=in_specs,
            out_specs=out_specs,
            out_shape=out_shape,
            scratch_shapes=[
                pltpu.VMEM((bq, 1), jnp.float32),
                pltpu.VMEM((bq, 1), jnp.float32),
                pltpu.VMEM((bq, D), jnp.float32),
            ],
            interpret=interpret,
        )(*inputs)
    out = res[0][:, :T].reshape(B, H, T, D)
    if with_lse:
        return out, res[1][:, :T, 0].reshape(B, H, T)
    return out


# ---------------------------------------------------------------------------
# flash backward (FlashAttention-2 style): recompute P tiles from (q, k,
# lse) in VMEM — no [T, T] materialization in HBM on the backward either
# ---------------------------------------------------------------------------

def _bwd_masks(qi, j, block_q, block_k, causal, causal_offset,
               true_tq, true_tk, qseg_ref=None, kvseg_ref=None):
    q_pos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    k_pos = j * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    valid = (q_pos < true_tq) & (k_pos < true_tk)
    if causal:
        valid &= q_pos + causal_offset >= k_pos
    if qseg_ref is not None:
        valid &= _segment_mask(qseg_ref, kvseg_ref, block_k)
    return valid


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         qseg_ref, kvseg_ref, dq_ref, acc_ref, *, scale,
                         causal, block_q, block_k, num_k_blocks,
                         causal_offset, true_tq, true_tk):
    from jax.experimental import pallas as pl

    j = pl.program_id(2)
    qi = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0][:, :1]                    # [bq, 1] (128-lane bcast)
        delta = delta_ref[0][:, :1]                # [bq, 1]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        valid = _bwd_masks(qi, j, block_q, block_k, causal, causal_offset,
                           true_tq, true_tk, qseg_ref, kvseg_ref)
        p = jnp.where(valid, jnp.exp(s - lse), 0.0)  # [bq, bk]
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        acc_ref[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    alive = _block_alive(qi, j, block_q, block_k, causal, causal_offset,
                         qseg_ref, kvseg_ref)
    if alive is None:
        _compute()
    else:
        pl.when(alive)(_compute)

    @pl.when(j == num_k_blocks - 1)
    def _finalize():
        dq_ref[0] = acc_ref[:].astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          qseg_ref, kvseg_ref, dk_ref, dv_ref, dk_acc,
                          dv_acc, *, scale, causal, block_q, block_k,
                          num_q_blocks, causal_offset, true_tq, true_tk):
    from jax.experimental import pallas as pl

    i = pl.program_id(2)      # inner: q blocks
    ki = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0][:, :1]                    # [bq, 1] (128-lane bcast)
        delta = delta_ref[0][:, :1]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        valid = _bwd_masks(i, ki, block_q, block_k, causal, causal_offset,
                           true_tq, true_tk, qseg_ref, kvseg_ref)
        p = jnp.where(valid, jnp.exp(s - lse), 0.0)  # [bq, bk]
        dv_acc[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)    # [bk, D]
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale              # [bq, bk]
        dk_acc[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)    # [bk, D]

    alive = _block_alive(i, ki, block_q, block_k, causal, causal_offset,
                         qseg_ref, kvseg_ref)
    if alive is None:
        _compute()
    else:
        pl.when(alive)(_compute)

    @pl.when(i == num_q_blocks - 1)
    def _finalize():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _flash_attention_bwd_pallas(q, k, v, o, lse, do, scale, causal,
                                block_q, block_k, interpret,
                                segment_ids=None, delta=None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    q_ids, kv_ids = _normalize_segment_ids(segment_ids, q, k)
    B, H, T, D = q.shape
    Tk = k.shape[2]
    bq = _clamp_block(block_q, T)
    bk = _clamp_block(block_k, Tk)
    Tp = -(-T // bq) * bq
    Tkp = -(-Tk // bk) * bk
    nq, nk = Tp // bq, Tkp // bk

    if delta is None:
        # delta_i = sum_d do*o — recomputed here on the single-device path;
        # ring attention passes the global delta in (o may then be None)
        delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                        axis=-1)                   # [B, H, T]
    qf = _pad_to(q.reshape(B * H, T, D), 1, Tp)
    kf = _pad_to(k.reshape(B * H, Tk, D), 1, Tkp)
    vf = _pad_to(v.reshape(B * H, Tk, D), 1, Tkp)
    dof = _pad_to(do.reshape(B * H, T, D), 1, Tp)
    # per-row residuals ride broadcast over 128 lanes (Mosaic tiling; see
    # the forward lse layout note)
    lsef = jnp.broadcast_to(
        _pad_to(lse.reshape(B * H, T), 1, Tp)[..., None],
        (B * H, Tp, 128))
    deltaf = jnp.broadcast_to(
        _pad_to(delta.reshape(B * H, T), 1, Tp)[..., None],
        (B * H, Tp, 128))

    common = dict(scale=scale, causal=causal, block_q=bq, block_k=bk,
                  causal_offset=Tk - T, true_tq=T, true_tk=Tk)
    has_seg = q_ids is not None
    q_spec = pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0))
    r_spec = pl.BlockSpec((1, bq, 128), lambda b, i, j: (b, i, 0))
    k_spec = pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0))

    def _splice_seg(kernel, n_in):
        """Generic adapter: insert (None, None) for the segment refs when
        no segment inputs are staged (pallas passes refs positionally:
        inputs, outputs, scratch)."""
        if has_seg:
            return kernel

        def body(*refs, _k=kernel):
            return _k(*refs[:n_in], None, None, *refs[n_in:])
        return body

    dq_inputs = [qf, kf, vf, dof, lsef, deltaf]
    dq_specs = [q_spec, k_spec, k_spec, q_spec, r_spec, r_spec]
    dq_kernel = functools.partial(_flash_bwd_dq_kernel, num_k_blocks=nk,
                                  **common)
    if has_seg:
        seg_inputs, seg_specs = _stage_segment_ids(
            q_ids, kv_ids, H, Tp, Tkp, bq, bk)
        dq_inputs += list(seg_inputs)
        dq_specs += list(seg_specs)
    with jax.named_scope("flash_bwd_dq"):
        dq = pl.pallas_call(
            _splice_seg(dq_kernel, 6),
            grid=(B * H, nq, nk),
            in_specs=dq_specs,
            out_specs=q_spec,
            out_shape=_out_struct((B * H, Tp, D), q.dtype, q, k, v, do),
            scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
            interpret=interpret,
        )(*dq_inputs)

    # dk/dv: k blocks are the outer (revisited) dim, q blocks stream inner
    qi_spec = pl.BlockSpec((1, bq, D), lambda b, j, i: (b, i, 0))
    ri_spec = pl.BlockSpec((1, bq, 128), lambda b, j, i: (b, i, 0))
    kj_spec = pl.BlockSpec((1, bk, D), lambda b, j, i: (b, j, 0))
    dkv_inputs = [qf, kf, vf, dof, lsef, deltaf]
    dkv_specs = [qi_spec, kj_spec, kj_spec, qi_spec, ri_spec, ri_spec]
    dkv_kernel = functools.partial(_flash_bwd_dkv_kernel, num_q_blocks=nq,
                                   **common)
    if has_seg:
        # grid order here is (b, k-block j, q-block i): swap the index-map
        # arguments accordingly
        qsegf, kvsegf = seg_inputs
        dkv_inputs += [qsegf, kvsegf]
        dkv_specs += [
            pl.BlockSpec((1, bq, 128), lambda b, j, i, H=H: (b // H, i, 0)),
            pl.BlockSpec((1, 8, bk), lambda b, j, i, H=H: (b // H, 0, j)),
        ]
    with jax.named_scope("flash_bwd_dkv"):
        dk, dv = pl.pallas_call(
            _splice_seg(dkv_kernel, 6),
            grid=(B * H, nk, nq),
            in_specs=dkv_specs,
            out_specs=[kj_spec, kj_spec],
            out_shape=[_out_struct((B * H, Tkp, D), k.dtype, q, k, v, do),
                       _out_struct((B * H, Tkp, D), v.dtype, q, k, v, do)],
            scratch_shapes=[pltpu.VMEM((bk, D), jnp.float32),
                            pltpu.VMEM((bk, D), jnp.float32)],
            interpret=interpret,
        )(*dkv_inputs)

    return (dq[:, :T].reshape(B, H, T, D),
            dk[:, :Tk].reshape(B, H, Tk, D),
            dv[:, :Tk].reshape(B, H, Tk, D))


def flash_attention(q, k, v, scale=None, causal=False, block_q=512,
                    block_k=1024, backend=None, segment_ids=None):
    """Fused multi-head attention. q/k/v: [B, H, T, D].

    backend: None = auto (pallas on TPU, XLA composite elsewhere);
    "pallas_interpret" forces the kernel through the pallas interpreter
    (CPU-testable); "xla" forces the composite.

    segment_ids: packed-batch masking (the LoD translation, SURVEY §5) —
    a [B, T] int array (self-attention) or a (q_ids, kv_ids) pair; a query
    attends a key iff their ids are equal, matching
    parallel.ring_attention's semantics. Composes with `causal`.
    """
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    if backend is None:
        backend = _auto_backend()
    return _fused_attention(q, k, v, segment_ids, scale, causal, backend,
                            block_q, block_k)


# ---------------------------------------------------------------------------
# differentiable wrapper + op registration
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _fused_attention(q, k, v, segment_ids, scale, causal, backend,
                     block_q=512, block_k=1024):
    if backend == "xla":
        return _attention_reference(q, k, v, scale, causal, segment_ids)
    return _flash_attention_pallas(q, k, v, scale, causal, block_q, block_k,
                                   interpret=(backend == "pallas_interpret"),
                                   segment_ids=segment_ids)


def _fused_attention_fwd(q, k, v, segment_ids, scale, causal, backend,
                         block_q=512, block_k=1024):
    if backend == "xla":
        out = _attention_reference(q, k, v, scale, causal, segment_ids)
        return out, (q, k, v, segment_ids, None, None)
    out, lse = _flash_attention_pallas(
        q, k, v, scale, causal, block_q, block_k,
        interpret=(backend == "pallas_interpret"), with_lse=True,
        segment_ids=segment_ids)
    return out, (q, k, v, segment_ids, out, lse)


def _fused_attention_bwd(scale, causal, backend, block_q, block_k, res, g):
    q, k, v, segment_ids, o, lse = res
    if backend == "xla":
        _, vjp = jax.vjp(
            lambda q_, k_, v_: _attention_reference(q_, k_, v_, scale,
                                                    causal, segment_ids),
            q, k, v)
        return vjp(g) + (None,)
    # flash backward: recompute P tiles from (q, k, lse) in VMEM — the
    # [T, T] score matrix never exists in HBM in either direction
    return _flash_attention_bwd_pallas(
        q, k, v, o, lse, g, scale, causal, block_q, block_k,
        interpret=(backend == "pallas_interpret"),
        segment_ids=segment_ids) + (None,)


_fused_attention.defvjp(_fused_attention_fwd, _fused_attention_bwd)


def _attention_over_mesh(mesh, q, k, v, segment_ids, scale, causal, backend):
    """The flash kernels inside an SPMD-partitioned step (ParallelExecutor).

    The partitioner cannot see into a Mosaic custom call: left bare, the
    kernel runs replicated at the FULL batch on every chip behind an
    all-gather of q/k/v. Attention is independent across batch rows and
    heads, so the call is mapped over the mesh instead: batch over the data
    axis, heads over the model axis (each only where it divides), sequence
    and head_dim whole — every chip runs the kernel on its own
    [B/dp, H/tp, T, D] shard and no collective is needed."""
    from jax.sharding import PartitionSpec as P

    from ..parallel.mesh import DATA_AXIS, MODEL_AXIS

    def axis_for(name, n):
        size = mesh.axis_size(name)
        return name if size > 1 and n % size == 0 else None

    b_ax = axis_for(DATA_AXIS, q.shape[0])
    h_ax = axis_for(MODEL_AXIS, q.shape[1])
    if b_ax is None and h_ax is None:
        return _fused_attention(q, k, v, segment_ids, scale, causal, backend)
    qkv = P(b_ax, h_ax, None, None)
    args, specs = [q, k, v], [qkv, qkv, qkv]
    if segment_ids is not None:
        args += list(segment_ids)
        specs += [P(b_ax, None), P(b_ax, None)]

    def per_shard(q, k, v, *seg):
        return _fused_attention(q, k, v, seg or None, scale, causal, backend)

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
        dispatch (op_registry.h:214)."""
        q, k, v = ins["Q"][0], ins["K"][0], ins["V"][0]
        scale = attrs.get("scale") or 1.0 / (q.shape[-1] ** 0.5)
        backend = attrs.get("backend") or _auto_backend()
        seg = None
        if ins.get("QSeg"):
            q_ids = ins["QSeg"][0]
            kv_ids = ins["KVSeg"][0] if ins.get("KVSeg") else q_ids
            seg = (q_ids, kv_ids)
        causal = attrs.get("causal", False)
        mesh = getattr(ctx, "mesh", None)
        if backend != "xla" and mesh is not None:
            out = _attention_over_mesh(mesh, q, k, v, seg, scale, causal,
                                       backend)
        else:
            out = _fused_attention(q, k, v, seg, scale, causal, backend)
        return {"Out": [out]}


_register()
