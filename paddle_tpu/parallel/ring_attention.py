"""Ring attention: exact attention over sequences sharded across devices.

NEW capability with no reference analogue (SURVEY.md §5 "long context": the
reference's story is LoD ragged batching, not sequence sharding). Design is
the ring/flash formulation: Q,K,V are sharded along the sequence dim over the
`sp` mesh axis; each device computes blockwise attention against its local KV
block while rotating KV blocks around the ICI ring with `ppermute`,
accumulating the softmax online, so the full [T, T] score matrix never
materializes and comm overlaps compute.

v2 (VERDICT r4 #2): each ring step's local block runs through the SAME
Pallas flash kernels as single-device attention (`ops/pallas_kernels.py`) —
O(t_local) memory per block, per-tile dead-block skipping inside the kernel
— and the kernels' `_KeyBlocks` idea is lifted to ring granularity: a causal ring
step whose held KV block is entirely in the query block's future (or a
packed step whose segment-id ranges cannot overlap) is a `lax.switch` branch
that computes NOTHING. A causal ring therefore executes n(n+1)/2 of the n^2
block computations (~half the FLOPs), matching the flash kernel's own
causal block skipping. Gradients are a ring-level `jax.custom_vjp`: the
backward re-runs the ring with the flash backward kernels against the
GLOBAL logsumexp/delta residuals (flash backward is block-decomposable),
rotating dk/dv accumulators home with the KV blocks.

Cost: n ring steps of flash-kernel block attention + (n-1) KV ppermutes on
the forward; (n-1) KV + n dKV ppermutes on the backward — exact, not
approximate, attention.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .mesh import DATA_AXIS, SEQUENCE_AXIS, DeviceMesh

_NEG_INF = -1e30


def _block_attn(q, k, v, bias, m_prev, l_prev, o_prev, scale):
    """One online-softmax block update (reference composite; kept as the
    semantic spec the kernels are tested against — test_pallas_attention
    matches the flash kernel to this block math).

    q: [B, Tq, H, D]; k,v: [B, Tk, H, D]; bias: [B, 1|H, Tq, Tk] additive
    mask (0 / -inf); m,l,o running max / denom / numerator.
    """
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if bias is not None:
        s = s + bias
    m_cur = jnp.max(s, axis=-1)                      # [B, H, Tq]
    m_new = jnp.maximum(m_prev, m_cur)
    # guard fully-masked rows: keep exp finite
    p = jnp.exp(s - m_new[..., None])                # [B, H, Tq, Tk]
    l_cur = jnp.sum(p, axis=-1)
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + l_cur
    o_cur = jnp.einsum("bhqk,bkhd->bqhd", p, v)
    o_new = o_prev * corr.transpose(0, 2, 1)[..., None] + o_cur
    return m_new, l_new, o_new


# ---------------------------------------------------------------------------
# per-block forward/backward, shared flash-kernel path + XLA fallback
# ---------------------------------------------------------------------------


def _block_fwd(q, k, v, scale, causal, q_ids, kv_ids, backend, block_q,
               block_k):
    """One ring block: q,k,v [B,H,t,D] -> (o f32 [B,H,t,D], lse f32
    [B,H,t]). A query row with no visible key gets o=0, lse=-inf (the flash
    kernels' convention), which the logsumexp merge treats as weight 0."""
    if backend == "xla":
        f32 = jnp.float32
        s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(f32),
                       k.astype(f32)) * scale
        valid = _block_valid(s.shape, causal, q_ids, kv_ids)
        if valid is not None:
            s = jnp.where(valid, s, _NEG_INF)
        m = jnp.max(s, axis=-1)                      # [B,H,t]
        p = jnp.where(s > _NEG_INF / 2, jnp.exp(s - m[..., None]), 0.0)
        l = jnp.sum(p, axis=-1)
        lse = jnp.where(l > 0, m + jnp.log(jnp.maximum(l, 1e-30)),
                        _NEG_INF)
        o = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(f32))
        o = o / jnp.maximum(l, 1e-30)[..., None]
        return o, lse
    from ..ops.pallas_kernels import _flash_attention_pallas
    seg = (q_ids, kv_ids) if q_ids is not None else None
    o, lse = _flash_attention_pallas(
        q, k, v, scale, causal, block_q, block_k,
        interpret=(backend == "pallas_interpret"), with_lse=True,
        segment_ids=seg)
    return o.astype(jnp.float32), lse


def _block_valid(s_shape, causal, q_ids, kv_ids):
    B, H, tq, tk = s_shape
    valid = None
    if causal:
        valid = jnp.tril(jnp.ones((tq, tk), bool))[None, None]
    if q_ids is not None:
        same = (q_ids[:, :, None] == kv_ids[:, None, :])[:, None]
        valid = same if valid is None else valid & same
    return valid


def _block_bwd(q, k, v, do, lse, delta, scale, causal, q_ids, kv_ids,
               backend, block_q, block_k):
    """One ring block backward against GLOBAL residuals: returns
    (dq, dk, dv) each [B,H,t,D] in q/k/v dtype. p = exp(s - lse) is the
    block's slice of the global softmax, so per-block grads sum to the
    exact global gradient."""
    if backend == "xla":
        f32 = jnp.float32
        s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(f32),
                       k.astype(f32)) * scale
        valid = _block_valid(s.shape, causal, q_ids, kv_ids)
        p = jnp.exp(s - lse[..., None])
        if valid is not None:
            p = jnp.where(valid, p, 0.0)
        dof = do.astype(f32)
        dv = jnp.einsum("bhqk,bhqd->bhkd", p, dof)
        dp = jnp.einsum("bhqd,bhkd->bhqk", dof, v.astype(f32))
        ds = p * (dp - delta[..., None]) * scale
        dq = jnp.einsum("bhqk,bhkd->bhqd", ds, k.astype(f32))
        dk = jnp.einsum("bhqk,bhqd->bhkd", ds, q.astype(f32))
        return (dq.astype(q.dtype), dk.astype(k.dtype),
                dv.astype(v.dtype))
    from ..ops.pallas_kernels import _flash_attention_bwd_pallas
    seg = (q_ids, kv_ids) if q_ids is not None else None
    return _flash_attention_bwd_pallas(
        q, k, v, None, lse, do, scale, causal, block_q, block_k,
        interpret=(backend == "pallas_interpret"), segment_ids=seg,
        delta=delta)


def _as_varying_as(x, *refs):
    """Mark `x` as device-varying over every mesh axis any of `refs` varies
    over (and `x` does not yet) — lax.switch requires all branches to
    produce identical vma types under shard_map, and the dead branch's
    fresh zeros would otherwise come out replicated."""
    axes = set()
    for r in refs:
        axes |= set(getattr(r.aval, "vma", ()) or ())
    axes -= set(getattr(jax.typeof(x), "vma", ()) or ())
    if not axes:
        return x
    return jax.lax.pcast(x, tuple(sorted(axes)), to="varying")


def _merge(o_acc, lse_acc, o_r, lse_r):
    """Online logsumexp merge of a new block's normalized output: keeps
    o_acc correctly normalized over every block seen so far."""
    lse_new = jnp.logaddexp(lse_acc, lse_r)
    w_acc = jnp.exp(lse_acc - lse_new)[..., None]
    w_r = jnp.exp(lse_r - lse_new)[..., None]
    return o_acc * w_acc + o_r * w_r, lse_new


def _step_case(r, idx, n, causal, seg_q_minmax, seg_blk):
    """Ring-step branch index: 0 = full block, 1 = diagonal (causal mask
    applies inside the block), 2 = dead (skip the computation entirely).
    The causal part is the ring-granularity `_KeyBlocks.n_live`: a held KV
    block from src > idx is entirely in every local query's future. The
    segment part mirrors the kernels' range-overlap test: if no row's
    [min,max] id ranges overlap, no (q, key) pair can match."""
    src = (idx - r) % n
    if causal:
        case = jnp.where(src == idx, 1, jnp.where(src < idx, 0, 2))
    else:
        case = jnp.int32(0)
    if seg_blk is not None:
        q_min, q_max = seg_q_minmax
        kv_min = jnp.min(seg_blk, axis=1)            # [B]
        kv_max = jnp.max(seg_blk, axis=1)
        overlap = jnp.any((q_max >= kv_min) & (q_min <= kv_max))
        case = jnp.where(overlap, case, 2)
    return case


def _ring_fwd_scan(q, k, v, segment_ids, axis_name, causal, scale, backend,
                   block_q, block_k):
    """Per-shard forward ring. q,k,v [B,H,t,D] (head-major). Returns
    (o f32, lse f32, live int32) with live = number of ring steps whose
    block computation actually executed (the skip-evidence counter)."""
    n = jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name)
    B, H, t, D = q.shape

    from .collective import ring_perm
    perm = ring_perm(int(n))

    o_acc = jnp.zeros((B, H, t, D), jnp.float32)
    lse_acc = jnp.full((B, H, t), _NEG_INF, jnp.float32)
    live = jnp.int32(0)
    seg_q_minmax = None
    if segment_ids is not None:
        seg_q_minmax = (jnp.min(segment_ids, axis=1),
                        jnp.max(segment_ids, axis=1))

    k_blk, v_blk, seg_blk = k, v, segment_ids
    for r in range(int(n)):
        case = _step_case(r, idx, n, causal, seg_q_minmax, seg_blk)

        def _full(kb, vb, sb):
            return _block_fwd(q, kb, vb, scale, False, segment_ids, sb,
                              backend, block_q, block_k)

        def _diag(kb, vb, sb):
            return _block_fwd(q, kb, vb, scale, True, segment_ids, sb,
                              backend, block_q, block_k)

        def _dead(kb, vb, sb):
            return (_as_varying_as(jnp.zeros((B, H, t, D), jnp.float32),
                                   q, kb),
                    _as_varying_as(jnp.full((B, H, t), _NEG_INF,
                                            jnp.float32), q, kb))

        if segment_ids is None:
            # keep branch signatures uniform; sb unused
            o_r, lse_r = jax.lax.switch(
                case, [lambda kb, vb: _full(kb, vb, None),
                       lambda kb, vb: _diag(kb, vb, None),
                       lambda kb, vb: _dead(kb, vb, None)], k_blk, v_blk)
        else:
            o_r, lse_r = jax.lax.switch(
                case, [_full, _diag, _dead], k_blk, v_blk, seg_blk)
        o_acc, lse_acc = _merge(o_acc, lse_acc, o_r, lse_r)
        live = live + jnp.where(case != 2, 1, 0).astype(jnp.int32)

        if r < int(n) - 1:                           # n-1 KV hops exactly
            k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
            v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
            if seg_blk is not None:
                seg_blk = jax.lax.ppermute(seg_blk, axis_name, perm)
    return o_acc, lse_acc, live


def _ring_bwd_scan(q, k, v, segment_ids, lse, delta, do, axis_name, causal,
                   scale, backend, block_q, block_k):
    """Per-shard backward ring against global (lse, delta). dk/dv
    accumulators rotate WITH the KV blocks and take the n-th hop home."""
    n = jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name)
    B, H, t, D = q.shape

    from .collective import ring_perm
    perm = ring_perm(int(n))

    dq_acc = jnp.zeros((B, H, t, D), jnp.float32)
    seg_q_minmax = None
    if segment_ids is not None:
        seg_q_minmax = (jnp.min(segment_ids, axis=1),
                        jnp.max(segment_ids, axis=1))

    k_blk, v_blk, seg_blk = k, v, segment_ids
    # dKV accumulators ride the ring in f32: bf16 accumulation across n
    # partial contributions would lose the low bits of the sum
    dk_blk = jnp.zeros(k.shape, jnp.float32)
    dv_blk = jnp.zeros(v.shape, jnp.float32)
    for r in range(int(n)):
        case = _step_case(r, idx, n, causal, seg_q_minmax, seg_blk)

        def _full(kb, vb, sb):
            return _block_bwd(q, kb, vb, do, lse, delta, scale, False,
                              segment_ids, sb, backend, block_q, block_k)

        def _diag(kb, vb, sb):
            return _block_bwd(q, kb, vb, do, lse, delta, scale, True,
                              segment_ids, sb, backend, block_q, block_k)

        def _dead(kb, vb, sb):
            return (_as_varying_as(jnp.zeros((B, H, t, D), q.dtype),
                                   q, kb, do),
                    _as_varying_as(jnp.zeros((B, H, t, D), k.dtype),
                                   q, kb, do),
                    _as_varying_as(jnp.zeros((B, H, t, D), v.dtype),
                                   q, kb, do))

        if segment_ids is None:
            dq_r, dk_r, dv_r = jax.lax.switch(
                case, [lambda kb, vb: _full(kb, vb, None),
                       lambda kb, vb: _diag(kb, vb, None),
                       lambda kb, vb: _dead(kb, vb, None)], k_blk, v_blk)
        else:
            dq_r, dk_r, dv_r = jax.lax.switch(
                case, [_full, _diag, _dead], k_blk, v_blk, seg_blk)
        dq_acc = dq_acc + dq_r.astype(jnp.float32)
        dk_blk = dk_blk + dk_r.astype(jnp.float32)
        dv_blk = dv_blk + dv_r.astype(jnp.float32)

        if r < int(n) - 1:
            k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
            v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
            if seg_blk is not None:
                seg_blk = jax.lax.ppermute(seg_blk, axis_name, perm)
        # the dKV accumulators take ALL n hops: after the last compute the
        # held block is (idx+1)'s, one more rotation returns it home
        dk_blk = jax.lax.ppermute(dk_blk, axis_name, perm)
        dv_blk = jax.lax.ppermute(dv_blk, axis_name, perm)
    return (dq_acc.astype(q.dtype), dk_blk.astype(k.dtype),
            dv_blk.astype(v.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _ring_attention(q, k, v, segment_ids, axis_name, causal, scale, backend,
                    block_q, block_k):
    o, _, _ = _ring_fwd_scan(q, k, v, segment_ids, axis_name, causal, scale,
                             backend, block_q, block_k)
    return o.astype(q.dtype)


def _ring_attention_fwd(q, k, v, segment_ids, axis_name, causal, scale,
                        backend, block_q, block_k):
    o, lse, _ = _ring_fwd_scan(q, k, v, segment_ids, axis_name, causal,
                               scale, backend, block_q, block_k)
    out = o.astype(q.dtype)
    return out, (q, k, v, segment_ids, out, lse)


def _ring_attention_bwd(axis_name, causal, scale, backend, block_q, block_k,
                        res, g):
    q, k, v, segment_ids, o, lse = res
    delta = jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    dq, dk, dv = _ring_bwd_scan(q, k, v, segment_ids, lse, delta, g,
                                axis_name, causal, scale, backend, block_q,
                                block_k)
    return dq, dk, dv, None


_ring_attention.defvjp(_ring_attention_fwd, _ring_attention_bwd)


def _resolve_backend(backend):
    if backend is not None:
        return backend
    from ..ops.pallas_kernels import _auto_backend
    return _auto_backend()


def ring_attention(q, k, v, *, axis_name: str = SEQUENCE_AXIS,
                   causal: bool = False, scale: Optional[float] = None,
                   segment_ids=None, backend: Optional[str] = None,
                   block_q: Optional[int] = None,
                   block_k: Optional[int] = None,
                   with_stats: bool = False):
    """Per-shard ring attention body. Must run inside shard_map with q/k/v
    sequence-sharded: q,k,v: [B, T_local, H, D].

    segment_ids: optional [B, T_local] int array (packed-batch masking — the
    static-shape translation of the reference's LoD batches, SURVEY.md §5).
    backend: None = auto (Pallas flash kernels on TPU, XLA composite
    elsewhere); "pallas_interpret" runs the kernels through the pallas
    interpreter (CPU-testable); "xla" forces the composite blocks.
    with_stats: also return the number of ring-step block computations this
    shard actually executed (dead causal/segment steps are skipped whole).
    """
    backend = _resolve_backend(backend)
    scale = scale if scale is not None else 1.0 / (q.shape[-1] ** 0.5)
    seg = None if segment_ids is None else jnp.asarray(segment_ids,
                                                       jnp.int32)
    # ring API carries [B, t, H, D]; the kernels run head-major [B, H, t, D]
    qh = jnp.transpose(q, (0, 2, 1, 3))
    kh = jnp.transpose(k, (0, 2, 1, 3))
    vh = jnp.transpose(v, (0, 2, 1, 3))
    if with_stats:
        o, _, live = _ring_fwd_scan(qh, kh, vh, seg, axis_name, causal,
                                    scale, backend, block_q, block_k)
        return jnp.transpose(o.astype(q.dtype), (0, 2, 1, 3)), live
    out = _ring_attention(qh, kh, vh, seg, axis_name, causal, scale,
                          backend, block_q, block_k)
    return jnp.transpose(out, (0, 2, 1, 3))


def ring_attention_sharded(mesh: DeviceMesh, q, k, v, *, causal=False,
                           scale=None, segment_ids=None, backend=None,
                           block_q: Optional[int] = None,
                           block_k: Optional[int] = None):
    """Entry point from the annotate-and-partition world: q,k,v [B, T, H, D]
    (any sharding); returns attention output with T sharded over sp."""
    if SEQUENCE_AXIS not in mesh.axes:
        raise ValueError(
            f"ring attention requires a {SEQUENCE_AXIS!r} axis in the mesh "
            f"(got axes {tuple(mesh.axes)}); for unsharded sequences use "
            f"plain attention")
    in_spec = mesh.pspec(DATA_AXIS, SEQUENCE_AXIS, None, None)
    seg_spec = mesh.pspec(DATA_AXIS, SEQUENCE_AXIS)
    backend = _resolve_backend(backend)
    # the pallas INTERPRETER's discharge path trips a jax vma bug inside
    # checked shard_map (dynamic_slice "varying manual axes" mismatch);
    # disable the check only for that test backend — the production
    # pallas/xla paths keep shard_map's varying-axes validation
    check_vma = backend != "pallas_interpret"

    if segment_ids is None:
        def body(q, k, v):
            return ring_attention(q, k, v, causal=causal, scale=scale,
                                  backend=backend, block_q=block_q,
                                  block_k=block_k)
        f = jax.shard_map(body, mesh=mesh.jax_mesh,
                          in_specs=(in_spec, in_spec, in_spec),
                          out_specs=in_spec, check_vma=check_vma)
        return f(q, k, v)

    def body(q, k, v, seg):
        return ring_attention(q, k, v, causal=causal, scale=scale,
                              segment_ids=seg, backend=backend,
                              block_q=block_q, block_k=block_k)
    f = jax.shard_map(body, mesh=mesh.jax_mesh,
                      in_specs=(in_spec, in_spec, in_spec, seg_spec),
                      out_specs=in_spec, check_vma=check_vma)
    return f(q, k, v, segment_ids)


def ring_attention_live_blocks(mesh: DeviceMesh, q, k, v, *, causal=False,
                               scale=None, segment_ids=None, backend=None):
    """Diagnostic entry: run the forward ring and return (out, total number
    of block computations executed across all shards). A causal ring over n
    shards executes n(n+1)/2 of the n^2 blocks; a non-causal ring executes
    all n^2. Evidence hook for the dead-step skipping tests/benches."""
    in_spec = mesh.pspec(DATA_AXIS, SEQUENCE_AXIS, None, None)
    seg_spec = mesh.pspec(DATA_AXIS, SEQUENCE_AXIS)
    specs = [in_spec, in_spec, in_spec]
    args = [q, k, v]
    if segment_ids is not None:
        specs.append(seg_spec)
        args.append(segment_ids)
    backend = _resolve_backend(backend)

    # sum over the axes the computation is actually SHARDED on (batch over
    # DATA, sequence over SEQUENCE): with a dp-sharded batch and
    # heterogeneous packing, different data shards skip different numbers
    # of steps — a SEQUENCE_AXIS-only psum would report one data shard's
    # count as the mesh total. But axes the body is REPLICATED over (e.g.
    # a tensor-parallel axis absent from in_specs) must NOT be summed:
    # each replica holds the identical count, and summing replicas would
    # inflate the diagnostic by the replication factor (ADVICE r5 #1).
    shard_axes = tuple(a for a in (DATA_AXIS, SEQUENCE_AXIS)
                       if a in mesh.axes)

    def body(*xs):
        seg = xs[3] if len(xs) > 3 else None
        out, live = ring_attention(
            xs[0], xs[1], xs[2], causal=causal, scale=scale,
            segment_ids=seg, backend=backend, with_stats=True)
        # without segment ids the count does not depend on the data shard,
        # so it is not varying over dp — and psum refuses an axis its
        # operand does not vary over
        return out, jax.lax.psum(_as_varying_as(live, *xs), shard_axes)

    f = jax.shard_map(body, mesh=mesh.jax_mesh, in_specs=tuple(specs),
                      out_specs=(in_spec, mesh.pspec()),
                      check_vma=backend != "pallas_interpret")
    out, live = f(*args)
    return out, int(jnp.max(live))
