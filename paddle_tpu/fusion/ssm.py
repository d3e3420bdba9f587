"""The Mamba-2 mixer's state path over a tick's rows, with the state a
request carries beside its per-token cache rows.

The mixer (`models/transformer.py _ssm_mixer`) is `[z, xBC, dt] = x W_in`;
`xBC = silu(conv1d(xBC) + b)`, a causal depthwise convolution of K taps whose
state is the last K-1 rows of its input (`short_conv.conv_rows`); `xBC`
splits into `x [H, P]`, `B [G, N]`, `C [G, N]` (head h reads group
h // (H/G)); `dt = softplus(dt + dt_bias)`, `A = -exp(A_log)`, a value a head;

    h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t        (a head's h is [P, N])
    y_t = h_t C_t + D x_t

then `RMSNorm_groups(y * silu(z)) W_out` (`gated_rms_norm`). Everything but
the convolution and `h` is row-wise. `h` [H, P, N] in float32 (4 MB a layer
at 128 x 64 x 128) and the conv rows are a request's STATE, whatever its
length. Per state-space layer four persistable arrays hold it (`_RecurrentState`
declares them):

- `slot_h` [n_slots, H, P, N] float32 and `slot_conv` [n_slots, K-1, CD]: the
  state of the request in each tick slot AFTER the last position it fed. A
  decode row updates its slot's `h` IN PLACE (`ssm_decode_update`: one Pallas
  call that reads the LIVE slots' state and writes it back into the same
  array; an idle slot costs a grid step and no bytes); a prefill lane leaves
  there the state after its chunk's last real row.
- `snap_h` [n_snapshots, H, P, N] and `snap_conv` [n_snapshots, K-1, CD]: a
  POOL of snapshots, far fewer than pool blocks (a snapshot a block, as the
  short convolutions keep them, would be gigabytes). `serving/kv_pager.py`
  owns the pool: a lane writes entry `snap_dst` with the state after its
  chunk's first `snap_rows` rows (the end of a prompt's last whole block may
  lie inside a chunk: the rows after it count as dead, one more state
  product), and a lane whose request was admitted onto a prefix hit starts
  from entry `snap_src` instead of its slot's state.

The lanes run the chunked (SSD) form over a chunk of Q positions, per head,
with `a_t = dt_t A`, `s_t = sum_{r<=t} a_r`:

    Y = ((C B^T) o L)(dt X) + exp(s) o (C h_in),  L[t, r] = exp(s_t - s_r), r <= t
    h_out = exp(s_Q) h_in + sum_r exp(s_Q - s_r) dt_r X_r (x) B_r

A dead row (the tail of a short chunk) has dt = 0: it leaves the state as it
is. Plain XLA products (`ssd_chunk`); the decode update is the kernel, with a
composite in `jax.numpy` for the CPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..framework.registry import register_op
from .decode_attention import _auto_backend
from .short_conv import conv_rows

KERNEL, COMPOSITE = "kernel", "composite"
_HI = jax.lax.Precision.HIGHEST


def decode_lowering(heads, head_dim, state, backend=None):
    backend = backend or _auto_backend()
    served = head_dim % 8 == 0 and state % 128 == 0
    if served and backend != "xla":
        return KERNEL
    if jax.default_backend() == "tpu" and backend != "xla":
        raise RuntimeError(
            f"ssm_decode_update: {heads} heads of {head_dim} x {state}: no "
            "kernel serves the shape, and the composite rewrites every "
            "slot's state: not a fallback on a TPU")
    return COMPOSITE


def _count(name, scope):
    """A set-up counter (a compiled tick records nothing), in the registry
    the flash kernels count in: `ssm/call`, a call of `scope`
    ("ssm_decode_update" | "ssd_chunk") at a call site, a layer of a tick
    program each, and `ssm/body_traced`, a trace of its body (the decode
    kernel is a jitted function: layers of one shape share one trace; the
    chunked form is plain XLA, traced where it is called)."""
    from ..observability import tracing
    tracing.record_counter(name, 1, scope=scope)


def _decode_composite(h, live, x, b, c, dt, decay):
    rep = h.shape[1] // b.shape[1]
    bh = jnp.repeat(b.astype(jnp.float32), rep, axis=1)          # [S,H,N]
    ch = jnp.repeat(c.astype(jnp.float32), rep, axis=1)
    xf = x.astype(jnp.float32)
    new = (decay[:, :, None, None] * h
           + (dt[:, :, None] * xf)[..., None] * bh[:, :, None, :])
    y = jnp.einsum("shpn,shn->shp", new, ch, precision=_HI)
    return y, jnp.where(live[:, None, None, None], new, h)


def _decode_kernel(order_ref, nlive_ref, h_ref, x_ref, b_ref, c_ref, dec_ref,
                   dt_ref, y_ref, o_ref, *, groups, rep, exact):
    from jax.experimental import pallas as pl

    step = pl.program_id(0)
    n_live = nlive_ref[0]
    P, N = h_ref.shape[2], h_ref.shape[3]
    eye = (jax.lax.broadcasted_iota(jnp.int32, (P, P), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (P, P), 1))
    low = jnp.bfloat16 if exact else jnp.float32

    def nt(a, bt):
        return jax.lax.dot_general(
            a, bt, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=None if exact else _HI)

    @pl.when((n_live == 0) & (step == 0))
    def _():
        # nothing is live: every step holds slot 0's block, which is written
        # back once, as it was
        o_ref[...] = h_ref[...]
        y_ref[...] = jnp.zeros(y_ref.shape, y_ref.dtype)

    @pl.when(step < n_live)
    def _():
        def group(g, carry):
            bb = jnp.broadcast_to(b_ref[0, pl.ds(g, 1), :], (P, N)).astype(low)
            cb = jnp.broadcast_to(c_ref[0, pl.ds(g, 1), :], (P, N)).astype(low)
            for k in range(rep):
                i = g * rep + k
                xr = x_ref[0, i]                                 # [1, P]
                diag = jnp.where(eye, jnp.broadcast_to(xr, (P, P)), 0.0)
                outer = jnp.dot(diag.astype(low), bb,
                                preferred_element_type=jnp.float32,
                                precision=None if exact else _HI)
                new = dec_ref[0, i] * h_ref[0, i] + dt_ref[0, i] * outer
                o_ref[0, i] = new
                if exact:
                    # C is a bfloat16 row: the state in two bfloat16 parts
                    # gives the product to 2**-16
                    hi = new.astype(low)
                    lo = (new - hi.astype(jnp.float32)).astype(low)
                    ybc = nt(hi, cb) + nt(lo, cb)
                else:
                    ybc = nt(new, cb)
                # every column of ybc is y: its diagonal is y as a row
                y_ref[0, i] = jnp.sum(jnp.where(eye, ybc, 0.0), axis=0,
                                      keepdims=True)
            return carry
        jax.lax.fori_loop(0, groups, group, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _decode_pallas(h, live, x, b, c, dt, decay, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    _count("ssm/body_traced", "ssm_decode_update")
    S, H, P, N = h.shape
    G = b.shape[1]
    exact = x.dtype == jnp.bfloat16
    live = live.astype(jnp.int32)
    n_live = jnp.sum(live).astype(jnp.int32)
    # live slots first, in order; the steps after them hold the last live
    # slot's block (no bytes move, nothing is computed)
    order = jnp.argsort(1 - live, stable=True).astype(jnp.int32)
    order = jnp.where(jnp.arange(S) < n_live, order,
                      order[jnp.maximum(n_live - 1, 0)])
    order = jnp.where(n_live > 0, order, 0)

    def row(s, order_ref, nlive_ref):
        return order_ref[s]

    spec4 = lambda last: pl.BlockSpec(  # noqa: E731
        (1, H) + last, lambda s, *r: (row(s, *r), 0, 0, 0))
    spec3 = lambda shape: pl.BlockSpec(  # noqa: E731
        (1,) + shape, lambda s, *r: (row(s, *r), 0, 0))
    lanes = lambda t: jnp.broadcast_to(  # noqa: E731
        t.astype(jnp.float32)[:, :, None, None], (S, H, 1, N))
    kernel = functools.partial(_decode_kernel, groups=G, rep=H // G,
                               exact=exact)
    with jax.named_scope("ssm_decode_update"):
        y, h = pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(S,),
                in_specs=[spec4((P, N)), spec4((1, P)), spec3((G, N)),
                          spec3((G, N)), spec4((1, N)), spec4((1, N))],
                out_specs=[spec4((1, P)), spec4((P, N))]),
            out_shape=[jax.ShapeDtypeStruct((S, H, 1, P), jnp.float32),
                       jax.ShapeDtypeStruct(h.shape, h.dtype)],
            input_output_aliases={2: 1},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=100 * 1024 * 1024),
            interpret=interpret,
        )(order, n_live.reshape(1), h,
          x.astype(jnp.float32).reshape(S, H, 1, P), b.astype(jnp.float32),
          c.astype(jnp.float32), lanes(decay), lanes(dt))
    # a row the kernel did not visit holds whatever its buffer held
    y = jnp.where(live[:, None, None] > 0, y.reshape(S, H, P), 0.0)
    return y, h


def ssm_decode_update(h, live, x, b, c, dt, decay, backend=None):
    """One decode step of every LIVE slot. h [S, H, P, N] float32 (updated
    in place by the kernel); live [S] (> 0: the slot fed a decode row); x
    [S, H, P]; b, c [S, G, N]; dt, decay = exp(dt A) [S, H] float32 ->
    (y [S, H, P] float32 without the D x term, h)."""
    live = live.reshape(-1) > 0
    if decode_lowering(h.shape[1], h.shape[2], h.shape[3],
                       backend) == KERNEL:
        _count("ssm/call", "ssm_decode_update")
        return _decode_pallas(h, live, x, b, c, dt, decay,
                              interpret=backend == "pallas_interpret")
    return _decode_composite(h, live, x, b, c, dt, decay)


def ssd_chunk(h_in, x, b, c, dt, a, snap_rows=None):
    """The chunked form over one chunk a lane. h_in [L, H, P, N] float32; x
    [L, Q, H, P]; b, c [L, Q, G, N]; dt [L, Q, H] float32, 0 on dead rows; a
    [H] float32 -> (y [L, Q, H, P] float32 without the D x term, h_out, and
    with `snap_rows` [L] the state after each lane's first `snap_rows`
    rows)."""
    _count("ssm/call", "ssd_chunk")
    _count("ssm/body_traced", "ssd_chunk")
    with jax.named_scope("ssd_chunk"):
        L, Q, H, _ = x.shape
        rep = H // b.shape[2]
        f32 = jnp.float32
        s = jnp.cumsum(dt * a, axis=1)                           # [L,Q,H]
        bh = jnp.repeat(b.astype(f32), rep, axis=2)              # [L,Q,H,N]
        ch = jnp.repeat(c.astype(f32), rep, axis=2)
        dtx = dt[..., None] * x.astype(f32)                      # [L,Q,H,P]
        cb = jnp.einsum("lthn,lrhn->lhtr", ch, bh, precision=_HI)
        t = jnp.arange(Q)
        diff = s.transpose(0, 2, 1)[:, :, :, None] \
            - s.transpose(0, 2, 1)[:, :, None, :]                # s_t - s_r
        decay = jnp.where(t[:, None] >= t[None, :], jnp.exp(diff), 0.0)
        y = jnp.einsum("lhtr,lrhp->lthp", cb * decay, dtx, precision=_HI)
        y = y + jnp.exp(s)[..., None] * jnp.einsum(
            "lthn,lhpn->lthp", ch, h_in, precision=_HI)

        def state(dtx_m, s_m):
            """The state after the rows `dtx_m` keeps, `s_m` their sums."""
            end = s_m[:, -1]                                     # [L,H]
            w = jnp.exp(end[:, None] - s_m)                      # [L,Q,H]
            return (jnp.exp(end)[..., None, None] * h_in
                    + jnp.einsum("lrhp,lrhn->lhpn", w[..., None] * dtx_m, bh,
                                 precision=_HI))

        h_out = state(dtx, s)
        if snap_rows is None:
            return y, h_out, None
        keep = (t[None, :] < snap_rows.reshape(-1, 1))[..., None]
        dt_m = jnp.where(keep, dt, 0.0)
        return y, h_out, state(dt_m[..., None] * x.astype(f32),
                               jnp.cumsum(dt_m * a, axis=1))


def _take(arr, idx):
    return jax.lax.dynamic_index_in_dim(arr, idx, 0, keepdims=False)


def _put(arr, idx, new, on):
    """arr[idx] = new where `on`, in place (idx clamped by the caller)."""
    old = _take(arr, idx)
    return jax.lax.dynamic_update_index_in_dim(
        arr, jnp.where(on, new.astype(arr.dtype), old), idx, 0)


def _lane_ints(lanes):
    """A mixed tick's lane feeds as flat int32 vectors: lpos, lrows, lslot,
    snap_src, snap_dst, snap_rows."""
    return tuple(t.reshape(-1).astype(jnp.int32) for t in lanes[2:8])


def lanes_start(lanes, slot_h, slot_conv):
    """The state each lane's chunk starts from, for any layer that keeps a
    slot state and a snapshot pool (`ssm_scan`, `kda.kda_scan`): a snapshot
    (`snap_src` >= 0), the slot's own (an earlier chunk of the request left
    it), zeros at position 0 -> ((lrows, snap_rows), h_in, conv_in)."""
    snap_h, snap_conv = lanes[:2]
    lpos, lrows, lslot, src, _, snap_rows = _lane_ints(lanes)
    from_snap, resumed = src >= 0, lpos > 0
    pick = lambda snap, slot: jnp.stack([jnp.where(  # noqa: E731
        from_snap[i], _take(snap, jnp.maximum(src[i], 0)),
        jnp.where(resumed[i], _take(slot, lslot[i]), 0).astype(snap.dtype))
        for i in range(lrows.shape[0])])
    return ((lrows, snap_rows), pick(snap_h, slot_h),
            pick(snap_conv, slot_conv))


def lanes_commit(lanes, slot_h, slot_conv, ext, r, h_out, h_snap):
    """What the lanes leave, in place: in each lane's slot the state after
    its last real row (`h_out`, and the `r` rows of `ext`, the convolution's
    input behind its state rows, that end there), in the pool entry
    `snap_dst` (>= 0) the state after its first `snap_rows` rows (`h_snap`)
    -> (slot_h, slot_conv, snap_h, snap_conv)."""
    snap_h, snap_conv = lanes[:2]
    _, lrows, lslot, _, dst, snap_rows = _lane_ints(lanes)
    rows_at = lambda n: jax.vmap(  # noqa: E731
        lambda e, k: jax.lax.dynamic_slice_in_dim(e, k, r, 0))(ext, n)
    conv_out, conv_snap = rows_at(lrows), rows_at(snap_rows)
    for i in range(lrows.shape[0]):
        fed, at = lrows[i] > 0, jnp.maximum(dst[i], 0)
        slot_h = _put(slot_h, lslot[i], h_out[i], fed)
        slot_conv = _put(slot_conv, lslot[i], conv_out[i], fed)
        snap_h = _put(snap_h, at, h_snap[i], dst[i] >= 0)
        snap_conv = _put(snap_conv, at, conv_snap[i], dst[i] >= 0)
    return slot_h, slot_conv, snap_h, snap_conv


def ssm_scan(xbc, dt_raw, taps, conv_bias, a_log, dt_bias, d_skip, slot_h,
             slot_conv, live, spec, lanes=None, backend=None):
    """One state-space layer's convolution and scan over a tick's rows.

    xbc [S + L*C, CD] and dt_raw [S + L*C, H] (S decode rows, then L lanes
    of C rows); `spec` (heads, head_dim, groups, state); `lanes` None (a
    decode tick) or (snap_h, snap_conv, lpos [L], lrows [L], lslot [L],
    snap_src [L] (-1: the slot's own state, zeros at position 0), snap_dst
    [L] (-1: none), snap_rows [L], chunk). Returns (y [S + L*C, H*P] in
    xbc's dtype, slot_h, slot_conv, and with lanes snap_h, snap_conv), the
    arrays updated in place."""
    H, P, G, N = spec
    S, dtype, f32 = slot_h.shape[0], xbc.dtype, jnp.float32
    r = taps.shape[1] - 1
    a = -jnp.exp(a_log.astype(f32))
    bias = conv_bias.astype(f32)

    def split(u):
        """silu(conv + bias), rounded as the activations are -> x, B, C."""
        u = jax.nn.silu(u + bias).astype(dtype)
        lead = u.shape[:-1]
        return (u[..., :H * P].reshape(lead + (H, P)),
                u[..., H * P:H * P + G * N].reshape(lead + (G, N)),
                u[..., H * P + G * N:].reshape(lead + (G, N)))

    def step_size(raw):
        return jax.nn.softplus(raw.astype(f32) + dt_bias.astype(f32))

    alive = live.reshape(-1) > 0
    ext = jnp.concatenate([slot_conv, xbc[:S, None]], axis=1)
    x, b, c = split(conv_rows(ext, taps, 1)[:, 0])
    dt = step_size(dt_raw[:S])
    y, slot_h = ssm_decode_update(slot_h, live, x, b, c, dt,
                                  jnp.exp(dt * a), backend)
    y = (y + d_skip.astype(f32)[:, None] * x.astype(f32)).reshape(S, H * P)
    slot_conv = jnp.where(alive[:, None, None], ext[:, 1:], slot_conv)
    if lanes is None:
        return y.astype(dtype), slot_h, slot_conv, None, None
    chunk = lanes[-1]
    (lrows, snap_rows), h_in, conv_in = lanes_start(lanes, slot_h, slot_conv)
    L = lrows.shape[0]
    ul = xbc[S:].reshape(L, chunk, -1)
    ext_l = jnp.concatenate([conv_in.astype(dtype), ul], axis=1)
    xl, bl, cl = split(conv_rows(ext_l, taps, chunk))
    real = jnp.arange(chunk)[None, :] < lrows[:, None]
    dt_l = jnp.where(real[..., None],
                     step_size(dt_raw[S:]).reshape(L, chunk, H), 0.0)
    y_l, h_out, h_snap = ssd_chunk(h_in, xl, bl, cl, dt_l, a, snap_rows)
    y_l = y_l + d_skip.astype(f32)[:, None] * xl.astype(f32)
    y = jnp.concatenate([y, y_l.reshape(L * chunk, H * P)], axis=0)
    return (y.astype(dtype),) + lanes_commit(lanes, slot_h, slot_conv, ext_l,
                                             r, h_out, h_snap)


def gated_rms_norm(y, z, scale, groups, eps):
    """RMSNorm over each of `groups` groups of (y * silu(z)), the gate first,
    with a learned scale a value; float32 inside, y's dtype out."""
    f32 = jnp.float32
    g = y.astype(f32) * jax.nn.silu(z.astype(f32))
    lead = g.shape[:-1]
    gg = g.reshape(lead + (groups, -1))
    gg = gg * jax.lax.rsqrt(jnp.mean(gg * gg, axis=-1, keepdims=True) + eps)
    return (gg.reshape(g.shape) * scale.astype(f32)).astype(y.dtype)


@register_op("ssm_scan", stop_gradient=True)
def _ssm_scan_op(ctx, ins, attrs):
    xbc, dt = ins["XBC"][0], ins["Dt"][0]
    lanes = None
    if ins.get("SnapH"):
        lanes = (ins["SnapH"][0], ins["SnapConv"][0], ins["LanePos"][0],
                 ins["LaneRows"][0], ins["LaneSlot"][0], ins["SnapSrc"][0],
                 ins["SnapDst"][0], ins["SnapRows"][0], attrs["chunk"])
    y, slot_h, slot_conv, snap_h, snap_conv = ssm_scan(
        xbc.reshape(-1, xbc.shape[-1]), dt.reshape(-1, dt.shape[-1]),
        ins["Taps"][0], ins["ConvBias"][0], ins["ALog"][0], ins["DtBias"][0],
        ins["D"][0], ins["SlotH"][0], ins["SlotConv"][0], ins["Live"][0],
        (attrs["heads"], attrs["head_dim"], attrs["groups"], attrs["state"]),
        lanes, backend=attrs.get("backend"))
    out = {"Out": [y.reshape(xbc.shape[:-1] + (y.shape[-1],))],
           "SlotHOut": [slot_h], "SlotConvOut": [slot_conv]}
    if snap_h is not None:
        out["SnapHOut"], out["SnapConvOut"] = [snap_h], [snap_conv]
    return out


@register_op("gated_rms_norm", stop_gradient=True)
def _gated_rms_norm_op(ctx, ins, attrs):
    return {"Out": [gated_rms_norm(ins["X"][0], ins["Z"][0], ins["Scale"][0],
                                   attrs["groups"], attrs["epsilon"])]}
