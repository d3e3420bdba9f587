"""The channel-wise gated delta-rule mixer's state path over a tick's rows
(Kimi Delta Attention, arXiv:2510.26692), with the state a request carries
beside its per-token cache rows.

The mixer (`models/transformer.py _kda_mixer`), H heads of d_k = d_v = D:

    q = silu(conv(u Wq)), k = silu(conv(u Wk)), v = silu(conv(u Wv)): a causal
        depthwise convolution of K taps, no bias, whose state is the last K-1
        rows of its input (`short_conv.conv_rows`), over [q | k | v]
    q_h = q_h / |q_h| * D^-1/2,  k_h = k_h / |k_h|     (eps 1e-6 under the root)
    g = lower_bound * sigmoid(exp(A_log_h) * (u Wf + dt_bias))   a value a KEY
        CHANNEL, in (lower_bound, 0): the log of that channel's decay
    beta = sigmoid(u Wb)                                         a value a head

    S~  = Diag(exp(g_t)) S_{t-1}          (a head's S is [D keys, D values])
    S_t = S~ + beta_t k_t (v_t - S~^T k_t)^T
    o_t = S_t^T q_t

then `W_o (RMSNorm_head(o) * sigmoid(u W_g))` (`gate_norm`). Everything but
the convolution and `S` is row-wise. `S` [H, D, D] in float32 (2 MB a layer at
32 x 128 x 128) and the conv rows are a request's STATE, whatever its length;
the four persistable arrays a layer are `fusion/ssm.py`'s (`slot_h`,
`slot_conv`, `snap_h`, `snap_conv`; `_RecurrentState` declares them, and
`serving/kv_pager.py` owns the snapshot pool): a decode row updates its slot's
`S` IN PLACE (`kda_decode_update`: one Pallas call over the LIVE slots), a
prefill lane starts from its slot's state, from a snapshot or from zeros,
leaves the state after its chunk's last real row in its slot and the state
after its first `snap_rows` rows in the pool.

The lanes run the CHUNKED form over a chunk of C rows, per head. With `G_r`
the running sum of g over the chunk's rows (G_r <= 0, a vector a row), the
recurrence unrolls to S_t = Diag(exp(G_t)) S_0 + sum_{s<=t} (k_s * exp(G_t -
G_s)) v'_s^T, where v'_t = beta_t (v_t - S~_t^T k_t) is what row t writes.
Putting S~_t = Diag(exp(G_t)) S_0 + sum_{s<t} (k_s * exp(G_t - G_s)) v'_s^T
into v'_t gives, for all rows at once,

    (I + A) V' = beta * V - (beta * K * exp(G)) S_0,
        A_ts = beta_t sum_c k_tc k_sc exp(G_tc - G_sc) for s < t, else 0
    o_t  = (q_t * exp(G_t))^T S_0 + sum_{s<=t} [sum_c q_tc k_sc exp(G_tc - G_sc)] v'_s
    S_C  = Diag(exp(G_C)) S_0 + sum_s (k_s * exp(G_C - G_s)) v'_s^T

ONE unit-lower-triangular solve a chunk and head. Every exponent that appears
is <= 0. The products over c run on the MXU in blocks of `_SUB` = 16 rows t:
exp(G_t - G_s) = exp(G_t - R) exp(R - G_s) with R the sum at the block's
MIDDLE row. For every s before the block the second factor is <= 1; inside the
block both exponents lie within 8 * |lower_bound| = 40 at the bound of -5, and
e^40 and e^-40 times a component of a unit vector are far inside float32.
(Factored from the block's FIRST row the exponents reach 80: e^80 fits, but
e^-80 = 1.8e-35 times a component of 1e-3 is a denormal that the hardware
flushes, and its partner is e^75 large: at the bound that read 3e-5 off the
recurrence.) This is what the bounded gate buys, and `chunk_lowering` refuses
a bound it does not cover. The solve is by forward substitution: the 16 x 16 diagonal blocks'
inverses row by row (all blocks at once), then block by block. A dead row (the
tail of a short chunk) has g = 0 and beta = 0: it decays nothing, writes
nothing. Plain XLA products (`kda_chunk`); the decode update is the kernel,
with a composite in `jax.numpy` for the CPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..framework.registry import register_op
from .decode_attention import _auto_backend
from .short_conv import conv_rows
from .ssm import lanes_commit, lanes_start

KERNEL, COMPOSITE = "kernel", "composite"
_HI = jax.lax.Precision.HIGHEST
_SUB = 16               # rows of a sub-block of the chunked form
_QK_EPS = 1e-6


def decode_lowering(heads, head_dim, backend=None):
    backend = backend or _auto_backend()
    served = head_dim % 128 == 0
    if served and backend != "xla":
        return KERNEL
    if jax.default_backend() == "tpu" and backend != "xla":
        raise RuntimeError(
            f"kda_decode_update: {heads} heads of {head_dim} x {head_dim}: no "
            "kernel serves the shape, and the composite rewrites every "
            "slot's state: not a fallback on a TPU")
    return COMPOSITE


def chunk_lowering(lower_bound):
    """The chunked form factors a decay over half of `_SUB` rows each way:
    exp(+-_SUB / 2 * |bound|) times a unit vector's component has to stay
    inside float32's normal range."""
    reach = _SUB // 2 * abs(float(lower_bound))
    if reach > 40.0:
        raise NotImplementedError(
            f"kda_chunk: a gate bound of {lower_bound} over sub-blocks of "
            f"{_SUB} rows leaves float32 (e^-{reach:.0f} times a small "
            "component is flushed)")


def _count(name, scope):
    """A set-up counter, as `fusion/ssm.py` counts: `kda/call`, a call of
    `scope` ("kda_decode" | "kda_chunk") at a call site, a layer of a tick
    program each, and `kda/body_traced`, a trace of its body."""
    from ..observability import tracing
    tracing.record_counter(name, 1, scope=scope)


def _decode_composite(s, live, q, k, v, g, beta):
    f32 = jnp.float32
    q, k, v = q.astype(f32), k.astype(f32), v.astype(f32)
    decayed = jnp.exp(g)[..., None] * s                          # [S,H,K,V]
    held = jnp.einsum("shkv,shk->shv", decayed, k, precision=_HI)
    new = decayed + k[..., None] * (beta[..., None] * (v - held))[:, :, None]
    o = jnp.einsum("shkv,shk->shv", new, q, precision=_HI)
    return o, jnp.where(live[:, None, None, None], new, s)


def _decode_kernel(order_ref, nlive_ref, s_ref, cols_ref, v_ref, o_ref,
                   so_ref, *, heads):
    """One live slot a step. The state of a head is [K sublanes, V lanes];
    q, k, beta * k and exp(g) come TRANSPOSED in one operand, [K, 4 * heads]
    (a head's column of each at h, heads + h, ..), so that each broadcasts
    over the lanes; v and o are rows."""
    from jax.experimental import pallas as pl

    step = pl.program_id(0)
    n_live = nlive_ref[0]

    @pl.when((n_live == 0) & (step == 0))
    def _():
        # nothing is live: every step holds slot 0's block, which is written
        # back once, as it was
        so_ref[...] = s_ref[...]
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    @pl.when(step < n_live)
    def _():
        for h in range(heads):
            q, k, kb, decay = (cols_ref[0, :, j * heads + h:j * heads + h + 1]
                               for j in range(4))                # [K, 1]
            decayed = decay * s_ref[0, h]                        # [K, V]
            held = jnp.sum(k * decayed, axis=0, keepdims=True)
            new = decayed + kb * (v_ref[0, h:h + 1, :] - held)
            so_ref[0, h] = new
            o_ref[0, h:h + 1, :] = jnp.sum(q * new, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _decode_pallas(s, live, q, k, v, g, beta, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    _count("kda/body_traced", "kda_decode")
    S, H, K, V = s.shape
    f32 = jnp.float32
    live = live.astype(jnp.int32)
    n_live = jnp.sum(live).astype(jnp.int32)
    # live slots first, in order; the steps after them hold the last live
    # slot's block (no bytes move, nothing is computed)
    order = jnp.argsort(1 - live, stable=True).astype(jnp.int32)
    order = jnp.where(jnp.arange(S) < n_live, order,
                      order[jnp.maximum(n_live - 1, 0)])
    order = jnp.where(n_live > 0, order, 0)

    def row(i, order_ref, nlive_ref):
        return order_ref[i]

    state = pl.BlockSpec((1, H, K, V), lambda i, *r: (row(i, *r), 0, 0, 0))
    cols = pl.BlockSpec((1, K, 4 * H), lambda i, *r: (row(i, *r), 0, 0))
    rows = pl.BlockSpec((1, H, V), lambda i, *r: (row(i, *r), 0, 0))
    kf = k.astype(f32)
    columns = jnp.concatenate(
        [q.astype(f32), kf, beta[..., None] * kf, jnp.exp(g)],
        axis=1).transpose(0, 2, 1)                               # [S,K,4H]
    with jax.named_scope("kda_decode"):
        o, s = pl.pallas_call(
            functools.partial(_decode_kernel, heads=H),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(S,),
                in_specs=[state, cols, rows],
                out_specs=[rows, state]),
            out_shape=[jax.ShapeDtypeStruct((S, H, V), f32),
                       jax.ShapeDtypeStruct(s.shape, s.dtype)],
            input_output_aliases={2: 1},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=100 * 1024 * 1024),
            interpret=interpret,
        )(order, n_live.reshape(1), s, columns, v.astype(f32))
    # a row the kernel did not visit holds whatever its buffer held
    return jnp.where(live[:, None, None] > 0, o, 0.0), s


def kda_decode_update(s, live, q, k, v, g, beta, backend=None):
    """One decode step of every LIVE slot. s [S, H, K, V] float32 (updated in
    place by the kernel); live [S] (> 0: the slot fed a decode row); q, k
    [S, H, K] (normalised); v [S, H, V]; g [S, H, K] float32 (log decays);
    beta [S, H] float32 -> (o [S, H, V] float32, s)."""
    live = live.reshape(-1) > 0
    if decode_lowering(s.shape[1], s.shape[2], backend) == KERNEL:
        _count("kda/call", "kda_decode")
        return _decode_pallas(s, live, q, k, v, g, beta,
                              interpret=backend == "pallas_interpret")
    return _decode_composite(s, live, q, k, v, g, beta)


def kda_chunk(s_in, q, k, v, g, beta, snap_rows=None):
    """The chunked form over one chunk a lane (the module docstring derives
    it). s_in [L, H, K, V] float32; q, k [L, C, H, K] (normalised); v
    [L, C, H, V]; g [L, C, H, K] float32 and beta [L, C, H] float32, both 0
    on dead rows -> (o [L, C, H, V] float32, s_out, and with `snap_rows` [L]
    the state after each lane's first `snap_rows` rows)."""
    _count("kda/call", "kda_chunk")
    _count("kda/body_traced", "kda_chunk")
    with jax.named_scope("kda_chunk"):
        f32 = jnp.float32
        L, C, H, K = k.shape
        nb = -(-C // _SUB)
        pad = nb * _SUB - C
        if pad:     # dead rows: g = 0 and beta = 0 decay and write nothing
            q, k, v, g, beta = (jnp.pad(
                x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))
                for x in (q, k, v, g, beta))
        Cp = nb * _SUB
        # heads outermost: [L, H, C, .]
        q, k, v, g = (x.astype(f32).transpose(0, 2, 1, 3)
                      for x in (q, k, v, g))
        beta = beta.astype(f32).transpose(0, 2, 1)               # [L,H,C]
        G = jnp.cumsum(g, axis=2)                                # [L,H,C,K]
        # the sum at the middle row of each row's block
        mid = _SUB // 2 - 1
        Gb = G.reshape(L, H, nb, _SUB, K)[:, :, :, mid:mid + 1]  # [L,H,nb,1,K]
        up = jnp.exp(G.reshape(L, H, nb, _SUB, K) - Gb)
        k_up = k.reshape(L, H, nb, _SUB, K) * up
        q_up = q.reshape(L, H, nb, _SUB, K) * up
        # k_s * exp(G_b' - G_s) for every block b and every row s up to the
        # block's end; the rows after it are masked BEFORE the exponential
        t = jnp.arange(Cp)
        seen = t[None, :] < (jnp.arange(nb)[:, None] + 1) * _SUB  # [nb,C]
        down = jnp.exp(jnp.where(seen[None, None, :, :, None],
                                 Gb - G[:, :, None], -jnp.inf))
        k_down = k[:, :, None] * down                            # [L,H,nb,C,K]
        lower = (t[:, None] >= t[None, :])                       # s <= t
        kk = jnp.einsum("lhbik,lhbsk->lhbis", k_up, k_down,
                        precision=_HI).reshape(L, H, Cp, Cp)
        qk = jnp.einsum("lhbik,lhbsk->lhbis", q_up, k_down,
                        precision=_HI).reshape(L, H, Cp, Cp)
        A = jnp.where(t[:, None] > t[None, :], beta[..., None] * kk, 0.0)
        P = jnp.where(lower, qk, 0.0)
        eG = jnp.exp(G)                                          # [L,H,C,K]
        rhs = beta[..., None] * (v - jnp.einsum(
            "lhtk,lhkv->lhtv", k * eG, s_in, precision=_HI))
        # (I + A) V' = rhs. The diagonal blocks' inverses, row by row
        Ab = A.reshape(L, H, nb, _SUB, nb, _SUB)
        diag = jnp.stack([Ab[:, :, b, :, b] for b in range(nb)], axis=2)
        inv = jnp.zeros_like(diag)
        eye = jnp.eye(_SUB, dtype=f32)
        for r in range(_SUB):
            inv = inv.at[..., r, :].set(eye[r] - jnp.einsum(
                "lhbs,lhbsc->lhbc", diag[..., r, :], inv, precision=_HI))
        # ... then block by block
        vp = jnp.zeros_like(rhs)
        for b in range(nb):
            lo, hi = b * _SUB, (b + 1) * _SUB
            w = rhs[:, :, lo:hi] - jnp.einsum(
                "lhts,lhsv->lhtv", A[:, :, lo:hi, :lo], vp[:, :, :lo],
                precision=_HI) if b else rhs[:, :, lo:hi]
            vp = vp.at[:, :, lo:hi].set(jnp.einsum(
                "lhts,lhsv->lhtv", inv[:, :, b], w, precision=_HI))
        o = jnp.einsum("lhtk,lhkv->lhtv", q * eG, s_in, precision=_HI) \
            + jnp.einsum("lhts,lhsv->lhtv", P, vp, precision=_HI)

        def state(G_m, vp_m):
            """The state after the rows `vp_m` keeps, `G_m` their sums."""
            end = G_m[:, :, -1:]                                 # [L,H,1,K]
            return (jnp.exp(end)[:, :, 0, :, None] * s_in
                    + jnp.einsum("lhsk,lhsv->lhkv", k * jnp.exp(end - G_m),
                                 vp_m, precision=_HI))

        o = o[:, :, :C].transpose(0, 2, 1, 3)
        s_out = state(G, vp)
        if snap_rows is None:
            return o, s_out, None
        keep = (t[None, :] < snap_rows.reshape(-1, 1))[:, None, :, None]
        return o, s_out, state(jnp.cumsum(jnp.where(keep, g, 0.0), axis=2),
                               jnp.where(keep, vp, 0.0))


def kda_scan(qkv, f_raw, b_raw, taps, a_log, dt_bias, slot_s, slot_conv, live,
             spec, lanes=None, backend=None):
    """One kda layer's convolution and delta-rule scan over a tick's rows.

    qkv [S + L*C, 3*H*D], f_raw [S + L*C, H*D] and b_raw [S + L*C, H] (S
    decode rows, then L lanes of C rows); `spec` (heads, head_dim,
    gate_lower_bound); `lanes` as `ssm.ssm_scan` takes them. Returns (o
    [S + L*C, H*D] in qkv's dtype, slot_s, slot_conv, and with lanes snap_s,
    snap_conv), the arrays updated in place."""
    H, D, bound = spec
    S, dtype, f32 = slot_s.shape[0], qkv.dtype, jnp.float32
    r = taps.shape[1] - 1
    decay_rate = jnp.exp(a_log.astype(f32))[:, None]             # [H,1]

    def split(u):
        """silu(conv), rounded as the activations are -> q, k (normalised,
        float32), v."""
        u = jax.nn.silu(u).astype(dtype)
        lead = u.shape[:-1]
        q, k, v = (u[..., j * H * D:(j + 1) * H * D].reshape(lead + (H, D))
                   for j in range(3))

        def unit(x):
            x = x.astype(f32)
            return x * jax.lax.rsqrt(
                jnp.sum(x * x, axis=-1, keepdims=True) + _QK_EPS)
        return unit(q) * D ** -0.5, unit(k), v

    def gates(f, b):
        lead = f.shape[:-1]
        f = (f.astype(f32) + dt_bias.astype(f32)).reshape(lead + (H, D))
        return (bound * jax.nn.sigmoid(decay_rate * f),
                jax.nn.sigmoid(b.astype(f32)))

    alive = live.reshape(-1) > 0
    ext = jnp.concatenate([slot_conv, qkv[:S, None]], axis=1)
    q, k, v = split(conv_rows(ext, taps, 1)[:, 0])
    g, beta = gates(f_raw[:S], b_raw[:S])
    o, slot_s = kda_decode_update(slot_s, live, q, k, v, g, beta, backend)
    o = o.reshape(S, H * D)
    slot_conv = jnp.where(alive[:, None, None], ext[:, 1:], slot_conv)
    if lanes is None:
        return o.astype(dtype), slot_s, slot_conv, None, None
    chunk_lowering(bound)
    chunk = lanes[-1]
    (lrows, snap_rows), s_in, conv_in = lanes_start(lanes, slot_s, slot_conv)
    L = lrows.shape[0]
    ul = qkv[S:].reshape(L, chunk, -1)
    ext_l = jnp.concatenate([conv_in.astype(dtype), ul], axis=1)
    ql, kl, vl = split(conv_rows(ext_l, taps, chunk))
    real = jnp.arange(chunk)[None, :] < lrows[:, None]
    g_l, beta_l = gates(f_raw[S:].reshape(L, chunk, -1),
                        b_raw[S:].reshape(L, chunk, -1))
    g_l = jnp.where(real[..., None, None], g_l, 0.0)
    beta_l = jnp.where(real[..., None], beta_l, 0.0)
    o_l, s_out, s_snap = kda_chunk(s_in, ql, kl, vl, g_l, beta_l, snap_rows)
    o = jnp.concatenate([o, o_l.reshape(L * chunk, H * D)], axis=0)
    return (o.astype(dtype),) + lanes_commit(lanes, slot_s, slot_conv, ext_l,
                                             r, s_out, s_snap)


def gate_norm(o, gate, scale, heads, eps):
    """RMSNorm over each head of `o` with ONE learned scale a head value
    (shared by the heads), times sigmoid(gate), a value a channel; float32
    inside, o's dtype out."""
    f32 = jnp.float32
    lead = o.shape[:-1]
    oh = o.astype(f32).reshape(lead + (heads, -1))
    oh = oh * jax.lax.rsqrt(jnp.mean(oh * oh, axis=-1, keepdims=True) + eps)
    oh = oh * scale.astype(f32)
    return (oh.reshape(o.shape)
            * jax.nn.sigmoid(gate.astype(f32))).astype(o.dtype)


def head_gate(ctx, gate, heads):
    """A head's output times sigmoid of the head's one gate value: ctx
    [.., heads * d], gate [.., heads]; float32 inside, ctx's dtype out."""
    f32 = jnp.float32
    lead = ctx.shape[:-1]
    ch = ctx.astype(f32).reshape(lead + (heads, -1))
    return (ch * jax.nn.sigmoid(gate.astype(f32))[..., None]) \
        .reshape(ctx.shape).astype(ctx.dtype)


@register_op("kda_scan", stop_gradient=True)
def _kda_scan_op(ctx, ins, attrs):
    qkv = ins["QKV"][0]
    lanes = None
    if ins.get("SnapH"):
        lanes = (ins["SnapH"][0], ins["SnapConv"][0], ins["LanePos"][0],
                 ins["LaneRows"][0], ins["LaneSlot"][0], ins["SnapSrc"][0],
                 ins["SnapDst"][0], ins["SnapRows"][0], attrs["chunk"])
    flat = lambda t: t.reshape(-1, t.shape[-1])  # noqa: E731
    o, slot_s, slot_conv, snap_s, snap_conv = kda_scan(
        flat(qkv), flat(ins["F"][0]), flat(ins["B"][0]), ins["Taps"][0],
        ins["ALog"][0], ins["DtBias"][0], ins["SlotH"][0], ins["SlotConv"][0],
        ins["Live"][0],
        (attrs["heads"], attrs["head_dim"], attrs["gate_lower_bound"]),
        lanes, backend=attrs.get("backend"))
    out = {"Out": [o.reshape(qkv.shape[:-1] + (o.shape[-1],))],
           "SlotHOut": [slot_s], "SlotConvOut": [slot_conv]}
    if snap_s is not None:
        out["SnapHOut"], out["SnapConvOut"] = [snap_s], [snap_conv]
    return out


@register_op("kda_gate_norm", stop_gradient=True)
def _kda_gate_norm_op(ctx, ins, attrs):
    return {"Out": [gate_norm(ins["X"][0], ins["Gate"][0], ins["Scale"][0],
                              attrs["heads"], attrs["epsilon"])]}


@register_op("head_gate", stop_gradient=True)
def _head_gate_op(ctx, ins, attrs):
    return {"Out": [head_gate(ins["X"][0], ins["Gate"][0], attrs["heads"])]}
