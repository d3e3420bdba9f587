"""Fused recurrent cells: the WHOLE LSTM/GRU recurrence as one Pallas kernel.

≙ reference operators/fusion_lstm_op.cc / fusion_gru_op.cc — the reference's
answer to the small-step problem: per-tick gate math fused into one kernel
instead of a chain of BLAS + elementwise launches. TPU translation goes one
step further: the kernel's grid iterates (batch-block, time) with the
hidden/cell state held in VMEM scratch across the sequential time steps
(TPU grid semantics, same mechanism as the flash kernel's online-softmax
accumulators), so the ENTIRE sequence is a single kernel launch — no
per-tick dispatch at all. The kernel sees the sequence TIME-MAJOR
([T, B, G*H], one (1, bb, G*H) block per step): Mosaic tiles a block's
last two dims, and (bb, G*H) is (8k, 128m)-legal where the batch-major
(bb, 1, G*H) block is not. The [B, T, 4H] input projections are computed
once outside (one big MXU matmul, exactly as `dynamic_lstm` already does);
what the kernel fuses is everything the unfused `lax.scan` body dispatched
per tick: the [H, 4H] recurrent matmul, four activations, the state update
and the sequence-length freeze.

Gradients: `jax.custom_vjp` with a manual reverse-time `lax.scan` against
gate activations stashed by the forward kernel — exact LSTM/GRU backward
(the math `jax.vjp` would derive from the unfused scan), so the fused ops
are drop-in for training graphs.

Gate orders match `ops/sequence_ops.py` exactly: LSTM (i, f, c_hat, o) on a
[H, 4H] recurrent weight, GRU (r, z | c) on [H, 3H] split as
w[:, :2H] / w[:, 2H:]. Sequence masking freezes state for finished rows
(`tpos < seqlen`), identical to the unfused lowerings.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..framework.registry import register_op

# batch rows per grid step (the VMEM gate below bounds bb x gate lanes)
_MAX_BATCH_BLOCK = 512
# the kernel's blocks must fit the compiler's default scoped-VMEM limit
# (16 MiB on a v5e) with room for the gate temporaries
_VMEM_BUDGET_BYTES = 12 << 20


def _auto_backend():
    from ..ops.pallas_kernels import _auto_backend as _ab
    return _ab()


def _batch_block(b):
    return min(_round_up(b, 8), _MAX_BATCH_BLOCK)


def _vmem_bytes(b, hidden, n_gates):
    """f32 bytes the whole-sequence kernel holds in VMEM per grid step:
    every input/output block twice (Pallas double-buffers them, the
    grid-invariant recurrent weight included) plus the state scratch."""
    bb = _batch_block(b)
    gh = n_gates * hidden
    n_states = 2 if n_gates == 4 else 1
    blocks = (bb * gh            # x step
              + bb * 128         # seqlen, lane-broadcast
              + n_states * bb * hidden   # h0[, c0]
              + hidden * gh      # recurrent weight
              + n_states * bb * hidden   # hs[, cs] step
              + bb * gh)         # gate stash step
    return 4 * (2 * blocks + n_states * bb * hidden)


def _pallas_ok(x, w, hidden):
    """The Mosaic path needs lane-sliceable gate columns (128 | H), f32
    compute, and blocks that fit scoped VMEM; anything else takes the XLA
    composite (identical math)."""
    return (hidden % 128 == 0 and x.dtype == jnp.float32
            and w.dtype == jnp.float32
            and _vmem_bytes(x.shape[0], hidden, x.shape[-1] // hidden)
            <= _VMEM_BUDGET_BYTES)


def _resolve_backend(backend, x, w, hidden):
    backend = backend or _auto_backend()
    if backend in ("pallas", "pallas_interpret") and not _pallas_ok(
            x, w, hidden):
        from ..core import flags
        flags.vlog(1, "fused recurrent cell: shape (B=%d, H=%d, dtype=%s) "
                   "not tile-aligned or over the VMEM budget; using XLA "
                   "composite", x.shape[0], hidden, x.dtype)
        return "xla"
    return backend


def _round_up(n, m):
    return -(-n // m) * m


def _pad_rows(a, rows):
    if a.shape[0] == rows:
        return a
    pad = [(0, rows - a.shape[0])] + [(0, 0)] * (a.ndim - 1)
    return jnp.pad(a, pad)


# ---------------------------------------------------------------------------
# Pallas whole-sequence kernels
# ---------------------------------------------------------------------------


def _step_valid(sl_ref, shape, t, t_total, reverse):
    """[bb, H] mask of rows still inside their sequence at grid step t.
    The int32 lengths are lane-broadcast BEFORE the compare so the select
    sees a full-width mask (no [bb, 1] bool broadcast for Mosaic)."""
    tpos = (t_total - 1 - t) if reverse else t
    return jnp.broadcast_to(sl_ref[:, :1], shape) > tpos


def _lstm_seq_kernel(x_ref, sl_ref, h0_ref, c0_ref, w_ref, hs_ref, cs_ref,
                     g_ref, h_scr, c_scr, *, hidden, t_total, reverse):
    from jax.experimental import pallas as pl

    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        h_scr[:] = h0_ref[:].astype(jnp.float32)
        c_scr[:] = c0_ref[:].astype(jnp.float32)

    h_prev = h_scr[:]
    c_prev = c_scr[:]
    xt = x_ref[0].astype(jnp.float32)                        # [bb, 4H]
    gates = xt + jax.lax.dot_general(
        h_prev, w_ref[:].astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    i = jax.nn.sigmoid(gates[:, :hidden])
    f = jax.nn.sigmoid(gates[:, hidden:2 * hidden])
    g = jnp.tanh(gates[:, 2 * hidden:3 * hidden])
    o = jax.nn.sigmoid(gates[:, 3 * hidden:])
    c_new = f * c_prev + i * g
    h_new = o * jnp.tanh(c_new)
    valid = _step_valid(sl_ref, h_prev.shape, t, t_total, reverse)
    h_new = jnp.where(valid, h_new, h_prev)
    c_new = jnp.where(valid, c_new, c_prev)
    h_scr[:] = h_new
    c_scr[:] = c_new
    hs_ref[0] = h_new.astype(hs_ref.dtype)
    cs_ref[0] = c_new.astype(cs_ref.dtype)
    if g_ref is not None:
        g_ref[0, :, :hidden] = i
        g_ref[0, :, hidden:2 * hidden] = f
        g_ref[0, :, 2 * hidden:3 * hidden] = g
        g_ref[0, :, 3 * hidden:] = o


def _gru_seq_kernel(x_ref, sl_ref, h0_ref, w_ref, hs_ref, g_ref, h_scr, *,
                    hidden, t_total, reverse):
    from jax.experimental import pallas as pl

    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        h_scr[:] = h0_ref[:].astype(jnp.float32)

    h_prev = h_scr[:]
    xt = x_ref[0].astype(jnp.float32)                        # [bb, 3H]
    w = w_ref[:].astype(jnp.float32)
    rz = jax.nn.sigmoid(xt[:, :2 * hidden] + jax.lax.dot_general(
        h_prev, w[:, :2 * hidden], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32))
    r = rz[:, :hidden]
    z = rz[:, hidden:]
    c = jnp.tanh(xt[:, 2 * hidden:] + jax.lax.dot_general(
        r * h_prev, w[:, 2 * hidden:], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32))
    h_new = z * h_prev + (1 - z) * c
    valid = _step_valid(sl_ref, h_prev.shape, t, t_total, reverse)
    h_new = jnp.where(valid, h_new, h_prev)
    h_scr[:] = h_new
    hs_ref[0] = h_new.astype(hs_ref.dtype)
    if g_ref is not None:
        g_ref[0, :, :hidden] = r
        g_ref[0, :, hidden:2 * hidden] = z
        g_ref[0, :, 2 * hidden:] = c


def _pallas_seq(kind, x, states0, w, seqlen, reverse, interpret, with_stash):
    """Run the whole-sequence kernel. x [B, T, G*H]; states0: (h0,) or
    (h0, c0); returns (hs[, cs][, stash])."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, t, gh = x.shape
    n_gates = 4 if kind == "lstm" else 3
    hidden = gh // n_gates
    bb = _batch_block(b)
    bp = _round_up(b, bb)
    nb = bp // bb

    xf = jnp.swapaxes(_pad_rows(x, bp), 0, 1)                # [T, bp, G*H]
    # seqlen rides broadcast over 128 lanes (a [B] vector output/input is
    # not Mosaic-tileable; same layout trick as the flash kernel's lse)
    slf = jnp.broadcast_to(
        _pad_rows(seqlen.astype(jnp.int32), bp)[:, None], (bp, 128))
    states = [_pad_rows(s, bp) for s in states0]

    grid = (nb, t)
    x_spec = pl.BlockSpec((1, bb, gh), lambda bi, ti: (ti, bi, 0))
    sl_spec = pl.BlockSpec((bb, 128), lambda bi, ti: (bi, 0))
    s_spec = pl.BlockSpec((bb, hidden), lambda bi, ti: (bi, 0))
    w_spec = pl.BlockSpec(w.shape, lambda bi, ti: (0, 0))
    seq_spec = pl.BlockSpec((1, bb, hidden), lambda bi, ti: (ti, bi, 0))
    g_spec = pl.BlockSpec((1, bb, gh), lambda bi, ti: (ti, bi, 0))

    in_specs = [x_spec, sl_spec] + [s_spec] * len(states) + [w_spec]
    inputs = [xf, slf] + states + [w]
    n_state_outs = 2 if kind == "lstm" else 1
    out_specs = [seq_spec] * n_state_outs
    out_shape = [jax.ShapeDtypeStruct((t, bp, hidden), x.dtype)
                 for _ in range(n_state_outs)]
    if with_stash:
        out_specs.append(g_spec)
        out_shape.append(jax.ShapeDtypeStruct((t, bp, gh), jnp.float32))

    kern = (_lstm_seq_kernel if kind == "lstm" else _gru_seq_kernel)
    kern = functools.partial(kern, hidden=hidden, t_total=t, reverse=reverse)
    n_in = len(in_specs)
    n_out = n_state_outs + (1 if with_stash else 0)

    def body(*refs, _k=kern):
        ins, outs = refs[:n_in], refs[n_in:n_in + n_out]
        scratch = refs[n_in + n_out:]
        g_ref = outs[n_state_outs] if with_stash else None
        _k(*ins, *outs[:n_state_outs], g_ref, *scratch)

    scratch = [pltpu.VMEM((bb, hidden), jnp.float32)]
    if kind == "lstm":
        scratch.append(pltpu.VMEM((bb, hidden), jnp.float32))
    res = pl.pallas_call(
        body, grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape, scratch_shapes=scratch,
        interpret=interpret)(*inputs)
    return tuple(jnp.swapaxes(r, 0, 1)[:b] for r in res)


# ---------------------------------------------------------------------------
# XLA composite (identical math; also the <128-hidden / non-f32 path)
# ---------------------------------------------------------------------------


def _xla_lstm_seq(x, h0, c0, w, seqlen, reverse, with_stash):
    b, t, _ = x.shape

    def step(carry, inp):
        h_prev, c_prev = carry
        xt, it = inp
        gates = xt + jnp.dot(h_prev, w)
        i, f, g, o = jnp.split(gates, 4, axis=-1)
        i, f, o = jax.nn.sigmoid(i), jax.nn.sigmoid(f), jax.nn.sigmoid(o)
        g = jnp.tanh(g)
        c_new = f * c_prev + i * g
        h_new = o * jnp.tanh(c_new)
        tpos = (t - 1 - it) if reverse else it
        valid = (tpos < seqlen)[:, None]
        h_new = jnp.where(valid, h_new, h_prev)
        c_new = jnp.where(valid, c_new, c_prev)
        stash = (jnp.concatenate([i, f, g, o], axis=-1)
                 if with_stash else jnp.zeros((0,), x.dtype))
        return (h_new, c_new), (h_new, c_new, stash)

    (_, _), (hs, cs, stash) = jax.lax.scan(
        step, (h0, c0), (jnp.swapaxes(x, 0, 1), jnp.arange(t)))
    out = (jnp.swapaxes(hs, 0, 1), jnp.swapaxes(cs, 0, 1))
    if with_stash:
        out = out + (jnp.swapaxes(stash, 0, 1),)
    return out


def _xla_gru_seq(x, h0, w, seqlen, reverse, with_stash):
    b, t, gh = x.shape
    h = gh // 3
    w_rz, w_c = w[:, :2 * h], w[:, 2 * h:]

    def step(h_prev, inp):
        xt, it = inp
        rz = jax.nn.sigmoid(xt[:, :2 * h] + jnp.dot(h_prev, w_rz))
        r, z = jnp.split(rz, 2, axis=-1)
        c = jnp.tanh(xt[:, 2 * h:] + jnp.dot(r * h_prev, w_c))
        h_new = z * h_prev + (1 - z) * c
        tpos = (t - 1 - it) if reverse else it
        valid = (tpos < seqlen)[:, None]
        h_new = jnp.where(valid, h_new, h_prev)
        stash = (jnp.concatenate([r, z, c], axis=-1)
                 if with_stash else jnp.zeros((0,), x.dtype))
        return h_new, (h_new, stash)

    _, (hs, stash) = jax.lax.scan(
        step, h0, (jnp.swapaxes(x, 0, 1), jnp.arange(t)))
    out = (jnp.swapaxes(hs, 0, 1),)
    if with_stash:
        out = out + (jnp.swapaxes(stash, 0, 1),)
    return out


def _run_lstm(x, h0, c0, w, seqlen, reverse, backend, with_stash):
    if backend == "xla":
        return _xla_lstm_seq(x, h0, c0, w, seqlen, reverse, with_stash)
    return _pallas_seq("lstm", x, [h0, c0], w, seqlen, reverse,
                       interpret=(backend == "pallas_interpret"),
                       with_stash=with_stash)


def _run_gru(x, h0, w, seqlen, reverse, backend, with_stash):
    if backend == "xla":
        return _xla_gru_seq(x, h0, w, seqlen, reverse, with_stash)
    return _pallas_seq("gru", x, [h0], w, seqlen, reverse,
                       interpret=(backend == "pallas_interpret"),
                       with_stash=with_stash)


# ---------------------------------------------------------------------------
# custom_vjp: manual reverse-time backward against the stashed activations
# ---------------------------------------------------------------------------


def _valid_mask(seqlen, t, reverse):
    pos = jnp.arange(t)
    if reverse:
        pos = t - 1 - pos
    return (pos[None, :] < seqlen[:, None])                  # [B, T]


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _fused_lstm(x, h0, c0, w, seqlen, reverse, backend):
    return _run_lstm(x, h0, c0, w, seqlen, reverse, backend, False)[:2]


def _fused_lstm_fwd(x, h0, c0, w, seqlen, reverse, backend):
    hs, cs, stash = _run_lstm(x, h0, c0, w, seqlen, reverse, backend, True)
    return (hs, cs), (hs, cs, stash, h0, c0, w, seqlen)


def _fused_lstm_bwd(reverse, backend, res, grads):
    hs, cs, stash, h0, c0, w, seqlen = res
    dhs, dcs = grads
    b, t, h = hs.shape
    f32 = jnp.float32
    hprev = jnp.concatenate([h0[:, None], hs[:, :-1]], axis=1)
    cprev = jnp.concatenate([c0[:, None], cs[:, :-1]], axis=1)
    valid = _valid_mask(seqlen, t, reverse)[:, :, None]      # [B, T, 1]

    def tm(a):                                               # time-major
        return jnp.swapaxes(a.astype(f32), 0, 1)

    xs = (tm(dhs), tm(dcs), tm(stash), tm(hprev), tm(cprev), tm(cs),
          jnp.swapaxes(valid, 0, 1))

    def step(carry, inp):
        dh_c, dc_c, dw_acc = carry
        dh_out, dc_out, st, hp, cp, c_t, vd = inp
        i, f, g, o = jnp.split(st, 4, axis=-1)
        dh = dh_c + dh_out
        dc = dc_c + dc_out
        dh_v = jnp.where(vd, dh, 0.0)
        dc_v = jnp.where(vd, dc, 0.0)
        tc = jnp.tanh(c_t)
        do = dh_v * tc
        dc_v = dc_v + dh_v * o * (1.0 - tc * tc)
        di = dc_v * g
        dg = dc_v * i
        df = dc_v * cp
        dgates = jnp.concatenate(
            [di * i * (1 - i), df * f * (1 - f), dg * (1 - g * g),
             do * o * (1 - o)], axis=-1)
        dh_next = dgates @ w.astype(f32).T + jnp.where(vd, 0.0, dh)
        dc_next = dc_v * f + jnp.where(vd, 0.0, dc)
        dw_acc = dw_acc + hp.T @ dgates
        return (dh_next, dc_next, dw_acc), dgates

    init = (jnp.zeros((b, h), f32), jnp.zeros((b, h), f32),
            jnp.zeros(w.shape, f32))
    (dh0, dc0, dw), dx = jax.lax.scan(step, init, xs, reverse=True)
    dx = jnp.swapaxes(dx, 0, 1)
    return (dx.astype(hs.dtype), dh0.astype(h0.dtype), dc0.astype(c0.dtype),
            dw.astype(w.dtype), None)


_fused_lstm.defvjp(_fused_lstm_fwd, _fused_lstm_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _fused_gru(x, h0, w, seqlen, reverse, backend):
    return _run_gru(x, h0, w, seqlen, reverse, backend, False)[0]


def _fused_gru_fwd(x, h0, w, seqlen, reverse, backend):
    hs, stash = _run_gru(x, h0, w, seqlen, reverse, backend, True)
    return hs, (hs, stash, h0, w, seqlen)


def _fused_gru_bwd(reverse, backend, res, dhs):
    hs, stash, h0, w, seqlen = res
    b, t, h = hs.shape
    f32 = jnp.float32
    wf = w.astype(f32)
    w_rz, w_c = wf[:, :2 * h], wf[:, 2 * h:]
    hprev = jnp.concatenate([h0[:, None], hs[:, :-1]], axis=1)
    valid = _valid_mask(seqlen, t, reverse)[:, :, None]

    def tm(a):
        return jnp.swapaxes(a.astype(f32), 0, 1)

    xs = (tm(dhs), tm(stash), tm(hprev), jnp.swapaxes(valid, 0, 1))

    def step(carry, inp):
        dh_c, dw_acc = carry
        dh_out, st, hp, vd = inp
        r, z, c = jnp.split(st, 3, axis=-1)
        dh = dh_c + dh_out
        dh_v = jnp.where(vd, dh, 0.0)
        dz = dh_v * (hp - c)
        dc = dh_v * (1.0 - z)
        dpre_c = dc * (1.0 - c * c)
        drh = dpre_c @ w_c.T
        dr = drh * hp
        dpre_r = dr * r * (1 - r)
        dpre_z = dz * z * (1 - z)
        dpre_rz = jnp.concatenate([dpre_r, dpre_z], axis=-1)
        dx_t = jnp.concatenate([dpre_rz, dpre_c], axis=-1)
        dh_next = (drh * r + dpre_rz @ w_rz.T + dh_v * z
                   + jnp.where(vd, 0.0, dh))
        dw_t = jnp.concatenate(
            [hp.T @ dpre_rz, (r * hp).T @ dpre_c], axis=-1)
        return (dh_next, dw_acc + dw_t), dx_t

    init = (jnp.zeros((b, h), f32), jnp.zeros(w.shape, f32))
    (dh0, dw), dx = jax.lax.scan(step, init, xs, reverse=True)
    dx = jnp.swapaxes(dx, 0, 1)
    return (dx.astype(hs.dtype), dh0.astype(h0.dtype), dw.astype(w.dtype),
            None)


_fused_gru.defvjp(_fused_gru_fwd, _fused_gru_bwd)


# ---------------------------------------------------------------------------
# public entry points + op registrations
# ---------------------------------------------------------------------------


def fused_lstm_sequence(x, h0, c0, w, seqlen, reverse=False, backend=None):
    """Whole-sequence fused LSTM. x [B, T, 4H] pre-projected (+bias),
    w [H, 4H] recurrent, seqlen [B] int; returns (hidden, cell) [B, T, H].
    Numerically equivalent to the `dynamic_lstm` scan (default
    activations), fwd and grad."""
    hidden = w.shape[0]
    backend = _resolve_backend(backend, x, w, hidden)
    if reverse:
        x = jnp.flip(x, axis=1)
    hs, cs = _fused_lstm(x, h0, c0, w, seqlen, bool(reverse), backend)
    if reverse:
        hs, cs = jnp.flip(hs, axis=1), jnp.flip(cs, axis=1)
    return hs, cs


def fused_gru_sequence(x, h0, w, seqlen, reverse=False, backend=None):
    """Whole-sequence fused GRU. x [B, T, 3H] pre-projected (+bias),
    w [H, 3H] (update/reset | candidate); returns hidden [B, T, H]."""
    hidden = w.shape[0]
    backend = _resolve_backend(backend, x, w, hidden)
    if reverse:
        x = jnp.flip(x, axis=1)
    hs = _fused_gru(x, h0, w, seqlen, bool(reverse), backend)
    if reverse:
        hs = jnp.flip(hs, axis=1)
    return hs


_DEFAULT_LSTM_ACTS = {"gate_activation": "sigmoid",
                      "cell_activation": "tanh",
                      "candidate_activation": "tanh"}
_DEFAULT_GRU_ACTS = {"gate_activation": "sigmoid", "activation": "tanh"}


def lstm_attrs_fusable(attrs) -> bool:
    return all(attrs.get(k, v) == v for k, v in _DEFAULT_LSTM_ACTS.items())


def gru_attrs_fusable(attrs) -> bool:
    return all(attrs.get(k, v) == v for k, v in _DEFAULT_GRU_ACTS.items())


@register_op("fused_lstm")
def _fused_lstm_op(ctx, ins, attrs):
    """Drop-in for `dynamic_lstm` (same slots/attrs, default activations
    only — `fuse_recurrent_cell_pass` rewrites only fusable instances)."""
    from ..core.enforce import InvalidArgumentError, enforce
    enforce(lstm_attrs_fusable(attrs),
            "fused_lstm supports only the default sigmoid/tanh activations",
            exc=InvalidArgumentError)
    x = ins["Input"][0]
    w = ins["Weight"][0]
    seqlen = ins["SeqLen"][0]
    h = w.shape[0]
    b = x.shape[0]
    bias = ins["Bias"][0] if ins.get("Bias") else None
    if bias is not None:
        x = x + bias.reshape(1, 1, -1)[:, :, :4 * h]
    h0 = ins["H0"][0] if ins.get("H0") else jnp.zeros((b, h), x.dtype)
    c0 = ins["C0"][0] if ins.get("C0") else jnp.zeros((b, h), x.dtype)
    hs, cs = fused_lstm_sequence(
        x, h0, c0, w, seqlen, reverse=attrs.get("is_reverse", False),
        backend=attrs.get("backend"))
    return {"Hidden": [hs], "Cell": [cs]}


@register_op("fused_gru")
def _fused_gru_op(ctx, ins, attrs):
    """Drop-in for `dynamic_gru` (same slots/attrs, default activations)."""
    from ..core.enforce import InvalidArgumentError, enforce
    enforce(gru_attrs_fusable(attrs),
            "fused_gru supports only the default sigmoid/tanh activations",
            exc=InvalidArgumentError)
    x = ins["Input"][0]
    w = ins["Weight"][0]
    seqlen = ins["SeqLen"][0]
    h = w.shape[0]
    b = x.shape[0]
    if ins.get("Bias"):
        x = x + ins["Bias"][0].reshape(1, 1, -1)
    h0 = ins["H0"][0] if ins.get("H0") else jnp.zeros((b, h), x.dtype)
    hs = fused_gru_sequence(
        x, h0, w, seqlen, reverse=attrs.get("is_reverse", False),
        backend=attrs.get("backend"))
    return {"Hidden": [hs]}
