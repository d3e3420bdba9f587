"""The channel-wise gated delta rule's two forms (fusion/kda.py, ISSUE 59)
against the recurrence run token by token: the decode kernel (interpreted)
and its composite, the chunked form the prefill lanes run (one triangular
solve a chunk and head, products in sub-blocks of 16 rows), chunk then decode
then chunk over one state, a snapshot at a row, every gate at its bound,
dead rows and dead slots untouched."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.fusion import kda

F32 = jnp.float32
TOL = 1e-5


def _rows(rng, lead, h, d, bound=-5.0, gate=None):
    """Seeded q, k (normalised), v, g, beta with leading shape `lead`."""
    def unit(x):
        return x / np.sqrt((x * x).sum(-1, keepdims=True) + 1e-6)
    q = unit(rng.standard_normal(lead + (h, d))) * d ** -0.5
    k = unit(rng.standard_normal(lead + (h, d)))
    v = rng.standard_normal(lead + (h, d))
    g = bound / (1.0 + np.exp(-rng.uniform(-7, 2, lead + (h, d))))
    if gate is not None:
        g = np.full_like(g, gate)
    beta = 1.0 / (1.0 + np.exp(-rng.standard_normal(lead + (h,))))
    return tuple(jnp.asarray(t, F32) for t in (q, k, v, g, beta))


def _recurrence(s, q, k, v, g, beta):
    """The equations, a row at a time. s [H,K,V]; rows [T, H, .]."""
    out = []
    with jax.default_matmul_precision("highest"):
        for t in range(q.shape[0]):
            s = jnp.exp(g[t])[:, :, None] * s
            held = jnp.einsum("hkv,hk->hv", s, k[t])
            s = s + k[t][:, :, None] * (
                beta[t][:, None] * (v[t] - held))[:, None, :]
            out.append(jnp.einsum("hkv,hk->hv", s, q[t]))
    return jnp.stack(out), s


def _close(a, b, tol=TOL):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=tol,
                               rtol=tol)


@pytest.mark.parametrize("backend", ["pallas_interpret", "xla"])
def test_decode_update_is_one_step_of_the_recurrence(backend):
    rng = np.random.default_rng(0)
    slots, h, d = 5, 2, 128
    s = jnp.asarray(rng.standard_normal((slots, h, d, d)), F32)
    q, k, v, g, beta = _rows(rng, (slots,), h, d)
    live = jnp.asarray([1, 0, 1, 1, 0], F32)
    o, new = kda.kda_decode_update(s, live, q, k, v, g, beta, backend=backend)
    for i in range(slots):
        want_o, want_s = _recurrence(s[i], q[i:i + 1], k[i:i + 1], v[i:i + 1],
                                     g[i:i + 1], beta[i:i + 1])
        if live[i] > 0:
            _close(new[i], want_s)
            _close(o[i], want_o[0])
        else:                       # a dead slot: the state as it was
            np.testing.assert_array_equal(np.asarray(new[i]),
                                          np.asarray(s[i]))
            if backend != "xla":
                assert not np.asarray(o[i]).any()


def test_decode_kernel_with_no_live_slot_writes_nothing_new():
    rng = np.random.default_rng(1)
    s = jnp.asarray(rng.standard_normal((3, 2, 128, 128)), F32)
    q, k, v, g, beta = _rows(rng, (3,), 2, 128)
    o, new = kda.kda_decode_update(s, jnp.zeros(3, F32), q, k, v, g, beta,
                                   backend="pallas_interpret")
    np.testing.assert_array_equal(np.asarray(new), np.asarray(s))
    assert not np.asarray(o).any()


@pytest.mark.parametrize("chunk", [128, 40])
def test_chunked_form_equals_the_recurrence(chunk):
    rng = np.random.default_rng(2)
    lanes, h, d = 2, 2, 16
    s0 = jnp.asarray(rng.standard_normal((lanes, h, d, d)), F32)
    q, k, v, g, beta = _rows(rng, (lanes, chunk), h, d)
    o, s_out, _ = kda.kda_chunk(s0, q, k, v, g, beta)
    for i in range(lanes):
        want_o, want_s = _recurrence(s0[i], q[i], k[i], v[i], g[i], beta[i])
        _close(o[i], want_o)
        _close(s_out[i], want_s)


def test_every_gate_at_its_bound():
    """g = -5 in every channel of every row: the products inside a sub-block
    of 16 rows run through e^80 and e^-80, which float32 holds."""
    rng = np.random.default_rng(3)
    lanes, chunk, h, d = 1, 64, 2, 16
    s0 = jnp.asarray(rng.standard_normal((lanes, h, d, d)), F32)
    q, k, v, g, beta = _rows(rng, (lanes, chunk), h, d, gate=-5.0)
    o, s_out, _ = kda.kda_chunk(s0, q, k, v, g, beta)
    assert np.isfinite(np.asarray(o)).all()
    want_o, want_s = _recurrence(s0[0], q[0], k[0], v[0], g[0], beta[0])
    _close(o[0], want_o)
    _close(s_out[0], want_s)
    with pytest.raises(NotImplementedError, match="leaves float32"):
        kda.chunk_lowering(-6.0)


def test_a_snapshot_at_a_row_and_dead_rows_after_it():
    """Lane 0 feeds 20 real rows of 32 and snapshots after 8; lane 1 feeds
    all 32 and snapshots after 24: the snapshot is the state after exactly
    those rows, the state left in the slot that after the real rows."""
    rng = np.random.default_rng(4)
    lanes, chunk, h, d = 2, 32, 2, 16
    s0 = jnp.asarray(rng.standard_normal((lanes, h, d, d)), F32)
    q, k, v, g, beta = _rows(rng, (lanes, chunk), h, d)
    real = jnp.asarray([20, 32])
    snap = jnp.asarray([8, 24])
    on = (jnp.arange(chunk)[None, :] < real[:, None])
    g_m = jnp.where(on[..., None, None], g, 0.0)
    beta_m = jnp.where(on[..., None], beta, 0.0)
    o, s_out, s_snap = kda.kda_chunk(s0, q, k, v, g_m, beta_m, snap)
    for i in range(lanes):
        n, m = int(real[i]), int(snap[i])
        want_o, want_s = _recurrence(s0[i], q[i, :n], k[i, :n], v[i, :n],
                                     g[i, :n], beta[i, :n])
        _close(o[i, :n], want_o)
        _close(s_out[i], want_s)
        _, at_snap = _recurrence(s0[i], q[i, :m], k[i, :m], v[i, :m],
                                 g[i, :m], beta[i, :m])
        _close(s_snap[i], at_snap)


def test_chunk_then_decode_then_chunk_over_one_state():
    rng = np.random.default_rng(5)
    h, d, chunk = 2, 128, 16
    total = chunk + 3 + chunk
    q, k, v, g, beta = _rows(rng, (total,), h, d)
    want_o, want_s = _recurrence(jnp.zeros((h, d, d), F32), q, k, v, g, beta)
    rows = lambda a, b: tuple(t[None, a:b] for t in (q, k, v, g, beta))  # noqa: E731
    o1, s, _ = kda.kda_chunk(jnp.zeros((1, h, d, d), F32), *rows(0, chunk))
    outs = [o1[0]]
    for t in range(chunk, chunk + 3):
        o, s = kda.kda_decode_update(
            s, jnp.ones(1, F32), *(x[:, 0] for x in rows(t, t + 1)),
            backend="pallas_interpret")
        outs.append(o)
    o2, s, _ = kda.kda_chunk(s, *rows(chunk + 3, total))
    outs.append(o2[0])
    _close(jnp.concatenate(outs), want_o)
    _close(s[0], want_s)


def test_scan_counts_its_calls_by_kernel_scope():
    """`kda/call` and `kda/body_traced` by scope, as `ssm/call` counts."""
    from paddle_tpu.observability import tracing
    rng = np.random.default_rng(6)
    tracing.force_enable(True)
    try:
        mark = tracing.mark()
        s = jnp.zeros((2, 2, 128, 128), F32)
        kda.kda_decode_update(s, jnp.ones(2, F32), *_rows(rng, (2,), 2, 128),
                              backend="pallas_interpret")
        kda.kda_chunk(jnp.zeros((1, 2, 16, 16), F32),
                      *_rows(rng, (1, 16), 2, 16))
        seen = {(s.name, s.attrs.get("scope")) for s in
                tracing.spans_since(mark) if s.name.startswith("kda/")}
    finally:
        tracing.force_enable(False)
    assert ("kda/call", "kda_decode") in seen
    assert ("kda/call", "kda_chunk") in seen
    assert ("kda/body_traced", "kda_chunk") in seen


def test_gate_norm_and_head_gate():
    rng = np.random.default_rng(7)
    o = jnp.asarray(rng.standard_normal((5, 4 * 16)), F32)
    gate = jnp.asarray(rng.standard_normal((5, 4 * 16)), F32)
    scale = jnp.asarray(rng.uniform(0.5, 1.5, 16), F32)
    got = kda.gate_norm(o, gate, scale, 4, 1e-6)
    oh = np.asarray(o).reshape(5, 4, 16)
    want = oh / np.sqrt((oh ** 2).mean(-1, keepdims=True) + 1e-6) \
        * np.asarray(scale)
    want = want.reshape(5, 64) / (1 + np.exp(-np.asarray(gate)))
    _close(got, want)
    hg = jnp.asarray(rng.standard_normal((5, 4)), F32)
    got = kda.head_gate(o, hg, 4)
    want = (oh / (1 + np.exp(-np.asarray(hg)))[..., None]).reshape(5, 64)
    _close(got, want)
