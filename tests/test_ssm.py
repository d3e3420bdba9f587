"""fusion/ssm.py: the Mamba-2 state path against the token-by-token
recurrence it is a reformulation of."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.fusion import ssm

H, P, G, N = 8, 8, 2, 128        # heads, head size, groups, state


def _rows(rng, t, dtype=jnp.float32):
    """t positions of one request: x, B, C, dt, and the per-head A."""
    x = jnp.asarray(rng.normal(size=(t, H, P)), dtype)
    b = jnp.asarray(rng.normal(size=(t, G, N)), dtype)
    c = jnp.asarray(rng.normal(size=(t, G, N)), dtype)
    dt = jnp.asarray(rng.uniform(0.01, 0.3, size=(t, H)), jnp.float32)
    a = -jnp.asarray(rng.uniform(0.5, 4.0, size=(H,)), jnp.float32)
    return x, b, c, dt, a


def _recurrence(h, x, b, c, dt, a):
    """h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t; y_t = h_t C_t, a
    position at a time, in numpy float64."""
    h = np.asarray(h, np.float64)
    x, b, c, dt, a = (np.asarray(t, np.float64) for t in (x, b, c, dt, a))
    rep, ys = H // G, []
    for t in range(x.shape[0]):
        bh, ch = np.repeat(b[t], rep, 0), np.repeat(c[t], rep, 0)
        h = np.exp(dt[t] * a)[:, None, None] * h \
            + (dt[t][:, None] * x[t])[:, :, None] * bh[:, None, :]
        ys.append(np.einsum("hpn,hn->hp", h, ch))
    return np.stack(ys), h


@pytest.mark.parametrize("backend", ["xla", "pallas_interpret"])
def test_decode_update_is_one_step_of_the_recurrence(backend):
    rng = np.random.default_rng(0)
    S = 4
    h0 = jnp.asarray(rng.normal(size=(S, H, P, N)), jnp.float32)
    x, b, c, dt, a = _rows(rng, S)
    live = jnp.asarray([1.0, 0.0, 1.0, 1.0])
    y, h1 = ssm.ssm_decode_update(h0, live, x, b, c, dt,
                                  jnp.exp(dt * a), backend=backend)
    for s in range(S):
        want_y, want_h = _recurrence(h0[s], x[s:s + 1], b[s:s + 1],
                                     c[s:s + 1], dt[s:s + 1], a)
        if live[s]:
            np.testing.assert_allclose(h1[s], want_h, rtol=1e-5, atol=1e-5)
            np.testing.assert_allclose(y[s], want_y[0], rtol=1e-4, atol=1e-4)
        else:                           # an idle slot's state is untouched
            np.testing.assert_array_equal(h1[s], h0[s])


@pytest.mark.parametrize("live", [[0, 0, 0, 0], [0, 0, 0, 1], [1, 1, 1, 1]])
def test_decode_kernel_leaves_idle_slots_as_they_are(live):
    rng = np.random.default_rng(1)
    h0 = jnp.asarray(rng.normal(size=(4, H, P, N)), jnp.float32)
    x, b, c, dt, a = _rows(rng, 4)
    live = jnp.asarray(live, jnp.float32)
    got = ssm.ssm_decode_update(h0, live, x, b, c, dt, jnp.exp(dt * a),
                                backend="pallas_interpret")
    want = ssm.ssm_decode_update(h0, live, x, b, c, dt, jnp.exp(dt * a),
                                 backend="xla")
    np.testing.assert_allclose(got[1], want[1], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(got[0] * live[:, None, None],
                               want[0] * live[:, None, None],
                               rtol=1e-4, atol=1e-4)
    for s in range(4):
        if not live[s]:
            np.testing.assert_array_equal(got[1][s], h0[s])


def test_decode_kernel_in_bfloat16_reads_the_state_in_two_parts():
    """bfloat16 rows: the outer product is exact in one pass and the readout
    takes the float32 state as two bfloat16 parts, 2**-16 of the product."""
    rng = np.random.default_rng(2)
    h0 = jnp.asarray(rng.normal(size=(2, H, P, N)), jnp.float32)
    x, b, c, dt, a = _rows(rng, 2, jnp.bfloat16)
    live = jnp.ones((2,))
    y, h1 = ssm.ssm_decode_update(h0, live, x, b, c, dt, jnp.exp(dt * a),
                                  backend="pallas_interpret")
    wy, wh = ssm.ssm_decode_update(h0, live, x, b, c, dt, jnp.exp(dt * a),
                                   backend="xla")
    np.testing.assert_allclose(h1, wh, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(y, wy, rtol=1e-3, atol=2e-3)


@pytest.mark.parametrize("rows", [16, 11, 1])
def test_chunked_form_is_the_recurrence(rows):
    """A chunk of 16 of which `rows` are real (the others have dt = 0)."""
    rng = np.random.default_rng(3)
    Q = 16
    h0 = jnp.asarray(rng.normal(size=(H, P, N)), jnp.float32)
    x, b, c, dt, a = _rows(rng, Q)
    dt = dt * (jnp.arange(Q) < rows)[:, None]
    y, h1, _ = ssm.ssd_chunk(h0[None], x[None], b[None], c[None], dt[None], a)
    want_y, want_h = _recurrence(h0, x[:rows], b[:rows], c[:rows], dt[:rows],
                                 a)
    np.testing.assert_allclose(y[0, :rows], want_y, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(h1[0], want_h, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("cut", [1, 5, 8, 15])
def test_a_chunk_split_anywhere_gives_the_same_state(cut):
    """The state after the first `cut` rows (the snapshot of an interior
    row) is where a second chunk of the rest starts from."""
    rng = np.random.default_rng(4)
    Q = 16
    h0 = jnp.asarray(rng.normal(size=(1, H, P, N)), jnp.float32)
    x, b, c, dt, a = _rows(rng, Q)
    whole_y, whole_h, snap = ssm.ssd_chunk(
        h0, x[None], b[None], c[None], dt[None], a,
        snap_rows=jnp.asarray([cut]))
    _, want_snap = _recurrence(h0[0], x[:cut], b[:cut], c[:cut], dt[:cut], a)
    np.testing.assert_allclose(snap[0], want_snap, rtol=1e-4, atol=1e-4)
    pad = lambda t: jnp.concatenate(  # noqa: E731
        [t[cut:], jnp.zeros((cut,) + t.shape[1:], t.dtype)])[None]
    rest_y, rest_h, _ = ssm.ssd_chunk(snap, pad(x), pad(b), pad(c), pad(dt), a)
    np.testing.assert_allclose(rest_h, whole_h, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(rest_y[0, :Q - cut], whole_y[0, cut:],
                               rtol=1e-4, atol=1e-4)


def _scan_args(rng, S, L, C, taps=4):
    cd = H * P + 2 * G * N
    n = S + L * C
    return dict(
        xbc=jnp.asarray(rng.normal(size=(n, cd)), jnp.float32),
        dt_raw=jnp.asarray(rng.normal(size=(n, H)), jnp.float32),
        taps=jnp.asarray(rng.normal(size=(cd, taps)) * 0.5, jnp.float32),
        conv_bias=jnp.asarray(rng.normal(size=(cd,)) * 0.1, jnp.float32),
        a_log=jnp.asarray(rng.uniform(0.0, 1.5, size=(H,)), jnp.float32),
        dt_bias=jnp.asarray(rng.normal(size=(H,)) - 2.0, jnp.float32),
        d_skip=jnp.ones((H,), jnp.float32))


def test_scan_restores_a_snapshot_and_writes_one_inside_a_chunk():
    """Lane 0 feeds 12 rows from position 0 into slot 2 and snapshots the
    state after row 8 into entry 1; a second tick starts lane 1 from that
    entry with rows 8..11 into slot 0: slot 0 then holds slot 2's state."""
    rng = np.random.default_rng(5)
    S, L, C, K = 3, 2, 16, 4
    p = _scan_args(rng, S, L, C)
    cd = p["xbc"].shape[1]
    zeros = lambda *s: jnp.zeros(s, jnp.float32)  # noqa: E731
    state = [zeros(S, H, P, N), zeros(S, K - 1, cd)]
    snaps = [zeros(2, H, P, N), zeros(2, K - 1, cd)]
    live = zeros(S)
    ints = lambda *v: jnp.asarray(v, jnp.int32)  # noqa: E731
    args = (p["taps"], p["conv_bias"], p["a_log"], p["dt_bias"], p["d_skip"])
    y1, h, conv, sh, sc = ssm.ssm_scan(
        p["xbc"], p["dt_raw"], *args, *state, live, (H, P, G, N),
        lanes=(*snaps, ints(0, 0), ints(12, 0), ints(2, 0), ints(-1, -1),
               ints(1, -1), ints(8, 0), C), backend="xla")
    assert not np.any(np.asarray(h[0])) and not np.any(np.asarray(sh[0]))
    assert np.any(np.asarray(sh[1])) and np.any(np.asarray(h[2]))
    # the second tick: the same rows 8..11, now lane 1's first
    xbc2 = p["xbc"].at[S + C:S + C + 4].set(p["xbc"][S + 8:S + 12])
    dt2 = p["dt_raw"].at[S + C:S + C + 4].set(p["dt_raw"][S + 8:S + 12])
    y2, h2, conv2, _, _ = ssm.ssm_scan(
        xbc2, dt2, *args, h, conv, live, (H, P, G, N),
        lanes=(sh, sc, ints(0, 8), ints(0, 4), ints(1, 0), ints(-1, 1),
               ints(-1, -1), ints(0, 0), C), backend="xla")
    np.testing.assert_allclose(h2[0], h[2], rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(conv2[0], conv[2], rtol=1e-6)
    np.testing.assert_allclose(y2[S + C:S + C + 4], y1[S + 8:S + 12],
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_array_equal(h2[1], h[1])    # nobody's slot: untouched


def test_gated_rms_norm_gates_first_and_norms_a_group():
    rng = np.random.default_rng(6)
    y, z = (jnp.asarray(rng.normal(size=(5, 32)), jnp.float32)
            for _ in range(2))
    scale = jnp.asarray(rng.normal(size=(32,)), jnp.float32)
    got = ssm.gated_rms_norm(y, z, scale, groups=4, eps=1e-5)
    g = np.asarray(y * jax.nn.silu(z), np.float64).reshape(5, 4, 8)
    want = (g / np.sqrt((g * g).mean(-1, keepdims=True) + 1e-5)) \
        .reshape(5, 32) * np.asarray(scale, np.float64)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


# -- heads of 128 x 256 over 2 groups (ISSUE 54: the Falcon-H1 mixer) ---------

def _wide_rows(rng, t, heads=4, groups=2, p=128, n=256, dtype=jnp.float32):
    x = jnp.asarray(rng.normal(size=(t, heads, p)), dtype)
    b = jnp.asarray(rng.normal(size=(t, groups, n)), dtype) * 0.3
    c = jnp.asarray(rng.normal(size=(t, groups, n)), dtype) * 0.3
    dt = jnp.asarray(rng.uniform(0.01, 0.2, size=(t, heads)), jnp.float32)
    a = -jnp.asarray(rng.uniform(0.5, 4.0, size=(heads,)), jnp.float32)
    return x, b, c, dt, a


def _wide_recurrence(h, x, b, c, dt, a, groups=2):
    h = np.asarray(h, np.float64)
    x, b, c, dt, a = (np.asarray(t, np.float64) for t in (x, b, c, dt, a))
    rep, ys = h.shape[0] // groups, []
    for t in range(x.shape[0]):
        bh, ch = np.repeat(b[t], rep, 0), np.repeat(c[t], rep, 0)
        h = np.exp(dt[t] * a)[:, None, None] * h \
            + (dt[t][:, None] * x[t])[:, :, None] * bh[:, None, :]
        ys.append(np.einsum("hpn,hn->hp", h, ch))
    return np.stack(ys), h


@pytest.mark.parametrize("backend", ["xla", "pallas_interpret"])
@pytest.mark.parametrize("live", [[1, 0, 1], [0, 0, 0]])
def test_decode_update_at_heads_of_128_by_256_and_two_groups(backend, live):
    """The Falcon-H1 mixer's head (128 x 256, sixteen heads a group there,
    two here): a live row is one step of the plain recurrence, head h
    reading group h // (heads / 2); an idle slot's state stays as it is."""
    rng = np.random.default_rng(11)
    S, heads = 3, 4
    assert ssm.decode_lowering(heads, 128, 256, "pallas") == ssm.KERNEL
    h0 = jnp.asarray(rng.normal(size=(S, heads, 128, 256)), jnp.float32)
    x, b, c, dt, a = _wide_rows(rng, S, heads)
    y, h1 = ssm.ssm_decode_update(h0, jnp.asarray(live, jnp.float32), x, b,
                                  c, dt, jnp.exp(dt * a), backend=backend)
    for s in range(S):
        want_y, want_h = _wide_recurrence(h0[s], x[s:s + 1], b[s:s + 1],
                                          c[s:s + 1], dt[s:s + 1], a)
        if live[s]:
            np.testing.assert_allclose(h1[s], want_h, rtol=1e-5, atol=1e-5)
            np.testing.assert_allclose(y[s], want_y[0], rtol=1e-4, atol=2e-4)
        else:
            np.testing.assert_array_equal(h1[s], h0[s])


@pytest.mark.parametrize("rows, cut", [(32, 32), (21, 8), (1, 1)])
def test_chunked_form_at_heads_of_128_by_256_and_two_groups(rows, cut):
    """`ssd_chunk` over a chunk of 32 of which `rows` are real, against the
    recurrence token by token; the snapshot inside the chunk is the state
    after its first `cut` rows."""
    rng = np.random.default_rng(12)
    Q, heads = 32, 4
    h0 = jnp.asarray(rng.normal(size=(heads, 128, 256)), jnp.float32)
    x, b, c, dt, a = _wide_rows(rng, Q, heads)
    dt = dt * (jnp.arange(Q) < rows)[:, None]
    y, h1, snap = ssm.ssd_chunk(h0[None], x[None], b[None], c[None],
                                dt[None], a, snap_rows=jnp.asarray([cut]))
    want_y, want_h = _wide_recurrence(h0, x[:rows], b[:rows], c[:rows],
                                      dt[:rows], a)
    np.testing.assert_allclose(y[0, :rows], want_y, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(h1[0], want_h, rtol=1e-4, atol=1e-4)
    _, want_snap = _wide_recurrence(h0, x[:cut], b[:cut], c[:cut], dt[:cut],
                                    a)
    np.testing.assert_allclose(snap[0], want_snap, rtol=1e-4, atol=1e-4)


def test_the_set_up_counters_count_calls_and_traces():
    """`ssm/call` a call site, `ssm/body_traced` a trace of the decode
    kernel's jitted body: a second call of one shape pays no second trace."""
    from paddle_tpu.observability import tracing
    rng = np.random.default_rng(13)
    S, heads = 2, 2
    h0 = jnp.asarray(rng.normal(size=(S, heads, 8, 128)), jnp.float32)
    x, b, c, dt, a = _wide_rows(rng, S, heads, 1, 8, 128)
    ssm._decode_pallas.clear_cache()
    mark = tracing.mark()
    for _ in range(2):
        ssm.ssm_decode_update(h0, jnp.ones((S,)), x, b, c, dt,
                              jnp.exp(dt * a), backend="pallas_interpret")
    seen = [(s.name, s.attrs["scope"]) for s in tracing.spans_since(mark)
            if s.name.startswith("ssm/")]
    assert seen.count(("ssm/call", "ssm_decode_update")) == 2
    assert seen.count(("ssm/body_traced", "ssm_decode_update")) == 1
