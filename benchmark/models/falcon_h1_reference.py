"""Plain reference of the Falcon-H1 block (configs/falcon-h1-34b-pp12.json): in
EVERY layer a Mamba-2 state-space mixer and grouped-query rotary attention on
one normed input, summed into one residual, then a gated SiLU pair under a
second norm, under the family's scalar multipliers; a final norm and an untied
head. The recurrence runs TOKEN BY TOKEN (a `lax.scan` over positions: the
program's chunked form is checked against an independent formulation), K and
V are uncached. Its own copy of every piece, independent of `paddle_tpu/`.

float32 `jax.numpy` under `jax.default_matmul_precision("highest")` (the caller
sets it), a full causal forward, no cache, no kernel, no batching. The
parameters come as stored (bfloat16) and are cast up a matrix at a time;
attention goes a block of query rows at a time, the feed-forward a block of its
width at a time, the head a block of vocabulary columns at a time and only on
the rows that are asked for (`rows_from`), so that 12,800 positions fit beside
a live engine: the hidden states of ALL positions are computed in full.

The equations (config keys in backticks; no projection has a bias; every norm
an RMSNorm with a learned scale and `rms_norm_eps`):

    x0 = E[id] * embedding_multiplier
    n  = RMSNorm(x)                                               (input norm)
    x  = x + ssm_out_multiplier * Mixer(ssm_in_multiplier * n)
           + attention_out_multiplier * Attn(attention_in_multiplier * n)
    x  = x + MLP(RMSNorm(x))                                      (second norm)
    logits = (RMSNorm(x) W_head) * lm_head_multiplier

    Attn(u): q = u Wq [heads x head_dim], k = (u Wk) * key_multiplier
        [kv heads x head_dim], v = u Wv; rotary over the whole head_dim,
        rotate-half (pairs (i, i + head_dim/2)), theta `rope_theta`, no
        scaling; causal softmax(q k^T / sqrt(head_dim)) v, query head i reads
        key/value head i // (heads / kv heads); then Wo
    Mixer(u): p = (u W_in) o m, columns [z | x | B | C | dt] of d_ssm | d_ssm |
        groups x state | groups x state | heads, m constant on each range:
        ssm_multipliers[0..4] in that order; xBC = silu(conv1d(xBC; `mamba_d_conv`
        taps, causal, depthwise, zero before position 0) + bias); x [heads,
        d_head], B, C [groups, state], head h reads group h // (heads / groups);
        dt = softplus(dt + dt_bias), no further limit; A = -exp(A_log) a value
        a head;  h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t;
        y_t = h_t C_t + D x_t;  g = RMSNorm over each group of (y * silu(z)),
        the gate FIRST, learned scale [d_ssm]; then W_out
    MLP(v): (silu((v W_gate) * mlp_multipliers[0]) o (v W_up)) W_down
        * mlp_multipliers[1]

Departures from the published description (each an entry of the
configuration's `assumed`): the rotary convention is rotate-half over the
whole head with angles taken in float64; `h` float32; dt has no limits beyond
softplus; weights are seeded, not the checkpoint's, each drawn so that its
branch has unit scale after its multiplier.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

ATTN_BLOCK = 128        # query rows of one attention block
FFN_BLOCK = 2688        # feed-forward columns cast up at a time
FFN_ROWS = 3200         # rows of one feed-forward block
COL_BLOCK = 8192        # vocabulary columns of the head cast up at a time
HEAD_ROWS = 128         # the head's rows come in whole numbers of these
F32 = jnp.float32
#: a dtype to round every matrix through before it is cast up (None: as
#: stored): the reading "one precision below" that a cell's limit has to refuse
ROUND_WEIGHTS_THROUGH = None
#: a dtype to round every value an operator hands on through (None: float32
#: throughout). With the stated dtype this is the WITNESS: these equations as
#: a program in the stated precision would compute them (the state h, softmax
#: and logits stay float32, as the configuration states)
ROUND_ACTIVATIONS_THROUGH = None
#: a dtype to round the mixers' state h through after every step (None:
#: float32, as the configuration states): part of "one precision below"
ROUND_STATE_THROUGH = None
#: a planted fault (benchmark/models/falcon_h1.py `planted`): one of `FAULTS`,
#: or ("stale", at, back): from position `at` on every mixer continues from
#: the state `back` positions earlier (a restore from a stale snapshot)
FAULT = None
FAULTS = ("ssm_out_dropped", "attention_out_dropped", "ssm_ranges_swapped",
          "key_multiplier_one", "no_rotation")


def _through(x, dtype):
    return x if dtype is None else x.astype(dtype).astype(F32)


def _act(x):
    return _through(x, ROUND_ACTIVATIONS_THROUGH)


def _w(w):
    return _through(jnp.asarray(w), ROUND_WEIGHTS_THROUGH).astype(F32)


def rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope_cos_sin(n, dim, theta):
    """cos, sin [n, dim/2] (float32) for positions 0..n-1, angles in float64."""
    inv = 1.0 / float(theta) ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    angle = np.arange(n, dtype=np.float64)[:, None] * inv[None, :]
    return np.cos(angle).astype(np.float32), np.sin(angle).astype(np.float32)


def rope(x, cos, sin):
    """x [T, heads, d] with pairs (i, i + d/2); cos, sin [T, d/2]."""
    half = x.shape[-1] // 2
    c, s = cos[:, None, :], sin[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * c - b * s, b * c + a * s], axis=-1)


def mixer(u, p, cfg, cache_round):
    """u [T, hidden] (the normed input times ssm_in_multiplier) -> [T, hidden]
    before ssm_out_multiplier."""
    H, P, G, N, K = (cfg["mamba_n_heads"], cfg["mamba_d_head"],
                     cfg["mamba_n_groups"], cfg["mamba_d_state"],
                     cfg["mamba_d_conv"])
    d_in, T = H * P, u.shape[0]
    by = list(cfg["ssm_multipliers"])
    if FAULT == "ssm_ranges_swapped":
        by[2], by[3] = by[3], by[2]
    m = np.repeat(np.asarray(by, np.float32), [d_in, d_in, G * N, G * N, H])
    # the projection in two parts, z's columns when the gate needs them: the
    # 9,248 float32 columns of 12,800 rows are never held at once
    w_in = p["ssm_in.w_0"]
    xbc_dt = _act((u @ _w(w_in[:, d_in:])) * m[d_in:])
    xbc, dt = xbc_dt[:, :d_in + 2 * G * N], xbc_dt[:, d_in + 2 * G * N:]
    xbc = _through(xbc, cache_round)             # the conv state's rows
    taps = _w(p["ssm_taps"])                     # [CD, K]
    ext = jnp.concatenate([jnp.zeros((K - 1, xbc.shape[1]), F32), xbc])
    conv = sum(taps[:, j] * ext[j:j + T] for j in range(K))
    x = _act(jax.nn.silu(conv + _w(p["ssm_conv_bias"])))
    xs = x[:, :d_in].reshape(T, G, H // G, P)
    b = x[:, d_in:d_in + G * N].reshape(T, G, N)
    c = x[:, d_in + G * N:].reshape(T, G, N)
    dt = jax.nn.softplus(dt + _w(p["ssm_dt_bias"])).reshape(T, G, H // G)
    a = -jnp.exp(_w(p["ssm_a_log"])).reshape(G, H // G)
    decay = jnp.exp(dt * a)

    def step(h, row):
        x_t, b_t, c_t, dt_t, dec_t = row
        h = _through(dec_t[..., None, None] * h
                     + (dt_t[..., None] * x_t)[..., None]
                     * b_t[:, None, None, :], ROUND_STATE_THROUGH)
        return h, jnp.einsum("ghpn,gn->ghp", h, c_t)

    def scan(h, lo, hi):
        return jax.lax.scan(step, h, tuple(
            t[lo:hi] for t in (xs, b, c, dt, decay)))

    h0 = jnp.zeros((G, H // G, P, N), F32)
    if isinstance(FAULT, tuple) and FAULT[0] == "stale" and FAULT[1] < T:
        _, at, back = FAULT
        h1, y1 = scan(h0, 0, at - back)
        _, y2 = scan(h1, at - back, at)
        _, y3 = scan(h1, at, T)                   # the wrong state
        y = jnp.concatenate([y1, y2, y3])
    else:
        _, y = scan(h0, 0, T)
    d_skip = _w(p["ssm_d"]).reshape(G, H // G)
    y = _act(y + d_skip[..., None] * xs).reshape(T, d_in)
    z = _act((u @ _w(w_in[:, :d_in])) * m[:d_in])
    g = (y * jax.nn.silu(z)).reshape(T, G, -1)
    g = g * jax.lax.rsqrt(jnp.mean(g * g, -1, keepdims=True)
                          + cfg["rms_norm_eps"])
    y = _act(g.reshape(T, d_in) * _w(p["ssm_norm.scale"]))
    return _act(y @ _w(p["ssm_out.w_0"]))


def attention(u, p, cfg, cache_round, cos, sin):
    """u [T, hidden] (the normed input times attention_in_multiplier) ->
    [T, hidden] before attention_out_multiplier."""
    nh, nkv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    T = u.shape[0]
    km = 1.0 if FAULT == "key_multiplier_one" else cfg["key_multiplier"]
    q = _act(u @ _w(p["attn_q.w_0"])).reshape(T, nh, dh)
    k = _act(_act(u @ _w(p["attn_k.w_0"])) * km).reshape(T, nkv, dh)
    if FAULT != "no_rotation":
        q, k = _act(rope(q, cos, sin)), _act(rope(k, cos, sin))
    q = q.reshape(T, nkv, nh // nkv, dh)
    k = _through(k, cache_round)
    v = _through(_act(u @ _w(p["attn_v.w_0"])), cache_round) \
        .reshape(T, nkv, dh)
    blk = math.gcd(T, ATTN_BLOCK)
    keys = jnp.arange(T)

    def block(lo):
        """Query rows lo..lo+blk-1 against every key, masked causally."""
        qb = jax.lax.dynamic_slice_in_dim(q, lo, blk, 0)
        s = jnp.einsum("tgrd,sgd->gtrs", qb, k) * dh ** -0.5
        seen = (lo + jnp.arange(blk))[:, None] >= keys[None, :]
        s = jnp.where(seen[None, :, None, :], s, -jnp.inf)
        return jnp.einsum("gtrs,sgd->tgrd", jax.nn.softmax(s, axis=-1), v) \
            .reshape(blk, nh * dh)

    ctx = jax.lax.map(block, jnp.arange(0, T, blk)).reshape(T, nh * dh)
    return _act(_act(ctx) @ _w(p["attn_o.w_0"]))


def mlp(v, p, cfg):
    """The gated SiLU pair with both `mlp_multipliers`, a block of the width
    at a time."""
    m_gate, m_down = cfg["mlp_multipliers"]
    width = p["ffn_gate.w_0"].shape[1]
    blk = math.gcd(width, FFN_BLOCK)

    def block(j, acc):
        cols = lambda w: _w(jax.lax.dynamic_slice_in_dim(  # noqa: E731
            w, j * blk, blk, 1))
        gate = _act(_act(v @ cols(p["ffn_gate.w_0"])) * m_gate)
        h = _act(jax.nn.silu(gate) * _act(v @ cols(p["ffn_up.w_0"])))
        return acc + h @ _w(jax.lax.dynamic_slice_in_dim(
            p["ffn_down.w_0"], j * blk, blk, 0))

    out = jax.lax.fori_loop(0, width // blk, block,
                            jnp.zeros((v.shape[0], p["ffn_down.w_0"].shape[1]),
                                      F32))
    return _act(_act(out) * m_down)


@functools.partial(jax.jit, static_argnames=("frozen", "cache_round", "hooks"))
def _ssm_branch(x, p, frozen, cache_round, hooks):
    """ssm_out_multiplier * Mixer(ssm_in_multiplier * RMSNorm(x)) of one layer
    over x [T, hidden]; `p` the layer's parameters by the part of their name
    behind `l{i}_` (every layer is alike: one trace serves them all). A
    program of its own, so that the two mixers' temporaries are never held
    together."""
    cfg = _thaw(frozen)
    n = _act(rms(x, _w(p["ln1.scale"]), cfg["rms_norm_eps"]))
    ssm = mixer(_act(n * cfg["ssm_in_multiplier"]), p, cfg, cache_round)
    return _act(ssm * (0.0 if FAULT == "ssm_out_dropped"
                       else cfg["ssm_out_multiplier"]))


@functools.partial(jax.jit, static_argnames=("frozen", "cache_round", "hooks"),
                   donate_argnums=(0, 1))
def _attention_branch(x, ssm, p, cos, sin, frozen, cache_round, hooks):
    """x + (the state-space mixer's term + attention_out_multiplier *
    Attn(attention_in_multiplier * RMSNorm(x))): both mixers read ONE normed
    input and are summed into one residual."""
    cfg = _thaw(frozen)
    n = _act(rms(x, _w(p["ln1.scale"]), cfg["rms_norm_eps"]))
    att = attention(_act(n * cfg["attention_in_multiplier"]), p, cfg,
                    cache_round, cos, sin)
    att = _act(att * (0.0 if FAULT == "attention_out_dropped"
                      else cfg["attention_out_multiplier"]))
    return _act(x + _act(ssm + att))


@functools.partial(jax.jit, static_argnames=("frozen", "blk", "hooks"),
                   donate_argnums=0)
def _feed_forward_rows(x, p, i, frozen, blk, hooks):
    """x with rows i*blk .. (i+1)*blk - 1 replaced by xb + MLP(RMSNorm(xb)),
    in place (the feed-forward is row-wise: a block of rows at a time, the
    21,504-wide products of 12,800 rows are never held at once)."""
    cfg = _thaw(frozen)
    xb = jax.lax.dynamic_slice_in_dim(x, i * blk, blk, 0)
    v = _act(rms(xb, _w(p["ln2.scale"]), cfg["rms_norm_eps"]))
    return jax.lax.dynamic_update_slice_in_dim(
        x, _act(xb + mlp(v, p, cfg)), i * blk, 0)


def _layer(x, p, cos, sin, frozen, cache_round, hooks):
    ssm = _ssm_branch(x, p, frozen, cache_round, hooks)
    x = _attention_branch(x, ssm, p, cos, sin, frozen, cache_round, hooks)
    blk = math.gcd(x.shape[0], FFN_ROWS)
    for i in range(x.shape[0] // blk):
        x = _feed_forward_rows(x, p, i, frozen, blk, hooks)
    # a layer to its end before the next is sent: sent ahead, every program's
    # output would be allocated at once (six layers' worth, gigabytes)
    return jax.block_until_ready(x)


@functools.partial(jax.jit, static_argnames=("by", "hooks"))
def _embed(table, tokens, by, hooks):
    return _act(jnp.asarray(table)[tokens].astype(F32) * by)


@functools.partial(jax.jit, static_argnames=("eps", "hooks"), donate_argnums=0)
def _final_norm(x, scale, eps, hooks):
    return _act(rms(x, _w(scale), eps))


@functools.partial(jax.jit, static_argnames=("hooks",))
def _head_block(x, w, hooks):
    return x @ _w(w)


def _freeze(cfg):
    return tuple(sorted(
        (k, tuple(v) if isinstance(v, list) else v) for k, v in cfg.items()
        if isinstance(v, (int, float, str, bool))
        or (isinstance(v, list) and all(isinstance(e, (int, float))
                                        for e in v))))


def _thaw(frozen):
    return {k: list(v) if isinstance(v, tuple) else v for k, v in frozen}


def _hooks():
    """What a trace depends on beside its arguments: a change retraces."""
    return (str(ROUND_WEIGHTS_THROUGH), str(ROUND_ACTIVATIONS_THROUGH),
            str(ROUND_STATE_THROUGH), str(FAULT))


def hidden(params, tokens, cfg, cache_round=None):
    """[T] token ids -> the final norm's output [T, hidden] (a device array):
    every position, every layer, in float32."""
    frozen, hooks = _freeze(cfg), _hooks()
    tokens = jnp.asarray(tokens, jnp.int32)
    cos, sin = rope_cos_sin(len(tokens), cfg["head_dim"], cfg["rope_theta"])
    x = _embed(params["tok_emb"], tokens, cfg["embedding_multiplier"], hooks)
    for i in range(cfg["num_hidden_layers"]):
        pre = f"l{i}_"
        part = {n[len(pre):]: v for n, v in params.items()
                if n.startswith(pre)}
        x = _layer(x, part, cos, sin, frozen, cache_round, hooks)
    return _final_norm(x, params["final_norm.scale"], cfg["rms_norm_eps"],
                       hooks)


def logits(params, tokens, cfg, cache_round=None, rows_from=0, rows_to=None):
    """[T] token ids -> float32 logits (numpy) of positions `rows_from` ..
    `rows_to` - 1 (None: T), [rows, vocab]: the hidden states of all T
    positions are computed; the head, a matrix of hidden x vocab, runs on the
    rows that are read (padded to a whole number of `HEAD_ROWS`, so that a
    few shapes serve every request)."""
    rows_to = len(tokens) if rows_to is None else rows_to
    x = hidden(params, tokens, cfg, cache_round)[rows_from:rows_to]
    n = x.shape[0]
    x = jnp.pad(x, ((0, -n % HEAD_ROWS), (0, 0)))
    head, hooks = params["lm_head.w_0"], _hooks()
    out = [np.asarray(_head_block(x, head[:, lo:lo + COL_BLOCK], hooks))[:n]
           for lo in range(0, head.shape[1], COL_BLOCK)]
    return np.concatenate(out, axis=1) * np.float32(cfg["lm_head_multiplier"])
