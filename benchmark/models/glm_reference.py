"""Plain reference of the GLM-5.3-Flash stack (configs/glm53-flash-ep8.json):
FOUR residual streams mixed around every sub-layer through Sinkhorn (mHC),
channel-wise gated delta-rule (KDA) layers with the recurrence run TOKEN BY
TOKEN, a sparse NoPE latent layer (DSA) uncached with K and V EXPANDED a few
heads at a time and the index scores as the full [T, T / kpool] matrix in
blocks of query rows, routed experts looped one by one over the held share
beside the shared expert, every gated pair clamped, the streams' sum, a final
norm and an untied head. Its own copy of every piece, independent of
`paddle_tpu/`.

float32 `jax.numpy` under `jax.default_matmul_precision("highest")` (the caller
sets it), a full causal forward, no cache, no kernel, a layer run to its end
before the next. The parameters come as stored (bfloat16) and are cast up a
matrix at a time. At 35k positions nothing of [T, anything wide] fits beside
an engine (the streams alone are 2.3 GB in float32, a kda layer's q | k | v
3.5 GB): the streams live on the HOST and every piece runs `ROW_BLOCK` rows at
a time, the recurrence carrying its state and the convolution's last rows from
block to block; only a sub-layer's input and output rows [T, d] and the
sparse layer's keys are whole on the device.

The equations (every norm an RMSNorm with `rms_norm_eps`; no bias but the index
key's LayerNorm):

  residual  X in R^{n x d}, n = hc_mult; X_0 = the embedding in all n rows.
     A sub-layer F (a mixer, then an FFN: two a layer, each its own maps):
        x~ = RMSNorm(flatten(X)) (no learned scale);  z = x~ P [nd -> 2n + n^2]
        H_pre = sigmoid(a_0 z[:n] + b[:n]);  H_post = 2 sigmoid(a_1 z[n:2n] + b[n:2n])
        H_res = Sinkhorn(exp(a_2 mat(z[2n:]) + b[2n:])): hc_sinkhorn_iters rounds
           of rows / (their sum + hc_eps), then columns / (their sum + hc_eps)
        X <- H_res X + H_post^T F(RMSNorm_learned(H_pre X))
     After the last layer the n rows are summed, then the final norm, the head.
  kind of layer i   layer_types[i]: linear_attention (K) | deepseek_sparse_attention (D)
  FFN of layer i    mlp_layer_types[i]: dense | sparse

  K  `ling_reference`'s equations with `linear_attn_config`'s sizes and the
     gate's f and the output gate as low-rank PAIRS (u W_a) W_b
  D  c_q = RMSNorm(u W_qa); q_h = c_q W_qb [nh x dn]; c = RMSNorm(u W_kva);
     [k_h | v_h] = c W_kvb; NO rotation; score q_h . k_{h,s} dn^-1/2, softmax
     over the SELECTED s <= t, context, W_o. The indexer:
        qI_{t,j} = (c_q W_iq)_j;  kI_s = LayerNorm(u_s W_ik) (scale and bias);
        the first `index_rope_dim` of both rotated (rotate-half) at the token's
        own position;  w_t = u_t W_iw index_n_heads^-1/2 index_head_dim^-1/2
        KI_b = mean of kI_s over s in group b (positions kpool b ..)
        I_{t,b} = sum_j w_{t,j} relu(qI_{t,j} . KI_b) for b < (t+1) // kpool
        selected: the index_topk / kpool largest I_{t,b} (all where no more),
        and the tail kpool ((t+1) // kpool) .. t
  E  s = sigmoid(u W_r) float32 over all; top-k of s + b; w = scaling * s_sel /
     (sum + 1e-20); y = Shared(u) + the HELD among the selected
  every gated pair: silu(min(gate, L)) * clip(up, -L, L), L = swiglu_limit

Departures from the published description are the configuration's `assumed`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

ROW_BLOCK = 1536        # rows of the sequence a call holds
ATTN_BLOCK = 256        # query rows of one attention / index call
HEAD_BLOCK = 2          # heads expanded at a time
COL_BLOCK = 8192        # vocabulary columns of the head cast up at a time
F32 = jnp.float32
QK_EPS = 1e-6
#: round every matrix / every value an operator hands on / the kda state
#: through a dtype (None: as stored, float32): `ling_reference`'s three hooks
ROUND_WEIGHTS_THROUGH = None
ROUND_ACTIVATIONS_THROUGH = None
ROUND_STATE_THROUGH = None
#: a planted fault (benchmark/models/glm.py `planted`)
FAULT = None

MANTISSA_BITS = {"float16": 10, "bfloat16": 7, "float8_e4m3fn": 3,
                 "float8_e5m2": 2}


def _through(x, dtype):
    """x (float32) as `dtype`'s MANTISSA would hold it, round to nearest, by
    arithmetic on the bits; the exponent's range is not narrowed."""
    if dtype is None or jnp.dtype(dtype) == jnp.float32:
        return x
    drop = 23 - MANTISSA_BITS[jnp.dtype(dtype).name]
    bits = jax.lax.bitcast_convert_type(x.astype(F32), jnp.uint32)
    bits = (bits + jnp.uint32(1 << (drop - 1))) & jnp.uint32(
        (0xFFFFFFFF >> drop) << drop)
    return jax.lax.bitcast_convert_type(bits, F32)


def _act(x):
    return _through(x, ROUND_ACTIVATIONS_THROUGH)


def _w(params, name):
    return _through(jnp.asarray(params[name]).astype(F32),
                    ROUND_WEIGHTS_THROUGH)


def rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def layer_kinds(cfg):
    return ["dsa" if t == "deepseek_sparse_attention" else "kda"
            for t in cfg["layer_types"]]


def ffn_kinds(cfg):
    return ["dense" if t == "dense" else "moe" for t in cfg["mlp_layer_types"]]


# -- rows [T, d] a block at a time --------------------------------------------

@functools.partial(jax.jit, donate_argnums=0)
def _put(rows, block, lo):
    """`block` as the rows lo .. of `rows`, in place: a list of blocks joined
    at the end is alive twice at the join (0.58 GB each time at 35k
    positions, beside an engine that leaves ~4 GB)."""
    return jax.lax.dynamic_update_slice(rows, block, (lo, 0))


@functools.partial(jax.jit, donate_argnums=0)
def _add(rows, block, lo):
    at = jax.lax.dynamic_slice(rows, (lo, 0), block.shape)
    return jax.lax.dynamic_update_slice(rows, at + block, (lo, 0))


def _stored(params, name):
    """A matrix as stored (no cast yet): sliced first, cast after."""
    return jnp.asarray(params[name])


def _cast(w):
    return _through(w.astype(F32), ROUND_WEIGHTS_THROUGH)


# -- the residual streams ----------------------------------------------------

def sinkhorn(m, iters, eps):
    for _ in range(iters):
        m = m / (jnp.sum(m, -1, keepdims=True) + eps)
        m = m / (jnp.sum(m, -2, keepdims=True) + eps)
    return m


def stream_maps(X, params, cfg):
    """X [T, n, d] -> H_pre [T, n], H_post [T, n], H_res [T, n, n]; `params`
    a sub-layer's maps under "p", "a", "b"."""
    n = cfg["hc_mult"]
    T = X.shape[0]
    flat = X.reshape(T, -1)
    xt = _act(flat * jax.lax.rsqrt(jnp.mean(flat * flat, -1, keepdims=True)
                                   + cfg["rms_norm_eps"]))
    z = xt @ _w(params, "p")
    a, b = (jnp.asarray(params[s], F32) for s in ("a", "b"))
    if FAULT == "hc_static":        # the dynamic part of every map dropped
        z = jnp.zeros_like(z)
    h_pre = jax.nn.sigmoid(a[0] * z[:, :n] + b[:n])
    h_post = 2.0 * jax.nn.sigmoid(a[1] * z[:, n:2 * n] + b[n:2 * n])
    m = jnp.exp(a[2] * z[:, 2 * n:] + b[2 * n:]).reshape(T, n, n)
    if FAULT == "sinkhorn_dropped":
        h_res = m
    elif FAULT == "hres_identity":
        h_res = jnp.broadcast_to(jnp.eye(n, dtype=F32), m.shape)
    else:
        h_res = sinkhorn(m, cfg["hc_sinkhorn_iters"], cfg["hc_eps"])
    if FAULT == "streams_collapsed":    # one stream: x + F(norm(x)) in each
        h_pre = jnp.full_like(h_pre, 1.0 / n)
        h_post = jnp.ones_like(h_post)
        h_res = jnp.broadcast_to(jnp.eye(n, dtype=F32), m.shape)
    return h_pre, h_post, h_res


@functools.partial(jax.jit, static_argnames=("frozen", "hooks"))
def mix_in(params, X, frozen, hooks):
    """A block of streams X [R, n, d] -> (the sub-layer's normed input
    [R, d], H_post, H_res); `params`: the sub-layer's maps and its norm's
    scale under "p", "a", "b", "ln" (one compiled piece for every layer)."""
    cfg = thawed(frozen)
    h_pre, h_post, h_res = stream_maps(X, params, cfg)
    u = _act(jnp.einsum("tk,tkd->td", h_pre, X))
    return (_act(rms(u, _w(params, "ln"), cfg["rms_norm_eps"])), h_post, h_res)


@functools.partial(jax.jit, static_argnames=("hooks",))
def mix_out(X, y, h_post, h_res, hooks):
    """X <- H_res X + H_post^T y."""
    return _act(jnp.einsum("tij,tjd->tid", h_res, X)
                + h_post[:, :, None] * y[:, None, :])


# -- K: the gated delta-rule mixer -------------------------------------------

@functools.partial(jax.jit, static_argnames=("frozen", "name", "cache_round",
                                             "hooks"))
def kda_rows(params, u, carry, row0, frozen, name, cache_round, hooks):
    """A block of rows u [R, d] from the carry (S [H, D, D], the S a stale
    restore would bring, the convolution's last K - 1 input rows), the first
    row at position `row0` -> (y [R, d], carry)."""
    cfg = thawed(frozen)
    lin = cfg["linear_attn_config"]
    H, D, K = lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"]
    T, eps = u.shape[0], cfg["rms_norm_eps"]
    S, S_kept, tail = carry
    qkv = _through(_act(u @ _w(params, name + "_qkv.w_0")), cache_round)
    taps = _w(params, name + "_taps")
    ext = jnp.concatenate([tail, qkv])
    conv = _act(jax.nn.silu(sum(taps[:, j] * ext[j:j + T] for j in range(K))))
    q, k, v = (conv[:, j * H * D:(j + 1) * H * D].reshape(T, H, D)
               for j in range(3))

    def unit(x):
        return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + QK_EPS)

    def pair(which):
        return _act(_act(u @ _w(params, f"{name}_{which}a.w_0"))
                    @ _w(params, f"{name}_{which}b.w_0"))
    q, k = unit(q) * D ** -0.5, unit(k)
    f = pair("f") + _w(params, name + "_dt_bias")
    g = lin["gate_lower_bound"] * jax.nn.sigmoid(
        jnp.exp(_w(params, name + "_a_log"))[:, None] * f.reshape(T, H, D))
    beta = jax.nn.sigmoid(_act(u @ _w(params, name + "_b.w_0")))
    stale = FAULT if isinstance(FAULT, tuple) and FAULT[0] == "stale" else None

    def step(both, row):
        S, kept = both
        q_t, k_t, v_t, g_t, b_t, at = row
        if stale is not None:
            # ("stale", at, back): from position `at` on the layer continues
            # from the state `back` positions earlier
            kept = jnp.where(at == stale[1] - stale[2], S, kept)
            S = jnp.where(at == stale[1], kept, S)
        S = jnp.exp(g_t)[:, :, None] * S
        held = jnp.einsum("hkv,hk->hv", S, k_t)
        S = _through(S + k_t[:, :, None]
                     * (b_t[:, None] * (v_t - held))[:, None, :],
                     ROUND_STATE_THROUGH)
        return (S, kept), jnp.einsum("hkv,hk->hv", S, q_t)

    (S, S_kept), o = jax.lax.scan(
        step, (S, S_kept), (q, k, v, g, beta, row0 + jnp.arange(T)))
    o = rms(_act(o), _w(params, name + "_norm.scale"), eps).reshape(T, H * D)
    o = _act(o * jax.nn.sigmoid(pair("g")))
    return _act(o @ _w(params, name + "_o.w_0")), (S, S_kept, ext[T:])


def kda(held, params, name, cfg, cache_round):
    u = held.pop()
    lin = cfg["linear_attn_config"]
    H, D, K = lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"]
    S0 = jnp.zeros((H, D, D), F32)
    carry = (S0, S0, jnp.zeros((K - 1, 3 * H * D), F32))
    out, key = jnp.zeros_like(u), frozen(cfg)
    for lo in range(0, u.shape[0], ROW_BLOCK):
        y, carry = kda_rows(params, u[lo:lo + ROW_BLOCK], carry, lo, key,
                            name, cache_round, hooks())
        out = _put(out, y, lo)
    return out


# -- D: the sparse latent layer ----------------------------------------------

def rope_cos_sin(n, dim, theta):
    angle = np.arange(n, dtype=np.float64)[:, None] \
        * (theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim))[None, :]
    return np.cos(angle).astype(np.float32), np.sin(angle).astype(np.float32)


def rope_first(x, cos, sin):
    """x [T, .., d]: its first 2 * cos.shape[1] values rotated (pairs (i,
    i + r/2)), the rest as they are."""
    half = cos.shape[1]
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (half,)
    c, s = cos.reshape(shape), sin.reshape(shape)
    a, b = x[..., :half], x[..., half:2 * half]
    return jnp.concatenate([a * c - b * s, b * c + a * s, x[..., 2 * half:]],
                           axis=-1)


@functools.partial(jax.jit, static_argnames=("frozen", "name", "cache_round",
                                             "hooks"))
def dsa_rows(params, u, cos, sin, frozen, name, cache_round, hooks):
    """A block of rows -> (c_q, the cached row c, the rotated index key kI,
    the index heads' weights w)."""
    cfg = thawed(frozen)
    nI, dI, eps = cfg["index_n_heads"], cfg["index_head_dim"], \
        cfg["rms_norm_eps"]
    c_q = _act(rms(_act(u @ _w(params, name + "_qa.w_0")),
                   _w(params, name + "_qa_norm.scale"), eps))
    c_kv = _through(_act(rms(_act(u @ _w(params, name + "_kva.w_0")),
                             _w(params, name + "_kva_norm.scale"), eps)),
                    cache_round)
    k_raw = _act(u @ _w(params, name + "_ik.w_0"))
    mu = jnp.mean(k_raw, -1, keepdims=True)
    var = jnp.mean((k_raw - mu) ** 2, -1, keepdims=True)
    kI = _act((k_raw - mu) * jax.lax.rsqrt(var + eps)
              * _w(params, name + "_ik_norm.scale")
              + _w(params, name + "_ik_norm.bias"))
    if FAULT != "indexer_unrotated":
        kI = _act(rope_first(kI, cos, sin))
    wI = _act(u @ _w(params, name + "_iw.w_0")) * nI ** -0.5 * dI ** -0.5
    return c_q, c_kv, kI, wI


@functools.partial(jax.jit, static_argnames=("frozen", "name", "hooks"))
def dsa_groups(params, c_q, wI, cos, sin, pooled, t0, frozen, name, hooks):
    """The [R, G] mask of the WHOLE groups the rows t0 .. t0 + R - 1 select
    (`t0` traced: one compiled piece for every block of a sequence)."""
    cfg = thawed(frozen)
    nI, dI, kp = cfg["index_n_heads"], cfg["index_head_dim"], cfg["index_kpool"]
    R, G = c_q.shape[0], pooled.shape[0]
    qI = _act(c_q @ _w(params, name + "_iq.w_0")).reshape(R, nI, dI)
    if FAULT != "indexer_unrotated":
        qI = _act(rope_first(qI, cos, sin))
    n_whole = (t0 + jnp.arange(R) + 1) // kp
    score = 0.0
    for h0 in range(0, nI, 8):
        dots = jnp.einsum("rhd,gd->rhg", qI[:, h0:h0 + 8], pooled)
        score = score + jnp.sum(jax.nn.relu(dots) * wI[:, h0:h0 + 8, None], 1)
    eligible = jnp.arange(G)[None, :] < n_whole[:, None]
    k = min(cfg["index_topk"] // kp, G)
    _, idx = jax.lax.top_k(jnp.where(eligible, score, -jnp.inf), k)
    picked = jnp.arange(k)[None, :] < jnp.minimum(n_whole, k)[:, None]
    return jnp.zeros((R, G), bool).at[jnp.arange(R)[:, None], idx].max(picked)


def _heads(w, h0, axis):
    return jax.lax.dynamic_slice_in_dim(w, h0, HEAD_BLOCK, axis)


@functools.partial(jax.jit, static_argnames=("frozen", "name", "hooks"))
def dsa_expand(params, c_kv, h0, frozen, name, hooks):
    """K and V of the heads h0 .. h0 + HEAD_BLOCK - 1 (`h0` traced) for EVERY
    position."""
    cfg = thawed(frozen)
    nh, dn, dv = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
                  cfg["v_head_dim"])
    kvb = _stored(params, name + "_kvb.w_0").reshape(-1, nh, dn + dv)
    kv = _act(jnp.einsum("tc,chd->thd", c_kv, _cast(_heads(kvb, h0, 1))))
    return kv[..., :dn], kv[..., dn:]


@functools.partial(jax.jit, static_argnames=("frozen", "name", "hooks"))
def dsa_attend(params, c_q, k, v, groups, t0, h0, frozen, name, hooks):
    """The rows t0 .. of one query block, the heads h0 .. of one head block
    (both traced) -> their part of the layer's output [R, d] (through their
    rows of W_o): softmax over the selected groups' positions and the tail,
    against EVERY position's K and V under the mask (one compiled piece a
    sequence length; a causal cut a block would be one a block)."""
    cfg = thawed(frozen)
    nh, dn, dv, kp = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
                      cfg["v_head_dim"], cfg["index_kpool"])
    R, T = c_q.shape[0], k.shape[0]
    t, s_at = t0 + jnp.arange(R), jnp.arange(T)
    causal = s_at[None, :] <= t[:, None]
    if FAULT == "selection_ignored":        # dense
        mask = causal
    else:
        sel = jnp.repeat(groups, kp, axis=1)
        sel = jnp.pad(sel, ((0, 0), (0, T - sel.shape[1])))
        n_whole = (t + 1) // kp
        if FAULT != "tail_dropped":
            sel = sel | (s_at[None, :] >= (n_whole * kp)[:, None])
        else:   # a row still sees itself (an empty softmax has no value)
            sel = sel | (s_at[None, :] == t[:, None])
        mask = sel & causal
    wq = _cast(_heads(_stored(params, name + "_qb.w_0").reshape(-1, nh, dn),
                      h0, 1))
    q = _act(jnp.einsum("rc,chd->rhd", c_q, wq))
    s = jnp.einsum("rhd,thd->hrt", q, k) * dn ** -0.5
    p = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
    o = _act(jnp.einsum("hrt,thd->rhd", p, v))
    wo = _cast(_heads(_stored(params, name + "_o.w_0").reshape(nh, dv, -1),
                      h0, 0))
    return jnp.einsum("rhd,hdo->ro", o, wo)


def dsa(held, params, name, cfg, cache_round):
    u = held.pop()
    T, kp, key = u.shape[0], cfg["index_kpool"], frozen(cfg)
    cos, sin = (jnp.asarray(t) for t in rope_cos_sin(
        T, cfg["index_rope_dim"], cfg["index_rope_theta"]))
    parts = [dsa_rows(params, u[lo:lo + ROW_BLOCK], cos[lo:lo + ROW_BLOCK],
                      sin[lo:lo + ROW_BLOCK], key, name, cache_round, hooks())
             for lo in range(0, T, ROW_BLOCK)]
    c_q, c_kv, kI, wI = (jnp.concatenate(t) for t in zip(*parts))
    del u, parts
    G = T // kp                 # whole groups: a half-full one is all tail
    grouped = kI[:G * kp].reshape(G, kp, -1)
    pooled = grouped[:, 0] if FAULT == "pool_first" else jnp.mean(grouped, 1)
    pooled = _through(_act(pooled), cache_round)
    blocks = [(lo, min(lo + ATTN_BLOCK, T)) for lo in range(0, T, ATTN_BLOCK)]
    groups = [dsa_groups(params, c_q[lo:hi], wI[lo:hi], cos[lo:hi],
                         sin[lo:hi], pooled, lo, key, name, hooks())
              for lo, hi in blocks]
    y = jnp.zeros((T, params[name + "_o.w_0"].shape[1]), F32)
    for h0 in range(0, cfg["num_attention_heads"], HEAD_BLOCK):
        k, v = dsa_expand(params, c_kv, h0, key, name, hooks())
        for j, (lo, hi) in enumerate(blocks):
            y = _add(y, dsa_attend(params, c_q[lo:hi], k, v, groups[j], lo,
                                   h0, key, name, hooks()), lo)
    return _act(y)


# -- the feed-forwards -------------------------------------------------------

def clamped(g, up, cfg):
    limit = 0 if FAULT == "clamp_dropped" else cfg["swiglu_limit"]
    if limit:
        g, up = jnp.minimum(g, limit), jnp.clip(up, -limit, limit)
    return _act(jax.nn.silu(g) * up)


def gated(u, params, name, cfg):
    h = clamped(_act(u @ _w(params, name + "_gate.w_0")),
                _act(u @ _w(params, name + "_up.w_0")), cfg)
    return _act(h @ _w(params, name + "_down.w_0"))


def scores_and_keys(u, params, name):
    s = jax.nn.sigmoid(u @ _w(params, name + "_router.w_0"))
    return s, s + jnp.asarray(params[name + "_router_bias"], F32)


def select(keys, cfg):
    return jax.lax.top_k(keys, cfg["num_experts_per_tok"])[1]


def moe(u, params, name, cfg, held=None):
    """The routed layer's part of the experts `held` (a range of ids; None:
    the configuration's share, experts 0 .. n_routed_experts - 1) plus the
    shared expert."""
    held = range(cfg["n_routed_experts"]) if held is None else held
    s, keys = scores_and_keys(u, params, name)
    idx = select(keys, cfg)
    sel = jnp.take_along_axis(s, idx, -1)
    w = cfg["routed_scaling_factor"] * sel / (
        jnp.sum(sel, -1, keepdims=True) + 1e-20)
    dense = jnp.zeros_like(s).at[jnp.arange(u.shape[0])[:, None], idx].set(w)
    stacks = [params[f"{name}_experts_{n}"] for n in ("gate", "up", "down")]

    def one(acc, e):
        wg, wu, wd = (_through(t[e - held[0]].astype(F32),
                               ROUND_WEIGHTS_THROUGH) for t in stacks)
        h = clamped(_act(u @ wg), _act(u @ wu), cfg)
        return acc + dense[:, e][:, None] * _act(h @ wd), None

    routed, _ = jax.lax.scan(one, jnp.zeros_like(u),
                             jnp.arange(held[0], held[-1] + 1))
    return _act(_act(routed) + gated(u, params, name + "_shared", cfg))


@functools.partial(jax.jit, static_argnames=("frozen", "kind", "hooks"))
def ffn_rows(params, u, frozen, kind, hooks):
    cfg = thawed(frozen)
    return gated(u, params, "ffn", cfg) if kind == "dense" \
        else moe(u, params, "moe", cfg)


# -- the stack ---------------------------------------------------------------

def hooks():
    return (str(ROUND_WEIGHTS_THROUGH), str(ROUND_ACTIVATIONS_THROUGH),
            str(ROUND_STATE_THROUGH), str(FAULT))   # a change of hook retraces


def frozen(cfg):
    """The configuration's scalars, its two lists of kinds and the kda
    sizes, hashable: a jitted piece's static key."""
    keep = {k: v for k, v in cfg.items()
            if isinstance(v, (int, float, str, bool))}
    keep["layer_types"] = tuple(cfg["layer_types"])
    keep["mlp_layer_types"] = tuple(cfg["mlp_layer_types"])
    keep["linear_attn_config"] = tuple(sorted(
        (k, v) for k, v in cfg["linear_attn_config"].items()
        if isinstance(v, (int, float))))
    return tuple(sorted(keep.items()))


def thawed(key):
    cfg = dict(key)
    cfg["linear_attn_config"] = dict(cfg["linear_attn_config"])
    return cfg


def layer_params(params, i):
    """Layer i's parameters under their names without the `l{i}_`: the
    jitted pieces are one compiled function for every layer of a kind."""
    return {n[len(f"l{i}_"):]: v for n, v in params.items()
            if n.startswith(f"l{i}_")}


def sublayer(part, X, cfg, j, run):
    """Sub-layer j (1: the mixer, 2: the FFN) of a layer whose parameters
    are `part` (`layer_params`), around the streams X [T, n, d] (numpy, on
    the host, updated in place): a block of rows at a time in, `run` over the
    whole input (handed over in a one-element list, for `run` to pop) and
    giving the output rows [T, d], a block at a time out."""
    key, T = frozen(cfg), X.shape[0]
    maps = {"p": part[f"hc{j}_p"], "a": part[f"hc{j}_a"],
            "b": part[f"hc{j}_b"], "ln": part[f"ln{j}.scale"]}
    blocks = [(lo, min(lo + ROW_BLOCK, T)) for lo in range(0, T, ROW_BLOCK)]
    rows, back = jnp.zeros((T, X.shape[2]), F32), []
    for lo, hi in blocks:
        u, h_post, h_res = mix_in(maps, jnp.asarray(X[lo:hi]), key, hooks())
        rows = _put(rows, u, lo)
        back.append((h_post, h_res))
    # ONE copy of the input rows is alive while the sub-layer runs, and it
    # is the sub-layer's to drop (at 35k positions a copy is 0.58 GB, and the
    # first runs of the cell peaked at 16.89 of 16.91 GB)
    held = [rows]
    del rows, u
    y = run(held)
    for (lo, hi), (h_post, h_res) in zip(blocks, back):
        X[lo:hi] = np.asarray(mix_out(jnp.asarray(X[lo:hi]), y[lo:hi], h_post,
                                      h_res, hooks()))


def layer(part, X, cfg, i, cache_round=None, before_ffn=None):
    """Layer i around the streams X, in place. `before_ffn(u)`: called with
    the FFN's input rows before it runs (benchmark/models/glm.py makes the
    router's bias there)."""
    key = frozen(cfg)
    mixer, which = (kda, "kda") if layer_kinds(cfg)[i] == "kda" \
        else (dsa, "attn")
    sublayer(part, X, cfg, 1, lambda held: mixer(held, part, which, cfg,
                                                 cache_round))

    def ffn(held):
        u = held.pop()
        if before_ffn is not None:
            before_ffn(u)
        out = jnp.zeros_like(u)
        for lo in range(0, u.shape[0], ROW_BLOCK):
            out = _put(out, ffn_rows(part, u[lo:lo + ROW_BLOCK], key,
                                     ffn_kinds(cfg)[i], hooks()), lo)
        return out
    sublayer(part, X, cfg, 2, ffn)


def hidden(params, tokens, cfg, cache_round=None, rows_from=0, rows_to=None):
    """[T] token ids -> the final norm's rows rows_from .. rows_to - 1 [., H]
    (float32, device)."""
    x = np.asarray(_act(jnp.asarray(params["tok_emb"])[
        jnp.asarray(tokens, jnp.int32)].astype(F32)))
    X = np.repeat(x[:, None, :], cfg["hc_mult"], axis=1)
    for i in range(len(cfg["layer_types"])):
        layer(layer_params(params, i), X, cfg, i, cache_round)
    rows = jnp.asarray(X[rows_from:rows_to])
    return _act(rms(_act(jnp.sum(rows, axis=1)),
                    _w(params, "final_norm.scale"), cfg["rms_norm_eps"]))


@functools.partial(jax.jit, static_argnames=("hooks",))
def _head_block(x, w, hooks):
    return x @ _through(w.astype(F32), ROUND_WEIGHTS_THROUGH)


def logits(params, tokens, cfg, cache_round=None, rows_from=0, rows_to=None):
    """[T] token ids -> the float32 logits of the rows rows_from .. rows_to - 1
    (numpy): the final norm and the head run on those rows alone."""
    x = hidden(params, tokens, cfg, cache_round, rows_from, rows_to)
    head = params["lm_head.w_0"]
    out = [np.asarray(_head_block(x, head[:, lo:lo + COL_BLOCK], hooks()))
           for lo in range(0, head.shape[1], COL_BLOCK)]
    return np.concatenate(out, axis=1)
