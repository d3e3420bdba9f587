"""Plain reference of the Ling-3.0-flash hybrid stack
(configs/ling3-flash-ep4.json): channel-wise gated delta-rule (KDA) layers with
the recurrence run TOKEN BY TOKEN (a `lax.scan` over positions: the program's
chunked form and its decode kernel are checked against an independent
formulation), latent attention uncached with K and V EXPANDED, group-limited
routed experts looped one by one over the held share beside the shared expert,
a final norm and an untied head. Its own copy of every piece, independent of
`paddle_tpu/`.

float32 `jax.numpy` under `jax.default_matmul_precision("highest")` (the caller
sets it), a full causal forward, no cache, no kernel, a layer run to its end
before the next. The parameters come as stored (bfloat16) and are cast up a
matrix at a time; attention goes a block of query rows at a time, the experts
one at a time, the head a block of vocabulary columns at a time.

The equations (u a normed row; x = x + Mixer(N1(x)), x = x + FFN(N2(x)); every
norm an RMSNorm with a learned scale and `rms_norm_eps`; no bias anywhere):

  kind of layer i   latent if (i + 1) % layer_group_size == 0, else kda
  FFN of layer i    the gated SiLU pair of `intermediate_size` if
                    i < first_k_dense_replace, else routed experts

  K  q = silu(conv(u Wq)), k = silu(conv(u Wk)), v = silu(conv(u Wv)) (causal,
     depthwise, `short_conv_kernel_size` taps, zero before position 0, no bias;
     Wq | Wk | Wv are the column thirds of ONE stored matrix `_qkv`);
     q_h = q_h / |q_h| * d^-1/2, k_h = k_h / |k_h| (eps 1e-6 under the root);
     g = kda_lower_bound * sigmoid(exp(A_log_h) * (u Wf + dt_bias)), a value a
     key channel; beta = sigmoid(u Wb), a value a head; a head's state S
     [d keys, d values], zero at position 0:
        S~  = Diag(exp(g_t)) S_{t-1}
        S_t = S~ + beta_t k_t (v_t - S~^T k_t)^T
        o_t = S_t^T q_t
     y = Wo (RMSNorm_head(o_t; one learned scale [d]) * sigmoid(u Wg))
  M  q = u Wq [nh x (128 | 64)] = q_nope | q_pe (no bottleneck: q_lora_rank
     null); [c | k_pe] = u Wkva; c = RMSNorm(c); [k_nope | v]_h = c Wkvb (a head
     at a time: 128 | 128); rotary (rotate-half pairs, `rope_theta`, no
     scaling) on q_pe and k_pe; causal softmax((q_nope k_nope^T + q_pe k_pe^T)
     / sqrt(192)) v; y = Wo (o_h * sigmoid(u Wgate)_h), ONE gate a head
  E  s = sigmoid(u Wr) over all `router_width`; s' = s + b; groups of
     router_width / n_group; a group's score the sum of its 2 largest s'; the
     `topk_group` best groups are kept; the top-k of s' among their experts;
     w_e = routed_scaling_factor * s_e / (sum of the selected s + 1e-20);
     y = Shared(u) + sum over the HELD among the selected of w_e Expert_e(u),
     Expert(u) = (silu(u Wg) * (u Wu)) Wd

Departures from the published description are the configuration's `assumed`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

ATTN_BLOCK = 512        # query rows of one attention call
COL_BLOCK = 8192        # vocabulary columns of the head cast up at a time
F32 = jnp.float32
QK_EPS = 1e-6
#: a dtype to round every matrix through before it is cast up (None: as
#: stored): the reading "one precision below" that a cell's limit has to refuse
ROUND_WEIGHTS_THROUGH = None
#: a dtype to round every value an operator hands on through (None: float32
#: throughout). With the stated dtype this is the WITNESS: these equations as
#: a program in the stated precision would compute them (the state S, the
#: gates g and beta, router scores, softmax and logits stay float32)
ROUND_ACTIVATIONS_THROUGH = None
#: a dtype to round the kda layers' state S through after every step (None:
#: float32, as the configuration states): part of "one precision below"
ROUND_STATE_THROUGH = None
#: a planted fault (benchmark/models/ling.py `planted`): "decay_dropped" (g =
#: 0), "delta_dropped" (S~^T k left out: plain gated linear attention),
#: "beta_dropped" (beta = 1), "group_step_dropped" (the top-k of all experts),
#: "bias_weighs" (the weights are the BIASED scores), "head_gate_dropped" (the
#: latent layers' gate), ("stale", at, back): from position `at` on every kda
#: layer continues from the state `back` positions earlier (a snapshot one
#: chunk stale; back None: from zeros, a restore that brought nothing)
FAULT = None

MANTISSA_BITS = {"float16": 10, "bfloat16": 7, "float8_e4m3fn": 3,
                 "float8_e5m2": 2}


def _through(x, dtype):
    """x (float32) as a value of `dtype`'s MANTISSA would hold it, round to
    nearest, by arithmetic on the bits (a convert to a type the chip does
    not have is normalised away by the compiler: PERF.md section 6, PR 36).
    The exponent's range is not narrowed."""
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
    return ["latent" if (i + 1) % cfg["layer_group_size"] == 0 else "kda"
            for i in range(cfg["num_hidden_layers"])]


def ffn_kinds(cfg):
    return ["dense" if i < cfg["first_k_dense_replace"] else "moe"
            for i in range(cfg["num_hidden_layers"])]


def kda(u, params, name, cfg, cache_round):
    H, D, K = (cfg["num_attention_heads"], cfg["head_dim"],
               cfg["short_conv_kernel_size"])
    T, eps = u.shape[0], cfg["rms_norm_eps"]
    qkv = _through(_act(u @ _w(params, name + "_qkv.w_0")), cache_round)
    taps = _w(params, name + "_taps")                            # [3HD, K]
    ext = jnp.concatenate([jnp.zeros((K - 1, qkv.shape[1]), F32), qkv])
    conv = _act(jax.nn.silu(sum(taps[:, j] * ext[j:j + T] for j in range(K))))
    q, k, v = (conv[:, j * H * D:(j + 1) * H * D].reshape(T, H, D)
               for j in range(3))

    def unit(x):
        return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + QK_EPS)
    q, k = unit(q) * D ** -0.5, unit(k)
    f = _act(u @ _w(params, name + "_f.w_0")) + _w(params, name + "_dt_bias")
    g = cfg["kda_lower_bound"] * jax.nn.sigmoid(
        jnp.exp(_w(params, name + "_a_log"))[:, None] * f.reshape(T, H, D))
    beta = jax.nn.sigmoid(_act(u @ _w(params, name + "_b.w_0")))  # [T,H]
    if FAULT == "decay_dropped":
        g = jnp.zeros_like(g)
    if FAULT == "beta_dropped":
        beta = jnp.ones_like(beta)

    def step(S, row):
        q_t, k_t, v_t, g_t, b_t = row
        S = jnp.exp(g_t)[:, :, None] * S                          # [H,K,V]
        held = jnp.einsum("hkv,hk->hv", S, k_t)
        if FAULT == "delta_dropped":
            held = jnp.zeros_like(held)
        S = _through(S + k_t[:, :, None]
                     * (b_t[:, None] * (v_t - held))[:, None, :],
                     ROUND_STATE_THROUGH)
        return S, jnp.einsum("hkv,hk->hv", S, q_t)

    def scan(S, lo, hi):
        return jax.lax.scan(step, S, tuple(
            t[lo:hi] for t in (q, k, v, g, beta)))

    S0 = jnp.zeros((H, D, D), F32)
    if isinstance(FAULT, tuple) and FAULT[0] == "stale" and FAULT[1] < T:
        _, at, back = FAULT
        S1, o1 = scan(S0, 0, at - (back or 0))
        _, o2 = scan(S1, at - (back or 0), at)
        _, o3 = scan(S0 if back is None else S1, at, T)   # the wrong state
        o = jnp.concatenate([o1, o2, o3])
    else:
        _, o = scan(S0, 0, T)
    o = _act(o)
    o = rms(o, _w(params, name + "_norm.scale"), eps).reshape(T, H * D)
    o = _act(o * jax.nn.sigmoid(_act(u @ _w(params, name + "_g.w_0"))))
    return _act(o @ _w(params, name + "_o.w_0"))


def rope_cos_sin(n, dim, theta):
    angle = np.arange(n, dtype=np.float64)[:, None] \
        * (theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim))[None, :]
    return np.cos(angle).astype(np.float32), np.sin(angle).astype(np.float32)


def rope(x, cos, sin):
    """x [T, .., d] with pairs (i, i + d/2); cos, sin [T, d/2]."""
    half = x.shape[-1] // 2
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (half,)
    c, s = cos.reshape(shape), sin.reshape(shape)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * c - b * s, b * c + a * s], axis=-1)


def latent(u, params, name, cfg, cache_round):
    nh = cfg["num_attention_heads"]
    dn, dr, dv, c = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"], cfg["kv_lora_rank"])
    T, eps = u.shape[0], cfg["rms_norm_eps"]
    cos, sin = (jnp.asarray(t) for t in rope_cos_sin(
        T, dr, cfg["rope_theta"]))
    q = _act(u @ _w(params, name + "_q.w_0")).reshape(T, nh, dn + dr)
    q_nope, q_pe = q[..., :dn], _act(rope(q[..., dn:], cos, sin))
    kv = _act(u @ _w(params, name + "_kva.w_0"))
    c_kv = _through(_act(rms(kv[:, :c], _w(params, name + "_kva_norm.scale"),
                             eps)), cache_round)
    k_pe = _through(_act(rope(kv[:, c:], cos, sin)), cache_round)
    kvb = _w(params, name + "_kvb.w_0").reshape(c, nh, dn + dv)
    k_and_v = _act(jnp.einsum("tc,chd->thd", c_kv, kvb))
    k_nope, v = k_and_v[..., :dn], k_and_v[..., dn:]
    scale = (dn + dr) ** -0.5
    out = []
    for lo in range(0, T, ATTN_BLOCK):
        hi = min(lo + ATTN_BLOCK, T)
        s = (jnp.einsum("rhd,thd->hrt", q_nope[lo:hi], k_nope[:hi])
             + jnp.einsum("rhd,td->hrt", q_pe[lo:hi], k_pe[:hi])) * scale
        mask = jnp.arange(lo, hi)[:, None] >= jnp.arange(hi)[None, :]
        p = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
        out.append(jnp.einsum("hrt,thd->rhd", p, v[:hi]))
    o = _act(jnp.concatenate(out))                               # [T,nh,dv]
    if FAULT != "head_gate_dropped":
        gate = jax.nn.sigmoid(_act(u @ _w(params, name + "_gate.w_0")))
        o = _act(o * gate[:, :, None])
    return _act(o.reshape(T, nh * dv) @ _w(params, name + "_o.w_0"))


def gated(u, params, name):
    h = _act(jax.nn.silu(_act(u @ _w(params, name + "_gate.w_0")))
             * _act(u @ _w(params, name + "_up.w_0")))
    return _act(h @ _w(params, name + "_down.w_0"))


def scores_and_keys(u, params, name):
    s = jax.nn.sigmoid(u @ _w(params, name + "_router.w_0"))
    return s, s + jnp.asarray(params[name + "_router_bias"], F32)


def select(keys, cfg):
    """The top-k of `keys` [T, E] among the experts of each row's
    `topk_group` best of `n_group` groups -> idx [T, k]."""
    T, E = keys.shape
    if FAULT != "group_step_dropped":
        grouped = keys.reshape(T, cfg["n_group"], -1)
        best2, _ = jax.lax.top_k(grouped, 2)
        _, kept = jax.lax.top_k(best2.sum(-1), cfg["topk_group"])
        on = jnp.zeros((T, cfg["n_group"]), bool).at[
            jnp.arange(T)[:, None], kept].set(True)
        keys = jnp.where(on[:, :, None], grouped, -jnp.inf).reshape(T, E)
    return jax.lax.top_k(keys, cfg["num_experts_per_tok"])[1]


def moe(u, params, name, cfg, held=None):
    """The routed layer's part of the experts `held` (a range of ids; None:
    the configuration's share, experts 0 .. num_experts - 1) plus the shared
    expert."""
    held = range(cfg["num_experts"]) if held is None else held
    s, keys = scores_and_keys(u, params, name)
    idx = select(keys, cfg)
    sel = jnp.take_along_axis(keys if FAULT == "bias_weighs" else s, idx, -1)
    w = cfg["routed_scaling_factor"] * sel / (
        jnp.sum(sel, -1, keepdims=True) + 1e-20)
    dense = jnp.zeros_like(s).at[jnp.arange(u.shape[0])[:, None], idx].set(w)
    stacks = [params[f"{name}_experts_{n}"] for n in ("gate", "up", "down")]

    def one(acc, e):
        # the stacks hold the experts held[0] .. held[-1] in order
        wg, wu, wd = (_through(t[e - held[0]].astype(F32),
                               ROUND_WEIGHTS_THROUGH) for t in stacks)
        h = _act(jax.nn.silu(_act(u @ wg)) * _act(u @ wu))
        return acc + dense[:, e][:, None] * _act(h @ wd), None

    routed, _ = jax.lax.scan(one, jnp.zeros_like(u),
                             jnp.arange(held[0], held[-1] + 1))
    return _act(_act(routed) + gated(u, params, name + "_shared"))


@functools.partial(jax.jit, static_argnames=("frozen", "i", "cache_round",
                                             "hooks"))
def mixed(params, x, frozen, i, cache_round, hooks):
    """Layer i's first half: x + Mixer(N1(x))."""
    cfg = dict(frozen)
    u = _act(rms(x, _w(params, f"l{i}_ln1.scale"), cfg["rms_norm_eps"]))
    if layer_kinds(cfg)[i] == "kda":
        return _act(x + kda(u, params, f"l{i}_kda", cfg, cache_round))
    return _act(x + latent(u, params, f"l{i}_attn", cfg, cache_round))


@functools.partial(jax.jit, static_argnames=("frozen", "i", "hooks"))
def fed(params, x, frozen, i, hooks):
    """Layer i's second half: x + FFN(N2(x))."""
    cfg = dict(frozen)
    u = _act(rms(x, _w(params, f"l{i}_ln2.scale"), cfg["rms_norm_eps"]))
    if ffn_kinds(cfg)[i] == "dense":
        return _act(x + gated(u, params, f"l{i}_ffn"))
    return _act(x + moe(u, params, f"l{i}_moe", cfg))


@functools.partial(jax.jit, static_argnames=("hooks",))
def _head_block(x, w, hooks):
    return x @ _through(w.astype(F32), ROUND_WEIGHTS_THROUGH)


def hooks():
    return (str(ROUND_WEIGHTS_THROUGH), str(ROUND_ACTIVATIONS_THROUGH),
            str(ROUND_STATE_THROUGH), str(FAULT))   # a change of hook retraces


def frozen(cfg):
    """The configuration's scalars, hashable: a jitted layer's static key."""
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, str, bool))))


def hidden(params, tokens, cfg, cache_round=None):
    """[T] token ids -> the final norm's rows [T, H] (float32, device)."""
    key = frozen(cfg)
    x = _act(jnp.asarray(params["tok_emb"])[jnp.asarray(tokens, jnp.int32)]
             .astype(F32))
    for i in range(cfg["num_hidden_layers"]):
        part = {n: v for n, v in params.items() if n.startswith(f"l{i}_")}
        # a layer run to its end before the next
        x = jax.block_until_ready(fed(
            part, mixed(part, x, key, i, cache_round, hooks()), key, i,
            hooks()))
    return _act(rms(x, _w(params, "final_norm.scale"), cfg["rms_norm_eps"]))


def logits(params, tokens, cfg, cache_round=None):
    """[T] token ids -> [T, vocab] float32 logits (numpy)."""
    x = hidden(params, tokens, cfg, cache_round)
    head = params["lm_head.w_0"]
    out = [np.asarray(_head_block(x, head[:, lo:lo + COL_BLOCK], hooks()))
           for lo in range(0, head.shape[1], COL_BLOCK)]
    return np.concatenate(out, axis=1)
