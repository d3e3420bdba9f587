"""Plain reference of the Nemotron-H hybrid stack
(configs/nemotron3-super-ep4.json): Mamba-2 mixers with the recurrence run
TOKEN BY TOKEN (a `lax.scan` over positions: the program's chunked form is
checked against an independent formulation), grouped-query attention with K
and V uncached, latent routed experts looped one by one over the held share
beside the shared expert, a final norm and an untied head. Its own copy of
every piece, independent of `paddle_tpu/`.

float32 `jax.numpy` under `jax.default_matmul_precision("highest")` (the caller
sets it), a full causal forward, no cache, no kernel. The parameters come as
stored (bfloat16) and are cast up a matrix at a time; attention goes a block of
query rows at a time, the experts one at a time, the head a block of vocabulary
columns at a time, so that 1,536 positions fit beside a live engine.

The equations (x a row of the residual; every layer is x = x + f(rms(x)) with
ONE sublayer, by the letter of `hybrid_override_pattern`; every norm an
RMSNorm with a learned scale and `layer_norm_epsilon`):

  M  [z, xBC, dt] = x W_in;  xBC = silu(conv1d(xBC) + b_conv) (causal,
     depthwise, `conv_kernel` taps, zero before position 0);  xBC splits into
     x_h [heads, head_dim], B [groups, N], C [groups, N] (head h reads group
     h // (heads / groups));  dt = softplus(dt + dt_bias);  A = -exp(A_log);
     h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t;  y_t = h_t C_t + D x_t;
     y = rms_groups(y * silu(z)) (the gate first, then an RMSNorm over each of
     `n_groups` groups, a learned scale a value);  out = y W_out
  *  q = x W_q (nh heads of head_dim), k = x W_k, v = x W_v (nkv heads); NO
     rotation, no bias, no QK-norm; query head i reads key/value head
     i // (nh / nkv); causal softmax at scale head_dim^-1/2; out = ctx W_o
  E  s = sigmoid(x W_r) over all `n_routed_published`; the selection is the
     top-k of s + b; w_e = routed_scaling_factor * s_e / (sum of the selected s
     + 1e-20); z = x W_dn; routed = sum over the HELD among the selected of
     w_e W2_e relu(W1_e z)^2;  out = routed W_up + Ws2 relu(Ws1 x)^2

Departures from the published description (each an entry of the
configuration's `assumed`): no rotation in attention (`rope_theta` and
`partial_rotary_factor` are read by nothing); `h` float32; the gate before the
group norm; dt has no limits beyond softplus; the multi-token-prediction
module is left out; weights are seeded, not the checkpoint's.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

ATTN_BLOCK = 512        # query rows of one attention call
COL_BLOCK = 8192        # vocabulary columns of the head cast up at a time
F32 = jnp.float32
#: a dtype to round every matrix through before it is cast up (None: as
#: stored): the reading "one precision below" that a cell's limit has to refuse
ROUND_WEIGHTS_THROUGH = None
#: a dtype to round every value an operator hands on through (None: float32
#: throughout). With the stated dtype this is the WITNESS: these equations as
#: a program in the stated precision would compute them (the state h, router
#: scores, softmax and logits stay float32, as the configuration states)
ROUND_ACTIVATIONS_THROUGH = None
#: a dtype to round the mixers' state h through after every step (None:
#: float32, as the configuration states): part of "one precision below"
ROUND_STATE_THROUGH = None
#: a planted fault (benchmark/models/nemotron_h.py `planted`): "dt_decay"
#: (the state decays by half its dt), "scaling_one" (routed_scaling_factor 1),
#: ("stale", at, back): from position `at` on every mixer continues from the
#: state `back` positions earlier (a snapshot one chunk stale; back None: from
#: zeros, a restore that brought nothing)
FAULT = None

LETTERS = {"M": "ssm", "*": "attention", "E": "moe"}


def _through(x, dtype):
    return x if dtype is None else x.astype(dtype).astype(F32)


def _act(x):
    return _through(x, ROUND_ACTIVATIONS_THROUGH)


def _w(params, name):
    return _through(jnp.asarray(params[name]), ROUND_WEIGHTS_THROUGH) \
        .astype(F32)


def rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def mixer(x, params, name, cfg, cache_round):
    H, P = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    G, N, K = cfg["n_groups"], cfg["ssm_state_size"], cfg["conv_kernel"]
    d_in, T = H * P, x.shape[0]
    zxd = _act(x @ _w(params, name + "_in.w_0"))
    z, xbc, dt = (zxd[:, :d_in], zxd[:, d_in:2 * d_in + 2 * G * N],
                  zxd[:, 2 * d_in + 2 * G * N:])
    xbc = _through(xbc, cache_round)             # the conv state's rows
    taps = _w(params, name + "_taps")            # [CD, K]
    ext = jnp.concatenate([jnp.zeros((K - 1, xbc.shape[1]), F32), xbc])
    conv = sum(taps[:, j] * ext[j:j + T] for j in range(K))
    u = _act(jax.nn.silu(conv + _w(params, name + "_conv_bias")))
    xs = u[:, :d_in].reshape(T, H, P)
    b = jnp.repeat(u[:, d_in:d_in + G * N].reshape(T, G, N), H // G, axis=1)
    c = jnp.repeat(u[:, d_in + G * N:].reshape(T, G, N), H // G, axis=1)
    dt = jax.nn.softplus(dt + _w(params, name + "_dt_bias"))
    a = -jnp.exp(_w(params, name + "_a_log"))
    decay = jnp.exp((0.5 * dt if FAULT == "dt_decay" else dt) * a)

    def step(h, row):
        x_t, b_t, c_t, dt_t, dec_t = row
        h = _through(dec_t[:, None, None] * h
                     + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :],
                     ROUND_STATE_THROUGH)
        return h, jnp.einsum("hpn,hn->hp", h, c_t)

    def scan(h, lo, hi):
        return jax.lax.scan(step, h, tuple(
            t[lo:hi] for t in (xs, b, c, dt, decay)))

    h0 = jnp.zeros((H, P, N), F32)
    if isinstance(FAULT, tuple) and FAULT[0] == "stale" and FAULT[1] < T:
        _, at, back = FAULT
        h1, y1 = scan(h0, 0, at - (back or 0))
        _, y2 = scan(h1, at - (back or 0), at)
        _, y3 = scan(h0 if back is None else h1, at, T)   # the wrong state
        y = jnp.concatenate([y1, y2, y3])
    else:
        _, y = scan(h0, 0, T)
    y = _act(y + _w(params, name + "_d")[:, None] * xs).reshape(T, d_in)
    g = (y * jax.nn.silu(z)).reshape(T, G, -1)
    g = g * jax.lax.rsqrt(jnp.mean(g * g, -1, keepdims=True)
                          + cfg["layer_norm_epsilon"])
    y = _act(g.reshape(T, d_in) * _w(params, name + "_norm.scale"))
    return _act(y @ _w(params, name + "_out.w_0"))


def attention(x, params, name, cfg, cache_round):
    nh, nkv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    T = x.shape[0]
    q = _act(x @ _w(params, name + "_q.w_0")).reshape(T, nkv, nh // nkv, dh)
    k = _through(_act(x @ _w(params, name + "_k.w_0")),
                 cache_round).reshape(T, nkv, dh)
    v = _through(_act(x @ _w(params, name + "_v.w_0")),
                 cache_round).reshape(T, nkv, dh)
    out = []
    for lo in range(0, T, ATTN_BLOCK):
        hi = min(lo + ATTN_BLOCK, T)
        s = jnp.einsum("tgrd,sgd->gtrs", q[lo:hi], k[:hi]) * dh ** -0.5
        mask = jnp.arange(lo, hi)[:, None] >= jnp.arange(hi)[None, :]
        s = jnp.where(mask[None, :, None, :], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        out.append(jnp.einsum("gtrs,sgd->tgrd", p, v[:hi])
                   .reshape(hi - lo, nh * dh))
    return _act(_act(jnp.concatenate(out)) @ _w(params, name + "_o.w_0"))


def scores_and_keys(x, params, name):
    s = jax.nn.sigmoid(x @ _w(params, name + "_router.w_0"))
    return s, s + jnp.asarray(params[name + "_router_bias"], F32)


def moe(x, params, name, cfg):
    k, held = cfg["num_experts_per_tok"], cfg["n_routed_experts"]
    scaling = 1.0 if FAULT == "scaling_one" else cfg["routed_scaling_factor"]
    s, keys = scores_and_keys(x, params, name)
    _, idx = jax.lax.top_k(keys, k)
    sel = jnp.take_along_axis(s, idx, axis=-1)
    w = scaling * sel / (jnp.sum(sel, -1, keepdims=True) + 1e-20)
    dense = jnp.zeros_like(s).at[jnp.arange(x.shape[0])[:, None], idx].set(w)
    z = _act(x @ _w(params, name + "_latent_down.w_0"))
    up, down = params[name + "_experts_up"], params[name + "_experts_down"]

    def one(acc, e):
        # experts 0..held-1 are the share this chip holds
        w1 = _through(up[e], ROUND_WEIGHTS_THROUGH).astype(F32)
        w2 = _through(down[e], ROUND_WEIGHTS_THROUGH).astype(F32)
        h = _act(jnp.square(jax.nn.relu(_act(z @ w1))))
        return acc + dense[:, e][:, None] * _act(h @ w2), None

    routed, _ = jax.lax.scan(one, jnp.zeros_like(z), jnp.arange(held))
    routed = _act(_act(routed) @ _w(params, name + "_latent_up.w_0"))
    h = _act(jnp.square(jax.nn.relu(
        _act(x @ _w(params, name + "_shared_up.w_0")))))
    return _act(routed + _act(h @ _w(params, name + "_shared_down.w_0")))


def layer_kinds(cfg):
    return [LETTERS[c] for c in
            cfg["hybrid_override_pattern"][:cfg["num_hidden_layers"]]]


@functools.partial(jax.jit, static_argnames=("frozen", "cache_round",
                                             "hooks"))
def _hidden(params, tokens, frozen, cache_round, hooks):
    cfg = dict(frozen)
    x = _act(jnp.asarray(params["tok_emb"])[tokens].astype(F32))
    eps = cfg["layer_norm_epsilon"]
    for i, kind in enumerate(layer_kinds(cfg)):
        h = _act(rms(x, _w(params, f"l{i}_ln1.scale"), eps))
        if kind == "ssm":
            f = mixer(h, params, f"l{i}_ssm", cfg, cache_round)
        elif kind == "attention":
            f = attention(h, params, f"l{i}_attn", cfg, cache_round)
        else:
            f = moe(h, params, f"l{i}_moe", cfg)
        x = _act(x + f)
    return _act(rms(x, _w(params, "final_norm.scale"), eps))


@functools.partial(jax.jit, static_argnames=("hooks",))
def _head_block(x, w, hooks):
    return x @ _through(w, ROUND_WEIGHTS_THROUGH).astype(F32)


def logits(params, tokens, cfg, cache_round=None):
    """[T] token ids -> [T, vocab] float32 logits (numpy)."""
    frozen = tuple(sorted((k, v) for k, v in cfg.items()
                          if isinstance(v, (int, float, str, bool))))
    hooks = (str(ROUND_WEIGHTS_THROUGH), str(ROUND_ACTIVATIONS_THROUGH),
             str(ROUND_STATE_THROUGH), str(FAULT))  # a change of hook retraces
    x = _hidden(params, jnp.asarray(tokens, jnp.int32), frozen, cache_round,
                hooks)
    head = params["lm_head.w_0"]
    out = [np.asarray(_head_block(x, head[:, lo:lo + COL_BLOCK], hooks))
           for lo in range(0, head.shape[1], COL_BLOCK)]
    return np.concatenate(out, axis=1)
