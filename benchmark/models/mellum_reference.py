"""Plain reference of the Mellum 2 block as the training cell runs it
(configs/mellum2-ep4.json): float32 `jax.numpy` under
`jax.default_matmul_precision("highest")` (the caller sets it), no kernel, a
full causal forward of ONE sequence, the loss with its balance term, and the
gradients as `jax.grad` of that forward. Nothing here is the program's: the
window is a MASK over the scores, the rotary tables are computed here, the
experts are looped one by one over every row with the rows that did not
select them weighted 0.

The equations (x a row of the residual; every norm an RMSNorm with a learned
scale and `rms_norm_eps`; no bias anywhere; the configuration's `assumed`
lists what the source's config leaves open):

  h = x + Attn_l(rms(x));  y = h + MoE_l(rms(h));  final rms;  logits = y W_head
  Attn: q = n W_q (nh heads of head_dim), k = n W_k, v = n W_v (nkv heads);
        q, k rotated over the whole head (rotate-half pairing) by the layer
        KIND's `rope_parameters`: "default" theta_i = theta^(-2i/d); "yarn"
        per frequency a blend of theta_i and theta_i / factor by the linear
        ramp between the correction dims of beta_fast and beta_slow over
        `original_max_position_embeddings`, cos and sin times
        `attention_factor`; scores at head_dim^-1/2, float32 softmax; query i
        of a `sliding_attention` layer sees keys i - sliding_window < j <= i,
        of a `full_attention` layer j <= i; query head h reads key/value head
        h // (nh / nkv); out = concat(heads) W_o
  MoE:  p = softmax(n W_r) over all `router_width` experts; S = the
        `num_experts_per_tok` largest; w_e = p_e / sum_S p; the sum over
        (e in S AND held) of w_e down_e(silu(gate_e n) * up_e n): the
        normalisation runs over all of S, held or not
  loss: mean cross-entropy + aux_coef * sum over layers of
        router_width * sum_e f_e P_e, f_e the share of the T x k assignments
        that chose e (no gradient), P_e the mean of p_e over the rows

Attention goes a block of query rows at a time (`ATTN_BLOCK`), each block
rematerialised in the backward, so that 8,192 x 8,192 x 32 float32 scores are
never held.

`cfg["fault"]` (a tuple of names, default none) is how a builder's tool
plants a fault on this side of the comparison (benchmark/models/mellum.py
`planted`): "window_as_full", "full_as_window", "plain_rope_on_full",
"no_balance_term", "norm_over_held", "router_bf16", and
"matmuls:<dtype>": the operands of every matmul but the router's rounded
through that dtype's mantissa, the control one precision below the stated one
(`one_precision_below`; the router is float32 in the statement).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
ATTN_BLOCK = 512


def rope_table(n, dim, params):
    """cos, sin [n, dim/2] float32 of one kind of layer's `rope_parameters`,
    the angles in float64 on the host."""
    theta = float(params["rope_theta"])
    freq = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    scale = 1.0
    if params.get("rope_type", "default") == "yarn":
        factor = float(params["factor"])
        orig = float(params["original_max_position_embeddings"])

        def correction_dim(rotations):
            return (dim * math.log(orig / (rotations * 2 * math.pi))
                    / (2 * math.log(theta)))
        low = max(math.floor(correction_dim(float(params["beta_fast"]))), 0)
        high = min(math.ceil(correction_dim(float(params["beta_slow"]))),
                   dim - 1)
        ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                       / max(high - low, 1e-3), 0.0, 1.0)
        freq = freq / factor * ramp + freq * (1.0 - ramp)
        scale = float(params.get("attention_factor")
                      or 0.1 * math.log(factor) + 1.0)
    elif params.get("rope_type", "default") != "default":
        raise ValueError(f"rope_type {params['rope_type']!r}")
    angle = np.arange(n, dtype=np.float64)[:, None] * freq[None, :]
    return ((np.cos(angle) * scale).astype(np.float32),
            (np.sin(angle) * scale).astype(np.float32))


MANTISSA_BITS = {"bfloat16": 7, "float8_e4m3fn": 3}


def round_through(x, dtype):
    """x (float32) as `dtype`'s MANTISSA would hold it, round to nearest, by
    arithmetic on the bits (a convert to a type the chip does not have is
    normalised away by the compiler); the exponent's range is not narrowed.
    Differentiable straight through."""
    drop = 23 - MANTISSA_BITS[dtype]
    bits = jax.lax.bitcast_convert_type(x.astype(F32), jnp.uint32)
    bits = (bits + jnp.uint32(1 << (drop - 1))) & jnp.uint32(
        (0xFFFFFFFF >> drop) << drop)
    rounded = jax.lax.bitcast_convert_type(bits, F32)
    # the rounded value forward, the identity backward (the bits carry no
    # derivative): a backward matmul then multiplies by the rounded operand
    return x + jax.lax.stop_gradient(rounded - x)


def low_precision(fault):
    """The dtype a planted "matmuls:<dtype>" rounds every matmul's operands
    through (the control one precision below the stated one), or None."""
    return next((f.split(":", 1)[1] for f in fault
                 if f.startswith("matmuls:")), None)


def mm(a, b, low=None):
    """a @ b in float32; with `low` on operands rounded through that dtype,
    the sum still float32, whatever the device."""
    if low:
        a, b = round_through(a, low), round_through(b, low)
    return jnp.matmul(a, b, preferred_element_type=F32)


def einsum(spec, a, b, low=None):
    if low:
        a, b = round_through(a, low), round_through(b, low)
    return jnp.einsum(spec, a, b, preferred_element_type=F32)


def rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope(t, cos, sin):
    """t [T, heads, d]: pairs (i, i + d/2) rotated by row t's angles."""
    half = t.shape[-1] // 2
    a, b = t[..., :half], t[..., half:]
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([a * c - b * s, b * c + a * s], -1)


def attention(p, name, n, cfg, kind, fault):
    """n [T, H] normed -> the layer's attention [T, H]."""
    T = n.shape[0]
    nh, nkv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    window = int(cfg["sliding_window"]) if kind == "sliding_attention" else 0
    if "window_as_full" in fault:
        window = 0
    if "full_as_window" in fault and kind == "full_attention":
        window = int(cfg["sliding_window"])
    ropes = cfg["rope_parameters"]
    params = ropes[kind]
    if "plain_rope_on_full" in fault:
        params = ropes["sliding_attention"]
    cos, sin = (jnp.asarray(t) for t in rope_table(T, dh, params))
    low = low_precision(fault)
    q = rope(mm(n, p[name + "_q.w_0"], low).reshape(T, nh, dh), cos, sin)
    k = rope(mm(n, p[name + "_k.w_0"], low).reshape(T, nkv, dh), cos, sin)
    v = mm(n, p[name + "_v.w_0"], low).reshape(T, nkv, dh)
    q = q.reshape(T, nkv, nh // nkv, dh)
    step = min(ATTN_BLOCK, T)
    assert T % step == 0, (T, step)

    @jax.checkpoint
    def block(args):
        qb, r0 = args                                   # [step, nkv, g, dh]
        s = einsum("rkgd,tkd->kgrt", qb, k, low) * dh ** -0.5
        t = jnp.arange(T)[None, None, None, :]
        r = (r0 + jnp.arange(step))[None, None, :, None]
        seen = t <= r
        if window:
            seen &= t > r - window
        w = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return einsum("kgrt,tkd->rkgd", w, v, low)

    ctx = jax.lax.map(block, (q.reshape(T // step, step, nkv, nh // nkv, dh),
                              jnp.arange(0, T, step)))
    return mm(ctx.reshape(T, nh * dh), p[name + "_o.w_0"], low)


def route(p, name, n, cfg, fault):
    """n [T, H] -> (scores [T, E] over all experts, idx [T, k], w [T, k])."""
    held = held_experts(cfg)
    w_r = p[name + "_router.w_0"]
    logits = mm(n, w_r, "bfloat16" if "router_bf16" in fault else None)
    scores = jax.nn.softmax(logits, axis=-1)
    top, idx = jax.lax.top_k(scores, cfg["num_experts_per_tok"])
    total = top
    if "norm_over_held" in fault:
        total = jnp.where(jnp.isin(idx, jnp.asarray(held)), top, 0.0)
    w = top / jnp.maximum(jnp.sum(total, -1, keepdims=True), 1e-30) \
        if cfg.get("norm_topk_prob", True) else top
    return scores, idx, w


def held_experts(cfg):
    return list(range(cfg["expert_rank"] * cfg["num_experts"],
                      (cfg["expert_rank"] + 1) * cfg["num_experts"]))


def moe(p, name, n, cfg, fault):
    """n [T, H] -> (the held selected experts' weighted sum [T, H], the
    assignments each of ALL experts got [E], their mean scores [E])."""
    scores, idx, w = route(p, name, n, cfg, fault)
    n_routed = scores.shape[-1]
    chosen = jnp.sum(jax.nn.one_hot(idx, n_routed, dtype=F32), axis=(0, 1))
    low = low_precision(fault)
    # each held expert's weight a row: 0 where the row did not select it
    dense = jnp.sum(jax.nn.one_hot(idx, n_routed, dtype=F32) * w[..., None],
                    axis=1)[:, jnp.asarray(held_experts(cfg))]       # [T, h]

    @jax.checkpoint
    def one(acc, args):
        gate, up, down, w_e = args
        hid = jax.nn.silu(mm(n, gate, low)) * mm(n, up, low) * w_e[:, None]
        return acc + mm(hid, down, low), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(n), (
        p[name + "_experts_gate"], p[name + "_experts_up"],
        p[name + "_experts_down"], dense.T))
    return out, chosen, jnp.mean(scores, axis=0)


def forward(p, tokens, cfg):
    """tokens [T] -> (logits [T, vocab], the assignments a (layer, expert)
    [L, E], the mean score a (layer, expert) [L, E])."""
    fault = tuple(cfg.get("fault", ()))
    eps = cfg["rms_norm_eps"]
    x = p["tok_emb"][tokens].astype(F32)
    chosen, scores = [], []
    for i, kind in enumerate(cfg["layer_types"][:cfg["num_hidden_layers"]]):
        layer = jax.checkpoint(functools.partial(
            _layer, name=f"l{i}", cfg=cfg, kind=kind, fault=fault, eps=eps))
        x, c, s = layer(p, x)
        chosen.append(c)
        scores.append(s)
    return (mm(rms(x, p["final_norm.scale"], eps), p["lm_head.w_0"],
               low_precision(fault)),
            jnp.stack(chosen), jnp.stack(scores))


def _layer(p, x, *, name, cfg, kind, fault, eps):
    h = x + attention(p, name + "_attn", rms(x, p[name + "_ln1.scale"], eps),
                      cfg, kind, fault)
    out, chosen, scores = moe(p, name + "_moe",
                              rms(h, p[name + "_ln2.scale"], eps), cfg, fault)
    return h + out, chosen, scores


def balance_terms(chosen, scores):
    """The sum over the layers of E * sum_e f_e P_e from the assignments
    [L, E] and the mean scores [L, E] (f carries no gradient)."""
    share = jax.lax.stop_gradient(chosen / jnp.sum(chosen, -1, keepdims=True))
    return chosen.shape[-1] * jnp.sum(share * scores)


def loss(p, tokens, targets, cfg):
    """Mean cross-entropy of one sequence plus `aux_coef` times the layers'
    balance terms."""
    logits, chosen, scores = forward(p, tokens, cfg)
    logp = jax.nn.log_softmax(logits, axis=-1)
    ce = -jnp.mean(jnp.take_along_axis(logp, targets[:, None], axis=-1))
    if "no_balance_term" in tuple(cfg.get("fault", ())):
        return ce
    return ce + float(cfg["aux_coef"]) * balance_terms(chosen, scores)
