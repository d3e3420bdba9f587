"""Plain reference of the K-EXAONE block (configs/k-exaone-ep8.json): grouped-
query attention with K and V uncached and the sliding window written as a
MASK over the positions (never a walk over blocks, never a second pool, so
the program's two pools and bounded reads are checked against neither),
rotary positions on the sliding layers alone, a dense gated pair in the
leading layer and the routed layer with its experts looped one by one after
it, a final norm and an untied head.

float32 `jax.numpy` under `jax.default_matmul_precision("highest")` (the
caller sets it), a full causal forward, no cache, no kernel. The parameters
come as stored (bfloat16) and are cast up a matrix at a time; attention goes a
block of query rows and one key/value head's group at a time, so that 17,408
positions at width 6144 fit beside a live engine.

The equations (x a row of the residual; every norm an RMSNorm with a learned
scale and `rms_norm_eps`; no bias anywhere; the configuration's `assumed`
lists what the source's config leaves open):

  h = x + Attn_l(rms(x));  y = h + F_l(rms(h));  final rms;  logits = y W_head
  Attn (every layer): q = x W_q (nh heads of head_dim), k = x W_k, v = x W_v
       (nkv heads); q, k <- rms over a head's values with one learned scale
       for q and one for k; on a `sliding_attention` layer q, k <- rope over
       the whole head (rotate-half pairing, theta `rope_parameters.rope_theta`),
       on a `full_attention` layer they are NOT rotated; query i of a sliding
       layer sees keys j with i - sliding_window < j <= i, of a full layer
       j <= i; query head h reads key/value head h // (nh / nkv); float32
       softmax at scale head_dim^-1/2; out = concat(heads) W_o
  F: down(silu(gate x) * up x), width `intermediate_size`, in the first
     `first_k_dense_replace` layers; after them sum over (selected AND held)
     of w_e E_e(x) + E_shared(x), with s = sigmoid(x W_r), the top-k of ALL
     experts, w = s_sel / sum(s_sel) * routed_scaling_factor.

The routed layer, its router, the experts' loop, the envelope over near-tied
selections (`tie_margin`, `alt_rows`: the module text of `axk1_reference.py`
says what a path is) and the blocked matrix products are `axk1_reference.py`'s
own functions: the two configurations route alike (sigmoid scores, top-8 of
all, normalised, x 2.5, one shared expert), and `reference_blocks.py` holds
the classic block's pieces only. What is written here is the attention and
the forward around it.

Departures from the source's modeling code: the multi-token-prediction block
is left out (the configuration's `left_out`); weights are seeded, not the
checkpoint's. `cfg["rotated"]` (default: the sliding layers) and a
`sliding_window` no sequence reaches are how a builder's tool plants a fault
on this side of the comparison (benchmark/models/kexaone.py `planted`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import axk1_reference as blocks
from .axk1_reference import (F32, by_blocks, mm, pad_rows, padded, rms, rope,
                             round_through)

ATTN_BLOCK = 512        # query rows of one attention call (one head group)
ROTATED = ("sliding_attention",)


def rope_cos_sin(n, dim, theta):
    """cos, sin [n, dim/2] (float32) for positions 0..n-1, angles in float64."""
    inv = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    angle = np.arange(n, dtype=np.float64)[:, None] * inv[None, :]
    return np.cos(angle).astype(np.float32), np.sin(angle).astype(np.float32)


# -- attention ---------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("scale", "window"))
def _attend(q, k, v, row0, key0, scale, window):
    """One block of query rows of one key/value head's group. q [r, g, d];
    k, v [t, d] the keys at positions key0..key0+t-1; query row i sits at
    position row0 + i and sees keys j <= its own, with `window` those with
    own - window < j."""
    s = jnp.einsum("rgd,td->grt", q, k) * scale
    t = key0 + jnp.arange(k.shape[0])[None, None, :]
    r = row0 + jnp.arange(q.shape[0])[None, :, None]
    seen = t <= r
    if window:
        seen &= t > r - window
    p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
    return jnp.einsum("grt,td->rgd", p, v)


@functools.partial(jax.jit, static_argnames=("scale", "window"))
def _attend_own(q, k, v, own_k, own_v, at, scale, window):
    """`_attend` for rows that are not the main forward's: row i sits at
    position at[i], attends the main keys BEFORE it (inside its window) and
    its own key and value (own_k, own_v [r, d])."""
    s = jnp.einsum("rgd,td->grt", q, k) * scale
    own = jnp.einsum("rgd,rd->gr", q, own_k) * scale
    t = jnp.arange(k.shape[0])[None, None, :]
    seen = t < at[None, :, None]
    if window:
        seen &= t > at[None, :, None] - window
    s = jnp.where(seen, s, -jnp.inf)
    p = jax.nn.softmax(jnp.concatenate([s, own[..., None]], -1), axis=-1)
    return (jnp.einsum("grt,td->rgd", p[..., :-1], v)
            + p[..., -1].T[..., None] * own_v[:, None, :])


def gqa(p, name, x, cfg, kind, cos, sin, into, alt=None):
    """`into` [T, H] + the attention of x [T, H] (already normed) of a layer
    of `kind`; `into` is given up. With `alt` = (x_alt [n, H] normed, at [n]
    positions, into_alt [n, H]), also those rows': each over the main rows'
    keys before its position and its own -> ([T, H], [n, H])."""
    T = x.shape[0]
    nh, nkv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    grp, eps, scale = nh // nkv, cfg["rms_norm_eps"], dh ** -0.5
    window = int(cfg["sliding_window"]) if kind == "sliding_attention" else 0
    window = window if window < T else 0        # a mask that hides nothing
    rotate = kind in cfg.get("rotated", ROTATED)

    def heads(rows, which, count, cs, sn, cols=None):
        w = p[f"{name}_{which}.w_0"]
        w = w if cols is None else w[:, cols]
        t = by_blocks(lambda b: mm(b, w), rows).reshape(rows.shape[0], -1, dh)
        if which == "v":
            return t
        t = rms(t, p[f"{name}_{which}_norm.scale"], eps)
        return rope(t, cs, sn) if rotate else t

    def keys_values(rows, cs, sn):
        k, v = heads(rows, "k", nkv, cs, sn), heads(rows, "v", nkv, cs, sn)
        if cfg.get("cache_round"):
            # the rows as a cache of that precision would hold them: the
            # reading "one precision below" that a cell's limit has to refuse
            k, v = (round_through(t, cfg["cache_round"]) for t in (k, v))
        return k, v

    k, v = keys_values(x, cos, sin)
    if alt is not None:
        x_alt, at, out_a = alt
        cos_a, sin_a = cos[at], sin[at]
        k_a, v_a = keys_values(x_alt, cos_a, sin_a)
    step = min(ATTN_BLOCK, T)
    assert T % step == 0, (T, step)
    out = into
    for g in range(nkv):
        cols = slice(g * grp * dh, (g + 1) * grp * dh)
        w_o = p[name + "_o.w_0"][cols]
        for r0 in range(0, T, step):
            q = heads(x[r0:r0 + step], "q", grp, cos[r0:r0 + step],
                      sin[r0:r0 + step], cols)
            # a window layer's block of rows sees no key before r0 - window
            lo = max(0, r0 - window) if window else 0
            ctx = _attend(q, k[lo:r0 + step, g], v[lo:r0 + step, g], r0, lo,
                          scale, window)
            out = blocks._add_at(out, r0,
                                 mm(ctx.reshape(step, grp * dh), w_o))
        if alt is not None:
            q = heads(x_alt, "q", grp, cos_a, sin_a, cols)
            ctx = by_blocks(
                lambda *b: _attend_own(b[0], k[:, g], v[:, g], *b[1:],
                                       scale=scale, window=window),
                q, k_a[:, g], v_a[:, g], at, step=ATTN_BLOCK)
            out_a = out_a + mm(ctx.reshape(len(at), grp * dh), w_o)
        jax.block_until_ready(out)      # as in `by_blocks`
    return out if alt is None else (out, out_a)


# -- the forward -------------------------------------------------------------

def hidden(p, tokens, cfg, held, tie_margin=0.0, alt_rows=(0, 0)):
    """tokens [T] -> the final normed hidden states [T, H] float32 and the
    paths beside them, as `axk1_reference.hidden` gives them: (positions [n]
    on the host, the widest pair each path swapped [n], their hidden states
    [n, H]); none (n = 0) with `tie_margin` 0."""
    eps = cfg["rms_norm_eps"]
    cos, sin = rope_cos_sin(len(tokens), cfg["head_dim"],
                            float(cfg["rope_parameters"]["rope_theta"]))
    cos, sin = jnp.asarray(cos), jnp.asarray(sin)
    x = p["tok_emb"][jnp.asarray(tokens)].astype(F32)
    lo, hi = alt_rows if tie_margin > 0 else (0, 0)
    at = np.zeros(0, np.int32)              # the paths' positions ...
    wide = np.zeros(0)                      # ... widest swapped pairs ...
    xa = jnp.zeros((0, x.shape[1]), F32)    # ... and residuals
    kinds = cfg["layer_types"][:cfg["num_hidden_layers"]]
    for i, kind in enumerate(kinds):
        norm1 = lambda t: rms(t, p[f"l{i}_ln1.scale"], eps)    # noqa: E731
        norm2 = lambda t: rms(t, p[f"l{i}_ln2.scale"], eps)    # noqa: E731
        n = len(at)
        if n:
            n_pad = padded(n)
            xa = pad_rows(xa, n_pad)
            x, xa = gqa(p, f"l{i}_attn", norm1(x), cfg, kind, cos, sin, x,
                        alt=(norm1(xa),
                             jnp.asarray(np.pad(at, (0, n_pad - n))), xa))
            xa = xa[:n]
        else:
            x = gqa(p, f"l{i}_attn", norm1(x), cfg, kind, cos, sin, x)
        if i < cfg["first_k_dense_replace"]:
            ffn = lambda t, into: blocks.gated_ffn(    # noqa: E731
                norm2(t), *(p[f"l{i}_ffn_{m}.w_0"]
                            for m in ("gate", "up", "down")), into=into)
            if n:
                xa = ffn(pad_rows(xa, padded(n)), pad_rows(xa, padded(n)))[:n]
            x = ffn(x, x)
            continue
        name = f"l{i}_moe"
        if hi > lo or n:
            # candidates: the main forward's rows lo..hi and every path
            cand_at = np.concatenate([np.arange(lo, hi, dtype=np.int32), at])
            cand_x = jnp.concatenate([x[lo:hi], xa], axis=0)
            m = len(cand_at)
            cand_h = pad_rows(norm2(cand_x), padded(m))
            ids, w, src, ids2, w2, dist = blocks.route_near(
                cand_h, p[name + "_router.w_0"], cfg, held, tie_margin, m)
            keep = np.arange(hi - lo, m)    # a path goes on as itself ...
            rows = np.concatenate([keep, src])            # ... and branches
            n2 = padded(len(rows))
            sel = np.pad(rows, (0, n2 - len(rows)))
            # a padding row selects no held expert
            ids_all = np.pad(np.concatenate([ids[keep], ids2]),
                             ((0, n2 - len(rows)), (0, 0)),
                             constant_values=-1)
            w_all = np.pad(np.concatenate([w[keep], w2]),
                           ((0, n2 - len(rows)), (0, 0)))
            hs = cand_h[sel]
            xa = blocks.experts(p, name, hs, ids_all, w_all, held,
                                blocks.shared(p, name, hs,
                                              cand_x[sel]))[:len(rows)]
            at = cand_at[rows]
            wide = np.concatenate([np.zeros(hi - lo), wide])
            wide = np.concatenate([wide[keep], np.maximum(wide[src], dist)])
        x = blocks.moe(p, name, norm2(x), cfg, held, into=x)
    out = lambda t: rms(t, p["final_norm.scale"], eps)    # noqa: E731
    return out(x), (at, wide, out(xa))


def logits(p, tokens, cfg, held, first_row=0, tie_margin=0.0,
           alt_rows=(0, 0), detail=None):
    """Logits of rows first_row.. as a host array [T - first_row, vocab];
    with `tie_margin` the rows alt_rows[0] <= r < alt_rows[1] hold the
    envelope of their paths, as `axk1_reference.logits` makes it."""
    x, (at, wide, xa) = hidden(p, tokens, cfg, held, tie_margin, alt_rows)
    out = blocks.head(p, x[first_row:])
    if len(at):
        paths = blocks.head(p, pad_rows(xa, padded(len(at))))[:len(at)]
        paths -= paths.max(-1, keepdims=True)
        if detail is not None:
            lo, hi = alt_rows
            detail.update(rows=(lo, hi), plain=out[lo - first_row:
                                                   hi - first_row].copy(),
                          at=at, wide=wide, paths=paths)
        for r in np.unique(at):
            row = out[r - first_row]
            top = row.max()
            out[r - first_row] = top + np.maximum(
                row - top, paths[at == r].max(0))
    return out
