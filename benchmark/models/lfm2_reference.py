"""Plain reference of the LFM2 hybrid block (configs/lfm2-8b-a1b.json): gated
short convolutions computed over the WHOLE sequence (no state), grouped-query
attention with K and V uncached, a dense gated pair in the leading layers and
routed experts looped one by one after them, a final norm and the embedding as
the head. Its own copy of every piece, independent of `paddle_tpu/`.

float32 `jax.numpy` under `jax.default_matmul_precision("highest")` (the caller
sets it), a full causal forward, no cache, no kernel. The parameters come as
stored (bfloat16) and are cast up a matrix at a time; attention goes a block
of query rows and one key/value head's group at a time, the head a block of
vocabulary columns at a time, so that 3,072 positions fit beside a live engine.

The equations (x a row of the residual; every norm an RMSNorm with a learned
scale and `norm_eps`; the configuration's `assumed` lists what the source's
config leaves open):

  h = x + Op(rms(x));  y = h + F(rms(h));  final rms;  logits = rms(x) E^T
  Op, `layer_types[l]` "conv":   [B, C, z] = x W_in;  u_t = B_t * z_t
       c_t = sum_j k[:, j] * u_{t-(K-1)+j}  (K = conv_L_cache taps, depthwise,
       causal, zero before position 0, no bias);  out_t = (C_t * c_t) W_out
  Op, "full_attention":  q = x W_q (nh heads), k = x W_k, v = x W_v (nkv heads)
       q, k <- rms over a head's values, with a learned scale;  q, k <- rope
       over the whole head (rotate-half pairing, theta `rope_theta`)
       query head i reads key/value head i // (nh / nkv); causal softmax at
       scale head_dim^-1/2;  out = concat(heads) W_o
  F: down(silu(gate x) * up x), width `intermediate_size`, in the first
     `num_dense_layers` layers; after them sum over the selected of w_e E_e(x)
     (E_e the same pair at `moe_intermediate_size`; no shared expert) with
     s = sigmoid(x W_r), the selection the top-k of s + b (b the expert bias),
     w_e = s_e / (sum of the selected s + 1e-6) * routed_scaling_factor: the
     bias chooses, the unbiased score weighs.

Departures from the source's modeling code: none in the equations above; the
config's `norm_topk_prob` false and `use_expert_bias` false branches are not
written (both are true in the source); weights are seeded, not the checkpoint's.

The top-k is the one step here that is not continuous: where an expert inside
the selection and one outside it lie within rounding of each other in s + b,
which of them a program in the stated precision (bfloat16) selects is not
determined, and that expert's whole output rides on it. With `tie_margin` > 0
the forward therefore follows, for the rows it is asked to (`alt_rows`), BOTH
resolutions of every such pair ("paths": the row's own residual, re-run through
the layers above against the main forward's keys, values and convolution
inputs), and a row's logits are the ENVELOPE of its paths: max over paths of
(logits - their largest). The largest is then 0 and a token reads how far it
lies below the top of the path that favours it most: a token is judged by the
selection that explains it, and by no selection the scores do not allow
(`axk1_reference.py` does the same for its router). With `tie_margin` 0 there
is one path, the plain forward.

What the envelope cannot follow is STATE: a row's selection also decides what
the rows after it read of it (its u in every conv layer above, which the next
K-1 rows convolve over, and its key and value in every attention layer above),
and a path re-runs its own row alone. A row that follows a row the program
resolved the other way is judged against a history the program never had; the
cell's limit is set with that in its clean readings (PERF.md section 6).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

ATTN_BLOCK = 1024       # query rows of one attention call (one head group)
COL_BLOCK = 8192        # vocabulary columns of the head cast up at a time
NEAR = 3                # experts either side of the selection's edge looked at
MIN_ROWS = 64           # row counts are padded to this or a power of two
F32 = jnp.float32
#: a dtype to round every matrix through before it is cast up (None: as
#: stored): the reading "one precision below" that a cell's limit has to refuse
ROUND_WEIGHTS_THROUGH = None
#: a dtype to round every value an operator hands on through (None: float32
#: throughout): matrix products, norms, gates, the convolution's inputs, keys
#: and values, the attention's context, each expert's output and the residual
#: itself. With the stated dtype this is the WITNESS: these same equations as
#: a program in the stated precision would compute them (router scores,
#: softmax and logits stay float32, as the configuration states), written
#: without a line of `paddle_tpu/`. How far its own choices lie from the
#: float32 forward is what the stated precision costs, whoever computes it
ROUND_ACTIVATIONS_THROUGH = None
MANTISSA_BITS = {"float16": 10, "bfloat16": 7, "float8_e4m3fn": 3}


def round_through(x, dtype):
    """x (float32) as a value of `dtype`'s MANTISSA would hold it, round to
    nearest, by arithmetic on the bits (a convert to a type the chip does not
    have is normalised away by the compiler). The exponent's range stays."""
    drop = 23 - MANTISSA_BITS[jnp.dtype(dtype).name]
    bits = jax.lax.bitcast_convert_type(x.astype(F32), jnp.uint32)
    bits = (bits + jnp.uint32(1 << (drop - 1))) & jnp.uint32(
        (0xFFFFFFFF >> drop) << drop)
    return jax.lax.bitcast_convert_type(bits, F32)


@functools.partial(jax.jit, static_argnames=("through", "transpose"))
def _mm(x, w, through, transpose=False):
    w = w.astype(F32)
    if through is not None:
        w = round_through(w, through)
    return x @ (w.T if transpose else w)


def stored(x):
    """x as the next operator reads it: rounded through
    `ROUND_ACTIVATIONS_THROUGH` where that is set."""
    if ROUND_ACTIVATIONS_THROUGH is None:
        return x
    return round_through(x, ROUND_ACTIVATIONS_THROUGH)


def mm(x, w):
    """x [n, a] float32 @ w [a, b] as stored, cast up here."""
    return stored(_mm(x, w, ROUND_WEIGHTS_THROUGH))


def rms(x, scale, eps):
    return stored(x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
                  * scale.astype(F32))


def padded(n):
    return max(MIN_ROWS, 1 << (max(n, 1) - 1).bit_length())


def pad_rows(a, n):
    return jnp.concatenate(
        [a, jnp.zeros((n - a.shape[0],) + a.shape[1:], a.dtype)], axis=0)


# -- positions -------------------------------------------------------------

def rope_cos_sin(n, dim, theta):
    """cos, sin [n, dim/2] (float32) for positions 0..n-1, angles in float64."""
    inv = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    angle = np.arange(n, dtype=np.float64)[:, None] * inv[None, :]
    return np.cos(angle).astype(np.float32), np.sin(angle).astype(np.float32)


def rope(x, cos, sin):
    """x [T, heads, d] with pairs (i, i + d/2); cos, sin [T, d/2]."""
    half = x.shape[-1] // 2
    c, s = cos[:, None, :], sin[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * c - b * s, b * c + a * s], axis=-1)


# -- the short convolution -------------------------------------------------

def short_conv(p, name, h, cfg, alt=None):
    """The operator over h [T, D] (already normed), whole sequence. With
    `alt` = (h_alt [n, D], at [n] positions): also those rows', each over the
    main rows' u before its position and its own -> ([T, D], [n, D])."""
    T, D = h.shape
    k = p[name + "_taps"].astype(F32)               # [D, K]
    K = k.shape[1]

    def gates(rows):
        bcz = mm(rows, p[name + "_in.w_0"])
        return stored(bcz[:, :D] * bcz[:, 2 * D:]), bcz[:, D:2 * D]   # u, C

    u, c_gate = gates(h)
    # up[t + j] = u_{t-(K-1)+j}: zero before position 0
    up = jnp.concatenate([jnp.zeros((K - 1, D), F32), u], axis=0)
    past = up
    if cfg.get("cache_round"):
        # the rows a state of that precision would hold (the control)
        past = round_through(up, cfg["cache_round"])
    conv = k[:, K - 1] * u + sum(k[:, j] * past[j:j + T] for j in range(K - 1))
    out = mm(stored(c_gate * conv), p[name + "_out.w_0"])
    if alt is None:
        return out
    h_a, at = alt
    u_a, c_a = gates(h_a)
    conv_a = k[:, K - 1] * u_a + sum(k[:, j] * past[at + j]
                                     for j in range(K - 1))
    return out, mm(stored(c_a * conv_a), p[name + "_out.w_0"])


# -- attention ---------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("scale",))
def _attend(q, k, v, row0, scale):
    """One block of query rows of one key/value head's group over every key.
    q [r, g, d], k, v [T, d]; query row i sits at position row0 + i."""
    s = jnp.einsum("rgd,td->grt", q, k) * scale
    t = jnp.arange(k.shape[0])[None, None, :]
    r = row0 + jnp.arange(q.shape[0])[None, :, None]
    p = jax.nn.softmax(jnp.where(t <= r, s, -jnp.inf), axis=-1)
    return jnp.einsum("grt,td->rgd", p, v)


@functools.partial(jax.jit, static_argnames=("scale",))
def _attend_own(q, k, v, own_k, own_v, at, scale):
    """`_attend` for rows that are not the main forward's: row i sits at
    position at[i], attends the main keys BEFORE it and its own key and value
    (own_k, own_v [r, d])."""
    s = jnp.einsum("rgd,td->grt", q, k) * scale
    own = jnp.einsum("rgd,rd->gr", q, own_k) * scale
    t = jnp.arange(k.shape[0])[None, None, :]
    s = jnp.where(t < at[None, :, None], s, -jnp.inf)
    p = jax.nn.softmax(jnp.concatenate([s, own[..., None]], -1), axis=-1)
    return (jnp.einsum("grt,td->rgd", p[..., :-1], v)
            + p[..., -1].T[..., None] * own_v[:, None, :])


def attention(p, name, h, cfg, cos, sin, alt=None):
    """Grouped-query attention of h [T, D] (already normed); `alt` as in
    `short_conv`."""
    T = h.shape[0]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh, grp, eps = cfg["head_dim"], nh // nkv, cfg["norm_eps"]
    scale = dh ** -0.5

    def qkv(rows, cs, sn):
        q = mm(rows, p[name + "_q.w_0"]).reshape(-1, nh, dh)
        k = mm(rows, p[name + "_k.w_0"]).reshape(-1, nkv, dh)
        v = mm(rows, p[name + "_v.w_0"]).reshape(-1, nkv, dh)
        q = stored(rope(rms(q, p[name + "_q_norm.scale"], eps), cs, sn))
        k = stored(rope(rms(k, p[name + "_k_norm.scale"], eps), cs, sn))
        if cfg.get("cache_round"):
            k, v = (round_through(t, cfg["cache_round"]) for t in (k, v))
        return q, k, v

    q, k, v = qkv(h, cos, sin)
    step = min(ATTN_BLOCK, T)
    assert T % step == 0, (T, step)
    ctx = jnp.concatenate([
        jnp.concatenate([
            _attend(q[r0:r0 + step, g * grp:(g + 1) * grp], k[:, g], v[:, g],
                    r0, scale) for g in range(nkv)], axis=1)
        for r0 in range(0, T, step)], axis=0)                 # [T, nh, dh]
    out = mm(stored(ctx).reshape(T, nh * dh), p[name + "_o.w_0"])
    if alt is None:
        return out
    h_a, at = alt
    q_a, k_a, v_a = qkv(h_a, cos[at], sin[at])
    ctx_a = jnp.concatenate([
        _attend_own(q_a[:, g * grp:(g + 1) * grp], k[:, g], v[:, g],
                    k_a[:, g], v_a[:, g], at, scale)
        for g in range(nkv)], axis=1)
    return out, mm(stored(ctx_a).reshape(len(at), nh * dh),
                   p[name + "_o.w_0"])


# -- feed-forward and routing ------------------------------------------------

def gated(x, gate, up, down):
    return mm(stored(jax.nn.silu(mm(x, gate)) * mm(x, up)), down)


def weights_of(sigma_sel, cfg):
    """The selected experts' UNBIASED scores [.., k] -> their weights."""
    w = sigma_sel / (sigma_sel.sum(-1, keepdims=True) + 1e-6) \
        if cfg["norm_topk_prob"] else sigma_sel
    return w * cfg["routed_scaling_factor"]


def scores_and_keys(h, p, name):
    """Per row of h and expert, on the host in float64: the router's
    UNBIASED score s = sigmoid(h W_r), which weighs, and the selection's key
    s + b, which chooses."""
    s = np.asarray(_mm(h, p[name + "_router.w_0"], ROUND_WEIGHTS_THROUGH),
                   np.float64)
    sigma = 1.0 / (1.0 + np.exp(-s))
    return sigma, sigma + np.asarray(p[name + "_router_bias"],
                                     np.float64)[None, :]


def route_near(h, p, name, cfg, margin, n_rows):
    """The selection of every row of h (the first `n_rows` are real: the top-k
    of sigmoid score + bias) and, beside it, every selection that differs from
    it by ONE pair the selection's key does not tell apart: an expert in it and
    one outside it whose keys (score + bias) lie within `margin`. Looks `NEAR`
    experts to either side of the edge. On the host: (ids [n, k], weights
    [n, k], source row [m], its ids [m, k], its weights [m, k], the pair's
    distance [m])."""
    if not cfg.get("use_expert_bias", True):
        raise NotImplementedError("the reference routes with the expert bias")
    k = cfg["num_experts_per_tok"]
    sigma, key = scores_and_keys(h, p, name)
    order = np.argsort(-key, axis=1, kind="stable")
    ids = order[:, :k]
    near_n = min(NEAR, k, key.shape[1] - k)
    inside, outside = order[:, k - near_n:k], order[:, k:k + near_n]
    gap = (np.take_along_axis(key, inside, 1)[:, :, None]
           - np.take_along_axis(key, outside, 1)[:, None, :])
    near = gap < margin
    near[n_rows:] = False
    src, a, b = np.nonzero(near)
    swapped = ids[src].copy()
    swapped[np.arange(len(src)), k - near_n + a] = outside[src, b]
    pick = lambda sg, which: weights_of(    # noqa: E731
        np.take_along_axis(sg, which, axis=1), cfg).astype(np.float32)
    return (ids, pick(sigma, ids), src, swapped, pick(sigma[src], swapped),
            gap[src, a, b])


@functools.partial(jax.jit, donate_argnums=0)
def _add_rows(acc, take, rows):
    return acc.at[take].add(rows)


def experts(p, name, h, idx, w, n_experts, into):
    """`into` (given up) + the routed sum over rows h [n, D] selected as idx
    [n, k] (host; -1 selects nothing) with weights w [n, k], expert by expert
    over the rows routed to it."""
    y = into
    w = jnp.asarray(w)
    for e in range(n_experts):
        hit = idx == e                                   # [n, k] host
        rows = np.nonzero(hit.any(axis=1))[0]
        if not len(rows):
            continue
        n = padded(len(rows))
        take = np.zeros(n, np.int32)
        take[:len(rows)] = rows
        we = jnp.sum(jnp.where(jnp.asarray(hit[take]), w[take], 0.0), axis=1)
        we = we * (np.arange(n) < len(rows))             # the padding adds 0
        part = gated(h[take], *(p[f"{name}_experts_{m}"][e]
                                for m in ("gate", "up", "down")))
        y = _add_rows(y, take, part * we[:, None])
    return y


# -- the forward -------------------------------------------------------------

def hidden(p, tokens, cfg, tie_margin=0.0, alt_rows=(0, 0)):
    """tokens [T] -> the final normed hidden states [T, D] float32 and the
    paths beside them: (positions [n] on the host, the widest pair each path
    swapped [n], their hidden states [n, D]); none (n = 0) with `tie_margin`
    0. Paths start at the routed layers, from the rows alt_rows[0] <= r <
    alt_rows[1] and from the paths before, wherever `route_near` finds a
    second selection."""
    eps, n_exp = cfg["norm_eps"], cfg["num_experts"]
    cos, sin = (jnp.asarray(t) for t in rope_cos_sin(
        len(tokens), cfg["head_dim"], cfg["rope_theta"]))
    x = p["tok_emb"][jnp.asarray(tokens)].astype(F32)   # stored as it is
    lo, hi = alt_rows if tie_margin > 0 else (0, 0)
    at = np.zeros(0, np.int32)              # the paths' positions ...
    wide = np.zeros(0)                      # ... widest swapped pairs ...
    xa = jnp.zeros((0, x.shape[1]), F32)    # ... and residuals
    for i, kind in enumerate(cfg["layer_types"][:cfg["num_hidden_layers"]]):
        norm1 = lambda t: rms(t, p[f"l{i}_ln1.scale"], eps)    # noqa: E731
        norm2 = lambda t: rms(t, p[f"l{i}_ln2.scale"], eps)    # noqa: E731
        op = (functools.partial(short_conv, p, f"l{i}_conv")
              if kind == "conv" else
              functools.partial(attention, p, f"l{i}_attn", cos=cos, sin=sin))
        n = len(at)
        if n:
            n_pad = padded(n)
            xa = pad_rows(xa, n_pad)
            out, out_a = op(h=norm1(x), cfg=cfg, alt=(
                norm1(xa), jnp.asarray(np.pad(at, (0, n_pad - n)))))
            x, xa = stored(x + out), stored(xa + out_a)[:n]
        else:
            x = stored(x + op(h=norm1(x), cfg=cfg))
        if i < cfg["num_dense_layers"]:
            ffn = lambda t: gated(norm2(t), *(     # noqa: E731
                p[f"l{i}_ffn_{m}.w_0"] for m in ("gate", "up", "down")))
            if n:
                xa = stored(xa + ffn(pad_rows(xa, padded(n)))[:n])
            x = stored(x + ffn(x))
            continue
        name = f"l{i}_moe"
        if hi > lo or n:
            # candidates: the main forward's rows lo..hi and every path
            cand_at = np.concatenate([np.arange(lo, hi, dtype=np.int32), at])
            cand_x = jnp.concatenate([x[lo:hi], xa], axis=0)
            m = len(cand_at)
            cand_h = pad_rows(norm2(cand_x), padded(m))
            ids, w, src, ids2, w2, dist = route_near(
                cand_h, p, name, cfg, tie_margin, m)
            keep = np.arange(hi - lo, m)    # a path goes on as itself ...
            rows = np.concatenate([keep, src])            # ... and branches
            n2 = padded(len(rows))
            sel = np.pad(rows, (0, n2 - len(rows)))
            ids_all = np.pad(np.concatenate([ids[keep], ids2]),
                             ((0, n2 - len(rows)), (0, 0)),
                             constant_values=-1)    # padding selects nothing
            w_all = np.pad(np.concatenate([w[keep], w2]),
                           ((0, n2 - len(rows)), (0, 0)))
            xa = stored(experts(p, name, cand_h[sel], ids_all, w_all, n_exp,
                                cand_x[sel])[:len(rows)])
            at = cand_at[rows]
            wide = np.concatenate([np.zeros(hi - lo), wide])
            wide = np.concatenate([wide[keep], np.maximum(wide[src], dist)])
        h = norm2(x)
        ids, w, *_ = route_near(h, p, name, cfg, 0.0, 0)
        x = stored(experts(p, name, h, ids, w, n_exp, x))
    out = lambda t: rms(t, p["final_norm.scale"], eps)    # noqa: E731
    return out(x), (at, wide, out(xa))


def head(p, x):
    """x [n, D] -> logits on the host [n, vocab]: x E^T, the embedding cast up
    `COL_BLOCK` vocabulary rows at a time."""
    table = p["tok_emb"]
    return np.concatenate(
        [np.asarray(_mm(x, table[j:j + COL_BLOCK], ROUND_WEIGHTS_THROUGH,
                        transpose=True))
         for j in range(0, table.shape[0], COL_BLOCK)], axis=1)


def logits(p, tokens, cfg, first_row=0, tie_margin=0.0, alt_rows=(0, 0),
           detail=None):
    """Logits of rows first_row.. as a host array [T - first_row, vocab]; with
    `tie_margin` the rows alt_rows[0] <= r < alt_rows[1] hold the envelope of
    their paths (the module's text), shifted back to where the plain row's
    largest logit lies. A dict given as `detail` takes what the envelope was
    made of: the plain rows, each path's position, widest swapped pair and
    centred logits."""
    x, (at, wide, xa) = hidden(p, tokens, cfg, tie_margin, alt_rows)
    out = head(p, x[first_row:])
    if len(at):
        paths = head(p, pad_rows(xa, padded(len(at))))[:len(at)]
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
