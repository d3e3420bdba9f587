"""Plain reference of the A.X-K1 block (configs/axk1-ep16.json): latent
attention with K and V EXPANDED through `kv_b_proj` (never the absorbed form
the program runs, so the two forms check each other), YaRN rotary positions,
pre-norm RMSNorm residuals, a gated SiLU feed-forward, and the routed layer
with its experts looped one by one over the tokens routed to them.

float32 `jax.numpy` under `jax.default_matmul_precision("highest")` (the
caller sets it), a full causal forward, no cache, no kernel, no batching.
The parameters come as stored (bfloat16) and are cast up a matrix at a time;
rows go through in blocks of `ROW_BLOCK`, attention a group of heads at a
time, so that 17,408 positions at width 7168 fit beside a live engine.

The equations (the configuration's `assumed` lists each departure from the
source):

  h = x + MLA(rms(x));  y = h + F(rms(h));  final rms; untied head; no bias
  MLA: c_q = rms(x W_qa); [q_nope_h | q_pe_h] = c_q W_qb
       [c_kv | k_pe] = x W_kva; c_kv <- rms(c_kv); k_pe, q_pe_h <- rope
       [k_nope_h | v_h] = c_kv W_kvb
       score = (q_nope_h . k_nope_h + q_pe_h . k_pe) * s, causal softmax
       o = concat_h(sum p v_h) W_o
  F: down(silu(gate x) * up x) in the dense layers; from `first_k_dense_replace`
     on: sum over (selected AND held) of w_e E_e(x) + E_shared(x), with
     sigma = sigmoid(x W_r), the top-k of ALL experts, w = sigma_sel /
     sum(sigma_sel) * routed_scaling_factor.

The top-k is the one step here that is not continuous: where an expert
inside the selection and one outside it score within rounding of each other,
which of them a program in the stated precision (bfloat16) selects is not
determined, and a held expert's whole output rides on it. With `tie_margin`
> 0 the forward therefore follows, for the rows it is asked to (`alt_rows`),
BOTH resolutions of every such pair that involves a held expert ("paths":
the row's own residual, re-run through the layers above against the main
forward's latent rows), and a row's logits are the ENVELOPE of its paths:
max over paths of (logits - their largest). The largest is then 0 and a
token reads how far it lies below the top of the path that favours it most:
a token is judged by the selection that explains it, and by no selection
the scores do not allow. With `tie_margin` 0 there is one path, the plain
forward.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

ROW_BLOCK = 1024
ATTN_BLOCK = 256        # query rows of one attention call ...
HEAD_GROUP = 4          # ... and its heads: scores [4, 256, T] a call
COL_BLOCK = 4096        # columns of a matrix cast up at a time
NEAR = 4                # experts either side of the selection's edge looked at
F32 = jnp.float32
#: a dtype to round every matrix through before it is cast up (None: as
#: stored): the reading "one precision below" that a cell's limit has to
#: refuse, without a second copy of the parameters
ROUND_WEIGHTS_THROUGH = None


# -- positions -------------------------------------------------------------

def yarn_inv_freq(rope_dim, theta, scaling):
    """YaRN's per-frequency blend, float64 on the host."""
    i = np.arange(0, rope_dim, 2, dtype=np.float64)
    extra = 1.0 / theta ** (i / rope_dim)
    if not scaling or scaling["factor"] <= 1:
        return extra
    inter = extra / scaling["factor"]

    def dim_of(rotations):
        return (rope_dim * math.log(scaling["original_max_position_embeddings"]
                                    / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))
    low = max(math.floor(dim_of(scaling["beta_fast"])), 0)
    high = min(math.ceil(dim_of(scaling["beta_slow"])), rope_dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(rope_dim // 2) - low) / (high - low), 0, 1)
    mask = 1.0 - ramp                 # 1: keep the frequency, 0: stretch it
    return inter * (1 - mask) + extra * mask


def mscale(factor, a):
    return 1.0 if factor <= 1 else 0.1 * a * math.log(factor) + 1.0


def rope_cos_sin(n, rope_dim, theta, scaling):
    """cos, sin [n, rope_dim/2] (float32) for positions 0..n-1."""
    angle = np.arange(n, dtype=np.float64)[:, None] \
        * yarn_inv_freq(rope_dim, theta, scaling)[None, :]
    m = 1.0
    if scaling:
        m = (mscale(scaling["factor"], scaling["mscale"])
             / mscale(scaling["factor"], scaling["mscale_all_dim"]))
    return ((np.cos(angle) * m).astype(np.float32),
            (np.sin(angle) * m).astype(np.float32))


def softmax_scale(cfg):
    s = (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5
    sc = cfg.get("rope_scaling")
    if sc:
        s *= mscale(sc["factor"], sc["mscale_all_dim"]) ** 2
    return s


def rope(x, cos, sin):
    """x [T, .., d] with pairs (i, i + d/2); cos, sin [T, d/2]."""
    half = x.shape[-1] // 2
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (half,)
    c, s = cos.reshape(shape), sin.reshape(shape)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * c - b * s, b * c + a * s], axis=-1)


# -- pieces ----------------------------------------------------------------

def rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(F32)


MANTISSA_BITS = {"float16": 10, "bfloat16": 7, "float8_e4m3fn": 3}


def round_through(x, dtype):
    """x (float32) as a value of `dtype`'s MANTISSA would hold it, round to
    nearest, by arithmetic on the bits: a convert to a type the chip does
    not have (float8 on a v5e) is normalised away by the compiler, and the
    "rounded" reference then reads as the exact one (my chip run, PR 36).
    The exponent's range is not narrowed."""
    drop = 23 - MANTISSA_BITS[jnp.dtype(dtype).name]
    bits = jax.lax.bitcast_convert_type(x.astype(F32), jnp.uint32)
    bits = (bits + jnp.uint32(1 << (drop - 1))) & jnp.uint32(
        (0xFFFFFFFF >> drop) << drop)
    return jax.lax.bitcast_convert_type(bits, F32)


@functools.partial(jax.jit, static_argnames=("through",))
def _mm(x, w, through):
    w = w.astype(F32)
    if through is not None:
        w = round_through(w, through)
    return x @ w


def mm(x, w):
    """x [n, a] float32 @ w [a, b] as stored, cast up here, `COL_BLOCK`
    columns at a time (the widest matrix, 7168 x 20480, is 587 MB in
    float32 and more inside an exact product)."""
    if w.shape[1] <= COL_BLOCK:
        return _mm(x, w, ROUND_WEIGHTS_THROUGH)
    return jnp.concatenate(
        [_mm(x, w[:, j:j + COL_BLOCK], ROUND_WEIGHTS_THROUGH)
         for j in range(0, w.shape[1], COL_BLOCK)], axis=1)


def by_blocks(fn, *arrays, step=ROW_BLOCK):
    """fn over row blocks of the arrays (same leading length), concatenated.
    Each block is waited for: a host loop that runs ahead of the device
    holds every block's temporaries at once (5 GB at 17k rows)."""
    n = arrays[0].shape[0]
    step = min(step, n)
    assert n % step == 0, (n, step)
    return jnp.concatenate(
        [jax.block_until_ready(fn(*(a[i:i + step] for a in arrays)))
         for i in range(0, n, step)], axis=0)


def _gated(x, gate, up, down):
    """One matrix cast up at a time (`mm`), the inner width in
    `COL_BLOCK`s: a block of it is a gated pair of its own."""
    F = gate.shape[1]
    return sum(mm(jax.nn.silu(mm(x, gate[:, j:j + COL_BLOCK]))
                  * mm(x, up[:, j:j + COL_BLOCK]), down[j:j + COL_BLOCK])
               for j in range(0, F, COL_BLOCK))


def gated_ffn(x, gate, up, down, into=None):
    """`into` + the pair over x; `into` is given up (None: zeros)."""
    into = jnp.zeros(x.shape, F32) if into is None else into
    return add_by_blocks(into, lambda b: _gated(b, gate, up, down), x)


def padded(n):
    """n rounded up to a length the row blocks divide, and few of them to
    compile: `ROW_BLOCK`, beyond it a power of two."""
    return max(ROW_BLOCK, 1 << (n - 1).bit_length())


@functools.partial(jax.jit, donate_argnums=0)
def _add_at(acc, r0, block):
    """acc[r0 : r0 + len(block)] += block, in acc's own memory: a residual
    of 17k rows is 499 MB, and a copy an update would be the peak."""
    old = jax.lax.dynamic_slice(acc, (r0, 0), block.shape)
    return jax.lax.dynamic_update_slice(acc, old + block, (r0, 0))


@functools.partial(jax.jit, donate_argnums=0)
def _add_rows(acc, take, rows):
    """acc[take] += rows, in acc's own memory."""
    return acc.at[take].add(rows)


def add_by_blocks(acc, fn, *arrays):
    """acc += fn over row blocks of the arrays, a block at a time."""
    n = arrays[0].shape[0]
    step = min(ROW_BLOCK, n)
    assert n % step == 0, (n, step)
    for i in range(0, n, step):
        acc = jax.block_until_ready(
            _add_at(acc, i, fn(*(a[i:i + step] for a in arrays))))
    return acc


def pad_rows(a, n):
    """a [m, ..] -> [n, ..], zeros below (few shapes to compile)."""
    return jnp.concatenate(
        [a, jnp.zeros((n - a.shape[0],) + a.shape[1:], a.dtype)], axis=0)


# -- attention ---------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("scale",))
def _attend(q_nope, q_pe, k_nope, k_pe, v, row0, scale):
    """One block of query rows, one group of heads, over every key.
    q_nope [r, g, dn], q_pe [r, g, dr], k_nope [T, g, dn], k_pe [T, dr],
    v [T, g, dv]; query row i sits at position row0 + i."""
    s = (jnp.einsum("rgd,tgd->grt", q_nope, k_nope)
         + jnp.einsum("rgd,td->grt", q_pe, k_pe)) * scale
    t = jnp.arange(k_pe.shape[0])[None, None, :]
    r = row0 + jnp.arange(q_nope.shape[0])[None, :, None]
    p = jax.nn.softmax(jnp.where(t <= r, s, -jnp.inf), axis=-1)
    return jnp.einsum("grt,tgd->rgd", p, v)


@functools.partial(jax.jit, static_argnames=("scale",))
def _attend_own(q_nope, q_pe, k_nope, k_pe, v, own_k, own_pe, own_v, at,
                scale):
    """`_attend` for query rows that are not the main forward's: row i sits
    at position at[i], attends the keys BEFORE it (k_nope, k_pe, v: the main
    forward's) and its own key and value (own_k [r, g, dn], own_pe [r, dr],
    own_v [r, g, dv])."""
    s = (jnp.einsum("rgd,tgd->grt", q_nope, k_nope)
         + jnp.einsum("rgd,td->grt", q_pe, k_pe)) * scale
    own = (jnp.einsum("rgd,rgd->gr", q_nope, own_k)
           + jnp.einsum("rgd,rd->gr", q_pe, own_pe)) * scale
    t = jnp.arange(k_pe.shape[0])[None, None, :]
    s = jnp.where(t < at[None, :, None], s, -jnp.inf)
    p = jax.nn.softmax(jnp.concatenate([s, own[..., None]], -1), axis=-1)
    return (jnp.einsum("grt,tgd->rgd", p[..., :-1], v)
            + p[..., -1].T[..., None] * own_v)


def mla(p, name, x, cfg, cos, sin, into, alt=None):
    """`into` [T, H] + the attention of x [T, H] (already normed); `into` is
    given up. With `alt` = (x_alt [n, H] normed, at [n] positions, into_alt
    [n, H]), also those rows': each over the main rows' keys before its
    position and its own -> ([T, H], [n, H])."""
    T = x.shape[0]
    nh = cfg["num_attention_heads"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    c, eps = cfg["kv_lora_rank"], cfg["rms_norm_eps"]
    scale = softmax_scale(cfg)

    def latents(rows, cs, sn):
        """(c_q, c_kv, k_pe) of rows at the positions of cs, sn."""
        c_q = by_blocks(lambda b: rms(mm(b, p[name + "_qa.w_0"]),
                                      p[name + "_qa_norm.scale"], eps), rows)
        kv = by_blocks(lambda b: mm(b, p[name + "_kva.w_0"]), rows)
        c_kv = rms(kv[:, :c], p[name + "_kva_norm.scale"], eps)
        k_pe = rope(kv[:, c:], cs, sn)
        if cfg.get("latent_dtype"):
            # the row as a cache of that precision would hold it: the reading
            # "one precision below" that a cell's limit has to refuse
            c_kv, k_pe = (round_through(t, cfg["latent_dtype"])
                          for t in (c_kv, k_pe))
        return c_q, c_kv, k_pe

    c_q, c_kv, k_pe = latents(x, cos, sin)
    del x                           # the caller's temporary: 499 MB at 17k
    if alt is not None:
        x_alt, at, out_a = alt
        cos_a, sin_a = cos[at], sin[at]
        cq_a, ckv_a, kpe_a = latents(x_alt, cos_a, sin_a)
    w_qb = p[name + "_qb.w_0"].reshape(-1, nh, dn + dr)
    w_kvb = p[name + "_kvb.w_0"].reshape(c, nh, dn + dv)
    w_o = p[name + "_o.w_0"].reshape(nh, dv, -1)
    step = min(ATTN_BLOCK, T)
    assert T % step == 0, (T, step)
    out = into
    for g in range(0, nh, min(HEAD_GROUP, nh)):
        hs = slice(g, g + min(HEAD_GROUP, nh))
        n_g = w_kvb[:, hs].shape[1]
        w_kv = w_kvb[:, hs].reshape(c, -1)
        k_and_v = mm(c_kv, w_kv).reshape(T, n_g, -1)
        k_nope, v = k_and_v[..., :dn], k_and_v[..., dn:]
        w_q = w_qb[:, hs].reshape(w_qb.shape[0], -1)
        w_og = w_o[hs].reshape(n_g * dv, -1)
        for r0 in range(0, T, step):
            q = mm(c_q[r0:r0 + step], w_q).reshape(step, n_g, dn + dr)
            q_pe = rope(q[..., dn:], cos[r0:r0 + step], sin[r0:r0 + step])
            ctx = _attend(q[..., :dn], q_pe, k_nope, k_pe, v, r0, scale)
            out = _add_at(out, r0, mm(ctx.reshape(step, n_g * dv), w_og))
        if alt is not None:
            own = mm(ckv_a, w_kv).reshape(len(at), n_g, -1)
            q = mm(cq_a, w_q).reshape(len(at), n_g, dn + dr)
            q_pe = rope(q[..., dn:], cos_a, sin_a)
            ctx = by_blocks(
                lambda *b: _attend_own(b[0], b[1], k_nope, k_pe, v, *b[2:],
                                       scale=scale),
                q[..., :dn], q_pe, own[..., :dn], kpe_a, own[..., dn:], at,
                step=ATTN_BLOCK)
            out_a = out_a + mm(ctx.reshape(len(at), n_g * dv), w_og)
        jax.block_until_ready(out)      # as in `by_blocks`
    return out if alt is None else (out, out_a)


# -- routing -----------------------------------------------------------------

def check_routing(cfg):
    if cfg.get("topk_method", "none") != "none":
        raise NotImplementedError(
            f"the reference routes with topk_method 'none', not "
            f"{cfg['topk_method']!r}")


def weights_of(sigma_sel, cfg):
    """The selected experts' scores [.., k] -> their weights."""
    w = sigma_sel / sigma_sel.sum(-1, keepdims=True) \
        if cfg["norm_topk_prob"] else sigma_sel
    return w * cfg["routed_scaling_factor"]


def route(x, w_router, cfg):
    """(selected expert ids [T, k] on the host, their weights [T, k])."""
    check_routing(cfg)
    sigma = jax.nn.sigmoid(by_blocks(lambda b: mm(b, w_router), x))
    top, idx = jax.lax.top_k(sigma, cfg["num_experts_per_tok"])
    return np.asarray(idx), weights_of(top, cfg)


def route_near(x, w_router, cfg, held, margin, n_rows):
    """The selection of every row of x (the first `n_rows` are real) and, beside it, every selection that
    differs from it by ONE pair the scores do not tell apart: an expert in
    it and one outside it whose router scores (before the sigmoid) lie
    within `margin`, where at least one of the two is held (a pair of absent
    experts moves the weights' normalisation by the margin and nothing
    else). Looks `NEAR` experts to either side of the edge. Returns
    (ids [n, k], weights [n, k], source row [m], its ids [m, k], its
    weights [m, k]), on the host."""
    check_routing(cfg)
    k = cfg["num_experts_per_tok"]
    s = np.asarray(by_blocks(lambda b: mm(b, w_router), x), np.float64)
    sigma = 1.0 / (1.0 + np.exp(-s))
    order = np.argsort(-s, axis=1, kind="stable")
    ids = order[:, :k]
    inside, outside = order[:, k - NEAR:k], order[:, k:k + NEAR]
    gap = (np.take_along_axis(s, inside, 1)[:, :, None]
           - np.take_along_axis(s, outside, 1)[:, None, :])
    is_held = np.zeros(s.shape[1], bool)
    is_held[list(held)] = True
    near = (gap < margin) & (is_held[inside][:, :, None]
                             | is_held[outside][:, None, :])
    near[n_rows:] = False
    src, a, b = np.nonzero(near)
    swapped = ids[src].copy()
    swapped[np.arange(len(src)), k - NEAR + a] = outside[src, b]
    pick = lambda sg, which: weights_of(
        np.take_along_axis(sg, which, axis=1), cfg).astype(np.float32)
    return (ids, pick(sigma, ids), src, swapped, pick(sigma[src], swapped),
            gap[src, a, b])


def experts(p, name, x, idx, w, held, into):
    """`into` (given up) + the held experts' part of the routed sum over
    rows x [n, H] selected as idx [n, k] (host) with weights w [n, k],
    expert by expert over the rows routed to it. `held[j]` is the expert
    whose weights sit at index j of the stacks."""
    y = into
    w = jnp.asarray(w)
    for j, e in enumerate(held):
        hit = idx == e                                   # [n, k] host
        rows = np.nonzero(hit.any(axis=1))[0]
        if not len(rows):
            continue
        n = padded(len(rows))
        take = np.zeros(n, np.int32)
        take[:len(rows)] = rows
        we = jnp.sum(jnp.where(jnp.asarray(hit[take]), w[take], 0.0), axis=1)
        we = we * (np.arange(n) < len(rows))             # the padding adds 0
        part = _gated(x[take], *(p[f"{name}_experts_{m}"][j]
                                 for m in ("gate", "up", "down")))
        y = _add_rows(y, take, part * we[:, None])
    return y


def shared(p, name, x, into):
    return gated_ffn(x, *(p[f"{name}_shared_{n}.w_0"]
                          for n in ("gate", "up", "down")), into=into)


def moe(p, name, x, cfg, held, into=None):
    """`into` (given up; None: zeros) + the routed layer: the held experts'
    part plus the shared expert."""
    idx, w = route(x, p[name + "_router.w_0"], cfg)
    return experts(p, name, x, idx, w, held, shared(p, name, x, into))


# -- the forward -------------------------------------------------------------

def hidden(p, tokens, cfg, held, tie_margin=0.0, alt_rows=(0, 0)):
    """tokens [T] -> the final normed hidden states [T, H] float32 and the
    paths beside them: (positions [n] on the host, the widest pair each
    path swapped [n], their hidden states [n, H]); none (n = 0) with
    `tie_margin` 0. Paths start at the routed layers, from the rows
    alt_rows[0] <= r < alt_rows[1] and from the paths before, wherever
    `route_near` finds a second selection."""
    eps = cfg["rms_norm_eps"]
    cos, sin = rope_cos_sin(len(tokens), cfg["qk_rope_head_dim"],
                            cfg["rope_theta"], cfg.get("rope_scaling"))
    cos, sin = jnp.asarray(cos), jnp.asarray(sin)
    x = p["tok_emb"][jnp.asarray(tokens)].astype(F32)
    lo, hi = alt_rows if tie_margin > 0 else (0, 0)
    at = np.zeros(0, np.int32)              # the paths' positions ...
    wide = np.zeros(0)                      # ... widest swapped pairs ...
    xa = jnp.zeros((0, x.shape[1]), F32)    # ... and residuals
    for i in range(cfg["num_hidden_layers"]):
        norm1 = lambda t: rms(t, p[f"l{i}_ln1.scale"], eps)
        norm2 = lambda t: rms(t, p[f"l{i}_ln2.scale"], eps)
        n = len(at)
        if n:
            n_pad = padded(n)
            xa = pad_rows(xa, n_pad)
            x, xa = mla(p, f"l{i}_attn", norm1(x), cfg, cos, sin, x,
                        alt=(norm1(xa),
                             jnp.asarray(np.pad(at, (0, n_pad - n))), xa))
            xa = xa[:n]
        else:
            x = mla(p, f"l{i}_attn", norm1(x), cfg, cos, sin, x)
        if i < cfg["first_k_dense_replace"]:
            ffn = lambda t, into: gated_ffn(
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
            ids, w, src, ids2, w2, dist = route_near(
                cand_h, p[name + "_router.w_0"], cfg, held, tie_margin, m)
            keep = np.arange(hi - lo, m)    # a path goes on as itself ...
            rows = np.concatenate([keep, src])            # ... and branches
            n2 = padded(len(rows))
            sel = np.pad(rows, (0, n2 - len(rows)))
            ids_all = np.concatenate([ids[keep], ids2])
            w_all = np.concatenate([w[keep], w2])
            # a padding row selects no held expert
            ids_all = np.pad(ids_all, ((0, n2 - len(rows)), (0, 0)),
                             constant_values=-1)
            w_all = np.pad(w_all, ((0, n2 - len(rows)), (0, 0)))
            hs = cand_h[sel]
            xa = experts(p, name, hs, ids_all, w_all, held,
                         shared(p, name, hs, cand_x[sel]))[:len(rows)]
            at = cand_at[rows]
            wide = np.concatenate([np.zeros(hi - lo), wide])
            wide = np.concatenate([wide[keep], np.maximum(wide[src], dist)])
        x = moe(p, name, norm2(x), cfg, held, into=x)
    out = lambda t: rms(t, p["final_norm.scale"], eps)
    return out(x), (at, wide, out(xa))


def head(p, x):
    """x [n, H] -> logits on the host [n, vocab]."""
    step = min(ROW_BLOCK, x.shape[0])
    return np.concatenate([np.asarray(mm(x[i:i + step], p["lm_head.w_0"]))
                           for i in range(0, x.shape[0], step)], axis=0)


def logits(p, tokens, cfg, held, first_row=0, tie_margin=0.0,
           alt_rows=(0, 0), detail=None):
    """Logits of rows first_row.. as a host array [T - first_row, vocab];
    with `tie_margin` the rows alt_rows[0] <= r < alt_rows[1] hold the
    envelope of their paths (the module's text), shifted back to where the
    plain row's largest logit lies. A dict given as `detail` takes what the
    envelope was made of: the plain rows, each path's position, widest
    swapped pair and centred logits."""
    x, (at, wide, xa) = hidden(p, tokens, cfg, held, tie_margin, alt_rows)
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
