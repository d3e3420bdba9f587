"""Manifold-constrained hyper-connections (mHC, arXiv:2512.24880): a residual
of `n` streams, mixed around every sub-layer by maps the streams themselves
give.

The residual is X [n, d] a row of the tick. A sub-layer F with its own
pre-norm sees ONE mixed row and its output goes back to all n:

    x~     = RMSNorm_eps(flatten(X))             [n d], no learned scale
    H_pre  = sigmoid(a_pre  (x~ P_pre)  + b_pre)         [n]
    H_post = 2 sigmoid(a_post (x~ P_post) + b_post)      [n]
    H_res  = Sinkhorn(exp(a_res mat(x~ P_res) + b_res))  [n, n]
    X     <- H_res X + H_post^T F(norm(H_pre X))

Sinkhorn: `iters` rounds of dividing each row by (its sum + eps), then each
column by (its sum + eps): H_res ends (nearly) doubly stochastic, so the
streams' mean is carried through a layer unchanged whatever the depth. The
three projections are ONE stored matrix P [n d, 2 n + n^2] (columns: pre,
post, res row-major), `a` [3] and `b` [2 n + n^2] float32. The maps are
float32; X, the mixed row and the result are the activations' dtype.

n = 4: there is nothing here for a kernel. The ops are plain `jax.numpy`
under ONE named scope, `hyper_connection`, which XLA fuses with what stands
around them and a device trace sums.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..framework.registry import register_op

SCOPE = "hyper_connection"


def sinkhorn(m, iters, eps):
    """m [.., n, n] positive -> rows, then columns, divided by (their sum +
    eps), `iters` times."""
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + eps)
    return m


def maps(x, p, a, b, n, iters, eps, norm_eps):
    """x [N, n*d] -> (H_pre [N, n], H_post [N, n], H_res [N, n, n]), float32."""
    f32 = jnp.float32
    xf = x.astype(f32)
    xt = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True)
                            + norm_eps)
    z = jnp.dot(xt.astype(p.dtype), p, preferred_element_type=f32)
    a, b = a.astype(f32), b.astype(f32)
    h_pre = jax.nn.sigmoid(a[0] * z[:, :n] + b[:n])
    h_post = 2.0 * jax.nn.sigmoid(a[1] * z[:, n:2 * n] + b[n:2 * n])
    m = jnp.exp(a[2] * z[:, 2 * n:] + b[2 * n:]).reshape(-1, n, n)
    return h_pre, h_post, sinkhorn(m, iters, eps)


def mix_in(x, p, a, b, n, iters, eps, norm_eps):
    """x [N, n*d] -> (the mixed row H_pre X [N, d] in x's dtype, H_post,
    H_res flattened [N, n*n])."""
    with jax.named_scope(SCOPE):
        h_pre, h_post, h_res = maps(x, p, a, b, n, iters, eps, norm_eps)
        streams = x.astype(jnp.float32).reshape(x.shape[0], n, -1)
        u = jnp.einsum("nk,nkd->nd", h_pre, streams)
        return u.astype(x.dtype), h_post, h_res.reshape(-1, n * n)


def mix_out(x, y, h_post, h_res, n):
    """X <- H_res X + H_post^T y: x [N, n*d], y [N, d] -> [N, n*d]."""
    with jax.named_scope(SCOPE):
        f32 = jnp.float32
        streams = x.astype(f32).reshape(x.shape[0], n, -1)
        out = jnp.einsum("nij,njd->nid", h_res.reshape(-1, n, n), streams) \
            + h_post[:, :, None] * y.astype(f32)[:, None, :]
        return out.reshape(x.shape).astype(x.dtype)


def _flat(t):
    return t.reshape(-1, t.shape[-1])


@register_op("hyper_connection_pre", stop_gradient=True)
def _pre_op(ctx, ins, attrs):
    x = ins["X"][0]
    u, h_post, h_res = mix_in(
        _flat(x), ins["P"][0], ins["A"][0], ins["B"][0], attrs["mult"],
        attrs["sinkhorn_iters"], attrs["eps"], attrs["norm_eps"])
    return {"Out": [u.reshape(x.shape[:-1] + (u.shape[-1],))],
            "HPost": [h_post], "HRes": [h_res]}


@register_op("hyper_connection_post", stop_gradient=True)
def _post_op(ctx, ins, attrs):
    x = ins["X"][0]
    out = mix_out(_flat(x), _flat(ins["Y"][0]), ins["HPost"][0],
                  ins["HRes"][0], attrs["mult"])
    return {"Out": [out.reshape(x.shape)]}


@register_op("hyper_connection_exit", stop_gradient=True)
def _exit_op(ctx, ins, attrs):
    """The streams' sum (float32 inside): [.., n*d] -> [.., d]."""
    x = ins["X"][0]
    with jax.named_scope(SCOPE):
        out = jnp.sum(x.astype(jnp.float32).reshape(
            x.shape[:-1] + (attrs["mult"], -1)), axis=-2)
    return {"Out": [out.astype(x.dtype)]}
