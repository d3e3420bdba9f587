"""Plain jax.numpy pieces the references share: float32 throughout, run under
jax.default_matmul_precision("highest"), no kernel, no cache, no batching.
They follow 'Attention Is All You Need' section 3 as models/transformer.py
builds it (post-LayerNorm, ReLU, sinusoidal positions)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def sinusoid(max_len: int, d_model: int) -> np.ndarray:
    pos = np.arange(max_len, dtype=np.float64)[:, None]
    i = np.arange(d_model)[None, :]
    angle = pos / np.power(10000.0, 2 * (i // 2) / d_model)
    return np.where(i % 2 == 0, np.sin(angle), np.cos(angle)).astype(np.float32)


def layer_norm(x, scale, bias, eps=1e-5):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * scale + bias


def attention(p, prefix, x_q, x_kv, num_heads, causal):
    """x_q [Tq, d], x_kv [Tk, d] -> [Tq, d]; every key is attended (no padding
    mask: see the configuration's `assumed`)."""
    t_q, d = x_q.shape
    t_k = x_kv.shape[0]
    dh = d // num_heads
    q = (x_q @ p[prefix + "_q.w_0"]).reshape(t_q, num_heads, dh)
    k = (x_kv @ p[prefix + "_k.w_0"]).reshape(t_k, num_heads, dh)
    v = (x_kv @ p[prefix + "_v.w_0"]).reshape(t_k, num_heads, dh)
    s = jnp.einsum("qhd,khd->hqk", q, k) * dh ** -0.5
    if causal:
        s = jnp.where(jnp.arange(t_k)[None, :] <= jnp.arange(t_q)[:, None],
                      s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    ctx = jnp.einsum("hqk,khd->qhd", w, v).reshape(t_q, d)
    return ctx @ p[prefix + "_o.w_0"]


def ffn(p, prefix, x):
    h = jax.nn.relu(x @ p[prefix + "_fc1.w_0"] + p[prefix + "_fc1.w_1"])
    return h @ p[prefix + "_fc2.w_0"] + p[prefix + "_fc2.w_1"]


def add_norm(p, prefix, x, residual):
    return layer_norm(x + residual, p[prefix + ".scale"], p[prefix + ".bias"])


def embed(p, name, tokens, d_model, pe):
    return p[name][tokens] * d_model ** 0.5 + pe[: tokens.shape[0]]
