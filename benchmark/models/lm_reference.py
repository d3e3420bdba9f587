"""Plain reference of the decoder-only language model (fairseq-lm-big)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import reference_blocks as rb


def logits(p, tokens, cfg):
    """tokens [T] int -> logits [T, vocab], float32, full causal forward."""
    d = cfg["d_model"]
    x = rb.embed(p, "tok_emb", tokens, d, rb.sinusoid(tokens.shape[0], d))
    for i in range(cfg["num_layers"]):
        a = rb.attention(p, f"l{i}_attn", x, x, cfg["num_heads"], causal=True)
        x = rb.add_norm(p, f"l{i}_ln1", a, x)
        x = rb.add_norm(p, f"l{i}_ln2", rb.ffn(p, f"l{i}_ffn", x), x)
    return x @ p["lm_head.w_0"] + p["lm_head.w_1"]


def row_loss_sum(p, tokens, targets, cfg):
    """Sum over positions of the cross-entropy of one full row."""
    logp = jax.nn.log_softmax(logits(p, tokens, cfg), axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, targets[:, None], axis=-1))
