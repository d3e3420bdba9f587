"""Plain reference of the encoder-decoder (vaswani-big-nmt): forward pass and
the label-smoothed, length-masked loss of one sentence pair."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import reference_blocks as rb


def logits(p, src, tgt, cfg):
    """src [T], tgt [T] padded token ids -> decoder logits [T, tgt_vocab]."""
    d, heads = cfg["d_model"], cfg["num_heads"]
    pe = rb.sinusoid(src.shape[0], d)
    enc = rb.embed(p, "src_emb", src, d, pe)
    for i in range(cfg["num_layers"]):
        a = rb.attention(p, f"enc{i}_attn", enc, enc, heads, causal=False)
        enc = rb.add_norm(p, f"enc{i}_ln1", a, enc)
        enc = rb.add_norm(p, f"enc{i}_ln2", rb.ffn(p, f"enc{i}_ffn", enc), enc)
    x = rb.embed(p, "tgt_emb", tgt, d, pe)
    for i in range(cfg["num_layers"]):
        a = rb.attention(p, f"dec{i}_self", x, x, heads, causal=True)
        x = rb.add_norm(p, f"dec{i}_ln1", a, x)
        c = rb.attention(p, f"dec{i}_cross", x, enc, heads, causal=False)
        x = rb.add_norm(p, f"dec{i}_ln2", c, x)
        x = rb.add_norm(p, f"dec{i}_ln3", rb.ffn(p, f"dec{i}_ffn", x), x)
    return x @ p["proj.w_0"] + p["proj.w_1"]


def row_loss_sum(p, src, tgt, lbl, tgt_len, cfg):
    """Sum over the first tgt_len positions of
    (1-eps) * CE(label) + eps * mean over the vocabulary of -log p."""
    eps = cfg["label_smooth"]
    logp = jax.nn.log_softmax(logits(p, src, tgt, cfg), axis=-1)
    hard = -jnp.take_along_axis(logp, lbl[:, None], axis=-1)[:, 0]
    tok = (1.0 - eps) * hard + eps * -jnp.mean(logp, axis=-1)
    return jnp.sum(jnp.where(jnp.arange(tgt.shape[0]) < tgt_len, tok, 0.0))
