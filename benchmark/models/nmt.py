"""Adapter of the encoder-decoder: training through
models.transformer.transformer."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .. import counts
from . import nmt_reference


def vocabs(cfg):
    return {"src_vocab": cfg["src_vocab"], "tgt_vocab": cfg["tgt_vocab"]}


def build_train(cfg, mix):
    from paddle_tpu.models import transformer
    loss, _ = transformer.transformer(
        src_vocab=cfg["src_vocab"], tgt_vocab=cfg["tgt_vocab"],
        max_len=mix["seq_len"], d_model=cfg["d_model"],
        d_inner=cfg["d_inner"], num_heads=cfg["num_heads"],
        num_layers=cfg["num_layers"], dropout=cfg["dropout"],
        label_smooth=cfg["label_smooth"])
    return loss


def param_names(cfg):
    names = ["src_emb", "tgt_emb", "proj.w_0", "proj.w_1"]
    for i in range(cfg["num_layers"]):
        for blk, atts, lns in ((f"enc{i}", ("attn",), (1, 2)),
                               (f"dec{i}", ("self", "cross"), (1, 2, 3))):
            names += [f"{blk}_{a}_{x}.w_0" for a in atts for x in "qkvo"]
            names += [f"{blk}_ffn_fc{j}.w_{k}" for j in (1, 2) for k in (0, 1)]
            names += [f"{blk}_ln{j}.{x}" for j in lns
                      for x in ("scale", "bias")]
    return names


def reference_loss(cfg, params, batch):
    f = jax.jit(lambda p, s, t, y, n: nmt_reference.row_loss_sum(
        p, s, t, y, n, cfg))
    feed = batch["feed"]
    with jax.default_matmul_precision("highest"):
        total = sum(float(f(params, *(jnp.asarray(a) for a in row)))
                    for row in zip(feed["src"], feed["tgt"], feed["lbl"],
                                   feed["tgt@SEQLEN"]))
    return total / batch["tokens"]


def train_flops(cfg, mix, batch):
    """Operations forward and backward need for the batch's REAL tokens:
    what padding costs is not needed work, so it lowers `mfu`."""
    d, di, dh = cfg["d_model"], cfg["d_inner"], cfg["head_dim"]
    L, H = cfg["num_layers"], cfg["num_heads"]
    src, tgt = batch["src_len"].astype(float), batch["tgt_len"].astype(float)
    enc = L * counts.block_matmul_params(d, di)
    # per decoder block: self attention, feed-forward and the cross
    # attention's q and o projections see target tokens; its k and v
    # projections see source tokens
    dec_tgt = L * (counts.block_matmul_params(d, di) + 2 * d * d) \
        + d * cfg["tgt_vocab"]
    dec_src = L * 2 * d * d
    flops = (counts.matmul_flops(src.sum(), enc + dec_src, True)
             + counts.matmul_flops(tgt.sum(), dec_tgt, True))
    for s, t in zip(src, tgt):
        flops += L * (counts.attention_flops(H, s, s, dh, False, True)
                      + counts.attention_flops(H, t, t, dh, True, True)
                      + counts.attention_flops(H, t, s, dh, False, True))
    return flops


def flash_calls(cfg, mix, rows):
    """The fused attention calls of one step, at the padded shapes they run
    at: encoder self (full), decoder self (causal), cross (full)."""
    T, dh, bh = mix["seq_len"], cfg["head_dim"], rows * cfg["num_heads"]
    calls = []
    for causal in (False, True, False):
        f = counts.attention_flops(bh, T, T, dh, causal, False)
        both = counts.attention_flops(bh, T, T, dh, causal, True)
        calls += [(f, counts.flash_call_bytes(bh, T, T, dh, False)),
                  (both - f, counts.flash_call_bytes(bh, T, T, dh, True))]
    return calls * cfg["num_layers"]
