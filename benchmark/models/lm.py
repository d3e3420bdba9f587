"""Adapter of the decoder-only language model: training through
models.transformer.transformer_lm, serving through PagedKVEngine from the
same parameter names."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .. import counts
from . import lm_reference


def _dims(cfg):
    return dict(vocab=cfg["vocab"], d_model=cfg["d_model"],
                d_inner=cfg["d_inner"], num_heads=cfg["num_heads"],
                num_layers=cfg["num_layers"], dropout=cfg["dropout"])


def vocabs(cfg):
    return {"vocab": cfg["vocab"]}


def build_train(cfg, mix):
    """The training graph in the default programs; returns the loss."""
    from paddle_tpu.models import transformer
    loss, _ = transformer.transformer_lm(max_len=mix["seq_len"], **_dims(cfg))
    return loss


def build_weights(cfg, seed):
    """Weights alone, from the startup program on the device: the forward
    graph is built only for its parameters (nothing of it is compiled)."""
    import paddle_tpu as pt
    from paddle_tpu.models import transformer
    with pt.core.unique_name.guard():
        transformer.transformer_lm(max_len=cfg["max_len"], is_test=True,
                                   **_dims(cfg))
    pt.default_startup_program().random_seed = seed
    pt.Executor().run(pt.default_startup_program())
    return pt.global_scope()


def build_engine(cfg, spec, scope):
    from paddle_tpu import serving
    if spec["class"] != "PagedKVEngine":
        raise ValueError(f"unknown engine class {spec['class']!r}")
    return serving.PagedKVEngine(
        n_slots=spec["n_slots"], max_len=spec["max_len"],
        block_size=spec["block_size"], n_blocks=spec["n_blocks"],
        scope=scope, **_dims(cfg))


def param_names(cfg):
    names = ["tok_emb", "lm_head.w_0", "lm_head.w_1"]
    for i in range(cfg["num_layers"]):
        names += [f"l{i}_attn_{x}.w_0" for x in "qkvo"]
        names += [f"l{i}_ffn_fc{j}.w_{k}" for j in (1, 2) for k in (0, 1)]
        names += [f"l{i}_ln{j}.{x}" for j in (1, 2) for x in ("scale", "bias")]
    return names


def reference_loss(cfg, params, batch):
    """Mean cross-entropy of one batch on `params`, row by row so that one
    row's [T, vocab] logits are all the reference ever holds."""
    f = jax.jit(lambda p, t, y: lm_reference.row_loss_sum(p, t, y, cfg))
    feed = batch["feed"]
    with jax.default_matmul_precision("highest"):
        total = sum(float(f(params, jnp.asarray(t), jnp.asarray(y)))
                    for t, y in zip(feed["tokens"], feed["targets"]))
    return total / batch["tokens"]


def reference_logits(cfg, params, tokens, pad_to):
    """Full forward over `tokens`, padded on the right to a fixed length (the
    model is causal, so the padding changes no earlier position)."""
    f = jax.jit(lambda p, t: lm_reference.logits(p, t, cfg))
    padded = np.zeros((pad_to,), np.int32)
    padded[:len(tokens)] = tokens
    with jax.default_matmul_precision("highest"):
        return np.asarray(f(params, jnp.asarray(padded)))[:len(tokens)]


def _matmul_params(cfg):
    return (cfg["num_layers"] * counts.block_matmul_params(cfg["d_model"],
                                                           cfg["d_inner"])
            + cfg["d_model"] * cfg["vocab"])


def train_flops(cfg, mix, batch):
    """Operations forward and backward need for one batch."""
    rows = len(batch["feed"]["tokens"])
    T = mix["seq_len"]
    return (counts.matmul_flops(rows * T, _matmul_params(cfg), True)
            + cfg["num_layers"] * counts.attention_flops(
                rows * cfg["num_heads"], T, T, cfg["head_dim"], True, True))


def flash_calls(cfg, mix, rows):
    """[(flops, bytes)] of the fused attention calls one training step on
    `rows` rows makes on ONE chip: per layer a forward and a backward."""
    T, dh, bh = mix["seq_len"], cfg["head_dim"], rows * cfg["num_heads"]
    fwd = (counts.attention_flops(bh, T, T, dh, True, False),
           counts.flash_call_bytes(bh, T, T, dh, False))
    both = counts.attention_flops(bh, T, T, dh, True, True)
    bwd = (both - fwd[0], counts.flash_call_bytes(bh, T, T, dh, True))
    return [fwd, bwd] * cfg["num_layers"]


def decode_tick_bytes(cfg, n_slots, live_positions):
    """Bytes one decode tick cannot avoid reading: every block's and the
    head's weights as stored (float32), one embedding row per slot, and the
    keys and values of the positions the live requests have written."""
    weights = 4 * (_matmul_params(cfg) + n_slots * cfg["d_model"])
    cache = live_positions * 2 * cfg["num_layers"] * cfg["d_model"] * 4
    return weights + cache
