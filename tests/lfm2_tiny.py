"""LFM2's hybrid block at a size the CPU runs in seconds: every mechanism of
benchmark/configs/lfm2-8b-a1b.json (gated short convolutions with a state,
grouped-query attention with QK-norm and rotary positions, a leading dense
layer, routed experts all held under a bias-corrected top-k, no shared expert,
a tied head), none of its widths."""

import numpy as np

import tiny_engines
from benchmark.models import lfm2, lfm2_reference as ref  # noqa: F401
from tiny_engines import emitted_logits, scored_engine  # noqa: F401

CFG = dict(
    model="lfm2", hidden_size=64, intermediate_size=96,
    num_attention_heads=8, num_key_value_heads=2, head_dim=8,
    conv_L_cache=3, conv_bias=False,
    layer_types=["conv", "conv", "full_attention", "conv", "full_attention"],
    num_layers=5, num_hidden_layers=5, num_dense_layers=1,
    moe_intermediate_size=256, num_experts=8, num_experts_per_tok=2,
    norm_topk_prob=True, use_expert_bias=True, routed_scaling_factor=1,
    expert_bias_sigma=0.05, norm_eps=1e-5, rope_theta=1000000, vocab=97,
    weights_dtype="bfloat16", cache_dtype="bfloat16", max_len=64)
ENGINE = {"class": "PagedKVEngine", "n_slots": 4, "max_len": 64,
          "block_size": 8, "n_blocks": 40}
F32 = dict(weights_dtype="float32", cache_dtype="float32")
# three periods of the published layer pattern and two leading dense layers:
# deep enough that bfloat16 reads against the reference as it does at the
# published depth (a routing flip in most rows, the rows after it reading its
# state), which is what the cell's limit is set for
DEEP = dict(layer_types=["conv", "conv", "full_attention", "conv"] * 3,
            num_layers=12, num_hidden_layers=12, num_dense_layers=2)


def cfg(**over):
    return dict(CFG, **over)


def engine(config, seed=7, scored=False, **spec):
    return tiny_engines.engine(lfm2, ENGINE, config, seed, scored, **spec)


def reference(config, params, req, pad_to=64):
    """The reference's logits for the positions `req` emitted from."""
    seq = np.asarray(req.prompt + req.tokens[:-1], np.int32)
    return lfm2.reference_logits(config, params, seq, pad_to)[
        len(req.prompt) - 1:]


def gaps(config, params, req, pad_to=64):
    """Per emitted token: how far its reference logit lies below the
    position's largest, in standard deviations of that position's logits
    (benchmark/loops/serve.py `_check`)."""
    r = reference(config, params, req, pad_to)
    toks = req.tokens
    return (r.max(-1) - r[np.arange(len(toks)), toks]) / r.std(-1)


def logit_error(config, params, req, got, pad_to=64):
    """max |program - reference| over the emitted positions' logits, in
    standard deviations of the reference's logits."""
    r = reference(config, params, req, pad_to)
    return float(np.abs(got - r).max() / r.std())
