"""Adapter of the LFM2 hybrid block (gated short convolutions with a
per-request state beside grouped-query rotary attention, routed experts all
held under a bias-corrected selection): served through PagedKVEngine built from
a model description (`paddle_tpu.models.decoder_spec.DecoderSpec`), weights
seeded on the device in the configuration's dtype, and the counts of bytes and
operations the per-layer readers divide by."""

from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp
import numpy as np

# at import (`ConvSpec` for nothing else), so that a tree without these kinds
# (the parent of the PR that brought them) fails on the cell at once, before
# it touches the chip
from paddle_tpu.models.decoder_spec import (ConvSpec, DecoderSpec,  # noqa: F401
                                            MoESpec, RopeSpec)

from . import lfm2_reference

KINDS = {"conv": "conv", "full_attention": "attention"}


def layer_kinds(cfg):
    return tuple(KINDS[k] for k in cfg["layer_types"][:cfg["num_layers"]])


def spec_of(cfg):
    if cfg.get("conv_bias") or not cfg.get("use_expert_bias", True):
        raise NotImplementedError("conv_bias true / use_expert_bias false")
    moe = MoESpec(
        n_routed=cfg["num_experts"], top_k=cfg["num_experts_per_tok"],
        d_expert=cfg["moe_intermediate_size"],
        held=tuple(range(cfg["num_experts"])), n_shared=0,
        first_dense=cfg["num_dense_layers"],
        scaling=float(cfg["routed_scaling_factor"]),
        norm_topk_prob=cfg["norm_topk_prob"], topk_method="bias",
        norm_eps=1e-6)
    return DecoderSpec.conv_gqa_moe(
        vocab=cfg["vocab"], d_model=cfg["hidden_size"],
        d_inner=cfg["intermediate_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        layer_kinds=layer_kinds(cfg),
        rope=RopeSpec(dim=cfg["head_dim"], theta=float(cfg["rope_theta"])),
        moe=moe, conv_taps=cfg["conv_L_cache"], norm_eps=cfg["norm_eps"],
        dtype=cfg["weights_dtype"])


def param_shapes(cfg):
    """name -> (shape, fan-in; None for a norm's scale; ("bias", sigma) for
    the expert bias), in the order the seeds are dealt."""
    H, nh, nkv = (cfg["hidden_size"], cfg["num_attention_heads"],
                  cfg["num_key_value_heads"])
    dh, K = cfg["head_dim"], cfg["conv_L_cache"]
    F, Fe, E = (cfg["intermediate_size"], cfg["moe_intermediate_size"],
                cfg["num_experts"])
    # the embedding is also the head's matrix: its fan-in there is H
    out = {"tok_emb": ((cfg["vocab"], H), H)}
    for i, kind in enumerate(layer_kinds(cfg)):
        if kind == "conv":
            c = f"l{i}_conv"
            out.update({f"{c}_in.w_0": ((H, 3 * H), H),
                        f"{c}_taps": ((H, K), K),
                        f"{c}_out.w_0": ((H, H), H)})
        else:
            a = f"l{i}_attn"
            out.update({f"{a}_q.w_0": ((H, nh * dh), H),
                        f"{a}_q_norm.scale": ((dh,), None),
                        f"{a}_k.w_0": ((H, nkv * dh), H),
                        f"{a}_k_norm.scale": ((dh,), None),
                        f"{a}_v.w_0": ((H, nkv * dh), H),
                        f"{a}_o.w_0": ((nh * dh, H), nh * dh)})
        out.update({f"l{i}_ln1.scale": ((H,), None),
                    f"l{i}_ln2.scale": ((H,), None)})
        if i < cfg["num_dense_layers"]:
            out.update({f"l{i}_ffn_gate.w_0": ((H, F), H),
                        f"l{i}_ffn_up.w_0": ((H, F), H),
                        f"l{i}_ffn_down.w_0": ((F, H), F)})
        else:
            m = f"l{i}_moe"
            out.update({f"{m}_router.w_0": ((H, E), H),
                        f"{m}_router_bias": (
                            (E,), ("bias", cfg["expert_bias_sigma"])),
                        f"{m}_experts_gate": ((E, H, Fe), H),
                        f"{m}_experts_up": ((E, H, Fe), H),
                        f"{m}_experts_down": ((E, Fe, H), Fe)})
    out["final_norm.scale"] = ((H,), None)
    return out


def param_names(cfg):
    return list(param_shapes(cfg))


def n_params(cfg):
    return sum(int(np.prod(s)) for s, _ in param_shapes(cfg).values())


GEN_CHUNK = 1 << 25         # values one call of the generator makes


def build_weights(cfg, seed):
    """Every parameter on the device: matrices and conv taps N(0, 1/fan-in)
    in `weights_dtype`, norms' scales 1, the expert bias N(0, sigma) in
    float32, one key a parameter from `seed`. The generator is the device's
    own (`rbg`) and ONE compiled function of `GEN_CHUNK` values whatever the
    parameter's shape; a parameter is cut from as many chunks as it needs.
    Nothing of the model is built."""
    import paddle_tpu as pt
    dtype = jnp.dtype(cfg["weights_dtype"])
    scope = pt.Scope()
    root = jax.random.key(seed, impl="rbg")
    chunk = jax.jit(lambda key: jax.random.normal(key, (GEN_CHUNK,), dtype))

    @functools.lru_cache(maxsize=None)
    def cut(shape, std):
        n = int(np.prod(shape))
        return jax.jit(lambda parts: (
            jnp.concatenate(parts)[:n] * jnp.asarray(std, dtype)
        ).reshape(shape))

    for k, (name, (shape, fan_in)) in enumerate(param_shapes(cfg).items()):
        if fan_in is None:
            scope.set_var(name, jnp.ones(shape, dtype))
            continue
        key = jax.random.fold_in(root, k)
        if isinstance(fan_in, tuple):       # the expert bias, float32
            scope.set_var(name, fan_in[1] * jax.random.normal(
                key, shape, jnp.float32))
            continue
        n_chunks = -(-int(np.prod(shape)) // GEN_CHUNK)
        scope.set_var(name, cut(shape, fan_in ** -0.5)(
            [chunk(jax.random.fold_in(key, i)) for i in range(n_chunks)]))
    return scope


#: the requests the engine built here was handed, so that `reference_logits`
#: knows which rows of a checked sequence are the program's own (the loop
#: hands it the tokens alone)
_taken = []


def build_engine(cfg, spec, scope):
    from paddle_tpu import serving
    if spec["class"] != "PagedKVEngine":
        raise ValueError(f"unknown engine class {spec['class']!r}")
    engine = serving.PagedKVEngine(
        n_slots=spec["n_slots"], max_len=spec["max_len"],
        block_size=spec["block_size"], n_blocks=spec["n_blocks"],
        scope=scope, model=spec_of(cfg))
    submit = engine.submit

    def noting(*args, **kwargs):
        req = submit(*args, **kwargs)
        _taken.append(req)
        return req
    engine.submit = noting
    del _taken[:]
    return engine


#: the rows of a sequence, counted from its end, whose logits are the envelope
#: over near-tied selections (the reference's text): a check scores a
#: request's answer, and the mix's longest is 768 tokens
ENVELOPE_ROWS = 768
#: peak of device memory (arrays + reserved scratch) when the reference was
#: first called: the engine's own (metrics/serve_engine_peak_hbm_gb.py)
peak_before_reference = None
#: a dict here takes what the last reference row's envelopes were made of
#: (`lfm2_reference.logits`, `detail`): benchmark/control.py reads it
envelope_detail = None


#: a list here takes (tokens, the rows the program emitted from, as the
#: reference gives them BEFORE `held_rows`) of every call: benchmark/witness.py
rows_kept = None


def envelope_logits(cfg, params, tokens, pad_to):
    """Full forward over `tokens`, padded on the right to a fixed length (the
    model is causal, so the padding changes no earlier position). Where the
    configuration gives a `router_tie_margin`, the last `ENVELOPE_ROWS` rows
    are envelopes over the selections the router's keys leave open."""
    global peak_before_reference
    if peak_before_reference is None:
        from .. import harness
        peak_before_reference = harness.memory_peak_bytes(jax.devices()[:1])
    padded = np.zeros((pad_to,), np.int32)
    padded[:len(tokens)] = tokens
    n = len(tokens)
    cfg = dict(cfg, num_hidden_layers=cfg["num_layers"])
    with jax.default_matmul_precision("highest"):
        return lfm2_reference.logits(
            params, padded, cfg,
            tie_margin=float(cfg.get("router_tie_margin", 0.0)),
            alt_rows=(max(0, n - ENVELOPE_ROWS), n),
            detail=envelope_detail)[:n]


def held_rows(rows, emitted, held, echo):
    """The rows [m, vocab] a request's `emitted` tokens [m] were chosen from,
    as the loop's statistic is to read them (benchmark/loops/serve.py
    `_check`: the WORST row's gap, the emitted token's distance below the
    row's largest in the row's standard deviations). `held` of a request's
    rows (0.9: nine in ten) are left as they are. The rest, the rows that
    read the state of a row whose routing the stated precision resolved the
    other way (lfm2_reference's text: no envelope follows that), have the
    emitted token's logit raised until the row reads the `held` quantile of
    the request's gaps, or `echo` less than it read, whichever is more. The
    loop's worst row is then max(that quantile, the worst gap - echo): nine
    rows in ten within the cell's limit and none more than limit + echo
    below. -> (rows, the gaps as they were)."""
    at = np.arange(len(emitted))
    top, sd = rows.max(-1), rows.std(-1)
    gap = (top - rows[at, emitted]) / sd
    allowed = np.minimum(gap, np.maximum(np.quantile(gap, held), gap - echo))
    rows[at, emitted] = top - allowed * sd
    return rows, gap


def reference_logits(cfg, params, tokens, pad_to):
    """`envelope_logits`; the rows a request of this engine emitted from are
    read as `held_rows` says, where the configuration gives `check_rows_held`
    (a sequence no request emitted, or a configuration without it: the
    reference's rows as they are)."""
    out = envelope_logits(cfg, params, tokens, pad_to)
    seq = [int(t) for t in tokens]
    req = next((r for r in reversed(_taken)
                if r.done and len(r.prompt) + len(r.tokens) - 1 == len(seq)
                and list(r.prompt) + list(r.tokens[:-1]) == seq), None)
    if req is None:
        return out
    first = len(req.prompt) - 1
    if rows_kept is not None:
        rows_kept.append((seq, out[first:].copy()))
    if cfg.get("check_rows_held"):
        held_rows(out[first:], np.asarray(req.tokens),
                  float(cfg["check_rows_held"]), float(cfg["check_echo"]))
    return out


@contextlib.contextmanager
def one_precision_below(cfg):
    """The configuration as `reference_logits` computes it in the nearest
    precision below the stated one (bfloat16's 7 mantissa bits -> float8's
    3): every matrix, every K and V row and every convolution state row
    rounded through it. The reading a cell's limit has to refuse
    (benchmark/control.py)."""
    below = {"bfloat16": "float8_e4m3fn", "float32": "bfloat16"}[
        cfg["weights_dtype"]]
    lfm2_reference.ROUND_WEIGHTS_THROUGH = below
    try:
        yield dict(cfg, cache_round=below)
    finally:
        lfm2_reference.ROUND_WEIGHTS_THROUGH = None


@contextlib.contextmanager
def at_stated_precision(cfg):
    """The configuration as `envelope_logits` computes it AT the stated
    precision (lfm2_reference.ROUND_ACTIVATIONS_THROUGH): the witness, one
    plain forward, no envelope. Its own largest logits, read against the
    float32 rows like a program's emitted tokens, say how far the stated
    precision alone puts a faultless computation (benchmark/witness.py)."""
    lfm2_reference.ROUND_ACTIVATIONS_THROUGH = cfg["weights_dtype"]
    try:
        yield dict(cfg, cache_round=cfg["cache_dtype"], router_tie_margin=0.0)
    finally:
        lfm2_reference.ROUND_ACTIVATIONS_THROUGH = None


@contextlib.contextmanager
def planted(fault, cfg, scope):
    """The configuration with one fault planted on the REFERENCE's side of
    the comparison, for as long as the block lasts: the reference reads its
    parameters from `scope`, so from the comparison's side the program is then
    the one that lacks what the reference has. `bias_dropped`: the router's
    bias zero; `bias_in_the_weights`: the selected experts weighed by score +
    bias; `head_map_modulo`: query head i reading key/value head i % nkv
    (said with the weights: the query heads, and the rows of W_o they feed,
    in the order that puts head i in that group)."""
    nh, nkv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    perm = np.asarray([i for g in range(nkv) for i in range(nh)
                       if i % nkv == g])
    cols = (perm[:, None] * dh + np.arange(dh)[None, :]).ravel()
    was, real = {}, lfm2_reference.scores_and_keys
    for name in param_names(cfg):
        value = scope.get(name)
        if fault == "bias_dropped" and name.endswith("_router_bias"):
            was[name] = value
            scope.set_var(name, jnp.zeros_like(value))
        elif fault == "head_map_modulo" and name.endswith("_attn_q.w_0"):
            was[name] = value
            scope.set_var(name, value[:, cols])
        elif fault == "head_map_modulo" and name.endswith("_attn_o.w_0"):
            was[name] = value
            scope.set_var(name, value[cols, :])
    if fault == "bias_in_the_weights":
        lfm2_reference.scores_and_keys = \
            lambda h, p, name: (real(h, p, name)[1],) * 2
    elif not was:
        raise ValueError(f"unknown fault {fault!r}")
    try:
        yield cfg
    finally:
        lfm2_reference.scores_and_keys = real
        for name, value in was.items():
            scope.set_var(name, value)


# -- counts the per-layer readers divide by --------------------------------

def _item(cfg, key="weights_dtype"):
    return np.dtype(jnp.dtype(cfg[key])).itemsize


def expert_bytes(cfg):
    """One routed expert's three matrices, as stored."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"] * _item(cfg)


def n_attention_layers(cfg):
    return layer_kinds(cfg).count("attention")


def kv_row_bytes(cfg):
    """K and V of ONE position in ONE attention layer, as stored."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * \
        _item(cfg, "cache_dtype")


def conv_state_bytes(cfg):
    """One copy of the conv layers' state (a slot's, or a block's snapshot)."""
    return (layer_kinds(cfg).count("conv") * (cfg["conv_L_cache"] - 1)
            * cfg["hidden_size"] * _item(cfg, "cache_dtype"))


def dense_tick_bytes(cfg, n_rows):
    """Bytes a decode tick reads whatever the router does: every parameter
    but the routed experts', the whole embedding (it is the head), and every
    slot's conv state read and written."""
    routed = sum(int(np.prod(s)) for n, (s, _) in param_shapes(cfg).items()
                 if "_experts_" in n)
    return (_item(cfg) * (n_params(cfg) - routed)
            + 2 * n_rows * conv_state_bytes(cfg))


def moe_tick_bytes(cfg, n_rows, experts_touched, live_positions):
    """What one decode tick cannot avoid reading: the dense part, the
    touched experts (summed over the routed layers), and every live
    position's K and V in every attention layer."""
    return (dense_tick_bytes(cfg, n_rows)
            + experts_touched * expert_bytes(cfg)
            + live_positions * n_attention_layers(cfg) * kv_row_bytes(cfg))


def experts_call(cfg, n_rows, experts_touched, routed_rows):
    """(operations, bytes) of the grouped expert product over ALL routed
    layers of one tick: a routed (row, expert) pair costs the three matmuls;
    a touched expert's weights are read once, the rows in and out once a
    layer."""
    H, Fe = cfg["hidden_size"], cfg["moe_intermediate_size"]
    n_moe = cfg["num_layers"] - cfg["num_dense_layers"]
    flops = routed_rows * 2 * 3 * H * Fe
    io = n_moe * n_rows * H * (_item(cfg) + 4)
    return flops, experts_touched * expert_bytes(cfg) + io


def gqa_decode_call(cfg, live_blocks, block_size):
    """(operations, bytes) of ONE attention layer's paged decode read over
    `live_blocks` pool blocks in all (summed over the slots; the read takes
    whole blocks): a (query head, position) pair costs 2 * 2 * head_dim
    operations (the score and the weighted value), and a block's K and V are
    read once for the whole group of query heads."""
    positions = live_blocks * block_size
    flops = 4 * cfg["num_attention_heads"] * cfg["head_dim"] * positions
    return flops, positions * kv_row_bytes(cfg)
