"""Adapter of the A.X-K1 block (latent attention, routed experts held as one
chip's share): served through PagedKVEngine built from a model description
(`paddle_tpu.models.decoder_spec.DecoderSpec`), weights seeded on the device
in the configuration's dtype, and the counts of bytes and operations the
per-layer readers divide by."""

from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp
import numpy as np

# at import, so that a tree without the model description (the parent of the
# PR that brought it) fails on the cell at once, before it touches the chip
from paddle_tpu.models.decoder_spec import (DecoderSpec, LatentSpec, MoESpec,
                                            RopeSpec)

from . import axk1_reference


def held_experts(cfg):
    """The experts this rank holds: `n_routed_experts` (the held count) in a
    row from rank * count, of the `router_width` the router scores."""
    n = cfg["n_routed_experts"]
    first = cfg.get("expert_rank", 0) * n
    return tuple(range(first, first + n))


def spec_of(cfg):
    sc = cfg.get("rope_scaling") or {}
    if sc and sc.get("type") != "yarn":
        raise NotImplementedError(f"rope_scaling type {sc.get('type')!r}")
    rope = RopeSpec(
        dim=cfg["qk_rope_head_dim"], theta=float(cfg["rope_theta"]),
        factor=float(sc.get("factor", 1.0)),
        beta_fast=float(sc.get("beta_fast", 32)),
        beta_slow=float(sc.get("beta_slow", 1)),
        mscale=float(sc.get("mscale", 1.0)),
        mscale_all_dim=float(sc.get("mscale_all_dim", 0.0)),
        original_max=int(sc.get("original_max_position_embeddings", 4096)))
    latent = LatentSpec(cfg["q_lora_rank"], cfg["kv_lora_rank"],
                        cfg["qk_nope_head_dim"], cfg["v_head_dim"], rope)
    moe = MoESpec(
        n_routed=cfg["router_width"], top_k=cfg["num_experts_per_tok"],
        d_expert=cfg["moe_intermediate_size"], held=held_experts(cfg),
        n_shared=cfg["n_shared_experts"],
        first_dense=cfg["first_k_dense_replace"],
        scaling=cfg["routed_scaling_factor"],
        norm_topk_prob=cfg["norm_topk_prob"], scoring=cfg["scoring_func"],
        topk_method=cfg["topk_method"])
    if cfg["hidden_act"] != "silu":
        raise NotImplementedError(f"hidden_act {cfg['hidden_act']!r}")
    return DecoderSpec.latent_moe(
        vocab=cfg["vocab"], d_model=cfg["hidden_size"],
        d_inner=cfg["intermediate_size"],
        num_heads=cfg["num_attention_heads"], num_layers=cfg["num_layers"],
        latent=latent, moe=moe, norm_eps=cfg["rms_norm_eps"],
        dtype=cfg["weights_dtype"])


def param_shapes(cfg):
    """name -> (shape, fan-in or None for a norm's scale), in the order the
    seeds are dealt."""
    H, nh = cfg["hidden_size"], cfg["num_attention_heads"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    cq, ckv = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    F, Fe = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    n_held = cfg["n_routed_experts"]
    out = {"tok_emb": ((cfg["vocab"], H), 1)}
    for i in range(cfg["num_layers"]):
        a = f"l{i}_attn"
        out.update({
            f"{a}_qa.w_0": ((H, cq), H), f"{a}_qa_norm.scale": ((cq,), None),
            f"{a}_qb.w_0": ((cq, nh * (dn + dr)), cq),
            f"{a}_kva.w_0": ((H, ckv + dr), H),
            f"{a}_kva_norm.scale": ((ckv,), None),
            f"{a}_kvb.w_0": ((ckv, nh * (dn + dv)), ckv),
            f"{a}_o.w_0": ((nh * dv, H), nh * dv),
            f"l{i}_ln1.scale": ((H,), None), f"l{i}_ln2.scale": ((H,), None)})
        if i < cfg["first_k_dense_replace"]:
            out.update({f"l{i}_ffn_gate.w_0": ((H, F), H),
                        f"l{i}_ffn_up.w_0": ((H, F), H),
                        f"l{i}_ffn_down.w_0": ((F, H), F)})
        else:
            m, Fs = f"l{i}_moe", Fe * cfg["n_shared_experts"]
            out.update({f"{m}_router.w_0": ((H, cfg["router_width"]), H),
                        f"{m}_experts_gate": ((n_held, H, Fe), H),
                        f"{m}_experts_up": ((n_held, H, Fe), H),
                        f"{m}_experts_down": ((n_held, Fe, H), Fe),
                        f"{m}_shared_gate.w_0": ((H, Fs), H),
                        f"{m}_shared_up.w_0": ((H, Fs), H),
                        f"{m}_shared_down.w_0": ((Fs, H), Fs)})
    out["final_norm.scale"] = ((H,), None)
    out["lm_head.w_0"] = ((H, cfg["vocab"]), H)
    return out


def param_names(cfg):
    return list(param_shapes(cfg))


def n_params(cfg):
    return sum(int(np.prod(s)) for s, _ in param_shapes(cfg).values())


GEN_CHUNK = 1 << 25         # values one call of the generator makes


def build_weights(cfg, seed):
    """Every parameter on the device in `weights_dtype`: N(0, 1/fan-in) (so
    that every projection keeps a row's scale, the scores spread over a few
    units and the logits have unit variance: see `assumed`), norms' scales
    1, one key a parameter from `seed`. The generator is the device's own
    (`rbg`) and ONE compiled function of `GEN_CHUNK` values, whatever the
    parameter's shape (a generator a shape compiled for most of a minute);
    a parameter is cut from as many chunks as it needs. Nothing of the
    model is built."""
    import paddle_tpu as pt
    dtype = jnp.dtype(cfg["weights_dtype"])
    scope = pt.Scope()
    root = jax.random.key(seed, impl="rbg")
    chunk = jax.jit(lambda key: jax.random.normal(key, (GEN_CHUNK,), dtype))

    @functools.lru_cache(maxsize=None)
    def cut(shape, fan_in):
        n = int(np.prod(shape))
        return jax.jit(lambda parts: (
            jnp.concatenate(parts)[:n] * jnp.asarray(fan_in ** -0.5, dtype)
        ).reshape(shape))

    for k, (name, (shape, fan_in)) in enumerate(param_shapes(cfg).items()):
        if fan_in is None:
            scope.set_var(name, jnp.ones(shape, dtype))
            continue
        key = jax.random.fold_in(root, k)
        n_chunks = -(-int(np.prod(shape)) // GEN_CHUNK)
        scope.set_var(name, cut(shape, fan_in)(
            [chunk(jax.random.fold_in(key, i)) for i in range(n_chunks)]))
    return scope


def build_engine(cfg, spec, scope):
    from paddle_tpu import serving
    if spec["class"] != "PagedKVEngine":
        raise ValueError(f"unknown engine class {spec['class']!r}")
    return serving.PagedKVEngine(
        n_slots=spec["n_slots"], max_len=spec["max_len"],
        block_size=spec["block_size"], n_blocks=spec["n_blocks"],
        scope=scope, model=spec_of(cfg))


#: the rows of a sequence, counted from its end, whose logits are the
#: envelope over near-tied selections (the reference's text): a check scores
#: a request's answer, and the mixes' longest is 512 tokens; a row before
#: them is held to the plain forward, which is the stricter reading
ENVELOPE_ROWS = 512
#: peak of device memory (arrays + reserved scratch) when the reference was
#: first called: the engine's own, before the reference's blocks sit beside
#: it (metrics/serve_engine_peak_hbm_gb.py)
peak_before_reference = None
#: a dict here takes what the last reference row's envelopes were made of
#: (`axk1_reference.logits`, `detail`): benchmark/control.py reads it
envelope_detail = None


def reference_logits(cfg, params, tokens, pad_to):
    """Full forward over `tokens`, padded on the right to a fixed length (the
    model is causal, so the padding changes no earlier position). Where the
    configuration gives a `router_tie_margin`, the last `ENVELOPE_ROWS` rows
    are envelopes over the selections the router's scores leave open."""
    global peak_before_reference
    if peak_before_reference is None:
        from .. import harness
        peak_before_reference = harness.memory_peak_bytes(jax.devices()[:1])
    padded = np.zeros((pad_to,), np.int32)
    padded[:len(tokens)] = tokens
    n = len(tokens)
    with jax.default_matmul_precision("highest"):
        return axk1_reference.logits(
            params, padded, cfg, held_experts(cfg),
            tie_margin=float(cfg.get("router_tie_margin", 0.0)),
            alt_rows=(max(0, n - ENVELOPE_ROWS), n),
            detail=envelope_detail)[:n]


@contextlib.contextmanager
def one_precision_below(cfg):
    """The configuration as `reference_logits` computes it in the nearest
    precision below the stated one (bfloat16's 7 mantissa bits -> float8's
    3): every matrix and every latent row rounded through it. The reading a
    cell's limit has to refuse (benchmark/control.py)."""
    below = {"bfloat16": "float8_e4m3fn", "float32": "bfloat16"}[
        cfg["weights_dtype"]]
    axk1_reference.ROUND_WEIGHTS_THROUGH = below
    try:
        yield dict(cfg, latent_dtype=below)
    finally:
        axk1_reference.ROUND_WEIGHTS_THROUGH = None


# -- counts the per-layer readers divide by --------------------------------

def expert_bytes(cfg):
    """One routed expert's three matrices, as stored."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"] * \
        np.dtype(jnp.dtype(cfg["weights_dtype"])).itemsize


def latent_row_bytes(cfg):
    """The bytes of ONE position's latent row a read cannot avoid: the
    values (c_kv and k_pe), not the padding the pool stores beside them."""
    return (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * \
        np.dtype(jnp.dtype(cfg["cache_dtype"])).itemsize


def dense_tick_bytes(cfg, n_rows):
    """Bytes a decode tick reads whatever the router does: every parameter
    but the routed experts', and one embedding row a decode row in place of
    the table."""
    item = np.dtype(jnp.dtype(cfg["weights_dtype"])).itemsize
    routed = sum(int(np.prod(s)) for n, (s, _) in param_shapes(cfg).items()
                 if "_experts_" in n)
    table = cfg["vocab"] * cfg["hidden_size"]
    return item * (n_params(cfg) - routed - table
                   + n_rows * cfg["hidden_size"])


def moe_tick_bytes(cfg, n_rows, experts_touched, live_positions):
    """What one decode tick cannot avoid reading: the dense part, the
    touched experts (summed over the routed layers), and every live
    position's latent row in every layer."""
    return (dense_tick_bytes(cfg, n_rows)
            + experts_touched * expert_bytes(cfg)
            + live_positions * cfg["num_layers"] * latent_row_bytes(cfg))


def mla_call(cfg, n_query, live_positions):
    """(operations, bytes) of ONE layer's latent read over `live_positions`
    cache rows in all (summed over the slots), `n_query` query positions a
    slot: a (query position, row) pair costs 2 * heads * (row values + c)
    operations, and a row's values are read once."""
    nh = cfg["num_attention_heads"]
    row = cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    flops = 2 * nh * (row + cfg["kv_lora_rank"]) * n_query * live_positions
    return flops, live_positions * latent_row_bytes(cfg)


def experts_call(cfg, n_rows, experts_touched, routed_rows):
    """(operations, bytes) of the grouped expert product over ALL routed
    layers of one tick: a routed (row, expert) pair costs the three
    matmuls; a touched expert's weights are read once, the rows in and
    out once a layer."""
    H, Fe = cfg["hidden_size"], cfg["moe_intermediate_size"]
    item = np.dtype(jnp.dtype(cfg["weights_dtype"])).itemsize
    n_moe = cfg["num_layers"] - cfg["first_k_dense_replace"]
    flops = routed_rows * 2 * 3 * H * Fe
    io = n_moe * n_rows * H * (item + 4)
    return flops, experts_touched * expert_bytes(cfg) + io
