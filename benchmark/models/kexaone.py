"""Adapter of the K-EXAONE block (sliding-window layers of 128 positions beside
full grouped-query layers, routed experts held as one chip's share of eight):
served through PagedKVEngine built from a model description
(`paddle_tpu.models.decoder_spec.DecoderSpec.window_gqa_moe`), weights seeded
on the device in the configuration's dtype, and the counts of bytes and
operations the per-layer readers divide by."""

from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp
import numpy as np

# at import, so that a tree without the window kinds (the parent of the PR
# that brought them) fails on the cell at once, before it touches the chip
from paddle_tpu.models.decoder_spec import DecoderSpec, MoESpec, RopeSpec

from . import axk1_reference, kexaone_reference

DecoderSpec.window_gqa_moe      # noqa: B018  (AttributeError on such a tree)

KINDS = {"sliding_attention": "window", "full_attention": "full"}


def held_experts(cfg):
    """The experts this rank holds: `num_experts` (the held count) in a row
    from rank * count, of the `router_width` the router scores."""
    n = cfg["num_experts"]
    first = cfg.get("expert_rank", 0) * n
    return tuple(range(first, first + n))


def attention_kinds(cfg):
    return tuple(KINDS[k] for k in cfg["layer_types"][:cfg["num_layers"]])


def check_config(cfg):
    """What the program builds and the row states, by name."""
    n = cfg["num_layers"]
    dense = cfg["mlp_layer_types"][:n].count("dense")
    if cfg["mlp_layer_types"][:n] != ["dense"] * dense + ["sparse"] * (
            n - dense) or dense != cfg["first_k_dense_replace"]:
        raise NotImplementedError("mlp_layer_types: dense layers first, "
                                  "`first_k_dense_replace` of them")
    if cfg["sliding_windows"][:n] != [
            cfg["sliding_window"] if k == "sliding_attention" else 0
            for k in cfg["layer_types"][:n]]:
        raise NotImplementedError("sliding_windows: `sliding_window` on the "
                                  "sliding layers, 0 on the others")
    if cfg["rope_parameters"].get("rope_type", "default") != "default":
        raise NotImplementedError(
            f"rope_type {cfg['rope_parameters']['rope_type']!r}")
    if cfg["n_group"] != 1 or cfg["topk_group"] != 1:
        raise NotImplementedError("a group limit on the top-k")
    if cfg["hidden_act"] != "silu" or cfg["scoring_func"] != "sigmoid":
        raise NotImplementedError("hidden_act / scoring_func")


def spec_of(cfg):
    check_config(cfg)
    moe = MoESpec(
        n_routed=cfg["router_width"], top_k=cfg["num_experts_per_tok"],
        d_expert=cfg["moe_intermediate_size"], held=held_experts(cfg),
        n_shared=cfg["num_shared_experts"],
        first_dense=cfg["first_k_dense_replace"],
        scaling=cfg["routed_scaling_factor"],
        norm_topk_prob=cfg["norm_topk_prob"], scoring=cfg["scoring_func"],
        topk_method=cfg["topk_method"])
    return DecoderSpec.window_gqa_moe(
        vocab=cfg["vocab"], d_model=cfg["hidden_size"],
        d_inner=cfg["intermediate_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], d_head=cfg["head_dim"],
        attention_kinds=attention_kinds(cfg), window=cfg["sliding_window"],
        rope=RopeSpec(dim=cfg["head_dim"],
                      theta=float(cfg["rope_parameters"]["rope_theta"])),
        moe=moe, norm_eps=cfg["rms_norm_eps"], dtype=cfg["weights_dtype"])


def param_shapes(cfg):
    """name -> (shape, fan-in; None for a norm's scale of 1; ("scale", v) for
    a norm's scale of v), in the order the seeds are dealt."""
    H, nh, nkv, dh = (cfg["hidden_size"], cfg["num_attention_heads"],
                      cfg["num_key_value_heads"], cfg["head_dim"])
    F, Fe = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    n_held = cfg["num_experts"]
    qk = ("scale", float(cfg.get("qk_norm_init", 1.0)))
    out = {"tok_emb": ((cfg["vocab"], H), 1)}
    for i in range(cfg["num_layers"]):
        a = f"l{i}_attn"
        out.update({f"{a}_q.w_0": ((H, nh * dh), H),
                    f"{a}_q_norm.scale": ((dh,), qk),
                    f"{a}_k.w_0": ((H, nkv * dh), H),
                    f"{a}_k_norm.scale": ((dh,), qk),
                    f"{a}_v.w_0": ((H, nkv * dh), H),
                    f"{a}_o.w_0": ((nh * dh, H), nh * dh),
                    f"l{i}_ln1.scale": ((H,), None),
                    f"l{i}_ln2.scale": ((H,), None)})
        if i < cfg["first_k_dense_replace"]:
            out.update({f"l{i}_ffn_gate.w_0": ((H, F), H),
                        f"l{i}_ffn_up.w_0": ((H, F), H),
                        f"l{i}_ffn_down.w_0": ((F, H), F)})
        else:
            m, Fs = f"l{i}_moe", Fe * cfg["num_shared_experts"]
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
    """Every parameter on the device in `weights_dtype`: N(0, 1/fan-in),
    norms' scales 1 (the q and k norms' `qk_norm_init`: the configuration's
    `assumed` says why), one key a parameter from `seed`; the device's own
    generator (`rbg`) as ONE compiled function of `GEN_CHUNK` values whatever
    the parameter's shape (benchmark/models/axk1.py has why). Nothing of the
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
        if fan_in is None or isinstance(fan_in, tuple):
            scope.set_var(name, jnp.full(shape, fan_in[1] if fan_in else 1.0,
                                         dtype))
            continue
        key = jax.random.fold_in(root, k)
        n_chunks = -(-int(np.prod(shape)) // GEN_CHUNK)
        scope.set_var(name, cut(shape, fan_in)(
            [chunk(jax.random.fold_in(key, i)) for i in range(n_chunks)]))
    return scope


#: the requests the engine built here was handed (benchmark/models/lfm2.py)
_taken = []


def build_engine(cfg, spec, scope):
    from paddle_tpu import serving
    if spec["class"] != "PagedKVEngine":
        raise ValueError(f"unknown engine class {spec['class']!r}")
    engine = serving.PagedKVEngine(
        n_slots=spec["n_slots"], max_len=spec["max_len"],
        block_size=spec["block_size"], n_blocks=spec["n_blocks"],
        n_window_blocks=spec["n_window_blocks"], scope=scope,
        model=spec_of(cfg))
    submit = engine.submit

    def noting(*args, **kwargs):
        req = submit(*args, **kwargs)
        _taken.append(req)
        return req
    engine.submit = noting
    del _taken[:]
    global last_engine
    last_engine = engine
    return engine


#: the engine `build_engine` made last: the pager's counters are read from it
#: after the window (metrics/window_*.py)
last_engine = None
ENVELOPE_ROWS = 512         # as axk1.py: the mix's longest answer
#: peak of device memory when the reference was first called
#: (metrics/serve_engine_peak_hbm_gb.py)
peak_before_reference = None
#: a dict here takes what the last reference row's envelopes were made of
#: (benchmark/control.py)
envelope_detail = None
#: a list here takes (tokens, the rows the program emitted from) of every
#: call (benchmark/witness.py)
rows_kept = None


def held_rows(rows, emitted, held, echo):
    """benchmark/witness.py's reading of a request's rows: the gaps as they
    are (this cell shapes no row: `held` 1, `echo` 0 are all it is called
    with) -> (rows, gaps)."""
    at = np.arange(len(emitted))
    gap = (rows.max(-1) - rows[at, emitted]) / rows.std(-1)
    return rows, gap


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
    cfg = dict(cfg, num_hidden_layers=cfg["num_layers"])
    with jax.default_matmul_precision("highest"):
        out = kexaone_reference.logits(
            params, padded, cfg, held_experts(cfg),
            tie_margin=float(cfg.get("router_tie_margin", 0.0)),
            alt_rows=(max(0, n - ENVELOPE_ROWS), n),
            detail=envelope_detail)[:n]
    if rows_kept is not None:
        seq = [int(t) for t in tokens]
        req = next((r for r in reversed(_taken)
                    if r.done and list(r.prompt) + list(r.tokens[:-1]) == seq),
                   None)
        if req is not None:
            rows_kept.append((seq, out[len(req.prompt) - 1:].copy()))
    return out


@contextlib.contextmanager
def one_precision_below(cfg):
    """The configuration as `reference_logits` computes it in the nearest
    precision below the stated one (bfloat16's 7 mantissa bits -> float8's
    3): every matrix and every K and V row rounded through it. The reading a
    cell's limit has to refuse (benchmark/control.py)."""
    below = {"bfloat16": "float8_e4m3fn", "float32": "bfloat16"}[
        cfg["weights_dtype"]]
    axk1_reference.ROUND_WEIGHTS_THROUGH = below
    try:
        yield dict(cfg, cache_round=below)
    finally:
        axk1_reference.ROUND_WEIGHTS_THROUGH = None


FAULTS = {
    # the REFERENCE's side of the comparison gets the fault, so from the
    # comparison's side the program is the one that lacks what it has
    "window_ignored": lambda cfg: dict(cfg, sliding_window=1 << 30),
    "window_off_by_one": lambda cfg: dict(
        cfg, sliding_window=cfg["sliding_window"] + 1),
    "rope_on_full": lambda cfg: dict(
        cfg, rotated=("sliding_attention", "full_attention")),
}


@contextlib.contextmanager
def planted(fault, cfg, scope):
    """The configuration with one fault planted on the reference's side for
    as long as the block lasts (benchmark/witness.py `--faults`):
    `window_ignored` (the sliding layers see every position),
    `window_off_by_one` (one position more), `rope_on_full` (the full layers
    rotated too), `qk_norm_dropped` (the q and k norms' scales as if the norm
    were not there cannot be said with the weights: the reference skips
    them)."""
    if fault == "qk_norm_dropped":
        real = kexaone_reference.rms

        def skipping(x, scale, eps):
            return x if x.ndim == 3 else real(x, scale, eps)
        kexaone_reference.rms = skipping
        try:
            yield cfg
        finally:
            kexaone_reference.rms = real
        return
    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    yield FAULTS[fault](cfg)


# -- counts the per-layer readers divide by --------------------------------

def _item(cfg, key="weights_dtype"):
    return np.dtype(jnp.dtype(cfg[key])).itemsize


def expert_bytes(cfg):
    """One routed expert's three matrices, as stored."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"] * _item(cfg)


def kv_row_bytes(cfg):
    """K and V of ONE position in ONE attention layer, as stored."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * \
        _item(cfg, "cache_dtype")


def dense_tick_bytes(cfg, n_rows):
    """Bytes a decode tick reads whatever the router does: every parameter
    but the routed experts', and one embedding row a decode row in place of
    the table."""
    routed = sum(int(np.prod(s)) for n, (s, _) in param_shapes(cfg).items()
                 if "_experts_" in n)
    table = cfg["vocab"] * cfg["hidden_size"]
    return _item(cfg) * (n_params(cfg) - routed - table
                         + n_rows * cfg["hidden_size"])


def moe_tick_bytes(cfg, n_rows, experts_touched, live_positions):
    """What one decode tick cannot avoid reading: the dense part, the touched
    experts (summed over the routed layers), and every live position's K and
    V in every FULL layer. The window layers' rows (at most `sliding_window`
    a live row and layer, under 1% of the rest at this cell's contexts) are
    left out: the count the reader hands over is of positions, not of rows,
    and a bound that is too low reads a share that is too low, never one over
    100."""
    n_full = attention_kinds(cfg).count("full")
    return (dense_tick_bytes(cfg, n_rows)
            + experts_touched * expert_bytes(cfg)
            + live_positions * n_full * kv_row_bytes(cfg))


def experts_call(cfg, n_rows, experts_touched, routed_rows):
    """(operations, bytes) of the grouped expert product over ALL routed
    layers of one tick (benchmark/models/axk1.py `experts_call`)."""
    H, Fe = cfg["hidden_size"], cfg["moe_intermediate_size"]
    n_moe = cfg["num_layers"] - cfg["first_k_dense_replace"]
    flops = routed_rows * 2 * 3 * H * Fe
    io = n_moe * n_rows * H * (_item(cfg) + 4)
    return flops, experts_touched * expert_bytes(cfg) + io


def window_span_blocks(cfg, block_size):
    """The most blocks ONE live row's window read spans."""
    return -(-(cfg["sliding_window"] - 1) // block_size) + 1


def gqa_decode_call(cfg, live_blocks, block_size):
    """(operations, bytes) of the ONE full layer's paged decode read
    (`paged_gqa_attention`: the window layers' read has a name of its own).
    `live_blocks` is the tick's `kv_blocks`, the blocks the reads span in
    BOTH pools; a live row spans at most `window_span_blocks` of the window
    pool beside at least `system_prompt_tokens / block_size` of the full one
    (every request of the cell starts from a session's context), so the
    full pool's part is at least live_blocks x ctx / (ctx + span): the bound
    used, a little under what was read, never over."""
    ctx = cfg["system_prompt_tokens"] // block_size
    span = window_span_blocks(cfg, block_size)
    positions = live_blocks * ctx / (ctx + span) * block_size
    flops = 4 * cfg["num_attention_heads"] * cfg["head_dim"] * positions
    return flops, positions * kv_row_bytes(cfg)


def window_decode_call(cfg, window_rows):
    """(operations, bytes) of ONE window layer's decode read over
    `window_rows` positions in all (`engine/tick`'s `window_rows`: summed
    over the live rows, min(position + 1, sliding_window) each): a (query
    head, position) pair costs 2 * 2 * head_dim operations, a position's K
    and V are read once for the whole group of query heads."""
    flops = 4 * cfg["num_attention_heads"] * cfg["head_dim"] * window_rows
    return flops, window_rows * kv_row_bytes(cfg)
