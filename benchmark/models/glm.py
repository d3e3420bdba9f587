"""Adapter of the GLM-5.3-Flash stack (four residual streams mixed through
Sinkhorn around every sub-layer; 64-head channel-wise gated delta-rule (KDA)
layers with low-rank gate pairs; ONE sparse NoPE latent layer whose indexer
selects 2,048 of a request's cached positions over pooled keys in a second
pool; 36 of 288 experts held beside a shared one, every gated pair clamped):
served through PagedKVEngine built from a model description
(`paddle_tpu.models.decoder_spec.DecoderSpec`), weights seeded on the device in
the configuration's dtype, and the counts of bytes and operations the per-layer
readers divide by."""

from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp
import numpy as np

# at import, so that a tree without these kinds (the parent of the PR that
# brought them) fails on the cell at once, before it touches the chip
from paddle_tpu.models.decoder_spec import (DecoderSpec, HyperSpec,
                                            IndexerSpec, KdaSpec, LatentSpec,
                                            MoESpec, RopeSpec)

from .. import scopes
from . import glm_reference
from .falcon_h1 import RowsFrom
from .lfm2 import held_rows

layer_kinds = glm_reference.layer_kinds
ffn_kinds = glm_reference.ffn_kinds
#: the engine `build_engine` made last: its two ticks' compiled texts say
#: which scope each of a trace's operations was traced under
_engine = None


def _tick_texts():
    return [_engine.tick_hlo(), _engine.mixed_tick_hlo()] if _engine else []


# the readers of what this model leaves to XLA (the stream mixing, the index
# scores, the gather) find it in a trace by its scope
scopes.install(_tick_texts)


def spec_of(cfg):
    lin = cfg["linear_attn_config"]
    if (cfg["scoring_func"], cfg["topk_method"], cfg["hidden_act"]) != \
            ("sigmoid", "noaux_tc", "silu") or cfg["n_group"] != 1 \
            or cfg["topk_group"] != 1 or cfg["qk_rope_head_dim"] \
            or not cfg["mla_use_nope"] or not cfg["mhc"] \
            or cfg["attention_bias"] or cfg["tie_word_embeddings"] \
            or not cfg["index_kpool_compress"] \
            or not cfg["index_kpool_always_select_tail"] \
            or set(cfg["indexer_types"]) != {"full"} \
            or lin["num_heads"] != cfg["num_attention_heads"]:
        raise NotImplementedError(
            "the adapter builds the published variant alone: sigmoid "
            "noaux_tc routing without groups, an unrotated latent row, four "
            "mixed residual streams, pooled index keys alone in the index "
            "pool with the tail always selected, every sparse layer its own "
            "index, as many kda heads as attention heads, no bias")
    kinds = ["attention" if k == "dsa" else "kda" for k in layer_kinds(cfg)]
    dense = ffn_kinds(cfg).count("dense")
    if ffn_kinds(cfg) != ["dense"] * dense + ["moe"] * (len(kinds) - dense):
        raise NotImplementedError("mlp_layer_types: dense layers lead")
    moe = MoESpec(
        n_routed=cfg["router_width"], top_k=cfg["num_experts_per_tok"],
        d_expert=cfg["moe_intermediate_size"],
        held=tuple(range(cfg["n_routed_experts"])),
        n_shared=cfg["n_shared_experts"], first_dense=dense,
        scaling=float(cfg["routed_scaling_factor"]),
        norm_topk_prob=cfg["norm_topk_prob"], topk_method="bias",
        norm_eps=1e-20, swiglu_limit=float(cfg["swiglu_limit"]))
    latent = LatentSpec(
        q_lora_rank=cfg["q_lora_rank"], kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"], v_head_dim=cfg["v_head_dim"],
        rope=None)
    indexer = IndexerSpec(
        heads=cfg["index_n_heads"], head_dim=cfg["index_head_dim"],
        topk=cfg["index_topk"], kpool=cfg["index_kpool"],
        rope=RopeSpec(dim=cfg["index_rope_dim"],
                      theta=float(cfg["index_rope_theta"])))
    kda = KdaSpec(heads=lin["num_heads"], head_dim=lin["head_dim"],
                  taps=lin["short_conv_kernel_size"],
                  gate_lower_bound=float(lin["gate_lower_bound"]),
                  gate_rank=cfg["kda_gate_rank"])
    return DecoderSpec.kda_latent_moe(
        vocab=cfg["vocab"], d_model=cfg["hidden_size"],
        d_inner=cfg["intermediate_size"], num_heads=cfg["num_attention_heads"],
        layer_kinds=kinds, kda=kda, latent=latent, moe=moe,
        norm_eps=cfg["rms_norm_eps"], dtype=cfg["weights_dtype"],
        indexer=indexer,
        hyper=HyperSpec(mult=cfg["hc_mult"],
                        sinkhorn_iters=cfg["hc_sinkhorn_iters"],
                        eps=cfg["hc_eps"]))


Q_GAIN = 2.5                # the latent query matrix's scale (`assumed.init`)
HC_DIAGONAL = 2.0           # b_res on the diagonal, before the exponential


def param_shapes(cfg):
    """name -> (shape, how it is seeded: a fan-in (N(0, 1/fan-in)), None (a
    norm's scale: 1), ("centred", fan-in, axis) (N(0, 1/fan-in) less its mean
    over the fan-in axis), or a tuple naming its own distribution), in the
    order the seeds are dealt."""
    H, nh, lin = (cfg["hidden_size"], cfg["num_attention_heads"],
                  cfg["linear_attn_config"])
    D, K, rank = (lin["head_dim"], lin["short_conv_kernel_size"],
                  cfg["kda_gate_rank"])
    dn, dv, c, cq = (cfg["qk_nope_head_dim"], cfg["v_head_dim"],
                     cfg["kv_lora_rank"], cfg["q_lora_rank"])
    nI, dI = cfg["index_n_heads"], cfg["index_head_dim"]
    F, Fe = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    Fs = Fe * cfg["n_shared_experts"]
    E, held, d_in = cfg["router_width"], cfg["n_routed_experts"], \
        lin["num_heads"] * D
    n = cfg["hc_mult"]
    maps = 2 * n + n * n
    out = {"tok_emb": ((cfg["vocab"], H), 1)}
    for i, (kind, ffn) in enumerate(zip(layer_kinds(cfg), ffn_kinds(cfg))):
        for j in (1, 2):
            out[f"l{i}_hc{j}_p"] = ((n * H, maps), n * H)
            out[f"l{i}_hc{j}_a"] = ((3,), ("ones",))
            out[f"l{i}_hc{j}_b"] = ((maps,), ("hc_b", n))
        out[f"l{i}_ln1.scale"] = ((H,), None)
        if kind == "kda":
            m = f"l{i}_kda"
            out.update({
                f"{m}_qkv.w_0": ((H, 3 * d_in), H),
                f"{m}_fa.w_0": ((H, rank), H),
                f"{m}_fb.w_0": ((rank, d_in), rank),
                f"{m}_b.w_0": ((H, lin["num_heads"]), H),
                f"{m}_taps": ((3 * d_in, K), K),
                f"{m}_a_log": ((lin["num_heads"],), ("a_log",)),
                f"{m}_dt_bias": ((d_in,), ("dt_bias",)),
                f"{m}_ga.w_0": ((H, rank), H),
                f"{m}_gb.w_0": ((rank, d_in), rank),
                f"{m}_norm.scale": ((D,), None),
                f"{m}_o.w_0": ((d_in, H), ("centred", d_in, 0))})
        else:
            a = f"l{i}_attn"
            out.update({
                f"{a}_qa.w_0": ((H, cq), H),
                f"{a}_qa_norm.scale": ((cq,), None),
                # scores of std 2.5, not 1 (`assumed.init`)
                f"{a}_qb.w_0": ((cq, nh * dn), cq / Q_GAIN ** 2),
                f"{a}_kva.w_0": ((H, c), H),
                f"{a}_kva_norm.scale": ((c,), None),
                f"{a}_kvb.w_0": ((c, nh * (dn + dv)), c),
                f"{a}_ik.w_0": ((H, dI), H),
                f"{a}_ik_norm.scale": ((dI,), None),
                f"{a}_ik_norm.bias": ((dI,), ("zeros",)),
                f"{a}_iw.w_0": ((H, nI), H),
                f"{a}_iq.w_0": ((cq, nI * dI), cq),
                f"{a}_o.w_0": ((nh * dv, H), nh * dv)})
        out[f"l{i}_ln2.scale"] = ((H,), None)
        if ffn == "dense":
            f = f"l{i}_ffn"
            out.update({f"{f}_gate.w_0": ((H, F), H),
                        f"{f}_up.w_0": ((H, F), H),
                        f"{f}_down.w_0": ((F, H), F)})
        else:
            m = f"l{i}_moe"
            out.update({
                f"{m}_router.w_0": ((H, E), H),
                f"{m}_experts_gate": ((held, H, Fe), H),
                f"{m}_experts_up": ((held, H, Fe), H),
                f"{m}_experts_down": ((held, Fe, H), Fe),
                f"{m}_router_bias": ((E,), ("balanced",)),
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


def _own(kind, key, shape):
    """The parameters with a distribution of their own, float32: the kda
    gate's `a_log` and `dt_bias` as `ling._own` draws them (a channel's decay
    a step spans 0.995 to 0.26); the stream maps' scalars 1 and their bias 0
    but `HC_DIAGONAL` on H_res's diagonal (before the exponential: a stream
    keeps ~70% of itself through a sub-layer, and the dynamic part, a unit
    normal, moves every entry by e^+-1); the router's bias zero until
    `balance_router_bias` sets it."""
    if kind[0] in ("balanced", "zeros"):
        return jnp.zeros(shape, jnp.float32)
    if kind[0] == "ones":
        return jnp.ones(shape, jnp.float32)
    if kind[0] == "hc_b":
        n = kind[1]
        return jnp.concatenate([jnp.zeros((2 * n,), jnp.float32),
                                HC_DIAGONAL * jnp.eye(n).reshape(-1)])
    u = jax.random.uniform(key, shape, jnp.float32)
    if kind[0] == "a_log":
        return jnp.log(0.5 + u)
    return -7.0 + 6.0 * u


def build_weights(cfg, seed):
    """Every parameter on the device: matrices and conv taps N(0, 1/fan-in)
    in `weights_dtype`, norms' scales 1, the parameters of `_own` in float32,
    one key a parameter from `seed`. The generator is the device's own (`rbg`)
    and ONE compiled function of `GEN_CHUNK` values whatever the parameter's
    shape. Nothing of the model is built."""
    import paddle_tpu as pt
    dtype = jnp.dtype(cfg["weights_dtype"])
    scope = pt.Scope()
    root = jax.random.key(seed, impl="rbg")
    chunk = jax.jit(lambda key: jax.random.normal(key, (GEN_CHUNK,), dtype))

    @functools.lru_cache(maxsize=None)
    def cut(shape, std, centre=None):
        n = int(np.prod(shape))

        def make(parts):
            w = (jnp.concatenate(parts)[:n] * jnp.asarray(std, dtype)
                 ).reshape(shape)
            if centre is None:
                return w
            w = w.astype(jnp.float32)
            return (w - jnp.mean(w, axis=centre, keepdims=True)).astype(dtype)
        return jax.jit(make)

    for k, (name, (shape, how)) in enumerate(param_shapes(cfg).items()):
        key = jax.random.fold_in(root, k)
        if how is None:
            scope.set_var(name, jnp.ones(shape, dtype))
        elif isinstance(how, tuple) and how[0] != "centred":
            scope.set_var(name, _own(how, key, shape))
        else:
            fan_in, centre = (how[1], how[2]) if isinstance(how, tuple) \
                else (how, None)
            n_chunks = -(-int(np.prod(shape)) // GEN_CHUNK)
            scope.set_var(name, cut(shape, float(fan_in) ** -0.5, centre)(
                [chunk(jax.random.fold_in(key, i)) for i in range(n_chunks)]))
    balance_router_bias(cfg, scope, seed)
    return scope


BALANCE_ROWS = 1536
BALANCE_STEPS, BALANCE_STEP, BALANCE_DECAY = 48, 0.02, 0.88


def balance_router_bias(cfg, scope, seed):
    """The routers' correction bias as load balancing leaves it
    (`ling.balance_router_bias`, without a group step): one pass of the
    reference's own sub-layers over `BALANCE_ROWS` seeded tokens, and in each
    routed layer, on the rows its second norm sees, b_e = mean(t) - t_e with
    t_e the (1 - k/E) quantile of expert e's score, then `BALANCE_STEPS`
    shrinking steps of the update a training run makes: every expert, and so
    every rank, is selected about equally often. Float32, default matmul
    precision: it is a parameter's value that is made here."""
    ref, f32 = glm_reference, jnp.float32
    k = cfg["num_experts_per_tok"]
    tokens = jax.random.randint(
        jax.random.fold_in(jax.random.key(seed, impl="rbg"), 2 ** 20),
        (min(BALANCE_ROWS, cfg["max_len"]),), 0, cfg["vocab"])
    x = np.asarray(jnp.asarray(scope.get("tok_emb"))[tokens].astype(f32))
    X = np.repeat(x[:, None, :], cfg["hc_mult"], axis=1)

    @jax.jit
    def bias(u, w):
        s = jax.nn.sigmoid(u @ w.astype(f32))
        t = jnp.quantile(s, 1.0 - k / s.shape[1], axis=0)

        def step(j, b):
            idx = ref.select(s + b, cfg)
            load = jnp.zeros_like(b).at[idx.ravel()].add(1.0)
            over = jnp.clip(load / jnp.mean(load) - 1.0, -1.0, 1.0)
            return b - BALANCE_STEP * BALANCE_DECAY ** j * over
        b = jax.lax.fori_loop(0, BALANCE_STEPS, step, jnp.mean(t) - t)
        return b - jnp.mean(b)

    params = {n: scope.get(n) for n in param_names(cfg)}
    for i, ffn in enumerate(ffn_kinds(cfg)):
        part = ref.layer_params(params, i)

        def balanced(u):    # on the rows as the layer's second norm sees them
            part["moe_router_bias"] = bias(u, part["moe_router.w_0"])
            scope.set_var(f"l{i}_moe_router_bias", part["moe_router_bias"])
        ref.layer(part, X, cfg, i, None, balanced if ffn == "moe" else None)


#: the requests the engine built here was handed, so that `reference_logits`
#: knows which rows of a checked sequence are the program's own
_taken = []


def build_engine(cfg, spec, scope):
    global _engine
    from paddle_tpu import serving
    if spec["class"] != "PagedKVEngine":
        raise ValueError(f"unknown engine class {spec['class']!r}")
    engine = serving.PagedKVEngine(
        n_slots=spec["n_slots"], max_len=spec["max_len"],
        block_size=spec["block_size"], n_blocks=spec["n_blocks"],
        n_snapshots=spec["n_snapshots"], scope=scope, model=spec_of(cfg))
    submit = engine.submit

    def noting(*args, **kwargs):
        req = submit(*args, **kwargs)
        _taken.append(req)
        return req
    engine.submit = noting
    del _taken[:]
    _engine = engine
    return engine


#: peak of device memory (arrays + reserved scratch) when the reference was
#: first called: the engine's own (metrics/serve_engine_peak_hbm_gb.py)
peak_before_reference = None
#: a list here takes (tokens, the rows the program emitted from, as the
#: reference gives them BEFORE `held_rows`) of every call: benchmark/witness.py
rows_kept = None


def _request_of(seq):
    return next((r for r in reversed(_taken)
                 if r.done and len(r.prompt) + len(r.tokens) - 1 == len(seq)
                 and list(r.prompt) + list(r.tokens[:-1]) == seq), None)


def envelope_logits(cfg, params, tokens, pad_to):
    """Full forward over `tokens`, padded on the right to a whole number of
    the reference's row blocks (the model is causal, so the padding changes no
    earlier position; `pad_to` bounds it). The final norm and the head run on
    the rows that are read: where `tokens` is a finished request of this
    engine, the rows from the prompt's last position on, returned as a
    `RowsFrom` that the loop's `ref[len(prompt) - 1:]` reads as it reads an
    array; any other sequence gets every row. One path: this reference
    follows no near-tied selection."""
    global peak_before_reference
    if peak_before_reference is None:
        from .. import harness
        peak_before_reference = harness.memory_peak_bytes(jax.devices()[:1])
    seq = [int(t) for t in tokens]
    block = glm_reference.ROW_BLOCK
    whole_blocks = -(-len(seq) // block) * block
    padded = np.zeros((min(whole_blocks, max(pad_to, len(seq))),), np.int32)
    padded[:len(seq)] = seq
    req = _request_of(seq)
    first = 0 if req is None else len(req.prompt) - 1
    with jax.default_matmul_precision("highest"):
        rows = glm_reference.logits(
            params, padded, cfg, cache_round=cfg.get("cache_round"),
            rows_from=first, rows_to=len(seq))
    return rows if req is None else RowsFrom(first, rows)


def reference_logits(cfg, params, tokens, pad_to):
    """`envelope_logits`; the rows a request of this engine emitted from are
    read as `lfm2.held_rows` says, where the configuration gives
    `check_rows_held`."""
    out = envelope_logits(cfg, params, tokens, pad_to)
    if not isinstance(out, RowsFrom):
        return out
    req = _request_of([int(t) for t in tokens])
    if rows_kept is not None:
        rows_kept.append(([int(t) for t in tokens], out.rows.copy()))
    if cfg.get("check_rows_held"):
        held_rows(out.rows, np.asarray(req.tokens),
                  float(cfg["check_rows_held"]), float(cfg["check_echo"]))
    return out


@contextlib.contextmanager
def one_precision_below(cfg):
    """The configuration as `reference_logits` computes it in the nearest
    precision below the stated one, ALL of it: every matrix, every value an
    operator hands on, every latent row, pooled key and convolution state row
    through float8 e4m3's 3 mantissa bits for bfloat16's 7 (by arithmetic on
    the bits: the exponent's range is not narrowed, so the clamp's 10 and the
    streams' values keep theirs), and the kda layers' state S through
    bfloat16 for float32."""
    below = {"bfloat16": "float8_e4m3fn", "float32": "bfloat16"}[
        cfg["weights_dtype"]]
    ref = glm_reference
    ref.ROUND_WEIGHTS_THROUGH = ref.ROUND_ACTIVATIONS_THROUGH = below
    ref.ROUND_STATE_THROUGH = "bfloat16"
    try:
        yield dict(cfg, cache_round=below)
    finally:
        ref.ROUND_WEIGHTS_THROUGH = ref.ROUND_ACTIVATIONS_THROUGH = None
        ref.ROUND_STATE_THROUGH = None


@contextlib.contextmanager
def at_stated_precision(cfg):
    """The configuration as `envelope_logits` computes it AT the stated
    precision: the witness, one plain forward (benchmark/witness.py)."""
    glm_reference.ROUND_ACTIVATIONS_THROUGH = cfg["weights_dtype"]
    try:
        yield dict(cfg, cache_round=cfg["cache_dtype"])
    finally:
        glm_reference.ROUND_ACTIVATIONS_THROUGH = None


FAULTS = ("sinkhorn_dropped", "hres_identity", "hc_static",
          "streams_collapsed", "tail_dropped", "selection_ignored",
          "pool_first", "indexer_unrotated", "clamp_dropped",
          "snapshot_stale")


@contextlib.contextmanager
def planted(fault, cfg, scope):
    """The configuration with one fault planted on the REFERENCE's side of
    the comparison, one in each new mechanism. `sinkhorn_dropped`: H_res is
    the exponential as it is; `hres_identity`: H_res = I; `hc_static`: the
    maps' dynamic part dropped (their biases alone); `streams_collapsed`:
    one stream (every stream x + F(norm(x))); `tail_dropped`: the unfinished
    group's positions not attended (a row keeps itself); `selection_ignored`:
    the read is dense; `pool_first`: a group's key is its first position's,
    not the mean; `indexer_unrotated`; `clamp_dropped`: no swiglu_limit;
    `snapshot_stale`: from the end of the resident context on
    (`system_prompt_tokens`) every kda layer continues from the state one
    chunk earlier."""
    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    glm_reference.FAULT = (
        ("stale", int(cfg["system_prompt_tokens"]), int(cfg["chunk_size"]))
        if fault == "snapshot_stale" else fault)
    try:
        yield cfg
    finally:
        glm_reference.FAULT = None


# -- counts the per-layer readers divide by --------------------------------

def _item(cfg, key="weights_dtype"):
    return np.dtype(jnp.dtype(cfg[key])).itemsize


def n_layers(cfg, kind):
    return layer_kinds(cfg).count(kind)


def n_moe(cfg):
    return ffn_kinds(cfg).count("moe")


def expert_bytes(cfg):
    """One routed expert's three matrices, as stored."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"] * _item(cfg)


def h_bytes(cfg):
    """One kda layer's state S of ONE request, float32."""
    lin = cfg["linear_attn_config"]
    return lin["num_heads"] * lin["head_dim"] ** 2 * 4


def latent_row_bytes(cfg):
    """ONE position's row in the sparse layer's latent pool: c alone."""
    return cfg["kv_lora_rank"] * _item(cfg, "cache_dtype")


def pooled_row_bytes(cfg):
    """ONE pooled index key (a group of `index_kpool` positions)."""
    return cfg["index_head_dim"] * _item(cfg, "cache_dtype")


def dsa_call(cfg, rows, live_positions, selected_positions):
    """(operations, bytes) of ONE sparse layer's read over `rows` rows of a
    tick whose contexts hold `live_positions` positions in all and which
    attend `selected_positions` of them: the index scores over every live
    pooled key (2 * index heads * index dim a (row, group) pair; a pooled row
    read once), then per selected position 2 * heads * (c + c) operations and
    the row of c read ONCE (the scratch the gather writes and the read takes
    back is this program's way, not the least)."""
    nh, c = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    groups = live_positions / cfg["index_kpool"]
    flops = 2 * cfg["index_n_heads"] * cfg["index_head_dim"] * groups \
        + 2 * nh * 2 * c * selected_positions
    io = rows * (nh * c * 2 + cfg["index_n_heads"] * cfg["index_head_dim"]) \
        * _item(cfg)
    return flops, (groups * pooled_row_bytes(cfg)
                   + selected_positions * latent_row_bytes(cfg) + io)


def experts_call(cfg, n_rows, experts_touched, routed_rows):
    """(operations, bytes) of the grouped expert product over ALL routed
    layers of one tick (`ling.experts_call`'s reckoning)."""
    H, Fe = cfg["hidden_size"], cfg["moe_intermediate_size"]
    flops = routed_rows * 2 * 3 * H * Fe
    io = n_moe(cfg) * n_rows * H * (_item(cfg) + 4)
    return flops, experts_touched * expert_bytes(cfg) + io


def kda_decode_call(cfg, live_rows):
    """(operations, bytes) of the delta-rule decode update over ALL kda
    layers of one tick with `live_rows` live decode rows
    (`ling.kda_decode_call`'s reckoning at this model's sizes)."""
    lin = cfg["linear_attn_config"]
    nh, D = lin["num_heads"], lin["head_dim"]
    n = n_layers(cfg, "kda")
    row_io = 4 * 6 * nh * D
    return (n * live_rows * 7 * nh * D * D,
            n * live_rows * (2 * h_bytes(cfg) + row_io))


def dense_bytes(cfg):
    """Every byte of weights a tick streams whatever it routes: all but the
    routed experts' stacks and the embedding (whose rows a tick gathers)."""
    return sum(int(np.prod(s)) * (4 if isinstance(how, tuple)
                                  and how[0] != "centred" else _item(cfg))
               for name, (s, how) in param_shapes(cfg).items()
               if "_experts_" not in name and name != "tok_emb")


def tick_call(cfg, rows, state_rows, experts_touched, routed_rows,
              live_positions, selected_positions):
    """(operations, bytes) of ONE whole tick at its least: the dense weights
    once, the touched experts, every live decode row's state read and
    written, the sparse read, and 2 operations a row and dense or routed
    weight value."""
    d_flops, d_bytes = dsa_call(cfg, rows, live_positions, selected_positions)
    e_flops, e_bytes = experts_call(cfg, rows, experts_touched, routed_rows)
    k_flops, k_bytes = kda_decode_call(cfg, state_rows)
    dense = dense_bytes(cfg)
    flops = d_flops + e_flops + k_flops + 2 * rows * dense / _item(cfg)
    return flops, dense + d_bytes + e_bytes + k_bytes
