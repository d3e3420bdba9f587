"""Adapter of the Ling-3.0-flash hybrid stack (five channel-wise gated
delta-rule (KDA) layers with a float32 MATRIX state a request to one
latent-attention layer with a gate a head; 128 of 512 small experts held as
one chip's two whole groups under group-limited top-8, beside a shared
expert): served through PagedKVEngine built from a model description
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
from paddle_tpu.models.decoder_spec import (DecoderSpec, KdaSpec, LatentSpec,
                                            MoESpec, RopeSpec)

from . import ling_reference
from .lfm2 import held_rows

layer_kinds = ling_reference.layer_kinds
ffn_kinds = ling_reference.ffn_kinds


def _ref_cfg(cfg):
    return dict(cfg, num_hidden_layers=cfg["num_layers"])


def spec_of(cfg):
    limits = cfg["expert_swiglu_limit_list"] \
        + cfg["share_expert_swiglu_limit_list"]
    if any(limits):
        raise NotImplementedError(
            "expert_swiglu_limit_list / share_expert_swiglu_limit_list: a "
            "clamped expert activation is not built (0 = off on every layer "
            "of the cut)")
    if (cfg["score_function"], cfg["topk_method"], cfg["hidden_act"]) != \
            ("sigmoid", "noaux_tc", "silu") or cfg["q_lora_rank"] is not None \
            or cfg["num_kv_heads_for_linear_attn"] or cfg["rope_scaling"] \
            or cfg["use_kda_lora"] or not cfg["kda_safe_gate"] \
            or not cfg["linear_silu"] or cfg["group_norm_size"] != 1 \
            or cfg["gated_attention_proj_granularity_type"] != "head_wise" \
            or cfg["use_bias"] or cfg["use_qkv_bias"] \
            or cfg["tie_word_embeddings"] or not cfg["use_qk_norm"]:
        raise NotImplementedError(
            "the adapter builds the published variant alone: sigmoid "
            "noaux_tc routing, one query matrix, as many kda heads as "
            "attention heads, the bounded sigmoid gate from full-rank "
            "projections, a head a norm group, a gate a head, no bias")
    kinds = ["attention" if k == "latent" else "kda"
             for k in layer_kinds(_ref_cfg(cfg))]
    moe = MoESpec(
        n_routed=cfg["router_width"], top_k=cfg["num_experts_per_tok"],
        d_expert=cfg["moe_intermediate_size"],
        held=tuple(range(cfg["num_experts"])),
        n_shared=cfg["num_shared_experts"],
        first_dense=cfg["first_k_dense_replace"],
        scaling=float(cfg["routed_scaling_factor"]),
        norm_topk_prob=cfg["norm_topk_prob"], topk_method="group_bias",
        norm_eps=1e-20, n_group=cfg["n_group"], topk_group=cfg["topk_group"],
        d_shared=cfg["moe_shared_expert_intermediate_size"])
    latent = LatentSpec(
        q_lora_rank=None, kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"], v_head_dim=cfg["v_head_dim"],
        rope=RopeSpec(dim=cfg["qk_rope_head_dim"],
                      theta=float(cfg["rope_theta"])), gate="head")
    kda = KdaSpec(heads=cfg["num_attention_heads"], head_dim=cfg["head_dim"],
                  taps=cfg["short_conv_kernel_size"],
                  gate_lower_bound=float(cfg["kda_lower_bound"]))
    return DecoderSpec.kda_latent_moe(
        vocab=cfg["vocab"], d_model=cfg["hidden_size"],
        d_inner=cfg["intermediate_size"], num_heads=cfg["num_attention_heads"],
        layer_kinds=kinds, kda=kda, latent=latent, moe=moe,
        norm_eps=cfg["rms_norm_eps"], dtype=cfg["weights_dtype"])


def param_shapes(cfg):
    """name -> (shape, how it is seeded: a fan-in (N(0, 1/fan-in)), None (a
    norm's scale: 1), ("centred", fan-in, axis) (N(0, 1/fan-in) less its mean
    over the fan-in axis: the kda output projection follows values whose mean
    is not zero, v = silu(..) and the sigmoid gate; centred, that mean adds no
    direction common to every row), or a tuple naming its own distribution),
    in the order the seeds are dealt."""
    H, nh, D, K = (cfg["hidden_size"], cfg["num_attention_heads"],
                   cfg["head_dim"], cfg["short_conv_kernel_size"])
    dn, dr, dv, c = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"], cfg["kv_lora_rank"])
    F, Fe, Fs = (cfg["intermediate_size"], cfg["moe_intermediate_size"],
                 cfg["moe_shared_expert_intermediate_size"])
    E, held, d_in = cfg["router_width"], cfg["num_experts"], nh * D
    ref = _ref_cfg(cfg)
    out = {"tok_emb": ((cfg["vocab"], H), 1)}
    for i, (kind, ffn) in enumerate(zip(layer_kinds(ref), ffn_kinds(ref))):
        out[f"l{i}_ln1.scale"] = ((H,), None)
        if kind == "kda":
            m = f"l{i}_kda"
            out.update({
                f"{m}_qkv.w_0": ((H, 3 * d_in), H),
                f"{m}_f.w_0": ((H, d_in), H),
                f"{m}_b.w_0": ((H, nh), H),
                f"{m}_taps": ((3 * d_in, K), K),
                f"{m}_a_log": ((nh,), ("a_log",)),
                f"{m}_dt_bias": ((d_in,), ("dt_bias",)),
                f"{m}_g.w_0": ((H, d_in), H),
                f"{m}_norm.scale": ((D,), None),
                f"{m}_o.w_0": ((d_in, H), ("centred", d_in, 0))})
        else:
            a = f"l{i}_attn"
            out.update({
                # scores of std 2.5, not 1: a trained head's weight lies on
                # 5-50 of ~2,500 keys (std sqrt(ln(n / n_eff)) = 2.0-2.5);
                # unit scores average ~900 and the layer's output vanishes
                f"{a}_q.w_0": ((H, nh * (dn + dr)), H / Q_GAIN ** 2),
                f"{a}_kva.w_0": ((H, c + dr), H),
                f"{a}_kva_norm.scale": ((c,), None),
                f"{a}_kvb.w_0": ((c, nh * (dn + dv)), c),
                f"{a}_gate.w_0": ((H, nh), H),
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
Q_GAIN = 2.5                # the latent query matrix's scale (`assumed.init`)


def _own(kind, key, shape):
    """The parameters with a distribution of their own, float32. The gate is
    g = kda_lower_bound * sigmoid(exp(A_log) * (u Wf + dt_bias)) with u Wf a
    unit normal: `a_log` is the log of a rate uniform in [0.5, 1.5] a head and
    `dt_bias` uniform in [-7, -1] a channel, so that a channel's decay a step
    spans exp(-5 sigmoid(-7)) = 0.995 to exp(-5 sigmoid(-1)) = 0.26 before the
    row's own term moves it: g spreads over (-5, 0), the slow channels
    remember across a 128-row chunk (a stale carry does not read clean) and
    the fast ones forget within one (a dropped decay reads)."""
    if kind[0] == "balanced":           # `balance_router_bias` sets it
        return jnp.zeros(shape, jnp.float32)
    u = jax.random.uniform(key, shape, jnp.float32)
    if kind[0] == "a_log":
        return jnp.log(0.5 + u)
    return -7.0 + 6.0 * u


def build_weights(cfg, seed):
    """Every parameter on the device: matrices and conv taps N(0, 1/fan-in)
    in `weights_dtype`, norms' scales 1, the kda gate's A_log and dt_bias and
    the router's bias in float32 (`_own`), one key a parameter from `seed`.
    The generator is the device's own (`rbg`) and ONE compiled function of
    `GEN_CHUNK` values whatever the parameter's shape. Nothing of the model
    is built."""
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
    """The routers' correction bias as load balancing leaves it (PR 43's
    lesson: with a random one the selection of seeded weights sits on a few
    experts and the bytes a tick streams are the seed's): one pass of the
    reference's own layers over `BALANCE_ROWS` seeded tokens, and in each
    routed layer, before it is applied, b_e = mean(t) - t_e with t_e the
    (1 - k/E) quantile of expert e's score over the rows, then
    `BALANCE_STEPS` steps of the update a training run makes UNDER THE GROUP
    STEP (b_e down where the group-limited top-k gave expert e more rows than
    the mean, up where fewer, a step that shrinks): every expert, and so
    every group and every chip, is selected about equally often. Float32,
    default matmul precision: it is a parameter's value that is made here,
    not a comparison."""
    ref, f32 = ling_reference, jnp.float32
    c = _ref_cfg(cfg)
    k, key = cfg["num_experts_per_tok"], ref.frozen(c)
    tokens = jax.random.randint(
        jax.random.fold_in(jax.random.key(seed, impl="rbg"), 2 ** 20),
        (min(BALANCE_ROWS, cfg["max_len"]),), 0, cfg["vocab"])
    x = jnp.asarray(scope.get("tok_emb"))[tokens].astype(f32)

    @jax.jit
    def bias(x, ln, w):
        u = ref.rms(x, ln.astype(f32), c["rms_norm_eps"])
        s = jax.nn.sigmoid(u @ w.astype(f32))
        t = jnp.quantile(s, 1.0 - k / s.shape[1], axis=0)

        def step(j, b):
            idx = ref.select(s + b, c)
            load = jnp.zeros_like(b).at[idx.ravel()].add(1.0)
            over = jnp.clip(load / jnp.mean(load) - 1.0, -1.0, 1.0)
            return b - BALANCE_STEP * BALANCE_DECAY ** j * over
        b = jax.lax.fori_loop(0, BALANCE_STEPS, step, jnp.mean(t) - t)
        return b - jnp.mean(b)

    for i, ffn in enumerate(ffn_kinds(c)):
        part = {n: scope.get(n) for n in param_names(cfg)
                if n.startswith(f"l{i}_")}
        x = ref.mixed(part, x, key, i, None, ref.hooks())
        if ffn == "moe":        # on the rows as the layer's second norm sees them
            part[f"l{i}_moe_router_bias"] = bias(
                x, part[f"l{i}_ln2.scale"], part[f"l{i}_moe_router.w_0"])
            scope.set_var(f"l{i}_moe_router_bias",
                          part[f"l{i}_moe_router_bias"])
        x = ref.fed(part, x, key, i, ref.hooks())


#: the requests the engine built here was handed, so that `reference_logits`
#: knows which rows of a checked sequence are the program's own
_taken = []


def build_engine(cfg, spec, scope):
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
    return engine


#: peak of device memory (arrays + reserved scratch) when the reference was
#: first called: the engine's own (metrics/serve_engine_peak_hbm_gb.py)
peak_before_reference = None
#: a list here takes (tokens, the rows the program emitted from, as the
#: reference gives them BEFORE `held_rows`) of every call: benchmark/witness.py
rows_kept = None


def envelope_logits(cfg, params, tokens, pad_to):
    """Full forward over `tokens`, padded on the right to a fixed length (the
    model is causal, so the padding changes no earlier position). One path:
    this reference follows no near-tied selection (the name is the one
    benchmark/witness.py calls)."""
    global peak_before_reference
    if peak_before_reference is None:
        from .. import harness
        peak_before_reference = harness.memory_peak_bytes(jax.devices()[:1])
    padded = np.zeros((pad_to,), np.int32)
    padded[:len(tokens)] = tokens
    with jax.default_matmul_precision("highest"):
        return ling_reference.logits(
            params, padded, _ref_cfg(cfg),
            cache_round=cfg.get("cache_round"))[:len(tokens)]


def reference_logits(cfg, params, tokens, pad_to):
    """`envelope_logits`; the rows a request of this engine emitted from are
    read as `lfm2.held_rows` says, where the configuration gives
    `check_rows_held` (a sequence no request emitted, or a configuration
    without it: the reference's rows as they are)."""
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
    precision below the stated one, ALL of it: every matrix, every value an
    operator hands on, every latent row and convolution state row through
    float8's 3 mantissa bits for bfloat16's 7 (by arithmetic on the bits: the
    exponent's range is not narrowed), and the kda layers' state S through
    bfloat16 for float32. The reading a cell's limit has to refuse
    (benchmark/control.py)."""
    below = {"bfloat16": "float8_e4m3fn", "float32": "bfloat16"}[
        cfg["weights_dtype"]]
    ref = ling_reference
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
    precision (ling_reference.ROUND_ACTIVATIONS_THROUGH): the witness, one
    plain forward (benchmark/witness.py)."""
    ling_reference.ROUND_ACTIVATIONS_THROUGH = cfg["weights_dtype"]
    try:
        yield dict(cfg, cache_round=cfg["cache_dtype"])
    finally:
        ling_reference.ROUND_ACTIVATIONS_THROUGH = None


FAULTS = ("decay_dropped", "delta_dropped", "beta_dropped",
          "group_step_dropped", "bias_weighs", "head_gate_dropped",
          "snapshot_stale")


@contextlib.contextmanager
def planted(fault, cfg, scope):
    """The configuration with one fault planted on the REFERENCE's side of
    the comparison, for as long as the block lasts, one in each new
    mechanism. `decay_dropped`: g = 0, no channel forgets; `delta_dropped`:
    the state is not corrected by what it holds (plain gated linear
    attention); `beta_dropped`: every write at full strength;
    `group_step_dropped`: the top-k of all experts; `bias_weighs`: the
    weights are the biased scores; `head_gate_dropped`: the latent layers'
    gate; `snapshot_stale`: from the end of the system prompt on
    (`system_prompt_tokens`) every kda layer continues from the state one
    chunk earlier, which is what a restore from a stale snapshot computes."""
    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    ling_reference.FAULT = (
        ("stale", int(cfg["system_prompt_tokens"]), int(cfg["chunk_size"]))
        if fault == "snapshot_stale" else fault)
    try:
        yield cfg
    finally:
        ling_reference.FAULT = None


# -- counts the per-layer readers divide by --------------------------------

def _item(cfg, key="weights_dtype"):
    return np.dtype(jnp.dtype(cfg[key])).itemsize


def n_layers(cfg, kind):
    return layer_kinds(_ref_cfg(cfg)).count(kind)


def n_moe(cfg):
    return ffn_kinds(_ref_cfg(cfg)).count("moe")


def expert_bytes(cfg):
    """One routed expert's three matrices, as stored."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"] * _item(cfg)


def h_bytes(cfg):
    """One kda layer's state S of ONE request, float32."""
    return cfg["num_attention_heads"] * cfg["head_dim"] ** 2 * 4


def latent_row_bytes(cfg):
    """ONE position's row in the ONE latent layer's pool, as stored: c and
    the rotated k_pe padded to whole 128-lane rows (576 -> 640 values)."""
    row = cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    return -(-row // 128) * 128 * _item(cfg, "cache_dtype")


def mla_call(cfg, n_query, live_positions):
    """(operations, bytes) of ONE layer's latent read over `live_positions`
    cache rows in all (summed over the slots), `n_query` query positions a
    slot: a (query position, row) pair costs 2 * heads * (row values + c)
    operations, and a row's stored lanes are read once."""
    nh = cfg["num_attention_heads"]
    row = cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    flops = 2 * nh * (row + cfg["kv_lora_rank"]) * n_query * live_positions
    return flops, live_positions * latent_row_bytes(cfg)


def experts_call(cfg, n_rows, experts_touched, routed_rows):
    """(operations, bytes) of the grouped expert product over ALL routed
    layers of one tick: a routed (row, expert) pair costs the three matmuls;
    a touched expert's weights are read once, the rows in and out once a
    layer."""
    H, Fe = cfg["hidden_size"], cfg["moe_intermediate_size"]
    flops = routed_rows * 2 * 3 * H * Fe
    io = n_moe(cfg) * n_rows * H * (_item(cfg) + 4)
    return flops, experts_touched * expert_bytes(cfg) + io


def kda_decode_call(cfg, live_rows):
    """(operations, bytes) of the delta-rule decode update over ALL kda
    layers of one tick with `live_rows` live decode rows: a live row's S read
    and written, its q, k, beta * k and exp(g) (a float32 value a key channel
    each) and v in and o out; per state value the decay's multiply, the
    held value's multiply-add, the write's and the readout's."""
    nh, D = cfg["num_attention_heads"], cfg["head_dim"]
    n = n_layers(cfg, "kda")
    row_io = 4 * 6 * nh * D
    return (n * live_rows * 7 * nh * D * D,
            n * live_rows * (2 * h_bytes(cfg) + row_io))
