"""Adapter of the Nemotron-H hybrid stack (one sublayer a layer: Mamba-2
mixers with a float32 state a request, grouped-query attention without
positions, latent routed experts held as one chip's share beside a shared
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

# at import (`SsmSpec` for nothing else), so that a tree without these kinds
# (the parent of the PR that brought them) fails on the cell at once, before
# it touches the chip
from paddle_tpu.models.decoder_spec import (DecoderSpec, MoESpec,  # noqa: F401
                                            SsmSpec)

from . import nemotron_h_reference
from .lfm2 import held_rows

layer_kinds = nemotron_h_reference.layer_kinds


def spec_of(cfg):
    if cfg["mlp_hidden_act"] != "relu2" or not cfg["use_conv_bias"] \
            or cfg["n_group"] != 1 or cfg["topk_group"] != 1:
        raise NotImplementedError("mlp_hidden_act / use_conv_bias / n_group")
    ssm = SsmSpec(heads=cfg["mamba_num_heads"], head_dim=cfg["mamba_head_dim"],
                  groups=cfg["n_groups"], state=cfg["ssm_state_size"],
                  taps=cfg["conv_kernel"])
    moe = MoESpec(
        n_routed=cfg["router_width"], top_k=cfg["num_experts_per_tok"],
        d_expert=cfg["moe_intermediate_size"],
        held=tuple(range(cfg["n_routed_experts"])),
        n_shared=cfg["n_shared_experts"], first_dense=0,
        scaling=float(cfg["routed_scaling_factor"]),
        norm_topk_prob=cfg["norm_topk_prob"], topk_method="bias",
        norm_eps=1e-20, activation="relu2", latent=cfg["moe_latent_size"],
        d_shared=cfg["moe_shared_expert_intermediate_size"])
    return DecoderSpec.ssm_gqa_moe(
        vocab=cfg["vocab"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], d_head=cfg["head_dim"],
        layer_kinds=layer_kinds(cfg), ssm=ssm, moe=moe,
        norm_eps=cfg["layer_norm_epsilon"], dtype=cfg["weights_dtype"])


def param_shapes(cfg):
    """name -> (shape, how it is seeded: a fan-in (N(0, 1/fan-in)), None (a
    norm's scale: 1), ("centred", fan-in, axis) (N(0, 1/fan-in) less its
    mean over the fan-in axis: the matrices that follow an activation whose
    mean is not zero, relu^2 and the gated state-space output; their columns
    then sum to zero and that mean adds no direction common to every row,
    which random out-projections would otherwise stack up layer by layer
    until every row routes to the same few experts and decodes the same
    token), or a tuple naming its own distribution), in the order the seeds
    are dealt."""
    H, nh, nkv, dh = (cfg["hidden_size"], cfg["num_attention_heads"],
                      cfg["num_key_value_heads"], cfg["head_dim"])
    mh, mp, G, N, K = (cfg["mamba_num_heads"], cfg["mamba_head_dim"],
                       cfg["n_groups"], cfg["ssm_state_size"],
                       cfg["conv_kernel"])
    d_in, cd = mh * mp, mh * mp + 2 * G * N
    Z, F, Fs = (cfg["moe_latent_size"], cfg["moe_intermediate_size"],
                cfg["moe_shared_expert_intermediate_size"])
    E, held = cfg["router_width"], cfg["n_routed_experts"]
    out = {"tok_emb": ((cfg["vocab"], H), 1)}
    for i, kind in enumerate(layer_kinds(cfg)):
        out[f"l{i}_ln1.scale"] = ((H,), None)
        if kind == "ssm":
            m = f"l{i}_ssm"
            out.update({
                f"{m}_in.w_0": ((H, 2 * d_in + 2 * G * N + mh), H),
                f"{m}_taps": ((cd, K), K),
                f"{m}_conv_bias": ((cd,), ("normal", 0.1)),
                f"{m}_a_log": ((mh,), ("a_log",)),
                f"{m}_dt_bias": ((mh,), ("dt_bias",)),
                f"{m}_d": ((mh,), ("ones",)),
                f"{m}_norm.scale": ((d_in,), None),
                f"{m}_out.w_0": ((d_in, H), ("centred", d_in, 0))})
        elif kind == "attention":
            a = f"l{i}_attn"
            out.update({f"{a}_q.w_0": ((H, nh * dh), H),
                        f"{a}_k.w_0": ((H, nkv * dh), H),
                        f"{a}_v.w_0": ((H, nkv * dh), H),
                        f"{a}_o.w_0": ((nh * dh, H), nh * dh)})
        else:
            m = f"l{i}_moe"
            out.update({
                f"{m}_router.w_0": ((H, E), H),
                f"{m}_experts_up": ((held, Z, F), Z),
                # relu(u)^2 of a unit-variance u has a second moment of 3/2
                f"{m}_experts_down": ((held, F, Z), ("centred", 1.5 * F, 1)),
                f"{m}_router_bias": ((E,), ("balanced",)),
                f"{m}_latent_down.w_0": ((H, Z), H),
                f"{m}_latent_up.w_0": ((Z, H), Z),
                f"{m}_shared_up.w_0": ((H, Fs), H),
                f"{m}_shared_down.w_0": ((Fs, H), ("centred", 1.5 * Fs, 0))})
    out["final_norm.scale"] = ((H,), None)
    out["lm_head.w_0"] = ((H, cfg["vocab"]), H)
    return out


def param_names(cfg):
    return list(param_shapes(cfg))


def n_params(cfg):
    return sum(int(np.prod(s)) for s, _ in param_shapes(cfg).values())


GEN_CHUNK = 1 << 25         # values one call of the generator makes


def _own(kind, key, shape, cfg):
    """The parameters with a distribution of their own, float32. `dt_bias`:
    softplus^-1 of a dt log-uniform in [time_step_min, time_step_max] (the
    source's own range); `a_log`: log of A uniform in [1, 16]. exp(dt A)
    then spans 0.2-0.999 a step with a median near 0.93: a state that
    remembers across a chunk, so that a wrong carry does not read clean."""
    if kind[0] == "ones":
        return jnp.ones(shape, jnp.float32)
    if kind[0] == "balanced":           # `balance_router_bias` sets it
        return jnp.zeros(shape, jnp.float32)
    if kind[0] == "normal":
        return kind[1] * jax.random.normal(key, shape, jnp.float32)
    u = jax.random.uniform(key, shape, jnp.float32)
    if kind[0] == "a_log":
        return jnp.log(1.0 + 15.0 * u)
    lo, hi = cfg["time_step_min"], cfg["time_step_max"]
    dt = jnp.maximum(jnp.exp(u * (np.log(hi) - np.log(lo)) + np.log(lo)),
                     cfg["time_step_floor"])
    return dt + jnp.log(-jnp.expm1(-dt))


def build_weights(cfg, seed):
    """Every parameter on the device: matrices and conv taps N(0, 1/fan-in)
    in `weights_dtype`, norms' scales 1, the mixer's A_log, dt_bias and D and
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
            value = _own(how, key, shape, cfg)
            scope.set_var(name, value.astype(dtype)
                          if name.endswith("_conv_bias") else value)
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
    """The routers' correction bias as load balancing leaves it: one pass of
    the reference's own layers over `BALANCE_ROWS` seeded tokens, and in each
    routed layer, before it is applied, b_e = mean(t) - t_e with t_e the
    (1 - k/E) quantile of expert e's score over the rows (every expert's key
    s_e + b_e then clears the common level in k/E of the rows), then
    `BALANCE_STEPS` steps of the update a training run makes (b_e down where
    the top-k gave expert e more rows than the mean, up where fewer, a step
    that shrinks), so the top-k takes each about equally often (a trained
    checkpoint's bias does this;
    with a random one the 22-of-512 selection of seeded weights sits on a
    few experts, the fullest 13-21 times the mean, and the bytes a tick
    streams are the seed's). Float32, default matmul precision: it is a
    parameter's value that is made here, not a comparison."""
    ref, f32 = nemotron_h_reference, jnp.float32
    c = dict(cfg, num_hidden_layers=cfg["num_layers"])
    k, eps = cfg["num_experts_per_tok"], cfg["layer_norm_epsilon"]
    tokens = jax.random.randint(
        jax.random.fold_in(jax.random.key(seed, impl="rbg"), 2 ** 20),
        (min(BALANCE_ROWS, cfg["max_len"]),), 0, cfg["vocab"])
    x = jnp.asarray(scope.get("tok_emb"))[tokens].astype(f32)

    def normed(x, i):
        return ref.rms(x, jnp.asarray(scope.get(f"l{i}_ln1.scale"), f32), eps)

    for i, kind in enumerate(layer_kinds(cfg)):
        name = f"l{i}_" + {"ssm": "ssm", "attention": "attn", "moe": "moe"}[
            kind]
        part = {n: scope.get(n) for n in param_names(cfg)
                if n.startswith(name)}
        if kind == "moe":
            @jax.jit
            def bias(x, w, i=i):
                s = jax.nn.sigmoid(normed(x, i) @ w.astype(f32))
                t = jnp.quantile(s, 1.0 - k / s.shape[1], axis=0)

                def step(j, b):
                    """The balancing update a training run makes: down where
                    an expert got more rows than the mean, up where fewer."""
                    _, idx = jax.lax.top_k(s + b, k)
                    load = jnp.zeros_like(b).at[idx.ravel()].add(1.0)
                    over = jnp.clip(load / jnp.mean(load) - 1.0, -1.0, 1.0)
                    return b - BALANCE_STEP * BALANCE_DECAY ** j * over
                b = jax.lax.fori_loop(0, BALANCE_STEPS, step, jnp.mean(t) - t)
                return b - jnp.mean(b)
            part[name + "_router_bias"] = bias(x, part[name + "_router.w_0"])
            scope.set_var(name + "_router_bias", part[name + "_router_bias"])
            layer = lambda h, p, name=name: ref.moe(h, p, name, c)  # noqa: E731
        elif kind == "ssm":
            layer = lambda h, p, name=name: ref.mixer(  # noqa: E731
                h, p, name, c, None)
        else:
            layer = lambda h, p, name=name: ref.attention(  # noqa: E731
                h, p, name, c, None)
        x = jax.jit(lambda x, p, i=i, layer=layer: x + layer(normed(x, i), p))(
            x, part)


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
    cfg = dict(cfg, num_hidden_layers=cfg["num_layers"])
    with jax.default_matmul_precision("highest"):
        return nemotron_h_reference.logits(
            params, padded, cfg,
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
    operator hands on, every K and V row and convolution state row through
    float8's 3 mantissa bits for bfloat16's 7, and the mixers' state h
    through bfloat16 for float32. The reading a cell's limit has to refuse
    (benchmark/control.py). (With the matrices and the cached rows alone
    through float8, as the two earlier adapters have it, this model's rows
    read as they do at the stated precision: 22 experts a row and the
    latent projections average the weights' rounding away, and what bfloat16
    costs here is its ACTIVATIONS' rounding, which flips a routed expert in a
    tenth of the rows; PERF.md section 6, PR 43.)"""
    below = {"bfloat16": "float8_e4m3fn", "float32": "bfloat16"}[
        cfg["weights_dtype"]]
    ref = nemotron_h_reference
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
    precision (nemotron_h_reference.ROUND_ACTIVATIONS_THROUGH): the witness,
    one plain forward. Its own largest logits, read against the float32 rows
    like a program's emitted tokens, say how far the stated precision alone
    puts a faultless computation (benchmark/witness.py)."""
    nemotron_h_reference.ROUND_ACTIVATIONS_THROUGH = cfg["weights_dtype"]
    try:
        yield dict(cfg, cache_round=cfg["cache_dtype"])
    finally:
        nemotron_h_reference.ROUND_ACTIVATIONS_THROUGH = None


FAULTS = ("dt_decay", "snapshot_stale", "snapshot_zero", "scaling_one")


@contextlib.contextmanager
def planted(fault, cfg, scope):
    """The configuration with one fault planted on the REFERENCE's side of
    the comparison, for as long as the block lasts (from the comparison's
    side the program is then the one that lacks what the reference has), one
    in each new mechanism. `dt_decay`: the state update decays by half its
    dt; `snapshot_stale`: from the end of the system prompt on
    (`system_prompt_tokens`) every mixer continues from the state one chunk
    earlier, which is what a restore from a stale snapshot computes;
    `snapshot_zero`: it continues from zeros there, a restore that brought
    nothing; `scaling_one`: the routed sum's `routed_scaling_factor` 1 for
    5."""
    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    at = int(cfg["system_prompt_tokens"])
    nemotron_h_reference.FAULT = {
        "snapshot_stale": ("stale", at, int(cfg["chunk_size"])),
        "snapshot_zero": ("stale", at, None)}.get(fault, fault)
    try:
        yield cfg
    finally:
        nemotron_h_reference.FAULT = None


# -- counts the per-layer readers divide by --------------------------------

def _item(cfg, key="weights_dtype"):
    return np.dtype(jnp.dtype(cfg[key])).itemsize


def n_layers(cfg, kind):
    return layer_kinds(cfg).count(kind)


def expert_bytes(cfg):
    """One routed expert's two matrices, as stored."""
    return 2 * cfg["moe_latent_size"] * cfg["moe_intermediate_size"] \
        * _item(cfg)


def h_bytes(cfg):
    """One state-space layer's h of ONE request, float32."""
    return (cfg["mamba_num_heads"] * cfg["mamba_head_dim"]
            * cfg["ssm_state_size"] * 4)


def kv_row_bytes(cfg):
    """K and V of ONE position in ONE attention layer, as stored."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * \
        _item(cfg, "cache_dtype")


def dense_tick_bytes(cfg):
    """Bytes a decode tick reads whatever the router and the load do: every
    parameter but the routed experts' and the embedding (a tick gathers a row
    a slot of it; the head is read whole)."""
    skipped = sum(int(np.prod(s)) for n, (s, _) in param_shapes(cfg).items()
                  if "_experts_" in n or n == "tok_emb")
    return _item(cfg) * (n_params(cfg) - skipped)


def moe_tick_bytes(cfg, n_rows, experts_touched, live_positions):
    """What one decode tick cannot avoid moving: the dense part, the touched
    experts (summed over the routed layers), every live position's K and V in
    every attention layer, and the live rows' state READ AND WRITTEN in every
    state-space layer. The reader hands over the engine's slots and the
    attended positions, not the live rows: they are the positions over
    `typical_context_tokens` (the mix's system prompt, median turn and half
    its median answer: the configuration says how it is reckoned), at most
    the slots."""
    live = min(n_rows, live_positions / cfg["typical_context_tokens"])
    return (dense_tick_bytes(cfg)
            + experts_touched * expert_bytes(cfg)
            + live_positions * n_layers(cfg, "attention") * kv_row_bytes(cfg)
            + 2 * live * n_layers(cfg, "ssm") * h_bytes(cfg))


def experts_call(cfg, n_rows, experts_touched, routed_rows):
    """(operations, bytes) of the grouped expert product over ALL routed
    layers of one tick: a routed (row, expert) pair costs the two matmuls; a
    touched expert's weights are read once, the latent rows in and out once
    a layer."""
    Z, F = cfg["moe_latent_size"], cfg["moe_intermediate_size"]
    flops = routed_rows * 2 * 2 * Z * F
    io = n_layers(cfg, "moe") * n_rows * Z * (_item(cfg) + 4)
    return flops, experts_touched * expert_bytes(cfg) + io


def ssm_decode_call(cfg, live_rows):
    """(operations, bytes) of the decode state update over ALL state-space
    layers of one tick with `live_rows` live decode rows: a live row's h read
    and written, its x, B, C, dt and decay in and y out; per state value a
    decay multiply, the outer product's multiply-add and the readout's."""
    H, P, G, N = (cfg["mamba_num_heads"], cfg["mamba_head_dim"],
                  cfg["n_groups"], cfg["ssm_state_size"])
    n = n_layers(cfg, "ssm")
    row_io = 4 * (2 * H * P + 2 * G * N + 2 * H)
    return (n * live_rows * 6 * H * P * N,
            n * live_rows * (2 * h_bytes(cfg) + row_io))
