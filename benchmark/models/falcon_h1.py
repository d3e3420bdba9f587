"""Adapter of the Falcon-H1 block (TWO mixers a layer: a Mamba-2 state-space
mixer with a float32 state a request and grouped-query rotary attention over
paged K/V, on one normed input and summed, under the family's scalar
multipliers; a gated SiLU pair; the whole vocabulary): served through
PagedKVEngine built from a model description
(`paddle_tpu.models.decoder_spec.DecoderSpec.parallel_ssm_gqa`), weights seeded
on the device in the configuration's dtype, each drawn so that its branch has
unit scale AFTER its multiplier, and the counts of bytes and operations the
per-layer readers divide by."""

from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp
import numpy as np

# at import (`Multipliers` for nothing else), so that a tree without the block
# (the parent of the PR that brought it) fails on the cell at once, before it
# touches the chip
from paddle_tpu.models.decoder_spec import (DecoderSpec,  # noqa: F401
                                            Multipliers, RopeSpec, SsmSpec)

from . import falcon_h1_reference
from .lfm2 import held_rows  # noqa: F401  (benchmark/witness.py reads gaps by it)

#: what the seeded attention is given so that it is seen (`assumed.init`): q
#: and k of this scale a value put the scores' deviation at its square, and
#: the context of a softmax that sharp has about this scale
QK_SCALE, CTX_SCALE = 1.6, 0.5
#: E[silu(g)^2] of a unit normal g: the gated pair's second moment
SILU_SECOND_MOMENT = 0.355


def multipliers_of(cfg):
    return Multipliers(
        embedding=cfg["embedding_multiplier"],
        attention_in=cfg["attention_in_multiplier"],
        attention_out=cfg["attention_out_multiplier"],
        key=cfg["key_multiplier"], ssm_in=cfg["ssm_in_multiplier"],
        ssm_out=cfg["ssm_out_multiplier"],
        ssm=tuple(cfg["ssm_multipliers"]), mlp=tuple(cfg["mlp_multipliers"]),
        lm_head=cfg["lm_head_multiplier"])


def spec_of(cfg):
    if cfg["mamba_norm_before_gate"] or not cfg["mamba_rms_norm"] \
            or not cfg["mamba_conv_bias"] or cfg["rope_scaling"] is not None \
            or cfg["hidden_act"] != "silu" or cfg["tie_word_embeddings"] \
            or cfg["attention_bias"] or cfg["mamba_proj_bias"] \
            or cfg["mlp_bias"] or cfg["projectors_bias"]:
        raise NotImplementedError(
            "mamba_norm_before_gate / mamba_rms_norm / mamba_conv_bias / "
            "rope_scaling / hidden_act / tie_word_embeddings / a bias")
    ssm = SsmSpec(heads=cfg["mamba_n_heads"], head_dim=cfg["mamba_d_head"],
                  groups=cfg["mamba_n_groups"], state=cfg["mamba_d_state"],
                  taps=cfg["mamba_d_conv"])
    if ssm.d_inner != cfg["mamba_d_ssm"]:
        raise ValueError("mamba_d_ssm is mamba_n_heads x mamba_d_head")
    return DecoderSpec.parallel_ssm_gqa(
        vocab=cfg["vocab"], d_model=cfg["hidden_size"],
        d_inner=cfg["intermediate_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], d_head=cfg["head_dim"],
        num_layers=cfg["num_layers"], ssm=ssm,
        rope=RopeSpec(dim=cfg["head_dim"], theta=float(cfg["rope_theta"])),
        multipliers=multipliers_of(cfg), norm_eps=cfg["rms_norm_eps"],
        dtype=cfg["weights_dtype"])


def param_shapes(cfg):
    """name -> (shape, how it is seeded), in the order the seeds are dealt.
    `how`: None (a norm's scale: 1); a tuple naming a distribution of its own
    (`_own`); or ("normal", variance a column range [(columns, variance),
    ..], the axis to centre over or None): N(0, variance), each matrix's
    variance 1 / (fan-in x multiplier^2) so that its branch has unit scale
    AFTER the multiplier the program applies (a trained muP model's weights
    are larger by the inverse of the multiplier: with N(0, 1/fan-in) both
    mixers and the feed-forward would vanish beside the residual and a dropped
    mixer would read clean). The state-space output projection follows the
    gated norm, whose mean is not zero: it is CENTRED over its fan-in (PR 43's
    lesson); the gated pair's product and the softmax's context have zero
    mean and their projections are not."""
    D, F, V = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab"]
    nh, nkv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    H, P, G, N, K = (cfg["mamba_n_heads"], cfg["mamba_d_head"],
                     cfg["mamba_n_groups"], cfg["mamba_d_state"],
                     cfg["mamba_d_conv"])
    d_in, cd = H * P, H * P + 2 * G * N
    m = multipliers_of(cfg)

    def normal(shape, variance, centre=None):
        ranges = variance if isinstance(variance, tuple) \
            else ((shape[-1], variance),)
        return (shape, ("normal", ranges, centre))

    in_var = tuple((cols, 1.0 / (D * (m.ssm_in * by) ** 2)) for cols, by in
                   zip((d_in, d_in, G * N, G * N, H), m.ssm))
    out = {"tok_emb": normal((V, D), m.embedding ** -2)}
    for i in range(cfg["num_layers"]):
        s, a, f = f"l{i}_ssm", f"l{i}_attn", f"l{i}_ffn"
        out[f"l{i}_ln1.scale"] = ((D,), None)
        out.update({
            f"{s}_in.w_0": normal((D, d_in + cd + H), in_var),
            f"{s}_taps": normal((cd, K), 1.0 / K),
            f"{s}_conv_bias": ((cd,), ("gauss", 0.1)),
            f"{s}_a_log": ((H,), ("a_log",)),
            f"{s}_dt_bias": ((H,), ("dt_bias",)),
            f"{s}_d": ((H,), ("ones",)),
            f"{s}_norm.scale": ((d_in,), None),
            f"{s}_out.w_0": normal((d_in, D),
                                   1.0 / (d_in * m.ssm_out ** 2), 0)})
        q_var = QK_SCALE ** 2 / (D * m.attention_in ** 2)
        out.update({
            f"{a}_q.w_0": normal((D, nh * dh), q_var),
            f"{a}_k.w_0": normal((D, nkv * dh), q_var / m.key ** 2),
            f"{a}_v.w_0": normal((D, nkv * dh),
                                 1.0 / (D * m.attention_in ** 2)),
            f"{a}_o.w_0": normal(
                (nh * dh, D),
                1.0 / (nh * dh * (CTX_SCALE * m.attention_out) ** 2))})
        out[f"l{i}_ln2.scale"] = ((D,), None)
        out.update({
            f"{f}_gate.w_0": normal((D, F), 1.0 / (D * m.mlp[0] ** 2)),
            f"{f}_up.w_0": normal((D, F), 1.0 / D),
            f"{f}_down.w_0": normal(
                (F, D), 1.0 / (SILU_SECOND_MOMENT * F * m.mlp[1] ** 2))})
    out["final_norm.scale"] = ((D,), None)
    out["lm_head.w_0"] = normal((D, V), 1.0 / (D * m.lm_head ** 2))
    return out


def param_names(cfg):
    return list(param_shapes(cfg))


def n_params(cfg):
    return sum(int(np.prod(s)) for s, _ in param_shapes(cfg).values())


GEN_CHUNK = 1 << 25         # values one call of the generator makes


def _own(kind, key, shape, cfg):
    """The parameters with a distribution of their own, float32. `dt_bias`:
    softplus^-1 of a dt log-uniform in [time_step_min, time_step_max] (the
    Mamba-2 initialiser's range, `assumed.dt`); `a_log`: log of A uniform in
    [1, 16]. With the row's own unit normal added before the softplus
    exp(dt A) spans ~0.2-0.999 a step: a state that remembers across a
    128-token chunk, so that a wrong carry does not read clean (PR 43)."""
    if kind[0] == "ones":
        return jnp.ones(shape, jnp.float32)
    if kind[0] == "gauss":
        return kind[1] * jax.random.normal(key, shape, jnp.float32)
    u = jax.random.uniform(key, shape, jnp.float32)
    if kind[0] == "a_log":
        return jnp.log(1.0 + 15.0 * u)
    lo, hi = cfg["time_step_min"], cfg["time_step_max"]
    dt = jnp.maximum(jnp.exp(u * (np.log(hi) - np.log(lo)) + np.log(lo)),
                     cfg["time_step_floor"])
    return dt + jnp.log(-jnp.expm1(-dt))


def build_weights(cfg, seed):
    """Every parameter on the device as `param_shapes` says, matrices and conv
    taps in `weights_dtype`, the mixer's A_log, dt_bias and D in float32, one
    key a parameter from `seed`. The generator is the device's own (`rbg`); a
    matrix is made a block of whole rows at a time (at most `GEN_CHUNK`
    values) and written into its array in place, so that the head's 1.34 B
    values never exist twice beside ten gigabytes of layers. Nothing of the
    model is built."""
    import paddle_tpu as pt
    dtype = jnp.dtype(cfg["weights_dtype"])
    scope = pt.Scope()
    root = jax.random.key(seed, impl="rbg")

    @functools.lru_cache(maxsize=None)
    def block_writer(shape, rows, ranges, centre):
        std = np.repeat(np.sqrt([v for _, v in ranges]),
                        [c for c, _ in ranges]).astype(np.float32)

        def put(buf, key, i):
            w = jax.random.normal(key, (rows, shape[1]), dtype)
            if centre is None:
                w = w * jnp.asarray(std, dtype)
            else:                       # one block, by construction
                w = w.astype(jnp.float32) * std
                w = (w - jnp.mean(w, axis=centre, keepdims=True)).astype(dtype)
            return jax.lax.dynamic_update_slice_in_dim(buf, w, i * rows, 0)
        return jax.jit(put, donate_argnums=0)

    for k, (name, (shape, how)) in enumerate(param_shapes(cfg).items()):
        key = jax.random.fold_in(root, k)
        if how is None:
            scope.set_var(name, jnp.ones(shape, dtype))
        elif how[0] != "normal":
            value = _own(how, key, shape, cfg)
            scope.set_var(name, value.astype(dtype)
                          if name.endswith("_conv_bias") else value)
        else:
            most = max(1, GEN_CHUNK // shape[1])
            rows = shape[0] if how[2] is not None else max(
                r for r in range(1, min(most, shape[0]) + 1)
                if shape[0] % r == 0)
            put = block_writer(tuple(shape), rows, how[1], how[2])
            buf = jnp.zeros(shape, dtype)
            for i in range(shape[0] // rows):
                buf = put(buf, jax.random.fold_in(key, i), i)
            scope.set_var(name, buf)
    return scope


#: the requests the engine built here was handed, so that the reference knows
#: which rows of a checked sequence are read
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
#: a list here takes (tokens, the rows the program emitted from) of every
#: call that found its request: benchmark/witness.py
rows_kept = None


class RowsFrom:
    """Logits [T, vocab] of which only the rows from `first` on were computed
    (a row is 1.04 MB here: 12,800 of them would be 13.4 GB): `self[first:]`,
    or any slice that starts at or behind `first`, gives them as an array;
    anything else raises."""

    def __init__(self, first, rows):
        self.first, self.rows = first, rows

    def __getitem__(self, key):
        if not isinstance(key, slice) or key.step is not None \
                or key.start is None or key.start < self.first:
            raise IndexError(
                f"only the rows from {self.first} on were computed (the head "
                "runs on the rows a request emitted from): slice from there")
        stop = None if key.stop is None else key.stop - self.first
        return self.rows[key.start - self.first:stop]


def _request_of(seq):
    """The finished request of this engine whose prompt and emitted tokens
    `seq` is (all but its last token), or None."""
    return next((r for r in reversed(_taken)
                 if r.done and len(r.prompt) + len(r.tokens) - 1 == len(seq)
                 and list(r.prompt) + list(r.tokens[:-1]) == seq), None)


def envelope_logits(cfg, params, tokens, pad_to):
    """Full forward over `tokens`, padded on the right to a fixed length (the
    model is causal, so the padding changes no earlier position): the hidden
    states of EVERY position through every layer in float32. The head, a
    matrix of 5,120 x 261,120, runs on the rows that are read and no others:
    where `tokens` is a finished request of this engine (its prompt and what
    it emitted: `build_engine` notes every request it is handed), the rows
    from the prompt's last position on, returned as a `RowsFrom` that the
    loop's `ref[len(prompt) - 1:]` reads as it reads an array; any other
    sequence gets every row, as an array. One path (the name is the one
    benchmark/witness.py calls)."""
    global peak_before_reference
    if peak_before_reference is None:
        from .. import harness
        peak_before_reference = harness.memory_peak_bytes(jax.devices()[:1])
    seq = [int(t) for t in tokens]
    padded = np.zeros((pad_to,), np.int32)
    padded[:len(seq)] = seq
    req = _request_of(seq)
    first = 0 if req is None else len(req.prompt) - 1
    ref, fault = falcon_h1_reference, falcon_h1_reference.FAULT
    if isinstance(fault, tuple) and fault[1] is None:
        # the planted stale restore, where a restore of THIS sequence would
        # lie: the end of its prompt's last whole block (a sequence no
        # request emitted: the configuration's `check_stale_at`)
        block = int(cfg["check_stale_block"])
        ref.FAULT = ("stale", int(cfg["check_stale_at"]) if req is None
                     else len(req.prompt) // block * block, fault[2])
    try:
        with jax.default_matmul_precision("highest"):
            rows = ref.logits(
                params, padded, cfg, cache_round=cfg.get("cache_round"),
                rows_from=first, rows_to=len(seq))
    finally:
        ref.FAULT = fault
    return rows if req is None else RowsFrom(first, rows)


def reference_logits(cfg, params, tokens, pad_to):
    """`envelope_logits`, the rows as the reference gives them (and, for
    benchmark/witness.py, kept in `rows_kept`)."""
    out = envelope_logits(cfg, params, tokens, pad_to)
    if rows_kept is not None and isinstance(out, RowsFrom):
        rows_kept.append(([int(t) for t in tokens], out.rows.copy()))
    return out


@contextlib.contextmanager
def one_precision_below(cfg):
    """The configuration as `reference_logits` computes it in the nearest
    precision below the stated one, ALL of it: every matrix, every value an
    operator hands on, every K and V row and convolution state row through
    float8's 2 mantissa bits for bfloat16's 7, and the mixers' state h
    through bfloat16 for float32. The reading a cell's limit has to refuse
    (benchmark/control.py). The float8 is e5m2, the one whose RANGE holds a
    muP model's values: before their multipliers (u Wk) has a scale of 145
    and the feed-forward's output of 90, and e4m3fn's largest value is 448,
    beyond which it has only NaN (a control that read NaN would PASS the
    loop's `_check`, whose max() drops it)."""
    below = {"bfloat16": "float8_e5m2", "float32": "bfloat16"}[
        cfg["weights_dtype"]]
    ref = falcon_h1_reference
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
    precision (falcon_h1_reference.ROUND_ACTIVATIONS_THROUGH): the witness,
    one plain forward. Its own largest logits, read against the float32 rows
    like a program's emitted tokens, say how far the stated precision alone
    puts a faultless computation (benchmark/witness.py)."""
    falcon_h1_reference.ROUND_ACTIVATIONS_THROUGH = cfg["weights_dtype"]
    try:
        yield dict(cfg, cache_round=cfg["cache_dtype"])
    finally:
        falcon_h1_reference.ROUND_ACTIVATIONS_THROUGH = None


FAULTS = falcon_h1_reference.FAULTS + ("snapshot_stale",)


@contextlib.contextmanager
def planted(fault, cfg, scope):
    """The configuration with one fault planted on the REFERENCE's side of
    the comparison, for as long as the block lasts (from the comparison's
    side the program is then the one that lacks what the reference has), one
    in each mechanism the block adds. `ssm_out_dropped` /
    `attention_out_dropped`: that mixer's output left out of every layer's
    sum; `ssm_ranges_swapped`: `ssm_multipliers` on the wrong column ranges
    (B's and C's swapped); `key_multiplier_one`: `key_multiplier` left out;
    `no_rotation`: q and k not rotated; `snapshot_stale`: from the end of
    the prompt's last whole block on (`check_stale_block` positions a block:
    where a prefix hit of the request would restore; `envelope_logits` finds
    the request) every mixer continues from the state one chunk earlier,
    which is what a restore from a stale snapshot computes."""
    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    falcon_h1_reference.FAULT = (
        ("stale", None, int(cfg["mamba_chunk_size"]))
        if fault == "snapshot_stale" else fault)
    try:
        yield cfg
    finally:
        falcon_h1_reference.FAULT = None


# -- counts the per-layer readers divide by --------------------------------

def _item(cfg, key="weights_dtype"):
    return np.dtype(jnp.dtype(cfg[key])).itemsize


def h_bytes(cfg):
    """One layer's h of ONE request, float32."""
    return (cfg["mamba_n_heads"] * cfg["mamba_d_head"] * cfg["mamba_d_state"]
            * 4)


def kv_row_bytes(cfg):
    """K and V of ONE position in ONE layer, as stored."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * \
        _item(cfg, "cache_dtype")


def ssm_decode_call(cfg, live_rows):
    """(operations, bytes) of the decode state update over ALL layers of one
    tick with `live_rows` live decode rows: a live row's h read and written,
    its x, B, C, dt and decay in and y out; per state value a decay multiply,
    the outer product's multiply-add and the readout's."""
    H, P, G, N = (cfg["mamba_n_heads"], cfg["mamba_d_head"],
                  cfg["mamba_n_groups"], cfg["mamba_d_state"])
    n = cfg["num_layers"]
    row_io = 4 * (2 * H * P + 2 * G * N + 2 * H)
    return (n * live_rows * 6 * H * P * N,
            n * live_rows * (2 * h_bytes(cfg) + row_io))


def gqa_decode_call(cfg, live_blocks, block_size):
    """(operations, bytes) of ONE layer's paged decode read over
    `live_blocks` pool blocks in all (summed over the slots; the read takes
    whole blocks): a (query head, position) pair costs 2 * 2 * head_dim
    operations (the score and the weighted value), and a block's K and V are
    read once for the whole group of query heads."""
    positions = live_blocks * block_size
    flops = 4 * cfg["num_attention_heads"] * cfg["head_dim"] * positions
    return flops, positions * kv_row_bytes(cfg)


def hybrid_tick_counts(cfg, state_rows, prefill_tokens, kv_blocks,
                       lane_kv_blocks, block_size=64):
    """(operations, bytes) ONE tick cannot avoid, whatever implements it, from
    what its `engine/tick` span counted: `state_rows` live decode rows,
    `prefill_tokens` prompt tokens in the lanes, `kv_blocks` pool blocks its
    reads span in all, `lane_kv_blocks` of them the lanes'.

    Bytes: every parameter of the layers, the final norm and the head ONCE
    (the embedding is gathered: a row a token); the decode rows' h read and
    written in every layer and a lane's once each way (at most a lane a
    128-token chunk); every spanned block's K and V read once a layer, and
    the new rows' written. Operations: 2 x the matmul parameters a row (the
    head on the rows that are sampled from: the decode rows and a row a lane);
    the attention products, 4 x query heads x head_dim a (row, attended
    position) pair, a lane's chunk causal over its own span; the state
    update, 6 a state value a decode row, and the chunked form's products a
    prefill token (the scores against the chunk's C and B, the weighted sum,
    the carried state's readout and its update)."""
    D, F, V, n = (cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab"],
                  cfg["num_layers"])
    nh, dh = cfg["num_attention_heads"], cfg["head_dim"]
    H, P, G, N, Q = (cfg["mamba_n_heads"], cfg["mamba_d_head"],
                     cfg["mamba_n_groups"], cfg["mamba_d_state"],
                     cfg["mamba_chunk_size"])
    layer = (n_params(cfg) - 2 * V * D - D) // n     # a layer's parameters
    rows = state_rows + prefill_tokens
    lanes = -(-prefill_tokens // Q) if prefill_tokens else 0
    sampled = state_rows + lanes
    nbytes = (_item(cfg) * (n * layer + D + D * V + rows * D)
              + n * 2 * (state_rows + lanes) * h_bytes(cfg)
              + n * (kv_blocks * block_size + rows) * kv_row_bytes(cfg))
    decode_blocks = kv_blocks - lane_kv_blocks
    # a lane's rows attend its request's span up to their own position: the
    # blocks the lane reads, less half of the chunk's own square
    attended = (decode_blocks * block_size
                + prefill_tokens * lane_kv_blocks * block_size / max(lanes, 1)
                - prefill_tokens * Q / 2)
    flops = (2.0 * rows * n * layer + 2.0 * sampled * D * V
             + n * 4.0 * nh * dh * max(attended, 0.0)
             + n * state_rows * 6.0 * H * P * N
             + n * prefill_tokens * (2.0 * 2 * H * Q * N / 2   # C B^T, halved
                                     + 2.0 * H * Q * P / 2     # (CB o L) X
                                     + 2.0 * 2 * H * P * N))   # C h_in, state
    return flops, nbytes
