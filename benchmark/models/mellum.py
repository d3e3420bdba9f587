"""Adapter of the Mellum 2 configuration (configs/mellum2-ep4.json): TRAINED
through `models.transformer.transformer_lm(model=spec)` -> Executor ->
vjp_region -> Adam, as one expert rank of four. The block is
`DecoderSpec.window_gqa_moe` with a rotation a kind of layer, no QK-norm,
softmax-routed experts under a balance term; the plain reference it is held
to is `mellum_reference.py`.

The count functions the per-layer metrics read live here (benchmark/counts.py
has the conventions: a multiply-add is 2 operations, only what the
mathematics needs is counted, once).
"""

from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp
import numpy as np

from .. import counts
from . import mellum_reference as ref

KINDS = {"sliding_attention": "window", "full_attention": "full"}


def held_experts(cfg):
    return ref.held_experts(cfg)


def _rope_spec(cfg, params):
    from paddle_tpu.models.decoder_spec import RopeSpec
    kind = params.get("rope_type", "default")
    if kind == "default":
        return RopeSpec(dim=cfg["head_dim"], theta=float(params["rope_theta"]))
    if kind != "yarn":
        raise ValueError(f"rope_type {kind!r}: the adapter builds 'default' "
                         "and 'yarn'")
    rope = RopeSpec(dim=cfg["head_dim"], theta=float(params["rope_theta"]),
                    factor=float(params["factor"]),
                    beta_fast=float(params["beta_fast"]),
                    beta_slow=float(params["beta_slow"]),
                    original_max=int(
                        params["original_max_position_embeddings"]))
    stated = params.get("attention_factor")
    if stated is not None and abs(rope.table_scale - stated) > 1e-9:
        raise ValueError(f"attention_factor {stated} is not 0.1 ln(factor) + "
                         f"1 = {rope.table_scale}: RopeSpec has no field "
                         "for another")
    return rope


def spec_of(cfg):
    from paddle_tpu.models.decoder_spec import DecoderSpec, MoESpec
    kinds = cfg["layer_types"][:cfg["num_hidden_layers"]]
    if set(cfg["mlp_layer_types"][:len(kinds)]) != {"sparse"}:
        raise ValueError("every layer of the configuration is sparse")
    ropes = cfg["rope_parameters"]
    moe = MoESpec(n_routed=cfg["router_width"],
                  top_k=cfg["num_experts_per_tok"],
                  d_expert=cfg["moe_intermediate_size"],
                  held=tuple(held_experts(cfg)), n_shared=0, first_dense=0,
                  norm_topk_prob=bool(cfg["norm_topk_prob"]),
                  scoring="softmax", aux_coef=float(cfg["aux_coef"]))
    return DecoderSpec.window_gqa_moe(
        cfg["vocab"], cfg["hidden_size"], cfg["intermediate_size"],
        cfg["num_attention_heads"], cfg["num_key_value_heads"],
        cfg["head_dim"], [KINDS[k] for k in kinds], cfg["sliding_window"],
        rope=_rope_spec(cfg, ropes["sliding_attention"]),
        rope_full=_rope_spec(cfg, ropes["full_attention"]), moe=moe,
        norm_eps=cfg["rms_norm_eps"], dtype="float32", qk_norm=False)


def vocabs(cfg):
    return {"vocab": cfg["vocab"]}


def _scale_init(program, name, gain):
    """Multiply what the startup program draws `name` from by `gain`."""
    for op in program.global_block().ops:
        if name in op.output_names():
            for key in ("min", "max", "std"):
                if key in op.attrs:
                    op.attrs[key] = float(op.attrs[key]) * gain
            return
    raise KeyError(f"no startup op initialises {name}")


def build_train(cfg, mix):
    """The training graph in the default programs; returns the loss (mean
    cross-entropy + `aux_coef` x the layers' balance terms). W_q and W_k
    start `qk_init_gain` times the library's default (the configuration's
    `assumed` says why)."""
    import paddle_tpu as pt
    from paddle_tpu.models import transformer
    loss, _ = transformer.transformer_lm(max_len=mix["seq_len"],
                                         model=spec_of(cfg))
    gain = float(cfg.get("qk_init_gain", 1.0))
    if gain != 1.0:
        for i in range(cfg["num_layers"]):
            for which in "qk":
                _scale_init(pt.default_startup_program(),
                            f"l{i}_attn_{which}.w_0", gain)
    return loss


def param_names(cfg):
    names = ["tok_emb", "lm_head.w_0", "final_norm.scale"]
    for i in range(cfg["num_layers"]):
        names += [f"l{i}_attn_{x}.w_0" for x in "qkvo"]
        names += [f"l{i}_ln1.scale", f"l{i}_ln2.scale",
                  f"l{i}_moe_router.w_0"]
        names += [f"l{i}_moe_experts_{x}" for x in ("gate", "up", "down")]
    return names


def param_group(name):
    """The group a parameter's gradient norm is reported under."""
    for key, group in (("_attn_q", "q"), ("_attn_k", "k"), ("_attn_v", "v"),
                       ("_attn_o", "o"), ("_router", "router"),
                       ("_experts_gate", "gate"), ("_experts_up", "up"),
                       ("_experts_down", "down"), ("tok_emb", "embedding"),
                       ("lm_head", "head")):
        if key in name:
            return group
    return "norms"


def batch_loss(params, tokens, targets, cfg):
    """The reference's loss of one batch [B, T]: the rows' mean
    cross-entropy, and the balance term over ALL the batch's rows as the
    program's op computes it (f and P are means over the B x T rows)."""
    coef = 0.0 if "no_balance_term" in tuple(cfg.get("fault", ())) \
        else float(cfg["aux_coef"])
    ce, chosen, scores = [], [], []
    for t, y in zip(tokens, targets):
        logits, c, p = ref.forward(params, t, cfg)
        logp = jax.nn.log_softmax(logits, axis=-1)
        ce.append(-jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=-1)))
        chosen.append(c)
        scores.append(p)
    aux = ref.balance_terms(sum(chosen), sum(scores) / len(scores))
    return sum(ce) / len(ce) + coef * aux


def _feed_arrays(batch):
    feed = batch["feed"]
    return (jnp.asarray(feed["tokens"], jnp.int32),
            jnp.asarray(feed["targets"], jnp.int32))


def _f32(params):
    return {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}


def reference_loss(cfg, params, batch):
    """The reference's loss of one batch on `params`, float32 at "highest"
    precision."""
    f = jax.jit(lambda p, t, y: batch_loss(p, t, y, cfg))
    with jax.default_matmul_precision("highest"):
        return float(f(_f32(params), *_feed_arrays(batch)))


def reference_grads(cfg, params, batch):
    """(loss, {name: gradient}) of the reference on one batch."""
    f = jax.jit(jax.value_and_grad(lambda p, t, y: batch_loss(p, t, y, cfg)))
    with jax.default_matmul_precision("highest"):
        loss, grads = f(_f32(params), *_feed_arrays(batch))
    return float(loss), grads


def group_norms(by_name, cfg):
    """{group: [norm a layer]} ("embedding", "head", "norms": one entry) of
    arrays by parameter name."""
    out = {}
    for name in param_names(cfg):
        out.setdefault(param_group(name), []).append(
            float(jnp.linalg.norm(jnp.asarray(by_name[name], jnp.float32))))
    out["norms"] = [float(np.sqrt(np.sum(np.square(out["norms"]))))]
    return out


def reference_grad_norms(cfg, params, batch):
    """(loss, {group: [gradient norm a layer]}) of the reference."""
    loss, grads = reference_grads(cfg, params, batch)
    return loss, group_norms(grads, cfg)


@contextlib.contextmanager
def one_precision_below(cfg):
    """The reference with every matmul's operands one precision below the
    stated one (`matmul_dtype` bfloat16's 7 mantissa bits -> float8's 3;
    float32 -> bfloat16), the sums float32: the control a limit has to
    refuse."""
    below = {"bfloat16": "float8_e4m3fn", "float32": "bfloat16"}[
        cfg["matmul_dtype"]]
    yield dict(cfg, fault=("matmuls:" + below,))


FAULTS = ("window_as_full", "full_as_window", "plain_rope_on_full",
          "no_balance_term", "norm_over_held", "router_bf16")


@contextlib.contextmanager
def planted(fault, cfg):
    """The configuration with one fault planted on the reference's side:
    from the comparison's side, a program that lacks the mechanism."""
    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}: one of {FAULTS}")
    yield dict(cfg, fault=(fault,))


# -- counts -------------------------------------------------------------------

def live_pairs(T, window):
    """(query, key) pairs a causal layer scores: the triangle, or under a
    window each query's last `window` keys."""
    if not window or window >= T:
        return T * (T + 1) / 2.0
    return window * (window + 1) / 2.0 + (T - window) * float(window)


def _layer_windows(cfg):
    return [cfg["sliding_window"] if k == "sliding_attention" else 0
            for k in cfg["layer_types"][:cfg["num_layers"]]]


def held_pairs_expected(cfg, tokens):
    """(row, expert) pairs that land on held experts under an even routing."""
    return (tokens * cfg["num_experts_per_tok"] * cfg["num_experts"]
            / cfg["router_width"])


def _attention_flops(cfg, rows, T, window, backward):
    """Two matmuls of 2 * pairs * head_dim forward a head, four backward."""
    one = 2.0 * rows * cfg["num_attention_heads"] * live_pairs(T, window) \
        * cfg["head_dim"]
    return one * (2 + (4 if backward else 0))


def train_flops(cfg, mix, batch):
    """Operations forward and backward need for one batch: the dense
    projections, the router and the head a token; the three expert matmuls a
    HELD (row, expert) pair, at the share an even routing holds (a quarter
    of the pairs; `routed_pairs_held_share` says what the run held);
    attention over the pairs a layer's mask leaves live, not the square and
    not the triangle under a window."""
    rows, T = len(batch["feed"]["tokens"]), mix["seq_len"]
    H, dh = cfg["hidden_size"], cfg["head_dim"]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dense = 2 * H * nh * dh + 2 * H * nkv * dh + H * cfg["router_width"]
    tokens = rows * T
    flops = counts.matmul_flops(
        tokens, cfg["num_layers"] * dense + H * cfg["vocab"], True)
    flops += cfg["num_layers"] * counts.matmul_flops(
        held_pairs_expected(cfg, tokens),
        3 * H * cfg["moe_intermediate_size"], True)
    return flops + sum(_attention_flops(cfg, rows, T, w, True)
                       for w in _layer_windows(cfg))


def _flash_bytes(cfg, rows, T, backward, itemsize=2):
    """HBM traffic a fused attention call cannot avoid, K and V counted once
    a GROUP of query heads: forward reads q, k, v and writes o; backward
    reads q, k, v, o, do and writes dq, dk, dv; the log-sum-exp rows ride
    along."""
    q = rows * cfg["num_attention_heads"] * T * cfg["head_dim"] * itemsize
    kv = rows * cfg["num_key_value_heads"] * T * cfg["head_dim"] * itemsize
    lse = rows * cfg["num_attention_heads"] * T * 4
    return (4 * q + 4 * kv + lse) if backward else (2 * q + 2 * kv + lse)


def flash_calls(cfg, mix, rows, kind):
    """[(flops, bytes)] of the fused attention calls of the layers of `kind`
    ("window" | "full") in one training step: a forward and a backward a
    layer, from the LIVE pairs."""
    T, out = mix["seq_len"], []
    for w in _layer_windows(cfg):
        if (w > 0) != (kind == "window"):
            continue
        fwd = _attention_flops(cfg, rows, T, w, False)
        out += [(fwd, _flash_bytes(cfg, rows, T, False)),
                (_attention_flops(cfg, rows, T, w, True) - fwd,
                 _flash_bytes(cfg, rows, T, True))]
    return out


def experts_train_call(cfg, held_pairs, itemsize=2):
    """(flops, bytes) of ONE routed layer's grouped products, forward and
    backward: 3 matmuls x 6 operations a parameter a held pair; the held
    experts' weights read once forward and twice backward, the pairs' rows
    read and written once in each of the three passes."""
    H, F = cfg["hidden_size"], cfg["moe_intermediate_size"]
    flops = counts.matmul_flops(held_pairs, 3 * H * F, True)
    weights = cfg["num_experts"] * 3 * H * F * itemsize
    return flops, 3 * weights + 3 * 2 * held_pairs * H * itemsize


# -- what the readers share -----------------------------------------------------

def is_flash(key, cfg, kind):
    """Is the trace key that of a flash kernel of the layers of `kind`? The
    kernels' names spell the plan (ops/pallas_kernels.py FlashPlan.scope):
    `_w<window>` last where the keys are windowed."""
    if not key.startswith("flash_") or "_custom-call" not in key:
        return False
    windowed = f"_w{cfg['sliding_window']}_custom-call" in key
    return windowed == (kind == "window")


def is_grouped_product(key):
    """megablox's kernels keep their own names in a trace (`gmm`, `tgmm`:
    the jitted wrappers' names win over fusion/moe.py's scopes); a training
    step has no other call of either."""
    return key.startswith(("gmm_custom-call", "tgmm_custom-call"))


def kernel_seconds(trace, match):
    """For each execution of the main program on chip 0 in which a Mosaic
    call whose key `match`es ran: the seconds those calls took together."""
    import bisect
    if trace is None or not trace.devices:
        return []
    dev = trace.devices[0]
    calls = [(s, e) for s, e, k, _, mosaic in dev.ops if mosaic and match(k)]
    starts = [s for s, _ in calls]
    out = []
    for s, e, name, _ in dev.modules:
        if name != trace.main_module():
            continue
        i, j = bisect.bisect_left(starts, s), bisect.bisect_left(starts, e)
        if j > i:
            out.append(sum(b - a for a, b in calls[i:j]))
    return out


def counters(cfg, scope=None):
    """The step's device counters read from the scope: {"rows": [L, held]
    pairs a (layer, held expert) got since the startup program ran, "pairs":
    [L, 3] (routed, held, dropped), "aux": [L] each layer's last balance
    term}; None where the program keeps none (the parent of the PR that
    added them)."""
    import paddle_tpu as pt
    scope = scope or pt.global_scope()
    try:
        got = {k: np.stack([np.asarray(scope.get(f"l{i}_moe.{k}"))
                            for i in range(cfg["num_layers"])])
               for k in ("rows", "pairs", "aux")}
    except Exception:
        return None
    return got
