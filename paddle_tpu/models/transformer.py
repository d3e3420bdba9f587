"""Transformer NMT (≙ reference benchmark/fluid/models/machine_translation.py
capability slot + nets.py:332 scaled_dot_product_attention — driver config #4).

The reference era predates a full in-repo Transformer; its attention exists
only as the composite in nets.py. Here the full encoder-decoder is first-class
because it is the TPU flagship: bf16 matmuls on the MXU, static shapes, and
parallelism-friendly structure (qkv/ffn weights laid out for tp sharding, the
sequence dim for sp/ring attention, batch for dp — see
paddle_tpu/parallel/tensor_parallel.py and __graft_entry__.py).

How a decoder graph is put together. Every graph that runs the LM's layers
— `transformer_lm` (training), `transformer_lm_generate` (a scan), the five
serving ticks — and `transformer_generate`'s decoder builds a layer through
ONE function, `_decoder_block`: q/k/v projections, `attend(layer, q, k, v)`,
output projection, add+norm, [cross-attention, add+norm,] FFN, add+norm,
parameter names derived from one prefix (`l{i}_attn_q`, `l{i}_ln1`, ...:
the graphs share trained weights BY NAME). `_lm_head` builds the vocabulary
head. What differs between the graphs is `attend` alone — where the new K/V
rows go and how the cache is read back:

- training: no cache; `_flash_attend` (`fused_attention` on q, k, v as the
  projections leave them, [B, T, H*D]);
- `_ScanCache`: the generate graphs' scan carry, every sequence at one
  position;
- `_SlotCache`: one persistable row of `max_len` positions per serving slot,
  each slot at its own position, a window of G rows a forward;
- `_PagedCache`: persistable block pools (float32, or int8 + scales) read
  through a block table, G rows a slot;
- `_PagedLaneCache`: the same pools under decode rows plus prefill lanes;
- `_LatentPagedCache`: ONE pool a layer of latent rows (latent attention:
  what is cached is the row every head shares), under decode rows and,
  when the tick has them, prefill lanes.

A layer whose operator is a gated short convolution (`_short_conv`) keeps no
row a position but a STATE a request (`_ConvState`: a slot's last rows, and a
snapshot beside every pool block for the prefix cache to hand over with it).

What a block is made of is a `DecoderSpec` (models/decoder_spec.py): norm
kind, residual order, position scheme, attention kind, feed-forward kind, and
where layers differ a kind a layer.
The classic spec (post-LayerNorm, sinusoid, full heads, ReLU pair) is what
every graph above builds, op for op as before a block had kinds; the paged
ticks take any spec (`model=`).

A tick builder declares its feeds (`layers.data`; the serving engines make
their feed arrays from these declarations, `serving.engine._feed_arrays`),
makes its cache, embeds, loops the block, and adds the head.
"""

from __future__ import annotations

import functools

import numpy as np

from .. import layers
from ..initializer import NormalInitializer
from ..param_attr import ParamAttr
from .decoder_spec import DecoderSpec


def positional_encoding_table(max_len, d_model):
    pos = np.arange(max_len)[:, None].astype("float32")
    i = np.arange(d_model)[None, :].astype("float32")
    angle = pos / np.power(10000.0, 2 * (i // 2) / d_model)
    table = np.zeros((max_len, d_model), dtype="float32")
    table[:, 0::2] = np.sin(angle[:, 0::2])
    table[:, 1::2] = np.cos(angle[:, 1::2])
    return table


def _proj(x, d_model, name):
    """A bias-free projection to d_model: one fused matmul, so XLA maps it
    onto the MXU as a large GEMM."""
    return layers.fc(x, size=d_model, num_flatten_dims=2, bias_attr=False,
                     use_bf16=True, name=name)


def _attention(q_in, k_in, v_in, d_model, name, attend):
    """q/k/v projections → `attend(q, k, v)` → output projection, named
    `{name}_{q,k,v,o}`: the one order every attention in this file has."""
    q, k, v = (_proj(x, d_model, name and f"{name}_{n}")
               for x, n in zip((q_in, k_in, v_in), "qkv"))
    return _proj(attend(q, k, v), d_model, name and name + "_o")


def _flash_attend(q, k, v, num_heads, dropout=0.0, is_test=False,
                  causal=False, segment_ids=None):
    """Attention of projected q [B,Tq,D] over k/v [B,Tk,D], `num_heads`
    heads side by side in D. Returns [B,Tq,D]. The fused op takes and gives
    that layout; only the path that needs the attention weights themselves
    splits the heads out."""
    b, t_q, d_model = q.shape
    t_k = k.shape[1]
    d_head = d_model // num_heads
    if segment_ids is not None and dropout and not is_test:
        raise NotImplementedError(
            "packed batches (segment_ids) require the fused attention "
            "path; set attention dropout to 0 (residual/ffn dropout is "
            "unaffected)")
    if not dropout or is_test:
        # fused flash-attention op: Pallas kernel on TPU (O(T) memory),
        # XLA composite elsewhere — see ops/pallas_kernels.py
        ctx = layers.fused_attention(q, k, v,
                                     scale=float(d_head) ** -0.5,
                                     causal=causal,
                                     segment_ids=segment_ids,
                                     num_heads=num_heads)
        return _infer_scale(ctx, dropout) if is_test else ctx

    def split_heads(x, t):
        x = layers.reshape(x, shape=[b, t, num_heads, d_head])
        return layers.transpose(x, perm=[0, 2, 1, 3])

    # attention-weight dropout needs the explicit weights tensor
    q = layers.scale(split_heads(q, t_q), scale=float(d_head) ** -0.5)
    k = split_heads(k, t_k)
    v = split_heads(v, t_k)
    scores = layers.matmul(q, k, transpose_y=True, use_bf16=True)
    if causal:
        mask_np = np.triu(np.full((t_q, t_k), -1e9, dtype="float32"), k=1)
        mask = layers.assign(mask_np.reshape(1, 1, t_q, t_k))
        scores = layers.elementwise_add(scores, mask)
    weights = layers.softmax(scores)
    weights = layers.dropout(weights, dropout_prob=dropout, is_test=is_test)
    ctx = layers.matmul(weights, v, use_bf16=True)
    ctx = layers.transpose(ctx, perm=[0, 2, 1, 3])
    return layers.reshape(ctx, shape=[b, t_q, d_model])


def multi_head_attention(q_in, k_in, v_in, d_model, num_heads, dropout=0.0,
                         is_test=False, causal=False, segment_ids=None,
                         name=None):
    """Multi-head attention with explicit head split (≙ nets.py:332 composite
    generalized with masking): `_attention` around `_flash_attend`.

    segment_ids ([B, T] int32 var): packed-batch masking through the flash
    kernel (tokens attend only within their own segment — the static-shape
    LoD translation). Requires the fused path (attention-weight dropout
    off), which is also the only path that scales to long sequences."""
    return _attention(
        q_in, k_in, v_in, d_model, name,
        lambda q, k, v: _flash_attend(q, k, v, num_heads, dropout, is_test,
                                      causal, segment_ids))


def ffn(x, d_model, d_inner, dropout=0.0, is_test=False, name=None):
    h = layers.fc(x, size=d_inner, num_flatten_dims=2, act="relu",
                  use_bf16=True, name=name and name + "_fc1")
    if dropout:
        h = layers.dropout(h, dropout_prob=dropout, is_test=is_test)
    return layers.fc(h, size=d_model, num_flatten_dims=2, use_bf16=True,
                     name=name and name + "_fc2")


def _add_norm(x, residual, dropout=0.0, is_test=False, name=None):
    """name (when given) pins the LayerNorm parameter names so a decode
    graph built later in the same program shares the trained weights (the
    generation path rebuilds per-step computation from the same names)."""
    if dropout:
        x = layers.dropout(x, dropout_prob=dropout, is_test=is_test)
    kw = {}
    if name:
        kw = {"param_attr": ParamAttr(name=name + ".scale"),
              "bias_attr": ParamAttr(name=name + ".bias")}
    return layers.layer_norm(layers.elementwise_add(x, residual),
                             begin_norm_axis=2, **kw)


def encoder_layer(x, d_model, num_heads, d_inner, dropout, is_test, name):
    attn = multi_head_attention(x, x, x, d_model, num_heads, dropout,
                                is_test, name=name + "_attn")
    x = _add_norm(attn, x, dropout, is_test, name=name + "_ln1")
    f = ffn(x, d_model, d_inner, dropout, is_test, name=name + "_ffn")
    return _add_norm(f, x, dropout, is_test, name=name + "_ln2")


def decoder_layer(x, enc_out, d_model, num_heads, d_inner, dropout, is_test,
                  name):
    self_attn = multi_head_attention(x, x, x, d_model, num_heads, dropout,
                                     is_test, causal=True,
                                     name=name + "_self")
    x = _add_norm(self_attn, x, dropout, is_test, name=name + "_ln1")
    cross = multi_head_attention(x, enc_out, enc_out, d_model, num_heads,
                                 dropout, is_test, name=name + "_cross")
    x = _add_norm(cross, x, dropout, is_test, name=name + "_ln2")
    f = ffn(x, d_model, d_inner, dropout, is_test, name=name + "_ffn")
    return _add_norm(f, x, dropout, is_test, name=name + "_ln3")


def rotary_table(rope, max_len):
    """[max_len, rope.dim] float32: row t holds cos(t * f_i) in its first
    half and sin(t * f_i) in its second, both times `rope.table_scale`, the
    angles in float64 on the host (at 17k positions a float32 product and a
    device cosine of it would lose three digits). YaRN as the family's code
    has it: f_i blends theta^(-2i/dim) and the same over `factor` by a
    linear ramp between the correction dims of beta_fast and beta_slow."""
    dim, half = rope.dim, rope.dim // 2
    freq = rope.theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    if rope.factor > 1.0:
        def correction_dim(rotations):
            return (dim * np.log(rope.original_max / (rotations * 2 * np.pi))
                    / (2 * np.log(rope.theta)))
        low = max(int(np.floor(correction_dim(rope.beta_fast))), 0)
        high = min(int(np.ceil(correction_dim(rope.beta_slow))), dim - 1)
        ramp = np.clip((np.arange(half, dtype=np.float64) - low)
                       / max(high - low, 1e-3), 0.0, 1.0)
        freq = freq / rope.factor * ramp + freq * (1.0 - ramp)
    angle = np.arange(max_len, dtype=np.float64)[:, None] * freq[None, :]
    table = np.concatenate([np.cos(angle), np.sin(angle)], axis=1)
    return (table * rope.table_scale).astype("float32")


class _TickRows:
    """What a block of a non-classic spec needs to know of the rows it is
    fed, beyond their values: each row's position (rotary positions enter
    attention, not the embedding), which rows are real (a dead row — an
    idle slot, the tail of a short chunk — selects no expert), and where
    the routed layers leave their counts (`expert_rows`: per layer, the
    rows each held expert got); `conv`, where the spec has conv layers, is
    their state (`_ConvState`), `ssm` the state-space or kda layers'
    (`_RecurrentState`).
    `training`: the rows are a training graph's, [B, T] under one row of
    positions: the rotation carries a gradient, the routed layers are
    `layers.moe_train` and leave their balance terms in `aux`."""

    def __init__(self, spec, positions, live, max_len, conv=None, ssm=None,
                 training=False):
        self.positions, self.live, self.conv = positions, live, conv
        self.ssm, self.training = ssm, training
        self.expert_rows, self.aux = [], []
        self.table, self.tables, self.index_table = None, {}, None
        if spec.indexer is not None:    # the indexer's own rotation
            self.index_table = layers.assign(
                rotary_table(spec.indexer.rope, max_len))
        rope = spec.latent.rope if spec.latent else spec.rope
        if spec.positions == "rotary" and rope is not None:
            self.table = layers.assign(rotary_table(rope, max_len))
            self.tables[rope] = self.table
            if spec.rope_full is not None and spec.rope_full not in self.tables:
                self.tables[spec.rope_full] = layers.assign(
                    rotary_table(spec.rope_full, max_len))

    def table_of(self, spec, layer):
        """The rotary table of attention layer `layer` (a kind of layer may
        have its own: `DecoderSpec.rope_of`), None where it is not rotated."""
        rope = spec.rope_of(layer)
        return None if rope is None else self.tables[rope]

    def with_counts(self, next_ids):
        """`next_ids` [R,1] int64 followed by the routed layers' counts, one
        value a (layer, held expert): ONE fetch, so the counts come back in
        the copy that brings the ids."""
        if not self.expert_rows:
            return next_ids
        counts = layers.cast(layers.concat(self.expert_rows, axis=0), "int64")
        return layers.concat(
            [next_ids, layers.reshape(counts, shape=[-1, 1])], axis=0)


def _param(name, shape, dtype, initializer=None):
    """A named parameter no layer function makes (an expert stack, the two
    halves of `kv_b`): in the main program, and the startup program with
    the default initializer, or the one given."""
    from ..layer_helper import LayerHelper
    return LayerHelper("param").create_parameter(
        ParamAttr(name=name, initializer=initializer), shape=shape,
        dtype=dtype)


def _pre_norm(x, spec, name):
    if spec.norm == "rms_norm":
        return layers.rms_norm(x, epsilon=spec.norm_eps,
                               param_attr=ParamAttr(name=name + ".scale"))
    return layers.layer_norm(x, begin_norm_axis=2, epsilon=spec.norm_eps,
                             param_attr=ParamAttr(name=name + ".scale"),
                             bias_attr=ParamAttr(name=name + ".bias"))


def _scaled(x, by):
    """x times a spec's scalar multiplier, on the activation; no op at 1."""
    return x if by == 1.0 else layers.scale(x, scale=float(by))


def _gated_ffn(x, d_model, d_inner, name, by=(1.0, 1.0), limit=0.0):
    """down(silu(gate x) * up x), no biases; `by` (`Multipliers.mlp`) scales
    the gate before the SiLU and the output; `limit` > 0 (`swiglu_limit`)
    clamps the pair: gate <- min(gate, limit), up <- clip(up, +-limit)."""
    gate, up = (_proj(x, d_inner, f"{name}_{n}") for n in ("gate", "up"))
    if limit:
        gate = layers.clip(gate, -3e38, float(limit))
        up = layers.clip(up, -float(limit), float(limit))
    return _scaled(
        _proj(layers.elementwise_mul(layers.silu(_scaled(gate, by[0])), up),
              d_model, name + "_down"), by[1])


def _moe_ffn(x, spec, name, rows):
    """The routed layer: the held experts' part of the top-k sum (routing
    over every expert; `fusion/moe.py`) plus the shared expert, where the
    spec has one."""
    moe, d = spec.moe, spec.d_model
    gated = moe.activation == "gated_silu"
    if rows.training:
        return _moe_train_ffn(x, spec, name, rows)
    if moe.scoring != "sigmoid":
        raise NotImplementedError(
            f"scoring {moe.scoring!r}: a serving tick routes by sigmoid "
            "scores (fusion/moe.py `route`); softmax is the training graph's")
    # the routed experts' row: x, or a latent between two projections that
    # every expert shares (the router and the shared expert read x itself)
    dz = moe.latent or d
    router = _param(name + "_router.w_0", [d, moe.n_routed], spec.dtype)
    stack = {n: _param(f"{name}_experts_{n}",
                       [len(moe.held), moe.d_expert, dz] if n == "down"
                       else [len(moe.held), dz, moe.d_expert], spec.dtype)
             for n in (("gate", "up", "down") if gated else ("up", "down"))}
    bias = None
    if moe.topk_method != "none":
        bias = _param(name + "_router_bias", [moe.n_routed], "float32")
    weights, n_rows = layers.moe_route(
        x, router, moe.held, moe.top_k, moe.scaling, moe.norm_topk_prob,
        live=rows.live, bias=bias, norm_eps=moe.norm_eps,
        groups=(moe.n_group, moe.topk_group)
        if moe.topk_method == "group_bias" else None)
    rows.expert_rows.append(n_rows)
    z = _proj(x, dz, name + "_latent_down") if moe.latent else x
    routed = layers.moe_experts(z, weights, n_rows, stack.get("gate"),
                                stack["up"], stack["down"],
                                limit=moe.swiglu_limit)
    if moe.latent:
        routed = _proj(routed, d, name + "_latent_up")
    if not moe.n_shared:
        return routed
    if gated:
        shared = _gated_ffn(x, d, moe.shared_width, name + "_shared",
                            limit=moe.swiglu_limit)
    else:
        shared = _proj(layers.square(layers.relu(
            _proj(x, moe.shared_width, name + "_shared_up"))), d,
            name + "_shared_down")
    return layers.elementwise_add(routed, shared)


def _moe_train_ffn(x, spec, name, rows):
    """The routed layer of a training graph (`layers.moe_train`: softmax
    scores, a gradient to the router and the stacks, the balance term left
    in `rows.aux`, counters `{name}.rows/.pairs/.aux` kept in the scope),
    under the parameter names the serving ticks read; the stacks start at
    N(0, 1 / fan-in)."""
    moe, d = spec.moe, spec.d_model
    if (moe.scoring, moe.activation, moe.topk_method) != \
            ("softmax", "gated_silu", "none") or moe.latent or moe.n_shared:
        raise NotImplementedError(
            "a training graph builds softmax-scored gated-SiLU experts "
            f"without a shared one, a latent or a selection bias: {moe}")
    router = _param(name + "_router.w_0", [d, moe.n_routed], "float32")
    stack = {n: _param(f"{name}_experts_{n}",
                       [len(moe.held), moe.d_expert, d] if n == "down"
                       else [len(moe.held), d, moe.d_expert], "float32",
                       NormalInitializer(0., float(
                           moe.d_expert if n == "down" else d) ** -0.5))
             for n in ("gate", "up", "down")}
    out, aux = layers.moe_train(
        x, router, moe.held, moe.top_k, stack["gate"], stack["up"],
        stack["down"], counters=name, scaling=moe.scaling,
        norm_topk_prob=moe.norm_topk_prob)
    rows.aux.append(aux)
    return out


def _latent_attention(x, spec, name, attend, rows):
    """Latent attention (MLA) around `attend(q_rows, cache_row)`: queries
    through the `q_a` bottleneck and its norm (`q_lora_rank` None: ONE matrix
    `_q`); a gate a head on the output where the spec has one (`_gate`);
    ONE row a token from `kv_a`
    (normalised `c_kv`, rotated `k_pe`); the key half of `kv_b` absorbed
    into the query, so that the cache is read as it is stored, and the
    value half applied to what the read returns (fusion/latent_attention.py).
    A query row and a cache row are padded alike to `row_lanes`. Without a
    `lat.rope` nothing is rotated and the row is `c_kv` alone. With
    `spec.indexer` the read is SPARSE and `attend` also takes the row's index
    inputs (`_index_inputs`)."""
    lat, nh = spec.latent, spec.num_heads
    n = x.shape[0]
    dn, dr, c = lat.qk_nope_head_dim, lat.rope_dim, lat.kv_lora_rank
    pad = lat.row_lanes - lat.row_values
    c_q = None
    if lat.q_lora_rank is None:
        q = _proj(x, nh * (dn + dr), name + "_q")
    else:
        c_q = layers.rms_norm(
            _proj(x, lat.q_lora_rank, name + "_qa"), epsilon=spec.norm_eps,
            param_attr=ParamAttr(name=name + "_qa_norm.scale"))
        q = _proj(c_q, nh * (dn + dr), name + "_qb")
    q = layers.reshape(q, shape=[n, nh, dn + dr])
    if dr:
        q_nope = layers.reshape(
            layers.slice(q, axes=[2], starts=[0], ends=[dn]),
            shape=[n, nh * dn])
        q_pe = layers.rotary(
            layers.reshape(
                layers.slice(q, axes=[2], starts=[dn], ends=[dn + dr]),
                shape=[n, nh * dr]), rows.positions, rows.table)
    else:
        q_nope = layers.reshape(q, shape=[n, nh * dn])
    kv = layers.reshape(_proj(x, c + dr, name + "_kva"), shape=[n, c + dr])
    c_kv = layers.rms_norm(
        layers.slice(kv, axes=[1], starts=[0], ends=[c]) if dr else kv,
        epsilon=spec.norm_eps,
        param_attr=ParamAttr(name=name + "_kva_norm.scale"))
    if dr:
        k_pe = layers.rotary(
            layers.slice(kv, axes=[1], starts=[c], ends=[c + dr]),
            rows.positions, rows.table)
    kv_b = _param(name + "_kvb.w_0", [c, nh * (dn + lat.v_head_dim)],
                  spec.dtype)
    q_lat = layers.latent_head_proj(q_nope, kv_b, "absorb_q", nh, dn,
                                    lat.v_head_dim)
    q_parts, row_parts = [layers.reshape(q_lat, shape=[n, nh, c])], [c_kv]
    if dr:
        q_parts.append(layers.reshape(q_pe, shape=[n, nh, dr]))
        row_parts.append(k_pe)
    if pad:
        q_parts.append(layers.fill_constant([n, nh, pad], spec.dtype, 0.0))
        row_parts.append(layers.fill_constant([n, pad], spec.dtype, 0.0))

    def joined(parts, axis):
        return parts[0] if len(parts) == 1 else layers.concat(parts,
                                                              axis=axis)
    index = () if spec.indexer is None else (
        _index_inputs(x, c_q, spec, name, rows),)
    ctx = attend(
        layers.reshape(joined(q_parts, 2), shape=[n, 1, nh * lat.row_lanes]),
        layers.reshape(joined(row_parts, 1), shape=[n, 1, lat.row_lanes]),
        *index)                                               # [n,1,nh*c]
    out = layers.latent_head_proj(ctx, kv_b, "expand_v", nh, dn,
                                  lat.v_head_dim)
    if lat.gate == "head":
        out = layers.head_gate(out, _proj(x, nh, name + "_gate"), nh)
    return _proj(out, spec.d_model, name + "_o")


def _index_inputs(x, c_q, spec, name, rows):
    """What a sparse latent read needs of the rows beside their queries
    (`IndexerSpec`; fusion/sparse_latent_attention.py): the index queries
    from the query bottleneck `c_q` (`_iq`), the index key (`_ik`, then a
    LayerNorm with scale and bias), the heads' weights (`_iw`, scaled by
    heads^-1/2 dim^-1/2), the rows' positions and the indexer's rotary
    table. The rotation, the pooling and the selection are the read's."""
    ix = spec.indexer
    ki = layers.layer_norm(
        _proj(x, ix.head_dim, name + "_ik"), begin_norm_axis=2,
        epsilon=spec.norm_eps,
        param_attr=ParamAttr(name=name + "_ik_norm.scale"),
        bias_attr=ParamAttr(name=name + "_ik_norm.bias"))
    wi = layers.scale(
        layers.fc(x, size=ix.heads, num_flatten_dims=2, bias_attr=False,
                  use_bf16=True, name=name + "_iw", out_dtype="float32"),
        scale=float(ix.heads) ** -0.5 * float(ix.head_dim) ** -0.5)
    return dict(qi=_proj(c_q, ix.heads * ix.head_dim, name + "_iq"), ki=ki,
                wi=wi, positions=rows.positions, table=rows.index_table)


def _grouped_attention(x, spec, name, attend, rows, table=None):
    """Attention with `spec.kv_heads` key/value heads under `num_heads`
    query heads (query head i reads key/value head i // group), an RMSNorm
    a head on q and k where the spec asks (`qk_norm`), rotary positions
    over the whole head by `table` (the layer's: `_TickRows.table_of`; None:
    not rotated), around `attend(q, k_new, v_new)`; `spec.multipliers`
    scales the input, the keys before the rotation and the output. x is a
    tick's rows [n, 1, d] or a training graph's [B, T, d]."""
    n, nh, nkv, dh = x.shape[0], spec.num_heads, spec.kv_heads, spec.d_head
    by = spec.multipliers
    x = _scaled(x, by.attention_in)

    def heads(t, count, which):
        if spec.qk_norm:
            t = layers.rms_norm(
                layers.reshape(t, shape=[n, count, dh]),
                epsilon=spec.norm_eps,
                param_attr=ParamAttr(name=f"{name}_{which}_norm.scale"))
        if table is not None:
            t = layers.rotary(layers.reshape(t, shape=[n, count * dh]),
                              rows.positions, table,
                              stop_gradient=not rows.training)
        return layers.reshape(t, shape=[n, x.shape[1], count * dh])

    q = heads(_proj(x, nh * dh, name + "_q"), nh, "q")
    k = heads(_scaled(_proj(x, nkv * dh, name + "_k"), by.key), nkv, "k")
    v = _proj(x, nkv * dh, name + "_v")
    return _scaled(_proj(attend(q, k, v), spec.d_model, name + "_o"),
                   by.attention_out)


def _short_conv(x, spec, name, rows):
    """The gated short convolution: `[B, C, z] = x W_in`, `u = B * z`, the
    causal depthwise convolution of u from the request's state
    (`rows.conv`, fusion/short_conv.py), `(C * conv) W_out`; no bias."""
    d = spec.d_model
    bcz = _proj(x, 3 * d, name + "_in")
    b, c, z = (layers.slice(bcz, axes=[2], starts=[j * d], ends=[(j + 1) * d])
               for j in range(3))
    taps = _param(name + "_taps", [d, spec.conv.taps], spec.dtype)
    conv = rows.conv.layer(layers.elementwise_mul(b, z), taps)
    return _proj(layers.elementwise_mul(c, conv), d, name + "_out")


def _ssm_mixer(x, spec, name, rows):
    """The Mamba-2 mixer: `[z, xBC, dt] = x W_in`; the convolution and the
    scan from the request's state (`rows.ssm`, fusion/ssm.py); the gated
    group RMSNorm; `W_out`; no bias on either projection. Under
    `spec.multipliers`: the input and the output by a scalar each, the
    projection's columns by a vector that is constant on each of the ranges
    z, x, B, C, dt (in float32, rounded once to the activations' dtype)."""
    ssm, by = spec.ssm, spec.multipliers
    zxd = _proj(_scaled(x, by.ssm_in), ssm.in_dim, name + "_in")
    if by.ssm != (1.0,) * 5:
        gn = ssm.groups * ssm.state
        ranges = np.repeat(np.asarray(by.ssm, "float32"),
                           [ssm.d_inner, ssm.d_inner, gn, gn, ssm.heads])
        zxd = layers.cast(layers.elementwise_mul(
            layers.cast(zxd, "float32"), layers.assign(ranges)), spec.dtype)
    z, xbc, dt = (layers.slice(zxd, axes=[2], starts=[a], ends=[b])
                  for a, b in ((0, ssm.d_inner),
                               (ssm.d_inner, ssm.d_inner + ssm.conv_dim),
                               (ssm.d_inner + ssm.conv_dim, ssm.in_dim)))
    params = dict(
        taps=_param(name + "_taps", [ssm.conv_dim, ssm.taps], spec.dtype),
        conv_bias=_param(name + "_conv_bias", [ssm.conv_dim], spec.dtype),
        a_log=_param(name + "_a_log", [ssm.heads], "float32"),
        dt_bias=_param(name + "_dt_bias", [ssm.heads], "float32"),
        d=_param(name + "_d", [ssm.heads], "float32"))
    y = rows.ssm.layer(xbc, (dt,), params, rows.live)
    y = layers.gated_rms_norm(y, z, ssm.groups, epsilon=spec.norm_eps,
                              param_attr=ParamAttr(name=name + "_norm.scale"))
    return _scaled(_proj(y, spec.d_model, name + "_out"), by.ssm_out)


def _kda_mixer(x, spec, name, rows):
    """The channel-wise gated delta-rule mixer: `[q | k | v] = x W_qkv`, the
    gate's `f = x W_f` (a value a key channel, ONE full matrix) and `b = x
    W_b` (a value a head); the convolution and the delta-rule scan from the
    request's state (`rows.ssm`, fusion/kda.py); an RMSNorm a head times
    sigmoid(x W_g); `W_o`; no bias anywhere, no rotation."""
    kda = spec.kda

    def gate_proj(which):
        """One full matrix, or the low-rank pair `_{which}a`, `_{which}b`."""
        if not kda.gate_rank:
            return _proj(x, kda.d_inner, f"{name}_{which}")
        return _proj(_proj(x, kda.gate_rank, f"{name}_{which}a"),
                     kda.d_inner, f"{name}_{which}b")
    qkv = _proj(x, kda.conv_dim, name + "_qkv")
    f = gate_proj("f")
    b = _proj(x, kda.heads, name + "_b")
    params = dict(
        taps=_param(name + "_taps", [kda.conv_dim, kda.taps], spec.dtype),
        a_log=_param(name + "_a_log", [kda.heads], "float32"),
        dt_bias=_param(name + "_dt_bias", [kda.d_inner], "float32"))
    o = rows.ssm.layer(qkv, (f, b), params, rows.live)
    o = layers.kda_gate_norm(o, gate_proj("g"), kda.heads,
                             epsilon=spec.norm_eps,
                             param_attr=ParamAttr(name=name + "_norm.scale"))
    return _proj(o, spec.d_model, name + "_o")


def _decoder_block(x, i, attend, d_model, d_inner, dropout, is_test=True,
                   prefix="l", attn="attn", cross=None, spec=None,
                   rows=None):
    """Decoder layer `i` of every graph but the NMT training graph, built
    from what `spec` (a `DecoderSpec`; None is the classic one) says a block
    is made of:

    - the operator, by `spec.layer_kind(i)`: attention with full heads
      (`_attention` around `attend(i, q, k_new, v_new)`, which is all a
      graph chooses: see the module docstring; `_grouped_attention` where
      the heads are rotated, normalised or grouped), latent attention
      (`_latent_attention` around `attend(i, q_rows, cache_row)`), or the
      gated short convolution (`_short_conv`, its state in `rows.conv`), or
      the gated delta-rule mixer (`_kda_mixer`, its state in `rows.ssm`);
    - `cross(i, x)` when given;
    - the feed-forward: the ReLU pair, the gated SiLU pair, or from
      `spec.moe.first_dense` on routed experts beside the shared one;
    - the residual order: post (`_add_norm`: add, then LayerNorm, named
      `{prefix}{i}_ln{1,2[,3]}`) or pre (`x + f(norm(x))`, same names);
    - with `spec.one_sublayer` ONE of the Mamba-2 mixer (`_ssm_mixer`, its
      state in `rows.ssm`), grouped attention or the routed experts, alone
      under one pre-norm residual;
    - with `spec.mixer` "ssm+attention" the Mamba-2 mixer AND grouped
      attention on one normed input, both added to the residual, then the
      feed-forward under its own norm.

    `rows` (`_TickRows`) carries what the rotary and routed kinds need of
    the tick. The parameter names are the contract: a graph built from this
    block runs the weights any other trained."""
    name = f"{prefix}{i}"
    spec = spec or DecoderSpec.classic(d_model=d_model, d_inner=d_inner,
                                       dropout=dropout)
    if spec.one_sublayer:
        # a layer is its kind ALONE, under one pre-norm residual
        kind = spec.layer_kind(i)
        sublayer = {
            "ssm": lambda x: _ssm_mixer(x, spec, f"{name}_ssm", rows),
            "attention": lambda x: _grouped_attention(
                x, spec, f"{name}_{attn}", functools.partial(attend, i),
                rows, rows.table),
            "moe": lambda x: _moe_ffn(x, spec, f"{name}_moe", rows)}[kind]
        return layers.elementwise_add(
            x, sublayer(_pre_norm(x, spec, f"{name}_ln1")))
    if spec.mixer == "ssm+attention":
        sublayers = [lambda x: layers.elementwise_add(
            _ssm_mixer(x, spec, f"{name}_ssm", rows),
            _grouped_attention(x, spec, f"{name}_{attn}",
                               functools.partial(attend, i), rows,
                               rows.table_of(spec, i)))]
    elif spec.layer_kind(i) == "conv":
        sublayers = [lambda x: _short_conv(x, spec, f"{name}_conv", rows)]
    elif spec.layer_kind(i) == "kda":
        sublayers = [lambda x: _kda_mixer(x, spec, f"{name}_kda", rows)]
    elif spec.attention == "latent":
        sublayers = [lambda x: _latent_attention(
            x, spec, f"{name}_{attn}", functools.partial(attend, i), rows)]
    elif spec.rope is not None or spec.qk_norm \
            or spec.kv_heads != spec.num_heads:
        sublayers = [lambda x: _grouped_attention(
            x, spec, f"{name}_{attn}", functools.partial(attend, i), rows,
            rows.table_of(spec, i))]
    else:
        sublayers = [lambda x: _attention(x, x, x, d_model, f"{name}_{attn}",
                                          functools.partial(attend, i))]
    if cross is not None:
        sublayers.append(functools.partial(cross, i))
    kind = spec.ffn_kind(i)
    if kind == "moe":
        sublayers.append(lambda x: _moe_ffn(x, spec, f"{name}_moe", rows))
    elif kind == "gated_silu":
        sublayers.append(lambda x: _gated_ffn(
            x, d_model, d_inner, f"{name}_ffn", spec.multipliers.mlp,
            limit=spec.moe.swiglu_limit if spec.moe else 0.0))
    else:
        sublayers.append(lambda x: ffn(x, d_model, d_inner, dropout, is_test,
                                       name=f"{name}_ffn"))
    if spec.residual == "pre":
        for n, sublayer in enumerate(sublayers, 1):
            x = layers.elementwise_add(
                x, sublayer(_pre_norm(x, spec, f"{name}_ln{n}")))
        return x
    if spec.residual == "mhc":
        # x holds `hyper.mult` streams side by side; a sub-layer sees their
        # mix under its own pre-norm, and its output goes back to all of
        # them (fusion/hyper_connection.py)
        hc, width = spec.hyper, spec.hyper.mult * d_model
        for n, sublayer in enumerate(sublayers, 1):
            u, h_post, h_res = layers.hyper_connection_pre(
                x, _param(f"{name}_hc{n}_p", [width, hc.maps], spec.dtype),
                _param(f"{name}_hc{n}_a", [3], "float32"),
                _param(f"{name}_hc{n}_b", [hc.maps], "float32"), hc,
                spec.norm_eps)
            x = layers.hyper_connection_post(
                x, sublayer(_pre_norm(u, spec, f"{name}_ln{n}")), h_post,
                h_res, hc)
        return x
    if spec.norm != "layer_norm":
        raise NotImplementedError(
            f"a post-norm block with norm {spec.norm!r}: no graph builds it")
    for n, sublayer in enumerate(sublayers, 1):
        x = _add_norm(sublayer(x), x, dropout, is_test, name=f"{name}_ln{n}")
    return x


def _lm_decoder(x, attend, num_layers, d_model, d_inner, dropout,
                is_test=True, param_prefix="", spec=None, rows=None):
    """The LM's stack of `_decoder_block`s, weights `{param_prefix}l{i}_*`;
    a pre-norm stack ends in its final norm (`{param_prefix}final_norm`)."""
    hyper = spec.hyper if spec is not None else None
    if hyper is not None:       # the embedding in every stream
        x = layers.concat([x] * hyper.mult, axis=2)
    for i in range(num_layers):
        x = _decoder_block(x, i, attend, d_model, d_inner, dropout, is_test,
                           prefix=f"{param_prefix}l", spec=spec, rows=rows)
    if hyper is not None:       # ... and their sum out
        x = layers.hyper_connection_exit(x, hyper)
    if spec is not None and spec.residual != "post":
        x = _pre_norm(x, spec, f"{param_prefix}final_norm")
    return x


def _lm_head(x, vocab, name="lm_head", ids=True, logp=False, bias=True,
             out_dtype=None):
    """The vocabulary head: (logits, their argmax if `ids`, their
    log-softmax if `logp`)."""
    logits = layers.fc(x, size=vocab, num_flatten_dims=2, use_bf16=True,
                       name=name, bias_attr=None if bias else False,
                       out_dtype=out_dtype)
    return (logits, layers.argmax(logits, axis=2) if ids else None,
            layers.log_softmax(logits) if logp else None)


def _embed(tokens, vocab_size, d_model, max_len, name, positions=None):
    """positions ([B, T] int32 var): per-token positional-encoding index.
    Packed batches use position-within-segment so a sequence embeds the
    same wherever it lands in the pack; default is the row position."""
    emb = layers.embedding(
        input=tokens, size=[vocab_size, d_model],
        param_attr=ParamAttr(name=name + "_emb",
                             initializer=NormalInitializer(0., d_model ** -0.5)))
    emb = layers.scale(emb, scale=float(d_model) ** 0.5)
    table = positional_encoding_table(max_len, d_model)
    if positions is not None:
        pos = layers.gather(layers.assign(table), positions)
    else:
        pos = layers.assign(table[None, :, :])
    return layers.elementwise_add(emb, pos)


def transformer(src=None, tgt=None, label=None, src_vocab=30000,
                tgt_vocab=30000, max_len=64, d_model=512, d_inner=2048,
                num_heads=8, num_layers=6, dropout=0.1, is_test=False,
                label_smooth=0.1):
    """Transformer-base encoder-decoder; returns (loss, logits).

    src/tgt: [B, T] int64 padded token ids (lod_level=1 data vars with
    companion lengths); label: [B, T] next-token targets.
    """
    if src is None:
        src = layers.data(name="src", shape=[max_len], dtype="int64",
                          lod_level=1)
    if tgt is None:
        tgt = layers.data(name="tgt", shape=[max_len], dtype="int64",
                          lod_level=1)
    if label is None:
        label = layers.data(name="lbl", shape=[max_len], dtype="int64")
    src_len = layers.sequence.get_seqlen(src)
    tgt_len = layers.sequence.get_seqlen(tgt)

    enc = _embed(src, src_vocab, d_model, max_len, "src")
    if dropout:
        enc = layers.dropout(enc, dropout_prob=dropout, is_test=is_test)
    for i in range(num_layers):
        enc = encoder_layer(enc, d_model, num_heads, d_inner, dropout,
                            is_test, f"enc{i}")

    dec = _embed(tgt, tgt_vocab, d_model, max_len, "tgt")
    if dropout:
        dec = layers.dropout(dec, dropout_prob=dropout, is_test=is_test)
    for i in range(num_layers):
        dec = decoder_layer(dec, enc, d_model, num_heads, d_inner, dropout,
                            is_test, f"dec{i}")

    logits = layers.fc(dec, size=tgt_vocab, num_flatten_dims=2,
                       use_bf16=True, name="proj")
    label3 = layers.unsqueeze(label, axes=[2])
    if label_smooth:
        # uniform label smoothing decomposed (identical math, no [B,T,V]
        # one-hot/smoothed-target materialization — those were measured as
        # avoidable HBM traffic on the NMT step):
        #   CE(smooth) = (1-eps)*CE(hard) + eps * mean_V(-log_softmax)
        eps = float(label_smooth)
        ce_hard = layers.softmax_with_cross_entropy(logits, label3)
        lp = layers.log_softmax(logits)
        uniform = layers.scale(
            layers.reduce_mean(lp, dim=[2], keep_dim=True), scale=-1.0)
        token_loss = layers.elementwise_add(
            layers.scale(ce_hard, scale=1.0 - eps),
            layers.scale(uniform, scale=eps))
    else:
        token_loss = layers.softmax_with_cross_entropy(logits, label3)
    mask = layers.sequence_mask(tgt_len, maxlen=max_len)
    mask = layers.unsqueeze(mask, axes=[2])
    masked = layers.elementwise_mul(token_loss, mask)
    loss = layers.reduce_sum(masked) / layers.reduce_sum(mask)
    return loss, logits


# ---------------------------------------------------------------------------
# Inference graphs: one position (or a short window) a forward, over a KV cache
# ---------------------------------------------------------------------------


def _infer_scale(ctx, dropout):
    """downgrade_in_infer: where the train graph dropped attention weights
    (scaling by the keep mask), inference scales the context by (1-p) to
    keep the expectation the downstream weights were trained against."""
    return layers.scale(ctx, scale=1.0 - dropout) if dropout else ctx


def _masked_attention(q5, k5, v5, bias, d_head):
    """softmax(q·Kᵀ/√dh + bias)·V over caches laid out [.., nh, T, dh]:
    scores read k via transpose_y — free on the MXU — so ONE cache layout
    serves both matmuls and a cache write lands on the sublane T axis, not
    the lane axis. This four-op chain is what fuse_decode_attention_pass
    matches (framework/passes.py)."""
    scores = layers.matmul(q5, k5, transpose_y=True,
                           alpha=float(d_head) ** -0.5)
    weights = layers.softmax(layers.elementwise_add(scores, bias))
    return layers.matmul(weights, v5)


def _attend_cached(q, k5, v5, bias, K, num_heads, d_head, dropout=0.0):
    """Per-head attention of a single-position query q [B,K,H] over cached
    k5 / v5 [B,*,nh,T*,dh] (the * dims broadcast over the beam axis), the
    additive `bias` masking invalid keys; (1-p) context scaling as
    `_infer_scale`."""
    q5 = layers.reshape(q, shape=[0, K, num_heads, 1, d_head])
    ctx = layers.reshape(_masked_attention(q5, k5, v5, bias, d_head),
                         shape=[0, K, num_heads * d_head])
    return _infer_scale(ctx, dropout)


def _gen_embed_step(ids_prev, pos, emb_name, vocab, d_model, pe_table,
                    dropout=0.0):
    """Embed the previous token + positional encoding at `pos` (one-hot
    row-select from the PE table), with the train graph's post-embedding
    dropout corrected to its (1-p) inference scaling."""
    T = pe_table.shape[0]
    onehot_t = layers.one_hot(layers.cast(pos, "int64"), depth=T)
    emb = layers.embedding(layers.unsqueeze(ids_prev, axes=[2]),
                           size=[vocab, d_model],
                           param_attr=ParamAttr(name=emb_name))
    x = layers.scale(emb, scale=float(d_model) ** 0.5)
    x = layers.elementwise_add(
        x, layers.matmul(onehot_t, layers.assign(pe_table)))
    if dropout:
        x = layers.dropout(x, dropout_prob=dropout, is_test=True)
    return x


def _mask_to_bias(mask, axes):
    """0/1 keep-mask -> additive attention bias (-1e9 on masked keys),
    unsqueezed to broadcast against [.., nh, 1, T] score tensors."""
    return layers.unsqueeze(layers.scale(mask, scale=1e9, bias=-1e9),
                            axes=axes)


def _next_pos(pos):
    return layers.elementwise_add(pos,
                                  layers.fill_constant([1], "float32", 1.0))


def _causal_bias(pos, T, axes):
    """Additive bias hiding cache positions beyond `pos` (one row of T keys
    per entry of `pos`). A window's row g at position pos+g so attends
    every window row written earlier in the same forward: its scores are
    EXACTLY those of feeding the same tokens one at a time."""
    arange = np.arange(T, dtype="float32").reshape(1, 1, T)
    valid = layers.cast(
        layers.less_than(layers.assign(arange), _next_pos(pos)), "float32")
    return _mask_to_bias(valid, axes=axes)


def _window_positions(pos, G):
    """Absolute positions of a G-row window: base `pos` [S,1,1] + offsets
    0..G-1 → [S,G,1] (position of each fed token / written cache row)."""
    offs = np.arange(G, dtype="float32").reshape(1, G, 1)
    return layers.elementwise_add(pos, layers.assign(offs))


class _ScanCache:
    """The KV cache of a decode scan (`BeamSearchDecoder` step): per-layer
    k and v in the scan carry, BOTH pre-transposed head-major [B,K,nh,T,dh]
    (see `_masked_attention`), every sequence at the same scalar position
    `states["pos"]`. A step writes one row (`cache_write`, an in-place
    dynamic_update_slice in the carry) and attends over the masked cache
    directly — no per-step transpose or full-cache rewrite, so the per-step
    HBM cost is one row write + one cache read (the decode roofline's
    structural floor). Made inside the step, after the embedding;
    `new_states` is the carry the step returns."""

    @staticmethod
    def initial(batch_ref, K, T, num_layers, num_heads, d_head):
        """The scan's initial carry: position counter + zeroed caches."""
        init = {"pos": layers.fill_constant_batch_size_like(
            batch_ref, shape=[-1, K, 1], dtype="float32", value=0.0)}
        for i in range(num_layers):
            for sname in ("k", "v"):
                init[f"{sname}{i}"] = layers.fill_constant_batch_size_like(
                    batch_ref, shape=[-1, K, num_heads, T, d_head],
                    dtype="float32", value=0.0)
        return init

    def __init__(self, states, K, T, num_heads, d_head, dropout):
        self.states, self.pos = states, states["pos"]
        self.K, self.num_heads, self.d_head = K, num_heads, d_head
        self.dropout = dropout
        self.bias = _causal_bias(self.pos, T, axes=[2, 3])
        self.new_states = {"pos": _next_pos(self.pos)}

    def attend(self, i, q, kn, vn):
        K, nh, dh = self.K, self.num_heads, self.d_head
        for s, new in (("k", kn), ("v", vn)):
            self.new_states[f"{s}{i}"] = layers.cache_write(
                self.states[f"{s}{i}"],
                layers.reshape(new, shape=[0, K, nh, 1, dh]), self.pos,
                axis=3)
        return _attend_cached(q, self.new_states[f"k{i}"],
                              self.new_states[f"v{i}"], self.bias, K, nh, dh,
                              self.dropout)


def transformer_generate(src=None, src_vocab=30000, tgt_vocab=30000,
                         max_src_len=64, max_gen=32, d_model=512,
                         d_inner=2048, num_heads=8, num_layers=6,
                         bos_id=0, eos_id=1, beam_size=4, dropout=0.0):
    """Encoder-decoder generation: encode the source once, then decode
    autoregressively with per-layer SELF-attention KV caches in the scan
    carry; cross-attention keys/values are projected once outside the
    scan and broadcast over the beam axis. Weights shared by name with a
    transformer(...) train graph (enc{i}_*, dec{i}_*, src/tgt_emb, proj)
    built with the same dims — train, then build this in its own program
    and run it in the same scope. Pass the SAME `dropout` the train graph
    used: every dropout site is corrected to its (1-p) inference scaling
    (downgrade_in_infer), exactly as is_test=True does on the train graph.

    Returns (sequences [B, max_gen, K], scores [B, K])."""
    from ..contrib.decoder import BeamSearchDecoder

    if src is None:
        src = layers.data(name="src", shape=[max_src_len], dtype="int64",
                          lod_level=1)
    src_len = layers.sequence.get_seqlen(src)
    K, T, H = beam_size, max_gen, d_model
    Ts = max_src_len
    d_head = d_model // num_heads

    enc = _embed(src, src_vocab, d_model, Ts, "src")
    if dropout:
        enc = layers.dropout(enc, dropout_prob=dropout, is_test=True)
    for i in range(num_layers):
        enc = encoder_layer(enc, d_model, num_heads, d_inner, dropout,
                            True, f"enc{i}")

    # cross K/V once per layer, [B, 1, nh, Ts, dh] views that broadcast
    # over the beam axis inside the scan
    def beam_view(x):
        return layers.transpose(
            layers.reshape(x, shape=[0, 1, Ts, num_heads, d_head]),
            perm=[0, 1, 3, 2, 4])

    cross_kv = []
    for i in range(num_layers):
        ck, cv = (_proj(enc, H, f"dec{i}_cross_{n}") for n in "kv")
        cross_kv.append((beam_view(ck), beam_view(cv)))
    src_mask = layers.sequence_mask(src_len, maxlen=Ts)   # [B,Ts]
    src_bias = _mask_to_bias(src_mask, axes=[1, 2, 3])

    decoder = BeamSearchDecoder(beam_size=K, bos_id=bos_id, eos_id=eos_id,
                                max_len=T, name="nmt_gen")
    pe_table = positional_encoding_table(T, d_model).astype("float32")
    init = _ScanCache.initial(src, K, T, num_layers, num_heads, d_head)

    def cross(i, x):
        """Cross-attention over the pre-projected encoder K/V."""
        ctx = _attend_cached(_proj(x, H, f"dec{i}_cross_q"), *cross_kv[i],
                             src_bias, K, num_heads, d_head, dropout)
        return _proj(ctx, H, f"dec{i}_cross_o")

    def step(states, ids_prev):
        x = _gen_embed_step(ids_prev, states["pos"], "tgt_emb", tgt_vocab,
                            d_model, pe_table, dropout)
        cache = _ScanCache(states, K, T, num_heads, d_head, dropout)
        for i in range(num_layers):
            x = _decoder_block(x, i, cache.attend, d_model, d_inner, dropout,
                               prefix="dec", attn="self", cross=cross)
        _, _, logp = _lm_head(x, tgt_vocab, "proj", ids=False, logp=True)
        return cache.new_states, logp

    return decoder.decode(src, init, step)


def transformer_lm_generate(prompt=None, vocab=32000, max_gen=32,
                            d_model=512, d_inner=2048, num_heads=8,
                            num_layers=6, bos_id=0, eos_id=-1, beam_size=1,
                            dropout=0.0, packed=False):
    """Autoregressive generation with a per-layer KV cache (capability ≙
    the reference transformer benchmark's fast decoder; the reference
    decodes by re-running the while_op decoder with LoD beam state).

    TPU-first: one StaticRNN (lax.scan) over max_gen positions, the KV
    cache in the scan carry (`_ScanCache`). Weights are shared BY NAME with
    a transformer_lm(...) built earlier in the same program — train first,
    then build this decode graph and run it in the same scope, passing the
    SAME `dropout` AND the same `packed` flag the train graph used (each
    dropout site is corrected to its (1-p) inference scaling, and —
    mirroring transformer_lm's `0.0 if packed else dropout`
    attention-weight dropout — packed training applied NO attention
    dropout, so packed=True here skips the (1-p) attention-context
    downscale the train graph never had).
    Generation is conditioned on the fed `prompt` ([B, 1] int64): each
    row's first token seeds the decode; `bos_id` is the fallback start
    used only when a caller builds its own decoder. beam_size=1 is
    greedy; >1 is beam search through the shared BeamSearchDecoder.

    Returns (sequences [B, max_gen, K], scores [B, K])."""
    from ..contrib.decoder import BeamSearchDecoder

    if prompt is None:
        prompt = layers.data(name="prompt", shape=[1], dtype="int64")
    K, T = beam_size, max_gen
    d_head = d_model // num_heads
    decoder = BeamSearchDecoder(beam_size=K, bos_id=bos_id, eos_id=eos_id,
                                max_len=T, name="lm_gen")

    pe_table = positional_encoding_table(T, d_model).astype("float32")
    init = _ScanCache.initial(prompt, K, T, num_layers, num_heads, d_head)

    def step(states, ids_prev):
        x = _gen_embed_step(ids_prev, states["pos"], "tok_emb", vocab,
                            d_model, pe_table, dropout)
        cache = _ScanCache(states, K, T, num_heads, d_head,
                           0.0 if packed else dropout)
        x = _lm_decoder(x, cache.attend, num_layers, d_model, d_inner,
                        dropout)
        _, _, logp = _lm_head(x, vocab, ids=False, logp=True)
        return cache.new_states, logp

    return decoder.decode(prompt, init, step, init_ids=prompt)


# ---------------------------------------------------------------------------
# Serving ticks: ONE forward over persistable KV state (serving/)
# ---------------------------------------------------------------------------


def _feed(name, shape, dtype="int64"):
    """A tick's feed, of STATIC shape (no -1 batch): the slot count is the
    program's shape, and the static form is what lets
    fuse_decode_attention_pass match the per-tick attention chain against
    the fixed-shape caches."""
    return layers.data(name=name, shape=shape, dtype=dtype,
                       append_batch_size=False)


def _decode_feeds(S, NLB):
    """The paged decode tick's feeds, declared here and nowhere else (the
    mixed ticks lead with them, in this order: a bound step lays its feeds
    out in declaration order and the decode step shares the leading span of
    the mixed step's buffer) -> (tok, pos, btab, wblock, woff, from_last)."""
    return (_feed("tick_tok", [S, 1]), _feed("tick_pos", [S, 1, 1], "float32"),
            _feed("tick_btab", [S, NLB]), _feed("tick_wblock", [S]),
            _feed("tick_woff", [S]), _feed("tick_from_last", [S, 1]))


def _lane_feeds(L, C, NLB, block_size):
    """The prefill lanes' feeds, after `_decode_feeds` -> (ltok, lpos, lbtab,
    lwblocks, lrows, llast)."""
    return (_feed("lane_tok", [L, C]), _feed("lane_pos", [L, 1, 1], "float32"),
            _feed("lane_btab", [L, NLB]),
            _feed("lane_wblocks", [L * C // block_size]),
            _feed("lane_rows", [L]), _feed("lane_last", [L]))


def _window_decode_feeds(S, NLB):
    """The decode rows' feeds of the WINDOW pool (a spec with window layers;
    declared right behind `_decode_feeds`, ahead of every lane feed) ->
    (wbtab, wwblock): a slot's second table, mapped only where a position
    of the block is still inside the window, and the window-pool block its
    row is written to."""
    return _feed("tick_wbtab", [S, NLB]), _feed("tick_wwblock", [S])


def _window_lane_feeds(L, C, NLB, block_size):
    """The lanes' feeds of the window pool -> (lwbtab, lwwblocks)."""
    return (_feed("lane_wbtab", [L, NLB]),
            _feed("lane_wwblocks", [L * C // block_size]))


def _slot_cache_var(name, shape, dtype="float32"):
    """Persistable zero-initialized cache variable (main + startup blocks,
    the optimizer-accumulator idiom): the serving engine's KV caches live
    in the Scope across ticks and ride the executor's donated read-write
    state path — updated in place on device, never re-staged."""
    from ..framework.program import (default_main_program,
                                     default_startup_program)
    mb = default_main_program().global_block()
    if name in mb.vars:
        return mb.vars[name]
    var = mb.create_var(name=name, shape=list(shape), dtype=dtype,
                        persistable=True)
    var.stop_gradient = True
    sb = default_startup_program().global_block()
    sv = sb.create_var(name=name, shape=list(shape), dtype=dtype,
                       persistable=True)
    sb.append_op("fill_constant", outputs={"Out": [sv.name]},
                 attrs={"shape": list(shape), "value": 0.0, "dtype": dtype})
    return var


class _LastIds:
    """The ids a tick's `n_slots` decode rows sampled, kept on the device for
    the next tick: `{cache_prefix}_last_ids` [S,1] int64, persistable, one
    name and one shape in every tick program of an engine, so it rides their
    donated read-write state like the caches. `feed(tok, from_last)` is the
    token each decode row consumes: the one the last tick sampled for its
    slot where `from_last` [S,1] is set (the host has not read that tick's
    ids yet: serving/engine.py reads them a launch late), else the host's
    `tok`. `keep(next_ids)` leaves this tick's first S ids for the next; the
    fetch the host reads stays an output of its own."""

    def __init__(self, cache_prefix, n_slots):
        self.S = n_slots
        self.var = _slot_cache_var(f"{cache_prefix}_last_ids", [n_slots, 1],
                                   dtype="int64")

    def feed(self, tok, from_last):
        from ..layer_helper import LayerHelper
        helper = LayerHelper("where")
        out = helper.create_tmp_variable(dtype="int64", shape=[self.S, 1],
                                         stop_gradient=True)
        late = layers.greater_than(from_last,
                                   layers.fill_constant([1], "int64", 0))
        helper.append_op(type="where",
                         inputs={"Condition": [late], "X": [self.var],
                                 "Y": [tok]}, outputs={"Out": [out]})
        return out

    def keep(self, next_ids):
        if next_ids.shape[0] != self.S:
            next_ids = layers.slice(next_ids, axes=[0], starts=[0],
                                    ends=[self.S])
        layers.assign(next_ids, output=self.var)


class _SlotCache:
    """The slot engine's KV cache: per layer a persistable k and v
    [S,1,nh,T,dh] (`{cache_prefix}_{k,v}{i}`), one row of T = max_len
    positions per slot, each slot at its OWN position. A forward writes a
    window of G rows per slot at the slot's base position — ONE
    `cache_write(batch_axis=0)` whose New spans G rows on the T axis
    (callers gate a window on pos+G <= T: dynamic_update_slice CLAMPS an
    overhanging start, which would silently relocate it) — and attends with
    the G query rows riding the query-row axis of `_masked_attention`, so
    the fused kernel reads the cache ONCE for all G positions. Nothing is
    reset on slot reuse: positions beyond a slot's own are masked, and rows
    are overwritten before they are exposed. Declares its variables when
    made, before the embedding (the startup program's order); `open_window`
    follows the embedding."""

    def __init__(self, cache_prefix, n_slots, max_len, num_heads, d_head,
                 num_layers, dropout):
        self.T, self.num_heads, self.d_head = max_len, num_heads, d_head
        self.dropout = dropout
        self.rows = {
            f"{s}{i}": _slot_cache_var(
                f"{cache_prefix}_{s}{i}",
                [n_slots, 1, num_heads, max_len, d_head])
            for i in range(num_layers) for s in "kv"}
        self.names = [v.name for v in self.rows.values()]

    def open_window(self, pos, posg=None):
        """Each slot writes and attends from its `pos` [S,1,1]; `posg`
        [S,G,1] the window's positions when G > 1."""
        self.pos = pos
        self.G = 1 if posg is None else posg.shape[1]
        self.bias = (_causal_bias(pos, self.T, axes=[2, 3]) if posg is None
                     else _causal_bias(posg, self.T, axes=[1, 2]))

    def _heads(self, x):
        """[S,G,H] → [S,1,nh,G,dh]: the window along the cache's T axis (one
        row needs no transpose: the decode tick's plain reshape)."""
        nh, dh, G = self.num_heads, self.d_head, self.G
        if G == 1:
            return layers.reshape(x, shape=[0, 1, nh, 1, dh])
        return layers.unsqueeze(
            layers.transpose(layers.reshape(x, shape=[0, G, nh, dh]),
                             perm=[0, 2, 1, 3]), axes=[1])

    def attend(self, i, q, kn, vn):
        kc, vc = (layers.cache_write(self.rows[f"{s}{i}"], self._heads(new),
                                     self.pos, axis=3, batch_axis=0,
                                     out=self.rows[f"{s}{i}"])
                  for s, new in (("k", kn), ("v", vn)))
        ctx5 = _masked_attention(self._heads(q), kc, vc, self.bias,
                                 self.d_head)                # [S,1,nh,G,dh]
        if self.G > 1:
            ctx5 = layers.transpose(ctx5, perm=[0, 1, 3, 2, 4])
        ctx = layers.reshape(ctx5, shape=[0, self.G,
                                          self.num_heads * self.d_head])
        return _infer_scale(ctx, self.dropout)


def transformer_lm_decode_tick(n_slots, vocab=32000, max_len=64,
                               d_model=512, d_inner=2048, num_heads=8,
                               num_layers=6, dropout=0.0, packed=False,
                               cache_prefix="srv", param_prefix="",
                               emit_logp=False):
    """ONE decode tick over a slot-indexed KV cache (`_SlotCache`) — the
    continuous-batching serving engine's compiled step (serving/engine.py).

    Where transformer_lm_generate scans max_gen positions with every
    sequence at the SAME position, this is a single-step program whose
    state is per-slot: `tick_pos` is PER-SLOT (one slot mid-prompt, one 30
    tokens into generation). One compiled program serves every mixture of
    request phases, which is what lets the scheduler admit a new request
    into the in-flight batch without recompiling or padding to a static
    batch.

    Inputs (all fed per tick): `tick_tok` [S,1] int64 (the token each
    slot consumes: next prompt token while prefilling, else the slot's
    previously sampled token), `tick_pos` [S,1,1] float32 (the position
    being written). Weights are shared BY NAME with transformer_lm — train
    first (or load), then build this in its own program and run it in the
    same scope; pass the SAME dropout/packed the train graph used
    (inference (1-p) corrections applied, as in transformer_lm_generate).

    Returns (next_ids [S,1] int64, cache_names list): argmax of the tick
    logits per slot, and the persistable cache variable names.

    param_prefix namespaces EVERY weight name (tok_emb, l{i}_*, lm_head)
    — the speculative DRAFT model is this same builder at param_prefix=
    "draft_" with its own cache_prefix, sharing the engine scope without
    colliding with the target weights (serving/speculative.py). With
    emit_logp=True the tick also returns the full log-softmax logits
    [S,1,V] — the draft-side distribution rejection sampling needs.
    """
    S, T = n_slots, max_len
    tok = _feed("tick_tok", [S, 1])
    pos = _feed("tick_pos", [S, 1, 1], "float32")
    from_last = _feed("tick_from_last", [S, 1])
    cache = _SlotCache(cache_prefix, S, T, num_heads, d_model // num_heads,
                       num_layers, 0.0 if packed else dropout)
    last = _LastIds(cache_prefix, S)
    x = _gen_embed_step(last.feed(tok, from_last), pos,
                        f"{param_prefix}tok_emb", vocab, d_model,
                        positional_encoding_table(T, d_model), dropout)
    cache.open_window(pos)
    x = _lm_decoder(x, cache.attend, num_layers, d_model, d_inner, dropout,
                    param_prefix=param_prefix)
    _, next_ids, logp = _lm_head(x, vocab, f"{param_prefix}lm_head",
                                 logp=emit_logp)
    last.keep(next_ids)
    if emit_logp:
        return next_ids, cache.names, logp
    return next_ids, cache.names


def transformer_lm_spec_verify_tick(n_slots, gamma, vocab=32000, max_len=64,
                                    d_model=512, d_inner=2048, num_heads=8,
                                    num_layers=6, dropout=0.0, packed=False,
                                    cache_prefix="srv", param_prefix=""):
    """ONE speculative VERIFY forward over the slot-indexed KV cache: score
    G = γ+1 positions per slot — the slot's committed next token followed
    by the draft model's γ proposals (or teacher-forced prompt tokens
    mid-prefill) — through the same fused decode-attention path as
    `transformer_lm_decode_tick`, writing all G KV rows into the SAME
    per-slot caches (shared by `cache_prefix` name with the plain tick's
    program in one scope). The serving engine commits the accepted prefix
    by advancing `fed` and leaves the rejected tail rows stale — masked by
    every later forward's position bias until overwritten, exactly the
    slot-reuse garbage contract the plain tick already lives with.

    Inputs (fed per round): `spec_tok` [S,G] int64, `spec_pos` [S,1,1]
    float32 (base position; rows land at pos..pos+γ — the engine gates
    participation on pos+G <= max_len).

    Returns (ids [S,G] int64, logp [S,G,V], cache_names): per-position
    argmax (greedy acceptance + bonus token) and full log-probs (rejection
    sampling against the draft's distribution)."""
    S, T, G = n_slots, max_len, gamma + 1
    tok = _feed("spec_tok", [S, G])
    pos = _feed("spec_pos", [S, 1, 1], "float32")
    cache = _SlotCache(cache_prefix, S, T, num_heads, d_model // num_heads,
                       num_layers, 0.0 if packed else dropout)
    posg = _window_positions(pos, G)                  # [S,G,1]
    x = _gen_embed_step(tok, posg, f"{param_prefix}tok_emb", vocab, d_model,
                        positional_encoding_table(T, d_model), dropout)
    cache.open_window(pos, posg)
    x = _lm_decoder(x, cache.attend, num_layers, d_model, d_inner, dropout,
                    param_prefix=param_prefix)
    _, ids, logp = _lm_head(x, vocab, f"{param_prefix}lm_head", logp=True)
    return ids, logp, cache.names


class _PagedCache:
    """The paged engine's KV cache (serving/kv_pager.py): per layer one
    device-resident POOL for k and one for v, [n_blocks, nh, block_size,
    dh] persistable variables `{cache_prefix}_{k,v}{i}` (declared
    lane-dense where a block's rows pack 128 lanes: `pool_block_shape`;
    axis 0 is the physical block either way). kv_quant=True: int8 payload
    pools plus f32 per-row scale pools (`..._sc`, [n_blocks, nh,
    block_size, 1]); a write then quantizes on the way in (symmetric
    amax/127 over each dh row) and the read dequantizes with one
    cast+multiply that XLA fuses into the cache read.

    Each slot sees the cache through its BLOCK TABLE (`btab` [S,NLB]):
    logical block j of slot s lives in physical block btab[s, j]. A forward
    scatters its new rows in place (`wblock`/`woff` give each row's
    physical block and offset; the pools round-trip through donated state)
    and THEN reads the WRITTEN pools through the table
    (`paged_decode_attention`, fusion/paged_attention.py), row g of a slot
    attending positions 0..pos+g: the new rows are attended within the same
    tick. Nothing of pool shape is computed.

    Physical block 0 is the pool's reserved NULL block: idle slots are
    steered to write there (tok/pos zeroed, btab all-zero) so one
    fixed-shape compiled tick serves any live/idle mix; a live block table
    never maps block 0, and the read attends no position beyond a slot's
    own, so null-block garbage is never attended. Prefix sharing needs no
    graph support: two rows of `btab` carry the SAME physical block id.

    `window` (a spec with sliding-window layers): those layers' pools are a
    SECOND pool of `n_blocks` blocks with its own null block, written and
    read through a second table and write block (`btab`, `wblock`; with
    lanes `lbtab`, `lwblocks`), the read bounded to the last `size`
    positions; the offsets and positions are the first pool's."""

    def __init__(self, cache_prefix, n_blocks, block_size, num_heads, d_head,
                 num_layers, btab, pos, wblock, woff, dropout,
                 kv_quant=False, kv_heads=None, dtype="float32",
                 cached_layers=None, window=None):
        """`kv_heads` (None: `num_heads`) key/value heads a pool holds;
        `dtype` the pools'; `cached_layers` the layers that have pools
        (None: all `num_layers`); `window` dict(layers, n_blocks, size,
        btab, wblock[, lbtab, lwblocks])."""
        from ..ops.tensor_ops import pool_block_shape
        self.num_heads, self.d_head, self.dropout = num_heads, d_head, dropout
        self.kv_heads = kv_heads or num_heads
        self.btab, self.pos, self.wblock, self.woff = btab, pos, wblock, woff
        self.window = window
        block = list(pool_block_shape(self.kv_heads, block_size, d_head))
        self.pools, self.scale_pools = {}, {}
        for i in (range(num_layers) if cached_layers is None
                  else cached_layers):
            nb = window["n_blocks"] if self._windowed(i) else n_blocks
            for s in "kv":
                self.pools[f"{s}{i}"] = _slot_cache_var(
                    f"{cache_prefix}_{s}{i}", [nb] + block,
                    dtype="int8" if kv_quant else dtype)
                if kv_quant:
                    self.scale_pools[f"{s}{i}"] = _slot_cache_var(
                        f"{cache_prefix}_{s}{i}_sc",
                        [n_blocks, num_heads, block_size, 1])
        self.names = [v.name for v in (*self.pools.values(),
                                       *self.scale_pools.values())]

    def _windowed(self, i):
        return self.window is not None and i in self.window["layers"]

    def _tables(self, i):
        """(block table, write blocks, read kinds) of layer `i`'s pools."""
        if self._windowed(i):
            w = self.window
            return w["btab"], w["wblock"], {"window": w["size"]}
        return self.btab, self.wblock, {}

    def _write(self, key, new, wblock, **lanes):
        """Scatter rows `new` into pool `key` → (pool, scale pool | None)."""
        pool = self.pools[key]
        new3 = layers.reshape(new, shape=[int(np.prod(wblock.shape)),
                                          self.kv_heads, self.d_head])
        if self.scale_pools:
            spool = self.scale_pools[key]
            return layers.paged_cache_write_quant(
                pool, spool, new3, wblock, self.woff, out=pool,
                scales_out=spool)
        return layers.paged_cache_write(pool, new3, wblock, self.woff,
                                        out=pool, **lanes), None

    def attend(self, i, q, kn, vn):
        btab, wblock, kinds = self._tables(i)
        (k, k_scale), (v, v_scale) = (self._write(f"k{i}", kn, wblock),
                                      self._write(f"v{i}", vn, wblock))
        ctx = layers.paged_decode_attention(
            q, k, v, btab, self.pos, self.num_heads,
            scale=float(self.d_head) ** -0.5, k_scale=k_scale,
            v_scale=v_scale, **kinds)
        return _infer_scale(ctx, self.dropout)


def transformer_lm_paged_decode_tick(n_slots, n_blocks, block_size,
                                     blocks_per_req, vocab=32000,
                                     d_model=512, d_inner=2048, num_heads=8,
                                     num_layers=6, dropout=0.0, packed=False,
                                     cache_prefix="pgd", topk_k=0,
                                     kv_quant=False, model=None,
                                     n_snapshots=0, n_window_blocks=0):
    """ONE decode tick over a PAGED KV cache (`_PagedCache`) — the
    block-table variant of `transformer_lm_decode_tick`, whose slots own a
    full [1,nh,max_len,dh] row each; here a request's span is T =
    blocks_per_req * block_size positions of a shared pool.

    Weights are shared BY NAME with transformer_lm — same contract as the
    slot tick; pass the SAME dropout/packed the train graph used.

    Inputs (fed per tick): `tick_tok` [S,1] int64, `tick_pos` [S,1,1]
    float32 (the LOGICAL position being written), `tick_btab` [S,NLB]
    int64, `tick_wblock` [S] int64, `tick_woff` [S] int64.

    Returns (next_ids [S,1] int64, cache_names); with topk_k > 0 also
    the per-slot top-k of the tick's log-probs — (topk_logp [S,1,k],
    topk_ids [S,1,k]) — the host-side scoring surface `paged_beam_search`
    ranks hypotheses with. kv_quant=True stores the pools as int8, so the
    resident pool bytes drop ~4x and the pager hands the freed bytes back
    as extra admitted blocks."""
    if model is not None and not model.is_classic:
        # `model` (a DecoderSpec) describes the block; the dims above are
        # the classic spec's and are not read
        return _kinds_paged_tick(model, n_slots, n_blocks, block_size,
                                 blocks_per_req, cache_prefix,
                                 n_snapshots=n_snapshots,
                                 n_window_blocks=n_window_blocks)
    S, NLB = n_slots, blocks_per_req
    tok, pos, btab, wblock, woff, from_last = _decode_feeds(S, NLB)
    cache = _PagedCache(
        cache_prefix, n_blocks, block_size, num_heads, d_model // num_heads,
        num_layers, btab, pos, wblock, woff, 0.0 if packed else dropout,
        kv_quant)
    last = _LastIds(cache_prefix, S)
    x = _gen_embed_step(
        last.feed(tok, from_last), pos, "tok_emb", vocab, d_model,
        positional_encoding_table(NLB * block_size, d_model), dropout)
    x = _lm_decoder(x, cache.attend, num_layers, d_model, d_inner, dropout)
    _, next_ids, logp = _lm_head(x, vocab, logp=bool(topk_k))
    last.keep(next_ids)
    if topk_k:
        return (next_ids, cache.names, *layers.topk(logp, k=topk_k))
    return next_ids, cache.names


def _split_rows(t, S, L, C):
    """A mixed tick's [S + L*C, 1, H] → (decode rows [S,1,H], lane rows
    [L,C,H])."""
    return (layers.slice(t, axes=[0], starts=[0], ends=[S]),
            layers.reshape(
                layers.slice(t, axes=[0], starts=[S], ends=[S + L * C]),
                shape=[L, C, t.shape[-1]]))


class _PagedLaneCache(_PagedCache):
    """`_PagedCache` under a tick of S decode rows AND L prefill lanes of
    C = chunk consecutive prompt tokens of ONE request each, from a block
    boundary (C a whole number of blocks). A layer's rows arrive as one
    [S + L*C, 1, H] batch (`rows_of` splits it); only the cache differs by
    row kind:

    - a decode row writes its K/V row and reads its cache as in
      `_PagedCache` (one query position);
    - a lane's chunk lands as whole blocks, by the same write op so that a
      pool keeps one writer (`lane_wblocks` names each block's physical
      home, and 0, the null block, for the blocks a short chunk leaves
      unused), and its C rows then attend causally over the request's
      table (`lane_btab`): the shared prefix's blocks, earlier chunks and
      the chunk itself, row c at position `lane_pos` + c, of which the
      first `lane_rows` are real.

    A lane with `lane_rows` 0 is idle (everything zero: it writes the null
    block and fetches nothing); the rows of a short chunk beyond
    `lane_rows` write rows beyond the request's position, which nothing
    attends before a later write replaces them."""

    def __init__(self, *paged, n_slots, n_lanes, chunk, lbtab, lpos,
                 lwblocks, lrows, **kinds):
        super().__init__(*paged, **kinds)
        self.S, self.L, self.C = n_slots, n_lanes, chunk
        self.lbtab, self.lpos = lbtab, lpos
        self.lwblocks, self.lrows = lwblocks, lrows

    def rows_of(self, t):
        """[S + L*C, 1, H] → (decode rows [S,1,H], lane rows [L,C,H])."""
        return _split_rows(t, self.S, self.L, self.C)

    def attend(self, i, q, kn, vn):
        (qd, ql), (kd, kl), (vd, vl) = (self.rows_of(q), self.rows_of(kn),
                                        self.rows_of(vn))
        btab, wblock, kinds = self._tables(i)
        lbtab, lwblocks = ((self.window["lbtab"], self.window["lwblocks"])
                           if self._windowed(i)
                           else (self.lbtab, self.lwblocks))
        k, v = (self._write(key, rows, wblock, chunk=lanes,
                            chunk_block_ids=lwblocks)[0]
                for key, rows, lanes in ((f"k{i}", kd, kl),
                                         (f"v{i}", vd, vl)))
        scale = float(self.d_head) ** -0.5
        ctx_d = layers.paged_decode_attention(
            qd, k, v, btab, self.pos, self.num_heads, scale=scale, **kinds)
        ctx_l = layers.paged_decode_attention(
            ql, k, v, lbtab, self.lpos, self.num_heads, scale=scale,
            n_rows=self.lrows, **kinds)
        ctx = layers.concat(
            [ctx_d, layers.reshape(ctx_l, shape=[self.L * self.C, 1,
                                                 q.shape[-1]])], axis=0)
        return _infer_scale(ctx, self.dropout)


class _LatentPagedCache:
    """The paged cache of latent attention: per layer ONE pool
    `{cache_prefix}_c{i}` [n_blocks, 1, block_size, row_lanes] in the
    spec's dtype, a row a position: the normalised `c_kv`, the rotated
    `k_pe`, zeros up to `LatentSpec.row_lanes` (the pool is declared as it
    is stored: a minor dimension of whole 128-lane rows; the one-head form
    of `_PagedCache`'s pool, so `paged_cache_write` writes it, rows and
    whole blocks, as it writes a K pool). Block tables, the null block and
    prefix sharing are `_PagedCache`'s; with `lanes` (the mixed tick's
    n_slots, n_lanes, chunk, lbtab, lpos, lwblocks, lrows) the rows split
    as in `_PagedLaneCache`. `attend(i, q_rows, row)` writes the new rows
    and reads the written pool on the latent itself
    (`layers.latent_paged_attention`)."""

    def __init__(self, cache_prefix, n_blocks, block_size, spec, btab, pos,
                 wblock, woff, lanes=None):
        lat = spec.latent
        self.num_heads, self.lat = spec.num_heads, lat
        self.btab, self.pos, self.wblock, self.woff = btab, pos, wblock, woff
        self.lanes = lanes
        # a pool a LATENT layer (every layer, unless the spec gives kinds)
        self.pools = {i: _slot_cache_var(
            f"{cache_prefix}_c{i}", [n_blocks, 1, block_size, lat.row_lanes],
            dtype=spec.dtype) for i in spec.attention_layers}
        # ... and with an indexer a SECOND pool beside it, a pooled index
        # key a group of `kpool` positions, under the same table
        self.indexer, self.index_pools = spec.indexer, {}
        if spec.indexer is not None:
            ix = spec.indexer
            if block_size % ix.kpool or (lanes and lanes["chunk"] % ix.kpool):
                raise ValueError(
                    f"a block of {block_size} positions (and a lane's chunk) "
                    f"holds whole groups of index_kpool {ix.kpool}")
            self.index_pools = {i: _slot_cache_var(
                f"{cache_prefix}_ci{i}",
                [n_blocks, 1, block_size // ix.kpool, ix.head_dim],
                dtype=spec.dtype) for i in spec.attention_layers}
        self.names = [v.name for v in (*self.pools.values(),
                                       *self.index_pools.values())]

    def _read(self, q, pool, btab, pos, n_rows=None):
        return layers.latent_paged_attention(
            q, pool, btab, pos, self.num_heads, self.lat.kv_lora_rank,
            self.lat.softmax_scale, n_rows=n_rows)

    def _sparse_read(self, i, q, pool, index):
        """The read over the rows the indexer selects, decode rows and lane
        rows as ONE batch (the selection is a row's own)."""
        return layers.sparse_latent_attention(
            q, pool, self.index_pools[i], index["qi"], index["ki"],
            index["wi"], index["positions"], index["table"], self.btab,
            self.wblock, self.woff, self.num_heads, self.lat.kv_lora_rank,
            self.lat.softmax_scale, self.indexer, lanes=self.lanes)

    def attend(self, i, q, row, index=None):
        pool, w = self.pools[i], self.lat.row_lanes
        if self.lanes is None:
            pool = layers.paged_cache_write(
                pool, layers.reshape(row, shape=[-1, 1, w]), self.wblock,
                self.woff, out=pool)
            if index is not None:
                return self._sparse_read(i, q, pool, index)
            return self._read(q, pool, self.btab, self.pos)
        ln = self.lanes
        split = functools.partial(_split_rows, S=ln["n_slots"],
                                  L=ln["n_lanes"], C=ln["chunk"])
        (qd, ql), (rd, rl) = split(q), split(row)
        pool = layers.paged_cache_write(
            pool, layers.reshape(rd, shape=[-1, 1, w]), self.wblock,
            self.woff, out=pool, chunk=rl, chunk_block_ids=ln["lwblocks"])
        if index is not None:
            return self._sparse_read(i, q, pool, index)
        ctx_d = self._read(qd, pool, self.btab, self.pos)
        ctx_l = self._read(ql, pool, ln["lbtab"], ln["lpos"], ln["lrows"])
        return layers.concat(
            [ctx_d, layers.reshape(ctx_l, shape=[-1, 1, ctx_d.shape[-1]])],
            axis=0)


class _ConvState:
    """The conv layers' state beside the paged cache (fusion/short_conv.py
    has the scheme): `{cache_prefix}_conv_slot` [n_slots, n_conv, K-1, D],
    a slot's last rows, and `{cache_prefix}_conv_block` [n_blocks, n_conv,
    K-1, D], the state after each pool block's last position, both
    persistable, in the spec's dtype, zero at start-up. `layer(u, taps)` is
    one conv layer over the tick's rows; `commit()`, after the last layer,
    writes what the layers left into the two arrays, in place. `lanes` as
    `_LatentPagedCache` takes them, plus `lslot` (each lane's slot) and
    `block_size`."""

    def __init__(self, cache_prefix, spec, n_slots, n_blocks, wblock,
                 lanes=None):
        shape = [len(spec.conv_layers), spec.conv.state_rows, spec.d_model]
        self.slot = _slot_cache_var(f"{cache_prefix}_conv_slot",
                                    [n_slots] + shape, dtype=spec.dtype)
        self.block = _slot_cache_var(f"{cache_prefix}_conv_block",
                                     [n_blocks] + shape, dtype=spec.dtype)
        self.n_slots, self.wblock, self.lanes = n_slots, wblock, lanes
        self.decode, self.snaps, self.last = [], [], []

    def layer(self, u, taps):
        ln = self.lanes
        out, new_d, snaps, last = layers.short_conv(
            u, taps, self.slot, len(self.decode), self.n_slots,
            lanes=ln and dict(ln, block_state=self.block))
        self.decode.append(new_d)
        self.snaps.append(snaps)
        self.last.append(last)
        return out

    def commit(self):
        ln = self.lanes
        layers.conv_state_commit(
            self.slot, self.decode, self.wblock,
            lanes=ln and dict(ln, block_state=self.block, snaps=self.snaps,
                              last=self.last))


class _RecurrentState:
    """The state-space or kda layers' state beside the paged cache
    (fusion/ssm.py has the scheme; the shapes are the spec's:
    `DecoderSpec.recurrent`): per layer j `{cache_prefix}_{kind}_h{j}`
    [n_slots, H, P, N] float32 (kind "ssm": Mamba-2's h; "kda": the delta
    rule's S [n_slots, H, D, D]) and `{cache_prefix}_{kind}_conv{j}`
    [n_slots, K-1, conv_dim] in the spec's dtype, a slot's state, and the
    snapshot POOL `{cache_prefix}_{kind}_snap_h{j}` / `_snap_conv{j}`
    [n_snapshots, ..], all persistable, zero at start-up, each written in
    place by its layer's one scan op (`ssm_scan` | `kda_scan`). `lanes`: the
    mixed tick's lpos and lrows plus the lanes' own feeds (`lane_slot`,
    `lane_snap_src`, `lane_snap_dst`, `lane_snap_rows`: serving/kv_pager.py
    fills them)."""

    def __init__(self, cache_prefix, spec, n_slots, n_snapshots, lanes=None):
        rec = spec.recurrent
        kind = "ssm" if spec.ssm is not None else "kda"
        h = list(rec.state_shape)
        conv = [rec.state_rows, rec.conv_dim]
        self.rec, self.lanes = rec, lanes
        self.scan = layers.ssm_scan if kind == "ssm" else layers.kda_scan
        self.states = [dict(
            slot_h=_slot_cache_var(f"{cache_prefix}_{kind}_h{j}",
                                   [n_slots] + h),
            slot_conv=_slot_cache_var(f"{cache_prefix}_{kind}_conv{j}",
                                      [n_slots] + conv, dtype=spec.dtype),
            snap_h=_slot_cache_var(f"{cache_prefix}_{kind}_snap_h{j}",
                                   [n_snapshots] + h),
            snap_conv=_slot_cache_var(f"{cache_prefix}_{kind}_snap_conv{j}",
                                      [n_snapshots] + conv, dtype=spec.dtype))
            for j in range(len(spec.recurrent_layers))]
        self.n_slots, self.built, self.live = n_slots, 0, None

    def layer(self, u, gates, params, live):
        """The next layer's scan: `u` the convolution's input, `gates` what
        else the scan reads of the row (ssm: (dt,); kda: (f, b))."""
        state = self.states[self.built]
        self.built += 1
        if self.live is None:           # the decode rows' part, cut once
            self.live = layers.slice(live, axes=[0], starts=[0],
                                     ends=[self.n_slots])
        return self.scan(u, *gates, params, state, self.live, self.rec,
                         lanes=self.lanes)


def _embed_rows(tok, spec, name="tok_emb"):
    """[N,1] ids -> [N,1,H] in the spec's dtype, unscaled, no positions
    (the rotary kinds put them inside attention)."""
    return layers.embedding(layers.unsqueeze(tok, axes=[2]),
                            size=[spec.vocab, spec.d_model],
                            param_attr=ParamAttr(name=name),
                            dtype=spec.dtype)


def _live_rows(wblock, lrows=None, chunk=0):
    """1.0 for the rows of a tick that are real: a decode row that writes a
    block of its own (an idle slot writes the null block, 0), a lane row
    below its lane's `lane_rows`."""
    zero = layers.fill_constant([1], "int64", 0)
    live = layers.cast(layers.greater_than(wblock, zero), "float32")
    if lrows is None:
        return live
    L = lrows.shape[0]
    in_chunk = layers.less_than(
        layers.assign(np.tile(np.arange(chunk, dtype="int64"), (L, 1))),
        layers.reshape(lrows, shape=[L, 1]))
    return layers.concat(
        [live, layers.reshape(layers.cast(in_chunk, "float32"),
                              shape=[L * chunk])], axis=0)


def _kinds_paged_tick(model, n_slots, n_blocks, block_size, blocks_per_req,
                      cache_prefix, lanes=None, n_snapshots=0,
                      n_window_blocks=0):
    """The paged decode tick (`lanes` None) or mixed tick of a non-classic
    `DecoderSpec`: the classic builders' feeds (`_decode_feeds`,
    `_lane_feeds`, and `lane_slot` where a lane leaves a state in its
    slot); rows embedded without positions; `_LatentPagedCache`, or
    `_PagedCache` / `_PagedLaneCache` over the attention layers' key/value
    heads, beside `_ConvState` or `_RecurrentState` (`n_snapshots` entries
    in its snapshot pool; kda layers' stands beside `_LatentPagedCache`);
    the blocks through `_lm_decoder`; a float32 head without
    bias, the embedding itself where the spec ties it.
    Returns (next_ids followed by the routed layers' counts, the K/V
    pools' names)."""
    if model.residual == "post" or model.positions == "sinusoid":
        raise NotImplementedError(
            "the paged ticks build the classic spec, latent attention or "
            "rotary grouped attention beside short convolutions, each in a "
            f"pre-norm block; norm {model.norm!r}, residual "
            f"{model.residual!r}, positions {model.positions!r} has no "
            "cache seam yet")
    S, NLB = n_slots, blocks_per_req
    tok, pos, btab, wblock, woff, from_last = _decode_feeds(S, NLB)
    window = None
    if model.window_layers:
        wbtab, wwblock = _window_decode_feeds(S, NLB)
        window = dict(layers=model.window_layers, n_blocks=n_window_blocks,
                      size=model.window, btab=wbtab, wblock=wwblock)
    last = _LastIds(cache_prefix, S)
    tok = last.feed(tok, from_last)
    toks, positions, lane_feeds, lrows = tok, pos, None, None
    if lanes is not None:
        L, C = lanes
        ltok, lpos, lbtab, lwblocks, lrows, llast = _lane_feeds(
            L, C, NLB, block_size)
        if window is not None:
            window["lbtab"], window["lwblocks"] = _window_lane_feeds(
                L, C, NLB, block_size)
        lane_feeds = dict(n_slots=S, n_lanes=L, chunk=C, lbtab=lbtab,
                          lpos=lpos, lwblocks=lwblocks, lrows=lrows)
        toks = layers.concat([tok, layers.reshape(ltok, shape=[L * C, 1])],
                             axis=0)
        positions = layers.concat(
            [pos, layers.reshape(_window_positions(lpos, C),
                                 shape=[L * C, 1, 1])], axis=0)
    conv = ssm = None
    if model.attention == "latent":
        cache = _LatentPagedCache(cache_prefix, n_blocks, block_size, model,
                                  btab, pos, wblock, woff, lane_feeds)
    else:
        paged = (cache_prefix, n_blocks, block_size, model.num_heads,
                 model.d_head, model.num_layers, btab, pos, wblock, woff, 0.0)
        kinds = dict(kv_heads=model.kv_heads, dtype=model.dtype,
                     cached_layers=model.attention_layers, window=window)
        cache = (_PagedCache(*paged, **kinds) if lanes is None
                 else _PagedLaneCache(*paged, **lane_feeds, **kinds))
        if model.conv is not None:
            conv = _ConvState(
                cache_prefix, model, S, n_blocks, wblock,
                lanes and dict(lane_feeds, block_size=block_size,
                               lslot=_feed("lane_slot", [lanes[0]])))
    if model.recurrent is not None:
        ssm = _RecurrentState(
            cache_prefix, model, S, n_snapshots,
            lanes and dict(
                lpos=lpos, lrows=lrows, chunk=lanes[1],
                lslot=_feed("lane_slot", [lanes[0]]),
                **{n: _feed("lane_" + n, [lanes[0]])
                   for n in ("snap_src", "snap_dst", "snap_rows")}))
    rows = _TickRows(model, positions,
                     _live_rows(wblock, lrows, lanes[1] if lanes else 0),
                     NLB * block_size, conv, ssm)
    x = _lm_decoder(
        _scaled(_embed_rows(toks, model), model.multipliers.embedding),
        cache.attend, model.num_layers, model.d_model, model.d_inner, 0.0,
        spec=model, rows=rows)
    if conv is not None:
        conv.commit()
    if lanes is not None:
        xd, xl = _split_rows(x, S, L, C)
        x = layers.concat(
            [xd, layers.reshape(
                layers.gather(layers.reshape(xl, shape=[L * C,
                                                        model.d_model]),
                              llast), shape=[L, 1, model.d_model])], axis=0)
    if model.tied_head:
        from ..framework.program import default_main_program
        table = default_main_program().global_block().vars["tok_emb"]
        next_ids = layers.argmax(
            layers.matmul(x, table, transpose_y=True, out_dtype="float32"),
            axis=2)
    else:
        logits, next_ids, _ = _lm_head(x, model.vocab, bias=False,
                                       out_dtype="float32", ids=False)
        next_ids = layers.argmax(
            _scaled(logits, model.multipliers.lm_head), axis=2)
    last.keep(next_ids)
    return rows.with_counts(next_ids), cache.names


def transformer_lm_paged_mixed_tick(n_slots, n_lanes, chunk, n_blocks,
                                    block_size, blocks_per_req, vocab=32000,
                                    d_model=512, d_inner=2048, num_heads=8,
                                    num_layers=6, dropout=0.0, packed=False,
                                    cache_prefix="pgd", model=None,
                                    n_snapshots=0, n_window_blocks=0):
    """ONE tick of decode rows AND prefill lanes over the paged KV pools
    (`_PagedLaneCache`): `transformer_lm_paged_decode_tick`'s S decode rows
    (same feeds, same pools and weights by name) plus L = `n_lanes` lanes
    of C = `chunk` prompt tokens. The S + L*C rows go through every weight
    ONCE (the matmuls see one [S + L*C, H] batch).

    The vocabulary head runs on S + L rows: the decode rows and each lane's
    LAST real row (`lane_last`, its index among the L*C lane rows), whose
    argmax is the request's first sampled token when the chunk ends its
    prompt.

    Inputs beyond the decode tick's: `lane_tok` [L,C] int64, `lane_pos`
    [L,1,1] float32, `lane_btab` [L,NLB] int64, `lane_wblocks` [L*C/BS]
    int64, `lane_rows` [L] int64, `lane_last` [L] int64.

    Returns (next_ids [S+L,1] int64: the decode rows' then the lanes',
    cache_names). It declares the decode tick's persistable variables and
    no other, so it needs no startup run where that tick's state exists."""
    S, L, C, BS, NLB = n_slots, n_lanes, chunk, block_size, blocks_per_req
    assert C % BS == 0, "a chunk is a whole number of blocks"
    if model is not None and not model.is_classic:
        return _kinds_paged_tick(model, S, n_blocks, BS, NLB, cache_prefix,
                                 lanes=(L, C), n_snapshots=n_snapshots,
                                 n_window_blocks=n_window_blocks)
    tok, pos, btab, wblock, woff, from_last = _decode_feeds(S, NLB)
    ltok, lpos, lbtab, lwblocks, lrows, llast = _lane_feeds(L, C, NLB, BS)
    last = _LastIds(cache_prefix, S)
    tok = last.feed(tok, from_last)
    cache = _PagedLaneCache(
        cache_prefix, n_blocks, BS, num_heads, d_model // num_heads,
        num_layers, btab, pos, wblock, woff, 0.0 if packed else dropout,
        n_slots=S, n_lanes=L, chunk=C, lbtab=lbtab, lpos=lpos,
        lwblocks=lwblocks, lrows=lrows)
    lposc = _window_positions(lpos, C)                    # [L,C,1]
    x = _gen_embed_step(
        layers.concat([tok, layers.reshape(ltok, shape=[L * C, 1])], axis=0),
        layers.concat([pos, layers.reshape(lposc, shape=[L * C, 1, 1])],
                      axis=0),
        "tok_emb", vocab, d_model,
        positional_encoding_table(NLB * BS, d_model), dropout)  # [N,1,H]
    x = _lm_decoder(x, cache.attend, num_layers, d_model, d_inner, dropout)
    xd, xl = cache.rows_of(x)
    heads = layers.concat(
        [xd, layers.reshape(
            layers.gather(layers.reshape(xl, shape=[L * C, d_model]), llast),
            shape=[L, 1, d_model])], axis=0)              # [S+L,1,H]
    _, next_ids, _ = _lm_head(heads, vocab)               # [S+L,1] int64
    last.keep(next_ids)
    return next_ids, cache.names


def transformer_lm_paged_spec_verify_tick(n_slots, gamma, n_blocks,
                                          block_size, blocks_per_req,
                                          vocab=32000, d_model=512,
                                          d_inner=2048, num_heads=8,
                                          num_layers=6, dropout=0.0,
                                          packed=False, cache_prefix="pgd",
                                          param_prefix="", kv_quant=False):
    """ONE speculative VERIFY forward over the PAGED KV pools — the
    block-table counterpart of `transformer_lm_spec_verify_tick`. Each
    slot scores G = γ+1 positions in one forward; the G new KV rows
    scatter into the slot's CURRENT blocks (`spec_wblock`/`spec_woff`
    [S,G]: per-position physical targets the engine derives from the
    block table at fed..fed+γ). Verify positions occupy the slot-tick
    layout the way beam forks do: rows of rejected positions stay in
    place, masked, until the pager's rollback detaches their
    fully-rejected blocks (`KVPager.rollback`) and later writes overwrite
    the partial boundary block. Idle slots steer every write to the
    reserved null block 0.

    Inputs (fed per round): `spec_tok` [S,G] int64, `spec_pos` [S,1,1]
    float32, `spec_btab` [S,NLB] int64, `spec_wblock` [S,G] int64,
    `spec_woff` [S,G] int64.

    Returns (ids [S,G] int64, logp [S,G,V], cache_names). kv_quant as in
    `transformer_lm_paged_decode_tick` (shares the SAME int8+scale pool
    variables by name)."""
    S, NLB, G = n_slots, blocks_per_req, gamma + 1
    tok = _feed("spec_tok", [S, G])
    pos = _feed("spec_pos", [S, 1, 1], "float32")
    cache = _PagedCache(
        cache_prefix, n_blocks, block_size, num_heads, d_model // num_heads,
        num_layers, _feed("spec_btab", [S, NLB]), pos,
        _feed("spec_wblock", [S, G]), _feed("spec_woff", [S, G]),
        0.0 if packed else dropout, kv_quant)
    x = _gen_embed_step(
        tok, _window_positions(pos, G), f"{param_prefix}tok_emb", vocab,
        d_model, positional_encoding_table(NLB * block_size, d_model),
        dropout)
    x = _lm_decoder(x, cache.attend, num_layers, d_model, d_inner, dropout,
                    param_prefix=param_prefix)
    _, ids, logp = _lm_head(x, vocab, f"{param_prefix}lm_head", logp=True)
    return ids, logp, cache.names


def _spec_lm(tokens, label, spec, max_len):
    """The training graph of a decoder whose block is a `DecoderSpec` of the
    window / grouped-query / routed-experts kind (`window_gqa_moe`): the
    embedding as it lies (no scale, no table: the positions are rotary,
    inside attention), `_decoder_block` a layer with the flash kernels as
    `attend` (grouped heads; the layer's window), the final norm, an untied
    head without a bias, and as the loss the mean cross-entropy plus
    `moe.aux_coef` times the routed layers' balance terms. Parameters are
    float32 under the names the serving ticks read; matmul operands
    bfloat16 (`use_bf16`)."""
    if spec.mixer != "kind":
        raise NotImplementedError(
            f"transformer_lm(model=spec): mixer {spec.mixer!r} has no "
            "training graph (the state-space scan carries no gradient); "
            "serve it through PagedKVEngine(model=spec)")
    if spec.kda is not None:
        raise NotImplementedError(
            "transformer_lm(model=spec): a 'kda' layer has no training graph "
            "(the delta-rule scan carries no gradient); serve it through "
            "PagedKVEngine(model=spec)")
    if spec.attention != "full" or spec.layer_kinds is not None \
            or spec.residual != "pre" or spec.positions != "rotary" \
            or spec.tied_head or spec.dropout or spec.packed:
        raise NotImplementedError(
            "transformer_lm(model=spec) trains the pre-norm block of "
            "rotary full-head attention (DecoderSpec.window_gqa_moe); "
            f"not {spec}")
    x = layers.embedding(
        input=tokens, size=[spec.vocab, spec.d_model],
        param_attr=ParamAttr(name="tok_emb",
                             initializer=NormalInitializer(0., 1.)))
    rows = _TickRows(spec, layers.assign(np.arange(max_len, dtype="int32")),
                     None, max_len, training=True)
    scale = float(spec.d_head) ** -0.5

    def attend(i, q, k, v):
        return layers.fused_attention(
            q, k, v, scale=scale, causal=True, num_heads=spec.num_heads,
            window=spec.window if spec.attention_kind(i) == "window" else 0)

    x = _lm_decoder(x, attend, spec.num_layers, spec.d_model, spec.d_inner,
                    0.0, is_test=False, spec=spec, rows=rows)
    logits, _, _ = _lm_head(x, spec.vocab, ids=False, bias=False,
                            out_dtype="float32")
    loss = layers.mean(layers.softmax_with_cross_entropy(
        logits, layers.unsqueeze(label, axes=[2])))
    if rows.aux and spec.moe.aux_coef:
        loss = layers.elementwise_add(loss, layers.scale(
            layers.sums(rows.aux), scale=float(spec.moe.aux_coef)))
    return loss, logits


def transformer_lm(tokens=None, label=None, vocab=32000, max_len=128,
                   d_model=512, d_inner=2048, num_heads=8, num_layers=6,
                   dropout=0.0, is_test=False, packed=False,
                   mean_loss=False, model=None):
    """Decoder-only causal LM — the flagship config used by
    __graft_entry__ (simplest shape that exercises dp/tp/sp sharding).

    model: a `DecoderSpec` in place of the six dims; a classic one builds
    what the dims build, op for op; `DecoderSpec.window_gqa_moe` builds the
    pre-norm block it describes (`_spec_lm`; every row is a full context:
    no padding, not packed).

    packed=True: each batch row holds MULTIPLE sequences back to back,
    described by a `segments` int32 input ([B, max_len]; 0 = padding,
    1..N = sequence index — see data.packing.pack_sequences). Attention is
    segment-masked through the flash kernel and the loss counts only
    non-pad tokens. This is the throughput idiom for ragged corpora: no
    compute wasted on padding (≙ the reference's LoD batches whose whole
    point is padding-free ragged training, lod_tensor.h:58)."""
    if model is not None and model.is_classic:
        (vocab, d_model, d_inner, num_heads, num_layers, dropout,
         packed), model = model.dims().values(), None
    if tokens is None:
        tokens = layers.data(name="tokens", shape=[max_len], dtype="int64",
                             lod_level=0 if packed else 1)
    if label is None:
        label = layers.data(name="targets", shape=[max_len], dtype="int64")
    if model is not None:
        return _spec_lm(tokens, label, model, max_len)
    segments = positions = None
    if packed:
        segments = layers.data(name="segments", shape=[max_len],
                               dtype="int32")
        positions = layers.data(name="positions", shape=[max_len],
                                dtype="int32")
    else:
        seqlen = layers.sequence.get_seqlen(tokens)
    x = _embed(tokens, vocab, d_model, max_len, "tok", positions=positions)
    if dropout:
        x = layers.dropout(x, dropout_prob=dropout, is_test=is_test)
    x = _lm_decoder(
        x, lambda i, q, k, v: _flash_attend(
            q, k, v, num_heads, 0.0 if packed else dropout, is_test,
            causal=True, segment_ids=segments),
        num_layers, d_model, d_inner, dropout, is_test)
    logits, _, _ = _lm_head(x, vocab, ids=False)
    label3 = layers.unsqueeze(label, axes=[2])
    token_loss = layers.softmax_with_cross_entropy(logits, label3)
    if packed:
        # a token trains iff it is non-pad AND its successor belongs to
        # the same segment (the last token of each packed sequence has no
        # valid next-token target)
        seg_next = layers.concat([
            layers.slice(segments, axes=[1], starts=[1], ends=[max_len]),
            layers.fill_constant_batch_size_like(segments, [-1, 1],
                                                 "int32", 0)], axis=1)
        nonpad = layers.greater_than(
            segments, layers.fill_constant([1], "int32", 0))
        same = layers.equal(segments, seg_next)
        mask = layers.elementwise_mul(layers.cast(nonpad, "float32"),
                                      layers.cast(same, "float32"))
    else:
        mask = layers.sequence_mask(seqlen, maxlen=max_len)
    mask = layers.unsqueeze(mask, axes=[2])
    masked = layers.elementwise_mul(token_loss, mask)
    if mean_loss:
        # mean over ALL positions instead of the mask-weighted sum/sum
        # quotient — identical for full-length sequences, and the MEAN
        # reduction form the explicit dp gradient pipeline requires
        # (grad_comm averages per-shard gradients; that equals the global
        # gradient only for a batch-mean loss — docs/data_parallel.md)
        loss = layers.mean(masked)
    else:
        loss = layers.reduce_sum(masked) / layers.reduce_sum(mask)
    return loss, logits
