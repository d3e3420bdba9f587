"""The description a decoder graph is built from.

`models.transformer._decoder_block` builds one layer from a `DecoderSpec`:
norm kind, residual order, position scheme, attention kind and feed-forward
kind. The six dims every builder used to take (`vocab`, `d_model`,
`d_inner`, `num_heads`, `num_layers`, `dropout`) are `DecoderSpec.classic`:
post-LayerNorm, sinusoidal positions at the embedding, full heads, a ReLU
pair, float32 parameters. `DecoderSpec.latent_moe` is the other point that
is built, `DecoderSpec.conv_gqa_moe` the third (a kind PER LAYER: gated
short convolutions with a per-request state beside grouped-query rotary
attention), `DecoderSpec.ssm_gqa_moe` the fourth (ONE sublayer a layer: a
Mamba-2 state-space mixer, grouped-query attention without positions, or
latent routed experts), `DecoderSpec.window_gqa_moe` the fifth (a kind of
ATTENTION a layer: a sliding window or every position, each kind with its
own rotary positions or none), `DecoderSpec.parallel_ssm_gqa` the sixth (TWO
mixers a layer: a Mamba-2 state-space mixer and rotary grouped-query
attention on one normed input, summed into one residual, under the family's
scalar multipliers), `DecoderSpec.kda_latent_moe` the seventh (a kind PER
LAYER under latent attention: a channel-wise gated delta-rule mixer with a
MATRIX state a head, or latent attention with no query bottleneck and a gate
a head; group-limited routing; with its optional fields the GLM-5-Next
family's: `hyper` residual streams mixed around every sub-layer, an `indexer`
that makes the latent read sparse over a second pool of pooled keys, an
unrotated latent row, low-rank kda gates, clamped gated pairs). A spec comes
from one of the constructors;
the fields are what `_decoder_block` reads, not a product to pick from: any
other combination raises where a graph would have to build it.
`serving.PagedKVEngine(model=spec)` takes any of them;
`models.transformer.transformer_lm(model=spec)` TRAINS the fifth (pre-norm
RMSNorm, grouped heads, a window and a rotation a kind of layer through the
flash kernels, softmax-routed experts with a gradient and a balance term);
everything else in the package takes the classic one.

Kinds (each a string, checked by name; nothing is guessed):

  norm        "layer_norm" | "rms_norm"
  residual    "post" (x = norm(x + f(x))) | "pre" (x = x + f(norm(x))) |
              "mhc" (`HyperSpec`: `mult` streams X, X = H_res X + H_post^T
              f(norm(H_pre X)), the three maps made from X, H_res through
              Sinkhorn; the embedding enters every stream, their sum leaves)
  positions   "sinusoid" (added at the embedding) | "rotary" (inside attention)
              | "none" (the state-space layers carry the order)
  attention   "full" (q/k/v heads over K and V pools; `num_kv_heads` fewer
              key/value heads than query heads, `qk_norm` an RMSNorm a head
              on q and k) | "latent" (`LatentSpec`; with `indexer`
              (`IndexerSpec`) a row attends the best pooled groups and its
              tail alone)
  attention_kinds  a kind an (attention) layer, "window" (a query sees the
              last `window` positions, itself among them; rotated by `rope`
              where the spec has one) | "full" (every position; rotated by
              `rope_full` where the spec has one, else NOT rotated);
              None: full everywhere, rotated where the spec has a `rope`
  layer_kinds a kind a layer, "attention" | "conv" (`ConvSpec`: a gated
              short convolution whose state is the last rows of its input),
              or under attention "latent": "attention" | "kda" (`KdaSpec`);
              None: attention everywhere. With `one_sublayer` a layer is its
              kind ALONE under one pre-norm residual, out of "ssm"
              (`SsmSpec`) | "attention" | "moe"
  mixer       "kind" (a layer's token mixer is its kind's, one) |
              "ssm+attention" (EVERY layer runs the state-space mixer and
              full-head attention on one normed input and adds both to the
              residual: the layer holds a slot state, snapshots AND K/V rows)
  ffn         "relu" | "gated_silu"; layers from `moe.first_dense` on are
              routed experts + shared expert (`MoESpec`: `scoring`
              "sigmoid" is what the serving ticks route by, "softmax" with
              a balance term `aux_coef` what a training graph does;
              `topk_method` "group_bias" keeps the `topk_group` best of
              `n_group` groups of experts before the top-k)
  tied_head   the vocabulary head is the embedding, transposed
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

LANES = 128


def yarn_mscale(factor: float, a: float) -> float:
    """m(f, a) = 0.1 a ln f + 1 (1 where nothing is stretched)."""
    return 1.0 if factor <= 1.0 else 0.1 * a * math.log(factor) + 1.0


@dataclasses.dataclass(frozen=True)
class RopeSpec:
    """Rotary positions over `dim` values, YaRN-stretched when `factor` > 1
    (per-frequency blend of theta_i and theta_i / factor, a linear ramp
    between the correction dims of `beta_fast` and `beta_slow` over
    `original_max` positions)."""
    dim: int
    theta: float = 10000.0
    factor: float = 1.0
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0
    original_max: int = 4096

    @property
    def table_scale(self) -> float:
        """What cos and sin are multiplied by."""
        return (yarn_mscale(self.factor, self.mscale)
                / yarn_mscale(self.factor, self.mscale_all_dim))

    @property
    def softmax_mscale(self) -> float:
        """m(factor, mscale_all_dim): its SQUARE multiplies the softmax scale."""
        return yarn_mscale(self.factor, self.mscale_all_dim)


@dataclasses.dataclass(frozen=True)
class LatentSpec:
    """Latent attention (MLA): queries through a rank-`q_lora_rank`
    bottleneck (None: ONE query matrix, no bottleneck and no norm); keys and
    values from ONE cached row a token, `kv_lora_rank` normalised values +
    `rope.dim` rotated ones, shared by all heads (`rope` None: NO rotation
    anywhere in the attention, the row is the `kv_lora_rank` values alone).
    `gate` "head": a head's output times sigmoid of one value a head,
    `u W_gate` [d_model -> heads], before the output projection; "none": no
    gate."""
    q_lora_rank: Optional[int]
    kv_lora_rank: int
    qk_nope_head_dim: int
    v_head_dim: int
    rope: Optional[RopeSpec]
    gate: str = "none"

    def __post_init__(self):
        if self.gate not in ("none", "head"):
            raise NotImplementedError(
                f"LatentSpec.gate {self.gate!r}: 'none' or 'head' (one "
                "sigmoid gate a head; no element-wise gate is built)")

    @property
    def rope_dim(self) -> int:
        return 0 if self.rope is None else self.rope.dim

    @property
    def row_values(self) -> int:
        return self.kv_lora_rank + self.rope_dim

    @property
    def row_lanes(self) -> int:
        """The stored width of a cache row: `row_values` padded with zeros
        to whole 128-lane rows (576 -> 640). A pool whose minor dimension
        is not a multiple of 128 is stored block-minor by XLA on a TPU
        (fusion/paged_attention.py, "The pool's shape"), so the row is
        padded, and a query row is padded alike: one matmul scores both
        parts and the zeros add nothing."""
        return -(-self.row_values // LANES) * LANES

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.rope_dim

    @property
    def softmax_scale(self) -> float:
        mscale = 1.0 if self.rope is None else self.rope.softmax_mscale
        return self.qk_head_dim ** -0.5 * mscale ** 2


@dataclasses.dataclass(frozen=True)
class IndexerSpec:
    """The selector of a sparse latent read (DeepSeek-V3.2's lightning
    indexer over POOLED keys; fusion/sparse_latent_attention.py has the
    equations): `heads` index heads of `head_dim` values whose first
    `rope.dim` are rotated at the token's own position; a key a position,
    mean-pooled over groups of `kpool` consecutive positions, ONE pooled row
    a group in a second pool beside the latent rows; a query attends the
    `topk // kpool` best whole groups and the tail (the positions of its own
    unfinished group). `share` "full": every sparse layer computes its own
    index (an index shared from another layer is not built)."""
    heads: int
    head_dim: int
    topk: int
    kpool: int
    rope: RopeSpec
    share: str = "full"

    def __post_init__(self):
        if self.share != "full":
            raise NotImplementedError(
                f"IndexerSpec.share {self.share!r}: an indexer that shares "
                "another layer's index is not built; every sparse layer "
                "computes its own ('full')")
        if self.kpool < 1 or self.topk % self.kpool:
            raise ValueError(f"index_topk {self.topk} counts positions: a "
                             f"whole number of groups of {self.kpool}")
        if self.rope.dim > self.head_dim or self.rope.factor != 1.0:
            raise ValueError("the indexer rotates the first `rope.dim` of "
                             "its `head_dim` values, unstretched")

    @property
    def top_groups(self) -> int:
        return self.topk // self.kpool


@dataclasses.dataclass(frozen=True)
class HyperSpec:
    """Manifold-constrained hyper-connections (mHC, arXiv:2512.24880): the
    residual is `mult` streams of `d_model` values, mixed around every
    sub-layer by three maps computed from the streams themselves
    (fusion/hyper_connection.py has the equations); the stream-to-stream map
    is made doubly stochastic by `sinkhorn_iters` rounds of row and column
    division with `eps` inside both."""
    mult: int = 4
    sinkhorn_iters: int = 20
    eps: float = 1e-6

    def __post_init__(self):
        if self.mult < 2 or self.sinkhorn_iters < 1:
            raise ValueError("HyperSpec: at least two streams and one "
                             "Sinkhorn round (one stream is residual='pre')")

    @property
    def maps(self) -> int:
        """Values the three maps hold: H_pre, H_post [n], H_res [n, n]."""
        return 2 * self.mult + self.mult ** 2


@dataclasses.dataclass(frozen=True)
class ConvSpec:
    """The gated short convolution: `[B, C, z] = x W_in`; `u = B * z`; a
    depthwise causal convolution of `taps` taps over u, no bias; the output
    `(C * conv) W_out`. What a request carries from token to token is the
    last `taps - 1` rows of u (zero before position 0)."""
    taps: int = 3

    @property
    def state_rows(self) -> int:
        return self.taps - 1


@dataclasses.dataclass(frozen=True)
class SsmSpec:
    """The Mamba-2 mixer (fusion/ssm.py has the equations): `heads` heads of
    `head_dim` values, `groups` groups sharing B and C of `state` values, a
    causal depthwise convolution of `taps` taps (bias, SiLU) over x, B and C
    (the chunked form's chunk is the engine's prefill chunk). What a request
    carries from
    token to token is `h` [heads, head_dim, state] in float32 and the last
    `taps - 1` rows of the convolution's input."""
    heads: int
    head_dim: int
    groups: int
    state: int
    taps: int = 4

    def __post_init__(self):
        if self.heads % self.groups:
            raise ValueError(f"{self.heads} heads over {self.groups} groups")

    @property
    def d_inner(self) -> int:
        return self.heads * self.head_dim

    @property
    def conv_dim(self) -> int:
        """Channels the convolution runs over: x, then B and C of a group."""
        return self.d_inner + 2 * self.groups * self.state

    @property
    def in_dim(self) -> int:
        """Columns of the input projection: z, xBC, dt."""
        return self.d_inner + self.conv_dim + self.heads

    @property
    def state_rows(self) -> int:
        return self.taps - 1

    @property
    def state_shape(self) -> Tuple[int, int, int]:
        return (self.heads, self.head_dim, self.state)

    def h_bytes(self) -> int:
        return self.heads * self.head_dim * self.state * 4


@dataclasses.dataclass(frozen=True)
class KdaSpec:
    """The channel-wise gated delta-rule mixer (Kimi Delta Attention;
    fusion/kda.py has the equations): `heads` heads whose state is a MATRIX
    `S` [head_dim (keys), head_dim (values)] in float32, decayed by a gate a
    KEY CHANNEL in (`gate_lower_bound`, 0) and corrected by what it already
    holds; a causal depthwise convolution of `taps` taps (no bias, SiLU) over
    q, k and v. What a request carries from token to token is `S` and the
    last `taps - 1` rows of the convolution's input. `gate_rank` > 0: the
    decay gate's `f` and the output gate are low-rank PAIRS
    [d_model -> gate_rank -> d_inner] (Kimi Linear's own layer); 0: one full
    matrix each."""
    heads: int
    head_dim: int
    taps: int = 4
    gate_lower_bound: float = -5.0
    gate_rank: int = 0

    def __post_init__(self):
        if not self.gate_lower_bound < 0:
            raise ValueError("KdaSpec.gate_lower_bound is the log of the "
                             f"smallest decay: below 0, not "
                             f"{self.gate_lower_bound}")

    @property
    def d_inner(self) -> int:
        return self.heads * self.head_dim

    @property
    def conv_dim(self) -> int:
        """Channels the convolution runs over: q, then k, then v."""
        return 3 * self.d_inner

    @property
    def state_rows(self) -> int:
        return self.taps - 1

    @property
    def state_shape(self) -> Tuple[int, int, int]:
        return (self.heads, self.head_dim, self.head_dim)

    def h_bytes(self) -> int:
        return self.heads * self.head_dim * self.head_dim * 4


@dataclasses.dataclass(frozen=True)
class Multipliers:
    """The scalar multipliers a muP-parametrised family publishes (Falcon-H1),
    each applied to an ACTIVATION where the equations put it, none folded
    into a weight; 1 everywhere builds no op. `ssm` scales the five column
    ranges of the state-space input projection, in the order z, x, B, C, dt;
    `mlp` the feed-forward's gate (before the SiLU) and its output; `key` the
    keys before the rotation; `lm_head` the logits."""
    embedding: float = 1.0
    attention_in: float = 1.0
    attention_out: float = 1.0
    key: float = 1.0
    ssm_in: float = 1.0
    ssm_out: float = 1.0
    ssm: Tuple[float, float, float, float, float] = (1.0,) * 5
    mlp: Tuple[float, float] = (1.0, 1.0)
    lm_head: float = 1.0

    def __post_init__(self):
        if len(self.ssm) != 5 or len(self.mlp) != 2:
            raise ValueError("Multipliers.ssm has five values (z, x, B, C, "
                             "dt) and Multipliers.mlp two (gate, output)")


TOPK_METHODS = ("none", "bias", "group_bias")
ACTIVATIONS = ("gated_silu", "relu2")
SCORING = ("sigmoid", "softmax")


@dataclasses.dataclass(frozen=True)
class MoESpec:
    """Routed experts beside shared ones. The router scores ALL `n_routed`
    experts and picks `top_k`; `held` names the experts whose weights this
    program has (one chip's share of an expert-parallel deployment), and
    only their part of the sum is computed: what the others would add is
    left out, and no code stands in for the chips that hold them.
    `topk_method` "bias": the selection is the top-k of score + a learned
    per-expert bias, the weights are the UNBIASED scores of the selected;
    `norm_eps` is added to the sum the weights are divided by. "group_bias":
    before that top-k the experts stand in `n_group` groups of equal size, a
    group scores the sum of its 2 largest score + bias, and only the
    `topk_group` best groups' experts are eligible (a deployment places whole
    groups on a chip, so the group step decides which CHIPS a token visits:
    `held` is whole groups).
    `activation` "relu2": an expert is `W2 relu(W1 z)^2`, two matrices and no
    gate; `latent` > 0: the routed experts run on a row of that width between
    a down- and an up-projection all of them share; `d_shared`: the shared
    expert's own width (None: `d_expert * n_shared`). `scoring` "softmax":
    the scores are a softmax over all `n_routed` experts before the top-k
    (a training graph; the serving ticks route by "sigmoid" alone);
    `aux_coef`: what a training loss adds of each routed layer's balance
    term `n_routed * sum_e f_e P_e` (f_e the share of the assignments that
    chose e, P_e the mean score of e, over ALL experts). `swiglu_limit` > 0
    clamps every gated pair of the block (routed, shared, and the dense
    layers'): `silu(min(gate, limit)) * clip(up, -limit, limit)`."""
    n_routed: int
    top_k: int
    d_expert: int
    held: Tuple[int, ...]
    n_shared: int = 1
    first_dense: int = 1
    scaling: float = 1.0
    norm_topk_prob: bool = True
    scoring: str = "sigmoid"
    topk_method: str = "none"
    norm_eps: float = 0.0
    activation: str = "gated_silu"
    latent: int = 0
    d_shared: Optional[int] = None
    aux_coef: float = 0.0
    n_group: int = 1
    topk_group: int = 1
    swiglu_limit: float = 0.0

    @property
    def shared_width(self) -> int:
        return (self.d_expert * self.n_shared if self.d_shared is None
                else self.d_shared)

    def __post_init__(self):
        if self.activation not in ACTIVATIONS:
            raise NotImplementedError(
                f"expert activation {self.activation!r}: one of {ACTIVATIONS}")
        if self.topk_method not in TOPK_METHODS:
            raise NotImplementedError(
                f"topk_method {self.topk_method!r}: the router implements "
                f"{TOPK_METHODS} (top-k over every expert, plain or of "
                "score + bias, or of score + bias inside the best groups)")
        if self.scoring not in SCORING:
            raise NotImplementedError(
                f"scoring_func {self.scoring!r}: the router implements "
                f"{SCORING}")
        if self.swiglu_limit < 0 or (self.swiglu_limit and (
                self.activation != "gated_silu" or self.scoring != "sigmoid")):
            raise NotImplementedError(
                f"swiglu_limit {self.swiglu_limit}: a clamp of the gated "
                "SiLU pair in a serving tick (>= 0; 0 = off)")
        if not self.held or sorted(set(self.held)) != list(self.held) or \
                not 0 <= self.held[0] <= self.held[-1] < self.n_routed:
            raise ValueError(f"held experts {self.held!r} must be distinct, "
                             f"ascending, inside 0..{self.n_routed - 1}")
        if self.topk_method != "group_bias":
            if (self.n_group, self.topk_group) != (1, 1):
                raise ValueError(
                    f"n_group {self.n_group} / topk_group {self.topk_group} "
                    "limit the selection under topk_method='group_bias', "
                    "and only it")
            return
        if self.n_group < 1 or self.n_routed % self.n_group:
            raise ValueError(f"group_bias: {self.n_routed} experts do not "
                             f"stand in {self.n_group} groups of equal size")
        size = self.n_routed // self.n_group
        if not 1 <= self.topk_group <= self.n_group or size < 2 \
                or self.topk_group * size < self.top_k:
            raise ValueError(
                f"group_bias: topk_group {self.topk_group} of {self.n_group} "
                f"groups of {size} (a group scores its 2 largest) cannot "
                f"give a top-{self.top_k}")
        groups = {e // size for e in self.held}
        if len(self.held) != len(groups) * size:
            raise ValueError(
                f"group_bias: held experts {self.held[0]}..{self.held[-1]} "
                f"cut a group of {size} in two: a chip holds whole groups")


@dataclasses.dataclass(frozen=True)
class DecoderSpec:
    vocab: int
    d_model: int
    d_inner: int
    num_heads: int
    num_layers: int
    dropout: float = 0.0
    packed: bool = False
    norm: str = "layer_norm"
    norm_eps: float = 1e-5
    residual: str = "post"
    positions: str = "sinusoid"
    attention: str = "full"
    ffn: str = "relu"
    dtype: str = "float32"          # parameters, activations and the cache
    latent: Optional[LatentSpec] = None
    moe: Optional[MoESpec] = None
    num_kv_heads: Optional[int] = None      # None: as many as query heads
    qk_norm: bool = False
    rope: Optional[RopeSpec] = None         # full heads' rotary positions
    layer_kinds: Optional[Tuple[str, ...]] = None
    conv: Optional[ConvSpec] = None
    tied_head: bool = False
    one_sublayer: bool = False              # a layer is its kind alone
    head_dim: Optional[int] = None          # None: d_model // num_heads
    ssm: Optional[SsmSpec] = None
    attention_kinds: Optional[Tuple[str, ...]] = None   # "window" | "full"
    window: int = 0                         # positions a window layer sees
    rope_full: Optional[RopeSpec] = None    # the "full" kind's own rotation
    mixer: str = "kind"                     # | "ssm+attention": both, summed
    multipliers: Multipliers = Multipliers()
    kda: Optional[KdaSpec] = None
    indexer: Optional[IndexerSpec] = None   # the latent layers read sparsely
    hyper: Optional[HyperSpec] = None       # residual="mhc": its streams

    def __post_init__(self):
        for field, kinds in (("norm", ("layer_norm", "rms_norm")),
                             ("residual", ("post", "pre", "mhc")),
                             ("positions", ("sinusoid", "rotary", "none")),
                             ("attention", ("full", "latent")),
                             ("ffn", ("relu", "gated_silu")),
                             ("mixer", ("kind", "ssm+attention")),
                             ("dtype", ("float32", "bfloat16"))):
            if getattr(self, field) not in kinds:
                raise ValueError(f"DecoderSpec.{field} = "
                                 f"{getattr(self, field)!r}: one of {kinds}")
        if (self.attention == "latent") != (self.latent is not None):
            raise ValueError("attention='latent' comes with a LatentSpec, "
                             "and only it")
        if self.attention == "latent" and (
                (self.latent.rope is None and self.indexer is None)
                != (self.positions == "none")
                or self.positions == "sinusoid"):
            raise ValueError(
                "latent attention rotates part of its row, or its indexer's "
                "keys: positions='rotary'; with neither (`LatentSpec.rope` "
                "None and no IndexerSpec), positions='none'")
        if self.indexer is not None and (
                self.latent is None or self.latent.q_lora_rank is None):
            raise ValueError(
                "an IndexerSpec selects the rows a LatentSpec's read attends "
                "and takes its queries from the latent query bottleneck "
                "(`q_lora_rank`): it stands beside both, and only them")
        if (self.residual == "mhc") != (self.hyper is not None):
            raise ValueError("residual='mhc' comes with a HyperSpec "
                             "(`hyper`), and only it")
        if self.hyper is not None and (
                self.norm != "rms_norm" or self.one_sublayer
                or self.mixer != "kind" or self.dropout):
            raise NotImplementedError(
                "residual='mhc' (hc_mult streams) mixes around the sub-layers "
                "of the pre-norm RMSNorm block: a post-norm block, a "
                "one-sublayer layer or the 'ssm+attention' mixer carries one "
                "stream")
        if self.attention == "full" and \
                (self.positions == "rotary") != (self.rope is not None):
            raise ValueError("rotary positions with full heads come with a "
                             "RopeSpec (`rope`), and only they")
        if self.num_heads % self.kv_heads:
            raise ValueError(f"{self.num_heads} query heads over "
                             f"{self.kv_heads} key/value heads")
        kinds = self.layer_kinds
        known = ({"ssm", "attention", "moe"} if self.one_sublayer
                 else {"attention", "kda"} if self.attention == "latent"
                 else {"attention", "conv"})
        if (kinds is None and self.one_sublayer) or (kinds is not None and (
                len(kinds) != self.num_layers or set(kinds) - known)):
            raise ValueError(f"layer_kinds {kinds!r}: one of "
                             f"{sorted(known)} for each of "
                             f"{self.num_layers} layers")
        if self.mixer == "ssm+attention" and (
                self.ssm is None or self.attention != "full"
                or self.rope is None or self.residual != "pre"
                or self.norm != "rms_norm" or self.ffn != "gated_silu"
                or kinds is not None or self.attention_kinds is not None
                or self.moe is not None or self.conv is not None
                or self.qk_norm or self.tied_head):
            raise ValueError(
                "mixer='ssm+attention' is the pre-norm RMSNorm block of an "
                "SsmSpec beside rotary full-head attention (`rope`) and a "
                "gated SiLU pair, the same in every layer: no layer_kinds, "
                "attention_kinds, latent, MoESpec, ConvSpec, qk_norm or tied "
                "head builds beside it")
        if self.multipliers != Multipliers() and self.mixer != "ssm+attention":
            raise ValueError("`multipliers` scale the 'ssm+attention' block: "
                             "no other block applies them")
        if (self.ssm is not None) != bool(self.ssm_layers):
            raise ValueError("an 'ssm' layer comes with an SsmSpec, and "
                             "only it")
        if self.one_sublayer and (self.moe is None) != (not self.moe_layers):
            raise ValueError("a 'moe' layer comes with a MoESpec, and only "
                             "it")
        if (self.conv is not None) != bool(self.conv_layers):
            raise ValueError("a 'conv' layer comes with a ConvSpec, and "
                             "only it")
        if (self.kda is not None) != bool(self.kda_layers):
            raise ValueError("a 'kda' layer comes with a KdaSpec, and only "
                             "it")
        if self.kda is not None and (
                self.kv_heads != self.num_heads or self.qk_norm
                or self.residual == "post" or self.norm != "rms_norm"
                or self.ffn != "gated_silu" or self.tied_head):
            raise ValueError(
                "a 'kda' layer stands beside latent attention (no grouped "
                "heads: num_kv_heads, no QK-norm of the full-head kind) in "
                "the pre-norm RMSNorm block with a gated SiLU pair and an "
                "untied head")
        akinds = self.attention_kinds
        if akinds is not None and (
                len(akinds) != self.num_layers
                or set(akinds) - {"window", "full"}
                or self.attention != "full" or self.layer_kinds is not None):
            raise ValueError(f"attention_kinds {akinds!r}: 'window' or "
                             f"'full' for each of {self.num_layers} layers "
                             "of full-head attention")
        if (self.window > 0) != bool(self.window_layers):
            raise ValueError("a 'window' layer comes with `window` > 0, and "
                             "only it")
        if self.rope_full is not None and (
                akinds is None or self.rope is None
                or self.rope_full.dim != self.rope.dim):
            raise ValueError("`rope_full` rotates the 'full' kind of "
                             "`attention_kinds` beside a `rope` of the same "
                             "width for the 'window' kind")

    @classmethod
    def classic(cls, vocab=32000, d_model=512, d_inner=2048, num_heads=8,
                num_layers=6, dropout=0.0, packed=False):
        """'Attention Is All You Need' section 3, as every graph here built
        it before a block had kinds."""
        return cls(vocab, d_model, d_inner, num_heads, num_layers, dropout,
                   packed)

    @classmethod
    def latent_moe(cls, vocab, d_model, d_inner, num_heads, num_layers,
                   latent: LatentSpec, moe: Optional[MoESpec] = None,
                   norm_eps=1e-6, dtype="bfloat16"):
        """The DeepSeek-V3 family's block: pre-norm RMSNorm residuals,
        rotary positions inside latent attention, a gated SiLU pair, routed
        experts from `moe.first_dense` on. With `classic`, the two points of
        the kinds' product that a graph here builds."""
        return cls(vocab, d_model, d_inner, num_heads, num_layers,
                   norm="rms_norm", norm_eps=norm_eps, residual="pre",
                   positions="rotary", attention="latent", ffn="gated_silu",
                   dtype=dtype, latent=latent, moe=moe)

    @classmethod
    def conv_gqa_moe(cls, vocab, d_model, d_inner, num_heads, num_kv_heads,
                     layer_kinds, rope: RopeSpec,
                     moe: Optional[MoESpec] = None, conv_taps=3,
                     norm_eps=1e-5, dtype="bfloat16"):
        """The LFM2 family's block: pre-norm RMSNorm residuals; a layer's
        operator by `layer_kinds`, a gated short convolution or
        grouped-query attention with an RMSNorm a head on q and k and
        rotary positions over the whole head; a gated SiLU pair, routed
        experts (no shared one) from `moe.first_dense` on; a final norm and
        the embedding as the head."""
        return cls(vocab, d_model, d_inner, num_heads, len(layer_kinds),
                   norm="rms_norm", norm_eps=norm_eps, residual="pre",
                   positions="rotary", ffn="gated_silu", dtype=dtype, moe=moe,
                   num_kv_heads=num_kv_heads, qk_norm=True, rope=rope,
                   layer_kinds=tuple(layer_kinds), conv=ConvSpec(conv_taps),
                   tied_head=True)

    @classmethod
    def ssm_gqa_moe(cls, vocab, d_model, num_heads, num_kv_heads, d_head,
                    layer_kinds, ssm: SsmSpec, moe: Optional[MoESpec] = None,
                    norm_eps=1e-5, dtype="bfloat16"):
        """The Nemotron-H family's block: ONE sublayer a layer under one
        pre-norm RMSNorm residual, by `layer_kinds` a Mamba-2 mixer ("ssm"),
        grouped-query attention with no positions, no bias and no QK-norm
        ("attention", heads of `d_head` whatever `d_model`), or routed
        experts beside a shared one ("moe"); a final norm and an untied
        head."""
        return cls(vocab, d_model, 0, num_heads, len(layer_kinds),
                   norm="rms_norm", norm_eps=norm_eps, residual="pre",
                   positions="none", dtype=dtype, moe=moe,
                   num_kv_heads=num_kv_heads, head_dim=d_head,
                   layer_kinds=tuple(layer_kinds), one_sublayer=True, ssm=ssm)

    @classmethod
    def window_gqa_moe(cls, vocab, d_model, d_inner, num_heads, num_kv_heads,
                       d_head, attention_kinds, window, rope: RopeSpec,
                       moe: Optional[MoESpec] = None, norm_eps=1e-5,
                       dtype="bfloat16", rope_full: Optional[RopeSpec] = None,
                       qk_norm=True):
        """The sliding-window families' block (EXAONE-4 / K-EXAONE; with
        `rope_full` and without `qk_norm`, Mellum 2): pre-norm RMSNorm
        residuals; grouped-query attention with heads of `d_head` (whatever
        `d_model`), an RMSNorm a head on q and k in EVERY layer where
        `qk_norm`; by `attention_kinds` a layer sees the last `window`
        positions, q and k rotated by `rope` over the whole head, or every
        position, rotated by `rope_full` (None: NOT rotated); a gated SiLU
        pair, routed experts beside the shared ones (`moe.n_shared` may be
        0) from `moe.first_dense` on; a final norm and an untied head."""
        return cls(vocab, d_model, d_inner, num_heads, len(attention_kinds),
                   norm="rms_norm", norm_eps=norm_eps, residual="pre",
                   positions="rotary", ffn="gated_silu", dtype=dtype, moe=moe,
                   num_kv_heads=num_kv_heads, qk_norm=bool(qk_norm),
                   rope=rope, head_dim=d_head,
                   attention_kinds=tuple(attention_kinds),
                   window=int(window), rope_full=rope_full)

    @classmethod
    def parallel_ssm_gqa(cls, vocab, d_model, d_inner, num_heads,
                         num_kv_heads, d_head, num_layers, ssm: SsmSpec,
                         rope: RopeSpec,
                         multipliers: Multipliers = Multipliers(),
                         norm_eps=1e-5, dtype="bfloat16"):
        """The Falcon-H1 family's block: a pre-norm RMSNorm residual whose
        token mixer is the SUM of a Mamba-2 mixer (`ssm`, an inner width of
        its own: heads x head_dim whatever `d_model`) and grouped-query
        attention (heads of `d_head`, q and k rotated by `rope` over the
        whole head, no bias, no QK-norm) on ONE normed input, then a gated
        SiLU pair under a second norm; `multipliers` on the embedding, both
        mixers' inputs and outputs, the keys, the state-space projection's
        column ranges, the feed-forward and the logits; a final norm and an
        untied head."""
        return cls(vocab, d_model, d_inner, num_heads, num_layers,
                   norm="rms_norm", norm_eps=norm_eps, residual="pre",
                   positions="rotary", ffn="gated_silu", dtype=dtype,
                   num_kv_heads=num_kv_heads, rope=rope, head_dim=d_head,
                   ssm=ssm, mixer="ssm+attention", multipliers=multipliers)

    @classmethod
    def kda_latent_moe(cls, vocab, d_model, d_inner, num_heads, layer_kinds,
                       kda: KdaSpec, latent: LatentSpec,
                       moe: Optional[MoESpec] = None, norm_eps=1e-6,
                       dtype="bfloat16",
                       indexer: Optional[IndexerSpec] = None,
                       hyper: Optional[HyperSpec] = None):
        """The Ling-3 / Kimi-Linear family's block: pre-norm RMSNorm
        residuals; a layer's mixer by `layer_kinds`, the channel-wise gated
        delta-rule mixer ("kda": a matrix state a head in float32, q, k and
        v through a short convolution, not rotated) or latent attention
        ("attention": `latent`, here with `q_lora_rank` None and a gate a
        head; ONE cached row a position in those layers alone); a gated SiLU
        pair, routed experts beside the shared one from `moe.first_dense`
        on (group-limited: `topk_method` "group_bias"); a final norm and an
        untied head. With the optional fields, the GLM-5-Next family's: an
        `indexer` makes the latent layers' read SPARSE (the best pooled
        groups and the tail; `latent.rope` None: nothing in the attention is
        rotated but the indexer's keys), `hyper` carries `hyper.mult`
        residual streams between the sub-layers (residual "mhc"),
        `kda.gate_rank` the gates' low-rank pairs, `moe.swiglu_limit` the
        clamp on every gated pair."""
        rotary = latent.rope is not None or indexer is not None
        return cls(vocab, d_model, d_inner, num_heads, len(layer_kinds),
                   norm="rms_norm", norm_eps=norm_eps,
                   residual="pre" if hyper is None else "mhc",
                   positions="rotary" if rotary else "none",
                   attention="latent", ffn="gated_silu",
                   dtype=dtype, latent=latent, moe=moe,
                   layer_kinds=tuple(layer_kinds), kda=kda, indexer=indexer,
                   hyper=hyper)

    @property
    def is_classic(self) -> bool:
        return self == DecoderSpec.classic(**self.dims())

    def dims(self) -> dict:
        """The six dims (and `packed`) the classic builders take."""
        return dict(vocab=self.vocab, d_model=self.d_model,
                    d_inner=self.d_inner, num_heads=self.num_heads,
                    num_layers=self.num_layers, dropout=self.dropout,
                    packed=self.packed)

    def ffn_kind(self, layer: int) -> str:
        if self.one_sublayer:
            return "moe" if self.layer_kinds[layer] == "moe" else "none"
        if self.moe is not None and layer >= self.moe.first_dense:
            return "moe"
        return self.ffn

    @property
    def moe_layers(self) -> Tuple[int, ...]:
        return tuple(i for i in range(self.num_layers)
                     if self.ffn_kind(i) == "moe")

    @property
    def kv_heads(self) -> int:
        return self.num_kv_heads or self.num_heads

    @property
    def d_head(self) -> int:
        return self.head_dim or self.d_model // self.num_heads

    def layer_kind(self, layer: int) -> str:
        return self.layer_kinds[layer] if self.layer_kinds else "attention"

    @property
    def attention_layers(self) -> Tuple[int, ...]:
        return tuple(i for i in range(self.num_layers)
                     if self.layer_kind(i) == "attention")

    def attention_kind(self, layer: int) -> str:
        return self.attention_kinds[layer] if self.attention_kinds else "full"

    @property
    def window_layers(self) -> Tuple[int, ...]:
        """The layers whose K/V live in the window pool."""
        return tuple(i for i in self.attention_layers
                     if self.attention_kind(i) == "window")

    @property
    def full_layers(self) -> Tuple[int, ...]:
        """The attention layers that see every position."""
        return tuple(i for i in self.attention_layers
                     if self.attention_kind(i) == "full")

    def rope_of(self, layer: int) -> Optional[RopeSpec]:
        """What rotates q and k of attention layer `layer`, or None. Where
        the spec gives a kind of attention a layer: `rope` the window
        layers, `rope_full` (None in the K-EXAONE family) the full ones."""
        if self.attention_kinds is None \
                or self.attention_kind(layer) == "window":
            return self.rope
        return self.rope_full

    def rotates(self, layer: int) -> bool:
        """Are q and k of attention layer `layer` rotated?"""
        return self.rope_of(layer) is not None

    @property
    def conv_layers(self) -> Tuple[int, ...]:
        return tuple(i for i in range(self.num_layers)
                     if self.layer_kind(i) == "conv")

    @property
    def ssm_layers(self) -> Tuple[int, ...]:
        """The layers with a state-space mixer: by kind, or every layer
        where the mixer is the sum of both (such a layer is among the
        `attention_layers` too)."""
        if self.mixer == "ssm+attention":
            return tuple(range(self.num_layers))
        return tuple(i for i in range(self.num_layers)
                     if self.layer_kind(i) == "ssm")

    @property
    def kda_layers(self) -> Tuple[int, ...]:
        return tuple(i for i in range(self.num_layers)
                     if self.layer_kind(i) == "kda")

    @property
    def recurrent(self):
        """What describes the layers that keep a slot state and a snapshot
        pool (`SsmSpec` or `KdaSpec`: `state_shape`, `state_rows`,
        `conv_dim`, `h_bytes`), or None."""
        return self.ssm if self.ssm is not None else self.kda

    @property
    def recurrent_layers(self) -> Tuple[int, ...]:
        return self.ssm_layers if self.ssm is not None else self.kda_layers

    # -- bytes ----------------------------------------------------------------
    @property
    def itemsize(self) -> int:
        return 2 if self.dtype == "bfloat16" else 4

    def cache_row_bytes(self) -> int:
        """Bytes ONE position holds in the cache, as stored: over the
        attention layers that see every position, K and V of the key/value
        heads (a window layer's rows are the window pool's:
        `window_row_bytes`)."""
        if self.attention == "latent":
            row = self.latent.row_lanes * self.itemsize
            if self.indexer is not None:    # a pooled key a group, beside it
                row += self.index_row_bytes()
            return len(self.attention_layers) * row
        return (len(self.full_layers) * 2 * self.kv_heads * self.d_head
                * self.itemsize)

    def index_row_bytes(self) -> int:
        """Bytes ONE position holds of ONE sparse layer's pooled index keys
        (a row of `head_dim` values a group of `kpool` positions)."""
        ix = self.indexer
        return ix.head_dim * self.itemsize // ix.kpool

    def window_row_bytes(self) -> int:
        """Bytes ONE position holds in the window pool, over the window
        layers (0 without any)."""
        return (len(self.window_layers) * 2 * self.kv_heads * self.d_head
                * self.itemsize)

    def state_bytes(self) -> int:
        """Bytes of ONE copy of a request's per-layer state beside its
        per-token rows (a slot's, or a snapshot): the conv layers' last
        rows, or the state-space layers' `h` / the kda layers' `S` (float32)
        and last conv rows (a layer whose mixer is both holds them BESIDE
        its rows of `cache_row_bytes`); 0 where every layer is attention
        alone."""
        rec = self.recurrent
        if rec is not None:
            return len(self.recurrent_layers) * (
                rec.h_bytes()
                + rec.state_rows * rec.conv_dim * self.itemsize)
        if self.conv is None:
            return 0
        return (len(self.conv_layers) * self.conv.state_rows * self.d_model
                * self.itemsize)
