"""A routed expert layer that is told which experts it holds.

`moe_route`: sigmoid scores over ALL `n_routed` experts (float32), the
`top_k` largest, weights `score / sum(selected scores) * scaling`; the sum
runs over every selected expert, held here or not. With a `bias` (one value
an expert) the selection is the top-k of score + bias and the weights stay
the UNBIASED scores of the selected; `norm_eps` joins the sum the weights
are divided by. What leaves the op is the
weights' HELD part, dense: `[n_held, N, 1]`, zero where a row did not select
the expert (or the row is dead: an idle slot, the tail of a short chunk),
and `rows[e]`, how many rows expert e got.

`moe_experts`: `sum_e w[e] * down_e(silu(gate_e x) * up_e x)` over the held
experts, a grouped matrix product with two lowerings:

- the kernel (a TPU): grid (held expert, tile of the expert width). The
  weights stay in HBM behind BlockSpecs whose index maps read a
  scalar-prefetched table: an expert no row selected maps every step to
  the block the step before it used, so NONE of its weights are fetched,
  and its steps compute nothing. A touched expert streams its three
  matrices once, a tile at a time, and all N rows ride each tile (N is a
  tick's rows: tens to a few hundred, so the product is bound by the
  weights it streams, and a row that did not select the expert costs
  MXU time that is idle anyway; its weight is zero). Accumulates in float32
  in the resident output block.
- the composite (a CPU, or asked for): the same sum in `jax.numpy`, over
  every held expert.

With `gate` None an expert is `down_e(relu(up_e x)^2)`: two matrices, no gate
(the latent experts, whose x is a latent row between projections the layer
shares: `models/transformer.py _moe_ffn`). Its kernel takes the tile of the
expert width from the shape, up to a whole expert a step (`relu2_tile`): 128
held experts of width 2,688 at a tile of 256 would be 1,408 steps of ~0.35 us
against the ~1.1 ms their weights stream in.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..framework.registry import register_op
from .decode_attention import _auto_backend

KERNEL, COMPOSITE = "kernel", "composite"
_TILE = 256            # columns of the expert width a step takes


def route(x, w_router, held, top_k, scaling, norm_topk_prob=True, live=None,
          bias=None, norm_eps=0.0):
    """x [N, D], w_router [D, E], bias [E] or None -> (weights
    [n_held, N, 1] float32, rows [n_held] int32)."""
    logits = jnp.dot(x, w_router, preferred_element_type=jnp.float32)
    scores = jax.nn.sigmoid(logits.astype(jnp.float32))
    if bias is None:
        top, idx = jax.lax.top_k(scores, top_k)               # [N, k]
    else:
        _, idx = jax.lax.top_k(scores + bias.astype(jnp.float32), top_k)
        top = jnp.take_along_axis(scores, idx, axis=-1)
    total = jnp.sum(top, axis=-1, keepdims=True)
    if norm_eps:
        total = total + norm_eps
    w = top / total if norm_topk_prob else top
    w = w * scaling
    held = jnp.asarray(held, jnp.int32)
    hit = idx[None, :, :] == held[:, None, None]              # [h, N, k]
    dense = jnp.sum(jnp.where(hit, w[None], 0.0), axis=-1)    # [h, N]
    sel = jnp.any(hit, axis=-1)
    if live is not None:
        alive = live.reshape(-1) > 0
        dense = jnp.where(alive[None, :], dense, 0.0)
        sel = sel & alive[None, :]
    return dense[:, :, None], jnp.sum(sel, axis=-1).astype(jnp.int32)


@register_op("moe_route", stop_gradient=True)
def _moe_route_op(ctx, ins, attrs):
    x = ins["X"][0]
    w, rows = route(x.reshape(-1, x.shape[-1]), ins["W"][0], attrs["held"],
                    attrs["top_k"], attrs["scaling"],
                    attrs.get("norm_topk_prob", True),
                    ins["Live"][0] if ins.get("Live") else None,
                    ins["Bias"][0] if ins.get("Bias") else None,
                    attrs.get("norm_eps", 0.0))
    return {"Weights": [w], "Rows": [rows]}


#: VMEM the two-matrix kernel's weight tiles may take, double-buffered
_RELU2_VMEM = 48 * 1024 * 1024


def relu2_tile(d_model, d_expert, itemsize):
    """The columns of the expert width a step of the two-matrix kernel
    takes: the largest divisor of the width in whole 128-lane rows whose two
    tiles, double-buffered, fit `_RELU2_VMEM`; 0 where the width has none."""
    for n in range(1, d_expert // 128 + 1):
        tile, rest = divmod(d_expert, n)
        if not rest and tile % 128 == 0 and \
                4 * d_model * tile * itemsize <= _RELU2_VMEM:
            return tile
    return 0


def experts_lowering(n_rows, d_model, d_expert, backend=None, tile=_TILE):
    backend = backend or _auto_backend()
    served = (n_rows % 16 == 0 and d_model % 128 == 0
              and tile and d_expert % tile == 0)
    if served and backend != "xla":
        return KERNEL
    if jax.default_backend() == "tpu" and backend != "xla":
        raise RuntimeError(
            f"moe_experts: {n_rows} rows of width {d_model}, experts of "
            f"width {d_expert}: no kernel serves the shape, and the "
            "composite streams every held expert: not a fallback on a TPU")
    return COMPOSITE


def _experts_composite(x, w, gate, up, down):
    xf = x.astype(gate.dtype)
    g = jnp.einsum("nd,edf->enf", xf, gate, preferred_element_type=jnp.float32)
    u = jnp.einsum("nd,edf->enf", xf, up, preferred_element_type=jnp.float32)
    h = (jax.nn.silu(g) * u * w).astype(down.dtype)
    return jnp.einsum("enf,efd->nd", h, down,
                      preferred_element_type=jnp.float32)


def _experts_kernel(eblk_ref, fhold_ref, touched_ref, x_ref, w_ref, g_ref,
                    u_ref, d_ref, o_ref):
    from jax.experimental import pallas as pl

    e, f = pl.program_id(0), pl.program_id(1)

    @pl.when((e == 0) & (f == 0))
    def _():
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    @pl.when(touched_ref[e] > 0)
    def _():
        x = x_ref[...]
        g = jnp.dot(x, g_ref[0], preferred_element_type=jnp.float32)
        u = jnp.dot(x, u_ref[0], preferred_element_type=jnp.float32)
        h = g * jax.nn.sigmoid(g) * u * w_ref[0]
        o_ref[...] += jnp.dot(h.astype(x.dtype), d_ref[0],
                              preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _experts_pallas(x, w, touched, gate, up, down, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, d = x.shape
    n_held, _, width = gate.shape
    nf = width // _TILE
    touched = touched.astype(jnp.int32)
    # an untouched expert holds the block its neighbour uses: the last tile
    # of the touched expert before it, or tile 0 of the first touched one
    ids = jnp.arange(n_held, dtype=jnp.int32)
    before = jax.lax.cummax(jnp.where(touched > 0, ids, -1))
    first = jnp.argmax(touched > 0).astype(jnp.int32)
    eblk = jnp.where(before >= 0, before, first)
    fhold = jnp.where(before >= 0, nf - 1, 0).astype(jnp.int32)

    def tile(e, f, eblk_ref, fhold_ref, touched_ref):
        on = touched_ref[e] > 0
        return eblk_ref[e], jnp.where(on, f, fhold_ref[e])

    def in_map(e, f, *refs):
        blk, col = tile(e, f, *refs)
        return blk, 0, col

    def down_map(e, f, *refs):
        blk, col = tile(e, f, *refs)
        return blk, col, 0

    with jax.named_scope("moe_experts"):
        return pl.pallas_call(
            _experts_kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3,
                grid=(n_held, nf),
                in_specs=[
                    pl.BlockSpec((n, d), lambda e, f, *_: (0, 0)),
                    pl.BlockSpec((1, n, 1), lambda e, f, *_: (e, 0, 0)),
                    pl.BlockSpec((1, d, _TILE), in_map),
                    pl.BlockSpec((1, d, _TILE), in_map),
                    pl.BlockSpec((1, _TILE, d), down_map)],
                out_specs=pl.BlockSpec((n, d), lambda e, f, *_: (0, 0))),
            out_shape=jax.ShapeDtypeStruct((n, d), jnp.float32),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary"),
                vmem_limit_bytes=100 * 1024 * 1024),
            interpret=interpret,
        )(eblk, fhold, touched, x.astype(gate.dtype), w, gate, up, down)


def _relu2_composite(x, w, up, down):
    u = jnp.einsum("nd,edf->enf", x.astype(up.dtype), up,
                   preferred_element_type=jnp.float32)
    h = (jnp.square(jax.nn.relu(u)) * w).astype(down.dtype)
    return jnp.einsum("enf,efd->nd", h, down,
                      preferred_element_type=jnp.float32)


def _relu2_kernel(eblk_ref, fhold_ref, touched_ref, x_ref, w_ref, u_ref,
                  d_ref, o_ref):
    from jax.experimental import pallas as pl

    e, f = pl.program_id(0), pl.program_id(1)

    @pl.when((e == 0) & (f == 0))
    def _():
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    @pl.when(touched_ref[e] > 0)
    def _():
        x = x_ref[...]
        u = jnp.maximum(
            jnp.dot(x, u_ref[0], preferred_element_type=jnp.float32), 0.0)
        o_ref[...] += jnp.dot((u * u * w_ref[0]).astype(x.dtype), d_ref[0],
                              preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def _relu2_pallas(x, w, touched, up, down, tile, interpret):
    """`_experts_pallas` for two matrices an expert, `tile` columns a step."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, d = x.shape
    n_held, _, width = up.shape
    nf = width // tile
    touched = touched.astype(jnp.int32)
    ids = jnp.arange(n_held, dtype=jnp.int32)
    before = jax.lax.cummax(jnp.where(touched > 0, ids, -1))
    first = jnp.argmax(touched > 0).astype(jnp.int32)
    eblk = jnp.where(before >= 0, before, first)
    fhold = jnp.where(before >= 0, nf - 1, 0).astype(jnp.int32)

    def held(e, f, eblk_ref, fhold_ref, touched_ref):
        return eblk_ref[e], jnp.where(touched_ref[e] > 0, f, fhold_ref[e])

    def up_map(e, f, *refs):
        blk, col = held(e, f, *refs)
        return blk, 0, col

    def down_map(e, f, *refs):
        blk, col = held(e, f, *refs)
        return blk, col, 0

    with jax.named_scope("latent_experts"):
        return pl.pallas_call(
            _relu2_kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3,
                grid=(n_held, nf),
                in_specs=[
                    pl.BlockSpec((n, d), lambda e, f, *_: (0, 0)),
                    pl.BlockSpec((1, n, 1), lambda e, f, *_: (e, 0, 0)),
                    pl.BlockSpec((1, d, tile), up_map),
                    pl.BlockSpec((1, tile, d), down_map)],
                out_specs=pl.BlockSpec((n, d), lambda e, f, *_: (0, 0))),
            out_shape=jax.ShapeDtypeStruct((n, d), jnp.float32),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary"),
                vmem_limit_bytes=100 * 1024 * 1024),
            interpret=interpret,
        )(eblk, fhold, touched, x.astype(up.dtype), w, up, down)


def experts(x, w, rows, gate, up, down, backend=None):
    """x [N, D]; w [n_held, N, 1] float32 (`route`); rows [n_held]; gate,
    up [n_held, D, F]; down [n_held, F, D] -> [N, D] float32. `gate` None:
    the two-matrix form, `down_e(relu(up_e x)^2)`."""
    interpret = backend == "pallas_interpret"
    if gate is None:
        tile = relu2_tile(x.shape[1], up.shape[-1], up.dtype.itemsize)
        if experts_lowering(x.shape[0], x.shape[1], up.shape[-1], backend,
                            tile) == KERNEL:
            return _relu2_pallas(x, w, rows, up, down, tile=tile,
                                 interpret=interpret)
        return _relu2_composite(x, w, up, down)
    lowering = experts_lowering(x.shape[0], x.shape[1], gate.shape[-1],
                                backend)
    if lowering == KERNEL:
        return _experts_pallas(x, w, rows, gate, up, down,
                               interpret=interpret)
    return _experts_composite(x, w, gate, up, down)


@register_op("moe_experts", stop_gradient=True)
def _moe_experts_op(ctx, ins, attrs):
    x = ins["X"][0]
    out = experts(x.reshape(-1, x.shape[-1]), ins["Weights"][0],
                  ins["Rows"][0],
                  ins["Gate"][0] if ins.get("Gate") else None, ins["Up"][0],
                  ins["Down"][0], backend=attrs.get("backend"))
    return {"Out": [out.reshape(x.shape).astype(x.dtype)]}
