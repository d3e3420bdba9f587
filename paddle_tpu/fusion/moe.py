"""A routed expert layer that is told which experts it holds.

`moe_route`: sigmoid scores over ALL `n_routed` experts (float32), the
`top_k` largest, weights `score / sum(selected scores) * scaling`; the sum
runs over every selected expert, held here or not. With a `bias` (one value
an expert) the selection is the top-k of score + bias and the weights stay
the UNBIASED scores of the selected; `norm_eps` joins the sum the weights
are divided by. With `groups` (n_group, topk_group) the experts stand in
`n_group` groups of equal size, a group scores the sum of its 2 largest
score + bias, and only the `topk_group` best groups' experts are eligible
(`group_limited`: the family deploys whole groups a chip, so this step
decides which chips a row visits). What leaves the op is the
weights' HELD part, dense: `[n_held, N, 1]`, zero where a row did not select
the expert (or the row is dead: an idle slot, the tail of a short chunk),
and `rows[e]`, how many rows expert e got.

`moe_experts`: `sum_e w[e] * down_e(silu(gate_e x) * up_e x)` over the held
experts, a grouped matrix product with two lowerings:

- the kernel (a TPU): a walk over the TOUCHED experts only (PR 51). From
  the rows every held expert got the jitted wrapper builds, on the device,
  the list of touched expert ids, ascending and packed to the front, and
  their count (`packed_walk`); the grid is (touched expert, tile of the
  expert width), its expert axis ending with the count (a dynamic grid
  bound: a call with 5 of 16 experts touched runs 5 experts' steps). The
  weights stay in HBM behind BlockSpecs whose index maps read the
  scalar-prefetched list, so step e streams the e-th touched expert's
  tiles and its `[1, N, 1]` routing weights, and Pallas' one-step lookahead
  always finds the next touched tile: the DMA queue does not drain between
  a call's first and last tile (the grid over the experts in their stored
  order, before it, lost a step's compute at every touched -> untouched
  edge). An expert no row selected is never fetched; a call with none
  touched runs one step that fetches one tile and computes nothing, and
  returns zeros. The `+=` into the resident float32 output block runs over
  the experts in ascending order, as the composite's sum does. All N rows
  ride each tile (N is a tick's rows: tens to a few hundred, so the product
  is bound by the weights it streams, and a row that did not select the
  expert costs MXU time that is idle anyway; its weight is zero). The
  interpreter takes no dynamic grid bound: there the expert axis runs over
  all held experts and the steps past the count hold the last touched tile.
- the composite (a CPU, or asked for): the same sum in `jax.numpy`, over
  every held expert.

`moe_train` (PR 50) is the layer as a TRAINING graph runs it, differentiable
in the rows, the router and the stacks: softmax scores (`train_route`), a
balance term (`balance_term`), and the held experts' part of the sum as a
grouped product over the (row, expert) pairs sorted by expert
(`train_experts`: megablox's Mosaic kernels on a TPU, `jax.lax.ragged_dot`
elsewhere; over ONE pair buffer with room for all the pairs, so no row is
ever dropped and no count changes a shape; since PR 53 the buffer is touched
by kernels alone, each over its live tiles: a row gather writes it, the
products and one fused elementwise step read and write it, a sum through
the same indices reads it back, and the rows past the held pairs are left
as they lie), with counters the step keeps on
the device. docs/fusion.md has the section. The two ops above stay what the
serving ticks use: their kernel is the decode shape, and they carry no
gradient.

With `gate` None an expert is `down_e(relu(up_e x)^2)`: two matrices, no gate
(the latent experts, whose x is a latent row between projections the layer
shares: `models/transformer.py _moe_ffn`). Both forms share the walk
(`_walk_pallas`, `_walk_step`) and the tile's rule (`experts_tile`): the
columns of the expert width a step takes come from the shape the op sees
(rows, d_model, d_expert, item size, matrices an expert), timed alone on a
v5e at the four serving cells' shapes: docs/fusion.md has the table.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..framework.registry import register_op
from ..ops.pallas_kernels import _traced_once
from .decode_attention import _auto_backend

KERNEL, COMPOSITE = "kernel", "composite"


def group_limited(keys, n_group, topk_group):
    """keys [N, E] with the experts outside each row's `topk_group` best of
    `n_group` groups at -inf: a group scores the sum of its 2 largest keys
    (DeepSeek-V3's `noaux_tc`)."""
    n = keys.shape[0]
    grouped = keys.reshape(n, n_group, -1)
    # the 2 largest of a group by two maxima (the first's ONE position
    # masked): `top_k` over a group's members is a sort on a TPU
    first = jnp.argmax(grouped, axis=-1, keepdims=True)
    member = jax.lax.broadcasted_iota(jnp.int32, grouped.shape, 2)
    best2 = jnp.max(grouped, axis=-1) + jnp.max(
        jnp.where(member == first, -jnp.inf, grouped), axis=-1)
    _, kept = jax.lax.top_k(best2, topk_group)                     # [N, g]
    on = jnp.any(kept[:, :, None] == jnp.arange(n_group)[None, None, :],
                 axis=1)                                          # [N, G]
    return jnp.where(on[:, :, None], grouped, -jnp.inf).reshape(keys.shape)


def route(x, w_router, held, top_k, scaling, norm_topk_prob=True, live=None,
          bias=None, norm_eps=0.0, groups=None):
    """x [N, D], w_router [D, E], bias [E] or None, groups (n_group,
    topk_group) or None -> (weights [n_held, N, 1] float32, rows [n_held]
    int32)."""
    logits = jnp.dot(x, w_router, preferred_element_type=jnp.float32)
    scores = jax.nn.sigmoid(logits.astype(jnp.float32))
    if bias is None and groups is None:
        top, idx = jax.lax.top_k(scores, top_k)               # [N, k]
    else:
        keys = scores if bias is None else scores + bias.astype(jnp.float32)
        if groups is not None:
            keys = group_limited(keys, *groups)
        _, idx = jax.lax.top_k(keys, top_k)
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
                    attrs.get("norm_eps", 0.0),
                    (attrs["n_group"], attrs["topk_group"])
                    if "n_group" in attrs else None)
    return {"Weights": [w], "Rows": [rows]}


#: columns a tile may have, and bytes of weights a step may fetch (its
#: `matrices` tiles together; the pipeline holds the next step's beside
#: them) under a decode tick's rows and under a mixed tick's. Timed alone on
#: a v5e AND in the four serving cells (PERF.md section 6, PR 51): alone a
#: decode step wants 11-22 MB and a mixed tick's, bound by the MXU, 9-11; in
#: a cell a call's first fetch costs more than alone, and the narrower tile
#: won wherever the two disagreed but at [6144, 2048]
_TILE_COLUMNS = 512
_STEP_BYTES = 20 * 1024 * 1024
_MIXED_STEP_BYTES = 12 * 1024 * 1024
#: VMEM a call may take (`vmem_limit_bytes`)
_VMEM_LIMIT = 100 * 1024 * 1024


def experts_tile(n_rows, d_model, d_expert, itemsize, matrices=3):
    """The columns of the expert width a step takes, from the shape the op
    sees: the largest divisor of the width in whole 128-lane rows, of at
    most `_TILE_COLUMNS` columns, whose `matrices` tiles are at most
    `_STEP_BYTES` together (`_MIXED_STEP_BYTES` under a mixed tick's rows:
    more than one pass of the MXU's 128); 0 where the width has none."""
    budget = _STEP_BYTES if n_rows <= 128 else _MIXED_STEP_BYTES
    for n in range(1, d_expert // 128 + 1):
        tile, rest = divmod(d_expert, n)
        if not rest and tile % 128 == 0 and tile <= _TILE_COLUMNS and \
                matrices * d_model * tile * itemsize <= budget:
            return tile
    return 0


def experts_vmem_bytes(n_rows, d_model, tile, itemsize, matrices=3):
    """What a call holds in VMEM at most: the weight tiles and the rows
    double-buffered, the resident float32 output (counted twice, as the
    pipeline may hold it), a routing-weights column padded to whole (8, 128)
    tiles, and three [n_rows, tile] float32 intermediates."""
    weights = 2 * matrices * d_model * tile * itemsize
    rows = 2 * n_rows * d_model * (itemsize + 4)
    column = 2 * -(-n_rows // 8) * 8 * 128 * 4
    return weights + rows + column + 3 * n_rows * tile * 4


def experts_lowering(n_rows, d_model, d_expert, backend=None, tile=None):
    backend = backend or _auto_backend()
    if tile is None:
        tile = experts_tile(n_rows, d_model, d_expert, 2)
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


def clamp_pair(g, u, limit):
    """`swiglu_limit`: gate <- min(gate, limit), up <- clip(up, -limit,
    limit); 0 leaves both."""
    if not limit:
        return g, u
    return jnp.minimum(g, limit), jnp.clip(u, -limit, limit)


def _experts_composite(x, w, gate, up, down, limit=0.0):
    xf = x.astype(gate.dtype)
    g = jnp.einsum("nd,edf->enf", xf, gate, preferred_element_type=jnp.float32)
    u = jnp.einsum("nd,edf->enf", xf, up, preferred_element_type=jnp.float32)
    g, u = clamp_pair(g, u, limit)
    h = (jax.nn.silu(g) * u * w).astype(down.dtype)
    return jnp.einsum("enf,efd->nd", h, down,
                      preferred_element_type=jnp.float32)


def _relu2_composite(x, w, up, down):
    u = jnp.einsum("nd,edf->enf", x.astype(up.dtype), up,
                   preferred_element_type=jnp.float32)
    h = (jnp.square(jax.nn.relu(u)) * w).astype(down.dtype)
    return jnp.einsum("enf,efd->nd", h, down,
                      preferred_element_type=jnp.float32)


def packed_walk(touched):
    """The walk's tables from `touched` [n_held] (rows an expert got, or any
    value that is positive where it got one): (order [n_held] int32, the
    touched experts' ids ascending and packed to the front, every entry past
    them the LAST touched id (0 where none is touched); count [1] int32)."""
    on = touched > 0
    ids = jnp.arange(on.shape[0], dtype=jnp.int32)
    count = jnp.sum(on, dtype=jnp.int32)
    # where each touched expert lands, and the table's inverse, by compares
    # over [n_held, n_held]: one small fusion where a sort or a scatter
    # would be an operation of its own before every call
    pos = jnp.sum(on[None, :] & (ids[None, :] <= ids[:, None]), axis=1) - 1
    order = jnp.sum(jnp.where(on[None, :] & (pos[None, :] == ids[:, None]),
                              ids[None, :], 0), axis=1)
    last = jnp.max(jnp.where(on, ids, 0))
    return (jnp.where(ids < count, order, last).astype(jnp.int32),
            count.reshape(1))


def _experts_kernel(order_ref, count_ref, x_ref, w_ref, g_ref, u_ref, d_ref,
                    o_ref, *, limit=0.0):
    def hidden(x):
        g = jnp.dot(x, g_ref[0], preferred_element_type=jnp.float32)
        u = jnp.dot(x, u_ref[0], preferred_element_type=jnp.float32)
        g, u = clamp_pair(g, u, limit)
        return g * jax.nn.sigmoid(g) * u

    _walk_step(count_ref, x_ref, w_ref, d_ref, o_ref, hidden)


def _relu2_kernel(order_ref, count_ref, x_ref, w_ref, u_ref, d_ref, o_ref):
    def hidden(x):
        u = jnp.maximum(
            jnp.dot(x, u_ref[0], preferred_element_type=jnp.float32), 0.0)
        return u * u

    _walk_step(count_ref, x_ref, w_ref, d_ref, o_ref, hidden)


def _walk_step(count_ref, x_ref, w_ref, d_ref, o_ref, hidden):
    """One step of the walk: step e of the expert axis is the e-th TOUCHED
    expert's; a step past the count computes nothing."""
    from jax.experimental import pallas as pl

    e, f = pl.program_id(0), pl.program_id(1)

    @pl.when((e == 0) & (f == 0))
    def _():
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    @pl.when(e < count_ref[0])
    def _():
        x = x_ref[...]
        o_ref[...] += jnp.dot((hidden(x) * w_ref[0]).astype(x.dtype),
                              d_ref[0], preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("tile", "interpret", "limit"))
def _walk_pallas(x, w, touched, stacks, tile, interpret, limit=0.0):
    """The grouped product over the touched experts only: `stacks` is (gate,
    up, down) or (up, down), `tile` columns of the expert width a step."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    *ins, down = stacks
    n, d = x.shape
    n_held, _, width = ins[0].shape
    nf = width // tile
    order, count = packed_walk(touched)

    # a step past the count holds the last touched expert's last tile: it
    # fetches nothing
    def col(e, f, count_ref):
        return jnp.where(e < count_ref[0], f, nf - 1)

    def in_map(e, f, order_ref, count_ref):
        return order_ref[e], 0, col(e, f, count_ref)

    def down_map(e, f, order_ref, count_ref):
        return order_ref[e], col(e, f, count_ref), 0

    gated = len(ins) == 2
    # the walk ends with the count (the interpreter wants a static grid)
    steps = n_held if interpret else jnp.maximum(count[0], 1)
    with jax.named_scope("moe_experts" if gated else "latent_experts"):
        return pl.pallas_call(
            (functools.partial(_experts_kernel, limit=limit) if limit
             else _experts_kernel) if gated else _relu2_kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(steps, nf),
                in_specs=[
                    pl.BlockSpec((n, d), lambda e, f, *_: (0, 0)),
                    pl.BlockSpec((1, n, 1),
                                 lambda e, f, order_ref, _: (order_ref[e], 0,
                                                             0)),
                    *[pl.BlockSpec((1, d, tile), in_map) for _ in ins],
                    pl.BlockSpec((1, tile, d), down_map)],
                out_specs=pl.BlockSpec((n, d), lambda e, f, *_: (0, 0))),
            out_shape=jax.ShapeDtypeStruct((n, d), jnp.float32),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary"),
                vmem_limit_bytes=_VMEM_LIMIT),
            interpret=interpret,
        )(order, count, x.astype(down.dtype), w, *ins, down)


def experts(x, w, rows, gate, up, down, backend=None, limit=0.0):
    """x [N, D]; w [n_held, N, 1] float32 (`route`); rows [n_held]; gate,
    up [n_held, D, F]; down [n_held, F, D] -> [N, D] float32. `gate` None:
    the two-matrix form, `down_e(relu(up_e x)^2)`. `limit`: the gated
    pair's clamp (`clamp_pair`)."""
    stacks = (up, down) if gate is None else (gate, up, down)
    tile = experts_tile(*x.shape, up.shape[-1], up.dtype.itemsize,
                        len(stacks))
    if experts_lowering(*x.shape, up.shape[-1], backend, tile) == KERNEL:
        return _walk_pallas(x, w, rows, stacks, tile=tile,
                            interpret=backend == "pallas_interpret",
                            limit=float(limit))
    if gate is None:
        return _relu2_composite(x, w, up, down)
    return _experts_composite(x, w, gate, up, down, limit)


# ---------------------------------------------------------------------------
# the routed layer as a TRAINING step runs it: a differentiable route, and a
# grouped product over the (row, expert) pairs sorted by expert
# ---------------------------------------------------------------------------

# rows of a tile of the grouped product, and what the pair buffer is padded to
_PAIR_TILE = 512
# (tm, tk, tn) of megablox's kernels, by the product: the forward's two
# (x @ [gate | up], hidden @ down), their transposes for the rows' gradients
# and the two weight gradients (tgmm: tk tiles the ROWS). Timed on a v5e at
# [16,384 live of 65,536, 2304] x [16, 2304, 2 x 896] (PERF.md section 6,
# PR 50)
_TILINGS = {"in": (512, 1152, 896), "out": (512, 896, 1152),
            "in_t": (512, 896, 1152), "out_t": (512, 1152, 896),
            "in_w": (512, 1152, 896), "out_w": (512, 896, 1152)}


def train_route(x, w_router, top_k, norm_topk_prob=True, scaling=1.0):
    """x [N, D], w_router [D, E] -> (p [N, E] softmax over ALL experts,
    idx [N, k] the top-k, w [N, k] their weights `p / sum of the selected`
    times `scaling`), float32 at full precision. The gradient reaches
    `w_router` through w and through p (the balance term); the indices
    carry none."""
    logits = jnp.dot(x.astype(jnp.float32), w_router.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST,
                     preferred_element_type=jnp.float32)
    p = jax.nn.softmax(logits, axis=-1)
    top, idx = jax.lax.top_k(p, top_k)
    if norm_topk_prob:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    return p, idx, top * scaling


def balance_term(p, idx):
    """E * sum_e f_e P_e over ALL E experts: f_e the share of the N x k
    assignments that chose e (no gradient), P_e the mean of p_e over the
    rows. 1 under an even spread. Returns (term, assignments an expert
    [E] float32)."""
    n_routed = p.shape[-1]
    chosen = jnp.sum(jax.nn.one_hot(idx, n_routed, dtype=jnp.float32),
                     axis=(0, 1))
    share = jax.lax.stop_gradient(chosen / idx.size)
    return n_routed * jnp.sum(share * jnp.mean(p, axis=0)), chosen


def _megablox():
    """megablox's kernel module: the package exports a FUNCTION under the
    module's own name (`gmm`, with one tiling for all of its derivatives), so
    a plain import of the module gets the function."""
    import importlib
    return importlib.import_module(
        "jax.experimental.pallas.ops.tpu.megablox.gmm")


def _count(name, scope):
    """A set-up counter (a compiled step records nothing): `moe_train/call`,
    a call of one of the layer's kernels at a call site, and
    `moe_train/body_traced`, a trace of its body; `scope` the kernel's name
    in the trace's scopes."""
    from ..observability import tracing
    tracing.record_counter(name, 1, scope=scope)


def _one_context():
    """JAX traces a custom derivative's backward under an empty abstract
    mesh that it SETS and its forward under none set; jit's cache takes the
    two for different contexts and would trace (and lower to Mosaic) a
    kernel that both call twice. Setting the mesh that is there makes them
    one, and changes nothing under a mesh of the caller's."""
    return jax.sharding.use_abstract_mesh(jax.sharding.get_abstract_mesh())


def _kernel_call(kernel, scope, backend, *operands, **static):
    """One call site of one of the layer's jitted kernels: counted, and
    traced in the one context."""
    _count("moe_train/call", scope)
    with _one_context():
        return kernel(*operands, interpret=backend == "pallas_interpret",
                      **static)


@_traced_once
def _product(lhs, rhs, sizes, *, role, interpret):
    """One of the layer's six grouped products by megablox's kernel under
    `_TILINGS[role]`, its body traced once a step: `in` / `out` are lhs[rows
    of group e] @ rhs[e], `in_t` / `out_t` the same against rhs[e]
    transposed (lhs's dtype out), `in_w` / `out_w` the weight gradients
    lhs[rows of e]^T @ rhs[rows of e] (float32 sums, `rhs`'s dtype out). The
    kernels visit the tiles that hold a group's rows and no other: the rows
    past `sum(sizes)` are left as they lie in memory, in the result, and are
    read by nobody (a weight gradient selects its rows by the groups)."""
    scope = "moe_train_" + role
    _count("moe_train/body_traced", scope)
    with jax.named_scope(scope):
        if role.endswith("_w"):
            return _megablox().tgmm(lhs.swapaxes(0, 1), rhs, sizes, rhs.dtype,
                                    _TILINGS[role], interpret=interpret)
        return _megablox().gmm(lhs, rhs, sizes, lhs.dtype, _TILINGS[role],
                               transpose_rhs=role.endswith("_t"),
                               interpret=interpret)


def _products(sizes, backend):
    """The layer's products over the pair buffer, by role (`_product`): (a
    row product `a[rows of e] @ b[e]`, or against b[e] transposed under a
    role that ends in `_t`; a weight gradient `a[rows of e]^T @ g[rows of
    e]`). megablox's kernels, or under backend "xla" (off a TPU)
    `jax.lax.ragged_dot` and its own derivative: XLA's lowering of it
    reached 14% of the chip's peak at the training cell's shape where the
    kernels reach 60% (PERF.md section 6, PR 50). The rows past the groups
    are DEAD out of a kernel: whatever memory held, NaN or Inf among it.
    Every product and every elementwise step keeps a row's garbage in that
    row, and what sums over rows selects the live ones first (`tgmm` by its
    groups, `_pair_total` by the count)."""
    if backend == "xla":
        def ragged(a, b):
            return jax.lax.ragged_dot(a, b, sizes,
                                      preferred_element_type=jnp.float32)

        def rows(a, b, role):
            return ragged(a, b.swapaxes(1, 2) if role.endswith("_t") else b
                          ).astype(a.dtype)

        def stack(a, g, role):
            like = jax.ShapeDtypeStruct(
                (sizes.shape[0], a.shape[1], g.shape[1]), g.dtype)
            return jax.linear_transpose(lambda b: ragged(a, b), like)(
                g.astype(jnp.float32))[0]
        return rows, stack

    def kernel(a, b, role):
        return _kernel_call(_product, "moe_train_" + role, backend, a, b,
                            sizes, role=role)
    return kernel, kernel


# VMEM the row gather may take: the source whole, a tile of rows staged in
# the source's dtype and the result's tile twice (the pipeline's); and the
# elementwise step's: its tiles twice and a handful of float32 forms of one
_ROWS_VMEM_LIMIT = 100 * 1024 * 1024
_GATED_VMEM_LIMIT = 64 * 1024 * 1024


def _live_tiles(m, count, interpret):
    """The grid of a kernel of this file over the pair buffer: it ends with
    the live rows' last tile of `_PAIR_TILE` (the interpreter wants a static
    grid and walks them all): a tile past it is never read or written, as
    megablox leaves it."""
    tiles = m // _PAIR_TILE
    return tiles if interpret else jnp.clip(-(-count[0] // _PAIR_TILE), 1,
                                            tiles)


def _rows_kernel(index_ref, count_ref, x_ref, o_ref, stage_ref):
    """One tile of the row gather: `o[i] = x[index[i]]` for the tile's rows,
    x whole in VMEM, a row a dynamic-sublane load (a row of a tiled array in
    HBM is no contiguous span: Mosaic refuses the slice a row DMA would need),
    eight rows stored as one aligned tile, then the tile cast at once."""
    from jax.experimental import pallas as pl

    tile = o_ref.shape[0]
    base = pl.program_id(0) * tile

    def eight(i, carry):
        at = pl.multiple_of(i * 8, 8)
        stage_ref[pl.ds(at, 8), :] = jnp.concatenate(
            [x_ref[pl.ds(index_ref[base + at + u], 1), :] for u in range(8)],
            axis=0)
        return carry

    jax.lax.fori_loop(0, tile // 8, eight, 0)
    o_ref[...] = stage_ref[...].astype(o_ref.dtype)


@_traced_once
def _rows_pallas(x, index, count, *, dtype, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    _count("moe_train/body_traced", "moe_train_rows")
    m, (_, d) = index.shape[0], x.shape
    with jax.named_scope("moe_train_rows"):
        return pl.pallas_call(
            _rows_kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(_live_tiles(m, count, interpret),),
                in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
                out_specs=pl.BlockSpec((_PAIR_TILE, d), lambda t, *_: (t, 0)),
                scratch_shapes=[pltpu.VMEM((_PAIR_TILE, d), x.dtype)]),
            out_shape=jax.ShapeDtypeStruct((m, d), dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=_ROWS_VMEM_LIMIT),
            interpret=interpret,
        )(index, count, x)


def rows_lowering(x, m, dtype, backend):
    """KERNEL where the row gather's kernel serves the shape: rows of 32-bit
    values in whole 128-lane tiles, the source and the kernel's tiles within
    `_ROWS_VMEM_LIMIT`, whole tiles of `_PAIR_TILE` to write."""
    n, d = x.shape
    held = (n * d * 4 + _PAIR_TILE * d * (4 + 2 * jnp.dtype(dtype).itemsize)
            + m * 4)
    served = (x.dtype.itemsize == 4 and d % 128 == 0 and m % _PAIR_TILE == 0
              and _PAIR_TILE % 8 == 0 and held <= _ROWS_VMEM_LIMIT)
    return KERNEL if served and backend != "xla" else COMPOSITE


def pair_rows(x, index, count, dtype, backend):
    """x [N, D] -> x[index] as `dtype`, [m, D], for the first `count[0]` of
    the m sorted pairs, whole tiles of `_PAIR_TILE`: the rows past them are
    DEAD (`_products`). The kernel where it serves the shape, else
    `x[index]`, every row of it."""
    if rows_lowering(x, index.shape[0], dtype, backend) == KERNEL:
        return _kernel_call(_rows_pallas, "moe_train_rows", backend, x,
                            index, count, dtype=jnp.dtype(dtype))
    return x[index].astype(dtype)


def _pair_total(y, inv, count, k):
    """The sorted pairs' rows y [m, D] -> [N, D] float32: a row's k pairs
    summed (`inv[p]`: where pair p lies in the sorted order). A pair at or
    past `count[0]` is dead and is SELECTED away, never multiplied: its row
    may hold NaN."""
    rows = jnp.where((inv < count[0])[:, None],
                     y[jnp.minimum(inv, y.shape[0] - 1)],
                     jnp.zeros((), y.dtype)).astype(jnp.float32)
    return jnp.sum(rows.reshape(-1, k, y.shape[-1]), axis=1)


def _total_kernel(source_ref, count_ref, y_ref, o_ref):
    """One tile of the sum back: `o[source[i]] += y[i]` for the tile's LIVE
    rows, o [N, D] float32 whole in VMEM for the call, zeroed by the first
    step. y's 16-bit rows lie two to a 32-bit sublane, rows 2q and 2q + 1 in
    the low and high halves of word row q, and a bfloat16 value is the high
    half of its float32: a word row is loaded once and shifted or masked
    into both rows' float32, no row of y is unpacked. A dead row is never
    read: the loop ends with the live rows."""
    from jax.experimental import pallas as pl

    tile = y_ref.shape[0]
    base = pl.program_id(0) * tile

    @pl.when(pl.program_id(0) == 0)
    def _():
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    words = y_ref.bitcast(jnp.uint32)
    live = jnp.clip(count_ref[0] - base, 0, tile)

    def add(row, bits):
        at = pl.ds(source_ref[base + row], 1)
        o_ref[at, :] += jax.lax.bitcast_convert_type(bits, jnp.float32)

    def two(q, carry):
        word = words[pl.ds(q, 1), :]
        add(2 * q, word << 16)
        add(2 * q + 1, word & jnp.uint32(0xFFFF0000))
        return carry

    jax.lax.fori_loop(0, live // 2, two, 0)

    @pl.when(live % 2 == 1)
    def _():
        add(live - 1, words[pl.ds(live // 2, 1), :] << 16)


@_traced_once
def _total_pallas(y, source, count, *, n, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    _count("moe_train/body_traced", "moe_train_total")
    m, d = y.shape
    with jax.named_scope("moe_train_total"):
        return pl.pallas_call(
            _total_kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(_live_tiles(m, count, interpret),),
                in_specs=[pl.BlockSpec((_PAIR_TILE, d),
                                       lambda t, *_: (t, 0))],
                out_specs=pl.BlockSpec(memory_space=pltpu.VMEM)),
            out_shape=jax.ShapeDtypeStruct((n, d), jnp.float32),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=_ROWS_VMEM_LIMIT),
            interpret=interpret,
        )(source, count, y)


def total_lowering(y, n, backend):
    """KERNEL where the sum back's kernel serves the shape: bfloat16 rows in
    whole 128-lane tiles, whole tiles of `_PAIR_TILE` to read, the float32
    result and the kernel's tiles within `_ROWS_VMEM_LIMIT`."""
    m, d = y.shape
    held = n * d * 4 + 2 * _PAIR_TILE * d * 2 + m * 4
    served = (y.dtype == jnp.bfloat16 and d % 128 == 0
              and m % _PAIR_TILE == 0 and _PAIR_TILE % 16 == 0
              and held <= _ROWS_VMEM_LIMIT)
    return KERNEL if served and backend != "xla" else COMPOSITE


def pair_total(y, source, inv, count, k, backend):
    """The sorted pairs' rows y [m, D] summed back to their rows, [N, D]
    float32 (N = inv.size // k): `out[source[i]] += y[i]` over the first
    `count[0]` sorted pairs by the kernel where it serves the shape (in the
    sorted order, one expert's pairs after another's), else `_pair_total`
    through `inv` (in a row's own order of its k pairs)."""
    n = inv.shape[0] // k
    if total_lowering(y, n, backend) == KERNEL:
        return _kernel_call(_total_pallas, "moe_train_total", backend, y,
                            source, count, n=n)
    return _pair_total(y, inv, count, k)


def sort_pairs(idx, held, n_routed):
    """The N x k (row, expert) pairs ordered by held expert, those that
    landed on an expert held elsewhere last: (perm [M] sorted -> pair, inv
    [N * k] pair -> sorted, sizes [n_held] pairs a held expert). M is N x k
    padded to whole tiles of `_PAIR_TILE`."""
    n_held = len(held)
    slot = jnp.full((n_routed,), n_held, jnp.int32).at[
        jnp.asarray(held, jnp.int32)].set(jnp.arange(n_held, dtype=jnp.int32))
    key = slot[idx.reshape(-1)]
    pad = -key.shape[0] % _PAIR_TILE
    key = jnp.concatenate([key, jnp.full((pad,), n_held, jnp.int32)])
    perm = jnp.argsort(key, stable=True).astype(jnp.int32)
    inv = jnp.zeros_like(perm).at[perm].set(
        jnp.arange(perm.shape[0], dtype=jnp.int32))[:idx.size]
    sizes = jnp.sum(jax.nn.one_hot(key, n_held + 1, dtype=jnp.int32),
                    axis=0)[:n_held]
    return perm, inv, sizes


def _gated(gu, ws, d_hidden=None):
    """The one elementwise step between the products: [gate | up] rows gu
    [m, 2 F] and the pairs' weights ws [m, 1] -> hidden = silu(gate) * up *
    ws in gu's dtype; with the hidden rows' cotangent also (d_gu, d_ws), the
    derivative. float32 inside."""
    width = gu.shape[-1] // 2
    gate, up = (gu[:, :width].astype(jnp.float32),
                gu[:, width:].astype(jnp.float32))
    sig = jax.nn.sigmoid(gate)
    act = gate * sig * up
    hidden = (act * ws).astype(gu.dtype)
    if d_hidden is None:
        return hidden
    d_hidden = d_hidden.astype(jnp.float32)
    by_w = d_hidden * ws
    d_gu = jnp.concatenate(
        [by_w * up * (sig * (1.0 + gate * (1.0 - sig))), by_w * gate * sig],
        axis=-1).astype(gu.dtype)
    return hidden, d_gu, jnp.sum(d_hidden * act, axis=-1, keepdims=True)


def _gated_kernel(count_ref, gu_ref, ws_ref, *refs):
    """`_gated` over one tile of the pair buffer, forward (hidden out) or
    backward (the cotangent in; hidden, d_gu, d_ws out)."""
    if len(refs) == 1:
        refs[0][...] = _gated(gu_ref[...], ws_ref[...])
    else:
        d_hidden_ref, *outs = refs
        for ref, value in zip(outs, _gated(gu_ref[...], ws_ref[...],
                                           d_hidden_ref[...])):
            ref[...] = value


@_traced_once
def _gated_pallas(gu, ws, d_hidden, count, *, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    scope = "moe_train_gate" + ("" if d_hidden is None else "_bwd")
    _count("moe_train/body_traced", scope)
    m, wide = gu.shape

    def rows_of(width):
        return pl.BlockSpec((_PAIR_TILE, width), lambda t, *_: (t, 0))

    hidden = jax.ShapeDtypeStruct((m, wide // 2), gu.dtype)
    ins, in_specs = [gu, ws], [rows_of(wide), rows_of(1)]
    out_shape, out_specs = hidden, rows_of(wide // 2)
    if d_hidden is not None:
        ins, in_specs = ins + [d_hidden], in_specs + [rows_of(wide // 2)]
        out_shape = (hidden, jax.ShapeDtypeStruct(gu.shape, gu.dtype),
                     jax.ShapeDtypeStruct((m, 1), jnp.float32))
        out_specs = (rows_of(wide // 2), rows_of(wide), rows_of(1))
    with jax.named_scope(scope):
        return pl.pallas_call(
            _gated_kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=(_live_tiles(m, count, interpret),),
                in_specs=in_specs, out_specs=out_specs),
            out_shape=out_shape,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=_GATED_VMEM_LIMIT),
            interpret=interpret,
        )(count, *ins)


def _gated_rows(gu, ws, count, backend, d_hidden=None):
    """`_gated` over the live tiles of the pair buffer by its kernel (the
    rows past the held pairs are left as they lie, dead: `_products`), or
    where the kernel does not serve (backend "xla", a width of no whole
    128-lane tiles) over every row by XLA."""
    m, wide = gu.shape
    if backend == "xla" or wide % 256 or m % _PAIR_TILE or _PAIR_TILE % 16:
        return _gated(gu, ws, d_hidden)
    return _kernel_call(
        _gated_pallas, "moe_train_gate" + ("" if d_hidden is None else "_bwd"),
        backend, gu, ws, d_hidden, count)


@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9, 10))
def _held_sum(x, w, gate, up, down, perm, inv, sizes, k, backend,
              compute_dtype):
    """`train_experts` on sorted pairs. What touches the pair buffer [m, D]:
    the row gather writes it (`pair_rows`), the products and the one fused
    elementwise step between them (`_gated_rows`) read and write its LIVE
    tiles, and the sum back reads the live rows (`pair_total`, through the
    gather's own indices). No pass zeroes a dead row and, under the
    kernels, none runs over one. The stacks are cast (and [gate | up]
    joined) once, here, and the backward takes the forward's copies and the
    forward's [gate | up] product (235 MB a layer at the training cell's
    shape); the pairs' rows it gathers again (302 MB a layer not held, for
    0.7 ms of the gather's kernel: PERF.md section 6, PR 53). Six products
    and their elementwise steps by hand: every kernel's body is then traced
    in one context, once a step."""
    return _held_sum_fwd(x, w, gate, up, down, perm, inv, sizes, k, backend,
                         compute_dtype)[0]


def _held_sum_fwd(x, w, gate, up, down, perm, inv, sizes, k, backend,
                  compute_dtype):
    rows, _ = _products(sizes, backend)
    count = jnp.sum(sizes).reshape(1)
    wide = jnp.concatenate([gate, up], axis=-1).astype(compute_dtype)
    narrow = down.astype(compute_dtype)
    source = jnp.minimum(perm // k, x.shape[0] - 1)
    ws = w.reshape(-1, 1)[jnp.minimum(perm, w.size - 1)]
    gu = rows(pair_rows(x, source, count, compute_dtype, backend), wide, "in")
    y = rows(_gated_rows(gu, ws, count, backend), narrow, "out")
    # empty arrays carry the operands' dtypes: a dtype is no residual
    like = tuple(jnp.zeros((0,), a.dtype) for a in (w, gate, up, down))
    return (pair_total(y, source, inv, count, k, backend),
            (x, source, gu, ws, wide, narrow, inv, sizes, count, like))


def _held_sum_bwd(k, backend, compute_dtype, res, g):
    # behind a barrier, so that XLA shares no subexpression of the backward
    # with the forward: the gathered rows and the float32 forms of gu would
    # else stay alive from one to the other (1.2 GB and more a step)
    x, source, gu, ws, wide, narrow, inv, sizes, count, like = \
        jax.lax.optimization_barrier(res)
    rows, stack = _products(sizes, backend)
    xs = pair_rows(x, source, count, compute_dtype, backend)
    d_y = pair_rows(g, source, count, compute_dtype, backend)
    hidden, d_gu, d_ws = _gated_rows(gu, ws, count, backend,
                                     rows(d_y, narrow, "out_t"))
    d_narrow = stack(hidden, d_y, "out_w")
    d_wide = stack(xs, d_gu, "in_w")
    width = narrow.shape[1]
    grads = (_pair_total(d_ws, inv, count, 1).reshape(-1, k),
             d_wide[..., :width], d_wide[..., width:], d_narrow)
    return (pair_total(rows(d_gu, wide, "in_t"), source, inv, count, k,
                       backend).astype(x.dtype),
            *(a.astype(b.dtype) for a, b in zip(grads, like)),
            None, None, None)


_held_sum.defvjp(_held_sum_fwd, _held_sum_bwd)


def train_experts(x, idx, w, held, n_routed, gate, up, down, backend=None,
                  compute_dtype=jnp.bfloat16):
    """sum over a row's selected experts that are HELD of
    w * down_e(silu(gate_e x) * up_e x): x [N, D], idx, w [N, k]
    (`train_route`), stacks [n_held, D, F] / [n_held, F, D] -> ([N, D]
    float32, sizes [n_held] int32). The pairs sorted by held expert, the
    held ones' rows gathered into ONE buffer with room for all N x k pairs,
    grouped products over its live tiles, a row's pairs summed back
    (`_held_sum`); differentiable in x, w and the stacks. No routing drops a
    row and none changes a shape: the routing changes `sizes`, and with them
    how many tiles the kernels visit."""
    perm, inv, sizes = sort_pairs(idx, held, n_routed)
    out = _held_sum(x, w, gate, up, down, perm, inv, sizes, idx.shape[1],
                    backend or _auto_backend(), jnp.dtype(compute_dtype))
    return out, sizes


@register_op("moe_train")
def _moe_train_op(ctx, ins, attrs):
    """The routed layer of a training step: X [.., D], the router, the held
    experts' stacks -> Out (the held selected experts' weighted sum), Aux
    (the balance term of the layer, a scalar), and the counters the step
    keeps on the device, this step's counts added to what the scope holds:
    RowsTotal [n_held] (pairs a held expert got), PairsTotal [3] (routed,
    held, dropped: the last is 0, there is no capacity), AuxLast [1]."""
    x = ins["X"][0]
    rows = x.reshape(-1, x.shape[-1])
    held, n_routed = attrs["held"], attrs["n_routed"]
    p, idx, w = train_route(
        rows, ins["W"][0], attrs["top_k"], attrs.get("norm_topk_prob", True),
        attrs.get("scaling", 1.0))
    aux, _ = balance_term(p, idx)
    from ..core import flags
    out, sizes = train_experts(
        rows, idx, w, held, n_routed, ins["Gate"][0], ins["Up"][0],
        ins["Down"][0], backend=attrs.get("backend"),
        # bfloat16 operands like every `use_bf16` matmul of the graph, and
        # like them the stored dtype under the global kill-switch
        compute_dtype=(jnp.bfloat16 if flags.get_flag("use_bf16_matmul")
                       else ins["Gate"][0].dtype))
    # dropped: the pairs that chose a held expert less those given a row
    mine = jnp.sum(jnp.isin(idx, jnp.asarray(held, jnp.int32)),
                   dtype=jnp.int32)
    pairs = jnp.stack([jnp.int32(idx.size), jnp.sum(sizes),
                       mine - jnp.sum(sizes)])
    return {"Out": [out.reshape(x.shape).astype(x.dtype)], "Aux": [aux],
            "RowsTotalOut": [ins["RowsTotal"][0] + sizes],
            "PairsTotalOut": [ins["PairsTotal"][0] + pairs],
            "AuxLastOut": [jax.lax.stop_gradient(aux).reshape(1)]}


@register_op("moe_experts", stop_gradient=True)
def _moe_experts_op(ctx, ins, attrs):
    x = ins["X"][0]
    out = experts(x.reshape(-1, x.shape[-1]), ins["Weights"][0],
                  ins["Rows"][0],
                  ins["Gate"][0] if ins.get("Gate") else None, ins["Up"][0],
                  ins["Down"][0], backend=attrs.get("backend"),
                  limit=attrs.get("limit", 0.0))
    return {"Out": [out.reshape(x.shape).astype(x.dtype)]}
