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
elsewhere; over a pair buffer with room for an even routing's held pairs
and a quarter more, or by `lax.cond` over the buffer of all the pairs, so no
row is ever dropped and no count changes a shape), with counters the
step keeps on the device. docs/fusion.md has the section. The two ops above
stay what the serving ticks use: their kernel is the decode shape, and they
carry no gradient.

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
from .decode_attention import _auto_backend

KERNEL, COMPOSITE = "kernel", "composite"


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


def _experts_composite(x, w, gate, up, down):
    xf = x.astype(gate.dtype)
    g = jnp.einsum("nd,edf->enf", xf, gate, preferred_element_type=jnp.float32)
    u = jnp.einsum("nd,edf->enf", xf, up, preferred_element_type=jnp.float32)
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
                    o_ref):
    def hidden(x):
        g = jnp.dot(x, g_ref[0], preferred_element_type=jnp.float32)
        u = jnp.dot(x, u_ref[0], preferred_element_type=jnp.float32)
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


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def _walk_pallas(x, w, touched, stacks, tile, interpret):
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
            _experts_kernel if gated else _relu2_kernel,
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


def experts(x, w, rows, gate, up, down, backend=None):
    """x [N, D]; w [n_held, N, 1] float32 (`route`); rows [n_held]; gate,
    up [n_held, D, F]; down [n_held, F, D] -> [N, D] float32. `gate` None:
    the two-matrix form, `down_e(relu(up_e x)^2)`."""
    stacks = (up, down) if gate is None else (gate, up, down)
    tile = experts_tile(*x.shape, up.shape[-1], up.dtype.itemsize,
                        len(stacks))
    if experts_lowering(*x.shape, up.shape[-1], backend, tile) == KERNEL:
        return _walk_pallas(x, w, rows, stacks, tile=tile,
                            interpret=backend == "pallas_interpret")
    if gate is None:
        return _relu2_composite(x, w, up, down)
    return _experts_composite(x, w, gate, up, down)


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


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _grouped(lhs, rhs, sizes, role, transpose_rhs, interpret):
    """lhs[rows of group e] @ rhs[e] for the groups laid one after another
    from row 0 (`sizes` rows each; the kernel leaves the rows past their sum
    as they lie in memory: they come back 0), by megablox's kernel under
    `_TILINGS[role]`; lhs's dtype out, float32 sums."""
    with jax.named_scope("moe_train_" + role):
        out = _megablox().gmm(lhs, rhs, sizes, lhs.dtype, _TILINGS[role],
                              transpose_rhs=transpose_rhs,
                              interpret=interpret)
    live = jnp.arange(lhs.shape[0])[:, None] < jnp.sum(sizes)
    return jnp.where(live, out, jnp.zeros((), out.dtype))


def _grouped_fwd(lhs, rhs, sizes, role, transpose_rhs, interpret):
    return (_grouped(lhs, rhs, sizes, role, transpose_rhs, interpret),
            (lhs, rhs, sizes))


def _grouped_bwd(role, transpose_rhs, interpret, res, g):
    lhs, rhs, sizes = res
    assert not transpose_rhs
    d_lhs = _grouped(g, rhs, sizes, role + "_t", True, interpret)
    with jax.named_scope("moe_train_" + role + "_w"):
        d_rhs = _megablox().tgmm(lhs.swapaxes(0, 1), g, sizes, rhs.dtype,
                                 _TILINGS[role + "_w"], interpret=interpret)
    return d_lhs, d_rhs, None


_grouped.defvjp(_grouped_fwd, _grouped_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _expand(x, perm, inv, k):
    """x [N, D] -> the rows of the sorted pairs `perm` holds, [m, D]: pair
    `perm[i]` is row `perm[i] // k`. Its transpose is `_combine` (a gather
    too: the derivative XLA would write is a scatter-add over the rows)."""
    return x[jnp.minimum(perm // k, x.shape[0] - 1)]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _combine(y, perm, inv, k):
    """The sorted pairs' rows y [m, D] -> [N, D] float32: a row's k pairs
    summed (`inv[p]`: where pair p lies in the sorted order; a pair past the
    m rows y has adds nothing)."""
    m = y.shape[0]
    rows = jnp.where((inv < m)[:, None], y[jnp.minimum(inv, m - 1)],
                     jnp.zeros((), y.dtype)).astype(jnp.float32)
    return jnp.sum(rows.reshape(-1, k, y.shape[-1]), axis=1)


def _expand_fwd(x, perm, inv, k):
    return _expand(x, perm, inv, k), (perm, inv)


def _expand_bwd(k, res, g):
    perm, inv = res
    return _combine(g, perm, inv, k).astype(g.dtype), None, None


def _combine_fwd(y, perm, inv, k):
    # an empty array carries y's dtype: a dtype is no residual JAX takes
    return _combine(y, perm, inv, k), (perm, inv, jnp.zeros((0,), y.dtype))


def _combine_bwd(k, res, g):
    perm, inv, like = res
    return _expand(g.astype(like.dtype), perm, inv, k), None, None


_expand.defvjp(_expand_fwd, _expand_bwd)
_combine.defvjp(_combine_fwd, _combine_bwd)


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


def _pair_rows(n_pairs, n_held, n_routed):
    """The rows of the two pair buffers, whole tiles of `_PAIR_TILE`: the
    held pairs an even routing makes and a quarter more (seeded routers are
    not even: PERF.md section 6, PR 50), and ALL the pairs, which no routing
    overflows. Both are in the program: nothing is dropped and no count
    changes a shape."""
    even = n_pairs * n_held // n_routed
    first, whole = (-(-n // _PAIR_TILE) * _PAIR_TILE
                    for n in (even + even // 4, n_pairs))
    return (first, whole) if first < whole else (whole,)


@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9, 10, 11))
def _held_sum(x, w, gate, up, down, perm, inv, sizes, k, rows, backend,
              compute_dtype):
    """`train_experts` on sorted pairs, over the first of the buffers `rows`
    that holds the held pairs. Its backward keeps none of the buffer's rows:
    it gathers and multiplies them again from x (one more [gate | up]
    product a layer, for the gathered rows, the hidden rows and their
    products' outputs of every layer not held from the forward to the
    backward)."""
    return _by_buffer(sizes, rows, lambda m: _pair_sum(
        x, w, gate, up, down, perm, inv, sizes, k, m, backend,
        compute_dtype))


def _by_buffer(sizes, rows, run):
    if len(rows) == 1:
        return run(rows[0])
    return jax.lax.cond(jnp.sum(sizes) > rows[0],
                        functools.partial(run, rows[1]),
                        functools.partial(run, rows[0]))


def _pair_sum(x, w, gate, up, down, perm, inv, sizes, k, m, backend,
              compute_dtype):
    """The layer over the first m sorted pairs (every held one is among
    them): their rows gathered, [gate | up] as ONE grouped product, the
    weighted hidden rows through down, a row's pairs summed back. The
    products are megablox's kernels, or under backend "xla" (off a TPU)
    `jax.lax.ragged_dot`: XLA's own lowering of it reached 14% of the chip's
    peak at the training cell's shape where the kernels reach 60% (PERF.md
    section 6, PR 50)."""
    perm = perm[:m]
    xs = _expand(x.astype(compute_dtype), perm, inv, k)
    ws = _expand(w.reshape(-1, 1), perm, inv, 1)
    width = gate.shape[-1]
    wide = jnp.concatenate([gate, up], axis=-1).astype(compute_dtype)
    narrow = down.astype(compute_dtype)
    if backend == "xla":
        def grouped(a, b, role):
            return jax.lax.ragged_dot(
                a, b, sizes, preferred_element_type=jnp.float32
            ).astype(compute_dtype)
    else:
        def grouped(a, b, role):
            return _grouped(a, b, sizes, role, False,
                            backend == "pallas_interpret")
    # the rows past the held pairs are 0 out of either product
    gu = grouped(xs, wide, "in").astype(jnp.float32)
    hidden = jax.nn.silu(gu[:, :width]) * gu[:, width:] * ws
    y = grouped(hidden.astype(compute_dtype), narrow, "out")
    return _combine(y, perm, inv, k)


def _held_sum_fwd(x, w, gate, up, down, perm, inv, sizes, k, rows, backend,
                  compute_dtype):
    out = _held_sum(x, w, gate, up, down, perm, inv, sizes, k, rows, backend,
                    compute_dtype)
    return out, (x, w, gate, up, down, perm, inv, sizes)


def _held_sum_bwd(k, rows, backend, compute_dtype, res, g):
    x, w, gate, up, down, perm, inv, sizes = res

    def back(m):
        _, vjp = jax.vjp(
            lambda *a: _pair_sum(*a, perm, inv, sizes, k, m, backend,
                                 compute_dtype), x, w, gate, up, down)
        return vjp(g)

    return _by_buffer(sizes, rows, back) + (None, None, None)


_held_sum.defvjp(_held_sum_fwd, _held_sum_bwd)


def train_experts(x, idx, w, held, n_routed, gate, up, down, backend=None,
                  compute_dtype=jnp.bfloat16):
    """sum over a row's selected experts that are HELD of
    w * down_e(silu(gate_e x) * up_e x): x [N, D], idx, w [N, k]
    (`train_route`), stacks [n_held, D, F] / [n_held, F, D] -> ([N, D]
    float32, sizes [n_held] int32). The pairs sorted by held expert, the
    held ones' rows gathered into a buffer, grouped products over it, a
    row's pairs summed back (`_pair_sum`); differentiable in x, w and the
    stacks. The buffer is the first of `_pair_rows`' two that holds the held
    pairs: room for an even routing's and a quarter more, else all N x k
    pairs, so no routing drops a row. Static shapes: the routing changes
    `sizes` and which buffer a step takes, not the program."""
    perm, inv, sizes = sort_pairs(idx, held, n_routed)
    rows = _pair_rows(idx.size, len(held), n_routed)
    out = _held_sum(x, w, gate, up, down, perm, inv, sizes, idx.shape[1],
                    rows, backend or _auto_backend(),
                    jnp.dtype(compute_dtype))
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
                  ins["Down"][0], backend=attrs.get("backend"))
    return {"Out": [out.reshape(x.shape).astype(x.dtype)]}
