"""Grouped queries over the paged pools (fusion/paged_attention.py), through
the kernels in interpret mode against the composite, float32 and bfloat16
pools: a decode row through the decode kernel (all key/value heads of a
group of blocks in one product, the query heads of a key/value head
block-diagonal over the lane segments, the next live slot's first group in
flight), a prefill lane through the chunk kernel (the query heads of a
key/value head as rows of its products)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.fusion import paged_attention
from paddle_tpu.fusion.paged_attention import (KERNEL,
                                               paged_attention_lowering,
                                               paged_decode_attention)

NB, NKV, NH, DH, BS, NLB, S = 12, 2, 8, 64, 16, 5, 3


def _pools(dtype, seed=0):
    rng = np.random.default_rng(seed)
    shape = (NB, NKV, BS * DH // 128, 128)
    k, v = (jnp.asarray(rng.normal(size=shape), dtype) for _ in range(2))
    table = rng.permutation(np.arange(1, NB))[:S * NLB - 4].tolist() + [0] * 4
    return rng, k, v, jnp.asarray(table).reshape(S, NLB)


def _read(q, k, v, table, pos, backend, rows=None, nh=NH):
    return paged_decode_attention(q, k, v, table, pos, nh, scale=DH ** -0.5,
                                  backend=backend, rows=rows)


# -- decode rows ------------------------------------------------------------
# Blocks of 64 positions (32 pool rows), a table of 20: the decode kernel
# takes _DECODE_KEY_ROWS // 32 blocks a step, so a slot is 1-3 steps long.
# A tick lists a position a slot; None is an idle slot (the null block
# everywhere, position 0).

_BLOCK, _TABLE = 64, 20
_STEP = paged_attention._DECODE_KEY_ROWS // (_BLOCK * DH // 128) * _BLOCK
_LAST = _BLOCK * _TABLE - 1


def _steps(n):
    """A position whose slot takes `n` steps of the decode kernel."""
    return min(n * _STEP - 7, _LAST)


_TICKS = {
    # block edges, step edges (the buffer changes), the last mapped block
    "edges": [0, 63, 64, 255, 256, _STEP - 1, _STEP, 2 * _STEP - 1,
              2 * _STEP, _LAST],
    # idle and live slots mixed, in orders that leave the next live slot's
    # first group in either buffer: odd and even numbers of steps in
    # consecutive slots, idle runs before, between and after them
    "idle-first": [None, None, _steps(1), None, _steps(2), _steps(1),
                   _steps(2), None],
    "live-first": [_steps(1), _steps(1), None, _steps(2), None, None,
                   _steps(3), _steps(1)],
    "even-then-odd": [_steps(2), _steps(2), _steps(1), _steps(1),
                      _steps(3), _steps(2), None, _steps(1)],
    "one-live-last": [None, None, None, _steps(2)],
    "one-live-first": [_steps(3), None, None, None],
    "all-idle": [None, None, None],
}


def _tick(positions, rng, n_blocks, block, table, shared=0):
    """(table, pos) of a tick: a slot's live blocks are its own physical
    blocks, but for the first `shared`, which every live slot shares."""
    free = rng.permutation(np.arange(1, n_blocks)).tolist()
    prefix = [free.pop() for _ in range(shared)]
    btab = np.zeros((len(positions), table), np.int32)
    for s, p in enumerate(positions):
        if p is not None:
            n = p // block + 1
            own = [free.pop() for _ in range(max(0, n - shared))]
            btab[s, :n] = (prefix + own)[:n]
    pos = [0 if p is None else p for p in positions]
    return jnp.asarray(btab), jnp.asarray(pos, jnp.int32)


def _decode_case(dtype, nh, positions, shared=0, seed=0, no_row=(),
                 block=_BLOCK, table=_TABLE):
    """The slots of `no_row` are live by their tables and idle by `rows`."""
    rng = np.random.default_rng(seed)
    n_blocks = 1 + sum(0 if p is None else p // block + 1 for p in positions)
    shape = (n_blocks, NKV, block * DH // 128, 128)
    k, v = (jnp.asarray(rng.normal(size=shape), dtype) for _ in range(2))
    btab, pos = _tick(positions, rng, n_blocks, block, table, shared)
    q = jnp.asarray(rng.normal(size=(len(positions), 1, nh * DH)),
                    jnp.float32)
    rows = [int(s not in no_row) for s in range(len(positions))]
    got = _read(q, k, v, btab, pos, "pallas_interpret", nh=nh,
                rows=jnp.asarray(rows) if no_row else None)
    want = _read(q, k, v, btab, pos, "xla", nh=nh)
    live = np.asarray([p is not None and r > 0
                       for p, r in zip(positions, rows)])
    assert np.isfinite(np.asarray(got)).all()
    assert not np.asarray(got)[~live].any()
    if live.any():
        assert float(jnp.abs(got - want)[live].max()) < 2e-6


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("tick", ["small-blocks"] + list(_TICKS))
@pytest.mark.parametrize("nh", [NH, 16], ids=["grp4", "grp8"])
def test_decode_rows_match_the_composite(dtype, tick, nh):
    """`grp` 4 is padded to a sublane tile of 8 rows, `grp` 8 fills it."""
    if tick == "small-blocks":          # blocks of 16: one step a slot
        _decode_case(dtype, nh, [5, 37, 70], block=BS, table=NLB)
    else:
        _decode_case(dtype, nh, _TICKS[tick])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_rows_sharing_prefix_blocks_match_the_composite(dtype):
    """Two slots whose first three blocks are the SAME physical blocks (a
    prefix hit), an idle slot between them."""
    _decode_case(dtype, NH, [200, None, 3 * _BLOCK + 5, 3 * _BLOCK - 1],
                 shared=3, seed=3)


def test_a_slot_without_a_real_row_is_idle():
    """`rows` 0 marks a slot idle whatever its table says: zeros, and the
    slots around it are read as if it were not there."""
    _decode_case(jnp.float32, NH, [_steps(1), _steps(2), _steps(1)], seed=4,
                 no_row=(1,))


# -- prefill lanes ----------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_lanes_match_the_composite_on_their_real_rows(dtype):
    rng, k, v, table = _pools(dtype, 1)
    c = 16
    q = jnp.asarray(rng.normal(size=(S, c, NH * DH)), jnp.float32)
    pos, rows = jnp.asarray([0, 32, 48]), jnp.asarray([16, 9, 0])
    got = _read(q, k, v, table, pos, "pallas_interpret", rows)
    want = _read(q, k, v, table, pos, "xla")
    real = np.arange(c)[None, :] < np.asarray(rows)[:, None]
    assert float(jnp.abs(got - want)[real].max()) < 2e-6
    assert np.isfinite(np.asarray(got)).all()


def test_the_head_map_is_i_over_group():
    """Query head i reads key/value head i // 4: zeroing key/value head 1
    changes exactly the query heads 4..7."""
    rng, k, v, table = _pools(jnp.float32, 2)
    q = jnp.asarray(rng.normal(size=(S, 1, NH * DH)), jnp.float32)
    pos = jnp.asarray([5, 37, 70])
    for backend in ("pallas_interpret", "xla"):
        a = _read(q, k, v, table, pos, backend)
        b = _read(q, k, v.at[:, 1].set(0.0), table, pos, backend)
        moved = np.abs(np.asarray(a - b)).reshape(S, NH, DH).max(-1) > 0
        assert moved[:, :4].sum() == 0 and moved[:, 4:].all()


# -- which shape takes which kernel -----------------------------------------

def test_bfloat16_pools_are_served_by_the_kernel():
    assert paged_attention_lowering("bfloat16", 128, 1, 64, False,
                                    backend="pallas") == KERNEL
    assert paged_attention_lowering("bfloat16", 128, 128, 64, False,
                                    backend="pallas") == KERNEL


def _pallas_calls(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _pallas_calls(sub)


@pytest.mark.parametrize("pool, nh, n_query, kernel, scope, result", [
    # grouped or bfloat16 pools: one position a slot -> the decode kernel,
    # whose FIRST result benchmark/kernel_ops.py keys on; a chunk -> the
    # chunk kernel, the group's heads as rows
    ((jnp.bfloat16, 8), 32, 1, "_decode_kernel", "paged_gqa_attention",
     (jnp.float32, (6, 8, 8, 128))),
    ((jnp.float32, 8), 32, 1, "_decode_kernel", "paged_gqa_attention",
     (jnp.float32, (6, 8, 8, 128))),
    ((jnp.bfloat16, 8), 8, 1, "_decode_kernel", "paged_gqa_attention",
     (jnp.float32, (6, 8, 8, 128))),
    ((jnp.bfloat16, 8), 32, 8, "_chunk_kernel", "paged_gqa_attention",
     (jnp.float32, (6, 8, 32, 128))),
    ((jnp.bfloat16, 8), 32, 128, "_chunk_kernel", "paged_gqa_attention",
     (jnp.float32, (6, 8, 512, 128))),
    # the classic pools keep their kernels
    ((jnp.float32, 8), 8, 1, "_paged_kernel", "paged_decode_attention",
     (jnp.float32, (6, 8, 1, 128))),
    ((jnp.float32, 8), 8, 16, "_chunk_kernel", "paged_chunk_attention",
     (jnp.float32, (6, 8, 16, 128))),
])
def test_the_shape_selects_the_kernel(pool, nh, n_query, kernel, scope,
                                      result):
    """`n_query`, the pool's dtype and the head counts choose, and nothing
    else; the decode call's result is f32[S, nkv, 8, 128] in the scope
    `paged_gqa_attention`."""
    dtype, nkv = pool
    pool = jax.ShapeDtypeStruct((16, nkv, 32, 128), dtype)
    jaxpr = jax.make_jaxpr(
        lambda q, k, v, t, p: paged_decode_attention(
            q, k, v, t, p, nh, scale=0.125, backend="pallas"))(
        jax.ShapeDtypeStruct((6, n_query, nh * DH), jnp.float32), pool, pool,
        jax.ShapeDtypeStruct((6, 4), jnp.int32),
        jax.ShapeDtypeStruct((6,), jnp.int32))
    (call,) = _pallas_calls(jaxpr.jaxpr)
    out = call.outvars[0].aval
    assert call.params["jaxpr"].debug_info.func_name == kernel
    assert str(call.source_info.name_stack).split("/")[-1] == scope
    assert (out.dtype, out.shape) == result
