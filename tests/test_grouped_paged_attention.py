"""Grouped queries over the paged pools (fusion/paged_attention.py): the
query heads of a key/value head as rows of one product, through the kernel in
interpret mode against the composite, decode rows and prefill lanes, float32
and bfloat16 pools."""

import jax.numpy as jnp
import numpy as np
import pytest

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


def _read(q, k, v, table, pos, backend, rows=None):
    return paged_decode_attention(q, k, v, table, pos, NH, scale=DH ** -0.5,
                                  backend=backend, rows=rows)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_rows_match_the_composite(dtype):
    rng, k, v, table = _pools(dtype)
    q = jnp.asarray(rng.normal(size=(S, 1, NH * DH)), jnp.float32)
    pos = jnp.asarray([5, 37, 70])
    got = _read(q, k, v, table, pos, "pallas_interpret")
    want = _read(q, k, v, table, pos, "xla")
    assert float(jnp.abs(got - want).max()) < 2e-6


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


def test_bfloat16_pools_are_served_by_the_kernel():
    assert paged_attention_lowering("bfloat16", 128, 1, 64, False,
                                    backend="pallas") == KERNEL
    assert paged_attention_lowering("bfloat16", 128, 128, 64, False,
                                    backend="pallas") == KERNEL
