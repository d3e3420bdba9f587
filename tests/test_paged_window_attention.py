"""`paged_decode_attention(window=...)` (ISSUE 45): the two grouped kernels
bounded by a sliding window (`_decode_kernel`, one query row; `_chunk_kernel`,
a lane's chunk), interpreted, against the composite, whose window is a mask
over the dense table view, at the window's edges. The table maps ONLY the
blocks a position of some row's window lies in, as the pager leaves it: a
kernel that looked below the window's first block would read the null
block."""

import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.fusion.paged_attention import (_paged_composite,
                                               paged_decode_attention)

W, BS, DH, NH, NKV = 128, 64, 128, 16, 2
NLB, NB = 8, 40


def _case(positions, rows, window, g=1, seed=0, dh=DH, bs=BS, nlb=NLB,
          dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    n = len(positions)
    shape = (NB, NKV, bs * dh // 128, 128)
    k_pool, v_pool = (jnp.asarray(rng.normal(size=shape), dtype)
                      for _ in range(2))
    q = jnp.asarray(rng.normal(size=(n, g, NH * dh)), jnp.float32)
    btab = np.zeros((n, nlb), np.int32)
    ids = iter(rng.permutation(np.arange(1, NB)).tolist() * 4)
    for s, (p, r) in enumerate(zip(positions, rows)):
        if r <= 0:
            continue
        first = max(p - (window - 1), 0) // bs if window else 0
        for j in range(first, (p + r - 1) // bs + 1):
            btab[s, j] = next(ids)
    return (q, k_pool, v_pool, jnp.asarray(btab),
            jnp.asarray(positions, jnp.int32)), jnp.asarray(rows, jnp.int32)


def _both(args, rows, window, dh=DH):
    kw = dict(scale=dh ** -0.5, rows=rows, window=window)
    return (paged_decode_attention(*args, NH, backend="xla", **kw),
            paged_decode_attention(*args, NH, backend="pallas_interpret",
                                   **kw))


@pytest.mark.parametrize("positions", [
    (0, 5, 63, 64),                 # inside the first window
    (126, 127, 128, 129),           # pos < 128, = 127, = 128: the edge
    (190, 191, 192, 255),           # a block boundary inside the window
    (300, 421, 447, 511),           # block-unaligned, far past the window
])
def test_the_decode_kernel_reads_the_window_and_nothing_below(positions):
    args, rows = _case(positions, [1] * 4, W)
    want, got = _both(args, rows, W)
    np.testing.assert_allclose(got, want, atol=2e-5)
    # and the window is not the whole cache: past it the full read differs
    full_args, _ = _case(positions, [1] * 4, 0)
    full = paged_decode_attention(*full_args, NH, backend="xla",
                                  scale=DH ** -0.5, rows=rows)
    far = [i for i, p in enumerate(positions) if p >= W]
    if far:
        assert float(jnp.abs(full[jnp.asarray(far)]
                             - want[jnp.asarray(far)]).max()) > 1e-2


def test_an_idle_slot_of_the_window_table_costs_nothing():
    args, rows = _case((200, 0, 77, 0), [1, 0, 1, 0], W)
    want, got = _both(args, rows, W)
    live = jnp.asarray([0, 2])
    np.testing.assert_allclose(got[live], want[live], atol=2e-5)
    assert bool(jnp.isfinite(got).all())


@pytest.mark.parametrize("positions, rows", [
    ((0, 64), (128, 100)),          # the first chunks: the window not full
    ((128, 320), (128, 128)),       # row g sees pos + g - 127 .. pos + g
    ((192, 384), (17, 64)),         # short last chunks
])
def test_the_chunk_kernel_masks_each_row_to_its_own_window(positions, rows):
    args, r = _case(positions, rows, W, g=128, seed=1)
    want, got = _both(args, r, W)
    for s, n in enumerate(rows):
        np.testing.assert_allclose(got[s, :n], want[s, :n], atol=3e-5)


@pytest.mark.parametrize("window", [24, 16, 7])
def test_windows_that_are_no_multiple_of_the_block(window):
    """Heads of 32 (four positions a pool row), blocks of 16: windows that
    end mid-block and mid-row."""
    args, rows = _case((0, 5, 23, 24, 77, 100), [1] * 6, window, dh=32,
                       bs=16, seed=2)
    want, got = _both(args, rows, window, dh=32)
    np.testing.assert_allclose(got, want, atol=2e-5)
    args, rows = _case((0, 16, 64), [16, 9, 16], window, g=16, dh=32, bs=16,
                       seed=3)
    want, got = _both(args, rows, window, dh=32)
    for s, n in enumerate((16, 9, 16)):
        np.testing.assert_allclose(got[s, :n], want[s, :n], atol=2e-5)


def test_window_off_by_one_is_seen():
    """The comparison these tests make tells a window of 128 from one of 127
    or 129: the planted fault fails."""
    args, rows = _case((300, 421), [1, 1], W)
    want, _ = _both(args, rows, W)
    for wrong in (W - 1, W + 1):
        # a window of 129 starts a block earlier only at an aligned edge;
        # map what it would read so that the fault is in the mask alone
        q, k, v, btab, pos = args
        wide = np.asarray(btab).copy()
        wide[wide == 0] = 1
        got = paged_decode_attention(q, k, v, jnp.asarray(wide), pos, NH,
                                     scale=DH ** -0.5, rows=rows,
                                     backend="pallas_interpret",
                                     window=wrong)
        assert float(jnp.abs(got - want).max()) > 1e-3


def test_no_window_is_the_read_it_was():
    """`window` 0 takes the code it took: the kernels' static arguments and
    the composite's mask are those of a call without the argument."""
    args, rows = _case((5, 200), [1, 1], 0)
    a = paged_decode_attention(*args, NH, backend="pallas_interpret",
                               scale=DH ** -0.5, rows=rows)
    b = paged_decode_attention(*args, NH, backend="pallas_interpret",
                               scale=DH ** -0.5, rows=rows, window=0)
    assert bool((a == b).all())
    q4 = args[0].reshape(2, 1, NH, DH).transpose(0, 2, 1, 3)
    c = _paged_composite(q4, *args[1:], DH ** -0.5, None, None)
    d = _paged_composite(q4, *args[1:], DH ** -0.5, None, None, 0)
    assert bool((c == d).all())


def test_bfloat16_pools_take_the_window_too():
    args, rows = _case((127, 128, 400), [1] * 3, W, dtype=jnp.bfloat16)
    want, got = _both(args, rows, W)
    np.testing.assert_allclose(got, want, atol=2e-5)
