"""The gated short convolution's causal part and its state
(fusion/short_conv.py): the whole-sequence convolution, a token at a time
through a slot's state, and chunks that carry the state through the blocks'
snapshots are the same numbers."""

import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.fusion import short_conv as sc

D, K, T = 16, 3, 20


def _case(seed=0):
    rng = np.random.default_rng(seed)
    u = jnp.asarray(rng.normal(size=(T, D)), jnp.float32)
    taps = jnp.asarray(rng.normal(size=(D, K)), jnp.float32)
    ext = jnp.concatenate([jnp.zeros((K - 1, D)), u])
    whole = sum(taps[:, j] * ext[j:j + T] for j in range(K))
    return u, taps, whole


def test_a_token_at_a_time_through_the_slots_state():
    u, taps, whole = _case()
    # two conv layers' worth of state, three slots; slot 1 is the request
    state = jnp.zeros((3, 2, K - 1, D))
    live = jnp.asarray([0, 1, 0])
    got = []
    for t in range(T):
        rows = jnp.zeros((3, D)).at[1].set(u[t])
        c, new_d, _, _ = sc.short_conv(rows, taps, state, 1, 3)
        state, _ = sc.commit(
            state, jnp.stack([jnp.zeros_like(new_d), new_d], axis=1), live)
        got.append(c[1])
    np.testing.assert_array_equal(np.stack(got), np.asarray(whole))
    # dead slots keep their state, layer 0 of the live one took zeros
    assert not np.asarray(state[0]).any() and not np.asarray(state[2]).any()
    np.testing.assert_array_equal(np.asarray(state[1, 1]),
                                  np.asarray(u[T - (K - 1):]))


@pytest.mark.parametrize("chunk", [1, 2, 5])
def test_chunks_carry_the_state_through_the_blocks_snapshots(chunk):
    """One lane, a chunk = one block of `chunk` positions: every chunk starts
    from the snapshot of the block before it in the request's table."""
    u, taps, whole = _case(chunk)
    n_blocks = T // chunk + 1
    table = jnp.arange(1, n_blocks + 1)[None, :]        # block j+1 holds j
    slot = jnp.zeros((2, 1, K - 1, D))
    block = jnp.full((n_blocks + 1, 1, K - 1, D), 7.0)  # garbage until written
    got = []
    for j in range(T // chunk):
        rows = jnp.concatenate([jnp.zeros((2, D)),
                                u[j * chunk:(j + 1) * chunk]])
        lanes = (block, table, jnp.asarray([j * chunk]),
                 jnp.asarray([chunk]), chunk, chunk)
        c, new_d, snaps, last = sc.short_conv(rows, taps, slot, 0, 2, lanes)
        slot, block = sc.commit(
            slot, new_d[:, None], jnp.zeros(2),
            (block, snaps[:, None], last[:, None], table[0, j:j + 1],
             jnp.asarray([chunk]), jnp.asarray([1]), chunk))
        got.append(c[2:])
    np.testing.assert_allclose(np.concatenate(got), np.asarray(whole),
                               rtol=0, atol=1e-6)
    # the lane left its last state in slot 1; slot 0 was never touched
    np.testing.assert_array_equal(np.asarray(slot[1, 0]),
                                  np.asarray(u[T - (K - 1):]))
    assert not np.asarray(slot[0]).any()


def test_a_short_chunk_hands_over_to_the_decode_rows():
    """A chunk of two blocks of 4 with 6 real rows: the first block gets its
    snapshot, the second (not filled) none, and the slot the state after row
    5, from which decode rows go on."""
    u, taps, whole = _case(3)
    bs, chunk = 4, 8
    table = jnp.asarray([[3, 5, 0]])
    slot = jnp.zeros((1, 1, K - 1, D))
    block = jnp.zeros((6, 1, K - 1, D))
    rows = jnp.concatenate([jnp.zeros((1, D)), u[:6], jnp.full((2, D), 9.0)])
    lanes = (block, table, jnp.asarray([0]), jnp.asarray([6]), chunk, bs)
    c, new_d, snaps, last = sc.short_conv(rows, taps, slot, 0, 1, lanes)
    slot, block = sc.commit(
        slot, new_d[:, None], jnp.zeros(1),
        (block, snaps[:, None], last[:, None], jnp.asarray([3, 5]),
         jnp.asarray([6]), jnp.asarray([0]), bs))
    np.testing.assert_allclose(np.asarray(c[1:7]), np.asarray(whole[:6]),
                               atol=1e-6)
    np.testing.assert_array_equal(np.asarray(block[3, 0]), np.asarray(u[2:4]))
    assert not np.asarray(block[5]).any()       # went to the null block
    got = []
    for t in range(6, T):
        c, new_d, _, _ = sc.short_conv(u[t:t + 1], taps, slot, 0, 1)
        slot, _ = sc.commit(slot, new_d[:, None], jnp.ones(1))
        got.append(c[0])
    np.testing.assert_allclose(np.stack(got), np.asarray(whole[6:]),
                               atol=1e-6)


def test_an_idle_lane_changes_nothing():
    _, taps, _ = _case()
    slot = jnp.ones((2, 1, K - 1, D))
    block = jnp.ones((4, 1, K - 1, D))
    lanes = (block, jnp.zeros((1, 3), jnp.int32), jnp.asarray([0]),
             jnp.asarray([0]), 4, 4)
    _, new_d, snaps, last = sc.short_conv(jnp.zeros((2 + 4, D)), taps, slot,
                                          0, 2, lanes)
    s2, b2 = sc.commit(slot, new_d[:, None], jnp.zeros(2),
                       (block, snaps[:, None], last[:, None],
                        jnp.asarray([0]), jnp.asarray([0]), jnp.asarray([0]),
                        4))
    np.testing.assert_array_equal(np.asarray(s2), np.asarray(slot))
    np.testing.assert_array_equal(np.asarray(b2[1:]), np.asarray(block[1:]))
