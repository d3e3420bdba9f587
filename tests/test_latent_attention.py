"""Latent attention on the latent itself (ISSUE 36): the absorbed read over
the padded latent rows equals attention with K and V expanded through
`kv_b_proj`; the kernels (interpreted: the lanes' body and the decode body,
ISSUE 42) equal the composite; YaRN's table equals the reference's angles
past the original length; the pool is written and read through a block
table; a prefix hit and a miss give the same logits."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tiny_engines as E
from axk1_tiny import TINY as T, axk1, ref
from test_grouped_paged_attention import _pallas_calls
from paddle_tpu.fusion import latent_attention as la
from paddle_tpu.models import transformer
from paddle_tpu.models.decoder_spec import LatentSpec, RopeSpec
from paddle_tpu.ops.tensor_ops import _write_pool_blocks, _write_pool_rows

NH, C, DR, DN, DV = 8, 128, 64, 16, 16
LAT = LatentSpec(q_lora_rank=32, kv_lora_rank=C, qk_nope_head_dim=DN,
                 v_head_dim=DV, rope=RopeSpec(dim=DR))
W = LAT.row_lanes                      # 192 values padded to 256
BS, NLB, NB = 16, 6, 24


def _pool_and_table(rng, n_slots, bs=BS, nlb=NLB, nb=NB):
    rows = rng.normal(size=(nb, 1, bs, W)).astype(np.float32)
    rows[..., LAT.row_values:] = 0.0
    btab = np.stack([rng.permutation(np.arange(1, nb))[:nlb]
                     for _ in range(n_slots)])
    return jnp.asarray(rows), jnp.asarray(btab)


def _padded(q_lat, q_pe):
    s, g = q_lat.shape[:2]
    pad = np.zeros((s, g, NH, W - LAT.row_values), np.float32)
    return jnp.asarray(np.concatenate([q_lat, q_pe, pad], -1)
                       .reshape(s, g, NH * W))


def test_the_row_is_padded_to_whole_lanes_and_says_so():
    assert (LAT.row_values, LAT.row_lanes) == (192, 256)
    full = LatentSpec(1536, 512, 128, 128, RopeSpec(dim=64))
    assert (full.row_values, full.row_lanes) == (576, 640)
    assert full.softmax_scale == pytest.approx(192 ** -0.5)
    yarn = LatentSpec(1536, 512, 128, 128, RopeSpec(
        dim=64, factor=32.0, mscale=1.0, mscale_all_dim=1.0))
    assert yarn.rope.table_scale == pytest.approx(1.0)
    assert yarn.softmax_scale == pytest.approx(192 ** -0.5 * 1.3466 ** 2,
                                               rel=1e-4)


@pytest.mark.parametrize("g, pos, rows, table", [
    (1, [5, 40, 0], None, {}), (16, [16, 32, 0], [16, 9, 0], {}),
    # the decode body over two whole groups of 1024 keys and a partly dead
    # one, over one group ending on its boundary, and at position 0
    (1, [2300, 1023, 0], None, dict(bs=64, nlb=40, nb=48))])
def test_absorbed_read_equals_expanded_attention(g, pos, rows, table):
    """score = q~ . c_kv + q_pe . k_pe with q~_h = W_k_h q_nope_h, and the
    value half applied after the read, against K and V expanded a head at a
    time and plain softmax attention."""
    rng = np.random.default_rng(3)
    s = len(pos)
    pool, btab = _pool_and_table(rng, s, **table)
    kv_b = rng.normal(size=(C, NH, DN + DV)).astype(np.float32) * C ** -0.5
    q_nope = rng.normal(size=(s, g, NH, DN)).astype(np.float32)
    q_pe = rng.normal(size=(s, g, NH, DR)).astype(np.float32)
    scale = 0.2
    q_lat = np.einsum("sghd,chd->sghc", q_nope, kv_b[..., :DN])
    rows_a = None if rows is None else jnp.asarray(rows)
    outs = {}
    for backend in ("xla", "pallas_interpret"):
        ctx = la.latent_paged_attention(
            _padded(q_lat, q_pe), pool, btab, jnp.asarray(pos), NH, C, scale,
            rows=rows_a, backend=backend)
        ctx = np.asarray(ctx).reshape(s, g, NH, C)
        outs[backend] = np.einsum("sghc,chd->sghd", ctx, kv_b[..., DN:])
    view = np.asarray(pool)[np.asarray(btab)].reshape(s, -1, W)
    for i in range(s):
        n = g if rows is None else rows[i]
        for j in range(n):
            t = pos[i] + j + 1                      # keys 0..pos+j
            c_kv, k_pe = view[i, :t, :C], view[i, :t, C:C + DR]
            k = np.einsum("tc,chd->thd", c_kv, kv_b[..., :DN])
            v = np.einsum("tc,chd->thd", c_kv, kv_b[..., DN:])
            sc = (np.einsum("hd,thd->ht", q_nope[i, j], k)
                  + np.einsum("hd,td->ht", q_pe[i, j], k_pe)) * scale
            p = np.exp(sc - sc.max(-1, keepdims=True))
            want = np.einsum("ht,thd->hd", p / p.sum(-1, keepdims=True), v)
            for backend, got in outs.items():
                np.testing.assert_allclose(got[i, j], want, atol=2e-5,
                                           err_msg=f"{backend} {i} {j}")
    assert all(np.isfinite(o).all() for o in outs.values())


# the decode body's edges, in units of its group (1024 keys = 16 blocks of 64)
# and its chunk (512 keys). A slot: (position, real rows, on the null block).
_LIVE1, _LIVE2, _LIVE3 = (700, 1, False), (1500, 1, False), (2300, 1, False)
_IDLE, _NULL = (900, 0, False), (0, 1, True)
_DECODE_CASES = {
    "one_group_one_chunk": [(5, 1, False), (511, 1, False), (63, 1, False),
                            (64, 1, False)],
    "one_group_two_chunks": [(512, 1, False), _LIVE1, (1000, 1, False),
                             (513, 1, False)],
    "last_group_partly_dead": [(1024, 1, False), _LIVE2, _LIVE3,
                               (2559, 1, False)],
    "ends_on_a_group_boundary": [(1023, 1, False), (2047, 1, False),
                                 (1535, 1, False), (2048, 1, False)],
    "position_0": [(0, 1, False), (0, 1, False), (1, 1, False), _LIVE1],
    "idle_between_live": [_LIVE2, _IDLE, _NULL, _LIVE1],
    "only_live_slot_last": [_IDLE, _NULL, _IDLE, _LIVE3],
    "only_live_slot_first": [_LIVE3, _NULL, _IDLE, _NULL],
    "all_idle": [_IDLE, _NULL, _NULL, _IDLE],
    # one, two and three groups before a slot boundary: the parity of the
    # buffer the next live slot's first group lands in
    "odd_groups_then_a_slot": [_LIVE1, _LIVE2, _LIVE3, _LIVE1],
    "even_groups_then_a_slot": [_LIVE2, _LIVE2, _LIVE1, _LIVE3],
    "three_groups_idle_then_a_slot": [_LIVE3, _IDLE, _LIVE3, _LIVE2],
}


@pytest.mark.parametrize("case", sorted(_DECODE_CASES))
def test_decode_read_matches_the_composite(case):
    """One position a slot through `_latent_decode_kernel` (interpreted,
    float32 pool) against the composite: live slots' rows equal, idle slots
    (no real row, or position 0 on the null block) zeros, all finite."""
    slots = _DECODE_CASES[case]
    rng = np.random.default_rng(sum(map(ord, case)))
    pool, btab = _pool_and_table(rng, len(slots), bs=64, nlb=40, nb=64)
    pos = jnp.asarray([p for p, _, _ in slots])
    rows = jnp.asarray([r for _, r, _ in slots])
    null = np.asarray([n for _, _, n in slots])
    btab = jnp.where(null[:, None], 0, btab)
    q = jnp.asarray(rng.normal(size=(len(slots), 1, NH * W)), jnp.float32)
    got, want = (np.asarray(la.latent_paged_attention(
        q, pool, btab, pos, NH, C, 0.2, rows=rows, backend=backend))
        for backend in ("pallas_interpret", "xla"))
    live = np.asarray([r > 0 and not n for _, r, n in slots])
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got[live], want[live], atol=2e-5)
    assert not got[~live].any()


def test_decode_read_over_a_table_shorter_than_a_group():
    """Six blocks of 16: the group is the whole table and the chunk divides
    it; positions in the first and the last block, and one idle slot."""
    rng = np.random.default_rng(8)
    pool, btab = _pool_and_table(rng, 3)
    q = jnp.asarray(rng.normal(size=(3, 1, NH * W)), jnp.float32)
    pos, rows = jnp.asarray([95, 3, 50]), jnp.asarray([1, 1, 0])
    got, want = (np.asarray(la.latent_paged_attention(
        q, pool, btab, pos, NH, C, 0.2, rows=rows, backend=backend))
        for backend in ("pallas_interpret", "xla"))
    np.testing.assert_allclose(got[:2], want[:2], atol=2e-5)
    assert not got[2].any()


@pytest.mark.parametrize("n_query, kernel, grid", [
    (1, "_latent_decode_kernel", (6,)), (128, "_latent_kernel", (6, 16))])
def test_the_shape_selects_the_body(n_query, kernel, grid):
    """One position a slot takes the decode body, whole tiles of positions
    the lanes' body; both are ONE call in the scope the benchmark looks
    for, the decode call's result `[S, num_heads, v_width]`."""
    S = jax.ShapeDtypeStruct
    jaxpr = jax.make_jaxpr(lambda q, pool, t, p: la.latent_paged_attention(
        q, pool, t, p, 64, 512, 0.13, backend="pallas"))(
        S((6, n_query, 64 * 640), jnp.bfloat16),
        S((32, 1, 64, 640), jnp.bfloat16), S((6, 8), jnp.int32),
        S((6,), jnp.int32))
    (call,) = _pallas_calls(jaxpr.jaxpr)
    out = call.outvars[0].aval
    assert call.params["jaxpr"].debug_info.func_name == kernel
    assert call.params["grid_mapping"].grid == grid
    assert str(call.source_info.name_stack).split("/")[-1] == \
        "latent_paged_attention"
    assert (out.dtype, out.shape) == (jnp.bfloat16, (6, 64 * n_query, 512))


def test_lowering_is_chosen_by_shape_and_backend():
    assert la.latent_attention_lowering(640, 512, 64, 1, "pallas") == la.KERNEL
    assert la.latent_attention_lowering(640, 512, 64, 128, "pallas") == la.KERNEL
    # which body of the kernel serves which: test_the_shape_selects_the_body
    assert la.latent_attention_body(1) is la._latent_decode_pallas
    assert la.latent_attention_body(128) is la._latent_pallas
    assert la.latent_attention_lowering(576, 512, 64, 1, "xla") == la.COMPOSITE
    # on a CPU a shape no kernel serves takes the composite
    assert la.latent_attention_lowering(576, 512, 64, 1) == la.COMPOSITE
    assert la.latent_attention_lowering(640, 512, 64, 3) == la.COMPOSITE


def test_yarn_table_equals_the_references_angles_past_the_original_length():
    sc = dict(type="yarn", factor=32, beta_fast=32, beta_slow=1, mscale=1,
              mscale_all_dim=1, original_max_position_embeddings=4096)
    rope = axk1.spec_of(T.cfg(qk_rope_head_dim=64, rope_scaling=sc)).latent.rope
    table = transformer.rotary_table(rope, 17408)
    cos, sin = ref.rope_cos_sin(17408, 64, 10000, sc)
    np.testing.assert_allclose(table[:, :32], cos, atol=1e-6)
    np.testing.assert_allclose(table[:, 32:], sin, atol=1e-6)
    # the blend: the fastest frequencies keep theta_i, the slowest are
    # stretched 32-fold, and positions past 4096 are not periodic copies
    freq = ref.yarn_inv_freq(64, 10000, sc)
    plain = ref.yarn_inv_freq(64, 10000, None)
    assert freq[0] == plain[0] and freq[-1] == pytest.approx(plain[-1] / 32)
    assert np.all(np.diff(freq) < 0)
    assert not np.allclose(table[4096 + 7], table[7], atol=1e-3)
    # the rotary op turns a row by its position's angles, float32 inside
    from paddle_tpu.ops.nn_ops import _rotary
    x = np.random.default_rng(0).normal(size=(3, 2 * 64)).astype(np.float32)
    pos = np.asarray([0, 5000, 17000])
    got = np.asarray(_rotary(None, {"X": [jnp.asarray(x)],
                                    "Pos": [jnp.asarray(pos)],
                                    "Table": [jnp.asarray(table)]},
                             {})["Out"][0]).reshape(3, 2, 64)
    want = np.asarray(ref.rope(jnp.asarray(x.reshape(3, 2, 64)),
                               jnp.asarray(cos[pos]), jnp.asarray(sin[pos])))
    np.testing.assert_allclose(got, want, atol=1e-5)
    np.testing.assert_allclose(got[0], x.reshape(3, 2, 64)[0], atol=1e-6)


def test_pool_is_written_by_rows_and_blocks_and_read_through_the_table():
    """The one-head pool `[NB, 1, BS, W]` takes `paged_cache_write`'s row
    and whole-block writes; what was written at (block, offset) is what the
    read attends at the table's logical position."""
    rng = np.random.default_rng(5)
    pool = jnp.zeros((NB, 1, BS, W), jnp.float32)
    btab = np.asarray([[7, 3, 11, 0, 0, 0]])
    rows = rng.normal(size=(2 * BS + 5, W)).astype(np.float32)
    rows[:, LAT.row_values:] = 0.0
    # two whole blocks by the chunk write, five rows by the row write
    pool = _write_pool_blocks(pool, jnp.asarray(rows[:2 * BS])[None],
                              jnp.asarray([7, 3]), new_heads=1)
    for k in range(5):
        pool = _write_pool_rows(pool, jnp.asarray(rows[2 * BS + k])[None, None],
                                jnp.asarray([11]), jnp.asarray([k]))
    got = np.asarray(pool)[btab[0]].reshape(-1, W)[:len(rows)]
    np.testing.assert_array_equal(got, rows)
    q = rng.normal(size=(1, 1, NH * W)).astype(np.float32)
    pos = len(rows) - 1
    ctx = np.asarray(la.latent_paged_attention(
        jnp.asarray(q), pool, jnp.asarray(btab), jnp.asarray([pos]), NH, C,
        0.1, backend="xla")).reshape(NH, C)
    sc = q.reshape(NH, W) @ rows.T * 0.1
    p = np.exp(sc - sc.max(-1, keepdims=True))
    np.testing.assert_allclose(ctx, (p / p.sum(-1, keepdims=True)) @ rows[:, :C],
                               atol=1e-5)


def test_a_prefix_hit_and_a_miss_give_the_same_logits():
    from paddle_tpu.core import flags
    old = flags.get_flag("use_bf16_matmul")
    flags.set_flag("use_bf16_matmul", False)
    try:
        cfg = T.cfg(**T.F32)
        scope = E.weights(axk1, cfg, 11)

        def engine(share):
            return E.scored_engine(
                n_slots=4, max_len=64, block_size=8, n_blocks=40, scope=scope,
                model=axk1.spec_of(cfg), prefix_sharing=share)
        rng = np.random.default_rng(2)
        doc = rng.integers(0, 97, 32).tolist()
        ask = doc + rng.integers(0, 97, 9).tolist()
        hit_eng = engine(True)
        E.emitted_logits(hit_eng, doc, 2)             # the document resident
        hit, hit_logits = E.emitted_logits(hit_eng, ask, 8)
        miss, miss_logits = E.emitted_logits(engine(False), ask, 8)
        assert (hit.shared_len, miss.shared_len) == (32, 0)
        assert hit.tokens == miss.tokens
        np.testing.assert_allclose(hit_logits, miss_logits, atol=1e-5)
    finally:
        flags.set_flag("use_bf16_matmul", old)
