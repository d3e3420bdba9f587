"""The sparse latent read's way from the pool to the context (ISSUE 62): the
fetch kernel (interpreted) and the composite against a plain float32
reference that attends the selected positions of a DENSE `c`, a request at a
time; and the selection itself against the parent's on fixed seeds."""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.fusion import sparse_latent_attention as sla

BS, KP, W, NH, NI, DI, R, TOP, NLB, NB, C = 16, 4, 128, 8, 4, 32, 16, 6, 12, \
    96, 16
SCALE = 0.2
KW = dict(num_heads=NH, v_width=W, scale=SCALE, index_heads=NI,
          top_groups=TOP, kpool=KP)


def _rotate(x, row):
    """x [..., d]: the first R values rotated (rotate-half) by `row` (cos |
    sin), in numpy."""
    half = R // 2
    cos, sin = row[:half], row[half:]
    x1, x2 = x[..., :half], x[..., half:R]
    return np.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                           x[..., R:]], axis=-1)


class _Tick:
    """A tick's inputs built from DENSE per-request state: the pools hold
    each request's rows through a shuffled block table; `expected` is the
    reference's context of every live row."""

    def __init__(self, seed, decode, lanes=(), null=0.0, top=TOP, nlb=NLB,
                 nb=NB):
        """decode: positions of the decode rows (None: an idle slot); lanes:
        (first position, real rows) of each lane's chunk."""
        rng = np.random.default_rng(seed)
        self.rng, self.top = rng, top
        NLB, NB = nlb, nb
        s, n = len(decode), len(decode) + len(lanes) * C
        self.table = rng.standard_normal((NLB * BS, R)).astype(np.float32)
        self.pool = np.zeros((NB, 1, BS, W), np.float32)
        self.pool[0] = null
        self.ipool = np.zeros((NB, 1, BS // KP, DI), np.float32)
        free = list(rng.permutation(np.arange(1, NB)))
        self.q = rng.standard_normal((n, 1, NH * W)).astype(np.float32)
        self.qi = rng.standard_normal((n, NI * DI)).astype(np.float32)
        self.ki = rng.standard_normal((n, DI)).astype(np.float32)
        self.wi = rng.standard_normal((n, NI)).astype(np.float32)
        self.pos = np.zeros((n,), np.int64)
        self.btab = np.zeros((s, NLB), np.int64)
        self.wblock, self.woff = np.zeros((s,), np.int64), \
            np.zeros((s,), np.int64)
        self.live = np.zeros((n,), bool)
        self.expected = np.zeros((n, NH, W), np.float64)
        self.picked = {}
        for row, t in enumerate(decode):
            if t is None:
                continue
            blocks = [free.pop() for _ in range(t // BS + 1)]
            c, pooled = self._context(blocks, t + 1, t // KP)
            # the half-full group's running sum, then this row's own key
            g = t // KP
            held = rng.standard_normal((DI,)).astype(np.float32) \
                if t % KP else np.zeros((DI,), np.float32)
            self.ipool[blocks[g // (BS // KP)], 0, g % (BS // KP)] = held
            pooled = np.concatenate([pooled, (
                held + _rotate(self.ki[row], self.table[t]) / KP)[None]])
            self.btab[row, :len(blocks)] = blocks
            self.pos[row], self.wblock[row], self.woff[row] = \
                t, blocks[-1], t % BS
            self.live[row] = True
            self._expect(row, t, c, pooled)
        self.lbtab = np.zeros((len(lanes), NLB), np.int64)
        self.lwblocks = np.zeros((len(lanes) * C // BS,), np.int64)
        self.lrows = np.asarray([r for _, r in lanes], np.int64)
        for j, (p0, real) in enumerate(lanes):
            assert p0 % BS == 0
            blocks = [free.pop() for _ in range((p0 + C) // BS)]
            c, pooled = self._context(blocks, p0 + real, p0 // KP)
            rows = slice(s + j * C, s + (j + 1) * C)
            self.pos[rows] = p0 + np.arange(C)
            ki = np.stack([_rotate(self.ki[s + j * C + i], self.table[p0 + i])
                           for i in range(C)])
            ki[real:] = 0.0
            pooled = np.concatenate(
                [pooled, ki.reshape(C // KP, KP, DI).mean(axis=1)])
            self.lbtab[j, :len(blocks)] = blocks
            self.lwblocks[j * (C // BS):(j + 1) * (C // BS)] = \
                blocks[-(C // BS):]
            for i in range(real):
                self.live[s + j * C + i] = True
                self._expect(s + j * C + i, p0 + i, c, pooled)

    def _context(self, blocks, n_positions, n_groups):
        """Dense rows of c for positions < n_positions and pooled index keys
        of the first `n_groups` groups, scattered into the pools."""
        rng = self.rng
        c = rng.standard_normal((n_positions, W)).astype(np.float32)
        pooled = rng.standard_normal((n_groups, DI)).astype(np.float32)
        for p in range(n_positions):
            self.pool[blocks[p // BS], 0, p % BS] = c[p]
        for g in range(n_groups):
            self.ipool[blocks[g // (BS // KP)], 0, g % (BS // KP)] = pooled[g]
        return c, pooled

    def _expect(self, row, t, c, pooled):
        n_whole = (t + 1) // KP
        qi = _rotate(self.qi[row].reshape(NI, DI), self.table[t])
        scores = (np.maximum(qi.astype(np.float64) @ pooled[:n_whole].T, 0.0)
                  * self.wi[row][:, None]).sum(axis=0)
        order = np.argsort(-scores, kind="stable")
        k = min(self.top, n_whole)
        if k < n_whole:     # no near-tie (zeros tie: the lower group first)
            gap = scores[order[k - 1]] - scores[order[k]]
            assert gap > 1e-4 or scores[order[k - 1]] == scores[order[k]] == 0
        groups = sorted(order[:k])
        at = [g * KP + i for g in groups for i in range(KP)] \
            + list(range(n_whole * KP, t + 1))
        self.picked[row] = groups
        rows = c[at].astype(np.float64)
        sc = self.q[row].reshape(NH, W).astype(np.float64) @ rows.T * SCALE
        p = np.exp(sc - sc.max(axis=1, keepdims=True))
        self.expected[row] = (p / p.sum(axis=1, keepdims=True)) @ rows

    def run(self, backend):
        f32, i32 = jnp.float32, jnp.int32
        n = self.q.shape[0]
        lanes = None
        if len(self.lrows):
            lanes = (jnp.asarray(self.lbtab, i32),
                     jnp.asarray(self.lwblocks, i32),
                     jnp.asarray(self.lrows, i32), C)
        out, ipool = sla.sparse_latent_attention(
            jnp.asarray(self.q), jnp.asarray(self.pool),
            jnp.asarray(self.ipool), jnp.asarray(self.qi),
            jnp.asarray(self.ki), jnp.asarray(self.wi),
            jnp.asarray(self.pos, f32).reshape(n, 1, 1),
            jnp.asarray(self.table), jnp.asarray(self.btab, i32),
            jnp.asarray(self.wblock, i32), jnp.asarray(self.woff, i32),
            lanes, **{**KW, "top_groups": self.top}, backend=backend)
        return np.asarray(out).reshape(n, NH, W), np.asarray(ipool)


CASES = {
    # a tail of 0, 1, 2 and 3 positions behind the whole groups
    "tail0": dict(decode=[83]), "tail1": dict(decode=[84]),
    "tail2": dict(decode=[85]), "tail3": dict(decode=[86]),
    # position 0: no group at all, a tail of one
    "position0": dict(decode=[0, 57]),
    # fewer whole groups than top_groups: the dense read
    "short_context": dict(decode=[TOP * KP - 3, 11, 3]),
    "idle_beside_live": dict(decode=[None, 120, None, None, 45, None]),
    "many_rows": dict(decode=[150, 33, 64, 191, 17]),
    "lanes_alone": dict(decode=[None], lanes=[(64, C)]),
    "lane_short_chunk": dict(decode=[None, None], lanes=[(48, 5), (0, 9)]),
    "decode_and_lanes": dict(decode=[101, None, 30], lanes=[(32, C), (96, 7)]),
}
BACKENDS = ["xla", "pallas_interpret"]


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("case", sorted(CASES))
def test_read_attends_the_selected_rows(case, backend):
    tick = _Tick(seed=sorted(CASES).index(case), **CASES[case])
    out, _ = tick.run(backend)
    assert tick.live.any()
    np.testing.assert_allclose(out[tick.live], tick.expected[tick.live],
                               rtol=2e-4, atol=2e-5)
    # an idle slot reads the null block (the composite) or nothing at all
    # (the kernel, which skips a lane's rows past its real ones too)
    idle = ~tick.live
    if backend == "xla":
        idle[len(tick.wblock):] = False
    assert np.all(out[idle] == 0.0)


@pytest.mark.parametrize("backend", BACKENDS)
def test_index_pool_written_as_before(backend):
    """Both lowerings share the selection: the pooled rows they write are
    the same bits."""
    tick = _Tick(seed=40, **CASES["decode_and_lanes"])
    a, b = tick.run("xla")[1], tick.run(backend)[1]
    assert np.array_equal(a, b)
    assert not np.array_equal(a, tick.ipool)


def test_two_picked_groups_of_one_chunk_are_scored_once_each():
    """Groups 2j and 2j + 1 lie in one 8-row chunk of the pool: the kernel
    fetches it twice and masks each fetch to its own group."""
    tick = _Tick(seed=7, decode=[150, 131])
    shared = [g for row in tick.picked.values() for g in row
              if g % 2 == 0 and g + 1 in row]
    assert shared and any(len(r) == TOP for r in tick.picked.values())
    assert sla.fetch_chunk(KP, BS) == 2 * KP
    out, _ = tick.run("pallas_interpret")
    np.testing.assert_allclose(out[tick.live], tick.expected[tick.live],
                               rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("positions", [[599, None, 636], [577], [259, 600]])
def test_rows_of_several_steps(positions):
    """140 picked groups and the tail's: 141 fetches in steps of 64, so two
    whole steps (their copies written out) before the short last one, the
    row after a live row started during it; a row of 65 groups beside."""
    tick = _Tick(seed=len(positions), decode=positions, top=140, nlb=40,
                 nb=128)
    assert sla._FETCH_STEP_KEYS // sla.fetch_chunk(KP, BS) == 64
    out, _ = tick.run("pallas_interpret")
    np.testing.assert_allclose(out[tick.live], tick.expected[tick.live],
                               rtol=2e-4, atol=2e-5)
    assert np.all(out[~tick.live] == 0.0)


def test_idle_rows_and_unpicked_slots_fetch_nothing():
    """The null block poisoned: an idle row's ids, and a live row's slots
    past its selection, all point at it. A fetch of it would leave NaN in
    the buffers, which the additive mask does not hide."""
    tick = _Tick(seed=9, decode=[None, 9, None, 77, None], null=np.nan)
    out, _ = tick.run("pallas_interpret")
    np.testing.assert_allclose(out[tick.live], tick.expected[tick.live],
                               rtol=2e-4, atol=2e-5)
    assert np.all(out[~tick.live] == 0.0)


def test_kernel_serves_whole_tiles_only():
    assert sla.fetch_chunk(4, 64) == 8 and sla.fetch_chunk(8, 64) == 8
    assert sla.fetch_chunk(16, 64) == 16 and sla.fetch_chunk(2, 16) == 8
    assert sla.fetch_chunk(3, 12) is None and sla.fetch_chunk(4, 4) is None


@pytest.mark.parametrize("bs, calls", [(4, 0), (16, 1)])
def test_groups_of_no_whole_chunk_take_the_composite(bs, calls):
    """Groups of 4 in blocks of 4: no chunk of 8 rows lies in one block, so
    the kernel is not asked for (the composite gathers the picked rows, no
    Mosaic call in the TPU lowering); blocks of 16 are."""
    n, nlb = 3, 16
    shapes = [(n, 1, NH * W), (64, 1, bs, W), (64, 1, bs // KP, DI),
              (n, NI * DI), (n, DI), (n, NI), (n, 1, 1), (nlb * bs, R)]
    args = [jax.ShapeDtypeStruct(s, jnp.float32) for s in shapes] \
        + [jax.ShapeDtypeStruct(s, jnp.int32) for s in ((n, nlb), (n,), (n,))]

    def f(*a):
        return sla.sparse_latent_attention(*a, **KW, backend="pallas")
    text = jax.jit(f).trace(*args).lower(lowering_platforms=("tpu",)) \
        .as_text()
    assert text.count("tpu_custom_call") == calls


# sha256 of `select`'s ids and count on seeded scores, read on the PARENT
# commit (1285a5b): the selection is not this change's to move
SELECTED = {
    0: "8cee63635609c682026bbd53c92f1f72"
       "5e9197cf0651018bc4342bda3e857be9",
    1: "bc5e2874abc3b9c670f799836424e370"
       "707b221bdfffe75d90958d66f8f4ee92",
    2: "113d2dc7166775d1fb5c4f98822fffb3"
       "76e7b1b6bcdd1e0ce42c5b67c432ef07",
}


def _selection(seed):
    rng = np.random.default_rng(seed)
    n, nlb, gpb, top = 9, 12, 4, 6 + seed
    scores = rng.standard_normal((n, nlb * gpb)).astype(np.float32)
    scores[:, ::5] = scores[:, 1::5]            # ties: the lower group first
    pos = rng.integers(0, nlb * gpb * KP, (n,))
    pos[0] = 0
    tab = rng.permutation(np.arange(1, 1 + n * nlb)).reshape(n, nlb)
    n_scratch = sla.scratch_rows(top, KP, gpb * KP, nlb) // KP
    ids, count = sla.select(jnp.asarray(scores), jnp.asarray(pos, jnp.int32),
                            jnp.asarray(tab, jnp.int32), KP, top, gpb,
                            n_scratch)
    ids, count = np.asarray(ids), np.asarray(count)
    assert ids.dtype == np.int32 and count.dtype == np.int32
    return hashlib.sha256(ids.tobytes() + count.tobytes()).hexdigest()


@pytest.mark.parametrize("seed", sorted(SELECTED))
def test_selection_is_the_parents(seed):
    assert _selection(seed) == SELECTED[seed]


# ISSUE 64: the selection runs for the rows that hold a token

SLOTS = 64
LIVE = [0, 1, 7, 8, 9, 33, 64]
LANES = {"alone": (), "lane_idle_lane": ((32, C), (0, 0)),
         "one_row_idle_lane": ((48, 1), (0, 0)),
         "lane_short_lane": ((32, C), (96, 5)),
         "two_lanes": ((64, C), (16, C))}


def _scattered(n_live, lanes, seed):
    """`n_live` of 64 decode rows live, scattered over the slots."""
    rng = np.random.default_rng(seed)
    decode = [None] * SLOTS
    for slot in rng.choice(SLOTS, n_live, replace=False):
        decode[slot] = int(rng.integers(0, NLB * BS))
    return _Tick(seed, decode, list(lanes), nb=1024)


def _op_args(tick):
    i32 = jnp.int32
    lanes = None
    if len(tick.lrows):
        lanes = (jnp.asarray(tick.lbtab, i32), jnp.asarray(tick.lwblocks, i32),
                 jnp.asarray(tick.lrows, i32), C)
    return (jnp.asarray(tick.ipool), jnp.asarray(tick.qi),
            jnp.asarray(tick.ki), jnp.asarray(tick.wi),
            jnp.asarray(tick.pos, i32), jnp.asarray(tick.table),
            jnp.asarray(tick.btab, i32), jnp.asarray(tick.wblock, i32),
            jnp.asarray(tick.woff, i32), lanes)


def _parents_selection(ipool, qi, ki, wi, pos, table, btab, wblock, woff,
                       lanes):
    """ids, count and the written index pool as the parent (5105f9a) made
    them: EVERY row of the tick gathered, scored and sorted."""
    f32 = jnp.float32
    n, s = qi.shape[0], btab.shape[0]
    gpb = BS // KP
    qi = sla.rotate_first(qi.reshape(n, NI, DI), pos, table)
    ki = sla.rotate_first(ki.reshape(n, 1, DI), pos, table)[:, 0]
    ipool = sla.write_index(ipool, ki, wblock, woff, KP,
                            lanes and lanes[1:])
    scores = sla.index_scores(
        qi[:s, None], wi[:s, None], ipool[btab].reshape(s, NLB * gpb, DI),
        head_block=NI)[:, 0]
    tab = btab
    if lanes:
        lbtab = lanes[0]
        sl = sla.index_scores(
            qi[s:].reshape(-1, C, NI, DI), wi[s:].reshape(-1, C, NI),
            ipool[lbtab].reshape(lbtab.shape[0], NLB * gpb, DI))
        scores = jnp.concatenate([scores, sl.reshape(n - s, -1)])
        tab = jnp.concatenate([btab, jnp.repeat(lbtab, C, axis=0)])
    ids, count = sla.select(scores, pos, tab, KP, TOP, gpb,
                            sla.scratch_rows(TOP, KP, BS, NLB) // KP)
    return (np.asarray(ids), np.asarray(jnp.maximum(count, 1)),
            np.asarray(ipool.astype(f32)))


def _read(tick, ids, count, backend):
    """The op's second half over a given selection."""
    q, pool = jnp.asarray(tick.q), jnp.asarray(tick.pool)
    ids, count = jnp.asarray(ids), jnp.asarray(count)
    if backend == "xla":
        rows = pool.reshape(-1, W)[sla.picked_rows(ids, KP)]
        out = sla._attend_composite(q, rows, count, NH, W, SCALE)
    else:
        out = sla._sparse_fetch_pallas(
            q, pool, ids, count, jnp.asarray(tick.live, jnp.int32), NH, W,
            SCALE, KP, sla.fetch_chunk(KP, BS), interpret=True)
    return np.asarray(out).reshape(-1, NH, W)


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("lanes", sorted(LANES))
@pytest.mark.parametrize("n_live", LIVE)
def test_live_rows_get_the_unbucketed_selection(n_live, lanes, backend):
    """However many steps the live count takes, a live row's ids, count and
    context are the ones a selection over EVERY row gives it, bit for bit,
    and the index pool is written as before."""
    tick = _scattered(n_live, LANES[lanes],
                      seed=100 + 10 * LIVE.index(n_live)
                      + sorted(LANES).index(lanes))
    live = tick.live
    assert live[:SLOTS].sum() == n_live
    args = _op_args(tick)
    ids, count, flags, ipool = sla.tick_selection(
        *args, dtype=jnp.float32, index_heads=NI, top_groups=TOP, kpool=KP)
    want_ids, want_count, want_ipool = _parents_selection(*args)
    assert np.array_equal(np.asarray(flags), live)
    assert np.array_equal(np.asarray(ids)[live], want_ids[live])
    assert np.array_equal(np.asarray(count)[live], want_count[live])
    assert np.array_equal(np.asarray(ipool), want_ipool)
    # an idle row's selection is nothing the read has to tolerate anew
    assert np.asarray(ids).min() >= 0 and np.asarray(count).min() >= 1
    out, written = tick.run(backend)
    assert np.array_equal(written, want_ipool)
    assert np.array_equal(out[live], _read(tick, want_ids, want_count,
                                           backend)[live])
    np.testing.assert_allclose(out[live], tick.expected[live], rtol=2e-4,
                               atol=2e-5)
    assert np.all(out[:SLOTS][~live[:SLOTS]] == 0.0)


def _eqns_outside_loops(jaxpr):
    """Every equation of `jaxpr` and of what it calls, the bodies of a
    `while` left out."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "while":
            continue
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns_outside_loops(sub)


@pytest.mark.parametrize("n_lanes", [0, 2])
def test_row_proportional_work_stands_inside_the_loops(n_lanes):
    """Outside the loops whose trip count the device takes from the live
    rows, the op holds no sort, no gather of the index pool through the
    decode rows' tables and no product that scores decode rows; the loops'
    bodies hold them, 8 rows at a time."""
    s, n = SLOTS, SLOTS + n_lanes * C
    g = NLB * BS // KP
    f32, i32 = jnp.float32, jnp.int32
    shapes = [((n, 1, NH * W), f32), ((NB, 1, BS, W), f32),
              ((NB, 1, BS // KP, DI), f32), ((n, NI * DI), f32),
              ((n, DI), f32), ((n, NI), f32), ((n, 1, 1), f32),
              ((NLB * BS, R), f32), ((s, NLB), i32), ((s,), i32), ((s,), i32)]
    if n_lanes:
        shapes += [((n_lanes, NLB), i32), ((n_lanes * C // BS,), i32),
                   ((n_lanes,), i32)]

    def f(*a):
        return sla.sparse_latent_attention(
            *a[:11], (*a[11:], C) if n_lanes else None, **KW, backend="xla")
    jaxpr = jax.make_jaxpr(f)(*(jax.ShapeDtypeStruct(*x) for x in shapes))
    outside = list(_eqns_outside_loops(jaxpr.jaxpr))
    loops = [e for e in outside if e.primitive.name == "while"]
    assert len(loops) == (3 if n_lanes else 2)

    def heavy(e, rows):
        """The index pool through `rows` tables, or a score of `rows` decode
        rows over every group."""
        shape = e.outvars[0].aval.shape
        return (e.primitive.name == "gather"
                and shape == (rows, NLB, 1, BS // KP, DI)
                or e.primitive.name == "dot_general" and shape[0] == rows
                and shape[-1] == g)
    assert not [e for e in outside if e.primitive.name == "sort"]
    assert not [e for e in outside if heavy(e, s) or heavy(e, sla._STEP)]
    inside = [e for w in loops
              for e in _eqns_outside_loops(w.params["body_jaxpr"].jaxpr)]
    sorts = [e for e in inside if e.primitive.name == "sort"]
    assert [e.outvars[0].aval.shape for e in sorts] == [(sla._STEP, g)]
    assert sum(heavy(e, sla._STEP) for e in inside) == 2


@pytest.mark.parametrize("count, n_rows, rows", [
    (0, 64, 0), (1, 64, 8), (7, 64, 8), (8, 64, 8), (9, 64, 16), (16, 64, 16),
    (17, 64, 24), (33, 64, 40), (57, 64, 64), (64, 64, 64), (0, 320, 0),
    (64, 320, 64), (65, 320, 72), (128, 320, 128), (135, 320, 136),
    (263, 320, 264), (313, 320, 320), (320, 320, 320), (5, 6, 6),
    (9, 12, 12), (1, 2, 2), (0, 2, 0)])
def test_rung_holds_the_live_rows(count, n_rows, rows):
    assert sla.rung(count, n_rows) == rows
    assert rows == n_rows or rows % sla._STEP == 0 and rows - count < sla._STEP
