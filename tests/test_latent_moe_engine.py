"""A.X-K1's block through PagedKVEngine (ISSUE 36): prefill through the
lanes, then decode through the latent paged cache, against the plain
reference's full forward (benchmark/models/axk1_reference.py: expanded K and
V, experts looped). In float32 with exact matmuls the two agree to rounding,
so the tolerance that accepts the program refuses every planted fault."""

import jax.numpy as jnp
import numpy as np
import pytest

import tiny_engines as E
from axk1_tiny import TINY as T, axk1
from paddle_tpu import serving
from paddle_tpu.observability import tracing

TOL = 1e-4          # in standard deviations of the reference's logits


exact_matmuls = E.exact_matmuls_fixture(T)
# a document of 24 tokens and four questions behind it: the first request
# prefills the document itself
exact = E.exact_fixture(T, (5, 11, 3, 17), alone=False)


def test_lanes_then_decode_agree_with_the_full_forward(exact):
    # the first request prefilled its document itself, the others hit it
    E.lanes_then_decode_agree(T, exact, TOL, [0, 24, 24, 24])
    runs = exact[3]
    assert all(len(r.tokens) == 10 for r, _ in runs)
    # positions run past YaRN's original length (16) and past one chunk
    assert max(len(r.prompt) + len(r.tokens) for r, _ in runs) > 48


def _no_shared(cfg, params):
    return cfg, {n: jnp.zeros_like(v) if "_shared_down" in n else v
                 for n, v in params.items()}


def _no_m2(cfg, params):
    sc = dict(cfg["rope_scaling"], mscale=0.0, mscale_all_dim=0.0)
    return dict(cfg, rope_scaling=sc), params


def _int8_experts(cfg, params):
    def rounded(w):
        scale = jnp.max(jnp.abs(w), axis=1, keepdims=True) / 127.0
        return jnp.round(w / scale) * scale
    return cfg, {n: rounded(v) if "_experts_" in n else v
                 for n, v in params.items()}


def _fp16_latent(cfg, params):
    return dict(cfg, latent_dtype="float16"), params


@pytest.mark.parametrize("fault", [_no_shared, _no_m2, _int8_experts,
                                   _fp16_latent])
def test_the_tolerance_catches_a_planted_fault(exact, fault):
    cfg, params, _, runs = exact
    assert T.worst(*fault(cfg, params), runs) > 10 * TOL


@pytest.mark.parametrize("seed", [2, 4, 7])
def test_bfloat16_engine_passes_the_cells_comparison_and_the_control_fails(
        seed):
    """As the cell serves it: bfloat16 weights, activations and cache, read
    by the cell's own comparison (the loop's worst gap of an emitted token
    below the reference's largest logit) under the cell's own limit and the
    configuration's own `router_tie_margin`. On the plain forward a
    selection that flips on rounding puts a token a few tenths off (seeds 2
    and 4 have one, 7 has none); the envelope over the selections the scores
    leave open explains it, and explains nothing of a reference computed
    one precision below."""
    tol = E.committed("cells", "axk1-ep16_serve_docqa")["logit_gap_tol"]
    margin = E.committed("configs", "axk1-ep16")["router_tie_margin"]
    cfg = T.cfg()
    eng, params = T.engine(cfg, seed)
    rng = np.random.default_rng(1)
    doc = rng.integers(0, 97, 24).tolist()
    reqs = [eng.submit(doc + rng.integers(0, 97, n).tolist(), 20)
            for n in (5, 11, 3, 17, 9, 13)]
    eng.run_until_idle()
    assert all(r.done and r.error is None for r in reqs)

    def worst(c):
        return max(T.gaps(c, params, r).max() for r in reqs)
    plain, enveloped = worst(cfg), worst(dict(cfg, router_tie_margin=margin))
    assert enveloped <= plain and enveloped < tol / 2
    assert (plain > tol) == (seed != 7)
    with axk1.one_precision_below(dict(cfg, router_tie_margin=margin)) as c:
        assert worst(c) > 1.5 * tol
    if seed == 7:
        pool = eng.scope.get(eng.cache_names[0])
        assert pool.dtype == jnp.bfloat16 and pool.shape == (40, 1, 8, 256)
        assert len(eng.cache_names) == cfg["num_layers"]
        st = eng.stats()
        assert st["latent_row"] == {"values": 144, "stored": 256}
        assert st["block_bytes"] == 3 * 8 * 256 * 2


@pytest.mark.parametrize("rows, touched, runs", [
    # (layer, held expert) -> rows; a run is a maximal stretch of touched
    # experts in one layer's stored order, and does not span two layers
    ([[0, 0, 0, 0, 0, 0], [0, 0, 0, 0, 0, 0]], 0, 0),
    ([[3, 1, 2, 9, 1, 1], [1, 1, 1, 1, 1, 1]], 12, 2),
    ([[0, 0, 0, 0, 0, 5], [7, 0, 0, 0, 0, 0]], 2, 2),
    ([[1, 0, 1, 0, 1, 0], [0, 1, 0, 1, 0, 1]], 6, 6),
    ([[0, 2, 2, 2, 0, 0], [4, 4, 0, 0, 1, 1]], 7, 3),
])
def test_a_ticks_counts_give_the_touched_experts_and_their_runs(
        rows, touched, runs):
    from types import SimpleNamespace
    eng = SimpleNamespace(expert_rows=np.ones((2, 6), np.int64))
    tick = SimpleNamespace(attrs={})
    # the tick's fetch: the ids' rows, then one count a (layer, held expert)
    ids = np.concatenate([np.arange(5), np.ravel(rows)])[:, None]
    serving.PagedKVEngine._note_tick_counts(eng, tick, ids)
    assert tick.attrs["experts_touched"] == touched
    assert tick.attrs["expert_runs"] == runs
    assert tick.attrs["routed_rows"] == np.sum(rows)
    assert (eng.expert_rows == 1 + np.asarray(rows)).all()


def test_a_tick_counts_the_rows_its_experts_got():
    cfg = T.cfg()
    eng, _ = T.engine(cfg, 7)
    mark = tracing.mark()
    req = eng.submit(list(range(1, 30)), 5)
    eng.run_until_idle()
    ticks = [s for s in tracing.spans_since(mark) if s.name == "engine/tick"]
    assert ticks and all("experts_touched" in s.attrs for s in ticks)
    rows = np.asarray([s.attrs["expert_rows"] for s in ticks])
    assert rows.shape[1] == 2 * 4          # routed layers x held experts
    assert [s.attrs["routed_rows"] for s in ticks] == rows.sum(1).tolist()
    assert [s.attrs["experts_touched"] for s in ticks] == \
        (rows > 0).sum(1).tolist()
    # the runs of touched experts a layer, by hand from the same counts
    on = rows.reshape(len(ticks), 2, 4) > 0
    assert [s.attrs["expert_runs"] for s in ticks] == [
        sum(len("".join("x" if t else " " for t in layer).split())
            for layer in tick) for tick in on]
    # a decode tick has one live row: it selects 4 of 16 experts a layer, so
    # at most 4 of the held ones; dead rows select nothing
    decode = [s for s in ticks if not s.attrs["prefill"]]
    assert decode and all(s.attrs["routed_rows"] <= 2 * 4 for s in decode)
    # ... and attends every position written so far, its own included
    assert [s.attrs["decode_rows"] for s in decode] == \
        list(range(len(req.prompt) + 1, len(req.prompt) + 1 + len(decode)))
    st = eng.stats()["expert_rows"]
    assert st["layers"] == [1, 2] and st["held"] == [0, 1, 2, 3]
    assert np.asarray(st["rows"]).ravel().tolist() == rows.sum(0).tolist()
    # every prompt and emitted position was routed once a layer: 29 + 4
    # rows, each selecting 4 of 16 experts, a quarter of them held on average
    assert 0 < rows.sum() <= 2 * 4 * (len(req.prompt) + 4)


@pytest.mark.parametrize("option, value", E.REFUSED)
def test_what_is_not_built_for_the_model_is_refused_by_name(option, value):
    E.refused_by_name(T, option, value)


def test_the_six_dims_are_the_classic_spec():
    from paddle_tpu.models.decoder_spec import DecoderSpec
    dims = dict(vocab=61, d_model=32, d_inner=64, num_heads=4, num_layers=2)
    import paddle_tpu as pt
    a = serving.PagedKVEngine(n_slots=2, max_len=16, block_size=4,
                              scope=pt.Scope(), **dims)
    b = serving.PagedKVEngine(n_slots=2, max_len=16, block_size=4,
                              scope=pt.Scope(),
                              model=DecoderSpec.classic(**dims))
    assert a.model == b.model and a.model.is_classic
    ops = [[op.type for op in e._program.global_block().ops] for e in (a, b)]
    assert ops[0] == ops[1]
    assert a.block_bytes == 2 * 2 * 32 * 4 * 4
