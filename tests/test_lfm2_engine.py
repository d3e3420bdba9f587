"""LFM2's hybrid block through PagedKVEngine (ISSUE 39): prefill through the
lanes with the conv state carried from chunk to chunk, a prefix hit that
resumes from a block's state snapshot, decode through the pool and the slot's
state, against the plain reference's full forward
(benchmark/models/lfm2_reference.py: whole-sequence convolution, K and V
uncached, experts looped). In float32 with exact matmuls the two agree to
rounding, so the tolerance that accepts the program refuses every planted
fault."""

import dataclasses
import types

import jax.numpy as jnp
import numpy as np
import pytest

import tiny_engines as E
from lfm2_tiny import DEEP, TINY as T, lfm2, ref
from paddle_tpu import serving
from paddle_tpu.observability import tracing

TOL = 1e-4          # in standard deviations of the reference's logits
TURNS = (5, 11, 3, 17)


def _prompts():
    return E.prompts(TURNS, alone=False)


exact_matmuls = E.exact_matmuls_fixture(T)
exact = E.exact_fixture(T, TURNS, alone=False)


def test_lanes_then_decode_agree_with_the_full_forward(exact):
    # the first request prefilled the preamble itself, the others resumed
    # from its third block's K/V and state snapshot
    E.lanes_then_decode_agree(T, exact, TOL, [0, 24, 24, 24])
    assert all(len(r.tokens) == 10 for r, _ in exact[3])
    # prompts of 29, 35, 27, 41 tokens: 3 + 1 + 0 + 2 blocks filled by lanes
    E.state_counts(T, exact, "conv_state", restores=3, snapshots=6,
                   blocks_with_snapshot=6)


def test_a_prefix_hit_equals_its_self_prefilled_twin(exact):
    E.a_prefix_hit_equals_its_twin(T, exact, _prompts(), atol=1e-5)


@pytest.mark.parametrize("fault", ["bias_dropped", "bias_in_the_weights",
                                   "head_map_modulo"])
def test_the_tolerance_catches_a_fault_planted_in_the_reference(exact, fault):
    """`lfm2.planted` (what benchmark/witness.py plants on the chip): the
    router's bias zero, the bias in the weights, query head i reading
    key/value head i % nkv."""
    cfg, params, _, runs = exact
    held = dict(params)
    scope = types.SimpleNamespace(get=held.get, set_var=held.__setitem__)
    with lfm2.planted(fault, cfg, scope) as c:
        assert T.worst(c, held, runs) > 10 * TOL
    assert all(held[n] is params[n] for n in params)       # and put back
    assert T.worst(cfg, held, runs) < TOL


@pytest.mark.parametrize("fault", ["qk_norm", "tied_head"])
def test_the_tolerance_catches_a_fault_planted_in_the_program(
        exact_matmuls, fault):
    """The program built without the RMSNorm on q and k, or with a head of
    its own in place of the embedding."""
    cfg = exact_matmuls
    scope = E.weights(lfm2, cfg, 7)
    spec = dataclasses.replace(lfm2.spec_of(cfg), **{fault: False})
    eng = E.scored_engine(n_slots=4, max_len=64, block_size=8, n_blocks=40,
                          scope=scope, model=spec)
    params = {n: scope.get(n) for n in lfm2.param_names(cfg)}
    run = E.emitted_logits(eng, _prompts()[0], 6)
    assert T.worst(cfg, params, [run]) > 10 * TOL


def test_the_tolerance_catches_a_state_zeroed_on_a_hit(exact_matmuls):
    cfg = exact_matmuls
    eng, params = T.engine(cfg, 7, scored=True)
    first = E.emitted_logits(eng, _prompts()[0], 4)
    assert T.worst(cfg, params, [first]) < TOL
    name = eng._cache_prefix + "_conv_block"
    eng.scope.set_var(name, jnp.zeros_like(eng.scope.get(name)))
    hit = E.emitted_logits(eng, _prompts()[1], 6)
    assert hit[0].shared_len == 24
    assert T.worst(cfg, params, [hit]) > 10 * TOL


def test_a_snapshots_block_is_evicted_and_refilled_under_pool_pressure(
        exact_matmuls):
    """A pool of 10 blocks: a second and third preamble push the first one's
    cached blocks (and their snapshots) out; asked for again it is prefilled
    again, into blocks whose old snapshots are void, and reads the same."""
    cfg = exact_matmuls
    eng, params = T.engine(cfg, 7, scored=True, n_blocks=11)
    rng = np.random.default_rng(3)
    heads = [rng.integers(0, 97, 24).tolist() for _ in range(3)]
    turn = rng.integers(0, 97, 5).tolist()
    runs = [E.emitted_logits(eng, h + turn, 8) for h in heads]
    assert eng.pager.evictions > 0
    again = E.emitted_logits(eng, heads[0] + turn, 8)
    assert again[0].shared_len < 24            # its blocks were evicted
    assert again[0].tokens == runs[0][0].tokens
    assert T.worst(cfg, params, runs + [again]) < TOL
    pager = eng.pager
    pager.pool.check()
    # a snapshot is valid only on a block somebody holds
    held = {b for b in range(1, 11) if pager.pool.refcount(b)}
    assert set(np.nonzero(pager._snap)[0]) <= held
    assert pager.sanitizer is None or pager.sanitizer.full_checks > 0


def _cell_cfg():
    """The tiny configuration at twelve layers with what the committed one
    gives the cell's comparison. ONE dict for every test that serves it: the
    weights are kept per configuration (tests/tiny_engines.py), and a key
    more makes another."""
    config = E.committed("configs", "lfm2-8b-a1b")
    return T.cfg(**{k: config[k] for k in (
        "router_tie_margin", "check_rows_held", "check_echo")}, **DEEP)


@pytest.mark.parametrize("seed", [4, 7])
def test_bfloat16_engine_passes_the_cells_comparison_and_the_control_fails(
        seed):
    """As the cell serves it: bfloat16 weights, activations, pools and state,
    at twelve layers, read by the cell's own comparison under the cell's own
    limit and the configuration's own `router_tie_margin`; the reference
    computed one precision below is refused by the same limit."""
    tol = E.committed("cells", "lfm2-8b-a1b_serve_assistant")["logit_gap_tol"]
    cfg = _cell_cfg()
    eng, params = T.engine(cfg, seed)
    rng = np.random.default_rng(seed)
    head = rng.integers(0, 97, 24).tolist()
    reqs = [eng.submit(head + rng.integers(0, 97, n).tolist(), 28)
            for n in (5, 11, 3, 9, 7, 10, 6, 4)]
    eng.run_until_idle()
    assert all(r.done and r.error is None for r in reqs)

    def worst(c):
        return max(T.gaps(c, params, r).max() for r in reqs)
    assert worst(cfg) < tol
    with lfm2.one_precision_below(cfg) as c:
        assert worst(c) > 1.2 * tol
    if seed == 7:
        pool = eng.scope.get(eng.cache_names[0])
        assert pool.dtype == jnp.bfloat16 and pool.shape == (40, 2, 8, 8)
        assert len(eng.cache_names) == 2 * 3        # K and V, three layers
        state = eng.scope.get(eng._cache_prefix + "_conv_slot")
        assert state.dtype == jnp.bfloat16 and state.shape == (4, 9, 2, 64)


def test_held_rows_read_the_quantile_and_no_row_an_echo_below_it():
    """What the loop's worst-row statistic reads of the rows `held_rows`
    hands it: the held quantile of a request's gaps, or the worst gap less
    the echo where that is more; rows inside the quantile as they were."""
    rng = np.random.default_rng(0)
    rows = rng.standard_normal((20, 4096)).astype(np.float32)
    at, emitted = np.arange(20), rng.integers(0, 4096, 20)
    want = np.zeros(20)
    want[[3, 7, 12]] = 0.5, 1.0, 3.0           # three rows off, one far off
    rows[at, emitted] = rows.max(-1) + 1.0     # the emitted token on top ...
    rows[at, (emitted + 1) % 4096] = rows[at, emitted] + want   # ... or not
    want = want / rows.std(-1)
    shaped, gap = lfm2.held_rows(rows.copy(), emitted, 0.9, 1.65)
    np.testing.assert_allclose(gap, want, rtol=1e-5)
    read = (shaped.max(-1) - shaped[at, emitted]) / shaped.std(-1)
    q = np.quantile(want, 0.9)                  # between the 2nd and 3rd
    assert want[3] < q < want[7]
    np.testing.assert_allclose(read[[3, 7]], [want[3], q], rtol=1e-2)
    np.testing.assert_allclose(read[12], want[12] - 1.65, rtol=1e-2)
    assert read.max() == read[12] and (read[want == 0] == 0).all()
    # nothing held back: the rows as they are
    same, _ = lfm2.held_rows(rows.copy(), emitted, 1.0, 0.0)
    np.testing.assert_array_equal(same, rows)


def test_the_witness_at_the_stated_precision_reads_like_the_program():
    """The reference's own equations computed AT bfloat16
    (`at_stated_precision`), teacher-forced on the program's tokens: its
    choices lie as far from the float32 rows as the program's do, under the
    cell's limit, and the control does not."""
    tol = E.committed("cells", "lfm2-8b-a1b_serve_assistant")["logit_gap_tol"]
    cfg = _cell_cfg()        # `envelope_logits` reads neither check key
    held, echo = cfg["check_rows_held"], cfg["check_echo"]
    eng, params = T.engine(cfg, 4)
    rng = np.random.default_rng(4)
    head = rng.integers(0, 97, 24).tolist()
    reqs = [eng.submit(head + rng.integers(0, 97, n).tolist(), 28)
            for n in (5, 11, 3, 9)]
    eng.run_until_idle()

    def read(c, r, toks=None):
        seq = np.asarray(r.prompt + r.tokens[:-1], np.int32)
        rows = lfm2.envelope_logits(c, params, seq, 64)[len(r.prompt) - 1:]
        if toks is None:
            return rows
        gap = lfm2.held_rows(rows, toks, 1.0, 0.0)[1]
        return max(np.quantile(gap, held), gap.max() - echo)
    with lfm2.at_stated_precision(cfg) as c:
        assert ref.ROUND_ACTIVATIONS_THROUGH == "bfloat16"
        own = [read(c, r).argmax(-1) for r in reqs]
    assert ref.ROUND_ACTIVATIONS_THROUGH is None
    # a computation of its own: not the program's tokens everywhere ...
    same = np.mean([np.mean(o == np.asarray(r.tokens))
                    for o, r in zip(own, reqs)])
    assert 0.5 < same < 1.0
    # ... and as close to the float32 rows as the program is
    assert max(read(cfg, r, o) for o, r in zip(own, reqs)) < tol
    assert max(read(cfg, r, np.asarray(r.tokens)) for r in reqs) < tol
    with lfm2.one_precision_below(cfg) as c:
        assert max(read(c, r, np.asarray(r.tokens)) for r in reqs) > tol


def test_bytes_count_attention_layers_and_key_value_heads_only():
    cfg = T.cfg()
    spec = lfm2.spec_of(cfg)
    assert spec.attention_layers == (2, 4) and spec.conv_layers == (0, 1, 3)
    assert spec.cache_row_bytes() == 2 * 2 * 2 * 8 * 2     # not 5 * 2 * 64 * 2
    assert spec.state_bytes() == 3 * 2 * 64 * 2
    eng, _ = T.engine(cfg, 7)
    st = eng.stats()
    assert st["block_bytes"] == 8 * spec.cache_row_bytes()
    assert st["conv_state"]["bytes_per_copy"] == spec.state_bytes()
    assert st["conv_state"]["snapshot_bytes"] == 40 * spec.state_bytes()
    # the K/V watermark is the pools': the state is reported beside it
    assert eng._kv_cache_bytes() == 40 * st["block_bytes"]
    # the published widths: 524 KB of K/V a block, 98 KB of state beside it
    big = lfm2.spec_of(E.committed("configs", "lfm2-8b-a1b"))
    assert big.cache_row_bytes() * 64 == 524288 and big.state_bytes() == 98304
    assert lfm2.kv_row_bytes(E.committed("configs", "lfm2-8b-a1b")) * 4 \
        == big.cache_row_bytes()


def test_admit_and_tick_spans_carry_the_states_counts():
    eng, _ = T.engine(T.cfg(), 7)
    prompts = _prompts()
    eng.submit(prompts[0], 3)
    eng.run_until_idle()
    mark = tracing.mark()
    eng.submit(prompts[3], 3)
    eng.run_until_idle()
    spans = tracing.spans_since(mark)
    admits = [s for s in spans if s.name == "engine/admit"
              and s.attrs.get("admitted")]
    assert [s.attrs["state_restored"] for s in admits] == [1]
    ticks = [s for s in spans if s.name == "engine/tick"]
    # 41 prompt tokens from position 24: one chunk of 16 (two blocks filled)
    # and one of 1
    assert [s.attrs["state_snapshots"] for s in ticks
            if s.attrs.get("prefill")] == [2, 0]
    assert all("experts_touched" in s.attrs for s in ticks)


@pytest.mark.parametrize("option, value", E.REFUSED)
def test_what_is_not_built_for_the_model_is_refused_by_name(option, value):
    E.refused_by_name(T, option, value, "conv state")


def test_the_classic_programs_are_unchanged_op_for_op():
    """An engine of the six dims builds no op this PR added."""
    import paddle_tpu as pt
    eng = serving.PagedKVEngine(n_slots=2, max_len=16, block_size=4,
                                scope=pt.Scope(), vocab=61, d_model=32,
                                d_inner=64, num_heads=4, num_layers=2)
    for program in (eng._program, eng._mixed_program):
        ops = [op.type for op in program.global_block().ops]
        assert not {"short_conv", "conv_state_commit", "rotary", "rms_norm",
                    "moe_route"} & set(ops)
    assert "lane_slot" not in eng._lane_feeds
    assert eng.state_bytes == 0 and "conv_state" not in eng.stats()
    assert eng.pager.stats()["block_state"] is None
