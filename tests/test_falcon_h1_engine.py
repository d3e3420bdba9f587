"""Falcon-H1's block through PagedKVEngine (ISSUE 54): in EVERY layer a
Mamba-2 mixer and rotary grouped-query attention on one normed input, summed,
under the family's multipliers. Prefill through the lanes (the state carried
from chunk to chunk in the request's slot, K/V written as whole blocks), a
prefix hit that needs blocks AND a snapshot, decode through the paged read
and the slot's state updated in place, against the plain reference's full
forward (benchmark/models/falcon_h1_reference.py: the recurrence token by
token, K and V uncached). In float32 with exact matmuls the two agree to
rounding, so the tolerance that accepts the program refuses every planted
fault."""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

import tiny_engines as E
from falcon_h1_tiny import TINY as T, falcon, ref
from paddle_tpu import serving
from paddle_tpu.models.decoder_spec import DecoderSpec, Multipliers
from paddle_tpu.observability import tracing

TOL = 1e-4          # in standard deviations of the reference's logits
TURNS = (5, 11, 3, 17)
HEAD = 24           # a shared head: three blocks of 8


def _prompts(seed=1):
    return E.prompts(TURNS, HEAD, seed)


exact_matmuls = E.exact_matmuls_fixture(T)


@pytest.fixture(scope="module")
def exact(exact_matmuls):
    """float32 weights, pools, state and matmuls: the program against the
    reference with nothing but float32 rounding between them. The head alone
    first, then four turns behind it (each a prefix hit), then three unshared
    prompts: one ending inside a chunk, one at a chunk's end, one a token."""
    cfg = exact_matmuls
    eng, params = T.engine(cfg, 7, scored=True)
    prompts = _prompts()
    runs = [E.emitted_logits(eng, prompts[0], 2)]
    runs += [E.emitted_logits(eng, p, 10) for p in prompts[1:]]
    rng = np.random.default_rng(8)
    runs += [E.emitted_logits(eng, rng.integers(0, 97, n).tolist(), 6)
             for n in (37, 32, 1)]
    return cfg, params, eng, runs


def test_lanes_then_decode_agree_with_the_full_forward(exact):
    E.lanes_then_decode_agree(T, exact, TOL, [0, 24, 24, 24, 24, 0, 0, 0])
    # every layer holds the state AND K/V rows
    E.state_counts(T, exact, "ssm_state", restores=4, layers=3,
                   layers_with_kv=3)


def test_a_prompt_ending_inside_a_chunk(exact):
    """37 tokens are two chunks of 16 and one of 5: the last chunk's dead
    rows leave the state as it is, and the first decode row continues it."""
    cfg, params, _, runs = exact
    req, got = runs[5]
    assert len(req.prompt) == 37 and len(req.tokens) == 6
    assert T.logit_error(cfg, params, req, got) < TOL


def test_a_prefix_hit_equals_its_self_prefilled_twin(exact):
    E.a_prefix_hit_equals_its_twin(T, exact, _prompts())


def test_a_hit_needs_blocks_and_a_snapshot(exact_matmuls):
    E.a_hit_is_truncated_to_the_deepest_snapshot(T, exact_matmuls, TOL)


@pytest.mark.parametrize("fault", falcon.FAULTS)
def test_the_tolerance_catches_a_fault_planted_in_the_reference(exact, fault):
    """`falcon.planted` (what benchmark/witness.py plants on the chip), one in
    each mechanism of the block: a mixer's output dropped, the multipliers on
    the wrong column ranges, the key multiplier left out, no rotation, a
    restore from a snapshot one chunk stale."""
    E.a_planted_fault_is_caught(
        T, exact, fault, TOL, dict(check_stale_at=HEAD, mamba_chunk_size=8),
        factor=100, faulty=slice(1, 6))


def test_the_tolerance_catches_a_stale_snapshot_in_the_program(exact_matmuls):
    cfg = exact_matmuls
    E.a_stale_snapshot_is_caught(
        T, cfg, TURNS, [f"_ssm_snap_h{j}" for j in range(
            len(falcon.spec_of(cfg).ssm_layers))], 100 * TOL)


def test_a_layer_declares_slot_state_snapshot_pool_and_kv_pools():
    eng, _ = T.engine(T.cfg(), 7)
    pre = eng._cache_prefix
    for j in range(3):
        h = eng.scope.get(f"{pre}_ssm_h{j}")
        assert h.dtype == jnp.float32 and h.shape == (4, 8, 8, 16)
        conv = eng.scope.get(f"{pre}_ssm_conv{j}")
        assert conv.dtype == jnp.bfloat16 and conv.shape == (4, 3, 64 + 64)
        assert eng.scope.get(f"{pre}_ssm_snap_h{j}").shape == (4, 8, 8, 16)
        for s in "kv":
            assert eng.scope.get(f"{pre}_{s}{j}").dtype == jnp.bfloat16
    assert len(eng.cache_names) == 6


def test_pool_and_snapshot_accounting_is_exact_with_both_states_in_a_layer():
    """Blocks and snapshot entries are counted as for a model whose kinds
    partition the layers: a request's blocks by its span, one entry a prompt
    whose last whole block ends beyond what it shares, all back after it."""
    eng, _ = T.engine(T.cfg(), 7)
    pool = eng.pager.pool
    free0 = pool.n_free
    a = eng.submit(_prompts()[1], 4)            # 29 tokens + 4: five blocks
    eng.step()
    assert free0 - pool.n_free == 5
    eng.run_until_idle()
    assert a.done and a.error is None
    st = eng.stats()["ssm_state"]
    assert st["written"] == 1 and st["valid"] == 1 and st["pinned"] == 0
    # blocks 0-2 stay, held by the index alone; the rest went back
    assert free0 - pool.n_free == 3
    b = eng.submit(_prompts()[2], 4)            # shares three blocks + entry
    eng.run_until_idle()
    assert b.shared_len == 24 and eng.stats()["ssm_state"]["restores"] == 1
    pool.check()
    spec = falcon.spec_of(T.cfg())
    assert eng.block_bytes == spec.cache_row_bytes() * 8 == 3 * 2 * 2 * 8 * 2 * 8
    assert eng.state_bytes == spec.state_bytes() \
        == 3 * (8 * 8 * 16 * 4 + 3 * 128 * 2)


def test_bytes_count_both_states_in_every_layer():
    spec = falcon.spec_of(T.cfg())
    assert spec.ssm_layers == spec.attention_layers == (0, 1, 2)
    assert spec.full_layers == (0, 1, 2) and not spec.window_layers
    big_cfg = E.committed("configs", "falcon-h1-34b-pp12")
    big = falcon.spec_of(big_cfg)
    assert big.cache_row_bytes() == 12288            # a position, six layers
    assert big.state_bytes() == 6 * (32 * 128 * 256 * 4 + 3 * 5120 * 2)
    assert falcon.h_bytes(big_cfg) == 32 * 128 * 256 * 4
    assert falcon.kv_row_bytes(big_cfg) == 2048
    # 5,255 M parameters at the cut (the configuration's `reduced_note`)
    assert round(falcon.n_params(big_cfg) / 1e6) == 5255
    assert big.ssm.in_dim == 9248 and big.ssm.conv_dim == 5120
    assert big.ssm.d_inner == 4096 != 2 * big.d_model    # mamba_d_ssm
    assert big.num_heads // big.kv_heads == 5 and big.d_head == 128
    assert big.vocab == 261120 and big.d_inner == 21504
    assert big.multipliers.lm_head == 2 ** -7
    assert big.rope.theta == 1e11 and big.rope.dim == 128


def test_tick_spans_carry_state_rows_and_the_lanes_blocks():
    eng, _ = T.engine(T.cfg(), 7)
    first = eng.submit(_prompts(seed=3)[0], 12)
    while not first.tokens:
        eng.step()
    mark = tracing.mark()
    eng.submit(_prompts(seed=4)[4], 3)          # 41 tokens: chunks 16, 16, 9
    eng.run_until_idle()
    ticks = [s for s in tracing.spans_since(mark) if s.name == "engine/tick"]
    mixed = [s for s in ticks if s.attrs["prefill"]]
    assert [s.attrs["prefill_tokens"] for s in mixed] == [16, 16, 9]
    # a lane reads its request's blocks up to its chunk: 2, 4, 6 of 8
    assert [s.attrs["lane_kv_blocks"] for s in mixed] == [2, 4, 6]
    # the first request decodes beside every chunk: one live decode row
    assert all(s.attrs["state_rows"] == 1 for s in mixed)
    assert all(s.attrs["kv_blocks"] >= s.attrs.get("lane_kv_blocks", 0)
               for s in ticks)
    decode = [s for s in ticks if not s.attrs["prefill"]]
    assert decode and {s.attrs["state_rows"] for s in decode} <= {1, 2}
    assert all(s.attrs["state_rows"] == s.attrs["active"] for s in decode)
    # no layer routes: the tick says so
    assert all(s.attrs["experts_touched"] == 0 for s in ticks)


def test_setup_counters_say_how_many_bodies_the_layers_traced():
    """`ssm/call` counts a call a layer a program, `ssm/body_traced` a trace
    of the decode kernel's body: three identical layers in two tick programs
    trace it once."""
    import jax
    jax.clear_caches()
    mark = tracing.mark()
    eng, _ = T.engine(T.cfg(), 7, n_slots=3)        # a shape no test traced
    eng.submit(_prompts()[1], 3)                    # a mixed, then decode ticks
    eng.run_until_idle()
    counts = {}
    for s in tracing.spans_since(mark):
        if s.name.startswith("ssm/"):
            key = (s.name, s.attrs["scope"])
            counts[key] = counts.get(key, 0) + 1
    assert counts["ssm/call", "ssd_chunk"] == 3             # the mixed tick
    assert counts["ssm/body_traced", "ssd_chunk"] == 3
    if ("ssm/call", "ssm_decode_update") in counts:         # the kernel path
        assert counts["ssm/call", "ssm_decode_update"] == 6
        assert counts["ssm/body_traced", "ssm_decode_update"] == 1


@pytest.mark.parametrize("option, value", E.REFUSED)
def test_what_is_not_built_for_the_model_is_refused_by_name(option, value):
    E.refused_by_name(T, option, value, "state-space state", n_snapshots=2)


def test_an_engine_without_a_snapshot_pool_is_refused():
    E.without_a_snapshot_pool_is_refused(T)


def test_a_spec_is_two_mixers_a_layer_and_nothing_else_beside_them():
    spec = falcon.spec_of(T.cfg())
    assert spec.mixer == "ssm+attention" and not spec.one_sublayer
    assert [spec.ffn_kind(i) for i in range(3)] == ["gated_silu"] * 3
    for change, match in (
            (dict(ssm=None), "ssm\\+attention"),
            (dict(rope=None, positions="none"), "ssm\\+attention"),
            (dict(layer_kinds=("attention",) * 3), "ssm\\+attention"),
            (dict(qk_norm=True), "ssm\\+attention"),
            (dict(tied_head=True), "ssm\\+attention"),
            (dict(ffn="relu"), "ssm\\+attention"),
            (dict(residual="post"), "ssm\\+attention"),
            (dict(mixer="both"), "mixer")):
        with pytest.raises(ValueError, match=match):
            dataclasses.replace(spec, **change)
    with pytest.raises(ValueError, match="multipliers"):
        dataclasses.replace(DecoderSpec.classic(),
                            multipliers=Multipliers(key=0.5))
    with pytest.raises(ValueError, match="five values"):
        Multipliers(ssm=(1.0, 1.0))


def test_the_training_graph_refuses_the_block_by_name():
    import paddle_tpu as pt
    from paddle_tpu.models import transformer
    pt.reset_default_programs()
    with pytest.raises(NotImplementedError, match="ssm\\+attention"):
        transformer.transformer_lm(max_len=16, model=falcon.spec_of(T.cfg()))
    pt.reset_default_programs()


def test_every_branch_has_unit_scale_after_its_multiplier(exact_matmuls):
    """The seeded initialisation (`assumed.init`): with the published
    multipliers applied, each mixer's and the feed-forward's contribution to
    the residual is of the order of the residual's own rows, the logits have
    unit variance, and the state-space output projection is centred."""
    import jax
    cfg = exact_matmuls
    scope = E.weights(falcon, cfg, 7)
    params = {n: scope.get(n) for n in falcon.param_names(cfg)}
    tokens = np.random.default_rng(0).integers(0, 97, 64)
    c = dict(cfg)
    cos, sin = ref.rope_cos_sin(64, cfg["head_dim"], cfg["rope_theta"])
    x = jnp.asarray(params["tok_emb"])[tokens].astype(jnp.float32) \
        * cfg["embedding_multiplier"]
    assert 0.7 < float(x.std()) < 1.4
    p = {n[3:]: v for n, v in params.items() if n.startswith("l0_")}
    with jax.default_matmul_precision("highest"):
        n = ref.rms(x, p["ln1.scale"], 1e-5)
        ssm = ref.mixer(n * cfg["ssm_in_multiplier"], p, c, None) \
            * cfg["ssm_out_multiplier"]
        att = ref.attention(n * cfg["attention_in_multiplier"], p, c, None,
                            cos, sin) * cfg["attention_out_multiplier"]
        ffn = ref.mlp(n, p, c)
    for name, branch in (("ssm", ssm), ("attention", att), ("ffn", ffn)):
        assert 0.3 < float(branch.std()) < 3.0, (name, float(branch.std()))
    assert float(jnp.abs(params["l0_ssm_out.w_0"].sum(0)).max()) < 1e-3
    logits = falcon.reference_logits(cfg, params, tokens, 64)
    assert 0.6 < float(np.std(logits)) < 1.6


def test_the_reference_runs_its_head_on_the_rows_that_are_read(exact_matmuls):
    cfg = exact_matmuls
    eng, params = T.engine(cfg, 7)      # the adapter's own: it notes requests
    req = eng.submit(_prompts()[1], 6)
    eng.run_until_idle()
    seq = np.asarray(req.prompt + req.tokens[:-1], np.int32)
    out = falcon.reference_logits(cfg, params, seq, 64)
    assert isinstance(out, falcon.RowsFrom) and out.first == len(req.prompt) - 1
    rows = out[len(req.prompt) - 1:]
    assert rows.shape == (len(req.tokens), 97)
    with pytest.raises(IndexError, match="only the rows"):
        out[0:]
    # a sequence no request emitted gets every row, as an array
    other = falcon.reference_logits(cfg, params, seq[:-1], 64)
    assert isinstance(other, np.ndarray) and other.shape == (len(seq) - 1, 97)
    np.testing.assert_allclose(other[len(req.prompt) - 1:], rows[:-1],
                               atol=1e-5)


def test_the_other_programs_are_unchanged_op_for_op():
    """An engine of the six dims builds no op this PR added: no multiplier's
    `scale`, no state, and its ticks carry neither new count."""
    import paddle_tpu as pt
    eng = serving.PagedKVEngine(n_slots=2, max_len=16, block_size=4,
                                scope=pt.Scope(), vocab=61, d_model=32,
                                d_inner=64, num_heads=4, num_layers=2)
    for program in (eng._program, eng._mixed_program):
        ops = [op.type for op in program.global_block().ops]
        assert not {"ssm_scan", "gated_rms_norm"} & set(ops)
    mark = tracing.mark()
    eng.submit([1, 2, 3, 4, 5, 6], 3)
    eng.run_until_idle()
    ticks = [s for s in tracing.spans_since(mark) if s.name == "engine/tick"]
    assert ticks and not any("state_rows" in s.attrs or
                             "experts_touched" in s.attrs for s in ticks)
    assert any("lane_kv_blocks" in s.attrs for s in ticks)
