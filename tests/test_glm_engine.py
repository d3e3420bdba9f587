"""GLM-5.3-Flash's stack through PagedKVEngine (ISSUE 61): four residual
streams mixed through Sinkhorn around every sub-layer, kda layers with
low-rank gate pairs, ONE sparse NoPE latent layer whose indexer scores pooled
keys in a second pool under the same block table and attends the best groups
and the tail, clamped gated pairs; prefill through the lanes, decode through
the pools and the slot's state, a prefix hit restored from a snapshot, against
the plain reference's full forward (benchmark/models/glm_reference.py: the
recurrence token by token, K and V expanded, the index scores as a full
matrix, experts looped). In float32 with exact matmuls the two agree to
rounding, so the tolerance that accepts the program refuses every planted
fault."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tiny_engines as E
from glm_tiny import TINY as T, glm, ref
from paddle_tpu.fusion import hyper_connection, moe
from paddle_tpu.fusion import sparse_latent_attention as sla
from paddle_tpu.models.decoder_spec import (HyperSpec, IndexerSpec,
                                            RopeSpec)
from paddle_tpu.observability import tracing

TOL = 1e-4          # in standard deviations of the reference's logits
TURNS = (5, 11, 3, 17)
HEAD = 24           # the shared context: three blocks of 8, six groups of 4


def _prompts(seed=1):
    return E.prompts(TURNS, HEAD, seed)


exact_matmuls = E.exact_matmuls_fixture(T)
# The shared context alone first (as the benchmark's warm-up sends it), then
# four turns behind it: prompts of 29, 35, 27 and 41 tokens cross a chunk of
# 16, end inside a group of 4 (29 = 7 groups + 1, 35 = 8 + 3, 27 = 6 + 3,
# 41 = 10 + 1) and decode ten tokens across two more group boundaries;
# `index_topk` 8 keeps 2 of up to 12 whole groups, so every row past position
# 11 drops some.
exact = E.exact_fixture(T, TURNS, HEAD)


def test_lanes_then_decode_agree_with_the_full_forward(exact):
    E.lanes_then_decode_agree(T, exact, TOL, [0, 24, 24, 24, 24])
    E.state_counts(T, exact, "ssm_state", restores=4, layers=4,
                   layers_with_kv=0)


def test_a_prefix_hit_equals_its_self_prefilled_twin(exact):
    """The hit brings the latent blocks, the POOLED index keys of the same
    blocks and the kda state; the twin writes all three itself."""
    E.a_prefix_hit_equals_its_twin(T, exact, _prompts())


def test_the_sparse_layer_is_dense_while_the_groups_fit_the_top_k(
        exact_matmuls):
    """`index_topk` 64 positions = 16 groups: no request of 64 positions
    holds more whole groups, so the read equals dense NoPE latent attention
    (the reference with the selection ignored)."""
    cfg = dict(exact_matmuls, index_topk=64)
    eng, params = T.engine(cfg, 7, scored=True)
    runs = [E.emitted_logits(eng, p, 10) for p in _prompts()[3:]]
    with glm.planted("selection_ignored", cfg, None) as c:
        assert T.worst(c, params, runs) < TOL
    assert T.worst(cfg, params, runs) < TOL


def test_a_request_preempted_and_resumed_reads_the_same(exact_matmuls):
    E.a_preempted_request_reads_the_same(T, exact_matmuls, TURNS)


@pytest.mark.parametrize("fault", glm.FAULTS)
def test_the_tolerance_catches_a_fault_planted_in_the_reference(exact, fault):
    """`glm.planted` (what benchmark/witness.py plants on the chip), one in
    each new mechanism: Sinkhorn left out, H_res the identity, the maps'
    dynamic part dropped, the streams collapsed to one, the tail not
    selected, the selection ignored, a group's key its first position's, the
    indexer unrotated, the clamp dropped, a restore one chunk stale."""
    E.a_planted_fault_is_caught(
        T, exact, fault, TOL, dict(system_prompt_tokens=HEAD, chunk_size=8),
        clean=slice(2))


def test_the_tolerance_catches_a_stale_index_pool_in_the_program(
        exact_matmuls):
    """The program's own second pool: with the shared blocks' pooled keys
    zeroed after the warm-up, a hit selects other groups and reads off."""
    cfg = exact_matmuls
    eng, params = T.engine(cfg, 7, scored=True)
    prompts = _prompts()
    E.emitted_logits(eng, prompts[0], 2)
    name = f"{eng._cache_prefix}_ci3"
    assert name in eng.cache_names
    eng.scope.set_var(name, jnp.zeros_like(eng.scope.get(name)))
    hit = E.emitted_logits(eng, prompts[4], 6)
    assert hit[0].shared_len == 24
    assert T.worst(cfg, params, [hit]) > 10 * TOL


def test_a_shared_contexts_snapshot_outlives_the_one_off_ones(exact_matmuls):
    """A pool of three entries, two shared contexts and a stream of turns
    behind the first: every turn writes a snapshot at its own prompt's end,
    which nothing will read. Least-recently-used alone would push the second
    context's snapshot out, and every later request behind it would prefill
    the whole context again (a hit is cut to the deepest node that holds a
    snapshot); the pager evicts the one-off entries, the deepest first."""
    cfg = exact_matmuls
    eng, params = T.engine(cfg, 7, scored=True, n_snapshots=3)
    rng = np.random.default_rng(3)
    a, b = (rng.integers(0, 97, HEAD).tolist() for _ in range(2))
    for context in (a, b):
        E.emitted_logits(eng, context, 2)
    for _ in range(5):
        req, _ = E.emitted_logits(eng, a + rng.integers(0, 97, 11).tolist(), 2)
        assert req.shared_len == HEAD
    assert eng.pager.snapshot_evictions >= 3
    run = E.emitted_logits(eng, b + rng.integers(0, 97, 5).tolist(), 6)
    assert run[0].shared_len == HEAD
    assert T.worst(cfg, params, [run]) < TOL
    # ... and once restored from, it is proven: the next pool-fuls of one-off
    # entries, shallower ones among them, do not move it
    for n in (1, 9, 1, 9, 1, 9):
        E.emitted_logits(eng, rng.integers(0, 97, 16 + n).tolist(), 2)
    assert E.emitted_logits(eng, b + [5, 6], 2)[0].shared_len == HEAD


def test_bfloat16_engine_keeps_its_pools_and_state_as_stated():
    eng, _ = T.engine(T.cfg(), 7)
    pre = eng._cache_prefix
    s = eng.scope.get(pre + "_kda_h0")
    assert s.dtype == jnp.float32 and s.shape == (4, 4, 16, 16)
    # ONE sparse layer: its latent pool (c alone: no rotated part) and beside
    # it the index pool, a pooled row a group of 4 positions
    assert eng.cache_names == [pre + "_c3", pre + "_ci3"]
    pool, ipool = (eng.scope.get(n) for n in eng.cache_names)
    assert pool.dtype == jnp.bfloat16 and pool.shape == (40, 1, 8, 128)
    assert ipool.dtype == jnp.bfloat16 and ipool.shape == (40, 1, 2, 16)


def test_bytes_count_the_state_the_latent_row_and_the_pooled_key():
    spec = glm.spec_of(T.cfg())
    assert spec.kda_layers == (0, 1, 2, 4) and spec.attention_layers == (3,)
    assert spec.moe_layers == (1, 2, 3, 4) and spec.residual == "mhc"
    assert spec.latent.rope is None and spec.latent.row_values == 32
    assert spec.cache_row_bytes() == 128 * 2 + 16 * 2 // 4
    assert spec.state_bytes() == 4 * (4 * 16 * 16 * 4 + 3 * 192 * 2)
    assert spec.moe.swiglu_limit == 1.5 and spec.kda.gate_rank == 8


def test_tick_spans_carry_the_sparse_reads_counts():
    eng, _ = T.engine(T.cfg(), 7)
    prompts = _prompts()
    eng.submit(prompts[0], 2)
    eng.run_until_idle()
    mark = tracing.mark()
    eng.submit(prompts[4], 4)       # 41 tokens: 24 shared, 16 + 1 in lanes
    eng.run_until_idle()
    ticks = [s for s in tracing.spans_since(mark) if s.name == "engine/tick"]
    keys = {"dsa_rows", "dsa_live_positions", "dsa_selected_positions",
            "dsa_scored_rows", "index_pool_rows", "state_rows", "kv_blocks"}
    assert all(keys <= set(s.attrs) for s in ticks)
    # the rows the selection sorted: the op's own `rung` (whole steps of 8)
    # for the tick's live rows (ONE sparse layer), of the decode tick's slots
    # or of the mixed tick's slots and lanes; never fewer than the rows that
    # hold a token
    for s in ticks:
        rows = eng.n_slots + (eng.n_lanes * eng.chunk_tokens
                              if s.attrs.get("prefill") else 0)
        assert s.attrs["dsa_scored_rows"] \
            == sla.rung(s.attrs["dsa_rows"], rows) >= s.attrs["dsa_rows"]
    mixed = [s for s in ticks if s.attrs.get("prefill")]
    assert [s.attrs["dsa_rows"] for s in mixed] == [16, 1]
    assert [s.attrs["dsa_scored_rows"] for s in mixed] == [16, 8]
    # rows at positions 24..39 hold 25..40 positions and attend 2 groups of
    # 4 and the tail of (t + 1) % 4
    assert mixed[0].attrs["dsa_live_positions"] == sum(range(25, 41))
    assert mixed[0].attrs["dsa_selected_positions"] == 16 * 8 + 4 * 6
    assert mixed[0].attrs["index_pool_rows"] == 4
    decode = [s for s in ticks if not s.attrs.get("prefill")]
    assert [s.attrs["dsa_live_positions"] for s in decode] == [42, 43, 44]
    assert [s.attrs["dsa_selected_positions"] for s in decode] == [10, 11, 8]
    assert all(s.attrs["index_pool_rows"] == 1 for s in decode)
    assert all(s.attrs["dsa_scored_rows"] == min(8, eng.n_slots)
               for s in decode)


def test_eight_ranks_shares_and_the_shared_expert_once_add_up():
    """The deployment's arithmetic: each of eight ranks holds two of the 16
    experts, the router and the shared expert; the ranks' routed parts, the
    shared expert counted once, sum to the uncut layer."""
    rng = np.random.default_rng(2)
    H, Fe, E, n = 32, 16, 16, 24
    norm = lambda *shape: jnp.asarray(  # noqa: E731
        rng.standard_normal(shape) * shape[-2] ** -0.5, jnp.float32)
    name = "moe"
    p = {f"{name}_router.w_0": norm(H, E),
         f"{name}_router_bias": jnp.asarray(rng.uniform(-0.2, 0.2, E),
                                            jnp.float32),
         f"{name}_experts_gate": norm(E, H, Fe),
         f"{name}_experts_up": norm(E, H, Fe),
         f"{name}_experts_down": norm(E, Fe, H)}
    for m, shape in (("gate", (H, Fe)), ("up", (H, Fe)), ("down", (Fe, H))):
        p[f"{name}_shared_{m}.w_0"] = norm(*shape)
    u = jnp.asarray(rng.standard_normal((n, H)), jnp.float32)
    cfg = dict(num_experts_per_tok=3, n_routed_experts=E,
               routed_scaling_factor=2.5, swiglu_limit=1.5)
    with jax.default_matmul_precision("highest"):
        whole = ref.moe(u, p, name, cfg)
        shared = ref.gated(u, p, name + "_shared", cfg)
        total = jnp.zeros_like(whole)
        for rank in range(8):
            held = range(2 * rank, 2 * rank + 2)
            part = dict(p, **{f"{name}_experts_{m}":
                              p[f"{name}_experts_{m}"][held.start:held.stop]
                              for m in ("gate", "up", "down")})
            total += ref.moe(u, part, name, cfg, held) \
                - (shared if rank else 0.0)
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               atol=1e-5)


def test_the_clamp_holds_planted_values_past_the_limit():
    """Rows whose gate and up pre-activations are planted at +-25, past a
    limit of 10: the walk's composite and its kernel body (interpreted)
    compute silu(min(gate, 10)) * clip(up, -10, 10)."""
    rng = np.random.default_rng(3)
    n, d, f = 8, 128, 128
    x = jnp.asarray(rng.standard_normal((n, d)), jnp.float32)
    big = jnp.full((d, f), 25.0 / d, jnp.float32)
    gate = jnp.stack([big * jnp.sign(x[0])[:, None], -big])
    up = jnp.stack([-big * jnp.sign(x[0])[:, None], big])
    down = jnp.asarray(rng.standard_normal((2, f, d)) * f ** -0.5,
                       jnp.float32)
    w = jnp.ones((2, n, 1), jnp.float32)
    rows = jnp.asarray([n, n], jnp.int32)
    g = jnp.einsum("nd,edf->enf", x, gate)
    assert float(jnp.abs(g[0, 0]).min()) > 10.0
    want = jnp.einsum(
        "enf,efd->nd", jax.nn.silu(jnp.minimum(g, 10.0)) * jnp.clip(
            jnp.einsum("nd,edf->enf", x, up), -10.0, 10.0), down)
    for backend in ("xla", "pallas_interpret"):
        got = moe.experts(x, w, rows, gate, up, down, backend=backend,
                          limit=10.0)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)
    free = moe.experts(x, w, rows, gate, up, down, backend="xla")
    assert float(jnp.abs(free - want).max()) > 1.0


def test_sinkhorn_leaves_the_stream_map_doubly_stochastic():
    rng = np.random.default_rng(4)
    n, d = 4, 16
    x = jnp.asarray(rng.standard_normal((6, n * d)), jnp.float32)
    p = jnp.asarray(rng.standard_normal((n * d, 2 * n + n * n)) * 0.1,
                    jnp.float32)
    b = jnp.asarray(rng.standard_normal(2 * n + n * n), jnp.float32)
    _, h_post, h_res = hyper_connection.maps(
        x, p, jnp.ones(3), b, n, 20, 1e-6, 1e-5)
    np.testing.assert_allclose(np.asarray(h_res.sum(-1)), 1.0, atol=1e-3)
    np.testing.assert_allclose(np.asarray(h_res.sum(-2)), 1.0, atol=1e-5)
    assert float(h_post.min()) > 0 and float(h_post.max()) < 2


@pytest.mark.parametrize("option, value", E.REFUSED)
def test_what_is_not_built_for_the_model_is_refused_by_name(option, value):
    E.refused_by_name(T, option, value, "indexer's pool", n_snapshots=2)


def test_the_spec_raises_for_what_no_graph_builds():
    spec = glm.spec_of(T.cfg())
    with pytest.raises(NotImplementedError, match="shares another layer"):
        IndexerSpec(4, 16, 8, 4, RopeSpec(8), share="shared")
    with pytest.raises(NotImplementedError, match="hc_mult streams"):
        dataclasses.replace(spec, norm="layer_norm")
    with pytest.raises(ValueError, match="HyperSpec"):
        dataclasses.replace(spec, hyper=None)
    with pytest.raises(ValueError, match="IndexerSpec selects"):
        dataclasses.replace(spec, latent=dataclasses.replace(
            spec.latent, q_lora_rank=None))
    with pytest.raises(ValueError, match="whole number of groups"):
        IndexerSpec(4, 16, 6, 4, RopeSpec(8))
    with pytest.raises(ValueError, match="at least two streams"):
        HyperSpec(mult=1)
    with pytest.raises(NotImplementedError, match="swiglu_limit"):
        dataclasses.replace(spec.moe, swiglu_limit=-1.0)


def test_an_unrotated_latent_row_without_an_indexer_needs_no_positions():
    spec = glm.spec_of(T.cfg())
    plain = dataclasses.replace(spec, indexer=None, positions="none")
    assert plain.latent.row_lanes == 128 and plain.latent.rope_dim == 0
    with pytest.raises(ValueError, match="positions='none'"):
        dataclasses.replace(spec, indexer=None)


def test_the_routers_bias_sends_this_rank_its_share():
    cfg = T.cfg(**T.F32, max_len=512)
    scope = E.weights(glm, cfg, 11)
    params = {n: scope.get(n) for n in glm.param_names(cfg)}
    tokens = np.random.default_rng(0).integers(0, 97, 512)
    x = jnp.asarray(params["tok_emb"])[tokens].astype(jnp.float32)
    x = ref.rms(x, jnp.ones(64), 1e-5)
    for i in (1, 4):
        _, keys = ref.scores_and_keys(x, params, f"l{i}_moe")
        idx = np.asarray(ref.select(keys, cfg))
        counts = np.bincount(idx.ravel(), minlength=16)
        assert counts.max() / counts.mean() < 2.0
        assert 0.3 < (idx < 8).mean() < 0.7
