"""Ling-3.0-flash's hybrid stack through PagedKVEngine (ISSUE 59): prefill
through the lanes with the delta-rule state carried from chunk to chunk in the
request's slot (the chunked form: one triangular solve a chunk and head), a
prefix hit that restores the state from the snapshot POOL and the ONE latent
layer's blocks from the prefix index, decode through the latent pool and the
slot's matrix state updated in place, against the plain reference's full
forward (benchmark/models/ling_reference.py: the recurrence token by token,
the latent layer uncached with K and V expanded, the group step, experts
looped). In float32 with exact matmuls the two agree to rounding, so the
tolerance that accepts the program refuses every planted fault."""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

import tiny_engines as E
from ling_tiny import TINY as T, ling, ref
from paddle_tpu.observability import tracing

TOL = 1e-4          # in standard deviations of the reference's logits
TURNS = (5, 11, 3, 17)
HEAD = 24           # the shared system prompt: three blocks of 8


def _prompts(seed=1):
    return E.prompts(TURNS, HEAD, seed)


exact_matmuls = E.exact_matmuls_fixture(T)
# the system prompt alone first (as the benchmark's warm-up sends it), then
# four turns behind it
exact = E.exact_fixture(T, TURNS, HEAD)


def test_lanes_then_decode_agree_with_the_full_forward(exact):
    # the warm-up prefilled the system prompt and left its snapshot at the
    # end of its third block; every turn resumed from it AND from the latent
    # layer's three shared blocks
    eng = E.lanes_then_decode_agree(T, exact, TOL, [0, 24, 24, 24, 24])
    E.state_counts(T, exact, "ssm_state", restores=4, written=3, valid=3,
                   pinned=0, layers=6, layers_with_kv=0)
    assert eng.pager.shared_blocks_total == 4 * 3


def test_a_prefix_hit_equals_its_self_prefilled_twin(exact):
    E.a_prefix_hit_equals_its_twin(T, exact, _prompts())


def test_a_hit_is_truncated_to_the_deepest_snapshot(exact_matmuls):
    """`kv-span-past-snapshot` stands beside latent blocks: a span of the
    latent layer's blocks is handed out only up to a node that holds a state
    snapshot."""
    E.a_hit_is_truncated_to_the_deepest_snapshot(T, exact_matmuls, TOL)


def test_a_request_preempted_and_resumed_reads_the_same(exact_matmuls):
    E.a_preempted_request_reads_the_same(T, exact_matmuls, TURNS)


@pytest.mark.parametrize("fault", ling.FAULTS)
def test_the_tolerance_catches_a_fault_planted_in_the_reference(exact, fault):
    """`ling.planted` (what benchmark/witness.py plants on the chip), one in
    each new mechanism: the decay, the delta correction, beta, the group
    step, the bias's role, the gate a head, a restore one chunk stale."""
    E.a_planted_fault_is_caught(
        T, exact, fault, TOL, dict(system_prompt_tokens=HEAD, chunk_size=8))


def test_the_tolerance_catches_a_stale_snapshot_in_the_program(exact_matmuls):
    cfg = exact_matmuls
    E.a_stale_snapshot_is_caught(
        T, cfg, TURNS, [f"_kda_snap_h{j}" for j in range(
            len(ling.spec_of(cfg).kda_layers))], 10 * TOL)


def test_bfloat16_engine_keeps_its_state_in_float32():
    eng, _ = T.engine(T.cfg(), 7)
    pre = eng._cache_prefix
    s = eng.scope.get(pre + "_kda_h0")
    assert s.dtype == jnp.float32 and s.shape == (4, 4, 16, 16)
    conv = eng.scope.get(pre + "_kda_conv5")
    assert conv.dtype == jnp.bfloat16 and conv.shape == (4, 3, 3 * 64)
    assert eng.scope.get(pre + "_kda_snap_h5").shape == (4, 4, 16, 16)
    # ONE latent pool: layer 5's, a padded row a position
    assert eng.cache_names == [pre + "_c5"]
    pool = eng.scope.get(eng.cache_names[0])
    assert pool.dtype == jnp.bfloat16 and pool.shape == (40, 1, 8, 128)


def test_bytes_count_the_state_and_the_one_latent_layer():
    spec = ling.spec_of(T.cfg())
    assert spec.kda_layers == (0, 1, 2, 3, 4, 6)
    assert spec.attention_layers == (5,) and spec.moe_layers == (1, 2, 3, 4,
                                                                 5, 6)
    assert spec.cache_row_bytes() == 128 * 2
    assert spec.state_bytes() == 6 * (4 * 16 * 16 * 4 + 3 * 192 * 2)
    big_cfg = E.committed("configs", "ling3-flash-ep4")
    big = ling.spec_of(big_cfg)
    assert big.layer_kinds == ("kda",) * 5 + ("attention", "kda")
    assert big.cache_row_bytes() == 1280
    assert big.kda.h_bytes() == 2097152 == ling.h_bytes(big_cfg)
    assert big.state_bytes() == 6 * (2097152 + 3 * 12288 * 2)
    assert ling.expert_bytes(big_cfg) == 3 * 2560 * 768 * 2
    # 5,232 M parameters at the cut (the configuration's `reduced_note`)
    assert round(ling.n_params(big_cfg) / 1e6) == 5232
    assert big.moe.n_routed == 512 and len(big.moe.held) == 128
    assert (big.moe.n_group, big.moe.topk_group, big.moe.top_k) == (8, 4, 8)
    assert big.latent.q_lora_rank is None and big.latent.gate == "head"
    assert big.latent.softmax_scale == 192 ** -0.5


def test_tick_spans_carry_the_state_rows_the_blocks_and_the_picks():
    eng, _ = T.engine(T.cfg(), 7)
    prompts = _prompts()
    eng.submit(prompts[0], 2)
    eng.run_until_idle()
    mark = tracing.mark()
    eng.submit(prompts[4], 4)
    eng.run_until_idle()
    ticks = [s for s in tracing.spans_since(mark) if s.name == "engine/tick"]
    # `kv_blocks` are the ONE latent layer's (a kda layer reads no block),
    # `routed_rows` the device's count of the picks that fell on held experts
    assert all({"state_rows", "kv_blocks", "routed_rows"} <= set(s.attrs)
               for s in ticks)
    decode = [s for s in ticks if not s.attrs.get("prefill")]
    # one live row, top-3 in each of six routed layers
    assert decode and all(s.attrs["state_rows"] == 1 for s in decode)
    assert all(0 <= s.attrs["routed_rows"] <= 18 for s in decode)
    mixed = [s for s in ticks if s.attrs.get("prefill")]
    assert [s.attrs["prefill_tokens"] for s in mixed] == [16, 1]
    assert all(s.attrs["routed_rows"] <= 18 * (
        s.attrs["state_rows"] + s.attrs["prefill_tokens"]) for s in mixed)


@pytest.mark.parametrize("option, value", E.REFUSED)
def test_what_is_not_built_for_the_model_is_refused_by_name(option, value):
    E.refused_by_name(T, option, value, "delta-rule state", n_snapshots=2)


def test_an_engine_without_a_snapshot_pool_is_refused():
    E.without_a_snapshot_pool_is_refused(T)


def test_a_clamped_expert_is_refused_by_name():
    with pytest.raises(NotImplementedError, match="swiglu_limit"):
        ling.spec_of(T.cfg(expert_swiglu_limit_list=[0] * 6 + [4]))


def test_the_spec_raises_for_what_no_graph_builds():
    spec = ling.spec_of(T.cfg())
    with pytest.raises(ValueError, match="layer_kinds"):
        dataclasses.replace(spec, layer_kinds=("conv",) * 7)
    with pytest.raises(ValueError, match="KdaSpec"):
        dataclasses.replace(spec, kda=None)
    with pytest.raises(ValueError, match="grouped"):
        dataclasses.replace(spec, num_kv_heads=2)


def test_the_routers_bias_sends_this_rank_its_share():
    """`balance_router_bias` under the group step: over fresh rows every
    expert of a routed layer is selected about equally often, so the two
    held groups of four get about half of the picks (a rank of the
    deployment: two groups of eight, a quarter)."""
    cfg = T.cfg(**T.F32, max_len=512)
    scope = E.weights(ling, cfg, 11)
    params = {n: scope.get(n) for n in ling.param_names(cfg)}
    c = dict(cfg, num_hidden_layers=7)
    tokens = np.random.default_rng(0).integers(0, 97, 512)
    x = jnp.asarray(params["tok_emb"])[tokens].astype(jnp.float32)
    x = ref.rms(x, jnp.ones(64), 1e-6)
    for i in (1, 6):
        _, keys = ref.scores_and_keys(x, params, f"l{i}_moe")
        idx = np.asarray(ref.select(keys, c))
        counts = np.bincount(idx.ravel(), minlength=16)
        assert counts.max() / counts.mean() < 2.0
        assert 0.3 < (idx < 8).mean() < 0.7
