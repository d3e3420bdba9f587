"""Nemotron-H's hybrid stack through PagedKVEngine (ISSUE 43): prefill
through the lanes with the state-space state carried from chunk to chunk in
the request's slot, a prefix hit that resumes from an entry of the snapshot
POOL (truncated to the deepest node that holds one), decode through the pool
and the slot's state updated in place, against the plain reference's full
forward (benchmark/models/nemotron_h_reference.py: the recurrence token by
token, K and V uncached, experts looped). In float32 with exact matmuls the
two agree to rounding, so the tolerance that accepts the program refuses
every planted fault."""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

import tiny_engines as E
from nemotron_h_tiny import TINY as T, nemo, ref
from paddle_tpu import serving
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
    # end of its third block; every turn resumed from it
    E.lanes_then_decode_agree(T, exact, TOL, [0, 24, 24, 24, 24])
    # prompts of 24, 29, 35, 27, 41 tokens: a snapshot where the last whole
    # block ends beyond the shared span (24; none; 32; none; 40)
    E.state_counts(T, exact, "ssm_state", restores=4, written=3, valid=3,
                   pinned=0)


def test_a_prefix_hit_equals_its_self_prefilled_twin(exact):
    E.a_prefix_hit_equals_its_twin(T, exact, _prompts())


def test_a_hit_is_truncated_to_the_deepest_snapshot(exact_matmuls):
    eng = E.a_hit_is_truncated_to_the_deepest_snapshot(T, exact_matmuls, TOL)
    st = eng.stats()["ssm_state"]
    assert st["hits_truncated"] == 1 and st["restores"] == 1


def test_snapshots_are_evicted_least_recently_used_under_a_pool_of_two(
        exact_matmuls):
    cfg = exact_matmuls
    eng, params = T.engine(cfg, 7, scored=True, n_snapshots=2)
    rng = np.random.default_rng(3)
    heads = [rng.integers(0, 97, 24).tolist() for _ in range(3)]
    turn = rng.integers(0, 97, 5).tolist()
    for h in heads:                      # three snapshots through two entries
        E.emitted_logits(eng, h, 2)
    st = eng.stats()["ssm_state"]
    assert st["written"] == 3 and st["evictions"] == 1 and st["valid"] == 2
    runs = [E.emitted_logits(eng, h + turn, 6) for h in reversed(heads)]
    # the first head's snapshot went: its K/V blocks are still indexed, but
    # the span is not handed out past a snapshot, so it prefills again (and
    # takes the least recently used entry for the state it leaves at 24)
    assert [r.shared_len for r, _ in runs] == [24, 24, 0]
    assert T.worst(cfg, params, runs) < TOL
    assert eng.pager.hits_truncated == 1
    eng.pager.pool.check()


def test_a_snapshot_is_void_once_its_nodes_block_is_evicted(exact_matmuls):
    cfg = exact_matmuls
    eng, params = T.engine(cfg, 7, scored=True, n_blocks=11)
    rng = np.random.default_rng(4)
    heads = [rng.integers(0, 97, 24).tolist() for _ in range(3)]
    turn = rng.integers(0, 97, 5).tolist()
    runs = [E.emitted_logits(eng, h + turn, 8) for h in heads]
    assert eng.pager.evictions > 0
    pool = eng.pager.stats()["snapshot_pool"]
    assert pool["valid"] < pool["written"]
    again = E.emitted_logits(eng, heads[0] + turn, 8)
    assert again[0].tokens == runs[0][0].tokens
    assert T.worst(cfg, params, runs + [again]) < TOL
    # every valid entry belongs to a node that is still in the index
    for entry, node in enumerate(eng.pager._snap_node):
        assert node is None or (node.snap == entry
                                and node.parent.children[node.key] is node)


@pytest.mark.parametrize("fault", nemo.FAULTS)
def test_the_tolerance_catches_a_fault_planted_in_the_reference(exact, fault):
    """`nemo.planted` (what benchmark/witness.py plants on the chip), one in
    each new mechanism: the state decayed by a wrong dt, a restore from a
    snapshot one chunk stale, the routed sum's scaling dropped."""
    E.a_planted_fault_is_caught(
        T, exact, fault, TOL, dict(system_prompt_tokens=HEAD, chunk_size=8))


def test_the_tolerance_catches_a_stale_snapshot_in_the_program(exact_matmuls):
    cfg = exact_matmuls
    E.a_stale_snapshot_is_caught(
        T, cfg, TURNS, [f"_ssm_snap_h{j}" for j in range(
            len(nemo.spec_of(cfg).ssm_layers))], 10 * TOL)


def test_bfloat16_engine_keeps_its_state_in_float32():
    eng, _ = T.engine(T.cfg(), 7)
    pre = eng._cache_prefix
    h = eng.scope.get(pre + "_ssm_h0")
    assert h.dtype == jnp.float32 and h.shape == (4, 16, 8, 16)
    conv = eng.scope.get(pre + "_ssm_conv4")
    assert conv.dtype == jnp.bfloat16 and conv.shape == (4, 3, 128 + 2 * 64)
    assert eng.scope.get(pre + "_ssm_snap_h4").shape == (4, 16, 8, 16)
    pool = eng.scope.get(eng.cache_names[0])
    assert pool.dtype == jnp.bfloat16 and len(eng.cache_names) == 2


def test_bytes_count_the_state_and_the_one_attention_layer():
    cfg = T.cfg()
    spec = nemo.spec_of(cfg)
    assert spec.ssm_layers == (0, 2, 4, 6, 9) and spec.attention_layers == (7,)
    assert spec.moe_layers == (1, 3, 5, 8, 10)
    assert spec.cache_row_bytes() == 2 * 2 * 8 * 2
    assert spec.state_bytes() == 5 * (16 * 8 * 16 * 4 + 3 * 256 * 2)
    big_cfg = E.committed("configs", "nemotron3-super-ep4")
    big = nemo.spec_of(big_cfg)
    assert big.cache_row_bytes() == 1024
    assert big.state_bytes() == 5 * (128 * 64 * 128 * 4 + 3 * 10240 * 2)
    assert nemo.h_bytes(big_cfg) == 128 * 64 * 128 * 4
    assert nemo.expert_bytes(big_cfg) == 2 * 1024 * 2688 * 2
    # 4,648 M parameters at the cut (the configuration's `reduced_note`)
    assert round(nemo.n_params(big_cfg) / 1e6) == 4648
    # every number of the catalog's row that is not reduced is as published
    assert big.ssm.in_dim == 18560 and big.ssm.conv_dim == 10240
    assert big.moe.latent == 1024 and big.moe.shared_width == 5376
    assert big.moe.n_routed == 512 and len(big.moe.held) == 128


def test_admit_and_tick_spans_carry_the_pools_counts():
    eng, _ = T.engine(T.cfg(), 7, n_snapshots=1)
    prompts = _prompts()
    eng.submit(prompts[0], 2)
    eng.run_until_idle()
    mark = tracing.mark()
    eng.submit(prompts[4], 3)            # 41 tokens: a hit, a new snapshot
    eng.run_until_idle()
    spans = tracing.spans_since(mark)
    admits = [s for s in spans if s.name == "engine/admit"
              and s.attrs.get("admitted")]
    assert [s.attrs["state_restored"] for s in admits] == [1]
    assert [s.attrs["snapshots_used"] for s in admits] == [1]
    ticks = [s for s in spans if s.name == "engine/tick"]
    # from position 24: one chunk of 16, whose last row ends block 4 (the
    # snapshot: the pool's one entry is pinned until this chunk read it, so
    # none is written), and one of 1
    assert [s.attrs["state_snapshots"] for s in ticks
            if s.attrs.get("prefill")] == [0, 0]
    assert all("experts_touched" in s.attrs for s in ticks)
    eng.submit(_prompts(seed=2)[1], 2)   # another head: takes the entry
    eng.run_until_idle()
    eng.submit([1], 1)                   # an admission after that tick
    eng.run_until_idle()
    later = [s for s in tracing.spans_since(mark) if s.name == "engine/admit"]
    assert sum(s.attrs.get("snapshot_evictions", 0) for s in later) == 1
    assert eng.stats()["ssm_state"]["evictions"] == 1


@pytest.mark.parametrize("option, value", E.REFUSED)
def test_what_is_not_built_for_the_model_is_refused_by_name(option, value):
    E.refused_by_name(T, option, value, "state-space state", n_snapshots=2)


def test_an_engine_without_a_snapshot_pool_is_refused():
    E.without_a_snapshot_pool_is_refused(T)


def test_the_other_programs_are_unchanged_op_for_op():
    """An engine of the six dims builds no op this PR added, and takes no
    snapshot pool."""
    import paddle_tpu as pt
    eng = serving.PagedKVEngine(n_slots=2, max_len=16, block_size=4,
                                scope=pt.Scope(), vocab=61, d_model=32,
                                d_inner=64, num_heads=4, num_layers=2,
                                n_snapshots=8)
    for program in (eng._program, eng._mixed_program):
        ops = [op.type for op in program.global_block().ops]
        assert not {"ssm_scan", "gated_rms_norm", "moe_route"} & set(ops)
    assert "lane_snap_src" not in eng._lane_feeds
    assert eng.n_snapshots == 0 and "ssm_state" not in eng.stats()
    assert eng.pager.stats()["snapshot_pool"] is None


def test_a_spec_is_one_kind_a_layer():
    spec = nemo.spec_of(T.cfg())
    assert spec.one_sublayer and spec.positions == "none"
    assert [spec.ffn_kind(i) for i in (0, 1, 7)] == ["none", "moe", "none"]
    with pytest.raises(ValueError, match="layer_kinds"):
        dataclasses.replace(spec, layer_kinds=("conv",) * 11)
    with pytest.raises(ValueError, match="SsmSpec"):
        dataclasses.replace(spec, ssm=None)
    with pytest.raises(NotImplementedError, match="activation"):
        dataclasses.replace(spec.moe, activation="gelu")


def test_the_routers_bias_is_made_so_that_the_load_is_even():
    """`balance_router_bias`: over fresh rows every expert of a routed layer
    is selected about equally often (a bias that favours four experts is
    what an uneven load reads like); the matrices behind relu^2 and the
    gated state output are centred over their fan-in."""
    import jax
    cfg = T.cfg(**T.F32, router_width=32, num_experts_per_tok=4,
                n_routed_experts=32, max_len=512)    # 512 rows to balance on
    scope = E.weights(nemo, cfg, 11)
    params = {n: scope.get(n) for n in nemo.param_names(cfg)}
    c = dict(cfg, num_hidden_layers=cfg["num_layers"])
    tokens = np.random.default_rng(0).integers(0, 97, 512)     # fresh rows

    def load(bias_of):
        """max over mean of the experts' selections in the LAST routed
        layer, with every router bias through `bias_of`."""
        p = {n: (bias_of(v) if n.endswith("_router_bias") else v)
             for n, v in params.items()}
        x = jnp.asarray(p["tok_emb"])[tokens].astype(jnp.float32)
        for i, kind in enumerate(nemo.layer_kinds(cfg)):
            h = ref.rms(x, p[f"l{i}_ln1.scale"], 1e-5)
            if kind == "moe":
                _, keys = ref.scores_and_keys(h, p, f"l{i}_moe")
                _, idx = jax.lax.top_k(keys, 4)
                f = ref.moe(h, p, f"l{i}_moe", c)
            elif kind == "ssm":
                f = ref.mixer(h, p, f"l{i}_ssm", c, None)
            else:
                f = ref.attention(h, p, f"l{i}_attn", c, None)
            x = x + f
        counts = np.bincount(np.asarray(idx).ravel(), minlength=32)
        return counts.max() / counts.mean()
    skew = lambda b: jnp.zeros_like(b).at[:4].set(0.3)      # noqa: E731
    balanced, skewed = load(lambda b: b), load(skew)
    assert balanced < 1.8 and skewed > 2 * balanced, (balanced, skewed)
    for name, axis in (("l1_moe_experts_down", 1), ("l1_moe_shared_down.w_0",
                                                    0), ("l0_ssm_out.w_0", 0)):
        assert float(jnp.abs(params[name].sum(axis)).max()) < 1e-5, name
    assert float(jnp.abs(params["l1_moe_experts_up"].sum(1)).max()) > 1e-2
    bias = params["l10_moe_router_bias"]
    assert bias.dtype == jnp.float32 and abs(float(bias.mean())) < 1e-6
