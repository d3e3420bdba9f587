"""The model is described in one place (ISSUE 31).

- Every graph that runs decoder layers through a KV cache or the flash path
  — the seven LM graphs and `transformer_generate` — builds each layer
  through `models.transformer._decoder_block`, the one caller of
  `_add_norm` outside the NMT training layers.
- An engine's feed arrays are made from what the tick builder declared
  (`serving.engine._feed_arrays`), so a feed exists on both sides or on
  neither; binding the step swaps them for views of the one host buffer a
  launch transfers (ISSUE 33).
"""

import ast
import inspect

import jax
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers, serving
from paddle_tpu.core import unique_name
from paddle_tpu.framework.program import Program, program_guard
from paddle_tpu.models import transformer as T
from paddle_tpu.serving.engine import _feed_arrays
from paddle_tpu.serving.kv_pager import prefill_chunk_tokens

DIMS = dict(vocab=61, d_model=32, d_inner=64, num_heads=4, num_layers=2)
NMT = dict(src_vocab=50, tgt_vocab=60, d_model=32, d_inner=64, num_heads=4,
           num_layers=2)

#: graph -> (builder, LayerNorms a layer)
GRAPHS = {
    "transformer_lm": (lambda: T.transformer_lm(max_len=16, **DIMS), 2),
    "transformer_lm_generate": (
        lambda: T.transformer_lm_generate(max_gen=8, beam_size=2, **DIMS), 2),
    "decode_tick": (
        lambda: T.transformer_lm_decode_tick(4, max_len=24, **DIMS), 2),
    "spec_verify_tick": (
        lambda: T.transformer_lm_spec_verify_tick(4, 3, max_len=24, **DIMS),
        2),
    "paged_decode_tick": (
        lambda: T.transformer_lm_paged_decode_tick(4, 20, 4, 6, **DIMS), 2),
    "paged_mixed_tick": (
        lambda: T.transformer_lm_paged_mixed_tick(4, 2, 8, 20, 4, 6, **DIMS),
        2),
    "paged_spec_verify_tick": (
        lambda: T.transformer_lm_paged_spec_verify_tick(4, 3, 20, 4, 6,
                                                        **DIMS), 2),
    "transformer_generate": (
        lambda: T.transformer_generate(max_src_len=10, max_gen=8, beam_size=2,
                                       **NMT), 3),
}


def _op_types(build):
    main, startup = Program(), Program()
    with program_guard(main, startup), unique_name.guard():
        build()
    return [op.type for b in main.blocks for op in b.ops]


@pytest.mark.parametrize("graph", sorted(GRAPHS))
def test_every_graph_builds_its_layers_through_the_one_block(graph,
                                                             monkeypatch):
    """Swap the norm inside `_decoder_block` (and nowhere else) for one
    that leaves a mark: every layer of every graph carries the mark."""
    build, norms = GRAPHS[graph]
    before = _op_types(build)

    block, add_norm = T._decoder_block, T._add_norm

    def marked_norm(*a, **kw):
        return layers.scale(add_norm(*a, **kw), scale=1.0, bias=0.0)

    def marked_block(*a, **kw):
        with monkeypatch.context() as m:
            m.setattr(T, "_add_norm", marked_norm)
            return block(*a, **kw)

    monkeypatch.setattr(T, "_decoder_block", marked_block)
    after = _op_types(build)
    n_layers = DIMS["num_layers"]
    assert after.count("scale") - before.count("scale") == norms * n_layers
    assert after.count("layer_norm") == before.count("layer_norm")
    assert len(after) == len(before) + norms * n_layers


def test_the_block_is_the_one_place():
    """`_add_norm` is called from the block and the two NMT training
    layers only, and no builder spells a projection's name of its own."""
    tree = ast.parse(inspect.getsource(T))
    callers = set()
    for fn in ast.walk(tree):
        if not isinstance(fn, ast.FunctionDef):
            continue
        for node in ast.walk(fn):
            if (isinstance(node, ast.Call)
                    and getattr(node.func, "id", None) == "_add_norm"):
                callers.add(fn.name)
    assert callers == {"_decoder_block", "encoder_layer", "decoder_layer"}
    for fn in tree.body:
        if isinstance(fn, ast.FunctionDef) and fn.name.startswith(
                "transformer_lm"):
            consts = [n.value for n in ast.walk(fn)
                      if isinstance(n, ast.Constant)
                      and isinstance(n.value, str)
                      and fn.body and n is not getattr(fn.body[0], "value",
                                                       None)]
            assert not [c for c in consts if "_attn" in c or "_ln" in c
                        or "_ffn" in c], fn.name


# -- the feed format is declared once ---------------------------------------

S, BS, NLB, G, L = 3, 4, 6, 3, 2      # slots, block, blocks a request, γ+1
C = prefill_chunk_tokens(BS, NLB)      # a lane's tokens
I64, F32 = np.dtype("int64"), np.dtype("float32")

#: the format as the engines spelled it by hand before: name -> (shape, dtype)
_TOK_POS = {"tick_tok": ((S, 1), I64), "tick_pos": ((S, 1, 1), F32)}
#: ... and `tick_from_last` since a decode row can take its token from the
#: device (the ids of the tick before, read a launch late)
_FROM_LAST = {"tick_from_last": ((S, 1), I64)}
TICK = {**_TOK_POS, **_FROM_LAST}
PAGED_TICK = {**_TOK_POS, "tick_btab": ((S, NLB), I64),
              "tick_wblock": ((S,), I64), "tick_woff": ((S,), I64),
              **_FROM_LAST}
LANES = {"lane_tok": ((L, C), I64), "lane_pos": ((L, 1, 1), F32),
         "lane_btab": ((L, NLB), I64), "lane_wblocks": ((L * C // BS,), I64),
         "lane_rows": ((L,), I64), "lane_last": ((L,), I64)}
VERIFY = {"spec_tok": ((S, G), I64), "spec_pos": ((S, 1, 1), F32)}
PAGED_VERIFY = {**VERIFY, "spec_btab": ((S, NLB), I64),
                "spec_wblock": ((S, G), I64), "spec_woff": ((S, G), I64)}


def _slot_engine(**kw):
    return serving.ContinuousBatchingEngine(
        n_slots=S, max_len=BS * NLB, scope=pt.Scope(), **DIMS, **kw)


def _paged_engine(**kw):
    return serving.PagedKVEngine(
        n_slots=S, max_len=BS * NLB, block_size=BS, scope=pt.Scope(), **DIMS,
        **kw)


def _spec():
    return serving.SpecConfig(gamma=G - 1)


PROGRAMS = {
    # kind -> (engine, program of, its bound feeds, fetches, expected format)
    "slot": (_slot_engine, lambda e: (
        e._program, e._feeds, e._tick_fetches(), TICK)),
    "paged_decode": (_paged_engine, lambda e: (
        e._program, e._feeds, e._tick_fetches(), PAGED_TICK)),
    "paged_mixed": (_paged_engine, lambda e: (
        e._mixed_program, e._mixed_feeds, [e._mixed_ids],
        {**PAGED_TICK, **LANES})),
    "draft": (lambda: _slot_engine(speculative=_spec()), lambda e: (
        e.spec._draft_program, e.spec._draft_feeds,
        [e.spec._draft_ids, e.spec._draft_logp], TICK)),
    "verify": (lambda: _slot_engine(speculative=_spec()), lambda e: (
        e.spec._verify_program, e.spec._verify_feeds,
        [e.spec._verify_ids, e.spec._verify_logp], VERIFY)),
    "paged_verify": (lambda: _paged_engine(speculative=_spec()), lambda e: (
        e.spec._verify_program, e.spec._verify_feeds,
        [e.spec._verify_ids, e.spec._verify_logp], PAGED_VERIFY)),
}


@pytest.mark.parametrize("kind", sorted(PROGRAMS))
def test_feed_arrays_are_what_the_program_takes(kind):
    make, pick = PROGRAMS[kind]
    eng = make()
    program, bound, fetches, expected = pick(eng)
    feeds = _feed_arrays(program)
    # the helper against the format the engines used to restate by hand
    assert {n: (a.shape, a.dtype) for n, a in feeds.items()} == expected
    assert list(feeds) == list(expected)            # declaration order
    assert not any(a.any() for a in feeds.values())
    # ... against the builder's declarations
    declared = {v.name: (tuple(v.shape), np.dtype(v.dtype))
                for v in program.global_block().vars.values() if v.is_data}
    assert declared == expected
    # ... and against what the engine fills: bind() swapped every array
    # for a view of the step's one buffer, of the declared shape and the
    # dtype the feed has on the device (int64 is int32 there)
    assert list(bound) == list(feeds)
    assert all(bound[n].shape == a.shape
               and bound[n].dtype == jax.dtypes.canonicalize_dtype(a.dtype)
               for n, a in feeds.items())
    # the prepared step takes exactly these, and runs on them
    step = eng._exe.prepare(program, dict(feeds), fetches, eng.scope)
    assert list(step._compiled.feed_names) == list(feeds)
    out = step.run(feeds, return_numpy=True)
    assert len(out) == len(fetches)
    # bound, its views alias ONE buffer: the seed's slot, then a span a
    # feed in declaration order, nothing between them and nothing beside
    assert step.bind(feeds) is step and step.host_args == 1
    buf = step._buf
    assert buf.dtype == np.int32 and buf.ndim == 1
    at = buf.ctypes.data + buf.itemsize
    for n, (shape, _) in expected.items():
        view = feeds[n]
        assert view.shape == shape and view.flags.c_contiguous
        assert not view.flags.owndata and view.ctypes.data == at, n
        at += view.nbytes
    assert at == buf.ctypes.data + buf.nbytes
    # a fill through a view is a write to the pack the launch transfers
    first = next(iter(feeds))
    feeds[first].flat[0] = 7
    assert buf[1] == 7


def test_mixed_tick_runs_on_the_decode_ticks_arrays():
    eng = _paged_engine()
    assert eng.prefill == "chunked"
    for name, arr in eng._feeds.items():
        assert eng._mixed_feeds[name] is arr
    assert list(eng._lane_feeds) == list(LANES)
    assert all(eng._mixed_feeds[n] is a for n, a in eng._lane_feeds.items())
    # one buffer: the decode tick transfers its leading span, the mixed
    # tick the whole, and the fills write the arrays the engine kept
    whole, lead = eng._mixed_step._buf, eng._step._buf
    assert lead.ctypes.data == whole.ctypes.data and lead.size < whole.size
    assert lead.size == 1 + sum(a.size for a in eng._feeds.values())
    assert whole.size == 1 + sum(a.size for a in eng._mixed_feeds.values())
    assert eng._tok is eng._feeds["tick_tok"]
    assert eng._pos is eng._feeds["tick_pos"]
    assert eng.stats()["dispatch"] == {"main": {"host_args": 1},
                                       "mixed": {"host_args": 1},
                                       "late_reads": 0, "run_ahead": 0,
                                       "copies_found": 0}


def test_a_feed_on_one_side_only_cannot_happen():
    """A feed the builder adds reaches the engine's arrays with no engine
    edit: the helper reads the program, not a second list."""
    main, startup = Program(), Program()
    with program_guard(main, startup), unique_name.guard():
        T.transformer_lm_decode_tick(S, max_len=24, **DIMS)
        layers.data(name="tick_extra", shape=[S, 2], dtype="int32",
                    append_batch_size=False)
    feeds = _feed_arrays(main)
    assert list(feeds) == ["tick_tok", "tick_pos", "tick_from_last",
                           "tick_extra"]
    assert feeds["tick_extra"].shape == (S, 2)
    assert feeds["tick_extra"].dtype == np.dtype("int32")
    shared = _feed_arrays(main, share={"tick_tok": feeds["tick_tok"]})
    assert shared["tick_tok"] is feeds["tick_tok"]
    assert shared["tick_pos"] is not feeds["tick_pos"]


# -- a block has kinds (ISSUE 36) --------------------------------------------

from paddle_tpu.models.decoder_spec import (DecoderSpec, LatentSpec, MoESpec,
                                            RopeSpec)

KINDS = DecoderSpec.latent_moe(
    vocab=61, d_model=32, d_inner=64, num_heads=8, num_layers=3,
    latent=LatentSpec(q_lora_rank=24, kv_lora_rank=128, qk_nope_head_dim=8,
                      v_head_dim=8, rope=RopeSpec(dim=16, factor=4.0,
                                                  original_max=8)),
    moe=MoESpec(n_routed=16, top_k=4, d_expert=256, held=(4, 5, 6, 7),
                scaling=2.5))

PAGED_BUILDERS = {
    "paged_decode_tick": lambda **kw: T.transformer_lm_paged_decode_tick(
        4, 20, 4, 6, **kw),
    "paged_mixed_tick": lambda **kw: T.transformer_lm_paged_mixed_tick(
        4, 2, 8, 20, 4, 6, **kw),
}


def _program(build):
    main, startup = Program(), Program()
    with program_guard(main, startup), unique_name.guard():
        build()
    return main


@pytest.mark.parametrize("graph", sorted(PAGED_BUILDERS))
def test_the_classic_spec_builds_the_program_the_six_dims_built(graph):
    """`model=DecoderSpec.classic(...)` and the six dims are one program,
    op for op, attr for attr, name for name: the cells that serve the
    classic block run what they ran before a block had kinds."""
    build = PAGED_BUILDERS[graph]
    a = _program(lambda: build(**DIMS))
    b = _program(lambda: build(model=DecoderSpec.classic(**DIMS), **DIMS))

    def text(program):
        return [(op.type, sorted(op.input_names()), sorted(op.output_names()),
                 sorted((k, repr(v)) for k, v in op.attrs.items()))
                for blk in program.blocks for op in blk.ops]
    assert text(a) == text(b)
    assert sorted(a.global_block().vars) == sorted(b.global_block().vars)
    assert DecoderSpec.classic(**DIMS).is_classic and not KINDS.is_classic


@pytest.mark.parametrize("graph", sorted(PAGED_BUILDERS))
def test_a_block_of_other_kinds_goes_through_the_one_block(graph,
                                                           monkeypatch):
    """The latent, routed, pre-norm tick builds every layer through
    `_decoder_block` (its two norms a layer carry the mark; the final norm,
    outside the block, does not), declares the classic tick's feeds in the
    classic order, and names its parameters from the block's prefix."""
    build = lambda: PAGED_BUILDERS[graph](model=KINDS)
    before = _op_types(build)
    block, pre_norm = T._decoder_block, T._pre_norm

    def marked_block(*a, **kw):
        with monkeypatch.context() as m:
            m.setattr(T, "_pre_norm", lambda *a, **kw: layers.scale(
                pre_norm(*a, **kw), scale=1.0, bias=0.0))
            return block(*a, **kw)

    monkeypatch.setattr(T, "_decoder_block", marked_block)
    after = _op_types(build)
    assert after.count("scale") - before.count("scale") == 2 * KINDS.num_layers
    monkeypatch.undo()
    n = KINDS.num_layers
    # rms_norm: two a block, two inside latent attention, the final one
    assert before.count("rms_norm") == 4 * n + 1 and "layer_norm" not in before
    assert before.count("latent_head_proj") == 2 * n
    assert before.count("paged_cache_write") == n       # one pool a layer
    reads = 2 if graph == "paged_mixed_tick" else 1
    assert before.count("latent_paged_attention") == reads * n
    assert before.count("moe_route") == before.count("moe_experts") == n - 1
    assert "paged_decode_attention" not in before
    program = _program(build)
    feeds = _feed_arrays(program)
    s, bs, nlb = 4, 4, 6
    want = {"tick_tok": (s, 1), "tick_pos": (s, 1, 1), "tick_btab": (s, nlb),
            "tick_wblock": (s,), "tick_woff": (s,), "tick_from_last": (s, 1)}
    if graph == "paged_mixed_tick":
        want.update({"lane_tok": (2, 8), "lane_pos": (2, 1, 1),
                     "lane_btab": (2, nlb), "lane_wblocks": (4,),
                     "lane_rows": (2,), "lane_last": (2,)})
    assert {k: v.shape for k, v in feeds.items()} == want
    assert list(feeds) == list(want)
    params = {v.name: (tuple(v.shape), str(v.dtype))
              for v in program.global_block().vars.values()
              if v.persistable and not v.name.startswith("pgd")}
    assert params["l0_ffn_gate.w_0"] == ((32, 64), "bfloat16")
    assert params["l1_moe_experts_down"] == ((4, 256, 32), "bfloat16")
    assert params["l2_attn_kvb.w_0"] == ((128, 8 * 16), "bfloat16")
    assert params["l1_moe_router.w_0"] == ((32, 16), "bfloat16")
    assert "l0_moe_router.w_0" not in params and "l1_ffn_gate.w_0" not in params
    assert {"final_norm.scale", "lm_head.w_0", "tok_emb", "l2_ln2.scale",
            "l0_attn_qa_norm.scale"} <= set(params)
    pools = [v for v in program.global_block().vars.values()
             if v.name.startswith("pgd_c")]
    assert len(pools) == n and all(tuple(v.shape) == (20, 1, 4, 256)
                                   for v in pools)


def test_kinds_are_checked_by_name():
    with pytest.raises(ValueError, match="norm"):
        DecoderSpec.classic(**DIMS).__class__(**{
            **DecoderSpec.classic(**DIMS).__dict__, "norm": "batch_norm"})
    with pytest.raises(ValueError, match="LatentSpec"):
        DecoderSpec(61, 32, 64, 4, 2, attention="latent", positions="rotary")
    # rotary positions with full heads are built since PR 39, with a RopeSpec
    with pytest.raises(ValueError, match="RopeSpec"):
        DecoderSpec(61, 32, 64, 4, 2, positions="rotary")
    with pytest.raises(ValueError, match="key/value heads"):
        DecoderSpec(61, 32, 64, 4, 2, num_kv_heads=3)
    with pytest.raises(ValueError, match="ConvSpec"):
        DecoderSpec(61, 32, 64, 4, 2, layer_kinds=("conv", "attention"))
    odd = DecoderSpec(61, 32, 64, 4, 2, norm="rms_norm")
    with pytest.raises(NotImplementedError, match="no cache seam"):
        _program(lambda: T.transformer_lm_paged_decode_tick(4, 20, 4, 6,
                                                           model=odd))


# -- two mixers in one layer, under multipliers (ISSUE 54) --------------------

from paddle_tpu.models.decoder_spec import (ConvSpec, Multipliers,  # noqa: E402
                                            SsmSpec)

PARALLEL = DecoderSpec.parallel_ssm_gqa(
    vocab=61, d_model=32, d_inner=48, num_heads=10, num_kv_heads=2, d_head=8,
    num_layers=2, ssm=SsmSpec(heads=4, head_dim=8, groups=2, state=16),
    rope=RopeSpec(dim=8, theta=1e11),
    multipliers=Multipliers(embedding=5.66, attention_out=0.0375, key=0.011,
                            ssm_in=0.25, ssm_out=0.088,
                            ssm=(0.354, 0.25, 0.177, 0.5, 0.354),
                            mlp=(0.177, 0.0112), lm_head=2 ** -7))

OLDER = {
    "classic": DecoderSpec.classic(**DIMS),
    "latent_moe": KINDS,
    "conv_gqa_moe": DecoderSpec.conv_gqa_moe(
        61, 32, 64, 4, 2, ("conv", "attention"), RopeSpec(dim=8)),
    "ssm_gqa_moe": DecoderSpec.ssm_gqa_moe(
        61, 32, 4, 2, 8, ("ssm", "attention", "moe"),
        SsmSpec(heads=4, head_dim=8, groups=2, state=16),
        MoESpec(n_routed=8, top_k=2, d_expert=16, held=(0, 1), first_dense=0,
                topk_method="bias", activation="relu2", latent=16,
                d_shared=32)),
    "window_gqa_moe": DecoderSpec.window_gqa_moe(
        61, 32, 64, 4, 2, 8, ("window", "full"), 8, RopeSpec(dim=8)),
}


def _kinds_build(graph, spec):
    return lambda: PAGED_BUILDERS[graph](model=spec, n_snapshots=2,
                                         n_window_blocks=9)


@pytest.mark.parametrize("graph", sorted(PAGED_BUILDERS))
@pytest.mark.parametrize("kind", sorted(OLDER))
def test_the_five_older_specs_build_no_multipliers_op(graph, kind):
    """Multipliers of 1 build no op: a spec of one of the five older
    constructors builds the program it built before a block had them, op for
    op (`_scaled` returns its argument; the head's argmax moved out of
    `_lm_head` and is the same op on the same variable)."""
    spec = OLDER[kind]
    assert spec.multipliers == Multipliers() and spec.mixer == "kind"
    ops = _op_types(_kinds_build(graph, spec))
    assert "scale" not in ops or kind == "classic"
    # with the helper taken out the program is the same list of ops
    with pytest.MonkeyPatch.context() as m:
        m.setattr(T, "_scaled", lambda x, by: x)
        assert _op_types(_kinds_build(graph, spec)) == ops
    assert ops.count("arg_max") == 1


@pytest.mark.parametrize("graph", sorted(PAGED_BUILDERS))
def test_two_mixers_a_layer_go_through_the_one_block(graph, monkeypatch):
    """Every layer of the parallel spec is built by `_decoder_block`: two
    norms a layer (one for BOTH mixers, one for the feed-forward), a state
    scan AND a K/V write pair a layer, the multipliers as `scale` ops on
    activations and one vector product a mixer."""
    build = _kinds_build(graph, PARALLEL)
    before = _op_types(build)
    block, pre_norm = T._decoder_block, T._pre_norm

    def marked_block(*a, **kw):
        with monkeypatch.context() as m:
            m.setattr(T, "_pre_norm", lambda *a, **kw: layers.gelu(
                pre_norm(*a, **kw)))
            return block(*a, **kw)

    monkeypatch.setattr(T, "_decoder_block", marked_block)
    after = _op_types(build)
    n = PARALLEL.num_layers
    assert after.count("gelu") == 2 * n and "gelu" not in before
    monkeypatch.undo()
    assert before.count("rms_norm") == 2 * n + 1
    assert before.count("ssm_scan") == before.count("gated_rms_norm") == n
    assert before.count("paged_cache_write") == 2 * n
    reads = 2 if graph == "paged_mixed_tick" else 1
    assert before.count("paged_decode_attention") == reads * n
    assert before.count("rotary") == 2 * n
    # embedding, logits; a layer: ssm in, ssm out, key, attention out, gate,
    # feed-forward out (attention_in is 1: no op)
    assert before.count("scale") == 2 + 6 * n
    program = _program(build)
    names = {v.name: tuple(v.shape)
             for v in program.global_block().vars.values() if v.persistable}
    for i in range(n):
        assert names[f"l{i}_ssm_in.w_0"] == (32, 32 + 96 + 4)
        assert names[f"l{i}_ssm_out.w_0"] == (32, 32)
        assert names[f"l{i}_attn_q.w_0"] == (32, 80)
        assert names[f"l{i}_attn_k.w_0"] == (32, 16)
        assert names[f"l{i}_ffn_gate.w_0"] == (32, 48)
        assert f"l{i}_ln2.scale" in names and f"l{i}_ln3.scale" not in names
        for var in (f"pgd_ssm_h{i}", f"pgd_ssm_snap_h{i}", f"pgd_k{i}",
                    f"pgd_v{i}"):
            assert var in names, var
    assert names["lm_head.w_0"] == (32, 61) and "lm_head.w_1" not in names


def test_a_layer_that_is_both_answers_for_both():
    assert PARALLEL.ssm_layers == PARALLEL.attention_layers == (0, 1)
    assert PARALLEL.full_layers == (0, 1) and PARALLEL.conv_layers == ()
    assert PARALLEL.moe_layers == () and not PARALLEL.is_classic
    assert PARALLEL.ssm.d_inner == 32 and PARALLEL.ssm.in_dim == 132
    assert PARALLEL.cache_row_bytes() == 2 * 2 * 2 * 8 * 2
    assert PARALLEL.state_bytes() == 2 * (4 * 8 * 16 * 4 + 3 * 96 * 2)
    assert PARALLEL.window_row_bytes() == 0
    assert PARALLEL.rotates(0) and PARALLEL.rope_of(1) == PARALLEL.rope
    # the kinds partition the layers everywhere else
    nemo = OLDER["ssm_gqa_moe"]
    assert not set(nemo.ssm_layers) & set(nemo.attention_layers)


@pytest.mark.parametrize("change, match", [
    (dict(mixer="parallel"), "mixer"),
    (dict(ssm=None), "ssm\\+attention"),
    (dict(rope=None, positions="none"), "ssm\\+attention"),
    (dict(attention="latent", latent=KINDS.latent), "ssm\\+attention"),
    (dict(moe=KINDS.moe), "ssm\\+attention"),
    (dict(conv=ConvSpec()), "ssm\\+attention"),
    (dict(layer_kinds=("attention", "attention")), "ssm\\+attention"),
    (dict(attention_kinds=("full", "full")), "ssm\\+attention"),
    (dict(one_sublayer=True, layer_kinds=("ssm", "attention")),
     "ssm\\+attention"),
    (dict(qk_norm=True), "ssm\\+attention"),
    (dict(tied_head=True), "ssm\\+attention"),
    (dict(norm="layer_norm"), "ssm\\+attention"),
    (dict(residual="post"), "ssm\\+attention"),
    (dict(ffn="relu"), "ssm\\+attention"),
])
def test_every_combination_no_graph_builds_raises_by_name(change, match):
    import dataclasses
    with pytest.raises(ValueError, match=match):
        dataclasses.replace(PARALLEL, **change)


@pytest.mark.parametrize("kind", sorted(set(OLDER) - {"classic"}))
def test_multipliers_belong_to_the_parallel_block_alone(kind):
    import dataclasses
    with pytest.raises(ValueError, match="multipliers"):
        dataclasses.replace(OLDER[kind], multipliers=Multipliers(lm_head=0.5))


def test_the_training_graph_refuses_two_mixers_by_name():
    pt.reset_default_programs()
    with pytest.raises(NotImplementedError, match="ssm\\+attention"):
        T.transformer_lm(max_len=16, model=PARALLEL)
    pt.reset_default_programs()


# -- a kda mixer or latent attention by layer, group-limited routing (ISSUE 59)

from paddle_tpu.models.decoder_spec import KdaSpec  # noqa: E402

KDA = DecoderSpec.kda_latent_moe(
    vocab=61, d_model=32, d_inner=48, num_heads=4,
    layer_kinds=("kda", "attention", "kda"), kda=KdaSpec(heads=4, head_dim=8),
    latent=LatentSpec(q_lora_rank=None, kv_lora_rank=16, qk_nope_head_dim=8,
                      v_head_dim=8, rope=RopeSpec(dim=4, theta=6e6),
                      gate="head"),
    moe=MoESpec(n_routed=8, top_k=2, d_expert=16, held=(0, 1, 2, 3),
                first_dense=1, scaling=2.5, topk_method="group_bias",
                n_group=4, topk_group=2, norm_eps=1e-20))
NEW_OPS = {"kda_scan", "kda_gate_norm", "head_gate"}


@pytest.mark.parametrize("graph", sorted(PAGED_BUILDERS))
def test_kda_and_latent_layers_go_through_the_one_block(graph):
    """The seventh constructor: a layer's mixer by kind, the delta-rule scan
    with its slot state and snapshot pool beside ONE latent pool (the latent
    layer's), a dense first layer and group-limited routed layers after."""
    build = _kinds_build(graph, KDA)
    ops = _op_types(build)
    assert ops.count("kda_scan") == ops.count("kda_gate_norm") == 2
    assert ops.count("head_gate") == 1
    reads = 2 if graph == "paged_mixed_tick" else 1
    assert ops.count("latent_paged_attention") == reads
    assert ops.count("paged_cache_write") == 1
    assert ops.count("moe_route") == ops.count("moe_experts") == 2
    assert ops.count("rms_norm") == 2 * 3 + 1 + 1      # + kva_norm + final
    assert "ssm_scan" not in ops and "short_conv" not in ops
    program = _program(build)
    routes = [op for op in program.global_block().ops
              if op.type == "moe_route"]
    assert all((op.attrs["n_group"], op.attrs["topk_group"]) == (4, 2)
               for op in routes)
    names = {v.name: tuple(v.shape)
             for v in program.global_block().vars.values() if v.persistable}
    assert names["l0_kda_qkv.w_0"] == (32, 96)
    assert names["l0_kda_f.w_0"] == names["l0_kda_g.w_0"] == (32, 32)
    assert names["l0_kda_b.w_0"] == (32, 4) == names["l1_attn_gate.w_0"]
    assert names["l0_kda_taps"] == (96, 4) and names["l0_kda_dt_bias"] == (32,)
    assert names["l0_kda_norm.scale"] == (8,)
    assert names["l1_attn_q.w_0"] == (32, 4 * 12)
    assert "l1_attn_qa.w_0" not in names and "l1_attn_qb.w_0" not in names
    assert names["l0_ffn_gate.w_0"] == (32, 48)
    assert names["l1_moe_router_bias"] == (8,)
    slots = names["pgd_kda_h0"][0]
    assert names["pgd_kda_h0"] == names["pgd_kda_h1"] == (slots, 4, 8, 8)
    assert names["pgd_kda_snap_h1"] == (2, 4, 8, 8)
    assert names["pgd_kda_conv0"] == (slots, 3, 96)
    assert [n for n in names if n.startswith("pgd_c")] == ["pgd_c1"]


def test_a_spec_of_kda_and_latent_layers_counts_its_bytes():
    assert KDA.kda_layers == (0, 2) and KDA.attention_layers == (1,)
    assert KDA.recurrent is KDA.kda and KDA.recurrent_layers == (0, 2)
    assert KDA.ssm_layers == () and KDA.moe_layers == (1, 2)
    assert KDA.kda.conv_dim == 96 and KDA.kda.state_shape == (4, 8, 8)
    assert KDA.cache_row_bytes() == 128 * 2          # ONE latent layer's row
    assert KDA.state_bytes() == 2 * (4 * 8 * 8 * 4 + 3 * 96 * 2)
    assert KINDS.cache_row_bytes() == (
        KINDS.num_layers * KINDS.latent.row_lanes * 2)
    assert OLDER["ssm_gqa_moe"].recurrent is OLDER["ssm_gqa_moe"].ssm


@pytest.mark.parametrize("graph", sorted(PAGED_BUILDERS))
@pytest.mark.parametrize("kind", sorted(OLDER) + ["parallel_ssm_gqa"])
def test_the_six_older_specs_build_none_of_the_new_ops(graph, kind):
    """The six constructors that were build the programs they built: no op
    of the kda mixer, no gate a head, no group step on a router."""
    spec = OLDER.get(kind, PARALLEL)
    program = _program(_kinds_build(graph, spec))
    ops = program.global_block().ops
    assert not NEW_OPS & {op.type for op in ops}
    assert all("n_group" not in op.attrs for op in ops
               if op.type == "moe_route")


@pytest.mark.parametrize("change, match", [
    (dict(kda=None), "KdaSpec"),
    (dict(layer_kinds=("kda", "conv", "kda")), "layer_kinds"),
    (dict(layer_kinds=("attention",) * 3), "KdaSpec"),
    (dict(num_kv_heads=2), "grouped"),
    (dict(qk_norm=True), "'kda' layer"),
    (dict(tied_head=True), "'kda' layer"),
    (dict(ffn="relu"), "'kda' layer"),
    (dict(positions="none"), "rotary"),
    (dict(attention="full", latent=None), "RopeSpec"),
])
def test_every_kda_combination_no_graph_builds_raises_by_name(change, match):
    import dataclasses
    with pytest.raises(ValueError, match=match):
        dataclasses.replace(KDA, **change)


def test_latent_and_kda_specs_refuse_what_they_do_not_build():
    import dataclasses
    with pytest.raises(NotImplementedError, match="gate"):
        dataclasses.replace(KDA.latent, gate="element")
    with pytest.raises(ValueError, match="gate_lower_bound"):
        KdaSpec(heads=4, head_dim=8, gate_lower_bound=0.5)
    # a kda kind under full-head attention is no kind at all
    with pytest.raises(ValueError, match="layer_kinds"):
        dataclasses.replace(OLDER["conv_gqa_moe"],
                            layer_kinds=("kda", "attention"))


def test_the_training_graph_refuses_a_kda_layer_by_name():
    pt.reset_default_programs()
    with pytest.raises(NotImplementedError, match="'kda' layer"):
        T.transformer_lm(max_len=16, model=KDA)
    pt.reset_default_programs()
