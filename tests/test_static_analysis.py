"""Static program analyzer tests (framework/analysis.py).

Three layers under test, mirroring the subsystem:
1. shape/dtype inference — every model builder in paddle_tpu/models verifies
   clean (train AND cloned-for-test programs), and seeded corruption (a
   shape lie) is caught with block/op#/op.type provenance;
2. structural + parallel verification — dropped producers, duplicate
   writers, broken pp_send/pp_recv pairs, displaced dp_grad_comm;
3. pass sanitizer — a deliberately broken pass rewrite is attributed to the
   pass by name (≙ the HLO verifier failing between two XLA passes).
"""

import os

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers, models
from paddle_tpu.core.enforce import NotFoundError
from paddle_tpu.framework import analysis
from paddle_tpu.framework.passes import Pass, get_pass, register_pass


def _errors(diags):
    return [d for d in diags if d.severity == "error"]


def _codes(diags):
    return {d.code for d in diags}


# ---------------------------------------------------------------------------
# every model builder verifies clean (train + cloned-for-test)
# ---------------------------------------------------------------------------


def _mt_train():
    from paddle_tpu.models import machine_translation as mt
    src = layers.data("src", shape=[6], dtype="int64")
    src_lens = layers.data("src_lens", shape=[], dtype="int64")
    tgt_in = layers.data("tgt_in", shape=[6], dtype="int64")
    tgt_out = layers.data("tgt_out", shape=[6], dtype="int64")
    tgt_mask = layers.data("tgt_mask", shape=[6], dtype="float32")
    return mt.train_net(src, src_lens, tgt_in, tgt_out, tgt_mask,
                        dict_size=200, embed_dim=16, hidden_dim=16)[0]


def _tp_transformer():
    """tp-annotated transformer (framework/sharding.py): analyze_program
    folds sharding propagation in whenever live tp annotations exist, so
    this builder keeps the propagation rules green on the flagship DAG."""
    from paddle_tpu.parallel import annotate_tp
    loss, _ = models.transformer.transformer_lm(
        vocab=256, max_len=16, d_model=32, d_inner=64, num_heads=2,
        num_layers=2, mean_loss=True)
    annotate_tp()
    return loss


def _decode_tick():
    """The continuous-batching engine's compiled step: an INFERENCE
    program (returns None — no loss to minimize), linted plain-config
    only like the serving path in tools/lint_program.py."""
    models.transformer.transformer_lm_decode_tick(
        n_slots=2, vocab=100, max_len=16, d_model=32, d_inner=64,
        num_heads=4, num_layers=2)
    return None


def _prefill():
    """The teacher-forced prefill + generation program the engine's
    prompt phase shares weights with."""
    models.transformer.transformer_lm_generate(
        vocab=100, max_gen=4, d_model=32, d_inner=64, num_heads=4,
        num_layers=2, beam_size=4)
    return None


def _paged_decode_tick():
    """The paged engine's compiled step (serving/kv_pager.py): block-table
    gather + paged_cache_write over the shared pools."""
    models.transformer.transformer_lm_paged_decode_tick(
        n_slots=2, n_blocks=9, block_size=4, blocks_per_req=4,
        vocab=100, d_model=32, d_inner=64, num_heads=4, num_layers=2)
    return None


def _paged_mixed_tick():
    """The paged engine's second compiled step: the decode rows plus two
    prefill lanes of two blocks, ONE paged_cache_write a pool a layer for
    rows and whole blocks alike."""
    models.transformer.transformer_lm_paged_mixed_tick(
        n_slots=2, n_lanes=2, chunk=8, n_blocks=9, block_size=4,
        blocks_per_req=4, vocab=100, d_model=32, d_inner=64, num_heads=4,
        num_layers=2)
    return None


def _quant_decode_tick():
    """The weight-only quantized engine's compiled step: the decode tick
    rewritten in place by quantize_params_pass (startup runs first so the
    pass has real weight arrays to quantize) — keeps qmatmul/qlookup
    shape inference green in the analyzer."""
    import paddle_tpu as pt
    from paddle_tpu.framework.passes import get_pass
    models.transformer.transformer_lm_decode_tick(
        n_slots=2, vocab=100, max_len=16, d_model=32, d_inner=64,
        num_heads=4, num_layers=2)
    pt.Executor().run(pt.default_startup_program())
    get_pass("quantize_params_pass", bits=8)(
        pt.default_main_program(), pt.global_scope())
    return None


def _draft_tick():
    """The speculative draft model's compiled tick (serving/speculative.py):
    the target architecture at half depth under the draft_ prefix, logp
    emitted for rejection sampling."""
    models.transformer.transformer_lm_decode_tick(
        n_slots=2, vocab=100, max_len=16, d_model=32, d_inner=64,
        num_heads=4, num_layers=1, cache_prefix="sadr",
        param_prefix="draft_", emit_logp=True)
    return None


def _spec_verify_tick():
    """The speculative verify forward: γ+1 window positions scored
    through one target forward against the slot caches."""
    models.transformer.transformer_lm_spec_verify_tick(
        n_slots=2, gamma=3, vocab=100, max_len=16, d_model=32,
        d_inner=64, num_heads=4, num_layers=2)
    return None


def _paged_spec_verify_tick():
    """... and its paged twin: the same window through the block-table
    gather + paged_cache_write path."""
    models.transformer.transformer_lm_paged_spec_verify_tick(
        n_slots=2, gamma=3, n_blocks=9, block_size=4,
        blocks_per_req=4, vocab=100, d_model=32, d_inner=64,
        num_heads=4, num_layers=2)
    return None


# one builder per model module (small configs: the analyzer only cares
# about the op DAG, not widths)
MODEL_BUILDERS = {
    "mnist_mlp": lambda: models.mnist.mlp()[0],
    "mnist_conv": lambda: models.mnist.conv_net()[0],
    "resnet_cifar10": lambda: models.resnet.resnet_cifar10(depth=20)[0],
    "resnet_imagenet": lambda: models.resnet.resnet_imagenet(depth=50)[0],
    "vgg16_cifar": lambda: models.vgg.vgg16_cifar()[0],
    "alexnet": lambda: models.alexnet.alexnet_imagenet()[0],
    "googlenet": lambda: models.googlenet.googlenet_imagenet()[0],
    "se_resnext": lambda: models.se_resnext.se_resnext_imagenet(
        depth=50)[0],
    "deepfm": lambda: models.deepfm.deepfm()[0],
    "ssd": lambda: models.ssd.ssd_detector()[0],
    "ocr_crnn": lambda: models.ocr_crnn.crnn_ctc()[0],
    "stacked_lstm": lambda: models.stacked_lstm.stacked_lstm_net(
        dict_dim=1000, emb_dim=64, hid_dim=64)[0],
    "lstm_lm": lambda: models.stacked_lstm.lstm_language_model(
        vocab_size=1000, emb_dim=32, hid_dim=32)[0],
    "transformer_lm": lambda: models.transformer.transformer_lm(
        vocab=256, max_len=16, d_model=32, d_inner=64, num_heads=2,
        num_layers=2)[0],
    "transformer_lm_tp": _tp_transformer,
    "transformer_lm_decode_tick": _decode_tick,
    "transformer_lm_paged_decode_tick": _paged_decode_tick,
    "transformer_lm_paged_mixed_tick": _paged_mixed_tick,
    "transformer_lm_quant_decode_tick": _quant_decode_tick,
    "transformer_lm_draft_tick": _draft_tick,
    "transformer_lm_spec_verify_tick": _spec_verify_tick,
    "transformer_lm_paged_spec_verify_tick": _paged_spec_verify_tick,
    "transformer_lm_prefill": _prefill,
    "machine_translation": _mt_train,
}


def test_builder_tables_cover_the_same_models():
    """tools/lint_program.py keeps its own builder table (realistic sizes
    for the memory estimate; this file uses small configs for speed) —
    this guard keeps the two name sets from drifting: a model added to
    one table must be added to the other."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "_lint_program", os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "tools", "lint_program.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    lint_names = set(mod._builders())
    test_names = set(MODEL_BUILDERS)
    # lint's "mnist"/"resnet"/"vgg" = this file's mnist_mlp/resnet_imagenet/
    # vgg16_cifar; normalize the aliases before comparing
    alias = {"mnist": "mnist_mlp", "mnist_conv": "mnist_conv",
             "resnet": "resnet_imagenet", "vgg": "vgg16_cifar"}
    lint_names = {alias.get(n, n) for n in lint_names}
    assert lint_names == test_names, (
        sorted(lint_names ^ test_names))


@pytest.mark.parametrize("name", sorted(MODEL_BUILDERS))
def test_model_programs_analyze_clean(name):
    loss = MODEL_BUILDERS[name]()
    if loss is not None:            # None = inference/serving program
        pt.optimizer.SGD(learning_rate=0.1).minimize(loss)
    prog = pt.default_main_program()
    errs = _errors(analysis.analyze_program(prog))
    assert not errs, "\n".join(str(d) for d in errs)
    test_errs = _errors(analysis.analyze_program(prog.clone(for_test=True)))
    assert not test_errs, "\n".join(str(d) for d in test_errs)


def test_decode_programs_analyze_clean():
    models.transformer.transformer_lm_generate(
        vocab=100, max_gen=4, d_model=32, d_inner=64, num_heads=4,
        num_layers=2, beam_size=4)
    errs = _errors(analysis.analyze_program(pt.default_main_program()))
    assert not errs, "\n".join(str(d) for d in errs)


# ---------------------------------------------------------------------------
# shape/dtype inference layer
# ---------------------------------------------------------------------------


def _mlp_program():
    x = layers.data("x", shape=[16])
    label = layers.data("label", shape=[1], dtype="int64")
    h = layers.fc(x, size=32, act="relu")
    logits = layers.fc(h, size=10)
    loss = layers.mean(layers.softmax_with_cross_entropy(logits, label))
    pt.optimizer.SGD(learning_rate=0.1).minimize(loss)
    return pt.default_main_program(), h, logits, loss


def test_infer_propagates_symbolic_batch():
    prog, h, logits, loss = _mlp_program()
    res = analysis.infer_program(prog)
    assert res.n_skipped == 0 and not res.errors
    hs = res.types[(0, h.name)]
    assert tuple(hs.shape) == (analysis.BATCH_SENTINEL, 32)
    assert tuple(res.types[(0, logits.name)].shape) == \
        (analysis.BATCH_SENTINEL, 10)
    assert res.types[(0, loss.name)].shape == ()
    # gradients mirror their targets through the vjp_region rule
    w = prog.global_block().ops[0].inputs["Y"][0]
    assert tuple(res.types[(0, w + "@GRAD")].shape) == (16, 32)


def test_seeded_shape_lie_caught_with_op_provenance():
    """The acceptance-criterion case: lie about a declared shape and the
    analyzer names the producing op (block/op#/op.type) and the var."""
    prog, h, logits, loss = _mlp_program()
    block = prog.global_block()
    block.vars[h.name].shape = (analysis.BATCH_SENTINEL and -1, 31)  # lie
    diags = _errors(analysis.analyze_program(prog))
    hits = [d for d in diags if d.code == "shape-mismatch"
            and h.name in d.message]
    assert hits, diags
    assert "op#" in hits[0].loc
    assert any(t in hits[0].loc
               for t in ("'mul'", "'elementwise_add'", "'relu'"))
    with pytest.raises(analysis.ProgramAnalysisError, match=h.name):
        analysis.check_program(prog)


def test_seeded_dtype_lie_caught():
    prog, h, logits, loss = _mlp_program()
    prog.global_block().vars[logits.name].dtype = np.dtype("int32")
    diags = _errors(analysis.analyze_program(prog))
    assert any(d.code == "dtype-mismatch" and logits.name in d.message
               for d in diags), diags


def test_infer_covers_at_least_90_percent_of_registry():
    import paddle_tpu.parallel  # noqa: F401 — registers dp/pp ops
    covered, waived = analysis.infer_coverage()
    total = len(covered) + len(waived)
    assert len(covered) / total >= 0.90, (len(covered), total)
    for op, reason in waived.items():
        assert isinstance(reason, str) and reason, op


# ---------------------------------------------------------------------------
# structural verification layer
# ---------------------------------------------------------------------------


def test_dropped_producer_caught():
    prog, h, logits, loss = _mlp_program()
    block = prog.global_block()
    # drop the first op (the mul producing the hidden pre-activation)
    dropped = block.ops[0]
    del block.ops[0]
    diags = _errors(analysis.verify_program(prog))
    assert any(d.code == "def-before-use"
               and dropped.outputs["Out"][0] in d.message
               for d in diags), diags


def test_duplicate_writer_caught():
    prog = pt.Program()
    blk = prog.global_block()
    blk.create_var(name="a", shape=[4], dtype="float32", is_data=True)
    blk.create_var(name="t", shape=[4], dtype="float32")
    blk.append_op("relu", inputs={"X": ["a"]}, outputs={"Out": ["t"]})
    blk.append_op("tanh", inputs={"X": ["a"]}, outputs={"Out": ["t"]})
    diags = _errors(analysis.verify_program(prog))
    assert any(d.code == "duplicate-writer" and "'t'" in d.message
               for d in diags), diags


def test_in_place_self_update_not_flagged():
    """increment(in_place=True) re-writes the var it reads — an ordered
    in-place update, not a rebinding hazard; the old CheckPass accepted
    these and the folded verifier must keep doing so."""
    x = layers.data("x", shape=[4])
    ctr = layers.fill_constant(shape=[1], dtype="float32", value=0.0)
    layers.increment(ctr, value=1.0, in_place=True)
    layers.fc(x, size=2)
    diags = _errors(analysis.verify_program(pt.default_main_program()))
    assert not any(d.code == "duplicate-writer" for d in diags), diags


def test_check_pass_alias_still_registered():
    """Folding CheckPass into the verifier keeps the registered name and
    the NotFoundError contract for existing callers."""
    x = layers.data("x", shape=[4])
    layers.fc(x, size=2)
    prog = pt.default_main_program()
    pt.Analyzer(passes=["check_pass"]).run(prog, pt.global_scope())

    bad = pt.Program()
    blk = bad.global_block()
    blk.create_var(name="ghost", shape=[2], dtype="float32")
    blk.create_var(name="out", shape=[2], dtype="float32")
    blk.append_op("relu", inputs={"X": ["ghost"]}, outputs={"Out": ["out"]})
    with pytest.raises(NotFoundError, match="ghost"):
        get_pass("check_pass")(bad)


# ---------------------------------------------------------------------------
# parallel invariants
# ---------------------------------------------------------------------------


def _pipelined_program():
    x = layers.data("x", shape=[32])
    label = layers.data("label", shape=[1], dtype="int64")
    h = layers.fc(x, size=64, act="relu")
    h = layers.fc(h, size=64, act="relu")
    loss = layers.mean(layers.softmax_with_cross_entropy(
        layers.fc(h, size=10), label))
    pt.optimizer.MomentumOptimizer(0.1, momentum=0.9).minimize(loss)
    return get_pass("pipeline_partition_pass", num_stages=2,
                    num_microbatches=4,
                    schedule="1f1b")(pt.default_main_program())


def test_pipelined_program_analyzes_clean():
    pp = _pipelined_program()
    errs = _errors(analysis.analyze_program(pp))
    assert not errs, "\n".join(str(d) for d in errs)


def test_broken_pp_send_recv_pair_caught():
    pp = _pipelined_program()
    block = pp.global_block()
    ridx, recv = next((i, op) for i, op in enumerate(block.ops)
                      if op.type == "pp_recv")
    del block.ops[ridx]
    diags = _errors(analysis.verify_program(pp))
    assert any(d.code == "pp-unmatched-boundary" for d in diags), diags


def test_pp_recv_name_mismatch_caught():
    pp = _pipelined_program()
    block = pp.global_block()
    recv = next(op for op in block.ops if op.type == "pp_recv")
    recv.outputs["Out"] = ["not_the_cut_var"]
    diags = _errors(analysis.verify_program(pp))
    assert any(d.code == "pp-unmatched-boundary"
               and "not_the_cut_var" in d.message for d in diags), diags


def _dp_comm_program():
    from paddle_tpu.parallel.grad_comm import comm_optimize_pass
    x = layers.data("x", shape=[32])
    label = layers.data("label", shape=[1], dtype="int64")
    h = layers.fc(x, size=64, act="relu")
    loss = layers.mean(layers.softmax_with_cross_entropy(
        layers.fc(h, size=10), label))
    pt.optimizer.MomentumOptimizer(0.1, momentum=0.9).minimize(loss)
    cfg = {"shard_update": True, "quant": "", "block": 512,
           "error_feedback": False, "bucket_bytes": 1 << 20}
    return comm_optimize_pass(pt.default_main_program(), 4, cfg)


def test_dp_comm_program_analyzes_clean():
    dp = _dp_comm_program()
    errs = _errors(analysis.analyze_program(dp))
    assert not errs, "\n".join(str(d) for d in errs)


def test_dp_comm_bypass_caught():
    """An optimizer rewired back to the raw (un-reduced) gradient — the
    exact hazard the comm pass placement contract forbids."""
    dp = _dp_comm_program()
    block = dp.global_block()
    comm = next(op for op in block.ops if op.type == "dp_grad_comm")
    raw = comm.inputs["X"][0]
    consumer = next(op for op in block.ops
                    if raw + "@COMM" in op.input_names())
    for slot, names in consumer.inputs.items():
        consumer.inputs[slot] = [raw if n == raw + "@COMM" else n
                                 for n in names]
    diags = _errors(analysis.verify_program(dp))
    assert any(d.code == "dp-comm-bypass" and raw in d.message
               for d in diags), diags


def test_dp_comm_misplaced_caught():
    dp = _dp_comm_program()
    block = dp.global_block()
    cidx = next(i for i, op in enumerate(block.ops)
                if op.type == "dp_grad_comm")
    comm = block.ops.pop(cidx)
    block.ops.insert(0, comm)          # before the backward region
    diags = _errors(analysis.verify_program(dp))
    assert any(d.code == "dp-comm-misplaced" for d in diags), diags


def test_dp_divisibility_caught():
    dp = _dp_comm_program()
    block = dp.global_block()
    comm = next(op for op in block.ops if op.type == "dp_grad_comm")
    si = comm.attrs["kinds"].index("sharded")
    comm.attrs["shapes"][si] = [63] + comm.attrs["shapes"][si][1:]
    diags = _errors(analysis.verify_program(dp))
    assert any(d.code == "dp-divisibility" for d in diags), diags


# ---------------------------------------------------------------------------
# pass sanitizer
# ---------------------------------------------------------------------------


@register_pass("_ta_bad_rewrite_pass")
class _BadRewritePass(Pass):
    """Deliberately broken rewrite: drops the first producer but leaves
    its consumers — the malformed-pass case the sanitizer must attribute."""

    allowed_attrs = ()

    def apply(self, program, scope=None):
        del program.global_block().ops[0]
        return program


def test_sanitizer_attributes_broken_rewrite_to_pass_by_name():
    prog, *_ = _mlp_program()
    from paddle_tpu.core import flags
    assert flags.get_flag("verify_passes"), \
        "sanitizer must be on under the test tier (PTPU_VERIFY_PASSES=1)"
    with pytest.raises(analysis.PassSanitizerError,
                       match="_ta_bad_rewrite_pass") as ei:
        get_pass("_ta_bad_rewrite_pass")(prog)
    assert ei.value.pass_name == "_ta_bad_rewrite_pass"
    assert any(d.code == "def-before-use" for d in ei.value.diagnostics)


def test_sanitizer_blames_only_new_violations():
    """Pre-existing violations belong to the caller: applying a HEALTHY
    pass to an already-broken program must not raise."""
    prog, *_ = _mlp_program()
    del prog.global_block().ops[0]     # caller-broken
    assert _errors(analysis.verify_program(prog))
    get_pass("graph_viz_pass", path="/dev/null")(prog)   # no new violations


@register_pass("_ta_renumbering_noop_pass")
class _RenumberingNoopPass(Pass):
    """Healthy rewrite that inserts one harmless op at index 0, renumbering
    every pre-existing op#."""

    allowed_attrs = ()

    def apply(self, program, scope=None):
        blk = program.global_block()
        blk.create_var(name="_ta_noop_c", shape=[1], dtype="float32")
        blk.append_op("fill_constant", inputs={},
                      outputs={"Out": ["_ta_noop_c"]},
                      attrs={"shape": [1], "value": 0.0, "dtype": "float32"})
        blk.ops.insert(0, blk.ops.pop())
        return program


def test_sanitizer_ignores_renumbered_preexisting_violations():
    """A pass that inserts/removes ops shifts every later op# — a
    pre-existing violation whose loc merely renumbered must stay the
    caller's, not be blamed on the healthy pass."""
    prog, *_ = _mlp_program()
    del prog.global_block().ops[0]     # caller-broken: def-before-use
    assert _errors(analysis.verify_program(prog))
    get_pass("_ta_renumbering_noop_pass")(prog)     # must not raise


def test_sanitizer_kill_switch():
    from paddle_tpu.core import flags
    prog, *_ = _mlp_program()
    old = flags.get_flag("verify_passes")
    flags.set_flag("verify_passes", False)
    try:
        get_pass("_ta_bad_rewrite_pass")(prog)   # no raise with switch down
    finally:
        flags.set_flag("verify_passes", old)


# ---------------------------------------------------------------------------
# static memory estimate
# ---------------------------------------------------------------------------


def test_peak_live_bytes_reports_provenance_and_scales_with_batch():
    prog, *_ = _mlp_program()
    small = analysis.peak_live_bytes(prog, nominal_batch=8)
    big = analysis.peak_live_bytes(prog, nominal_batch=64)
    assert small["peak_transient_bytes"] > 0
    assert big["peak_transient_bytes"] > small["peak_transient_bytes"]
    assert small["persistent_bytes"] == big["persistent_bytes"]
    assert "op#" in small["peak_at"]
