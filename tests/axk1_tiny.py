"""A.X-K1's block at a size the CPU runs in seconds: every mechanism of
benchmark/configs/axk1-ep16.json (latent attention with a padded row, YaRN
past its original length, a leading dense layer, routed experts of which a
share is held, a shared expert), none of its widths."""

import importlib
import importlib.util
import os
import sys

import numpy as np


def _benchmark_models():
    """The repo's `benchmark.models.axk1` and its reference, loaded under a
    name of their own: `tools/benchmark.py` is a MODULE called `benchmark`
    that other tests put first on the path, and whichever is imported
    first in a worker wins the name."""
    root = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark")
    alias = "ptpu_benchmark"
    if alias not in sys.modules:
        spec = importlib.util.spec_from_file_location(
            alias, os.path.join(root, "__init__.py"),
            submodule_search_locations=[root])
        sys.modules[alias] = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(sys.modules[alias])
    return (importlib.import_module(alias + ".models.axk1"),
            importlib.import_module(alias + ".models.axk1_reference"))


axk1, ref = _benchmark_models()

CFG = dict(
    model="axk1", hidden_size=64, intermediate_size=96,
    num_attention_heads=8, q_lora_rank=48, kv_lora_rank=128,
    qk_nope_head_dim=16, qk_rope_head_dim=16, v_head_dim=16,
    moe_intermediate_size=256, n_routed_experts=4, router_width=16,
    num_experts_per_tok=4, n_shared_experts=1, first_k_dense_replace=1,
    routed_scaling_factor=2.5, norm_topk_prob=True, scoring_func="sigmoid",
    topk_method="none", hidden_act="silu", rms_norm_eps=1e-6,
    rope_theta=10000,
    rope_scaling=dict(type="yarn", factor=32, beta_fast=32, beta_slow=1,
                      mscale=1, mscale_all_dim=1,
                      original_max_position_embeddings=16),
    num_layers=3, num_hidden_layers=3, vocab=97, weights_dtype="bfloat16",
    cache_dtype="bfloat16", max_len=64)
ENGINE = {"class": "PagedKVEngine", "n_slots": 4, "max_len": 64,
          "block_size": 8, "n_blocks": 40}


def cfg(**over):
    return dict(CFG, **over)


def engine(config, seed=7, **spec):
    scope = axk1.build_weights(config, seed)
    eng = axk1.build_engine(config, dict(ENGINE, **spec), scope)
    params = {n: scope.get(n) for n in axk1.param_names(config)}
    return eng, params


def gaps(config, params, req, pad_to=64):
    """Per emitted token of a finished request: how far its reference logit
    lies below the position's largest, in standard deviations of that
    position's logits (benchmark/loops/serve.py `_check`)."""
    seq = np.asarray(req.prompt + req.tokens[:-1], np.int32)
    ref = axk1.reference_logits(config, params, seq, pad_to)
    ref = ref[len(req.prompt) - 1:]
    toks = req.tokens
    return (ref.max(-1) - ref[np.arange(len(toks)), toks]) / ref.std(-1)


def _head_logits(program):
    """The variable a tick program's head takes its argmax of."""
    op = next(o for o in program.global_block().ops if o.type == "arg_max")
    return program.global_block().var(op.inputs["X"][0])


def scored_engine(**kw):
    """A PagedKVEngine whose two ticks also fetch the head's float32 logits
    (`last_logits` [rows, 1, vocab]: the decode rows, then in a mixed tick
    the lanes' last rows): a test's view into the programs the engine runs,
    where the ids alone say too little. The engine has no such option.
    `emitted_logits` reads a tick's logits for the token that tick emitted,
    so this engine commits every tick at once, as one that fetches top-k
    does (tests/test_late_read.py has the late order against it)."""
    from paddle_tpu import serving

    class Scored(serving.PagedKVEngine):
        last_logits = None

        def _commits_every_tick(self):
            return True

        def _tick_fetches(self):
            return super()._tick_fetches() + [_head_logits(self._program)]

        def _mixed_fetches(self):
            return super()._mixed_fetches() + [
                _head_logits(self._mixed_program)]

        def _launch_tick(self):
            fetches = super()._launch_tick()
            self.last_logits = fetches[1]
            return fetches

    return Scored(**kw)


def emitted_logits(eng, prompt, max_new):
    """Run one request alone through `eng` (a `scored_engine`) and
    return (request, the float32 logits the program made for each token it
    emitted [max_new, vocab]): the lane's last row when a chunk ended the
    prompt, the request's decode row after."""
    req = eng.submit(prompt, max_new)
    rows = []
    while not req.done:
        before = len(req.tokens)
        eng.step()
        if len(req.tokens) > before:
            row = eng.n_slots if eng._lanes else req.slot
            rows.append(np.asarray(eng.last_logits)[row, 0])
    return req, np.stack(rows)


def logit_error(config, params, req, got, pad_to=64):
    """max |program - reference| over the emitted positions' logits, in
    standard deviations of the reference's logits."""
    seq = np.asarray(req.prompt + req.tokens[:-1], np.int32)
    ref = axk1.reference_logits(config, params, seq, pad_to)
    ref = ref[len(req.prompt) - 1:]
    return float(np.abs(got - ref).max() / ref.std())
