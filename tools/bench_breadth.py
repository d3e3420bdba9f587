"""Benchmark breadth: driver configs #3-#5 with the SAME audit fields as
the ResNet headline (VERDICT r2 #8; ≙ reference
benchmark/fluid/fluid_benchmark.py:299 printing throughput for all five
models).

Run on the real TPU and commit the output:

    python tools/bench_breadth.py | tee BENCH_BREADTH_r03.json

Sync discipline: host-value realization of the last fetched loss bounds
every timed step (see bench.py).
"""

from __future__ import annotations

import json
import time

import numpy as np

_CHIP_SPECS = (("v5 lite", 197.0, 819.0), ("v5e", 197.0, 819.0),
               ("v5p", 459.0, 2765.0), ("v6", 918.0, 1640.0),
               ("v4", 275.0, 1228.0))


def _peak(dev):
    kind = (getattr(dev, "device_kind", "") or "").lower()
    for sub, p, _ in _CHIP_SPECS:
        if sub in kind:
            return p
    return None


def _hbm(dev):
    kind = (getattr(dev, "device_kind", "") or "").lower()
    for sub, _, h in _CHIP_SPECS:
        if sub in kind:
            return h
    return None


# every row names its binding bound so the artifact is self-interpreting
# (VERDICT r5 #4): mxu | hbm = roofline sides from XLA's own cost model;
# gather-bw = the scattered-row bandwidth bound (deepfm — its traffic IS
# the bound, the MXU is ~idle by design); tick-latency = the serialized
# per-tick kernel-latency floor (stacked_lstm — fraction_of_bound shows
# how far BELOW its roofline the latency floor pins it, the ROUND4
# attribution pulled into the artifact).
_BOUND_KIND = {
    "stacked_lstm": "tick-latency",
    "deepfm": "gather-bw",
}


def _bound_fields(name, step_ms, flops, bytes_acc, peak, hbm_gbps):
    if not (flops and peak):
        return {}
    ideal_mxu = flops / (peak * 1e12) * 1e3
    ideal_hbm = (bytes_acc / (hbm_gbps * 1e9) * 1e3
                 if bytes_acc and hbm_gbps else None)
    kind = next((v for k, v in _BOUND_KIND.items() if k in name), None)
    if kind is None:
        kind = ("hbm" if ideal_hbm and ideal_hbm > ideal_mxu else "mxu")
    binding = max(ideal_mxu, ideal_hbm or 0.0)
    return {
        "bound_kind": kind,
        "ideal_mxu_ms": round(ideal_mxu, 3),
        "ideal_hbm_ms_xla_bytes": (round(ideal_hbm, 3)
                                   if ideal_hbm else None),
        "fraction_of_bound": round(binding / step_ms, 3),
    }


def _measure(name, build, unit, iters=20):
    """build(rng) -> (loss_var, feed_or_feeds, units_per_step, optimizer).

    `feed_or_feeds` may be a list of distinct batches: the timed loop cycles
    through them so the model trains on a real dataset slice instead of
    memorizing one fixed batch (a fixed batch drives synthetic losses to 0.0
    inside the window, making the loss-decreased audit vacuous — VERDICT r3
    weak #4). All batches are staged to the device ONCE before timing: the
    timed window measures the training step, not the host link (the ResNet
    headline bench stages the same way and measures the input pipeline
    separately via its prefetcher variant)."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu as pt

    pt.reset_default_programs()
    pt.reset_global_scope()
    rng = np.random.RandomState(0)
    with pt.core.unique_name.guard():
        loss, feed, units, opt = build(rng)
        opt.minimize(loss)
    exe = pt.Executor()
    exe.run(pt.default_startup_program())

    feeds = feed if isinstance(feed, list) else [feed]
    feeds = [{k: jnp.asarray(v) for k, v in f.items()} for f in feeds]
    k = len(feeds)

    out = exe.run(feed=feeds[0], fetch_list=[loss], return_numpy=False)
    float(np.asarray(out[0]).ravel()[0])  # compile + drain

    # best of 3 windows (losses tracked across ALL windows — training
    # continues through every one)
    losses, dt = [], None
    step_i = 0
    for _ in range(3):
        fetched = []
        t0 = time.time()
        for _ in range(iters):
            out = exe.run(feed=feeds[step_i % k], fetch_list=[loss],
                          return_numpy=False)
            fetched.append(out[0])
            step_i += 1
        float(np.asarray(fetched[-1]).ravel()[0])
        w = time.time() - t0
        dt = w if dt is None else min(dt, w)
        losses.extend(float(np.asarray(x).ravel()[0]) for x in fetched)

    ca = exe.cost_analysis(feed=feeds[0], fetch_list=[loss])
    flops = float(ca.get("flops", 0.0)) if ca else 0.0
    bytes_acc = float(ca.get("bytes accessed", 0.0)) if ca else 0.0
    dev = jax.devices()[0]
    peak = _peak(dev)
    implied = flops * iters / dt / 1e12 if flops else None
    rec = {
        "model": name,
        "value": round(units * iters / dt, 2),
        "unit": unit,
        "evidence": {
            "device_kind": getattr(dev, "device_kind", str(dev)),
            "step_ms": round(dt / iters * 1e3, 2),
            "flops_per_step_xla": flops,
            "implied_tflops": round(implied, 2) if implied else None,
            "mfu": (round(implied / peak, 4) if implied and peak else None),
            **_bound_fields(name, dt / iters * 1e3, flops, bytes_acc,
                            peak, _hbm(dev)),
            # first/last = mean over one full feed cycle, so the comparison
            # is over the same batches and batch-to-batch jitter cancels
            "loss_first": round(float(np.mean(losses[:k])), 4),
            "loss_last": round(float(np.mean(losses[-k:])), 4),
            "loss_decreased": bool(np.mean(losses[-k:]) < np.mean(losses[:k])
                                   and np.mean(losses[-k:]) > 0.0),
            "n_distinct_batches": k,
        },
    }
    print(json.dumps(rec), flush=True)
    return rec


def build_stacked_lstm(rng):
    import paddle_tpu as pt
    from paddle_tpu.models import stacked_lstm
    b, t = 64, 64
    loss, acc, _ = stacked_lstm.stacked_lstm_net(
        dict_dim=10000, emb_dim=256, hid_dim=256, max_len=t)
    # 8 distinct batches, labels = a real function of the sequence (token-sum
    # parity): learnable, so loss decreases, but 512 examples cannot be
    # memorized to 0.0 inside the timed window (VERDICT r3 weak #4)
    feeds = []
    for _ in range(8):
        words = rng.randint(0, 10000, (b, t)).astype("int64")
        label = (words.sum(axis=1, keepdims=True) % 2).astype("int64")
        feeds.append({"words": words,
                      "words@SEQLEN": np.full((b,), t, "int32"),
                      "label": label})
    opt = pt.optimizer.AdamOptimizer(learning_rate=5e-4)
    return loss, feeds, b * t, opt


def _markov_tokens(rng, b, t, vocab):
    """Sequences where tok[i+1] = (tok[i]*13 + 7 + eps) % vocab, eps∈[0,8):
    a 1st-order process any of the models here can learn, with a known
    entropy floor — distinct batches share the map, so descent is signal."""
    toks = np.empty((b, t), np.int64)
    toks[:, 0] = rng.randint(0, vocab, (b,))
    for i in range(1, t):
        toks[:, i] = (toks[:, i - 1] * 13 + 7
                      + rng.randint(0, 8, (b,))) % vocab
    return toks


def build_transformer(rng):
    import paddle_tpu as pt
    from paddle_tpu.models import transformer
    b, t = 16, 512
    loss, _ = transformer.transformer_lm(
        vocab=32000, max_len=t, d_model=512, d_inner=2048, num_heads=8,
        num_layers=6, dropout=0.0)   # dropout 0 -> flash-attention path
    # 4 distinct batches drawn from a learnable process: the next token is a
    # deterministic map of the current plus 3 bits of noise, so the CE floor
    # is ln(8)≈2.08 and descent reflects learning the map, not memorizing a
    # single fixed batch
    feeds = []
    for _ in range(4):
        toks = _markov_tokens(rng, b, t + 1, 32000)
        feeds.append({"tokens": toks[:, :-1].copy(),
                      "tokens@SEQLEN": np.full((b,), t, "int32"),
                      "targets": toks[:, 1:].copy()})
    opt = pt.optimizer.AdamOptimizer(learning_rate=1e-4)
    return loss, feeds, b * t, opt


def build_transformer_big(rng):
    """d_model=1024, 12 layers: a config whose arithmetic intensity sits
    ABOVE the v5e balance point — demonstrates the stack's MFU when the
    model shape permits it (the bs16·d512 line is HBM-intensity-capped at
    ~0.33 no matter the kernels; see tools/probe_lm.py)."""
    import paddle_tpu as pt
    from paddle_tpu.models import transformer
    b, t = 8, 1024
    loss, _ = transformer.transformer_lm(
        vocab=32000, max_len=t, d_model=1024, d_inner=4096, num_heads=16,
        num_layers=12, dropout=0.0)
    feeds = []
    for _ in range(2):
        toks = _markov_tokens(rng, b, t + 1, 32000)
        feeds.append({"tokens": toks[:, :-1].copy(),
                      "tokens@SEQLEN": np.full((b,), t, "int32"),
                      "targets": toks[:, 1:].copy()})
    opt = pt.optimizer.AdamOptimizer(learning_rate=1e-4)
    return loss, feeds, b * t, opt


def build_transformer_nmt(rng):
    import paddle_tpu as pt
    from paddle_tpu.models import transformer
    b, t = 16, 256
    loss, _ = transformer.transformer(
        src_vocab=16000, tgt_vocab=16000, max_len=t, d_model=512,
        d_inner=2048, num_heads=8, num_layers=4, dropout=0.0)
    # 4 distinct batches of a learnable translation task: tgt is a fixed
    # pointwise map of src ((src+5) mod V), lbl the next-token shift — the
    # decoder can learn it through cross-attention; no single batch to
    # memorize
    feeds = []
    for _ in range(4):
        src = _markov_tokens(rng, b, t + 1, 16000)
        tgt = (src + 5) % 16000
        feeds.append({"src": src[:, :-1].copy(),
                      "src@SEQLEN": np.full((b,), t, "int32"),
                      "tgt": tgt[:, :-1].copy(),
                      "tgt@SEQLEN": np.full((b,), t, "int32"),
                      "lbl": tgt[:, 1:].copy()})
    opt = pt.optimizer.AdamOptimizer(learning_rate=1e-4)
    return loss, feeds, b * t, opt


def build_deepfm(rng):
    import paddle_tpu as pt
    from paddle_tpu.models import deepfm
    b = 4096
    loss, _ = deepfm.deepfm(num_fields=39, vocab_size=1000000,
                            is_sparse=True, row_pad=128)
    # 8 distinct batches; each example's ids hit near-unique rows of the
    # 1M-row tables, so a single fixed batch is memorized through its own
    # embedding rows within a few visits — labels are instead a function of
    # the dense feature values (learnable through the shared MLP, not
    # memorizable through per-example rows)
    feeds = []
    for _ in range(8):
        vals = rng.rand(b, 39).astype("float32")
        label = (vals.mean(axis=1, keepdims=True) >
                 0.5).astype("float32")
        feeds.append({"feat_ids": rng.randint(0, 1000000,
                                              (b, 39)).astype("int64"),
                      "feat_vals": vals, "label": label})
    opt = pt.optimizer.AdamOptimizer(learning_rate=3e-4)
    return loss, feeds, b, opt


_RAGGED_T, _RAGGED_VOCAB = 512, 32000


def _ragged_corpus(rng, n_seqs=64):
    """Deterministic ragged corpus (~median length 100, up to T) shared by
    the packed and padded variants so the comparison is apples-to-apples."""
    lengths = np.clip((np.exp(rng.randn(n_seqs) * 0.6 + 4.6)).astype(int),
                      32, _RAGGED_T)
    seqs = [rng.randint(1, _RAGGED_VOCAB, (L,)).astype(np.int64)
            for L in lengths]
    real_tokens = int(sum(len(s) - 1 for s in seqs))  # trainable positions
    return seqs, real_tokens


def _build_ragged_lm(rng, packed, n_seqs=64):
    import paddle_tpu as pt
    from paddle_tpu.data.packing import pack_lm_batch
    from paddle_tpu.models import transformer

    seqs, real_tokens = _ragged_corpus(rng, n_seqs)
    T = _RAGGED_T
    loss, _ = transformer.transformer_lm(
        vocab=_RAGGED_VOCAB, max_len=T, d_model=512, d_inner=2048,
        num_heads=8, num_layers=6, dropout=0.0, packed=packed)
    if packed:
        feed = pack_lm_batch(seqs, T)
    else:
        rows = len(seqs)
        toks = np.zeros((rows, T), np.int64)
        tgts = np.zeros((rows, T), np.int64)
        sl = np.zeros((rows,), np.int32)
        for i, s in enumerate(seqs):
            toks[i, :len(s)] = s
            tgts[i, :len(s) - 1] = s[1:]
            sl[i] = len(s) - 1
        feed = {"tokens": toks, "tokens@SEQLEN": sl, "targets": tgts}
    opt = pt.optimizer.AdamOptimizer(learning_rate=1e-4)
    # `units` = REAL (non-pad) tokens: both variants share the numerator,
    # so value is directly comparable and the packed/padded ratio is the
    # padding waste eliminated (≙ the reference's LoD ragged batches whose
    # purpose is exactly not burning compute on padding)
    return loss, feed, real_tokens, opt


def measure_packed_vs_padded(iters=10):
    """The packed (segment-id) path's reason to exist: REAL tokens/sec on
    a ragged corpus, packed multi-sequence rows vs one padded sequence per
    row — full audit fields via the shared _measure harness."""
    packed = _measure("packed_ragged_lm_6l_512d_T512",
                      lambda rng: _build_ragged_lm(rng, True),
                      "real_tokens/sec", iters)
    padded = _measure("padded_ragged_lm_6l_512d_T512",
                      lambda rng: _build_ragged_lm(rng, False),
                      "real_tokens/sec", iters)
    # equal-ROW-COUNT packed run (4x corpus -> ~64 packed rows, the padded
    # run's row count): packing 64 sequences yields only ~16 rows, and a
    # 16-row program has lower MFU than a 64-row one on any path — this
    # line separates the segment-id kernel's true overhead from that
    # batch-size effect
    packed_eq = _measure("packed_ragged_lm_6l_512d_T512_eqrows",
                         lambda rng: _build_ragged_lm(rng, True, 256),
                         "real_tokens/sec", iters)
    print(json.dumps({
        "packed_over_padded_speedup":
            round(packed["value"] / padded["value"], 2),
        "packed_eqrows_mfu_over_padded_mfu":
            round(packed_eq["evidence"]["mfu"]
                  / padded["evidence"]["mfu"], 3)}), flush=True)
    return packed, padded, packed_eq


def main():
    import jax
    on_accel = jax.devices()[0].platform != "cpu"
    iters = 20 if on_accel else 2
    recs = [
        _measure("stacked_lstm_bs64_T64", build_stacked_lstm,
                 "tokens/sec", iters),
        _measure("transformer_lm_6l_512d_bs16_T512_flash",
                 build_transformer, "tokens/sec", iters),
        _measure("transformer_lm_12l_1024d_bs8_T1024_flash",
                 build_transformer_big, "tokens/sec", iters),
        _measure("transformer_nmt_4l_512d_bs16_T256_flash",
                 build_transformer_nmt, "tokens/sec", iters),
        _measure("deepfm_bs4096_vocab1M_sparse", build_deepfm,
                 "examples/sec", iters),
    ]
    recs.extend(measure_packed_vs_padded(iters=10 if on_accel else 1))
    ok = all(r["evidence"]["loss_decreased"] for r in recs)
    print(json.dumps({"all_losses_decreased": ok}), flush=True)


if __name__ == "__main__":
    main()
