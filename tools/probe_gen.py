"""Decode roofline attribution (VERDICT r4 #3): what fraction of the
HBM-bandwidth decode bound does each BENCH_GEN config achieve, and where do
the per-step bytes go?

Decode is HBM-bound: every generated token must stream the parameters and
the live KV cache through the chip. This probe computes, per config:

  - structural_bytes_per_step: bf16 params + one full KV-cache read (the
    attention) + one cache write — the floor no decode formulation beats
    while the cache layout is dense;
  - xla_bytes_per_step: XLA cost-model bytes for the compiled generate
    graph divided by gen_len (amortizes the prologue);
  - bound_tokens_per_sec = batch / (xla_bytes_per_step / HBM_BW) and the
    achieved fraction at the measured tokens/s;
  - the same fraction against the structural floor, which says how much a
    better formulation (not a faster chip) could still win.

    python tools/probe_gen.py
"""

from __future__ import annotations

import json
import time

import numpy as np

V5E_HBM_BPS = 819e9

VOCAB, D, DI, NH, NL = 32000, 512, 2048, 8, 6


def _param_bytes():
    """bf16 bytes of every weight the decode step streams: 6 layers of
    (qkvo projs + 2 ffn mats + 2 LN) + tok_emb row gather + lm_head."""
    per_layer = 4 * D * D + D * DI + DI * D + 4 * D
    # tok_emb is a one-hot matmul in the decode graph: the whole [V, D]
    # table streams per step (the graph's actual formulation); lm_head too
    return 2 * (NL * per_layer + VOCAB * D + D * VOCAB)


def _cache_traffic_per_step(batch, beam, T, dtype_bytes=4):
    """One attention read of k+v caches across layers + the one-hot write's
    full read+write (the current formulation rewrites the whole cache)."""
    cache = batch * beam * T * D * dtype_bytes          # one [B,K,T,H]
    read_attn = 2 * NL * cache
    write_onehot = 2 * NL * 2 * cache                   # read + write, k+v
    return read_attn, write_onehot, cache


def measure(batch, gen_len, beam, iters=3):
    import jax
    import jax.numpy as jnp
    import paddle_tpu as pt
    from paddle_tpu.core import unique_name
    from paddle_tpu.models import transformer

    pt.reset_default_programs()
    pt.reset_global_scope()
    with unique_name.guard():
        seqs, scores = transformer.transformer_lm_generate(
            vocab=VOCAB, max_gen=gen_len, d_model=D, d_inner=DI,
            num_heads=NH, num_layers=NL, bos_id=1, beam_size=beam)
    exe = pt.Executor()
    exe.run(pt.default_startup_program())
    feed = {"prompt": jnp.asarray(np.full((batch, 1), 1, "int64"))}
    out = exe.run(feed=feed, fetch_list=[seqs])[0]
    assert np.asarray(out).shape == (batch, gen_len, beam)

    ca = exe.cost_analysis(feed=feed, fetch_list=[seqs]) or {}
    total_bytes = float(ca.get("bytes accessed", 0.0))

    best = None
    for _ in range(3):
        t0 = time.time()
        for _ in range(iters):
            out = exe.run(feed=feed, fetch_list=[seqs])[0]
        np.asarray(out)
        dt = (time.time() - t0) / iters
        best = dt if best is None else min(best, dt)

    tokens_per_sec = batch * gen_len / best
    xla_step_bytes = total_bytes / gen_len
    p_bytes = _param_bytes()
    read_attn, write_onehot, cache1 = _cache_traffic_per_step(
        batch, beam, gen_len)
    structural = p_bytes + read_attn + 2 * NL * cache1 / gen_len  # DUS write

    bound_structural = batch / (structural / V5E_HBM_BPS)
    rec = {
        "config": f"lm6l_512d_bs{batch}_gen{gen_len}_beam{beam}",
        "tokens_per_sec": round(tokens_per_sec, 1),
        "ms_per_step": round(best / gen_len * 1e3, 3),
        # diagnostic only: XLA's cost model underreports while-loop bodies
        # (~1/loop-count of the real traffic), so no bound is derived
        # from it
        "xla_bytes_per_step_MB_diagnostic": round(xla_step_bytes / 1e6, 1),
        "model_bytes_per_step_MB": {
            "params_bf16": round(p_bytes / 1e6, 1),
            "kv_attention_read": round(read_attn / 1e6, 1),
            "kv_onehot_write_readwrite_legacy": round(write_onehot / 1e6,
                                                      1),
            "structural_floor_dus_write": round(structural / 1e6, 1),
        },
        # THE committed metric: achieved fraction of the HBM-bandwidth
        # decode bound at the structural byte model (params + one cache
        # read + one row write per step)
        "decode_bound_tokens_per_sec": round(bound_structural, 1),
        "fraction_of_decode_bound": round(
            tokens_per_sec / bound_structural, 3),
    }
    print(json.dumps(rec), flush=True)
    return rec


def main():
    import jax
    if jax.devices()[0].platform == "cpu":
        measure(2, 4, 1, iters=1)
        return
    measure(16, 64, 1)
    measure(64, 64, 1)
    measure(16, 64, 4)


if __name__ == "__main__":
    main()
