"""Attribute the transformer-NMT bench config's step time on the TPU.

Same roofline-position analysis probe_lm.py gives the LM line: XLA's own
bytes-accessed + flops for the compiled train step, so the measured MFU can
be read against the chip's 240 flops/byte balance point instead of standing
as a bare number.

    python tools/probe_nmt.py
"""
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from probe_common import (V5E_HBM_BPS, V5E_PEAK_TFLOPS,  # noqa: E402
                          measure_step, roofline_fields)


def main(b=16, t=256):
    import paddle_tpu as pt
    from paddle_tpu.models import transformer

    rng = np.random.RandomState(0)

    def build():
        loss, _ = transformer.transformer(
            src_vocab=16000, tgt_vocab=16000, max_len=t, d_model=512,
            d_inner=2048, num_heads=8, num_layers=4, dropout=0.0)
        return loss, pt.optimizer.AdamOptimizer(learning_rate=1e-4)

    def make_feed():
        return {"src": rng.randint(1, 16000, (b, t)).astype("int64"),
                "src@SEQLEN": np.full((b,), t, "int32"),
                "tgt": rng.randint(1, 16000, (b, t)).astype("int64"),
                "tgt@SEQLEN": np.full((b,), t, "int32"),
                "lbl": rng.randint(1, 16000, (b, t)).astype("int64")}

    m = measure_step(build, make_feed, iters=15,
                     hlo_path="/tmp/nmt_train.hlo")
    out = roofline_fields(m["step_s"], m["flops"], m["bytes_acc"])
    if m["flops"] and m["bytes_acc"]:
        out["roofline_mfu_cap"] = round(
            m["flops"] / max(m["flops"] / V5E_PEAK_TFLOPS,
                             m["bytes_acc"] / V5E_HBM_BPS)
            / V5E_PEAK_TFLOPS, 3)
    out["tokens_per_s"] = round(b * t / m["step_s"])
    print(json.dumps(out))


if __name__ == "__main__":
    main()
