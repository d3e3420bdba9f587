"""Attribute the stacked-LSTM bench config's step time on the TPU.

The LSTM line's low MFU is structural, not a kernel defect: a recurrent
scan serializes T steps, and each tick's recurrent matmul on this config
is [B=64, H=256] x [256, 1024] — ~34 MFLOP, far too small to fill a
197-TFLOP/s MXU whose granularity wants >=10x that per dispatch. This
probe prints the numbers that show where the time actually goes: XLA's
own flops/bytes (roofline position), the scan tick count, and per-tick
wall time vs the per-tick ideal.

    python tools/probe_lstm.py
"""
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from probe_common import measure_step, roofline_fields  # noqa: E402


def main(b=64, t=64, emb=256, hid=256):
    import paddle_tpu as pt
    from paddle_tpu.models import stacked_lstm

    rng = np.random.RandomState(0)

    def build():
        loss, acc, _ = stacked_lstm.stacked_lstm_net(
            dict_dim=10000, emb_dim=emb, hid_dim=hid, max_len=t)
        return loss, pt.optimizer.AdamOptimizer(learning_rate=5e-4)

    def make_feed():
        return {"words": rng.randint(0, 10000, (b, t)).astype("int64"),
                "words@SEQLEN": np.full((b,), t, "int32"),
                "label": rng.randint(0, 2, (b, 1)).astype("int64")}

    m = measure_step(build, make_feed, iters=20)
    out = roofline_fields(m["step_s"], m["flops"], m["bytes_acc"])

    # 3 stacked LSTMs, each a T-tick scan, fwd + bwd (bwd re-scans) ->
    # sequential tick chain the step time divides over
    ticks = 3 * t * 2
    out.update({
        "sequential_ticks_fwd_bwd": ticks,
        "wall_us_per_tick": round(m["step_s"] / ticks * 1e6, 1),
        "recurrent_matmul_mflops_per_tick":
            round(2 * b * hid * (4 * hid) / 1e6, 1),
        "note": "a ~34-MFLOP matmul per tick cannot fill the MXU; the "
                "step is bound by the serialized scan ticks, not "
                "flops or HBM (both ideals are far below measured)",
    })
    print(json.dumps(out))


if __name__ == "__main__":
    main()
