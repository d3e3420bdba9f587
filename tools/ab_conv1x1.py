"""Flagship A/B of the mixed-emitter 1x1 conv backward (PROBE_DGRAD #1).

ResNet-50's bottleneck/projection 1x1 convs are ~2/3 of its conv count;
probe_dgrad.py --exp mixed_1x1 measured the mixed custom_vjp (dot dgrad + conv wgrad) at
1.52x on the worst-traffic 1x1 unit in isolation. This runs the WHOLE
train step (bs256) with the lowering flag on / off / on (ABA bounds
drift across the run) and reports step time + cost-model traffic for each.

    python tools/ab_conv1x1.py
"""

from __future__ import annotations

import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from probe_common import measure_step  # noqa: E402


def _measure(flag: bool, iters=10):
    import paddle_tpu as pt
    from paddle_tpu import models
    from paddle_tpu.core import flags as _flags

    _flags._REGISTRY["conv1x1_mixed_vjp"].value = flag
    rng = np.random.RandomState(0)

    def build():
        loss, acc, _ = models.resnet.resnet_imagenet(
            depth=50, is_test=False, data_format="NHWC", use_bf16=True)
        return loss, pt.optimizer.MomentumOptimizer(learning_rate=3e-3,
                                                    momentum=0.9)

    def feed(b=256):
        return {"img": rng.rand(b, 224, 224, 3).astype("float32"),
                "label": rng.randint(0, 1000, (b, 1)).astype("int64")}

    m = measure_step(build, feed, iters=iters)
    rec = {"conv1x1_mixed_vjp": flag,
           "step_ms": round(m["step_s"] * 1e3, 2),
           "bytes_GB": round(m["bytes_acc"] / 1e9, 2),
           "flops_G": round(m["flops"] / 1e9, 1)}
    print(json.dumps(rec), flush=True)
    return rec


def main():
    a1 = _measure(True)
    b = _measure(False)
    a2 = _measure(True)
    best_mixed = min(a1["step_ms"], a2["step_ms"])
    print(json.dumps({
        "exp": "flagship_ab_conv1x1_mixed_vjp",
        "mixed_best_ms": best_mixed,
        "plain_ms": b["step_ms"],
        "speedup": round(b["step_ms"] / best_mixed, 3),
        "bytes_GB": {"mixed": a1["bytes_GB"], "plain": b["bytes_GB"]},
    }), flush=True)


if __name__ == "__main__":
    main()
