"""Roofline-cap reconciliation (VERDICT r4 #5).

Round 4 quoted HBM-roofline "MFU caps" derived from XLA's bytes-accessed —
and the committed NMT line (mfu 0.224) EXCEEDS its own quoted cap
(0.18-0.19). The contradiction is methodological: bytes-accessed is an
UPPER bound on true HBM traffic (it double-charges the VMEM-prefetch
overlay and multi-consumer fusion reads — PROF_r04 §2 measured 19.7 of
89.6 GB as prefetch double-count on the flagship), so a "cap" computed
from it is the LOWER end of an interval, not a ceiling.

This probe computes, for the three cap-quoted configs (LM d512, NMT,
flagship ResNet-50), the traffic INTERVAL:

  traffic_high = XLA cost-model bytes accessed (upper bound: overlays +
                 multi-consumer double-charges)
  traffic_low  = top-level entry census MINUS the copy-done/async-done
                 prefetch overlay (the attribute_bytes methodology) —
                 still an over-estimate of unique HBM bytes when a buffer
                 has several top-level consumers, but strictly tighter

and restates each cap as the interval
  mfu_cap in [flops / max(t_mxu, traffic_high/BW) / peak,
              flops / max(t_mxu, traffic_low /BW) / peak]
with the invariant: measured mfu <= cap_high * (1 + timing jitter).

    python tools/probe_caps.py
"""

from __future__ import annotations

import json
import os
import re
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from probe_common import (V5E_HBM_BPS, V5E_PEAK_TFLOPS,  # noqa: E402
                          hlo_shape_bytes as _shape_bytes, measure_step)

_SKIP = {"get-tuple-element", "bitcast", "parameter", "tuple", "constant",
         "after-all", "copy-start", "async-start"}


def entry_census(hlo: str):
    """(total_charged_bytes, prefetch_overlay_bytes) over top-level entry
    instructions, charging operands+outputs (attribute_bytes methodology,
    generalized to any program)."""
    cur = None
    defs = {}
    total = prefetch = 0
    for line in hlo.splitlines():
        mc = re.match(r"(ENTRY )?%?([\w.\-]+)\s*\([^)]*\)\s*->", line)
        if mc:
            cur = "ENTRY" if mc.group(1) else mc.group(2)
            continue
        if cur != "ENTRY":
            continue
        m = re.match(r"\s+%?([\w.\-]+)\s*=\s*(\([^=]*?\)|\S+)\s+([a-z\-]+)",
                     line)
        if not m:
            continue
        name, sh, op = m.groups()
        out_b = _shape_bytes(sh)
        defs[name] = out_b
        if op in _SKIP:
            continue
        if op in ("copy-done", "async-done"):
            prefetch += out_b
            continue
        call = line[m.end():]
        operands = re.findall(r"%([\w.\-]+)", call.split("metadata")[0])
        in_b = sum(defs[o] for o in dict.fromkeys(operands) if o in defs)
        total += in_b + out_b
    return total, prefetch


def cap_interval(flops, traffic_high, traffic_low):
    t_mxu = flops / (V5E_PEAK_TFLOPS)
    lo = flops / max(t_mxu, traffic_high / V5E_HBM_BPS) / V5E_PEAK_TFLOPS
    hi = flops / max(t_mxu, traffic_low / V5E_HBM_BPS) / V5E_PEAK_TFLOPS
    return round(lo, 3), round(hi, 3)


def _run(name, build, make_feed, iters=12):
    hlo_path = f"/tmp/caps_{name}.hlo"
    m = measure_step(build, make_feed, iters=iters, hlo_path=hlo_path)
    hlo = open(hlo_path).read()
    charged, overlay = entry_census(hlo)
    traffic_high = m["bytes_acc"]
    traffic_low = max(charged - overlay, 1.0)
    lo, hi = cap_interval(m["flops"], traffic_high, traffic_low)
    mfu = m["flops"] / m["step_s"] / V5E_PEAK_TFLOPS
    rec = {
        "config": name,
        "step_ms": round(m["step_s"] * 1e3, 2),
        "flops_G": round(m["flops"] / 1e9, 1),
        "traffic_GB": {
            "xla_bytes_accessed": round(traffic_high / 1e9, 2),
            "entry_census_charged": round(charged / 1e9, 2),
            "prefetch_overlay": round(overlay / 1e9, 2),
            "census_minus_overlay": round(traffic_low / 1e9, 2),
        },
        "achieved_GBps_vs_xla_bytes": round(
            traffic_high / m["step_s"] / 1e9, 1),
        "mfu_measured": round(mfu, 3),
        "mfu_cap_interval": [lo, hi],
        "measured_within_cap": bool(mfu <= hi * 1.05),
    }
    print(json.dumps(rec), flush=True)
    return rec


def main():
    import paddle_tpu as pt
    from paddle_tpu import models
    from paddle_tpu.models import transformer

    rng = np.random.RandomState(0)

    def build_lm():
        loss, _ = transformer.transformer_lm(
            vocab=32000, max_len=512, d_model=512, d_inner=2048,
            num_heads=8, num_layers=6, dropout=0.0)
        return loss, pt.optimizer.AdamOptimizer(learning_rate=1e-4)

    def feed_lm(b=16, t=512):
        return {"tokens": rng.randint(0, 32000, (b, t)).astype("int64"),
                "tokens@SEQLEN": np.full((b,), t, "int32"),
                "targets": rng.randint(0, 32000, (b, t)).astype("int64")}

    def build_nmt():
        loss, _ = transformer.transformer(
            src_vocab=16000, tgt_vocab=16000, max_len=256, d_model=512,
            d_inner=2048, num_heads=8, num_layers=4, dropout=0.0)
        return loss, pt.optimizer.AdamOptimizer(learning_rate=1e-4)

    def feed_nmt(b=16, t=256):
        return {"src": rng.randint(1, 16000, (b, t)).astype("int64"),
                "src@SEQLEN": np.full((b,), t, "int32"),
                "tgt": rng.randint(1, 16000, (b, t)).astype("int64"),
                "tgt@SEQLEN": np.full((b,), t, "int32"),
                "lbl": rng.randint(1, 16000, (b, t)).astype("int64")}

    def build_resnet():
        loss, acc, _ = models.resnet.resnet_imagenet(
            depth=50, is_test=False, data_format="NHWC", use_bf16=True)
        return loss, pt.optimizer.MomentumOptimizer(learning_rate=3e-3,
                                                    momentum=0.9)

    def feed_resnet(b=256):
        return {"img": rng.rand(b, 224, 224, 3).astype("float32"),
                "label": rng.randint(0, 1000, (b, 1)).astype("int64")}

    _run("lm6l_512d_bs16_T512", build_lm, feed_lm)
    _run("nmt4l_512d_bs16_T256", build_nmt, feed_nmt)
    _run("resnet50_bs256", build_resnet, feed_resnet, iters=8)


if __name__ == "__main__":
    main()
