"""Operation and byte counts against hand-worked values."""

import json
import os

import numpy as np
import pytest

from benchmark import counts
from benchmark.models import lm, nmt

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LM = json.load(open(os.path.join(HERE, "configs", "fairseq-lm-big.json")))
NMT = json.load(open(os.path.join(HERE, "configs", "vaswani-big-nmt.json")))
V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_attention_flops_by_hand():
    # one head, 4 queries, 4 keys, width 2: QK^T is 4*4*2 multiply-adds
    assert counts.attention_flops(1, 4, 4, 2, False, False) == 2 * (2 * 32)
    assert counts.attention_flops(1, 4, 4, 2, True, False) == 2 * 32
    # backward adds four matmuls of the same size
    assert counts.attention_flops(1, 4, 4, 2, False, True) == 6 * (2 * 32)


def test_flash_bytes_by_hand():
    # bf16: q, k, v read and o written, 8*16 values each, and 8 f32 lse rows
    assert counts.flash_call_bytes(1, 8, 8, 16, False) == 4 * 8 * 16 * 2 + 32
    assert counts.flash_call_bytes(1, 8, 8, 16, True) == 8 * 8 * 16 * 2 + 32


def test_lm_big_operations_per_token():
    """12 blocks of 4*1024^2 + 2*1024*4096 weights and a 1024 x 32000 head
    are 183.8 M weights, 6 ops each per token forward and backward; causal
    attention adds 12 layers x 3 x 2*1024*1024 per token at context 1024."""
    mix = {"seq_len": 1024}
    batch = {"feed": {"tokens": np.zeros((8, 1024))}}
    per_token = lm.train_flops(LM, mix, batch) / 8192
    weights = 12 * (4 * 1024 ** 2 + 2 * 1024 * 4096) + 1024 * 32000
    assert weights == 183_762_944
    assert per_token == pytest.approx(6 * weights + 12 * 6 * 1024 * 1024)
    # at PR 22's 70,770 tokens/s that is the 42.3% it reported
    assert 100 * per_token * 70770 / 197e12 == pytest.approx(42.3, abs=0.1)


def test_lm_flash_calls_least_time():
    calls = lm.flash_calls(LM, {"seq_len": 1024}, 8)
    assert len(calls) == 24
    fwd_flops, fwd_bytes = calls[0]
    assert fwd_flops == 4 * 128 * 1024 * 1024 * 64 / 2
    assert fwd_bytes == 4 * 128 * 1024 * 64 * 2 + 128 * 1024 * 4
    # forward: 87.2 us of compute against 82.6 us of traffic -> compute bound
    assert counts.roofline_min_seconds(fwd_flops, fwd_bytes, V5E) == \
        pytest.approx(87.2e-6, rel=0.01)
    assert calls[1][0] == 2 * fwd_flops


def test_decode_tick_bytes():
    # weights as stored (f32) dominate: 183.8 M x 4 B = 735 MB -> 0.9 ms
    b = lm.decode_tick_bytes(LM, 16, 0)
    assert b == 4 * (183_762_944 + 16 * 1024)
    per_position = 2 * 12 * 1024 * 4
    assert lm.decode_tick_bytes(LM, 16, 1000) - b == 1000 * per_position


def test_nmt_flops_count_real_tokens_only():
    mix = {"seq_len": 128}
    short = {"src_len": np.array([10]), "tgt_len": np.array([10])}
    long = {"src_len": np.array([20]), "tgt_len": np.array([20])}
    assert 2 < nmt.train_flops(NMT, mix, long) / \
        nmt.train_flops(NMT, mix, short) < 2.1
    # one source token and one target token: the encoder's blocks and the
    # cross attention's k, v see the source token; the decoder's blocks, the
    # cross attention's q, o and the output projection see the target token
    one = {"src_len": np.array([1]), "tgt_len": np.array([1])}
    blk = 4 * 1024 ** 2 + 2 * 1024 * 4096
    weights = 6 * blk + 6 * 2 * 1024 ** 2 \
        + 6 * (blk + 2 * 1024 ** 2) + 1024 * 37000
    full = counts.attention_flops(16, 1, 1, 64, False, True)
    attn = 6 * (2 * full + full / 2)     # encoder self, cross; causal self
    assert nmt.train_flops(NMT, mix, one) == pytest.approx(6 * weights + attn)
    assert len(nmt.flash_calls(NMT, mix, 64)) == 36


def test_configurations_keep_the_published_widths():
    for cfg in (LM, NMT):
        assert (cfg["d_model"], cfg["d_inner"], cfg["num_heads"],
                cfg["head_dim"]) == (1024, 4096, 16, 64)
        assert cfg["reduced"] == []
    assert LM["num_layers"] == 12 and NMT["num_layers"] == 6
