"""The generator: the seed orders and fills the work, never changes it."""

import collections
import json
import os

import numpy as np
import pytest

from benchmark import traffic

MIX = traffic.load("serve_chat")
VOCAB = 32000


def _multiset(load):
    return collections.Counter((r["user_len"], r["max_new"])
                               for r in load["requests"])


def test_two_seeds_send_the_same_schedule_with_other_tokens():
    a = traffic.open_loop_requests(MIX, 7, 45, VOCAB)
    b = traffic.open_loop_requests(MIX, 3000000019, 45, VOCAB)
    assert _multiset(a) == _multiset(b)
    for key in ("due", "user_len", "max_new", "system"):
        assert [r[key] for r in a["requests"]] == [r[key] for r in b["requests"]]
    assert a["system_prompts"] != b["system_prompts"]
    assert [r["prompt"] for r in a["requests"]] != \
        [r["prompt"] for r in b["requests"]]


def test_another_schedule_seed_orders_the_same_multiset_differently():
    a = traffic.open_loop_requests(MIX, 7, 45, VOCAB)
    b = traffic.open_loop_requests(dict(MIX, schedule_seed=99), 7, 45, VOCAB)
    assert _multiset(a) == _multiset(b)
    assert [r["user_len"] for r in a["requests"]] != \
        [r["user_len"] for r in b["requests"]]
    assert [r["due"] for r in a["requests"]] != [r["due"] for r in b["requests"]]
    # which prompt is popular is fixed in count, not in assignment
    assert collections.Counter(r["system"] for r in a["requests"]) == \
        collections.Counter(r["system"] for r in b["requests"])


def test_same_seed_gives_the_same_requests():
    a = traffic.open_loop_requests(MIX, 11, 45, VOCAB)
    b = traffic.open_loop_requests(MIX, 11, 45, VOCAB)
    assert a == b


@pytest.mark.parametrize("seconds", [10, 45, 51])
def test_count_follows_seconds_and_all_are_due_inside(seconds):
    load = traffic.open_loop_requests(MIX, 5, seconds, VOCAB)
    assert len(load["requests"]) == round(MIX["rate_per_s"] * seconds)
    dues = [r["due"] for r in load["requests"]]
    assert dues == sorted(dues) and 0 <= dues[0] and dues[-1] < seconds


def test_the_cell_sends_what_the_files_rate_says():
    # the rate is a number in the file, set by a sweep (rate_note), and the
    # count of a 45 s window follows from it alone
    assert isinstance(MIX["rate_per_s"], (int, float)) and MIX["rate_per_s"] > 0
    n = traffic.request_count(MIX, 45)
    assert n == round(MIX["rate_per_s"] * 45)
    load = traffic.open_loop_requests(MIX, 3500000077, 45, VOCAB)
    assert len(load["requests"]) == n
    # the 90th percentile has a tenth of them beyond it: some hundreds
    assert n // 10 >= 100
    assert "PERF.md" in MIX["rate_note"]


def test_lengths_are_the_clipped_lognormal_quantiles():
    spec = MIX["user_tokens"]
    got = traffic.lengths(spec, 135)
    assert got.min() >= spec["min"] and got.max() <= spec["max"]
    assert (np.diff(got) >= 0).all()
    assert abs(np.median(got) - spec["median"]) <= 1
    # hand-worked: the quantile 0.5 of a lognormal is its median
    assert traffic.lengths({"dist": "lognormal_quantiles", "median": 40,
                            "sigma": 0.7, "min": 1, "max": 999}, 1)[0] == 40
    assert list(traffic.lengths({"dist": "uniform_quantiles", "min": 0,
                                 "max": 100}, 4)) == [12, 38, 62, 88]
    assert list(traffic.lengths({"dist": "fixed", "value": 32}, 3)) == [32] * 3


def test_prompts_share_a_system_prompt_and_hold_the_user_text():
    load = traffic.open_loop_requests(MIX, 9, 20, VOCAB)
    for r in load["requests"]:
        sp = load["system_prompts"][r["system"]]
        assert r["prompt"][:len(sp)] == sp
        assert len(r["prompt"]) == len(sp) + r["user_len"]
        assert all(0 <= t < VOCAB for t in r["prompt"])


@pytest.mark.parametrize("n", [1, 2, 30, 135, 144])
def test_golden_stride_is_a_permutation(n):
    assert sorted(traffic.golden_stride(n)) == list(range(n))


def test_zipf_counts_are_fixed_and_sum():
    assert list(traffic.zipf_counts(135, 4, 1.0)) == [65, 32, 22, 16]
    assert traffic.zipf_counts(7, 3, 0.0).sum() == 7


def test_bursts_arrive_together():
    mix = dict(MIX, arrivals={"process": "uniform_order_statistics",
                              "burst_size": 5})
    load = traffic.open_loop_requests(mix, 3, 20, VOCAB)
    dues = [r["due"] for r in load["requests"]]
    assert len(set(dues)) == -(-len(dues) // 5)


def test_train_pairs_deal_out_one_fixed_multiset():
    mix = traffic.load("train_pairs_1chip")
    vocabs = {"src_vocab": 37000, "tgt_vocab": 37000}
    a = traffic.train_batches(mix, 1, 1, vocabs)
    b = traffic.train_batches(mix, 2 ** 31 + 5, 1, vocabs)
    assert len(a) == mix["ring"]
    for side in ("src_len", "tgt_len"):
        assert sorted(np.concatenate([x[side] for x in a])) == \
            sorted(np.concatenate([x[side] for x in b]))
    assert sum(x["tokens"] for x in a) == sum(x["tokens"] for x in b)
    x = a[0]
    assert x["feed"]["src"].shape == (64, 128)
    assert x["tokens"] == int(x["feed"]["tgt@SEQLEN"].sum())
    # padding is token 0 beyond each row's length, on every array
    for arr, n in ((x["feed"]["src"], x["src_len"]),
                   (x["feed"]["tgt"], x["tgt_len"]),
                   (x["feed"]["lbl"], x["tgt_len"])):
        for row, k in zip(arr, n):
            assert (row[k:] == 0).all() and (row[:k] > 0).all()


def test_train_stream_rows_are_full_and_shifted_by_one():
    mix = traffic.load("train_stream_dp4")
    ring = traffic.train_batches(mix, 4, 4, {"vocab": 32000})
    f = ring[0]["feed"]
    assert f["tokens"].shape == (32, 1024) and ring[0]["tokens"] == 32 * 1024
    assert (f["tokens"][:, 1:] == f["targets"][:, :-1]).all()
    assert not (ring[0]["feed"]["tokens"] == ring[1]["feed"]["tokens"]).all()


def test_every_cell_of_the_manifest_has_its_files():
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    bench = json.load(open(os.path.join(here, os.pardir, "BENCHMARK.json")))
    for w in bench["workloads"]:
        for rel in (f"cells/{w['name']}.json", f"configs/{w['config']}.json",
                    f"traffic/{w['traffic']}.json"):
            assert os.path.exists(os.path.join(here, rel)), rel
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert os.path.exists(os.path.join(here, "metrics", m["name"] + ".py"))
