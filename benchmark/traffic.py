"""The one traffic generator: a mix is a data file under benchmark/traffic/.

A mix fixes the WORK: how many requests or rows, and the multiset of their
lengths; the seed never changes it. For served traffic the mix also fixes the
SCHEDULE, from its own `schedule_seed`: which request arrives when. A tail
over random arrivals moves with the queue (measured on PR 23's engine, at
four fifths of its knee: 12-22% between seeds), so every run replays one drawn
arrival trace, as a recorded trace would be replayed. The rate is the file's
`rate_per_s`, a number a sweep set once (benchmark/sweep.py; `rate_note`
says which), and the count of requests follows from it and --seconds alone. The run's seed fills the requests with
token ids (and the model with weights). For training rows the seed also deals
the fixed multiset of lengths into batches. So two runs with different seeds
do the same work at the same times, and a metric does not move with which
seed the driver happened to pass.

Kinds of mix (`kind` in the file):
  train_tokens  rows of `seq_len` tokens, all full (language-model stream)
  train_pairs   source and target rows padded to `seq_len`, lengths from `lengths`
  open_loop     requests on an arrival schedule; see `open_loop_requests`
"""

from __future__ import annotations

import json
import math
import os
from statistics import NormalDist

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def load(name: str) -> dict:
    with open(os.path.join(HERE, "traffic", name + ".json")) as f:
        mix = json.load(f)
    if mix.get("name") != name:
        raise ValueError(f"traffic file {name}.json names itself {mix.get('name')!r}")
    return mix


def rng_for(seed: int, stream: str) -> np.random.Generator:
    """Independent streams from one --seed (any non-negative whole number)."""
    return np.random.default_rng([int(seed), sum(stream.encode()), len(stream)])


def lengths(spec: dict, n: int) -> np.ndarray:
    """The fixed multiset of n lengths a spec stands for, ascending. No seed:
    `*_quantiles` are the n evenly spaced quantiles (i + 1/2) / n."""
    dist = spec["dist"]
    if dist == "fixed":
        vals = np.full(n, float(spec["value"]))
    else:
        q = (np.arange(n) + 0.5) / n
        if dist == "lognormal_quantiles":
            inv = NormalDist().inv_cdf
            vals = np.array([math.exp(math.log(spec["median"])
                                      + spec["sigma"] * inv(x)) for x in q])
        elif dist == "uniform_quantiles":
            vals = spec["min"] + q * (spec["max"] - spec["min"])
        else:
            raise ValueError(f"unknown length distribution {dist!r}")
        vals = np.clip(vals, spec["min"], spec["max"])
    return np.rint(vals).astype(np.int64)


def golden_stride(n: int) -> np.ndarray:
    """A fixed permutation of range(n) that depends on n alone: i -> i*s mod n
    with s the integer nearest n/phi that is coprime to n. It pairs the i-th
    quantile of one length with a far-away quantile of the other, so long
    prompts do not always come with long answers."""
    if n <= 1:
        return np.zeros(n, np.int64)
    s = max(1, round(n / ((1 + 5 ** 0.5) / 2)))
    while math.gcd(s, n) != 1:
        s += 1
    return (np.arange(n) * s) % n


def zipf_counts(n: int, k: int, exponent: float) -> np.ndarray:
    """n items over k classes with weights 1/rank**exponent, by largest
    remainder, so the counts are fixed and sum to n."""
    w = 1.0 / np.arange(1, k + 1) ** exponent
    exact = n * w / w.sum()
    counts = np.floor(exact).astype(np.int64)
    for i in np.argsort(-(exact - counts), kind="stable")[: n - counts.sum()]:
        counts[i] += 1
    return counts


def request_count(mix: dict, seconds: float) -> int:
    return max(1, round(mix["rate_per_s"] * seconds))


def open_loop_requests(mix: dict, seed: int, seconds: float, vocab: int) -> dict:
    """Requests of an open-loop mix: `n` requests, all due inside
    [0, seconds). Returns {"system_prompts": [[tok]], "requests": [{due,
    prompt, max_new, user_len, system}]} ordered by due time. Lengths, their
    order, the system prompt of each request and the due times come from the
    mix (`schedule_seed`); the token ids come from `seed`."""
    n = request_count(mix, seconds)
    schedule = int(mix["schedule_seed"])
    user = lengths(mix["user_tokens"], n)
    out = lengths(mix["output_tokens"], n)
    if mix.get("pairing", "golden_stride") != "golden_stride":
        raise ValueError(f"unknown pairing {mix['pairing']!r}")
    out = out[golden_stride(n)]

    sp = mix.get("system_prompts") or {"count": 0, "tokens": 0}
    tok_rng = rng_for(seed, "tokens")
    systems = [tok_rng.integers(0, vocab, sp["tokens"]).tolist()
               for _ in range(sp["count"])]
    if systems:
        counts = zipf_counts(n, sp["count"], sp["popularity"]["exponent"])
        which = np.repeat(np.arange(sp["count"]), counts)
        rng_for(schedule, "system").shuffle(which)
    order = rng_for(schedule, "order").permutation(n)

    arr = mix["arrivals"]
    if arr["process"] != "uniform_order_statistics":
        raise ValueError(f"unknown arrival process {arr['process']!r}")
    burst = int(arr.get("burst_size", 1))
    n_bursts = -(-n // burst)
    # n sorted uniform points of the window: a Poisson process given its count
    times = np.sort(rng_for(schedule, "arrivals").uniform(0.0, seconds, n_bursts))
    due = np.repeat(times, burst)[:n]

    requests = []
    for slot, idx in enumerate(order):
        text = tok_rng.integers(0, vocab, int(user[idx])).tolist()
        s = int(which[slot]) if systems else -1
        requests.append({"due": float(due[slot]),
                         "prompt": (systems[s] if systems else []) + text,
                         "max_new": int(out[idx]), "user_len": int(user[idx]),
                         "system": s})
    return {"system_prompts": systems, "requests": requests}


def train_batches(mix: dict, seed: int, chips: int, vocabs: dict) -> list:
    """The ring of distinct seeded batches a training cell feeds round-robin
    (made before the window opens). Each batch is {"feed": {...}, "tokens":
    counted tokens, "src_len"/"tgt_len" for pairs}. The global batch is
    `batch_per_chip` x chips."""
    rows, T, ring = mix["batch_per_chip"] * chips, mix["seq_len"], mix["ring"]
    tok = rng_for(seed, "tokens")
    if mix["kind"] == "train_tokens":
        batches = []
        for _ in range(ring):
            t = tok.integers(0, vocabs["vocab"], (rows, T + 1)).astype("int64")
            batches.append({
                "feed": {"tokens": t[:, :-1].copy(),
                         "tokens@SEQLEN": np.full((rows,), T, "int32"),
                         "targets": t[:, 1:].copy()},
                "tokens": rows * T})
        return batches
    if mix["kind"] != "train_pairs":
        raise ValueError(f"{mix['name']} is not a training mix")
    # one fixed multiset of rows*ring lengths per side; the seed deals it out
    pool = lengths(mix["lengths"], rows * ring)
    src_all = rng_for(seed, "src_order").permutation(pool).reshape(ring, rows)
    tgt_all = rng_for(seed, "tgt_order").permutation(pool).reshape(ring, rows)
    batches = []
    for src_len, tgt_len in zip(src_all, tgt_all):
        src = tok.integers(2, vocabs["src_vocab"], (rows, T)).astype("int64")
        tgt = tok.integers(2, vocabs["tgt_vocab"], (rows, T + 1)).astype("int64")
        pos = np.arange(T)[None, :]
        src *= pos < src_len[:, None]
        tgt_in = tgt[:, :-1] * (pos < tgt_len[:, None])
        lbl = tgt[:, 1:] * (pos < tgt_len[:, None])
        batches.append({
            "feed": {"src": src, "src@SEQLEN": src_len.astype("int32"),
                     "tgt": tgt_in, "tgt@SEQLEN": tgt_len.astype("int32"),
                     "lbl": lbl},
            "tokens": int(tgt_len.sum()),
            "src_len": src_len, "tgt_len": tgt_len})
    return batches
