"""One general generator for every traffic mix (``bench/traffic/<mix>.json``).

A mix is data: its loop (``open`` with a fixed ``rate_per_s``, or
``closed`` with a number of ``clients`` drawing from a ``pool`` of
requests), the distributions of prompt and output lengths, and the
``order_seed`` of its draw. The sizes and arrival gaps are fixed by the
mix alone, so every run does the same work; ``--seed`` draws the token ids
(and the weights). Sizes come in blocks of ``BLOCK``, each block a
stratified sample of the distribution (levels ``(i + offset_j) / BLOCK``,
the offset moving by the golden ratio from block to block) put in an order
drawn from ``order_seed``, so every whole-block prefix of a schedule holds
the same multiset of sizes. A mix that differs only in ``order_seed`` is
the same traffic in another order: held-out traffic, added as data.
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np

BLOCK = 16
_GOLDEN = (math.sqrt(5.0) - 1.0) / 2.0


@dataclasses.dataclass
class Planned:
    """One request of a schedule: its due offset (open loop, seconds after
    the window opens; ``None`` in a closed loop, where a client sends it
    when its previous request retires), prompt ids and output length."""
    index: int
    client: int
    due: float | None
    prompt: np.ndarray
    max_new: int


def _inverse_cdf(dist: dict, u: np.ndarray) -> np.ndarray:
    kind = dist["dist"]
    if kind == "lognormal":
        from statistics import NormalDist

        z = np.array([NormalDist().inv_cdf(float(x)) for x in u])
        vals = dist["median"] * np.exp(dist["sigma"] * z)
    elif kind == "uniform":
        vals = dist["min"] + u * (dist["max"] + 1 - dist["min"])
    elif kind == "exponential":
        vals = -np.log1p(-u) * dist["mean"]
        return vals
    else:
        raise ValueError(f"unknown distribution {kind!r}")
    return np.clip(np.floor(vals), dist["min"], dist["max"]).astype(np.int64)


def stratified(dist: dict, n: int) -> np.ndarray:
    """``n`` values of ``dist`` in blocks of ``BLOCK`` stratified levels,
    each block in ascending order."""
    out = []
    for j in range(-(-n // BLOCK)):
        off = (0.5 + j * _GOLDEN) % 1.0
        u = (np.arange(BLOCK) + off) / BLOCK
        out.append(_inverse_cdf(dist, u))
    return np.concatenate(out)[:n]


def _shuffle_blocks(rng: np.random.Generator, a: np.ndarray) -> np.ndarray:
    a = a.copy()
    for s in range(0, len(a), BLOCK):
        rng.shuffle(a[s:s + BLOCK])
    return a


def schedule(mix: dict, seed: int, seconds: float, vocab: int
             ) -> list[Planned]:
    """The requests of one run, in the order they are sent (open loop) or
    handed to clients as they free up (closed loop)."""
    order = np.random.default_rng(mix["order_seed"])
    rng = np.random.default_rng([seed, 0x7AFF1C])
    if mix["loop"] == "open":
        n = max(1, round(mix["rate_per_s"] * seconds))
        gaps = stratified({"dist": "exponential",
                           "mean": 1.0 / mix["rate_per_s"]}, n)
        gaps = _shuffle_blocks(order, gaps)
        # the n-th request is due half a gap before the window closes
        due = np.cumsum(gaps) - gaps[0]
        due = due * (seconds * (1 - 0.5 / n)) / max(due[-1], 1e-9)
    elif mix["loop"] == "closed":
        n = mix["pool"]
        due = [None] * n
    else:
        raise ValueError(f"unknown loop {mix['loop']!r}")
    plens = _shuffle_blocks(order, stratified(mix["prompt"], n))
    olens = _shuffle_blocks(order, stratified(mix["output"], n))
    clients = mix.get("clients", 0)
    out = []
    for i in range(n):
        ids = rng.integers(0, vocab, int(plens[i]), dtype=np.int32)
        out.append(Planned(i, i % clients if clients else i,
                           None if due[i] is None else float(due[i]),
                           ids, int(olens[i])))
    return out


def prefill_buckets(mix: dict, bucket_min: int, max_len: int) -> list[int]:
    """Every prefill width a schedule of ``mix`` can ask for: the server
    pads a wave to the next power of two of its longest prompt."""
    lo, hi = mix["prompt"]["min"], mix["prompt"]["max"]
    out, b = [], max(bucket_min, 1)
    while b < lo:
        b *= 2
    while True:
        out.append(min(b, max_len))
        if b >= hi:
            break
        b *= 2
    return sorted(set(out))
