"""The one traffic generator: every mix is a data file in ``bench/traffic/``.

Keys of a traffic file:

- ``loop``: ``open`` (arrivals on a schedule, ``rate_per_s``) or
  ``closed`` (``clients`` that each send the next request when the last
  one is delivered, after ``think_s``);
- ``arrivals`` (open loop): ``stratified`` or ``poisson``, below;
- ``prompt_tokens`` / ``output_tokens``: ``values`` and their shares ``p``;
- ``block``: every run of ``block`` consecutive requests holds each value
  exactly ``p * block`` times, so every seed serves the same sizes in
  another order.

The same seed gives the same requests.  Prompts are random token ids.
``poisson`` arrivals have independent exponential gaps from the seed.
``stratified`` arrivals are smoother than Poisson: their gaps are the
exponential distribution's quantiles, scaled so that the arrivals fill the
window exactly, and dealt to blocks of ``block`` arrivals by rank (each
block gets one gap of each stratum) before each block is shuffled, so every
seed offers the same load at the same pace through the window.
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional

import numpy as np


@dataclasses.dataclass
class Req:
    idx: int
    prompt: np.ndarray            # (S,) int32
    max_new: int
    due: Optional[float] = None   # seconds after the window opens (open loop)


def _rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % (1 << 64), *stream])


def block_counts(dist: dict, block: int) -> np.ndarray:
    counts = np.asarray(dist["p"], float) * block
    if not np.allclose(counts, np.round(counts)) or round(counts.sum()) != block:
        raise ValueError(f"shares {dist['p']} do not split a block of {block}")
    return np.round(counts).astype(int)


def lengths(dist: dict, block: int, n: int, rng) -> np.ndarray:
    """``n`` values, each block of ``block`` holding the exact counts
    (a last partial block by largest remainder), shuffled within blocks."""
    values = np.asarray(dist["values"], int)
    full = np.repeat(values, block_counts(dist, block))
    out = []
    for start in range(0, n, block):
        m = min(block, n - start)
        if m == block:
            part = full.copy()
        else:
            want = np.asarray(dist["p"], float) * m
            c = np.floor(want).astype(int)
            c[np.argsort(c - want)[:m - c.sum()]] += 1
            part = np.repeat(values, c)
        out.append(rng.permutation(part))
    return np.concatenate(out)[:n] if out else np.zeros(0, int)


def prompt_tokens(seed: int, idx: int, length: int, vocab: int) -> np.ndarray:
    return _rng(seed, 1, idx).integers(0, vocab, length, dtype=np.int32)


def requests(traffic: dict, seed: int, n: int, vocab: int) -> List[Req]:
    """The first ``n`` requests of the seed's stream (sizes and prompts)."""
    block = int(traffic["block"])
    p = lengths(traffic["prompt_tokens"], block, n, _rng(seed, 2))
    o = lengths(traffic["output_tokens"], block, n, _rng(seed, 3))
    return [Req(i, prompt_tokens(seed, i, int(p[i]), vocab), int(o[i]))
            for i in range(n)]


def arrival_times(traffic: dict, seconds: float, seed: int) -> np.ndarray:
    """Due times, in seconds after the window opens, of the arrivals inside
    a window of ``seconds``."""
    rate = float(traffic["rate_per_s"])
    rng = _rng(seed, 4)
    kind = traffic["arrivals"]
    if kind == "poisson":
        due = np.zeros(0)
        while not due.size or due[-1] < seconds:
            more = rng.exponential(1.0 / rate, int(rate * seconds) + 16)
            due = np.concatenate([due, (due[-1] if due.size else 0.0)
                                  + np.cumsum(more)])
        return due[due < seconds]
    if kind != "stratified":
        raise ValueError(f"unknown arrivals {kind!r}")
    n = max(1, int(round(rate * seconds)))
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)      # ascending quantiles
    gaps *= seconds / gaps.sum()
    blocks = -(-n // int(traffic["block"]))
    gaps = np.concatenate([rng.permutation(gaps[b::blocks])
                           for b in rng.permutation(blocks)])
    return np.concatenate([[0.0], np.cumsum(gaps)[:-1]])


def open_schedule(traffic: dict, seconds: float, seed: int,
                  vocab: int) -> List[Req]:
    """Open loop: the requests due inside the window, in order."""
    due = arrival_times(traffic, seconds, seed)
    reqs = requests(traffic, seed, len(due), vocab)
    for r, t in zip(reqs, due):
        r.due = float(t)
    return reqs


class ClosedStream:
    """Closed loop: clients take requests from one seeded stream, in order."""

    def __init__(self, traffic: dict, seed: int, vocab: int,
                 capacity: int = 1 << 14):
        self.clients = int(traffic["clients"])
        self.think_s = float(traffic.get("think_s", 0.0))
        block = int(traffic["block"])
        self._p = lengths(traffic["prompt_tokens"], block, capacity,
                          _rng(seed, 2))
        self._o = lengths(traffic["output_tokens"], block, capacity,
                          _rng(seed, 3))
        self._seed, self._vocab, self._next = seed, vocab, 0

    def take(self) -> Req:
        i = self._next
        self._next += 1
        return Req(i, prompt_tokens(self._seed, i, int(self._p[i]),
                                    self._vocab), int(self._o[i]))
