"""Request streams from a traffic mix file (``traffic/<mix>.json``).

A mix file holds parameters only: the arrival process, the prompt-length
buckets with their weights, the output-length distribution and the block
size.  This one generator reads every mix.  A mix knows nothing of
configurations: an open-loop cell's offered rate is the cell's own, in
``offered/<cell>.json`` (four fifths of the knee a sweep on the chip
found for that cell), and reaches ``load`` as ``rate_per_s``.

Sizes: every block of ``block`` requests holds the same multiset of
prompt lengths and output lengths whatever the seed: bucket counts in the
mix's proportions, output lengths at the block's quantiles of their
distribution.  The seed orders them within each block and draws the
token ids, so two seeds offer the same work in another order.

Arrivals (``poisson``): a Poisson process at the offered rate, conditioned
on its count: each period of ``period / rate`` seconds holds exactly
``period`` arrivals at sorted uniform times, which is how a Poisson
process places a given count.  The draw comes from ``path_seed``, so
every run seed meets the same sample path and a run's spread measures the
system rather than the burstiness of one draw; a mix without
``path_seed`` draws its path from the run seed.
"""
from __future__ import annotations

import dataclasses
import json
import math
from pathlib import Path
from statistics import NormalDist
from typing import Iterator, List, Optional, Tuple

import numpy as np

ARRIVALS = ("backlog", "poisson")


@dataclasses.dataclass(frozen=True)
class Mix:
    name: str
    arrival: str                       # "backlog" | "poisson"
    rate_per_s: float                  # poisson: mean arrivals per second
    queued_per_row: int                # backlog: waiting requests per row
    buckets: Tuple[Tuple[int, float], ...]   # (prompt length, weight)
    out_median: float
    out_sigma: float
    out_min: int
    out_max: int
    block: int
    period: int = 0                    # poisson: arrivals per period
    path_seed: Optional[int] = None    # poisson: None draws from the seed

    @property
    def prompt_lengths(self) -> List[int]:
        return sorted(length for length, _ in self.buckets)


@dataclasses.dataclass(frozen=True)
class Request:
    index: int
    due_s: float                       # offset from the window's start
    prompt: np.ndarray                 # (plen,) int32 token ids
    max_new_tokens: int


def load(path: Path, name: str, rate_per_s: Optional[float] = None) -> Mix:
    """The mix, offered at ``rate_per_s`` when its arrivals are open."""
    raw = json.loads(Path(path).read_text())
    arrival = raw["arrival"]
    if arrival["kind"] not in ARRIVALS:
        raise ValueError(f"{path}: arrival kind {arrival['kind']!r} not in "
                         f"{ARRIVALS}")
    if arrival["kind"] == "poisson" and not rate_per_s:
        raise ValueError(f"{path}: an open-loop mix needs the cell's "
                         f"offered rate")
    out = raw["output_tokens"]
    if out["kind"] != "lognormal":
        raise ValueError(f"{path}: output kind {out['kind']!r} is not "
                         f"lognormal")
    path_seed = arrival.get("path_seed")
    return Mix(
        name=name, arrival=arrival["kind"],
        rate_per_s=float(rate_per_s or 0.0),
        queued_per_row=int(arrival.get("queued_per_row", 0)),
        buckets=tuple((int(n), float(w)) for n, w in raw["prompt_buckets"]),
        out_median=float(out["median"]), out_sigma=float(out["sigma"]),
        out_min=int(out["min"]), out_max=int(out["max"]),
        block=int(raw["block"]), period=int(arrival.get("period", 0)),
        path_seed=None if path_seed is None else int(path_seed))


def bucket_counts(mix: Mix) -> List[int]:
    """Requests of each bucket in one block, by largest remainder."""
    total = sum(w for _, w in mix.buckets)
    exact = [mix.block * w / total for _, w in mix.buckets]
    counts = [math.floor(e) for e in exact]
    order = sorted(range(len(exact)), key=lambda i: counts[i] - exact[i])
    for i in order[:mix.block - sum(counts)]:
        counts[i] += 1
    return counts


def block_prompt_lengths(mix: Mix) -> List[int]:
    return [length for (length, _), n in zip(mix.buckets, bucket_counts(mix))
            for _ in range(n)]


def block_output_lengths(mix: Mix) -> List[int]:
    """The block's quantiles of the clipped lognormal output length."""
    unit = NormalDist()
    out = []
    for i in range(mix.block):
        z = unit.inv_cdf((i + 0.5) / mix.block)
        n = round(mix.out_median * math.exp(mix.out_sigma * z))
        out.append(min(max(n, mix.out_min), mix.out_max))
    return out


def arrivals(mix: Mix, seed: int) -> Iterator[float]:
    """Endless due times (seconds from the window's start)."""
    if mix.arrival != "poisson":
        while True:
            yield 0.0
    path = seed if mix.path_seed is None else mix.path_seed
    rng = np.random.default_rng(np.random.SeedSequence([path, 3]))
    span = mix.period / mix.rate_per_s
    start = 0.0
    while True:
        for t in np.sort(rng.uniform(0.0, span, mix.period)):
            yield start + float(t)
        start += span


def stream(mix: Mix, seed: int, vocab_size: int,
           stream_id: int = 0) -> Iterator[Request]:
    """Endless requests: block after block, each shuffled by the seed."""
    rng = np.random.default_rng(np.random.SeedSequence([seed, stream_id]))
    plens, outs = block_prompt_lengths(mix), block_output_lengths(mix)
    due = arrivals(mix, seed)
    index = 0
    while True:
        order_p = rng.permutation(mix.block)
        order_o = rng.permutation(mix.block)
        for j in range(mix.block):
            plen = plens[order_p[j]]
            prompt = rng.integers(0, vocab_size, plen, dtype=np.int64)
            yield Request(index=index, due_s=next(due),
                          prompt=prompt.astype(np.int32),
                          max_new_tokens=outs[order_o[j]])
            index += 1
