"""One generator for every traffic mix: ``traffic/<mix>.json`` holds the
parameters, this module turns them and a seed into requests.

Every seed gets the same multiset of lengths and of inter-arrival gaps, in
another order: values are the distribution's quantiles at ``(i + 0.5) / n``,
dealt in blocks of about ``BLOCK`` requests that each cover the whole
distribution. So the work in a window does not move with the seed, and
runs of different seeds spread no wider than runs of one seed: in an open
loop each block is a slice of the window whose order within is fixed and
the seed orders the slices (a random order within them made tokens/s
spread 7% from seed to seed, since which lengths arrive last decides what
the window completes); in a backlog any prefix of whole blocks, what a
run consumes, has the same mix.
"""
from __future__ import annotations

import dataclasses
import json
import math
import pathlib
from statistics import NormalDist
from typing import List, Optional

import numpy as np

HERE = pathlib.Path(__file__).resolve().parent
BLOCK = 16


@dataclasses.dataclass
class Req:
    rid: int
    prompt: np.ndarray      # int32 token ids
    max_new: int
    due: float              # seconds after the window opens (open loop)


def load_mix(name: str) -> dict:
    with open(HERE / "traffic" / f"{name}.json") as f:
        mix = json.load(f)
    mix["name"] = name
    return mix


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """Independent numpy stream per purpose; any non-negative seed."""
    return np.random.default_rng([int(seed), stream])


def quantiles(spec: dict, n: int) -> np.ndarray:
    """``n`` lengths at the mid-quantiles of ``spec``, clipped, as ints."""
    u = (np.arange(n) + 0.5) / n
    if spec["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(x) for x in u])
        v = spec["median"] * np.exp(spec["sigma"] * z)
    elif spec["dist"] == "uniform":
        v = spec["min"] + u * (spec["max"] + 1 - spec["min"])
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.floor(v), spec["min"], spec["max"]).astype(np.int64)


def dealt(values: np.ndarray, rng: np.random.Generator,
          pattern: Optional[np.random.Generator] = None) -> np.ndarray:
    """Sorted values dealt into blocks of about ``BLOCK`` that each span
    the distribution (block b takes every ``nb``-th value from b), each
    block in ``pattern``'s order (fixed across seeds) or else ``rng``'s,
    the blocks in ``rng``'s order."""
    nb = max(1, len(values) // BLOCK)
    v = np.sort(values)
    order = pattern or rng
    blocks = [v[b::nb][order.permutation(len(v[b::nb]))] for b in range(nb)]
    return np.concatenate([blocks[i] for i in rng.permutation(nb)])


def requests(mix: dict, seed: int, seconds: float, slots: int,
             vocab: int) -> List[Req]:
    """The cell's requests for one run. Open loop: ``rate * seconds``
    arrivals with exponential gaps. Backlog: enough requests that the
    queue never runs dry (every ``due`` is 0; the client tops the queue
    up as slots free)."""
    if mix["kind"] == "open_loop":
        # rate * seconds arrivals, all due inside the window: exponential
        # gaps scaled to sum to the window, the first due at its opening
        n = max(1, int(round(float(mix["rate_per_s"]) * seconds)))
        u = (np.arange(n) + 0.5) / n
        gaps = -np.log1p(-u)

        def deal(values, stream):
            # each ~10 s slice of the window gets a like share of gaps and
            # lengths in a fixed order; the seed orders the slices
            return dealt(values, rng_for(seed, stream),
                         np.random.default_rng([0, stream]))
        due = np.concatenate([[0.0], np.cumsum(
            deal(gaps * (seconds / gaps.sum()), 1))[:-1]])
        plen = deal(quantiles(mix["prompt"], n), 2)
        olen = deal(quantiles(mix["output"], n), 3)
    elif mix["kind"] == "backlog":
        # more than a window can consume: every slot finishing its
        # shortest output at one step per 2 ms, faster than the weight
        # read alone allows at these sizes (the client fails if it runs dry)
        per_slot = seconds * 500.0 / mix["output"]["min"] + 1
        n = int(math.ceil(slots * (per_slot + 1 + mix["backlog_per_slot"])
                          / BLOCK)) * BLOCK
        due = np.zeros(n)
        plen = dealt(quantiles(mix["prompt"], n), rng_for(seed, 2))
        olen = dealt(quantiles(mix["output"], n), rng_for(seed, 3))
    else:
        raise ValueError(f"unknown traffic kind {mix['kind']!r}")
    tok = rng_for(seed, 4)
    return [Req(rid=i, prompt=tok.integers(1, vocab, size=int(plen[i]),
                                           dtype=np.int32),
                max_new=int(olen[i]), due=float(due[i]))
            for i in range(n)]


def buckets(mix: dict, max_len: int, min_bucket: int = 8) -> List[int]:
    """Power-of-two prefill buckets the mix's prompt lengths can land in
    (the scheduler pads each prompt to the next power of two)."""
    lo, hi = mix["prompt"]["min"], mix["prompt"]["max"]
    out, b = [], min_bucket
    while True:
        if b >= lo:
            out.append(min(b, max_len))
        if b >= hi or b >= max_len:
            break
        b *= 2
    return sorted(set(out))
