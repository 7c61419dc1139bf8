"""The one traffic generator: requests for a serving cell, from its mix file.

A mix file (`bench/workloads/<name>.json`) names this generator and gives:

  loop         "closed": every request is queued at time 0 and the engine
               keeps its slots full; "open": requests arrive on a schedule
  requests     closed loop: how many requests are queued
  rate_per_s   open loop: mean arrival rate (Poisson: exponential gaps)
  prompt_len   {"values": [...]} and optionally {"lognormal": {"median",
               "sigma"}}, which weights the values by a log-normal density
  output_len   {"values": [...]} or {"lognormal": {"median", "sigma"},
               "min", "max"}

Every seed gets the same multiset of lengths and of arrival gaps: each is
the set of quantiles (i + 1/2) / n of its distribution. The seed decides
their order, and the prompts' contents. So the work a run offers does not
change with the seed; only which request gets which size, and when.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import List

import numpy as np


@dataclasses.dataclass(frozen=True)
class Request:
    rid: int
    prompt_len: int
    gen: int
    arrival_s: float


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(spec: dict, n: int) -> np.ndarray:
    """The n lengths of a length spec, as quantiles, in ascending order."""
    u = _quantiles(n)
    ln = spec.get("lognormal")
    if "values" in spec:
        values = np.asarray(spec["values"], float)
        if ln is None:
            w = np.ones_like(values)
        else:
            # each value takes the log-normal's mass between the geometric
            # midpoints to its neighbours
            nd = NormalDist(math.log(ln["median"]), ln["sigma"])
            edges = np.sqrt(values[1:] * values[:-1])
            cdf = [0.0] + [nd.cdf(math.log(e)) for e in edges] + [1.0]
            w = np.diff(cdf)
        cdf = np.cumsum(w / w.sum())
        idx = np.minimum(np.searchsorted(cdf, u, side="right"), len(values) - 1)
        return values[idx].astype(int)
    nd = NormalDist(math.log(ln["median"]), ln["sigma"])
    out = np.exp([nd.inv_cdf(x) for x in u])
    return np.clip(np.rint(out), spec["min"], spec["max"]).astype(int)


def max_lengths(mix: dict):
    """The longest prompt and output the mix can draw: the cache's size."""
    def top(spec):
        return int(max(spec["values"])) if "values" in spec else int(spec["max"])
    return top(mix["prompt_len"]), top(mix["output_len"])


def generate(mix: dict, seed: int, seconds: float) -> List[Request]:
    rng = np.random.default_rng(int(seed))
    if mix["loop"] == "closed":
        n = int(mix["requests"])
        arrivals = np.zeros(n)
    elif mix["loop"] == "open":
        rate = float(mix["rate_per_s"])
        n = max(1, int(rate * seconds))
        gaps = rng.permutation(-np.log1p(-_quantiles(n)) / rate)
        # n gaps and one more mean gap span the window exactly, so the
        # last arrival falls inside it
        arrivals = np.cumsum(gaps) * seconds / (gaps.sum() + gaps.mean())
    else:
        raise ValueError(f"unknown loop {mix['loop']!r}")
    prompts = rng.permutation(lengths(mix["prompt_len"], n))
    outputs = rng.permutation(lengths(mix["output_len"], n))
    return [Request(i, int(prompts[i]), int(outputs[i]), float(arrivals[i]))
            for i in range(n)]
