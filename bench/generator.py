"""Requests of one run, drawn from a traffic mix file and the run's seed.

A mix file (``bench/traffic/<mix>.json``) names an arrival process and
two length distributions.  Lengths and inter-arrival gaps are drawn in
stratified blocks of ``block`` requests: each block holds the same
``block`` quantiles of each distribution, and the seed only orders them
(and picks the prompt tokens).  A block of Poisson arrivals spans
exactly ``block / rate_per_s`` seconds, and that span has to divide the
pre-roll and the window: every seed then sends the same requests, by
size, into each, in another order.  Every request is greedy.

Arrivals start ``preroll_s`` seconds before the window opens, so that
the window finds the engine in its steady state; requests due before the
window are served but not answered for.  Arrival kinds:

- ``poisson``: open loop at ``rate_per_s``; a block of gaps holds the
  exponential quantiles, scaled so that the block spans exactly
  ``block / rate_per_s`` seconds.
- ``backlog``: ``count`` requests all due at the start of the pre-roll,
  served in order as slots free up (an offline batch).

Another kind is a module ``bench/traffic/<kind>.py`` with a function
``gaps(spec, n, rng) -> np.ndarray`` of ``n`` inter-arrival seconds.
"""
from __future__ import annotations

import importlib
import math
from dataclasses import dataclass
from statistics import NormalDist

import numpy as np


@dataclass
class Planned:
    rid: int
    due_s: float            # seconds after the window opens (< 0: pre-roll)
    prompt: np.ndarray      # int32 token ids
    max_new_tokens: int


def _quantile(spec: dict, u: float) -> float:
    dist = spec["dist"]
    if dist == "uniform":
        return spec["min"] + u * (spec["max"] + 1 - spec["min"])
    if dist == "lognormal":
        return spec["median"] * math.exp(spec["sigma"]
                                         * NormalDist().inv_cdf(u))
    raise ValueError(f"unknown length distribution {dist!r}")


def stratified_lengths(spec: dict, n: int, block: int,
                       rng: np.random.Generator) -> np.ndarray:
    """``n`` whole lengths in [min, max]: each block of ``block`` holds
    the distribution's quantiles at (i + 0.5) / block, shuffled."""
    q = np.array([_quantile(spec, (i + 0.5) / block) for i in range(block)])
    q = np.clip(np.floor(q), spec["min"], spec["max"]).astype(np.int64)
    blocks = -(-n // block)
    return np.concatenate([rng.permutation(q) for _ in range(blocks)])[:n]


def _poisson_due(spec: dict, n: int, block: int,
                 rng: np.random.Generator) -> np.ndarray:
    """Due times of ``n`` arrivals from 0: block k starts at exactly k
    spans (to the nanosecond, so that no rounding moves a block's first
    request across the window's edge), then its shuffled gaps."""
    u = (np.arange(block) + 0.5) / block
    g = -np.log1p(-u)
    span = block / spec["rate_per_s"]
    g *= span / g.sum()
    blocks = -(-n // block)
    due = [k * span + np.concatenate([[0.0], np.cumsum(rng.permutation(g))[:-1]])
           for k in range(blocks)]
    return np.round(np.concatenate(due)[:n], 9)


def check_aligned(span: float, preroll: float, seconds: float) -> None:
    """Raise unless a block's span divides the pre-roll and the window."""
    for name, length in (("pre-roll", preroll), ("window", seconds)):
        k = round(length / span)
        if abs(k * span - length) > 1e-6 * max(span, length):
            raise ValueError(f"a block spans {span} s, which does not "
                             f"divide the {length} s {name}")


def plan_requests(mix: dict, seed: int, seconds: float,
                  vocab: int) -> tuple[list[Planned], bool]:
    """The run's requests in due order, and whether they form a backlog
    (all due at once, none of them late)."""
    rng = np.random.default_rng(seed)
    block = int(mix.get("block", 64))
    preroll = float(mix.get("preroll_s", 0.0))
    arrivals = mix["arrivals"]
    kind = arrivals["kind"]
    if kind == "backlog":
        n = int(arrivals["count"])
        due = np.zeros(n)
    else:
        # enough whole blocks to cover the pre-roll and the window
        rate = float(arrivals.get("rate_per_s", 1.0))
        n = (int(math.ceil(rate * (preroll + seconds) / block)) + 1) * block
        if kind == "poisson":
            check_aligned(block / rate, preroll, seconds)
            due = _poisson_due(arrivals, n, block, rng)
        else:
            gaps = importlib.import_module(
                f"bench.traffic.{kind}").gaps(arrivals, n, rng)
            due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
        n = int(np.searchsorted(due, preroll + seconds - 1e-9, side="left"))
        due = due[:n]
    due = due - preroll
    prompts = stratified_lengths(mix["prompt_len"], n, block, rng)
    outputs = stratified_lengths(mix["output_len"], n, block, rng)
    reqs = [Planned(i, float(due[i]),
                    rng.integers(0, vocab, size=int(prompts[i]),
                                 dtype=np.int32),
                    int(outputs[i])) for i in range(n)]
    return reqs, kind == "backlog"


def prompt_range(mix: dict) -> tuple[int, int]:
    """Shortest and longest prompt the mix can send."""
    spec = mix["prompt_len"]
    return int(spec["min"]), int(spec["max"])
