"""One generator for every traffic mix (`bench/traffic/<mix>.json`).

A mix file gives the arrival process, the prompt lengths with their
weights, the output-length distribution and the token distribution.  The
cell file gives the rate.  A run's work is fixed by the rate and the
window alone: the inter-arrival gaps (stratified quantiles of the
process), prompt lengths (counts by weight) and output lengths
(stratified quantiles) are shuffled once by a fixed generator, the same
schedule for every seed; the seed draws the token ids (and, elsewhere,
the weights).  A schedule shuffled by the seed made the seed change the
work: which requests were still running when the window closed moved
`out_tok_s` by 4 % between seeds, 20 times the spread of one seed's runs.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field

import numpy as np


@dataclass
class Request:
    """One request of the open loop, and what the client saw of it."""

    index: int
    due: float                 # seconds after the window opens
    prompt: np.ndarray         # int32 token ids
    max_new: int
    submit_t: float | None = None      # host clock at submit
    refused: bool = False
    rid: int | None = None             # the engine's request id
    token_t: list = field(default_factory=list)   # host clock per token
    tokens: list = field(default_factory=list)    # served token ids
    done_t: float | None = None


def _counts(weights, n: int) -> list[int]:
    """Split ``n`` by ``weights`` (largest remainder)."""
    w = np.asarray(weights, float) / float(np.sum(weights))
    raw = w * n
    base = np.floor(raw).astype(int)
    for i in np.argsort(-(raw - base))[: n - int(base.sum())]:
        base[i] += 1
    return base.tolist()


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def _gaps(arrivals: dict, rate: float, n: int) -> np.ndarray:
    q = _quantiles(n)
    if arrivals["process"] == "poisson":
        return -np.log1p(-q) / rate
    if arrivals["process"] == "uniform":
        return np.full(n, 1.0 / rate)
    raise ValueError(f"unknown arrival process {arrivals['process']!r}")


def _output_lens(spec: dict, n: int) -> np.ndarray:
    q = _quantiles(n)
    lo, hi = spec["min"], spec["max"]
    if spec["dist"] == "uniform":
        v = lo + q * (hi - lo)
    elif spec["dist"] == "loguniform":
        v = np.exp(math.log(lo) + q * (math.log(hi) - math.log(lo)))
    elif spec["dist"] == "fixed":
        v = np.full(n, lo)
    else:
        raise ValueError(f"unknown output-length dist {spec['dist']!r}")
    return np.rint(v).astype(int)


def n_requests(rate: float, seconds: float) -> int:
    return max(1, int(round(rate * seconds)))


def make_requests(mix: dict, rate: float, seconds: float, vocab: int,
                  seed: int) -> list[Request]:
    """The requests due in a window of ``seconds`` at ``rate`` req/s, in
    due order; the first is due at 0, the n - 1 gaps average 1 / rate.
    Only the token ids depend on ``seed``."""
    n = n_requests(rate, seconds)
    fixed = np.random.default_rng(0)
    gaps = fixed.permutation(_gaps(mix["arrivals"], rate, n - 1)) if n > 1 else []
    due = np.concatenate([[0.0], np.cumsum(gaps)])
    if n > 1:
        due *= ((n - 1) / rate) / float(np.sum(gaps))
    pl = mix["prompt_len"]
    lens = np.repeat(pl["values"], _counts(pl["weights"], n))
    lens = fixed.permutation(lens)
    outs = fixed.permutation(_output_lens(mix["output_len"], n))
    rng = np.random.default_rng(seed)
    return [
        Request(i, float(due[i]),
                rng.integers(0, vocab, size=int(lens[i]), dtype=np.int32),
                int(outs[i]))
        for i in range(n)
    ]


def max_len(mix: dict) -> int:
    """The engine's ``max_len``: the longest prompt plus the longest output."""
    return max(mix["prompt_len"]["values"]) + mix["output_len"]["max"]
