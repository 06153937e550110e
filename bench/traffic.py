"""The one traffic generator: it reads a mix's parameters from
``bench/traffic/<mix>.json`` and makes the requests of one run.

Every seed gets the same prompt lengths, output lengths and
inter-arrival gaps, drawn at fixed quantiles of the stated distributions
and put in one fixed order; the seed draws only the prompt tokens.  So
two seeds do the same work at the same times: on the chip, a seed that
only reordered the lengths moved an offline batch's drain, and with it
the window, by up to 15 %.

A mix file holds:

- ``arrivals``: ``{"kind": "batch", "rate": r}`` puts ``round(r *
  seconds)`` requests due at t = 0 (an offline batch);
  ``{"kind": "poisson", "rate": r}`` spreads as many over the window
  with exponential gaps of mean ``1 / r`` (open loop);
- ``prompt`` and ``output``: token lengths, ``{"dist": "lognormal",
  "median": m, "sigma": s, "min": a, "max": b}`` or ``{"dist":
  "uniform", "min": a, "max": b}``;
- ``serving``: the ServeLoop arena the cell runs with (``slots``,
  ``max_len``, ``prefill_chunk``, ``block_size``);
- ``check``: how many finished requests the correctness check samples.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist

import numpy as np


@dataclass(frozen=True)
class Due:
    rid: int
    tokens: np.ndarray  # (prompt_len,) int32
    max_new_tokens: int
    due: float  # seconds after the window opens


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(spec: dict, n: int) -> np.ndarray:
    """``n`` token lengths at fixed quantiles of ``spec``, ascending."""
    u = _quantiles(n)
    lo, hi = int(spec["min"]), int(spec["max"])
    if spec["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(q)) for q in u])
        v = np.rint(float(spec["median"]) * np.exp(float(spec["sigma"]) * z))
    elif spec["dist"] == "uniform":
        v = np.floor(lo + u * (hi - lo + 1))
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(v, lo, hi).astype(np.int64)


def count(mix: dict, seconds: float) -> int:
    return max(1, int(round(float(mix["arrivals"]["rate"]) * seconds)))


def _order(n: int, stream: int) -> np.ndarray:
    """One fixed shuffle of ``n`` quantiles, the same for every seed."""
    return np.random.default_rng([n, stream]).permutation(n)


def due_times(mix: dict, n: int) -> np.ndarray:
    arr = mix["arrivals"]
    if arr["kind"] == "batch":
        return np.zeros(n)
    if arr["kind"] == "poisson":
        # n - 1 exponential gaps of mean 1 / rate; the first request is
        # due when the window opens
        gaps = -np.log1p(-_quantiles(n - 1)) / float(arr["rate"])
        return np.concatenate([[0.0], np.cumsum(gaps[_order(n - 1, 2)])])
    raise ValueError(f"unknown arrival kind {arr['kind']!r}")


def make(mix: dict, seed: int, seconds: float, vocab: int) -> list[Due]:
    """The requests of one run, in due order."""
    n = count(mix, seconds)
    prompt = lengths(mix["prompt"], n)[_order(n, 0)]
    output = lengths(mix["output"], n)[_order(n, 1)]
    due = due_times(mix, n)
    rng = np.random.default_rng(int(seed))
    return [
        Due(
            rid=i,
            tokens=rng.integers(0, vocab, int(p)).astype(np.int32),
            max_new_tokens=int(o),
            due=float(t),
        )
        for i, (p, o, t) in enumerate(zip(prompt, output, due))
    ]


def percentile(values, q: float) -> float:
    """Nearest-rank percentile ``q`` (0-100) of ``values``; a failed
    request enters as ``math.inf`` and so counts as missing any limit."""
    v = sorted(values)
    if not v:
        raise ValueError("percentile of no values")
    return v[max(0, math.ceil(q / 100.0 * len(v)) - 1)]
