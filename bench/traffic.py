"""The one traffic generator: every mix under ``bench/traffic/`` is a file
of parameters that this module reads.

Serving mixes (``"kind": "closed"``) give prompt-length buckets with
weights and a clipped lognormal for output lengths. Every seed gets the
same multiset of sizes (bucket counts in proportion to the weights,
output lengths at evenly spaced quantiles); the seed only shuffles their
order and draws the token ids. So two seeds offer the same work, and the spread
between seeds is the spread of the system, not of the draw.

A rollout mix (``"kind": "rollout"``) names the group size and the
warm-up steps of a simulation rollout; its actions come from the
engine's own seeded generator.
"""

from __future__ import annotations

import dataclasses
import math
import statistics
from typing import Any, Dict, List

import numpy as np

SERVING_KINDS = ("closed",)


@dataclasses.dataclass
class Request:
    """One generated request: its prompt and its output length."""
    prompt: np.ndarray
    max_new: int


def _bucket_lengths(buckets: List[List[float]], n: int) -> List[int]:
    """``n`` prompt lengths with each bucket's count in proportion to its
    weight (largest remainders get the leftovers)."""
    lens = [int(b[0]) for b in buckets]
    weights = np.asarray([float(b[1]) for b in buckets])
    exact = n * weights / weights.sum()
    counts = np.floor(exact).astype(int)
    for i in np.argsort(-(exact - counts), kind="stable")[: n - counts.sum()]:
        counts[i] += 1
    return [length for length, c in zip(lens, counts) for _ in range(c)]


def _output_lengths(out: Dict[str, Any], n: int) -> List[int]:
    """``n`` output lengths at the quantiles (i + 1/2) / n of a lognormal
    with the given median and sigma, clipped to [min, max]."""
    dist = statistics.NormalDist()
    vals = []
    for i in range(n):
        z = dist.inv_cdf((i + 0.5) / n)
        v = round(out["median"] * math.exp(out["sigma"] * z))
        vals.append(int(min(max(v, out["min"]), out["max"])))
    return vals


def serving_requests(traffic: Dict[str, Any], seed: int,
                     vocab: int) -> List[Request]:
    """The requests of one run: a pool of ``pool`` requests that the
    clients take in order."""
    kind = traffic["kind"]
    if kind not in SERVING_KINDS:
        raise ValueError(f"not a serving mix: kind {kind!r}")
    rng = np.random.RandomState(seed)
    n = int(traffic["pool"])
    prompt_lens = rng.permutation(_bucket_lengths(traffic["prompt_buckets"], n))
    outputs = rng.permutation(_output_lengths(traffic["output"], n))
    return [Request(prompt=rng.randint(0, vocab, size=int(p)).astype(np.int32),
                    max_new=int(m))
            for p, m in zip(prompt_lens, outputs)]


def prompt_lengths(traffic: Dict[str, Any]) -> List[int]:
    """Every prompt length the mix can send (each needs its own prefill
    program, warmed in set-up)."""
    return sorted({int(b[0]) for b in traffic["prompt_buckets"]})
