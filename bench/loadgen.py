"""The one traffic generator: reads a mix's parameters from
``traffic/<name>.json`` and makes its inputs from the seed.

Every seed gets the same multiset of sizes in another order: lengths come
from a fixed grid of quantiles of their
distribution (a pool), and each pass over a pool is a fresh permutation
drawn from the seed.  So two seeds do the same work, and a run whose
window covers a few passes sees nearly the same mix whatever its seed.

Request mixes (the serving driver): ``prompt`` and ``output`` length
distributions, ``pool`` requests per pass, and ``arrival``
``{"kind": "closed", "clients": n}``: each client sends its next request
when its last one finishes.
"""

from __future__ import annotations

import json
import os
from statistics import NormalDist
from typing import Dict, Iterator, List, Tuple

import numpy as np

__all__ = ["load", "quantile_pool", "RequestStream"]

HERE = os.path.dirname(os.path.abspath(__file__))


def load(name: str) -> Dict:
    with open(os.path.join(HERE, "traffic", f"{name}.json")) as f:
        return json.load(f)


def quantile_pool(spec: Dict, n: int) -> np.ndarray:
    """``n`` values at the quantiles (i + 1/2) / n of ``spec``'s
    ``lognormal`` distribution (``median``, ``sigma``), clipped to
    ``min``..``max``, whole numbers."""
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown distribution {spec['dist']!r}")
    q = (np.arange(n) + 0.5) / n
    z = np.asarray([NormalDist().inv_cdf(float(p)) for p in q])
    x = spec["median"] * np.exp(spec["sigma"] * z)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def _passes(pool: np.ndarray, rng: np.random.Generator) -> Iterator:
    while True:
        yield from pool[rng.permutation(pool.size)]


class RequestStream:
    """Requests of a mix in the order they are sent: ``next()`` gives
    ``(prompt token ids, output length)``; token ids are uniform over
    ``vocab`` from the seed."""

    def __init__(self, traffic: Dict, seed: int, vocab: int):
        rng = np.random.default_rng([seed, 1])
        pool = int(traffic.get("pool", 128))
        self._prompt = _passes(quantile_pool(traffic["prompt"], pool),
                               np.random.default_rng([seed, 2]))
        self._output = _passes(quantile_pool(traffic["output"], pool),
                               np.random.default_rng([seed, 3]))
        self._rng = rng
        self.vocab = vocab

    def next(self, prompt_len: int = 0) -> Tuple[List[int], int]:
        n = int(next(self._prompt))
        n = prompt_len or n
        toks = self._rng.integers(0, self.vocab, n).tolist()
        return toks, int(next(self._output))

