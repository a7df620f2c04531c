"""One general traffic generator, driven by a mix's data file.

A mix file (``bench/traffic/<name>.json``) sets the loop (``closed``: every
slot holds a session from set-up on; ``open``: Poisson arrivals at
``rate_per_s``), the prompt and output length distributions, and the slot
count. Every seed gets the same work: lengths and inter-arrival gaps are
the quantiles of their distributions, laid out in blocks of ``block``
requests, and the seed only permutes each block and draws the token ids.
So any stretch of ``block`` consecutive requests carries the same lengths
whatever the seed, and two seeds differ in order, not in work.
"""
from __future__ import annotations

import dataclasses
import json
import math
from pathlib import Path
from statistics import NormalDist
from typing import List, Optional

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"


@dataclasses.dataclass
class BenchRequest:
    """One request as the benchmark tracks it. Times are host-clock seconds
    on the schedule's own clock (0 = the first arrival of the schedule)."""
    req_id: int
    prompt: np.ndarray
    max_new_tokens: int
    due: float
    admit_start: Optional[float] = None
    emissions: List[tuple] = dataclasses.field(default_factory=list)
    tokens: List[int] = dataclasses.field(default_factory=list)
    done: bool = False

    @property
    def first_token(self) -> Optional[float]:
        return self.emissions[0][0] if self.emissions else None


def load_mix(name: str) -> dict:
    path = TRAFFIC_DIR / f"{name}.json"
    return json.loads(path.read_text())


def _quantiles(spec: dict, n: int) -> np.ndarray:
    """The n mid-quantiles (i + 0.5) / n of a length distribution, clipped,
    rounded up to its buckets, plus ``plus`` tokens."""
    return _bucketed(spec, n) + int(spec.get("plus", 0))


def _bucketed(spec: dict, n: int) -> np.ndarray:
    if "fixed" in spec:
        return np.full(n, int(spec["fixed"]), np.int64)
    if "buckets" in spec and "lognormal" not in spec:
        b = np.asarray(spec["buckets"], np.int64)
        return b[np.arange(n) * len(b) // n]
    ln = spec["lognormal"]
    nd = NormalDist(math.log(ln["median"]), ln["sigma"])
    vals = np.array([math.exp(nd.inv_cdf((i + 0.5) / n)) for i in range(n)])
    lo, hi = spec.get("clip", (1, float("inf")))
    vals = np.ceil(np.clip(vals, lo, hi)).astype(np.int64)
    if "buckets" in spec:
        b = np.asarray(spec["buckets"], np.int64)
        vals = b[np.minimum(np.searchsorted(b, vals), len(b) - 1)]
    return vals


def _exp_gaps(rate: float, n: int) -> np.ndarray:
    """Mid-quantiles of the exponential gap of a Poisson process."""
    u = (np.arange(n) + 0.5) / n
    return -np.log1p(-u) / rate


def _seed_words(seed: int) -> List[int]:
    """A seed of any size as 32-bit words for numpy's SeedSequence."""
    seed = int(seed)
    sign = 1 if seed < 0 else 0
    seed = abs(seed)
    words = [sign]
    while True:
        words.append(seed & 0xFFFFFFFF)
        seed >>= 32
        if not seed:
            return words


def rng_for(seed: int, *salt: int) -> np.random.Generator:
    return np.random.default_rng(_seed_words(seed) + list(salt))


def generate(mix: dict, seed: int, vocab_size: int) -> List[BenchRequest]:
    """The mix's requests for ``seed``, in due order. Closed-loop sessions
    are all due at 0; open-loop ones follow the Poisson schedule."""
    n, block = int(mix["requests"]), int(mix["block"])
    prompt_q = _quantiles(mix["prompt"], block)
    out_q = _quantiles(mix["output"], block)
    gaps_q = _exp_gaps(mix["rate_per_s"], block) if mix["loop"] == "open" else None
    rng = rng_for(seed, 1)
    reqs: List[BenchRequest] = []
    t = 0.0
    for b0 in range(0, n, block):
        # prompts and outputs are permuted apart, so a long prompt is not
        # tied to a long output
        pp, po = rng.permutation(block), rng.permutation(block)
        pg = rng.permutation(block)
        for j in range(min(block, n - b0)):
            i = b0 + j
            if gaps_q is not None:
                t += float(gaps_q[pg[j]])
            plen = int(prompt_q[pp[j]])
            prompt = rng_for(seed, 2, i).integers(0, vocab_size, plen,
                                                  dtype=np.int32)
            reqs.append(BenchRequest(req_id=i, prompt=prompt,
                                     max_new_tokens=int(out_q[po[j]]),
                                     due=t if gaps_q is not None else 0.0))
    return reqs


def prompt_buckets(mix: dict) -> List[int]:
    """Every prompt length the mix can send (the shapes set-up warms)."""
    return sorted({int(x) for x in _quantiles(mix["prompt"], int(mix["block"]))})
