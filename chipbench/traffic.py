"""The one traffic generator: arrivals of a mix, as a pure function of the seed.

A mix is a data file ``traffic/<name>.json`` of parameters; this module reads
it and draws everything a run feeds the service from ``--seed``:

``backlog``  arrivals in a queue that never empties, handed to the service
             ``group`` at a time.  Each arrival carries its staleness (model
             versions behind the server), drawn i.i.d. from ``staleness``.
``rounds``   synchronous rounds: every session slot is assigned a client,
             and each client misses the deadline with probability
             ``dropout``.  The number of absent clients per round is
             stratified over cycles of ``stratify_rounds`` rounds (the
             binomial quantiles, in an order drawn from the seed), so every
             seed does the same work in another order; which slots are
             absent is drawn from the seed.

The delta pool (``pool``: how many seeded delta pytrees, and the lognormal
spread of their norms around the clip norm) is part of the mix too.
"""
from __future__ import annotations

import json
import math
from pathlib import Path
from typing import List

import numpy as np

TRAFFIC_DIR = Path(__file__).with_name("traffic")
KINDS = ("backlog", "rounds")


def load(name: str, root: Path = TRAFFIC_DIR) -> dict:
    path = root / f"{name}.json"
    mix = json.loads(path.read_text())
    if mix.get("kind") not in KINDS:
        raise ValueError(f"{path}: kind {mix.get('kind')!r} is not one of "
                         f"{KINDS}")
    return mix


def seed_words(seed: int, n: int = 2) -> List[int]:
    """``n`` 32-bit words derived from any whole-number seed (the driver's
    seeds need more than 32 bits)."""
    ss = np.random.SeedSequence(int(seed) % (1 << 64))
    return [int(w) for w in ss.generate_state(n, np.uint32)]


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """An independent numpy stream per (seed, purpose)."""
    return np.random.default_rng([*seed_words(seed), stream])


def staleness(mix: dict, seed: int, n: int) -> np.ndarray:
    """(n,) int staleness of the first n arrivals of a backlog mix."""
    st = mix.get("staleness", {"dist": "constant", "value": 0})
    if st["dist"] == "constant":
        return np.full(n, int(st["value"]), np.int64)
    if st["dist"] == "geometric":  # on {0, 1, ...} with the given mean
        p = 1.0 / (1.0 + float(st["mean"]))
        return rng_for(seed, 1).geometric(p, size=n).astype(np.int64) - 1
    raise ValueError(f"staleness dist {st['dist']!r}")


def _binomial_quantiles(n: int, p: float, k: int) -> List[int]:
    """The k stratified quantiles of Binomial(n, p), at (j + 1/2) / k."""
    cdf, acc = [], 0.0
    for x in range(n + 1):
        acc += math.comb(n, x) * p ** x * (1 - p) ** (n - x)
        cdf.append(acc)
    out = []
    for j in range(k):
        q = (j + 0.5) / k
        out.append(next(x for x, c in enumerate(cdf) if c >= q))
    return out


def absent_slots(mix: dict, seed: int, slots: int,
                 rounds: int) -> List[List[int]]:
    """Per round, the sorted session slots whose clients miss the deadline."""
    p = float(mix.get("dropout", 0.0))
    if p <= 0.0:
        return [[] for _ in range(rounds)]
    k = int(mix.get("stratify_rounds", 20))
    counts = _binomial_quantiles(slots, p, k)
    r_order, r_slots = rng_for(seed, 2), rng_for(seed, 3)
    out: List[List[int]] = []
    while len(out) < rounds:
        for c in r_order.permutation(counts):
            out.append(sorted(int(s) for s in
                              r_slots.choice(slots, int(c), replace=False)))
    return out[:rounds]
