"""The one traffic generator: a traffic file's parameters + a seed ->
the requests of a run.

A traffic file (``bench/traffic/<name>.json``) is data only:

- ``loop``: ``"closed"`` (``outstanding_batches`` × the serving batch
  requests stay in the server; each answer is replaced at once) or
  ``"open"`` (Poisson arrivals at ``rate_per_s`` over the window,
  whatever the server does);
- ``mix``: {op: share} over ``mul``, ``mul_plain``, ``rotate``,
  ``rescale``; ``rotate_by`` the rotation amount;
- ``levels``: moduli served, as steps of logp below logQ;
- ``pool``: ciphertexts (and plaintexts) made per level in set-up, which
  the requests draw their operands from;
- optional server settings the mix needs: ``max_age_s``;
- ``latency_limit_ms`` and ``grace_s`` for an open loop.

Every seed gives the same work: the same count of requests per (op,
level) bucket and, in an open loop, the same set of inter-arrival gaps,
in an order and with operands drawn from the seed.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, Iterator, List, Tuple

import numpy as np

__all__ = ["Request", "ARITY", "closed_loop", "open_loop", "bucket_list"]

ARITY = {"mul": 2, "mul_plain": 1, "rotate": 1, "rescale": 1}

# closed-loop requests are drawn in blocks with exact op counts
_BLOCK = 20


@dataclasses.dataclass(frozen=True)
class Request:
    due: float                   # seconds after the window opens
    op: str
    level: int                   # steps of logp below logQ
    operands: Tuple[int, ...]    # pool indices at that level


def _rng(seed: int, salt: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & (2**63 - 1), salt])


def _counts(mix: Dict[str, float], n: int) -> Dict[str, int]:
    """Largest-remainder split of n requests by the mix's shares."""
    for op in mix:
        if op not in ARITY:
            raise ValueError(f"traffic mixes {op!r}; known ops: "
                             f"{sorted(ARITY)}")
    total = sum(mix.values())
    raw = {op: n * s / total for op, s in mix.items()}
    out = {op: int(math.floor(v)) for op, v in raw.items()}
    left = n - sum(out.values())
    for op in sorted(raw, key=lambda o: (out[o] - raw[o], o))[:left]:
        out[op] += 1
    return out


def _ops_and_levels(traffic: dict, n: int, rng: np.random.Generator
                    ) -> List[Tuple[str, int]]:
    """n (op, level) pairs with exact per-bucket counts, in seeded order."""
    levels = traffic["levels"]
    pairs = []
    for op, c in sorted(_counts(traffic["mix"], n).items()):
        pairs += [(op, levels[k % len(levels)]) for k in range(c)]
    return [pairs[i] for i in rng.permutation(len(pairs))]


def _operands(op: str, rng: np.random.Generator, pool: int
              ) -> Tuple[int, ...]:
    return tuple(int(x) for x in rng.integers(0, pool, size=ARITY[op]))


def bucket_list(traffic: dict) -> List[Tuple[str, int]]:
    """Every (op, level) the traffic can send, in a fixed order."""
    return [(op, lv) for op in sorted(traffic["mix"])
            for lv in traffic["levels"] if traffic["mix"][op] > 0]


def closed_loop(traffic: dict, seed: int) -> Iterator[Request]:
    """Endless requests for a closed loop (all due at once)."""
    rng = _rng(seed, 1)
    while True:
        for op, lv in _ops_and_levels(traffic, _BLOCK, rng):
            yield Request(0.0, op, lv, _operands(op, rng, traffic["pool"]))


def open_loop(traffic: dict, seconds: float, seed: int) -> List[Request]:
    """Poisson arrivals over [0, seconds): round(rate·seconds) requests.

    The gaps are the n midpoint quantiles of the exponential law with
    the traffic's rate, shuffled by the seed, so every seed offers the
    same load, the same burstiness and the same count in each bucket.
    """
    rate = float(traffic["rate_per_s"])
    n = int(round(rate * seconds))
    rng = _rng(seed, 2)
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    due = np.cumsum(gaps[rng.permutation(n)])
    pairs = _ops_and_levels(traffic, n, rng)
    return [Request(float(t), op, lv, _operands(op, rng, traffic["pool"]))
            for t, (op, lv) in zip(due, pairs)]
