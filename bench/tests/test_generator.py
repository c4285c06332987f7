"""The traffic generator: what a seed decides, and what it must not."""

from __future__ import annotations

import collections
import itertools
import json
from pathlib import Path

import numpy as np
import pytest

from bench import generator as G

TRAFFIC = Path(__file__).resolve().parents[1] / "traffic"
HERE = Path(__file__).resolve().parent
SEEDS = [0, 7, 2**31 + 11, 2**40 + 3]


def _traffic(name):
    path = TRAFFIC / f"{name}.json"
    if not path.exists():
        path = HERE / f"{name}.json"
    return json.loads(path.read_text())


@pytest.mark.parametrize("seed", SEEDS)
def test_open_loop_repeats_for_a_seed(seed):
    t = _traffic("mix")
    assert G.open_loop(t, 30, seed) == G.open_loop(t, 30, seed)


def test_open_loop_seeds_share_the_work_not_the_order():
    t = _traffic("mix")
    runs = [G.open_loop(t, 30, s) for s in SEEDS]
    n = round(t["rate_per_s"] * 30)
    buckets = [collections.Counter((r.op, r.level) for r in run)
               for run in runs]
    gaps = [np.sort(np.diff([0.0] + [r.due for r in run])) for run in runs]
    for run, b, g in zip(runs, buckets, gaps):
        assert len(run) == n
        assert b == buckets[0]
        np.testing.assert_allclose(g, gaps[0])
        assert all(0 < r.due < 30 for r in run)
    assert [r.op for r in runs[0]] != [r.op for r in runs[1]]
    assert [r.due for r in runs[0]] != [r.due for r in runs[1]]


def test_open_loop_follows_the_mix_and_rate():
    t = _traffic("mix")
    run = G.open_loop(t, 30, 5)
    ops = collections.Counter(r.op for r in run)
    n = len(run)
    for op, share in t["mix"].items():
        assert abs(ops[op] - share * n) <= 1
    assert run[-1].due == pytest.approx(30, rel=0.05)
    assert {r.level for r in run} == set(t["levels"])


@pytest.mark.parametrize("seed", SEEDS)
def test_closed_loop_repeats_for_a_seed(seed):
    t = _traffic("mul_sat")
    a = list(itertools.islice(G.closed_loop(t, seed), 64))
    b = list(itertools.islice(G.closed_loop(t, seed), 64))
    assert a == b
    assert all(r.op == "mul" and r.level == 0 and len(r.operands) == 2
               and all(0 <= i < t["pool"] for i in r.operands) for r in a)


def test_closed_loop_operands_follow_the_seed():
    t = _traffic("mul_sat")
    a = [r.operands for r in itertools.islice(G.closed_loop(t, 1), 64)]
    b = [r.operands for r in itertools.islice(G.closed_loop(t, 2), 64)]
    assert a != b


def test_unknown_op_is_refused():
    with pytest.raises(ValueError, match="known ops"):
        G.open_loop({"rate_per_s": 1, "mix": {"bootstrap": 1},
                     "levels": [0], "pool": 1}, 10, 0)
