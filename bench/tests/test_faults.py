"""A run with the timed path broken underneath must come out not
correct: once per fault a one-chip HE cell can have.

The harness's look for a chip is skipped (``require_tpu=False``); the
rest of a run (set-up, window, sampling, reference check) runs at the
toy parameters of tiny.json on the CPU. The fault is planted in
`OpEngine.wait`, where the served answers come from. The exchange
between chips is not among the faults: every cell runs on one chip.
"""

from __future__ import annotations

import dataclasses

import numpy as np
import pytest

from bench import run as R
from bench.tests.test_loaders import tiny_cell


def _unchanged(outs, inf):
    """The step returns its input: each answer is its first operand."""
    a = inf.batch.arrays
    return [dataclasses.replace(o, ax=a["ax1"][i][:, :o.ax.shape[-1]],
                                bx=a["bx1"][i][:, :o.bx.shape[-1]])
            for i, o in enumerate(outs)]


def _half_batch(outs, inf):
    """Only the first half of the batch's answers is computed; the rest
    are left as zeros."""
    h = -(-len(outs) // 2)
    return [o if i < h else dataclasses.replace(
        o, ax=np.zeros_like(np.asarray(o.ax)),
        bx=np.zeros_like(np.asarray(o.bx))) for i, o in enumerate(outs)]


def _altered(outs, inf):
    """Every answer has one bit flipped where it is produced."""
    out = []
    for o in outs:
        ax = np.array(o.ax)
        ax[0, 0] ^= 1
        out.append(dataclasses.replace(o, ax=ax))
    return out


FAULTS = {"unchanged_state": _unchanged, "half_batch": _half_batch,
          "altered_answer": _altered}


def _plant(monkeypatch, fault):
    from repro.hserve.engine import OpEngine
    wait = OpEngine.wait

    def broken(self, inf):
        outs, wall = wait(self, inf)
        return fault(outs, inf), wall

    monkeypatch.setattr(OpEngine, "wait", broken)


@pytest.mark.parametrize("traffic", ["mul_sat", "mix"])
@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_broken_path_is_not_correct(monkeypatch, traffic, fault):
    _plant(monkeypatch, FAULTS[fault])
    # the mix at a load whose batches hold several answers
    over = {"rate_per_s": 40.0, "max_age_s": 0.5} \
        if traffic == "mix" else {}
    res = R.run_cell(tiny_cell(traffic, **over), 2**33 + 1, 3.0, False,
                     require_tpu=False)
    assert not res["correct"]
    assert res["checks"]["mismatched_words"]["value"] > 0


def test_the_unbroken_mix_is_correct():
    res = R.run_cell(tiny_cell("mix", rate_per_s=40.0,
                               max_age_s=0.5), 2**33 + 1, 3.0, False,
                     require_tpu=False)
    assert res["correct"], res["checks"]
    assert res["checks"]["unchecked_buckets"]["value"] == 0
