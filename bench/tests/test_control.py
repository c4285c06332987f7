"""The control at a size a test run holds: the reference one precision
lower (complex64) fails the word-for-word comparison the program
passes."""

from __future__ import annotations

import pytest

from bench import control
from bench.tests.test_loaders import tiny_cell


@pytest.mark.parametrize("traffic", ["mul_sat", "mix"])
@pytest.mark.parametrize("seed", [3, 2**31 + 9, 2**40 + 1])
def test_lower_precision_control_is_not_correct(traffic, seed):
    out = control.readings(tiny_cell(traffic), seed)
    assert out["mismatched_words"] > 0
    assert out["compared_words"] > 0
