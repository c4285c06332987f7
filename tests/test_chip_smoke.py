"""chip_smoke.py off the chip: its serve-and-compare path at SMOKE params
on the CPU backend, and its refusal to run without a TPU."""

from __future__ import annotations

import importlib.util
from pathlib import Path

import pytest

from repro.configs.heaan_mul import SMOKE

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", REPO / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_serve_and_compare_is_bitwise_core_at_smoke(chip_smoke):
    stats = chip_smoke.serve_and_compare(SMOKE, batch=2)
    # mul at two levels, mul_plain, rotate, rescale at two levels
    assert stats["bitwise_checked"] == 6
    assert set(stats["per_op"]) == {"mul", "mul_plain", "rotate",
                                    "rescale"}
    assert stats["max_err"] < 1e-2


def test_main_refuses_without_a_tpu(chip_smoke, capsys):
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr()
    assert "no TPU" in out.err
    assert '"ok"' not in out.out
