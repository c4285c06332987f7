"""The harness's loaders, its contract file, and its refusals."""

from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench import run as R

ROOT = Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
# every reader under bench/metrics, listed in BENCHMARK.json or not
METRICS = sorted(p.stem for p in (ROOT / "bench" / "metrics").glob("*.py"))


def test_benchmark_file_keeps_its_shape():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["file"].startswith("bench/")
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert len(w["why"]) <= 200
    layers = set()
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert set(m) - {"workloads"} <= {"name", "unit", "better",
                                          "bound", "source", "layer",
                                          "moves"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in SPEC["per_layer"]:
        layers.add(m["layer"])
        assert m["moves"] in {e["name"] for e in SPEC["end_to_end"]}
    assert any(m["name"] == "setup_s" for m in SPEC["end_to_end"])


@pytest.mark.parametrize("name", [w["name"] for w in SPEC["workloads"]])
def test_every_cell_loads(name):
    cell = R.load_cell(name, ROOT)
    params = R.make_params(cell["config"])
    assert params.logQ % params.logp == 0
    assert cell["config"]["reduced"] == []
    names = {m["name"] for m in cell["end_to_end"]}
    assert "setup_s" in names and len(names) >= 2
    assert cell["per_layer"]
    for m in cell["per_layer"]:
        assert callable(R.metric_reader(m["name"], ROOT))


def test_unknown_cell_is_refused():
    with pytest.raises(KeyError):
        R.load_cell("no_such_cell", ROOT)


def _record():
    return {"window_s": 10.0, "batch": 8,
            "batches": [("mul", 0, 8, 1.0), ("mul", 0, 6, 9.0),
                        ("mul", 0, 8, 11.0)],
            "serve": {"per_op": {"mul": {"batches": 20, "wall_s": 8.0}}},
            "latencies_s": [], "due_by_rid": {1: 0.0, 2: 1.0},
            "lifecycle": {1: 0.5, 2: 1.25}, "trace": None}


def test_metric_readers_read_a_record():
    rec = _record()
    read = {name: R.metric_reader(name, ROOT) for name in METRICS}
    assert read["engine.mul_batch_ms.sat"](rec) == pytest.approx(400.0)
    assert read["engine.host_ms_per_batch.sat"](rec) == pytest.approx(100.0)
    assert read["queue.wait_ms_p95.paced"](rec) == pytest.approx(500.0)
    # the batch after the window does not count
    assert read["queue.pad_frac.paced"](rec) == pytest.approx(2 / 16)


def test_metric_readers_return_nothing_without_data():
    empty = {"window_s": 10.0, "batch": 8, "batches": [],
             "serve": {"per_op": {}}, "latencies_s": [], "due_by_rid": {},
             "lifecycle": None, "trace": None}
    for name in METRICS:
        assert R.metric_reader(name, ROOT)(empty) is None


def _run(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py", *args],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=300)


def test_refuses_without_a_tpu():
    p = _run(ROOT, "--workload", "t3_mul_sat", "--seed", "5",
             "--seconds", "1", "--trace", "0")
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


def test_refuses_in_a_bare_checkout(tmp_path):
    """Only BENCHMARK.json and bench/: there is no program to measure."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path, "--workload", "t3_mul_sat", "--seed", "5",
             "--seconds", "1", "--trace", "0")
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


def test_tiny_cell_runs_correct_on_cpu():
    """The whole run on the CPU at a toy size: window, reference check."""
    res = R.run_cell(tiny_cell("mul_sat"), 2**31 + 5, 2.0, False,
                     require_tpu=False)
    assert res["correct"], res["checks"]
    assert res["metrics"]["ops_per_s"]["value"] > 0
    assert res["device"]["count"] == 1


def tiny_cell(traffic: str, **over) -> dict:
    """The t3_mul_sat cell with the toy CKKS parameters of tiny.json, and
    either its own traffic ("mul_sat") or the open-loop mix of mix.json
    ("mix"); CPU tests only."""
    here = Path(__file__).parent
    cell = R.load_cell("t3_mul_sat", ROOT)
    cell["config"] = json.loads((here / "tiny.json").read_text())
    if traffic == "mix":
        cell["traffic"] = json.loads((here / "mix.json").read_text())
    cell["traffic"] = dict(cell["traffic"], **over)
    return cell
