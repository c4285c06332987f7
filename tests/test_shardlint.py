"""shardlint (repro.analysis.xla + repro.analysis.manifest) tests.

`check_cell` is pure, so the HS1xx rule logic runs on hand-crafted cell
records without compiling anything; the manifest schema/drift layer is
stdlib and exercised against the committed SHARD_MANIFEST.json; one
in-process 1x1 compile checks measure_cell's record end-to-end; and the
two acceptance behaviors — exit 0 on a clean grid, exit 1 when a bogus
ciphertext sharding rule is injected (HS101 + HS103 fire) — run on the
(2, 4) mesh via the shared run_in_8dev_subprocess harness.
"""

from __future__ import annotations

import copy
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

import repro.core  # noqa: F401
from repro.analysis.manifest import (
    MANIFEST_NAME, cell_key, diff_manifests, load_manifest,
    validate_manifest,
)
from repro.analysis.rules import RULES
from repro.analysis.xla import DEFAULT_HBM_BUDGET, check_cell
from repro.launch.mesh import make_mesh

REPO = Path(__file__).resolve().parents[1]


def _clean_cell():
    """A cell record matching its own analytic expectation — shaped like
    the committed mul/120/2x4 cell, with the per-instruction detail the
    in-memory record carries (the manifest strips it)."""
    return {
        "collectives": {
            "counts": {"all-reduce": 15},
            "bytes": {"all-reduce": 77568.0},
            "total_bytes": 77568.0,
            "ops": [],
        },
        "expected": {
            "counts": {"all-reduce": 15},
            "wire_bytes": 77568.0,
            "axis": "model",
            "allowed": {},
        },
        "group_axes": ["model"],
        "fusions": 273,
        "memory": {"argument_bytes": 42096, "output_bytes": 2064,
                   "temp_bytes": 81704, "peak_bytes": None},
        "flops": 546902.0,
    }


def _rules(diags):
    return sorted({d.rule for d in diags})


# --------------------------------------------------------------------------
# check_cell — the HS1xx rule logic, on hand-crafted records
# --------------------------------------------------------------------------

def test_check_cell_clean_cell_yields_no_findings():
    assert check_cell("mul/120/2x4", _clean_cell()) == []


def test_hs101_unexpected_collective_kind():
    cell = _clean_cell()
    cell["collectives"]["counts"]["all-gather"] = 2
    cell["collectives"]["ops"] = [
        {"op": "all-gather", "size_bytes": 4096, "group_size": 4}] * 2
    diags = check_cell("mul/120/2x4", cell)
    assert _rules(diags) == ["HS101"]
    assert all(d.severity == "error" for d in diags)
    assert "implicit resharding" in diags[0].message


def test_hs101_allowance_tolerates_bounded_evk_slice_permutes():
    cell = _clean_cell()
    cell["expected"]["allowed"] = {
        "collective-permute": {"max_count": 4, "max_bytes_each": 768}}
    cell["collectives"]["counts"]["collective-permute"] = 4
    cell["collectives"]["ops"] = [
        {"op": "collective-permute", "size_bytes": 768, "group_size": 1}] * 4
    assert check_cell("rotate/72/2x4", cell) == []
    # one permute too many -> HS101
    cell["collectives"]["counts"]["collective-permute"] = 5
    cell["collectives"]["ops"].append(
        {"op": "collective-permute", "size_bytes": 768, "group_size": 1})
    assert _rules(check_cell("rotate/72/2x4", cell)) == ["HS101"]
    # count back in bounds but one payload over the per-permute cap
    cell["collectives"]["counts"]["collective-permute"] = 4
    cell["collectives"]["ops"] = cell["collectives"]["ops"][:3] + [
        {"op": "collective-permute", "size_bytes": 769, "group_size": 1}]
    assert _rules(check_cell("rotate/72/2x4", cell)) == ["HS101"]


def test_hs102_all_reduce_bytes_drift():
    cell = _clean_cell()
    cell["collectives"]["bytes"]["all-reduce"] = 77568.0 * 1.05
    diags = check_cell("mul/120/2x4", cell)
    assert _rules(diags) == ["HS102"]
    assert "ring" in diags[0].message or "analytic" in diags[0].message
    # within the 1% tolerance -> clean
    cell["collectives"]["bytes"]["all-reduce"] = 77568.0 * 1.005
    assert check_cell("mul/120/2x4", cell) == []


def test_hs103_wrong_axis_and_count_mismatch():
    cell = _clean_cell()
    cell["group_axes"] = ["data", "model"]
    diags = check_cell("mul/120/2x4", cell)
    assert _rules(diags) == ["HS103"]
    assert "layout churn" in diags[0].message
    cell = _clean_cell()
    cell["collectives"]["counts"]["all-reduce"] = 12
    cell["collectives"]["bytes"]["all-reduce"] = 77568.0  # bytes kept equal
    diags = check_cell("mul/120/2x4", cell)
    assert _rules(diags) == ["HS103"]
    assert "exactly 15" in diags[0].message


def test_hs104_peak_memory_budget_and_cpu_fallback():
    cell = _clean_cell()
    # peak_bytes is None on CPU: the fallback sums argument+output+temp
    fallback = 42096 + 2064 + 81704
    diags = check_cell("mul/120/2x4", cell, hbm_budget=fallback - 1)
    assert _rules(diags) == ["HS104"]
    assert check_cell("mul/120/2x4", cell, hbm_budget=fallback) == []
    # an explicit backend peak wins over the fallback
    cell["memory"]["peak_bytes"] = 10 * fallback
    assert _rules(check_cell("m", cell,
                             hbm_budget=DEFAULT_HBM_BUDGET)) == []
    assert _rules(check_cell("m", cell, hbm_budget=fallback)) == ["HS104"]


def test_hs105_fusion_drift_is_a_warning():
    cell = _clean_cell()
    diags = check_cell("mul/120/2x4", cell, baseline_fusions=100)
    assert _rules(diags) == ["HS105"]
    assert diags[0].severity == "warning"
    # warnings don't gate: run_shardlint counts only errors
    assert check_cell("mul/120/2x4", cell, baseline_fusions=273) == []
    assert check_cell("mul/120/2x4", cell, baseline_fusions=250) == []


def test_hs1xx_rules_are_registered_in_the_catalog():
    for rid, sev in [("HS101", "error"), ("HS102", "error"),
                     ("HS103", "error"), ("HS104", "error"),
                     ("HS105", "warning")]:
        assert rid in RULES and RULES[rid].severity == sev
        assert RULES[rid].check is None     # emitted by the xla pass


# --------------------------------------------------------------------------
# manifest schema + drift diff (stdlib), against the committed file
# --------------------------------------------------------------------------

def test_committed_manifest_validates_and_selfdiffs_clean():
    obj = load_manifest(REPO / MANIFEST_NAME)
    assert validate_manifest(obj) == []
    assert diff_manifests(obj, copy.deepcopy(obj)) == []
    # both meshes, every level, and the full op table are covered
    assert obj["meshes"] == {"1x1": [1, 1], "2x4": [2, 4]}
    from repro.launch.cells import HE_SERVING_OPS
    for op in HE_SERVING_OPS:
        # mod_raise has no headroom at the top of the chain — its grid
        # starts one level down (serving_op_levels); check the bottom
        lq = obj["levels"][-1] if op == "mod_raise" else obj["levels"][0]
        assert cell_key(op, lq, "2x4") in obj["cells"], op


def test_validate_manifest_catches_schema_violations():
    obj = load_manifest(REPO / MANIFEST_NAME)
    bad = copy.deepcopy(obj)
    del bad["params"]["logN"]
    bad["batch"] = "two"
    key = next(iter(bad["cells"]))
    del bad["cells"][key]["fusions"]
    errs = "\n".join(validate_manifest(bad))
    assert "params: missing key 'logN'" in errs
    assert ".batch: expected int" in errs
    assert f"cells[{key}]: missing key 'fusions'" in errs
    empty = copy.deepcopy(obj)
    empty["cells"] = {}
    assert any("empty" in e for e in validate_manifest(empty))


def test_diff_manifests_flags_every_drift_class():
    old = load_manifest(REPO / MANIFEST_NAME)
    new = copy.deepcopy(old)
    k_mul = cell_key("mul", 120, "2x4")
    k_add = cell_key("add", 120, "1x1")
    new["cells"][k_mul]["collectives"]["counts"]["all-reduce"] += 3
    new["cells"][k_mul]["collectives"]["total_bytes"] *= 1.5
    new["cells"][k_mul]["fusions"] = 10
    new["cells"][k_add]["group_axes"] = ["data"]
    del new["cells"][cell_key("sub", 24, "1x1")]
    new["cells"]["bootstrap/120/2x4"] = new["cells"][k_add]
    errs = diff_manifests(old, new)
    text = "\n".join(errs)
    assert f"cells[{k_mul}]: all-reduce count" in text
    assert f"cells[{k_mul}]: wire bytes" in text
    assert f"cells[{k_mul}]: fused-kernel count" in text
    assert f"cells[{k_add}]: replica-group axes" in text
    assert "cells[sub/24/1x1]: in the committed manifest but not" in text
    assert "cells[bootstrap/120/2x4]: measured but not in" in text
    assert len(errs) == 6


def test_diff_manifests_tolerances_come_from_the_committed_side():
    old = load_manifest(REPO / MANIFEST_NAME)
    new = copy.deepcopy(old)
    k = cell_key("mul", 120, "2x4")
    new["cells"][k]["collectives"]["total_bytes"] *= 1.05
    assert diff_manifests(old, new)             # 5% > default 1%
    loose = copy.deepcopy(old)
    loose["tolerances"]["bytes_rtol"] = 0.10    # the reviewed contract
    assert diff_manifests(loose, new) == []


# --------------------------------------------------------------------------
# measure_cell in-process (1-dev mesh) + the CLI acceptance behaviors
# --------------------------------------------------------------------------

def test_measure_cell_single_device_record_and_clean_check():
    import jax

    from repro.analysis.xla import measure_cell
    from repro.core.params import test_params

    params = test_params(logN=4, beta_bits=32)
    mesh = make_mesh((1, 1), ("data", "model"))
    cell = measure_cell("mul", params.logQ, mesh, params, 2)
    # one device: nothing on the wire, predicted and measured alike
    assert cell["collectives"]["counts"] == {}
    assert cell["collectives"]["total_bytes"] == 0.0
    assert cell["expected"]["counts"] == {}
    assert cell["group_axes"] == []
    assert cell["fusions"] > 0
    assert check_cell("mul/120/1x1", cell) == []


def test_shardlint_wrapper_help_runs_without_jax():
    out = subprocess.run(
        [sys.executable, str(REPO / "tools" / "shardlint.py"), "--help"],
        capture_output=True, text=True, timeout=120,
        env={**os.environ, "PYTHONPATH": str(REPO / "src")})
    assert out.returncode == 0, out.stderr[-2000:]
    assert "shardlint" in out.stdout and "--inject" in out.stdout


def test_shardlint_cli_clean_and_injected_on_8_device_mesh(
        run_in_8dev_subprocess):
    """The acceptance pair in one interpreter: a clean focused grid on
    the (2, 4) mesh exits 0 with the collective schedule matching the
    analytic prediction, and the same grid with the bogus ciphertext
    sharding injected exits 1 with HS101 (unpredicted collectives) and
    HS103 (replica groups on the wrong mesh axis) among the findings."""
    res = run_in_8dev_subprocess("""
        import contextlib, io
        from repro.analysis.xla import main as xla_main

        def run(extra):
            argv = ["--json", "--logn", "4", "--levels", "120",
                    "--meshes", "2x4", "--ops", "mul,rotate,add",
                    "--manifest", "/tmp/_no_such_manifest.json"] + extra
            buf = io.StringIO()
            with contextlib.redirect_stdout(buf):
                rc = xla_main(argv)
            return rc, json.loads(buf.getvalue().strip().splitlines()[-1])

        rc_ok, rep_ok = run([])
        rc_bad, rep_bad = run(["--inject", "bogus-ct-sharding"])
        mul = rep_ok["cells"]["mul/120/2x4"]
        print(json.dumps({
            "rc_ok": rc_ok, "errors_ok": rep_ok["errors"],
            "cells_ok": sorted(rep_ok["cells"]),
            "ar_mul": mul["collectives"]["counts"].get("all-reduce"),
            "bytes_match": mul["collectives"]["total_bytes"]
                == mul["expected"]["wire_bytes"],
            "rc_bad": rc_bad, "errors_bad": rep_bad["errors"],
            "rules_bad": sorted({d["rule"]
                                 for d in rep_bad["diagnostics"]}),
        }))
    """)
    assert res["rc_ok"] == 0 and res["errors_ok"] == 0
    assert res["cells_ok"] == ["add/120/2x4", "mul/120/2x4",
                               "rotate/120/2x4"]
    # mul at full depth: (3 + 2) iCRT reductions x 1 all-reduced tensor
    # (the byte-piece GEMM's partial sums) each, and the measured
    # ring-model bytes equal the analytic prediction
    assert res["ar_mul"] == 5
    assert res["bytes_match"]
    assert res["rc_bad"] == 1 and res["errors_bad"] >= 2
    assert "HS101" in res["rules_bad"]
    assert "HS103" in res["rules_bad"]


def test_run_shardlint_rejects_unknown_op_and_injection():
    from repro.analysis.xla import run_shardlint
    with pytest.raises(ValueError, match="unknown serving op"):
        run_shardlint(ops=("bootstrap",), meshes={"1x1": (1, 1)})
    with pytest.raises(ValueError, match="unknown injection"):
        run_shardlint(inject="flip-bits", meshes={"1x1": (1, 1)})
