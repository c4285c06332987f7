"""shardlint — static HLO/collective analysis of the compiled serving
engines (`python -m repro.analysis.xla`, or `tools/shardlint.py` which
forces an 8-device host so both meshes exist).

hslint (HS001–HS006) checks circuits before they run; this pass checks
what the COMPILED programs will do on the wire. For every served op in
`analysis.dataflow.OPS`/`PLAIN_OPS`, at each level, on the 1-dev and
(2,4) meshes, it lowers the exact engine step via
`launch.cells.lower_he_serving_cell` (abstract `he_table_specs` tables —
no twiddle build, milliseconds per cell), statically parses the
optimized HLO with `launch.hlo_analysis`, and compares against the
analytic prediction `dist.sharding.he_expected_collectives` derives
from the paper's Fig. 2 dataflow (only iCRT's cross-prime accumulation
communicates: one all-reduced tensor over model-axis groups per reduction).

Findings ship as the HS1xx rule series through the hslint Diagnostic
machinery:

  HS101  unexpected-collective   error   a collective kind the sharding
         rules never predict for this cell (implicit resharding);
  HS102  collective-bytes-drift  error   measured all-reduce wire bytes
         off the analytic ring-model prediction beyond tolerance;
  HS103  layout-churn            error   replica groups on the wrong
         mesh axis, or a collective count off the predicted schedule;
  HS104  peak-memory-over-budget error   backend peak-live-buffer
         estimate above the per-device HBM budget;
  HS105  fusion-break            warning fused-kernel count drifted
         from the committed SHARD_MANIFEST.json baseline.

Measured-vs-expected numbers are written to SHARD_MANIFEST.json
(`--write`); `tools/check_docs.py --shard-manifest` drift-gates a fresh
measurement against the committed file in CI. jax is imported lazily so
`import repro.analysis` stays light.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path
from typing import Any, Dict, List, Optional, Tuple

from repro.analysis.manifest import (
    DEFAULT_TOLERANCES, MANIFEST_NAME, SCHEMA_VERSION, cell_key,
    load_manifest, validate_manifest,
)
from repro.analysis.rules import Diagnostic

__all__ = ["DEFAULT_HBM_BUDGET", "DEFAULT_MESHES", "measure_cell",
           "check_cell", "run_shardlint", "main"]

# per-device budget the peak-live-buffer estimate is gated against; the
# manifest params are tiny, so the default only catches runaway
# materialization (a real deployment passes its device's HBM)
DEFAULT_HBM_BUDGET = 1 << 30

DEFAULT_MESHES: Dict[str, Tuple[int, int]] = {"1x1": (1, 1), "2x4": (2, 4)}
DEFAULT_LEVELS = (120, 72, 24)
_INJECTIONS = ("bogus-ct-sharding",)


def _make_mesh(shape: Tuple[int, int]) -> Any:
    import jax
    import numpy as np
    from jax.sharding import Mesh
    n = shape[0] * shape[1]
    devs = jax.devices()
    if len(devs) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices, have {len(devs)} — run via "
            "tools/shardlint.py (it forces an 8-device host before jax "
            "loads) or set XLA_FLAGS=--xla_force_host_platform_"
            "device_count=8")
    return Mesh(np.array(devs[:n]).reshape(shape), ("data", "model"))


def _bogus_ct_sharding(mesh: Any) -> Any:
    """A deliberately wrong ciphertext placement — the ring dimension N
    on "data" with the batch replicated, violating every rule in
    `dist.sharding` (batch-on-data, N local) — used by the injected-
    regression test to prove HS101 (unpredicted all-gathers) and HS103
    (replica groups over the wrong mesh axis) actually fire."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    return NamedSharding(mesh, P(None, "data"))


def _classify_groups(ops: List[Dict[str, Any]],
                     axis_groups: Dict[str, List[Tuple[int, ...]]]
                     ) -> List[str]:
    """Mesh-axis names the measured replica groups run over ("?" for a
    group set matching no single axis — the layout-churn signal)."""
    axes = set()
    for op in ops:
        if op["op"] == "collective-permute" or op["group_size"] <= 1:
            continue
        groups = op.get("groups")
        if groups is None:
            axes.add("?")
            continue
        gg = sorted(tuple(g) for g in groups)
        for name, agroups in axis_groups.items():
            if gg == agroups:
                axes.add(name)
                break
        else:
            axes.add("?")
    return sorted(axes)


def measure_cell(op: str, logq: int, mesh: Any, params: Any, batch: int, *,
                 n_slots: Optional[int] = None,
                 ct_sharding: Optional[Any] = None) -> Dict[str, Any]:
    """Lower + compile one serving cell and statically analyze its HLO.

    Returns the manifest cell record: collective schedule (per-kind
    counts / ring-model wire bytes / per-instruction detail), replica-
    group axis classification, analytic expectation, fused-kernel count,
    and the backend memory estimate.
    """
    import time
    from repro.dist.sharding import (
        he_expected_collectives, mesh_collective_groups,
    )
    from repro.launch.cells import lower_he_serving_cell
    from repro.launch.hlo_analysis import analyze_compiled
    t0 = time.time()
    lowered = lower_he_serving_cell(op, batch, mesh, logq=logq,
                                    params=params, n_slots=n_slots,
                                    ct_sharding=ct_sharding)
    rec: Dict[str, Any] = analyze_compiled(lowered, lowered.compile(),
                                           time.time() - t0)
    coll = rec["collectives"]
    expected = he_expected_collectives(op, mesh, params, logq, batch=batch,
                                       n_slots=n_slots)
    axis_groups = {str(k): [tuple(g) for g in v]
                   for k, v in mesh_collective_groups(mesh).items()}
    return {
        "collectives": {
            "counts": {k: v for k, v in coll["counts"].items() if v},
            "bytes": {k: round(v, 1) for k, v in coll["bytes"].items()
                      if v},
            "total_bytes": round(float(coll["total_bytes"]), 1),
            "ops": coll["ops"],
        },
        "expected": {
            "counts": dict(expected["counts"]),
            "wire_bytes": round(float(expected["wire_bytes"]), 1),
            "axis": expected["axis"],
            "allowed": expected["allowed"],
        },
        "group_axes": _classify_groups(coll["ops"], axis_groups),
        "fusions": int(rec["fusions"]),
        "memory": rec["memory"],
        "flops": rec["flops"],
    }


def _peak_estimate(memory: Dict[str, Any]) -> Optional[int]:
    """Backend peak bytes, falling back to arguments+output+temps where
    the backend reports no peak (CPU)."""
    peak = memory.get("peak_bytes")
    if isinstance(peak, int):
        return peak
    parts = [memory.get(k) for k in
             ("argument_bytes", "output_bytes", "temp_bytes")]
    known = [p for p in parts if isinstance(p, int)]
    return sum(known) if known else None


def check_cell(key: str, cell: Dict[str, Any], *,
               tolerances: Optional[Dict[str, float]] = None,
               hbm_budget: int = DEFAULT_HBM_BUDGET,
               baseline_fusions: Optional[int] = None
               ) -> List[Diagnostic]:
    """HS1xx findings for one measured cell vs its analytic expectation
    (and, for HS105, the committed manifest's fusion baseline)."""
    tol = dict(DEFAULT_TOLERANCES)
    tol.update(tolerances or {})
    diags: List[Diagnostic] = []
    meas = cell["collectives"]
    exp = cell["expected"]
    allowed = exp.get("allowed") or {}

    # HS101 — collective kinds the sharding rules never predict here
    for kind, count in sorted(meas["counts"].items()):
        if not count or kind in exp["counts"]:
            continue
        allow = allowed.get(kind)
        if allow is not None:
            over = [o for o in meas["ops"] if o["op"] == kind
                    and o["size_bytes"] > allow["max_bytes_each"]]
            if count <= allow["max_count"] and not over:
                continue            # the tolerated evk-slice permutes
        diags.append(Diagnostic(
            "HS101", "error",
            f"{key}: {count} {kind} instruction(s) the sharding rules "
            f"never predict for this cell — an implicit resharding "
            f"crept into the lowered HLO"))

    # HS102 — all-reduce wire bytes off the analytic ring model
    meas_ar = float(meas["bytes"].get("all-reduce", 0.0))
    exp_b = float(exp["wire_bytes"])
    drift = abs(meas_ar - exp_b) / max(meas_ar, exp_b, 1.0)
    if drift > tol["expected_rtol"]:
        diags.append(Diagnostic(
            "HS102", "error",
            f"{key}: all-reduce wire bytes {meas_ar:.0f} vs analytic "
            f"{exp_b:.0f} (drift {drift:.1%} > "
            f"{tol['expected_rtol']:.1%}) — the iCRT reduction "
            f"schedule no longer matches Fig. 2"))

    # HS103 — groups on the wrong mesh axis / schedule shape changed
    bad_axes = [a for a in cell["group_axes"] if a != exp["axis"]]
    if bad_axes:
        diags.append(Diagnostic(
            "HS103", "error",
            f"{key}: replica groups run over {bad_axes} where the "
            f"sharding rules predict only {exp['axis']!r}-axis "
            f"reductions — layout churn"))
    for kind, want in sorted(exp["counts"].items()):
        got = meas["counts"].get(kind, 0)
        if got != want:
            diags.append(Diagnostic(
                "HS103", "error",
                f"{key}: {got} {kind}(s) where the dataflow predicts "
                f"exactly {want} — the collective schedule changed "
                f"shape"))

    # HS104 — peak live buffers vs the HBM budget
    peak = _peak_estimate(cell["memory"])
    if peak is not None and peak > hbm_budget:
        diags.append(Diagnostic(
            "HS104", "error",
            f"{key}: peak-live-buffer estimate {peak} bytes exceeds "
            f"the {hbm_budget}-byte per-device HBM budget"))

    # HS105 — fused-kernel count drifted from the committed baseline
    if baseline_fusions is not None:
        got_f = int(cell["fusions"])
        fdrift = abs(got_f - baseline_fusions) / max(
            got_f, baseline_fusions, 1)
        if fdrift > tol["fusion_rtol"]:
            diags.append(Diagnostic(
                "HS105", "warning",
                f"{key}: fused-kernel count {got_f} vs the committed "
                f"baseline {baseline_fusions} (drift {fdrift:.0%} > "
                f"{tol['fusion_rtol']:.0%}) — XLA broke or merged "
                f"fusions; regenerate SHARD_MANIFEST.json if intended"))
    return diags


def run_shardlint(*, params: Any = None, batch: int = 2,
                  levels: Tuple[int, ...] = DEFAULT_LEVELS,
                  meshes: Optional[Dict[str, Tuple[int, int]]] = None,
                  ops: Optional[Tuple[str, ...]] = None,
                  hbm_budget: int = DEFAULT_HBM_BUDGET,
                  tolerances: Optional[Dict[str, float]] = None,
                  manifest: Optional[Dict[str, Any]] = None,
                  inject: Optional[str] = None) -> Dict[str, Any]:
    """Measure + check every (op, level, mesh) cell.

    Returns {"manifest": fresh manifest dict, "diagnostics": [...],
    "errors": n}. `manifest` (the committed one) supplies the HS105
    fusion baselines; `ops` restricts to a subset of the served table
    (a focused run — the resulting manifest is partial and must not be
    committed); `inject` forces a named regression (`bogus-ct-sharding`)
    for the CI self-test.
    """
    from repro.core.params import test_params
    from repro.launch.cells import HE_SERVING_OPS, serving_op_levels
    if params is None:
        params = test_params(logN=6, beta_bits=32, logQ=120, logp=24)
    if meshes is None:
        meshes = dict(DEFAULT_MESHES)
    if ops is None:
        ops = HE_SERVING_OPS
    else:
        unknown = sorted(set(ops) - set(HE_SERVING_OPS))
        if unknown:
            raise ValueError(f"unknown serving op(s) {unknown}; "
                             f"the served table is {HE_SERVING_OPS}")
    if inject is not None and inject not in _INJECTIONS:
        raise ValueError(f"unknown injection {inject!r}; "
                         f"one of {_INJECTIONS}")
    tol = dict(DEFAULT_TOLERANCES)
    tol.update(tolerances or {})
    # HS105 fusion baselines only make sense when the committed manifest
    # was measured at the SAME parameters (cell keys carry op/level/mesh
    # but not logN/batch)
    base_cells: Dict[str, Any] = {}
    if manifest and manifest.get("batch") == batch \
            and manifest.get("params") == {
                "logN": params.logN, "logQ": params.logQ,
                "logp": params.logp, "beta_bits": params.beta_bits}:
        base_cells = manifest.get("cells") or {}
    cells: Dict[str, Dict[str, Any]] = {}
    diags: List[Diagnostic] = []
    for mesh_name, shape in meshes.items():
        mesh = _make_mesh(shape)
        ct_sh = _bogus_ct_sharding(mesh) \
            if inject == "bogus-ct-sharding" else None
        for op in ops:
            for logq in serving_op_levels(op, list(levels), params):
                key = cell_key(op, int(logq), mesh_name)
                cell = measure_cell(op, int(logq), mesh, params, batch,
                                    ct_sharding=ct_sh)
                base = base_cells.get(key) or {}
                baseline_f = base.get("fusions") \
                    if isinstance(base.get("fusions"), int) else None
                diags += check_cell(key, cell, tolerances=tol,
                                    hbm_budget=hbm_budget,
                                    baseline_fusions=baseline_f)
                cell = dict(cell)
                coll = dict(cell["collectives"])
                coll.pop("ops", None)      # per-instruction detail is
                cell["collectives"] = coll  # too volatile to commit
                cell["expected"] = {
                    "counts": cell["expected"]["counts"],
                    "wire_bytes": cell["expected"]["wire_bytes"],
                }
                cells[key] = cell
    fresh: Dict[str, Any] = {
        "schema": SCHEMA_VERSION,
        "params": {"logN": params.logN, "logQ": params.logQ,
                   "logp": params.logp, "beta_bits": params.beta_bits},
        "batch": batch,
        "levels": sorted(set(int(x) for x in levels), reverse=True),
        "meshes": {k: list(v) for k, v in meshes.items()},
        "tolerances": tol,
        "hbm_budget_bytes": hbm_budget,
        "cells": cells,
    }
    return {"manifest": fresh, "diagnostics": diags,
            "errors": sum(1 for d in diags if d.severity == "error")}


def _parse_meshes(text: str) -> Dict[str, Tuple[int, int]]:
    out: Dict[str, Tuple[int, int]] = {}
    for part in text.split(","):
        part = part.strip()
        d, m = part.split("x")
        out[part] = (int(d), int(m))
    return out


def main(argv: Optional[List[str]] = None) -> int:
    import os
    if "jax" not in sys.modules:        # both meshes need 8 host devices
        os.environ.setdefault(
            "XLA_FLAGS", "--xla_force_host_platform_device_count=8")
    ap = argparse.ArgumentParser(
        prog="shardlint", description=__doc__.splitlines()[0])
    ap.add_argument("--json", action="store_true",
                    help="machine-readable report on stdout")
    ap.add_argument("--write", action="store_true",
                    help="(re)generate the manifest at --manifest")
    ap.add_argument("--out", default=None, type=Path,
                    help="also write the fresh measurement JSON here "
                         "(check_docs --shard-manifest compares it "
                         "against the committed manifest)")
    ap.add_argument("--manifest", default=None, type=Path,
                    help=f"committed manifest path (default: "
                         f"{MANIFEST_NAME} next to the repo's "
                         f"tools/ dir, else cwd)")
    ap.add_argument("--levels", default=None,
                    help="comma-separated logq levels (default "
                         f"{','.join(map(str, DEFAULT_LEVELS))})")
    ap.add_argument("--meshes", default=None,
                    help="comma-separated DxM meshes (default 1x1,2x4)")
    ap.add_argument("--ops", default=None,
                    help="comma-separated subset of served ops (default: "
                         "the full table; a subset run's manifest is "
                         "partial — don't commit it)")
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--logn", type=int, default=6)
    ap.add_argument("--logq-max", type=int, default=120,
                    help="logQ of the parameter set")
    ap.add_argument("--logp", type=int, default=24)
    ap.add_argument("--hbm-budget", type=int, default=DEFAULT_HBM_BUDGET,
                    help="per-device peak-live-buffer budget in bytes "
                         "(HS104)")
    ap.add_argument("--inject", default=None, choices=_INJECTIONS,
                    help="force a named regression (CI self-test: "
                         "shardlint must exit 1 on it)")
    args = ap.parse_args(argv)

    from repro.core.params import test_params
    params = test_params(logN=args.logn, beta_bits=32,
                         logQ=args.logq_max, logp=args.logp)
    levels = tuple(int(x) for x in args.levels.split(",")) \
        if args.levels else DEFAULT_LEVELS
    meshes = _parse_meshes(args.meshes) if args.meshes else None

    manifest_path = args.manifest
    if manifest_path is None:
        for cand in (Path(__file__).resolve().parents[3] / MANIFEST_NAME,
                     Path.cwd() / MANIFEST_NAME):
            if cand.exists():
                manifest_path = cand
                break
        else:
            manifest_path = Path.cwd() / MANIFEST_NAME
    committed: Optional[Dict[str, Any]] = None
    if manifest_path.exists() and not args.write:
        committed = load_manifest(manifest_path)
        for err in validate_manifest(committed, manifest_path.name):
            print(f"shardlint: {err}", file=sys.stderr)

    ops = tuple(x.strip() for x in args.ops.split(",") if x.strip()) \
        if args.ops else None
    report = run_shardlint(params=params, batch=args.batch, levels=levels,
                           meshes=meshes, ops=ops,
                           hbm_budget=args.hbm_budget,
                           manifest=committed, inject=args.inject)
    fresh, diags = report["manifest"], report["diagnostics"]

    if args.write:
        manifest_path.write_text(json.dumps(fresh, indent=1,
                                            sort_keys=True) + "\n")
        print(f"shardlint: wrote {len(fresh['cells'])} cells to "
              f"{manifest_path}", file=sys.stderr)
    if args.out is not None:
        args.out.write_text(json.dumps(fresh, indent=1, sort_keys=True)
                            + "\n")

    if args.json:
        print(json.dumps({
            "cells": fresh["cells"],
            "diagnostics": [vars(d) for d in diags],
            "errors": report["errors"],
        }, sort_keys=True))
    else:
        for d in diags:
            print(d.format())
        print(f"shardlint: {len(fresh['cells'])} cells, "
              f"{report['errors']} error(s), "
              f"{sum(1 for d in diags if d.severity == 'warning')} "
              f"warning(s)")
    return 1 if report["errors"] else 0


if __name__ == "__main__":
    sys.exit(main())
