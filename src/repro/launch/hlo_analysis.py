"""Side-effect-free HLO analysis helpers (no jax import, no env mutation).

`launch.dryrun` sets XLA_FLAGS for 512 placeholder devices at module
import, which poisons any process that merely wants the HLO parsers —
so those parsers live here and dryrun re-exports them. Import this
module from tests and benchmarks, never dryrun.

Replica-group grammar (all forms newer XLA emits are handled):

  replica_groups={{0,1,2,3},{4,5,6,7}}     literal multi-group lists
  replica_groups=[2,4]<=[8]                iota form: 2 groups of 4,
                                           iota(8) reshaped to (2,4)
  replica_groups=[2,4]<=[4,2]T(1,0)        iota + transpose: groups are
                                           the COLUMNS of iota(8)->(4,2)
  replica_groups={}                        one group of every participant
"""

from __future__ import annotations

import re
from typing import Dict, List, Optional, Tuple

__all__ = ["collective_bytes_from_hlo", "analyze_compiled",
           "parse_replica_groups", "count_fusions"]

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")
_SHAPE_RE = re.compile(r"\b([a-z0-9]+)\[([0-9,]*)\]")
_OP_RE = re.compile(r"=\s*(\([^)]*\)|\S+)\s+([a-z0-9\-]+)\(")
_GROUPS_LITERAL_RE = re.compile(
    r"replica_groups=\{(\{[0-9, ]*\}(?:,\s*\{[0-9, ]*\})*)\}")
_GROUPS_EMPTY_RE = re.compile(r"replica_groups=\{\s*\}")
_GROUPS_IOTA_RE = re.compile(
    r"replica_groups=\[(\d+),(\d+)\]<=\[([0-9,]+)\]"
    r"(?:T\(([0-9,]+)\))?")
_FUSION_KIND_RE = re.compile(r"kind=k(\w+)")
_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}


def _base_collective(op: str) -> Tuple[str, str]:
    for suf in ("-start", "-done"):
        if op.endswith(suf):
            return op[: -len(suf)], suf
    return op, ""


def _expand_iota(n_groups: int, group_size: int, dims: List[int],
                 perm: Optional[List[int]]) -> Optional[List[Tuple[int, ...]]]:
    """Materialize `[G,S]<=[d0,d1,...]T(perm)` into explicit id groups:
    iota(prod dims) reshaped to dims, transposed by perm, reshaped (G,S)."""
    total = 1
    for d in dims:
        total *= d
    if total != n_groups * group_size or total == 0:
        return None
    if perm is None:
        perm = list(range(len(dims)))
    if sorted(perm) != list(range(len(dims))):
        return None
    strides = [1] * len(dims)                      # row-major source strides
    for i in range(len(dims) - 2, -1, -1):
        strides[i] = strides[i + 1] * dims[i + 1]
    tdims = [dims[p] for p in perm]
    vals = []
    for flat in range(total):
        rem, idx = flat, [0] * len(tdims)
        for j in range(len(tdims) - 1, -1, -1):    # flat -> transposed index
            idx[j] = rem % tdims[j]
            rem //= tdims[j]
        vals.append(sum(idx[j] * strides[p] for j, p in enumerate(perm)))
    return [tuple(vals[i * group_size:(i + 1) * group_size])
            for i in range(n_groups)]


def parse_replica_groups(line: str, *, default_group_size: Optional[int] = None
                         ) -> Tuple[Optional[List[Tuple[int, ...]]], int]:
    """(explicit groups or None, participants per group) for one HLO line.

    Handles literal multi-group lists, both iota forms (with and without
    a transpose suffix — the transposed form's groups are materialized so
    callers can check WHICH mesh axis a collective runs over, not just
    how many devices it spans), and the empty `replica_groups={}` (all
    participants, one group — group size falls back to
    `default_group_size`, or 1 when unknown).
    """
    m = _GROUPS_LITERAL_RE.search(line)
    if m:
        groups = [tuple(int(x) for x in g.split(",") if x.strip())
                  for g in re.findall(r"\{([0-9, ]*)\}", m.group(1))]
        size = max((len(g) for g in groups), default=1)
        return groups, size
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        n_groups, size = int(m.group(1)), int(m.group(2))
        dims = [int(x) for x in m.group(3).split(",") if x.strip()]
        perm = ([int(x) for x in m.group(4).split(",") if x.strip()]
                if m.group(4) else None)
        return _expand_iota(n_groups, size, dims, perm), size
    if _GROUPS_EMPTY_RE.search(line):
        return None, default_group_size if default_group_size else 1
    return None, 1


def _group_size(line: str, default: Optional[int] = None) -> int:
    """Participants per replica group (ring size) for a collective line."""
    return parse_replica_groups(line, default_group_size=default)[1]


def count_fusions(hlo_text: str) -> int:
    """Fused-kernel count of an optimized HLO module: `fusion(...)`
    instructions in the entry (and nested) computations. A drop against
    a baseline means XLA broke a fusion — more kernel launches and HBM
    round trips for the same math."""
    n = 0
    for line in hlo_text.splitlines():
        m = _OP_RE.search(line.strip())
        if m and m.group(2) == "fusion":
            n += 1
    return n


def collective_bytes_from_hlo(hlo_text: str, *,
                              default_group_size: Optional[int] = None
                              ) -> dict:
    """Per-device ICI wire bytes of every collective in the partitioned HLO.

    Modern HLO text omits operand shapes, so bytes derive from the OUTPUT
    shape + replica-group size g with the standard ring model:
      all-reduce       2·S·(g-1)/g        (reduce-scatter + all-gather)
      all-gather       S_out·(g-1)/g
      reduce-scatter   S_out·(g-1)        (input = S_out·g)
      all-to-all       S·(g-1)/g
      collective-permute S
    This refines the assignment's "sum operand sizes" into the actual
    per-device traffic each op puts on the links.

    Returns {"bytes": per-kind wire bytes, "counts": per-kind counts,
    "total_bytes", "ops": [one record per collective instruction with
    its kind, payload size, group size/shape, and wire bytes]}. Async
    pairs count once: `-start` carries the cost, `-done` is skipped; an
    `all-gather-start`/`collective-permute-start` tuple holds
    (operands..., outputs...) so only the output half is sized.
    `default_group_size` backs the empty `replica_groups={}` form (all
    participants — pass the device count of the program).

    An all-reduce counts once per tensor it reduces: XLA's combiner
    merges independent all-reduces into one tuple instruction, and that
    grouping is scheduling, not dataflow.
    """
    out = {k: 0.0 for k in _COLLECTIVES}
    counts = {k: 0 for k in _COLLECTIVES}
    ops: List[dict] = []
    for line in hlo_text.splitlines():
        stripped = line.strip()
        m = _OP_RE.search(stripped)
        if not m:
            continue
        op = m.group(2)
        base, suf = _base_collective(op)
        if base not in _COLLECTIVES or suf == "-done":
            continue
        shapes = _SHAPE_RE.findall(m.group(1))      # output shape(s)
        if (suf == "-start" and base in ("all-gather", "collective-permute")
                and len(shapes) >= 2 and len(shapes) % 2 == 0):
            shapes = shapes[len(shapes) // 2:]      # (operands..., outputs...)
        size = 0
        for dt, dims in shapes:
            if dt not in _DTYPE_BYTES:
                continue                            # unknown dtype: 0 bytes,
            n = 1                                   # op still counted
            for d in dims.split(","):
                if d:
                    n *= int(d)
            size += n * _DTYPE_BYTES[dt]
        groups, g = parse_replica_groups(
            stripped, default_group_size=default_group_size)
        if base == "collective-permute":             # point-to-point
            wire = float(size)
        elif g <= 1:
            wire = 0.0
        elif base == "all-reduce":
            wire = 2.0 * size * (g - 1) / g
        elif base == "all-gather":
            wire = size * (g - 1) / g
        elif base == "reduce-scatter":
            wire = float(size) * (g - 1)
        elif base == "all-to-all":
            wire = size * (g - 1) / g
        else:
            wire = float(size)
        counts[base] += max(1, len(shapes)) if base == "all-reduce" else 1
        out[base] += wire
        ops.append({"op": base, "async": suf == "-start",
                    "size_bytes": size, "group_size": g,
                    "n_groups": len(groups) if groups is not None else None,
                    "groups": ([list(t) for t in groups]
                               if groups is not None else None),
                    "wire_bytes": wire})
    return {"bytes": out, "counts": counts,
            "total_bytes": sum(out.values()), "ops": ops}


def analyze_compiled(lowered, compiled, seconds: float) -> dict:
    """Cost/memory/collective/fusion record for one compiled cell."""
    cost = compiled.cost_analysis() or {}
    try:
        mem = compiled.memory_analysis()
        mem_d = {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "peak_bytes": getattr(mem, "peak_memory_in_bytes", None),
        }
    except Exception:
        mem_d = {}
    text = compiled.as_text()
    coll = collective_bytes_from_hlo(text)
    return {
        "flops": cost.get("flops"),
        "bytes_accessed": cost.get("bytes accessed"),
        "transcendentals": cost.get("transcendentals"),
        "memory": mem_d,
        "collectives": coll,
        "fusions": count_fusions(text),
        "compile_seconds": round(seconds, 2),
    }
