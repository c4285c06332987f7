#!/usr/bin/env python3
"""The program's own spans and stage scopes in a benchmark window's trace.

`bench/trace_reduce.py` reduces a `--trace 1` window to device busy and
idle time and names each idle gap by the benchmark's ``bench.*`` span
over it. The program records more in the same profiler trace:

- its `repro.obs.Tracer` mirrors every span as a host annotation named
  ``hserve.<span>`` (poll, batch_assemble, dispatch, h2d, launch, wait,
  retire, prefetch, ...), nested inside the benchmark's spans, with the
  span's arguments as statistics (``hserve.h2d`` carries ``bytes``, the
  bytes handed to ``device_put``);
- `dist.he_pipeline` wraps each stage call of the served step in a
  `jax.named_scope` (``he.crt``, ``he.ntt``, ``he.intt``, ``he.modmul``,
  ``he.icrt``), which XLA keeps as each instruction's ``op_name``; the
  profiler stores every program's optimized HLO in the trace's
  ``/host:metadata`` plane, and a device op's event names its HLO
  instruction and the program it ran in.

`reduce_program` gives, over the window (``bench.window``):

- ``program_spans``: for each ``hserve.*`` name, seconds and count;
- ``idle_by_program_span``: idle device seconds by the innermost
  ``hserve.*`` span open at each instant (``none`` where no program span
  is open: only a ``bench.*`` span or nothing);
- ``idle_gaps``: the longest idle gaps, each named by the path of spans
  open over most of it (``bench.poll/hserve.poll/hserve.h2d``);
- ``h2d_bytes``: the ``bytes`` of the ``hserve.h2d`` spans;
- ``device_by_scope``: own device time of the ops of the programs that
  carry stage scopes, by the paper's Fig. 3 stage (``crt``, ``ntt`` =
  ``he.ntt`` + ``he.intt``, ``modmul``, ``icrt``) and ``other`` for ops
  under no stage scope; a fusion takes its own metadata's scope, or, with
  none, the scope most of its fused instructions carry. None where no
  program carries a scope.

A trace of a program without the spans or scopes gives empty
``program_spans`` and ``device_by_scope`` None, so the readers built on
it (`bench/metrics/engine.*_ms.sat.py`, `pipeline.*`) read nothing.

    python3 bench/program_trace.py [xplane.pb]

prints the reduction of a trace (by default the newest under the
benchmark's trace directory) as JSON.
"""

from __future__ import annotations

import collections
import dataclasses
import json
import sys
from pathlib import Path
from typing import Dict, Iterator, List, Optional, Tuple

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench.trace_reduce import (  # noqa: E402
    SPAN_PREFIX, TOP, WINDOW, Event, _gaps, _self_times, _union,
    find_xplane,
)

__all__ = ["ProgramTrace", "load_program", "reduce_program", "for_record",
           "stage_of", "STAGES"]

PROGRAM_PREFIX = "hserve."
# the paper's Fig. 3 stages by the named scope the program gives them
STAGE_SCOPES = {"he.crt": "crt", "he.ntt": "ntt", "he.intt": "ntt",
                "he.modmul": "modmul", "he.icrt": "icrt"}
STAGES = ("crt", "ntt", "modmul", "icrt")


@dataclasses.dataclass
class ProgramTrace:
    """Host spans (``bench.*`` and ``hserve.*``), the bytes of each
    ``hserve.h2d`` span, per device its ops as (start, duration, HLO
    instruction, program), and each program's instruction -> stage."""
    host: List[Event]
    h2d: List[Tuple[float, int]]                  # (start_ns, bytes)
    devices: Dict[str, List[Tuple[float, float, str, str]]]
    stages: Dict[str, Dict[str, str]]


# --------------------------------------------------------------------------
# protobuf wire format: just enough to read the HLO of /host:metadata
# --------------------------------------------------------------------------

def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one message; a length-delimited value is
    its bytes, a varint its int."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            v, i = _varint(buf, i)
        elif kind == 2:
            ln, i = _varint(buf, i)
            v, i = buf[i:i + ln], i + ln
        elif kind == 1:
            v, i = buf[i:i + 8], i + 8
        elif kind == 5:
            v, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"wire type {kind} at {i}")
        yield key >> 3, v


def _first(buf, field: int, default=b""):
    return next((v for f, v in _fields(buf) if f == field), default)


def _hlo_protos(xspace: bytes) -> Dict[str, bytes]:
    """program name (``jit_step(<id>)``) -> its HloModuleProto, from the
    ``Hlo Proto`` statistics of the ``/host:metadata`` plane (XSpace
    field 1 = planes; XPlane 2 = name, 4 = event metadata, 5 = stat
    metadata; XEventMetadata 2 = name, 5 = stats; XStat 1 = metadata id,
    6 = bytes; HloProto 1 = the module)."""
    out: Dict[str, bytes] = {}
    mv = memoryview(xspace)
    for f, plane in _fields(mv):
        if f != 1 or bytes(_first(plane, 2)) != b"/host:metadata":
            continue
        stat_ids, events = set(), []
        for g, v in _fields(plane):
            if g == 5:                    # map<int64, XStatMetadata>
                meta = _first(v, 2)
                if bytes(_first(meta, 2)) == b"Hlo Proto":
                    stat_ids.add(_first(meta, 1, 0))
            elif g == 4:                  # map<int64, XEventMetadata>
                events.append(_first(v, 2))
        for ev in events:
            name = bytes(_first(ev, 2)).decode()
            for g, stat in _fields(ev):
                if g == 5 and _first(stat, 1, 0) in stat_ids:
                    out[name] = bytes(_first(_first(stat, 6), 1))
    return out


def stage_of(op_name: str) -> Optional[str]:
    """The innermost Fig. 3 stage scope in an ``op_name`` path."""
    for part in reversed(op_name.split("/")):
        if part in STAGE_SCOPES:
            return STAGE_SCOPES[part]
    return None


def _stage_map(module: bytes) -> Dict[str, str]:
    """instruction -> stage of one HloModuleProto (module 3 =
    computations; computation 1 = name, 2 = instructions, 5 = id;
    instruction 1 = name, 7 = metadata, 38 = called computation ids;
    OpMetadata 2 = op_name)."""
    own: Dict[str, Optional[str]] = {}
    calls: Dict[str, List[int]] = {}
    in_comp: Dict[int, collections.Counter] = {}
    for f, comp in _fields(module):
        if f != 3:
            continue
        cid, instrs = 0, []
        for g, v in _fields(comp):
            if g == 5:
                cid = v
            elif g == 2:
                instrs.append(v)
        count = in_comp.setdefault(cid, collections.Counter())
        for ins in instrs:
            name, stage, called = "", None, []
            for g, v in _fields(ins):
                if g == 1:
                    name = bytes(v).decode()
                elif g == 7:
                    stage = stage_of(bytes(_first(v, 2)).decode())
                elif g == 38:
                    if isinstance(v, int):
                        called.append(v)
                    else:                 # packed
                        j = 0
                        while j < len(v):
                            c, j = _varint(v, j)
                            called.append(c)
            own[name] = stage
            calls[name] = called
            if stage:
                count[stage] += 1
    out = {}
    for name, stage in own.items():
        if stage is None:
            votes = sum((in_comp.get(c, collections.Counter())
                         for c in calls[name]), collections.Counter())
            stage = votes.most_common(1)[0][0] if votes else None
        if stage is not None:
            out[name] = stage
    return out


# --------------------------------------------------------------------------
# loading
# --------------------------------------------------------------------------

def load_program(path: str) -> ProgramTrace:
    from jax.profiler import ProfileData
    with open(path, "rb") as f:
        raw = f.read()
    stages = {k: m for k, m in ((k, _stage_map(v))
                                for k, v in _hlo_protos(raw).items()) if m}
    pd = ProfileData.from_serialized_xspace(raw)
    host: List[Event] = []
    h2d: List[Tuple[float, int]] = []
    devices: Dict[str, List[Tuple[float, float, str, str]]] = {}
    cpu_ops: List[Tuple[float, float, str, str]] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            lines = {line.name: list(line.events) for line in plane.lines}
            if not lines.get("XLA Ops"):
                continue                  # not a device that runs XLA ops
            mods = sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                          for e in lines.get("XLA Modules", []))
            ops, k = [], 0
            for e in sorted(lines.get("XLA Ops", []),
                            key=lambda e: e.start_ns):
                while k < len(mods) and mods[k][1] <= e.start_ns:
                    k += 1
                mod = mods[k][2] if k < len(mods) \
                    and mods[k][0] <= e.start_ns else ""
                ops.append((e.start_ns, e.duration_ns,
                            e.name.split(" = ")[0].lstrip("%"), mod))
            devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    name = e.name
                    if name.startswith((SPAN_PREFIX, PROGRAM_PREFIX)):
                        host.append(Event(name, e.start_ns, e.duration_ns))
                        if name == PROGRAM_PREFIX + "h2d":
                            h2d.append((e.start_ns, int(
                                dict(e.stats).get("bytes", 0))))
                    elif name and not name.startswith(("$", "end: ")):
                        st = dict(e.stats)
                        if "hlo_op" in st:
                            cpu_ops.append((
                                e.start_ns, e.duration_ns,
                                str(st["hlo_op"]),
                                f"{st.get('hlo_module', '')}"
                                f"({st.get('program_id', '')})"))
    if not devices and cpu_ops:
        devices["/host:CPU"] = cpu_ops
    return ProgramTrace(host, h2d, devices, stages)


# --------------------------------------------------------------------------
# reduction
# --------------------------------------------------------------------------

def _segments(spans: List[Event], lo: float, hi: float
              ) -> List[Tuple[float, float, Tuple[str, ...]]]:
    """[lo, hi) cut wherever a span opens or closes: (start, end, names
    of the spans open there, outermost first)."""
    marks = sorted([(max(e.start_ns, lo), 1, i)
                    for i, e in enumerate(spans)]
                   + [(min(e.end_ns, hi), 0, i)
                      for i, e in enumerate(spans)])
    out, open_, at = [], [], lo
    for t, opening, i in marks:
        if t > at:
            out.append((at, t, tuple(spans[j].name for j in open_)))
            at = t
        if opening:
            open_.append(i)
        else:
            open_.remove(i)
    if hi > at:
        out.append((at, hi, ()))
    return out


def _innermost_program(path: Tuple[str, ...]) -> str:
    for name in reversed(path):
        if name.startswith(PROGRAM_PREFIX):
            return name
    return "none"


def reduce_program(tr: ProgramTrace) -> dict:
    """The window's program spans, idle time by innermost program span,
    idle gaps by span path, h2d bytes and device time by stage scope.

    Raises ValueError when the trace has no window span.
    """
    windows = [e for e in tr.host if e.name == WINDOW]
    if not windows:
        raise ValueError(f"trace has no {WINDOW!r} span")
    lo, hi = windows[0].start_ns, windows[0].end_ns
    spans = [e for e in tr.host if e.name != WINDOW
             and e.end_ns > lo and e.start_ns < hi]
    segs = _segments(spans, lo, hi)
    n = max(1, len(tr.devices))
    idle: Dict[str, float] = {}
    gaps: List[Tuple[float, str]] = []
    by_scope = {k: 0.0 for k in STAGES + ("other",)}
    scoped = False
    for dev, ops in sorted(tr.devices.items()):
        events = [Event("", s, d) for s, d, _, _ in ops]
        mine = [Event(tr.stages[m].get(h, "other"), s, d)
                for s, d, h, m in ops if m in tr.stages]
        scoped = scoped or bool(mine)
        _self_times(mine, lo, hi, by_scope)
        k = 0
        for s, t in _gaps(_union(events, lo, hi), lo, hi):
            by_path: Dict[Tuple[str, ...], float] = {}
            while segs[k][1] <= s:
                k += 1
            j = k
            while j < len(segs) and segs[j][0] < t:
                a, b, path = segs[j]
                ov = min(b, t) - max(a, s)
                by_path[path] = by_path.get(path, 0.0) + ov
                inner = _innermost_program(path)
                idle[inner] = idle.get(inner, 0.0) + ov
                j += 1
            by_path.pop((), None)
            path = max(by_path, key=by_path.get) if by_path else ()
            gaps.append((t - s, "/".join(path) or "idle"))
    program: Dict[str, Dict[str, float]] = {}
    for e in spans:
        if e.name.startswith(PROGRAM_PREFIX):
            p = program.setdefault(e.name, {"s": 0.0, "n": 0})
            p["s"] += (min(e.end_ns, hi) - max(e.start_ns, lo)) * 1e-9
            p["n"] += 1
    gaps.sort(key=lambda g: -g[0])
    return {
        "window_s": (hi - lo) * 1e-9,
        "program_spans": program,
        "idle_by_program_span": {
            k: v * 1e-9 / n for k, v in
            sorted(idle.items(), key=lambda kv: -kv[1])},
        "idle_gaps": [[name, d * 1e-9] for d, name in gaps[:TOP]],
        "h2d_bytes": sum(b for t, b in tr.h2d if lo <= t < hi),
        "device_by_scope": ({k: v * 1e-9 / n for k, v in by_scope.items()}
                            if scoped else None),
    }


def for_record(rec: dict) -> Optional[dict]:
    """The program reduction of a traced run's window, computed once per
    record (kept under ``rec["program"]``); None for a run without a
    trace, or a trace without a window."""
    if "program" not in rec:
        rec["program"] = None
        if rec.get("trace") is not None:
            from bench.run import TRACE_DIR
            path = find_xplane(str(TRACE_DIR))
            if path is not None:
                try:
                    rec["program"] = reduce_program(load_program(path))
                except ValueError:
                    pass
    return rec["program"]


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if argv:
        path = argv[0]
    else:
        from bench.run import TRACE_DIR
        path = find_xplane(str(TRACE_DIR))
    print(json.dumps(reduce_program(load_program(path)), indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
