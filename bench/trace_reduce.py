"""Reduce a profiler trace of one benchmark window to device numbers.

A `--trace 1` run wraps its window in a host span ``bench.window`` and
each of its calls into the server (submit, poll, result handling, the
generator's sleeps) in host spans named ``bench.<what>``. This module
reads the profiler's ``.xplane.pb`` with `jax.profiler.ProfileData`
and gives, over the window:

- the seconds in which an operation ran on each device (the union of
  the device's op intervals), averaged over the devices;
- the device operations that took the most time (each op's own time,
  without the ops nested in it);
- the longest idle gaps, each named by the ``bench.*`` host span that
  covers most of it.

Device operations are the events of the ``XLA Ops`` line of every
``/device:...`` plane. A trace with no device plane (the CPU backend)
has its XLA operations on host threads, marked with an ``hlo_op``
statistic; those stand in for the device so that the reduction can be
tested without a chip.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

__all__ = ["Event", "Trace", "load", "find_xplane", "reduce_trace"]

WINDOW = "bench.window"
SPAN_PREFIX = "bench."
TOP = 10


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class Trace:
    """What the reduction needs of a trace: host spans and, per device,
    its operations."""
    host: List[Event]
    devices: Dict[str, List[Event]]


def find_xplane(trace_dir: str) -> Optional[str]:
    """The newest ``.xplane.pb`` under a `jax.profiler` output dir."""
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(found, key=os.path.getmtime) if found else None


_OPCODE = re.compile(r"[\])}] ([a-z][a-z0-9-]*)\(")


def short_name(hlo: str) -> str:
    """A device op's name as the TPU trace gives it is its whole HLO
    line; keep the instruction's name, its opcode and its first result
    type, e.g. ``%while.395 while u32[122,524288]``."""
    if " = " not in hlo:
        return hlo
    name, rest = hlo.split(" = ", 1)
    op = _OPCODE.search(rest)
    shapes = re.findall(r"[a-z0-9]+\[[0-9,]+\]",
                        rest[:op.start()] if op else rest)
    return " ".join(x for x in (name, op.group(1) if op else "",
                                shapes[0] if shapes else "") if x)


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    host: List[Event] = []
    devices: Dict[str, List[Event]] = {}
    cpu_ops: List[Event] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    devices.setdefault(plane.name, []).extend(
                        Event(short_name(e.name), e.start_ns,
                              e.duration_ns) for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    ev = Event(e.name, e.start_ns, e.duration_ns)
                    if e.name.startswith(SPAN_PREFIX):
                        host.append(ev)
                    elif "hlo_op" in dict(e.stats):
                        cpu_ops.append(ev)
    if not devices and cpu_ops:
        devices["/host:CPU"] = cpu_ops
    return Trace(host=host, devices=devices)


def _union(events: List[Event], lo: float, hi: float
           ) -> List[Tuple[float, float]]:
    """Merged [start, end) intervals of events, clipped to [lo, hi)."""
    iv = sorted((max(e.start_ns, lo), min(e.end_ns, hi)) for e in events
                if e.end_ns > lo and e.start_ns < hi)
    out: List[List[float]] = []
    for s, t in iv:
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], t)
        else:
            out.append([s, t])
    return [(s, t) for s, t in out]


def _self_times(events: List[Event], lo: float, hi: float,
                into: Dict[str, float]) -> None:
    """Add each op's own time in [lo, hi) to `into`: its duration less
    the ops nested in it (a loop's body ops run inside the loop's
    event), so that no time is counted twice."""
    evs = sorted(((max(e.start_ns, lo), min(e.end_ns, hi), e.name)
                  for e in events if e.end_ns > lo and e.start_ns < hi),
                 key=lambda x: (x[0], -x[1]))
    stack: List[list] = []          # [end, name, own time]

    def close():
        _, name, own = stack.pop()
        into[name] = into.get(name, 0.0) + own

    for s, t, name in evs:
        while stack and stack[-1][0] <= s:
            close()
        if stack:
            stack[-1][2] -= min(t, stack[-1][0]) - s
        stack.append([t, name, t - s])
    while stack:
        close()


def _gaps(busy: List[Tuple[float, float]], lo: float, hi: float
          ) -> List[Tuple[float, float]]:
    out, at = [], lo
    for s, t in busy:
        if s > at:
            out.append((at, s))
        at = max(at, t)
    if hi > at:
        out.append((at, hi))
    return out


def _cover_name(spans: List[Event], s: float, t: float) -> str:
    """The host span overlapping [s, t) the most (``idle`` if none)."""
    best, best_ov = "idle", 0.0
    for e in spans:
        ov = min(e.end_ns, t) - max(e.start_ns, s)
        if ov > best_ov:
            best, best_ov = e.name, ov
    return best


def reduce_trace(tr: Trace) -> dict:
    """busy_s, window_s, top device ops and longest idle gaps.

    Raises ValueError when the trace has no window span or no device
    operation in the window: a traced run then measured nothing.
    """
    windows = [e for e in tr.host if e.name == WINDOW]
    if not windows:
        raise ValueError(f"trace has no {WINDOW!r} span")
    lo, hi = windows[0].start_ns, windows[0].end_ns
    spans = [e for e in tr.host if e.name != WINDOW
             and e.end_ns > lo and e.start_ns < hi]
    busy_by_dev, by_op = {}, {}
    gaps: List[Tuple[float, str]] = []
    idle_by_span: Dict[str, float] = {}
    for dev, events in sorted(tr.devices.items()):
        busy = _union(events, lo, hi)
        busy_by_dev[dev] = sum(t - s for s, t in busy)
        _self_times(events, lo, hi, by_op)
        for s, t in _gaps(busy, lo, hi):
            name = _cover_name(spans, s, t)
            gaps.append((t - s, name))
            idle_by_span[name] = idle_by_span.get(name, 0.0) + (t - s)
    if not busy_by_dev or not any(busy_by_dev.values()):
        raise ValueError("no device operation ran in the traced window")
    n = len(busy_by_dev)
    window_s = (hi - lo) * 1e-9
    busy_s = sum(busy_by_dev.values()) * 1e-9 / n
    top_ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:TOP]
    gaps.sort(key=lambda g: -g[0])
    return {
        "busy_s": busy_s,
        "window_s": window_s,
        "idle_share": 1.0 - busy_s / window_s,
        "devices": n,
        "device_ops": [[k, v * 1e-9 / n] for k, v in top_ops],
        "idle_gaps": [[name, d * 1e-9] for d, name in gaps[:TOP]],
        "idle_by_span": {k: v * 1e-9 / n for k, v in
                         sorted(idle_by_span.items(), key=lambda kv: -kv[1])},
        "device_op_s": sum(by_op.values()) * 1e-9 / n,
    }
