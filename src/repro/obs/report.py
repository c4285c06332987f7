"""Offline trace analysis: the latency decomposition.

`python -m repro.obs report trace.json` reads a Chrome trace-event file
written by `serve --he --trace` and prints a queue-wait vs device-wall
latency decomposition (lifecycle events): how much of each op's request
latency is spent waiting in a bucket (the batching/SLO trade) vs on the
device (the compute floor) — the serving-side split HEAX argues
pipeline occupancy from. The paper's Fig. 3 stage split lives in the
profiler trace (`serve --he --profile-dir DIR`), where the ``he.*``
named scopes label the fused step's device ops.

Stdlib-only on purpose: the report runs anywhere the trace file lands,
no jax/numpy needed.
"""

from __future__ import annotations

import json
from collections import defaultdict
from typing import Dict, List

__all__ = ["load_events", "analyze", "format_report"]


def load_events(path: str) -> List[dict]:
    with open(path) as f:
        doc = json.load(f)
    events = doc.get("traceEvents", doc if isinstance(doc, list) else [])
    return [e for e in events if e.get("ph") == "X"]


def analyze(events: List[dict]) -> dict:
    """Aggregate a trace into the report's table (seconds)."""
    wait_s: Dict[str, float] = defaultdict(float)
    wait_n: Dict[str, int] = defaultdict(int)
    dev_s: Dict[str, float] = defaultdict(float)
    dev_batches: Dict[str, int] = defaultdict(int)
    complete_n: Dict[str, int] = defaultdict(int)
    latency_s: Dict[str, float] = defaultdict(float)
    for e in events:
        cat = e.get("cat")
        op = (e.get("args") or {}).get("op", "?")
        dur = e.get("dur", 0.0) / 1e6
        name = e.get("name")
        if cat == "lifecycle":
            if name == "bucket_wait":
                wait_s[op] += dur
                wait_n[op] += 1
            elif name == "device_wall":
                dev_s[op] += dur
                dev_batches[op] += 1
            elif name == "complete":
                complete_n[op] += 1
                latency_s[op] += (e.get("args") or {}).get("latency_s",
                                                           0.0)
    return {
        "queue_wait": {op: {"total_s": wait_s[op], "n": wait_n[op]}
                       for op in wait_n},
        "device_wall": {op: {"total_s": dev_s[op],
                             "batches": dev_batches[op]}
                        for op in dev_batches},
        "complete": {op: {"n": complete_n[op],
                          "latency_total_s": latency_s[op]}
                     for op in complete_n},
    }


def _fmt_ms(s: float) -> str:
    return f"{1e3 * s:10.2f}"


def format_report(a: dict) -> str:
    lines = ["latency decomposition: queue wait vs device wall",
             f"{'op':>10} {'waits':>7} {'wait_ms':>10} "
                 f"{'batches':>8} {'device_ms':>10} {'mean_lat_ms':>12}"]
    ops = sorted(set(a["queue_wait"]) | set(a["device_wall"])
                 | set(a["complete"]))
    for op in ops:
        w = a["queue_wait"].get(op, {"total_s": 0.0, "n": 0})
        d = a["device_wall"].get(op, {"total_s": 0.0, "batches": 0})
        c = a["complete"].get(op, {"n": 0, "latency_total_s": 0.0})
        mean_lat = 1e3 * c["latency_total_s"] / c["n"] if c["n"] else 0.0
        lines.append(f"{op:>10} {w['n']:>7} {_fmt_ms(w['total_s'])} "
                     f"{d['batches']:>8} {_fmt_ms(d['total_s'])} "
                     f"{mean_lat:>12.2f}")
    return "\n".join(lines)
