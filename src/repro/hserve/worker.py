"""Worker engine process for the multi-host serving tier.

A worker owns exactly the device-side half of the old monolithic
``HEServer``: a mesh, a resident level-sliced :class:`TableCache`, and
the jit-once :class:`OpEngine` steps.  Everything queue/scheduler/cache
shaped stays on the frontend (``repro.hserve.frontend``); the worker
only sees fully-assembled fixed-shape batches arriving as transport
frames, executes them, and frames the stacked results back.

Requests cross the wire as metadata only (rid + per-operand
(logq, logp, n_slots) + op parameters) — the engine reads nothing else
off a ``Request`` once the batch arrays are assembled, so
:class:`_CtMeta` stands in for operand ciphertexts and no limb data is
duplicated outside the batch arrays.

Health: each worker publishes a ``runtime.monitor.Heartbeat`` file
embedding its :class:`MetricsRegistry` snapshot (``worker.*`` counters
plus engine/cache sources).  The frontend's ``check_workers`` reads
these; a stale heartbeat marks the worker dead and its in-flight batch
is requeued.

``python -m repro.hserve.worker`` runs the subprocess loop: read an
``init`` frame from stdin (params, mesh shape, key material), then
serve ``batch``/``add_key``/``stats`` frames until ``shutdown``.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, Optional, Tuple

import numpy as np

from repro.core.params import HEParams
from repro.hserve.queue import Batch, Request
from repro.hserve.tables import TableCache
from repro.obs.registry import MetricsRegistry
from repro.runtime.monitor import Heartbeat

__all__ = ["WorkerEngine", "main"]


@dataclasses.dataclass(frozen=True)
class _CtMeta:
    """Operand stand-in: the level metadata the engine's output-wrap
    reads (`OpEngine._wrap` touches cts[i].logq/.logp/.n_slots only —
    the limb arrays already ride the batch's stacked arrays)."""

    logq: int
    logp: int
    n_slots: int


def _batch_from_frame(head: Dict[str, Any],
                      arrays: Dict[str, np.ndarray]) -> Batch:
    """Rebuild an assembly-complete Batch from a "batch" frame."""
    op, logq, extra = head["key"]
    key = (op, int(logq), None if extra is None else int(extra))
    reqs = []
    for m in head["reqs"]:
        cts = tuple(_CtMeta(logq=int(logq), logp=int(lp),
                            n_slots=int(m["n_slots"]))
                    for lp in m["logps"])
        reqs.append(Request(
            rid=int(m["rid"]), op=op, cts=cts, r=int(m.get("r", 0)),
            dlogp=int(m.get("dlogp", 0)), logq2=int(m.get("logq2", 0)),
            pt=None, pt_logp=int(m.get("pt_logp", 0))))
    return Batch(key=key, requests=reqs,
                 arrays=dict(arrays), n_valid=int(head["n_valid"]))


class WorkerEngine:
    """One worker: mesh + TableCache + OpEngine behind a frame handler.

    Constructed directly by the frontend for the in-process transport,
    or from an ``init`` frame by :func:`main` for the subprocess one.
    Either way the message surface is :meth:`handle`.
    """

    def __init__(self, params: HEParams, evk=None, rot_keys=None,
                 conj_key=None, *, mesh=None, wid: int = 0,
                 clock: Callable[[], float] = time.perf_counter,
                 heartbeat_path=None, heartbeat_interval: float = 0.0,
                 heartbeat_clock: Optional[Callable[[], float]] = None,
                 **engine_knobs):
        from repro.hserve.engine import OpEngine
        from repro.launch.mesh import make_mesh

        self.params = params
        self.wid = wid
        self.mesh = mesh if mesh is not None else \
            make_mesh((1, 1), ("data", "model"))
        self.cache = TableCache(params, evk, rot_keys, conj_key)
        self.registry = MetricsRegistry()
        self.engine = OpEngine(params, self.mesh, self.cache,
                               registry=self.registry, **engine_knobs)
        self._clock = clock
        self.batches = 0
        self._c_batches = self.registry.counter("worker.batches")
        self._c_requests = self.registry.counter("worker.requests")
        self._h_wall = self.registry.histogram("worker.batch.wall_s")
        self.registry.add_source("cache", self.cache.stats)
        self.registry.add_source(
            "engine", lambda: {"steps_compiled": self.engine.n_compiled,
                               "compile_s": self.engine.compile_s})
        self.heartbeat = None
        if heartbeat_path is not None:
            # the heartbeat timestamp must live on the FRONTEND's
            # death-detection timeline (wall time.time for subprocess
            # workers, the injected fake clock for in-process tests) —
            # not on the perf_counter batch-wall clock.
            hb_clock = heartbeat_clock if heartbeat_clock is not None \
                else time.time
            self.heartbeat = Heartbeat(heartbeat_path,
                                       interval=heartbeat_interval,
                                       metrics=self.registry,
                                       clock=hb_clock)
            self.heartbeat.beat(step=0, payload={"wid": wid})

    def _beat(self) -> None:
        if self.heartbeat is not None:
            self.heartbeat.beat(step=self.batches,
                                payload={"wid": self.wid})

    def handle(self, head: Dict[str, Any], arrays: Dict[str, np.ndarray]
               ) -> Optional[Tuple[Dict[str, Any], Dict[str, np.ndarray]]]:
        """Dispatch one frontend frame; returns the reply frame parts."""
        t = head["type"]
        if t == "batch":
            reply = self.serve_batch(head, arrays)
        elif t == "add_key":
            from repro.core.cipher import EvalKey
            ek = EvalKey(ax_ev=arrays["ax_ev"],
                         ax_ev_shoup=arrays["ax_ev_shoup"],
                         bx_ev=arrays["bx_ev"],
                         bx_ev_shoup=arrays["bx_ev_shoup"])
            if head["kind"] == "rot":
                self.cache.add_rot_key(int(head["r"]), ek)
            elif head["kind"] == "conj":
                self.cache.add_conj_key(ek)
            else:
                raise ValueError(f"unknown key kind {head['kind']!r}")
            reply = ({"type": "ok"}, {})
        elif t == "stats":
            reply = ({"type": "stats",
                      "snapshot": self.registry.snapshot()}, {})
        elif t == "shutdown":
            reply = ({"type": "ok"}, {})
        else:
            raise ValueError(f"unknown message type {t!r}")
        self._beat()
        return reply

    def serve_batch(self, head: Dict[str, Any],
                    arrays: Dict[str, np.ndarray]
                    ) -> Tuple[Dict[str, Any], Dict[str, np.ndarray]]:
        b = _batch_from_frame(head, arrays)
        t0 = self._clock()
        outs, _ = self.engine.wait(self.engine.dispatch(b))
        wall = self._clock() - t0
        self.batches += 1
        self._c_batches.inc()
        self._c_requests.inc(b.n_valid)
        self._h_wall.add(wall)
        rhead = {"type": "result", "seq": head["seq"], "wall": wall,
                 "outs": [{"logq": c.logq, "logp": c.logp,
                           "n_slots": c.n_slots} for c in outs]}
        rarrays = {"ax": np.stack([np.asarray(c.ax) for c in outs]),
                   "bx": np.stack([np.asarray(c.bx) for c in outs])}
        return rhead, rarrays


def _keys_from_init(head: Dict[str, Any], arrays: Dict[str, np.ndarray]):
    """Rebuild (evk, rot_keys, conj_key) from an init frame's arrays
    (named ``evk.<f>`` / ``rot.<r>.<f>`` / ``conj.<f>``)."""
    from repro.core.cipher import EvalKey

    def ek(prefix: str) -> EvalKey:
        return EvalKey(ax_ev=arrays[f"{prefix}.ax_ev"],
                       ax_ev_shoup=arrays[f"{prefix}.ax_ev_shoup"],
                       bx_ev=arrays[f"{prefix}.bx_ev"],
                       bx_ev_shoup=arrays[f"{prefix}.bx_ev_shoup"])

    evk = ek("evk") if head.get("has_evk") else None
    rot_keys = {int(r): ek(f"rot.{r}") for r in head.get("rot_rs", [])}
    conj_key = ek("conj") if head.get("has_conj") else None
    return evk, rot_keys or None, conj_key


def main() -> None:
    """Subprocess entry: frames over stdin/stdout.

    stdout is reserved for frames — any stray print() from imported
    code is rerouted to stderr so it cannot corrupt the stream.
    """
    import sys

    out = sys.stdout.buffer
    inp = sys.stdin.buffer
    sys.stdout = sys.stderr

    from repro.hserve.transport import encode_frame, read_frame

    head, arrays = read_frame(inp)
    if head["type"] != "init":
        raise SystemExit(f"expected init frame, got {head['type']!r}")
    from repro.launch.mesh import make_mesh

    params = HEParams(**head["params"])
    evk, rot_keys, conj_key = _keys_from_init(head, arrays)
    mesh = make_mesh(tuple(head["mesh"]), ("data", "model"))
    hb = head.get("heartbeat") or {}
    worker = WorkerEngine(
        params, evk, rot_keys, conj_key, mesh=mesh,
        wid=int(head.get("wid", 0)),
        heartbeat_path=hb.get("path"),
        heartbeat_interval=float(hb.get("interval", 0.0)),
        **head.get("knobs", {}))
    out.write(encode_frame({"type": "ok", "wid": worker.wid}))
    out.flush()
    while True:
        head, arrays = read_frame(inp)
        reply = worker.handle(head, arrays)
        if reply is not None:
            out.write(encode_frame(*reply))
            out.flush()
        if head["type"] == "shutdown":
            break


if __name__ == "__main__":
    main()
