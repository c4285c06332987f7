"""HEFrontend: the multi-host disaggregated serving tier.

The monolithic ``HEServer`` owns both halves of serving: the
queue/scheduler/plain-cache frontend AND the mesh/tables/engine
backend. This module splits them. :class:`HEFrontend` keeps the
engine-free serving core (it subclasses HEServer and reuses
``_init_core`` / ``_choose_flush`` / ``_pop_assemble`` / ``_complete``
verbatim — submit, circuits, metrics, scheduling are all inherited) and
routes assembled batches to N :class:`~repro.hserve.worker.WorkerEngine`
processes over :mod:`~repro.hserve.transport` frames. Each worker owns
its own device mesh, resident TableCache, and jit-once OpEngine steps —
the per-host state that cannot be shared across processes.

Routing is (op, level)-bucket affinity with load-first tiebreak:
an idle worker always beats a busy one (a single hot bucket must spill
across hosts or scaling is zero), and among equally-loaded workers the
one whose compiled-step/table cache is already warm for the bucket
wins — so in steady state hot levels stay pinned to the worker holding
their table slices, and a spill warms exactly one new worker.

Health and death: workers publish ``runtime.monitor.Heartbeat`` files
(registry snapshots embedded); the frontend marks a worker dead on a
transport error OR a stale heartbeat (``check_workers``), requeues the
dead worker's in-flight batch at the original rids — circuit routing
and FIFO order survive — and re-routes on the next poll. Ops are
deterministic integer arithmetic, so a re-served batch is bitwise
identical to the first attempt. With every worker dead and work still
queued, :class:`NoLiveWorkersError` is raised (drain propagates it
instead of spinning).

``runtime.failures.FailureInjector(kill_worker_at={wid: n})`` drives
worker death deterministically for the fault tests and the bench's
requeue block.
"""

from __future__ import annotations

import time
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from repro.core.cipher import Ciphertext, EvalKey
from repro.core.params import HEParams
from repro.hserve.queue import Batch
from repro.hserve.server import HEServer
from repro.hserve.tables import PlainCache
from repro.hserve.transport import (
    InProcTransport, SubprocessTransport, WorkerDied,
)
from repro.hserve.worker import WorkerEngine
from repro.runtime.monitor import Heartbeat

__all__ = ["NoLiveWorkersError", "FrontendCatalog", "WorkerHandle",
           "HEFrontend"]


class NoLiveWorkersError(RuntimeError):
    """Work is queued (or in flight) but every worker is dead — the
    typed drain-instead-of-hang contract of the fault tests."""


class FrontendCatalog:
    """The frontend's key/plain-operand catalog — TableCache's submit-
    time surface with NO device state.

    The frontend must answer "can this op be served?" at submit (the
    same raise-before-enqueue contract TableCache gives HEServer) and
    resolve plaintext operands, but the device pytrees live in the
    workers. So this holds raw EvalKeys + a PlainCache, mirrors
    TableCache's query API (evk/rot_key/conj_key/rotation_amounts/
    has_conj_key/put_plain/get_plain/has_plain), and forwards key
    additions to every live worker via the frontend's broadcast hook.
    """

    def __init__(self, params: HEParams, evk: Optional[EvalKey] = None,
                 rot_keys: Optional[Dict[int, EvalKey]] = None,
                 conj_key: Optional[EvalKey] = None,
                 plain_cache_mib: Optional[float] = 256.0):
        self.params = params
        self._ek = evk
        self._rot: Dict[int, EvalKey] = {
            int(r): rk for r, rk in (rot_keys or {}).items()}
        self._conj = conj_key
        self.plain = PlainCache(cap_mib=plain_cache_mib)
        self.tracer = None
        # set by HEFrontend: broadcast(kind, r, key) ships a key to
        # every live worker before it can be referenced by a batch
        self._broadcast: Optional[Callable] = None

    # ---- submit-time key checks (same messages as TableCache) ---------

    def evk(self) -> EvalKey:
        if self._ek is None:
            raise ValueError("no evaluation key loaded (mul unavailable)")
        return self._ek

    def rot_key(self, r: int) -> EvalKey:
        try:
            return self._rot[int(r)]
        except KeyError:
            raise KeyError(
                f"no rotation key for r={r}; loaded: "
                f"{sorted(self._rot)}") from None

    def conj_key(self) -> EvalKey:
        if self._conj is None:
            raise ValueError(
                "no conjugation key loaded (conjugate unavailable)")
        return self._conj

    def add_rot_key(self, r: int, rk: EvalKey) -> None:
        r = int(r)
        new = r not in self._rot
        self._rot[r] = rk
        if new and self._broadcast is not None:
            self._broadcast("rot", r, rk)

    def add_conj_key(self, ck: EvalKey) -> None:
        new = self._conj is None
        self._conj = ck
        if new and self._broadcast is not None:
            self._broadcast("conj", 0, ck)

    @property
    def has_conj_key(self) -> bool:
        return self._conj is not None

    @property
    def rotation_amounts(self):
        return sorted(self._rot)

    # ---- plaintext operands (delegated; HEServer.submit's surface) ----

    def put_plain(self, h: str, logq: int, pt) -> np.ndarray:
        return self.plain.put(h, logq, pt)

    def get_plain(self, h: str, logq: int) -> np.ndarray:
        return self.plain.get(h, logq)

    def has_plain(self, h: str, logq: int) -> bool:
        return self.plain.has(h, logq)

    def stats(self) -> dict:
        return {
            "rot_keys": self.rotation_amounts,
            "conj_key": self.has_conj_key,
            "plain_entries": len(self.plain),
            "plain_hits": self.plain.hits,
            "plain_misses": self.plain.misses,
            "plain_evictions": self.plain.evictions,
            "plain_mib": round(self.plain.nbytes / 2**20, 3),
        }


class _Pending:
    """One dispatched-but-unretired batch on a worker."""

    __slots__ = ("batch", "seq", "t0")

    def __init__(self, batch: Batch, seq: int, t0: float):
        self.batch = batch
        self.seq = seq
        self.t0 = t0


class WorkerHandle:
    """Frontend-side view of one worker: transport + routing state."""

    def __init__(self, wid: int, transport, heartbeat_path=None):
        self.wid = wid
        self.transport = transport
        self.heartbeat_path = heartbeat_path
        self.alive = True
        self.pending: Optional[_Pending] = None
        # routing state: buckets this worker has served (its compiled
        # steps + table slices are warm for these), and busy seconds
        self.keys_warm: set = set()
        self.busy_s = 0.0
        self.batches = 0             # lifetime dispatches (injector key)
        self.served_requests = 0

    def stats(self) -> dict:
        return {"wid": self.wid, "alive": self.alive,
                "transport": self.transport.kind,
                "batches": self.batches,
                "served_requests": self.served_requests,
                "busy_s": round(self.busy_s, 6),
                "keys_warm": sorted(str(k) for k in self.keys_warm),
                "pending": self.pending is not None}


def _key_frames(evk: Optional[EvalKey], rot: Dict[int, EvalKey],
                conj: Optional[EvalKey]) -> Dict[str, np.ndarray]:
    """Flatten key material into init-frame array names."""
    out: Dict[str, np.ndarray] = {}

    def put(prefix: str, ek: EvalKey) -> None:
        for f in ("ax_ev", "ax_ev_shoup", "bx_ev", "bx_ev_shoup"):
            out[f"{prefix}.{f}"] = np.asarray(getattr(ek, f))

    if evk is not None:
        put("evk", evk)
    for r, rk in rot.items():
        put(f"rot.{r}", rk)
    if conj is not None:
        put("conj", conj)
    return out


class HEFrontend(HEServer):
    """The frontend process of the disaggregated serving tier.

    Inherits the whole intake/scheduling surface from HEServer (submit,
    submit_circuit, drain, metrics, the flush policy) and replaces the
    local engine with routed dispatch to `workers` worker engines.

    transport: "inproc" (worker engines in this process, framed — the
        default; simulated multi-host, shares this process's devices) or
        "subprocess" (real `python -m repro.hserve.worker` processes,
        each with its own XLA host devices).
    worker_platform: ``JAX_PLATFORMS`` of each subprocess worker; required
        with transport="subprocess" (see `SubprocessTransport`).
    worker_devices: host device count per subprocess worker.
    injector: optional `runtime.failures.FailureInjector` whose
        `kill_worker_at` schedule this frontend consults after every
        dispatch (deterministic worker death for tests/benches).
    heartbeat_dir / heartbeat_timeout / heartbeat_interval: worker
        health files; `check_workers()` marks a worker dead when its
        file goes stale past the timeout. In-process workers beat on
        the frontend's (injectable) clock; subprocess workers beat on
        wall time.

    Unsupported vs the monolith: `overlap` (the per-worker pipeline IS
    the overlap — every worker holds one in-flight batch while the
    frontend assembles the next).
    """

    def __init__(self, params: HEParams, evk: Optional[EvalKey] = None,
                 rot_keys: Optional[Dict[int, EvalKey]] = None,
                 conj_key: Optional[EvalKey] = None, *,
                 workers: int = 2, transport: str = "inproc",
                 mesh=None, batch: int = 8, use_kernels: bool = False,
                 max_age_s: Optional[float] = None,
                 adaptive_target: bool = True,
                 schedule: bool = False, lookahead: int = 2,
                 cost_model=None,
                 plain_cache_mib: Optional[float] = 256.0,
                 clock: Callable[[], float] = time.perf_counter,
                 tracer=None, registry=None, injector=None,
                 heartbeat_dir: Optional[str] = None,
                 heartbeat_timeout: float = 30.0,
                 heartbeat_interval: float = 0.0,
                 worker_platform: Optional[str] = None,
                 worker_devices: int = 1,
                 **engine_knobs):
        if workers < 1:
            raise ValueError(f"need at least one worker, got {workers}")
        if transport not in ("inproc", "subprocess"):
            raise ValueError(f"unknown transport {transport!r} "
                             "(inproc | subprocess)")
        if transport == "subprocess" and worker_platform is None:
            raise ValueError("subprocess workers need an explicit "
                             "worker_platform (e.g. 'cpu')")
        if mesh is None:
            from repro.launch.mesh import make_host_mesh
            mesh = make_host_mesh()
        self.cache = FrontendCatalog(params, evk, rot_keys, conj_key,
                                     plain_cache_mib=plain_cache_mib)
        self.engine = None           # no local engine — workers own them
        self._init_core(params, mesh=mesh, batch=batch,
                        max_age_s=max_age_s,
                        adaptive_target=adaptive_target, overlap=False,
                        schedule=schedule, lookahead=lookahead,
                        cost_model=cost_model, prefetch=False,
                        clock=clock, tracer=tracer, registry=registry)
        self.injector = injector
        self.transport_kind = transport
        self.heartbeat_timeout = heartbeat_timeout
        # spawn-time worker config, kept so revive_workers() can replay
        # a full init frame into a respawned subprocess worker
        self.worker_devices = worker_devices
        self.heartbeat_interval = heartbeat_interval
        self.use_kernels = use_kernels
        self.engine_knobs = dict(engine_knobs)
        self._seq = 0
        # results completed out-of-poll (quiesce before a key
        # broadcast, eager retires) buffer here until the next poll
        self._ready: List[Tuple[int, Ciphertext]] = []
        self.workers: List[WorkerHandle] = []
        rot = {int(r): rk for r, rk in (rot_keys or {}).items()}
        for wid in range(workers):
            hb_path = None
            if heartbeat_dir is not None:
                import os
                hb_path = os.path.join(heartbeat_dir,
                                       f"worker{wid}.heartbeat.json")
            if transport == "inproc":
                eng = WorkerEngine(
                    params, evk, dict(rot) or None, conj_key,
                    mesh=mesh, wid=wid, clock=clock,
                    heartbeat_path=hb_path,
                    heartbeat_interval=heartbeat_interval,
                    heartbeat_clock=clock, use_kernels=use_kernels,
                    **engine_knobs)
                tp = InProcTransport(eng)
            else:
                tp = SubprocessTransport(worker_platform,
                                         devices=worker_devices)
                self._send_worker_init(tp, wid, hb_path)
            self.workers.append(WorkerHandle(wid, tp,
                                             heartbeat_path=hb_path))
        if transport == "subprocess":
            # collect each worker's init ack (keys loaded, mesh up)
            for w in self.workers:
                head, _ = w.transport.recv()
                if head.get("type") != "ok":
                    raise WorkerDied(
                        f"worker {w.wid} failed init: {head}")
        self.cache._broadcast = self._broadcast_key
        self._c_deaths = self.registry.counter("worker.deaths")
        self._c_requeued = self.registry.counter(
            "worker.requeued_requests")
        self._g_alive = self.registry.gauge("worker.alive")
        self._g_alive.set(len(self.workers))
        for w in self.workers:
            self.registry.add_source(f"worker{w.wid}", w.stats)

    # ---- worker lifecycle ------------------------------------------------

    def _send_worker_init(self, tp, wid: int,
                          hb_path: Optional[str]) -> None:
        """Ship the init frame (params/mesh/knobs + ALL current key
        material) to a fresh subprocess worker. Reads keys from the
        catalog, not the constructor args, so a respawned worker also
        receives keys that were added (auto-provisioned rotations,
        bootstrap key sets) after the fleet first came up. The caller
        awaits the "ok" ack."""
        import dataclasses
        cat = self.cache
        init = {"type": "init",
                "params": dataclasses.asdict(self.params),
                "mesh": [1, self.worker_devices],
                "wid": wid,
                "has_evk": cat._ek is not None,
                "rot_rs": sorted(cat._rot),
                "has_conj": cat._conj is not None,
                "heartbeat": {"path": hb_path,
                              "interval": self.heartbeat_interval}
                if hb_path else None,
                "knobs": {"use_kernels": self.use_kernels,
                          **self.engine_knobs}}
        tp.send(init, _key_frames(cat._ek, cat._rot, cat._conj))

    def _alive_workers(self) -> List[WorkerHandle]:
        return [w for w in self.workers if w.alive]

    def _on_death(self, w: WorkerHandle, cause: str) -> None:
        """Mark a worker dead and requeue its in-flight batch (original
        rids — circuit routing and metrics bookkeeping survive)."""
        if not w.alive:
            return
        w.alive = False
        try:
            w.transport.kill()
        except Exception:                     # noqa: BLE001 — best effort
            pass
        self._c_deaths.inc()
        self._g_alive.set(len(self._alive_workers()))
        if w.pending is not None:
            reqs = w.pending.batch.requests[:w.pending.batch.n_valid]
            self.queue.requeue(reqs)
            self._c_requeued.inc(len(reqs))
            w.pending = None
        if self._tracer is not None:
            self._tracer.event(
                "worker_death", cat="worker", lane=f"worker{w.wid}",
                ts=self._clock(), args={"wid": w.wid, "cause": cause})

    def check_workers(self, now: Optional[float] = None) -> None:
        """Heartbeat sweep: a live worker whose heartbeat file has gone
        stale past `heartbeat_timeout` is declared dead (its in-flight
        batch requeues). In-process workers beat on the frontend's
        injected clock, so pass the same clock's reading via `now`
        (default: this frontend's clock for inproc, wall time for
        subprocess workers)."""
        for w in self._alive_workers():
            if w.heartbeat_path is None:
                continue
            t = now
            if t is None and w.transport.kind == "inproc":
                t = self._clock()
            if not Heartbeat.is_alive(w.heartbeat_path,
                                      self.heartbeat_timeout, now=t):
                self._on_death(w, "heartbeat_timeout")

    def revive_workers(self) -> None:
        """Bring every killed worker back online and restore the fleet
        to full strength.

        In-process workers are un-killed in place — their engines kept
        their compiled steps. Subprocess workers are RESPAWNED: a new
        interpreter comes up, the init frame is replayed with the
        catalog's CURRENT key material (including keys broadcast after
        the original spawn), and the "ok" ack is awaited before the
        worker is routable. The fresh process has no compiled steps or
        table slices, so its warm-bucket routing state resets; anything
        it was serving when it died was already requeued at death, and
        re-served batches are bitwise identical (deterministic integer
        ops)."""
        respawned: List[WorkerHandle] = []
        for w in self.workers:
            if w.alive:
                continue
            if w.transport.kind == "inproc":
                w.transport.revive()     # engine kept its compiled steps
            else:
                w.transport.respawn()
                self._send_worker_init(w.transport, w.wid,
                                       w.heartbeat_path)
                w.keys_warm = set()      # blank interpreter: nothing warm
                respawned.append(w)
            w.alive = True
            w.pending = None
        for w in respawned:
            head, _ = w.transport.recv()
            if head.get("type") != "ok":
                w.alive = False
                raise WorkerDied(
                    f"worker {w.wid} failed respawn init: {head}")
        self._g_alive.set(len(self._alive_workers()))

    # ---- key broadcast ---------------------------------------------------

    def _broadcast_key(self, kind: str, r: int, ek: EvalKey) -> None:
        """Ship a late-added key to every live worker. Each worker is
        quiesced first (its pending batch retired into the ready
        buffer) so the strict request-reply protocol stays in step."""
        arrays = {f: np.asarray(getattr(ek, f))
                  for f in ("ax_ev", "ax_ev_shoup", "bx_ev",
                            "bx_ev_shoup")}
        for w in self._alive_workers():
            if w.pending is not None:
                self._retire_worker(w)
                if not w.alive:
                    continue
            try:
                w.transport.send({"type": "add_key", "kind": kind,
                                  "r": r}, arrays)
                head, _ = w.transport.recv()
                if head.get("type") != "ok":
                    raise WorkerDied(f"add_key nacked: {head}")
            except WorkerDied:
                self._on_death(w, "transport")

    # ---- routed dispatch (replaces the local engine) ---------------------

    def _route(self, b: Batch) -> WorkerHandle:
        """Pick a worker: load first, bucket affinity second.

        Affinity-first would pin a single hot bucket onto one worker
        and serialize the whole stream (zero scaling); load-first lets
        a hot bucket spill to idle and less-busy workers — each spill
        warms exactly one more worker, converging to a balanced pinning
        — while the affinity tiebreak keeps multi-bucket streams from
        bouncing warm levels between equally loaded workers. Idle
        workers rank warmth before accumulated busy_s (their past load
        is sunk; reusing compiled steps + resident slices is free);
        busy workers rank busy_s before warmth (a warm-but-backlogged
        worker must NOT beat an idle-ish one — that is the pinning
        failure mode). wid breaks remaining ties deterministically
        (routing must be replayable: the bench re-runs the same stream
        and compares bitwise).
        """
        alive = self._alive_workers()
        if not alive:
            raise NoLiveWorkersError(
                f"no live workers ({len(self.workers)} configured, all "
                f"dead) with {self.queue.depth} queued request(s)")

        def score(w: WorkerHandle):
            warm = 0 if b.key in w.keys_warm else 1
            if w.pending is None:
                return (0, warm, w.busy_s, w.wid)
            return (1, w.busy_s, warm, w.wid)

        return min(alive, key=score)

    def _dispatch_to(self, w: WorkerHandle, b: Batch) -> bool:
        """Frame + send one batch; False when the send killed the
        worker (caller re-routes)."""
        self._seq += 1
        seq = self._seq
        head = {"type": "batch", "seq": seq,
                "key": list(b.key), "n_valid": b.n_valid,
                "reqs": [{"rid": r.rid, "r": r.r, "dlogp": r.dlogp,
                          "logq2": r.logq2, "pt_logp": r.pt_logp,
                          "n_slots": r.cts[0].n_slots,
                          "logps": [c.logp for c in r.cts]}
                         for r in b.requests[:b.n_valid]]}
        tr = self._tracer
        try:
            if tr is not None:
                with tr.span("dispatch", cat="lifecycle", lane="server",
                             args={"op": b.op, "batch": b.size,
                                   "worker": w.wid}):
                    w.transport.send(head, b.arrays)
            else:
                w.transport.send(head, b.arrays)
        except WorkerDied:
            self._on_death(w, "transport")
            return False
        w.pending = _Pending(b, seq, self._clock())
        w.batches += 1
        w.keys_warm.add(b.key)
        if self.injector is not None and \
                self.injector.maybe_kill_worker(w.wid, w.batches):
            # die AFTER the send: the batch is in flight on a worker
            # that will never answer — the mid-batch death window
            w.transport.kill()
        return True

    def _retire_worker(self, w: WorkerHandle) -> None:
        """Collect one worker's pending result into the ready buffer
        (or requeue it if the worker died under us)."""
        p = w.pending
        if p is None:
            return
        try:
            head, arrays = w.transport.recv()
            if head.get("type") != "result" or head.get("seq") != p.seq:
                raise WorkerDied(
                    f"protocol skew from worker {w.wid}: {head}")
        except WorkerDied:
            self._on_death(w, "transport")
            return
        w.pending = None
        wall = float(head["wall"])
        w.busy_s += wall
        w.served_requests += p.batch.n_valid
        if self._tracer is not None:
            self._tracer.event(
                "device_wall", cat="lifecycle", lane=f"worker{w.wid}",
                ts=p.t0, dur=wall,
                args={"op": p.batch.op, "logq": p.batch.logq,
                      "worker": w.wid, "n_valid": p.batch.n_valid})
        outs = [Ciphertext(ax=arrays["ax"][i], bx=arrays["bx"][i],
                           logq=int(m["logq"]), logp=int(m["logp"]),
                           n_slots=int(m["n_slots"]))
                for i, m in enumerate(head["outs"])]
        self._ready.extend(self._complete(p.batch, outs, wall))

    def _retire_oldest(self) -> None:
        pend = [w for w in self._alive_workers() if w.pending is not None]
        if pend:
            self._retire_worker(min(pend, key=lambda w: w.pending.t0))

    def _take_ready(self) -> List[Tuple[int, Ciphertext]]:
        out, self._ready = self._ready, []
        return out

    def _work_pending(self) -> bool:
        return bool(self._ready) or any(
            w.pending is not None for w in self._alive_workers())

    # ---- the serving loop (routed) ---------------------------------------

    def poll(self, flush: bool = False) -> List[Tuple[int, Ciphertext]]:
        """One frontend scheduling step: health-check workers, release
        at most one batch per the inherited flush policy, route it, and
        return whatever results have completed. Workers run one-deep
        pipelines — a routed batch is NOT awaited here; it retires when
        its worker is next needed (or at drain), so W workers hold W
        batches in flight while the frontend keeps assembling."""
        self._c_polls.inc()
        self._g_depth.set(self.queue.depth)
        self.metrics.record_depth(self.queue.depth)
        now = self._clock()
        self.check_workers()
        key, cause = self._choose_flush(flush, now)
        if key is None:
            # nothing to release — retire the oldest pipelined batch
            # instead (the monolith retires its in-flight step here)
            self._retire_oldest()
            return self._take_ready()
        b = self._pop_assemble(key, cause)
        while True:
            w = self._route(b)
            if w.pending is not None:
                self._retire_worker(w)        # free its pipeline slot
                if not w.alive:
                    continue                  # died on retire: re-route
            if self._dispatch_to(w, b):
                break
        return self._take_ready()

    def drain(self) -> Dict[int, Ciphertext]:
        results = super().drain()
        # retire any stragglers still pipelined on the workers
        for w in self._alive_workers():
            self._retire_worker(w)
        for rid, ct in self._take_ready():
            results[rid] = ct
        return results

    # ---- accounting ------------------------------------------------------

    def reset_metrics(self) -> None:
        super().reset_metrics()
        for w in self.workers:
            w.busy_s = 0.0
            w.served_requests = 0
            # NOT w.batches: the injector's kill schedule counts
            # lifetime dispatches

    def stats(self) -> dict:
        eng = {"steps_compiled": 0, "compile_s": 0.0}
        for w in self.workers:
            if w.transport.kind == "inproc":
                e = w.transport.worker.engine
                eng["steps_compiled"] += e.n_compiled
                eng["compile_s"] += e.compile_s
        eng["compile_s"] = round(eng["compile_s"], 3)
        return {
            **self.metrics.summary(),
            "cache": self.cache.stats(),
            "engine": eng,
            "mesh": dict(self.mesh.shape),
            "batch": self.batch,
            "flush_policy": {
                "max_age_s": self.max_age_s,
                "adaptive_target": self.adaptive_target,
                "bucket_target": self._bucket_target(),
                "overlap": False,
            },
            "scheduler": {"enabled": self.schedule,
                          "prefetch_tables": self.prefetch,
                          **self.scheduler.stats()},
            "submitted": self.queue.submitted,
            "frontend": {
                "transport": self.transport_kind,
                "workers": len(self.workers),
                "alive": len(self._alive_workers()),
                "deaths": self._c_deaths.value,
                "requeued_requests": self._c_requeued.value,
            },
            "workers": [w.stats() for w in self.workers],
        }

    def close(self) -> None:
        """Shut every worker down (subprocess transports exit their
        frame loops; in-process ones just drop)."""
        for w in self.workers:
            try:
                w.transport.close()
            except Exception:                 # noqa: BLE001 — best effort
                pass
            w.alive = False
