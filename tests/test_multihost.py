"""Multi-host serving tier: fault injection and telemetry merging.

The disaggregated frontend/worker split (repro.hserve.frontend /
worker / transport) must keep the serving contract under every failure
the tier is built for:

  - a worker killed MID-BATCH (computed but undelivered) requeues its
    in-flight requests and the stream re-serves bitwise identically;
  - a worker killed while holding the only warm table slices for a
    level re-routes to a cold worker (compile + slice load) — still
    bitwise;
  - a drain with every worker dead raises the typed
    ``NoLiveWorkersError`` instead of hanging;
  - heartbeat staleness (fake clock, no real sleeps) is a death signal
    equivalent to a broken transport;
  - per-worker telemetry (registry snapshots, step monitors, heartbeat
    payloads) never collides across publishers.

The worker-death requeue contract runs on BOTH the in-process 1-device
harness and the (2, 4) 8-device subprocess harness
(``run_in_8dev_subprocess``, tests/conftest.py).
"""

import numpy as np
import pytest

import jax

from repro.core import heaan as H
from repro.core import test_params as small_params
from repro.core.keys import keygen
from repro.core.rotate import rot_keygen
from repro.hserve import (
    HEFrontend, HEServer, NoLiveWorkersError, WorkerDied,
)
from repro.obs import MetricsRegistry, merge_snapshots
from repro.runtime.failures import FailureInjector
from repro.runtime.monitor import Heartbeat, StepMonitor
from repro.launch.mesh import make_mesh

PARAMS = small_params(logN=4, beta_bits=32)   # N=16, n_slots=8, L=5


def _mesh():
    return make_mesh((1, 1), ("data", "model"))


def _bitwise(a, b) -> bool:
    return bool((np.asarray(a.ax) == np.asarray(b.ax)).all()
                and (np.asarray(a.bx) == np.asarray(b.bx)).all())


class FakeClock:
    def __init__(self, t: float = 0.0):
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


@pytest.fixture(scope="module")
def keys():
    sk, pk, evk = keygen(PARAMS, seed=0)
    return sk, pk, evk


@pytest.fixture(scope="module")
def pool(keys):
    """Pre-encrypted operands at the top level and one level down."""
    _, pk, _ = keys
    rng = np.random.default_rng(0)
    n = PARAMS.n_slots_max
    top = [H.encrypt_message(rng.normal(size=n) + 1j * rng.normal(size=n),
                             pk, PARAMS, seed=i + 1) for i in range(4)]
    lo = [H.he_mod_down(c, PARAMS, PARAMS.logQ - PARAMS.logp)
          for c in top]
    return top, lo


def _submit_stream(srv, top, lo, n_each: int = 4):
    """The canonical two-level mul stream; returns the rid order."""
    rids = []
    for i in range(n_each):
        rids.append(srv.submit_mul(top[i % len(top)],
                                   top[(i + 1) % len(top)]))
        rids.append(srv.submit_mul(lo[i % len(lo)],
                                   lo[(i + 1) % len(lo)]))
    return rids


@pytest.fixture(scope="module")
def reference(keys, pool):
    """The monolithic HEServer's outputs for the canonical stream."""
    _, _, evk = keys
    top, lo = pool
    srv = HEServer(PARAMS, evk, mesh=_mesh(), batch=2)
    rids = _submit_stream(srv, top, lo)
    res = srv.drain()
    return [res[r] for r in rids]


# --------------------------------------------------------------------------
# fault injection (in-process, 1 device, fake clocks — no real sleeps)
# --------------------------------------------------------------------------

def test_worker_killed_mid_batch_requeues_and_reserves_bitwise(
        keys, pool, reference):
    """Worker 0 dies right after its first dispatch: the batch was
    computed but never delivered. The frontend must requeue the exact
    in-flight requests and the full stream must come back bitwise
    identical to the monolith."""
    _, _, evk = keys
    top, lo = pool
    fe = HEFrontend(PARAMS, evk, mesh=_mesh(), batch=2, workers=2,
                    injector=FailureInjector(kill_worker_at={0: 1}))
    rids = _submit_stream(fe, top, lo)
    res = fe.drain()
    assert all(_bitwise(res[r], ref) for r, ref in zip(rids, reference))
    fr = fe.stats()["frontend"]
    assert fr["deaths"] == 1
    assert fr["requeued_requests"] == 2     # one full batch
    assert fr["alive"] == 1
    fe.close()


def test_kill_worker_with_only_warm_slice_reroutes_cold_bitwise(
        keys, pool, reference):
    """After a warm-up that pins the low level's only warm slices on
    worker 0, killing it forces the re-route onto worker 1 — a cold
    compile + table-slice load — and results must stay bitwise."""
    _, _, evk = keys
    top, lo = pool
    fe = HEFrontend(PARAMS, evk, mesh=_mesh(), batch=2, workers=2)
    # warm exactly one batch at the low level -> only worker 0 warm
    fe.submit_mul(lo[0], lo[1])
    fe.submit_mul(lo[1], lo[2])
    fe.drain()
    warm = [w for w in fe.workers if w.keys_warm]
    assert [w.wid for w in warm] == [0]
    compiled_before = fe.workers[1].transport.worker.engine.n_compiled
    fe.workers[0].transport.kill()

    rids = _submit_stream(fe, top, lo)
    res = fe.drain()
    assert all(_bitwise(res[r], ref) for r, ref in zip(rids, reference))
    fr = fe.stats()["frontend"]
    assert fr["deaths"] == 1 and fr["alive"] == 1
    # worker 1 really did the cold work
    assert fe.workers[1].transport.worker.engine.n_compiled \
        > compiled_before
    assert all(k in fe.workers[1].keys_warm
               for k in fe.workers[0].keys_warm)
    fe.close()


def test_drain_with_all_workers_dead_raises_typed_error(keys, pool):
    """No live workers + queued work must be a typed, immediate error —
    never a hang waiting on replies that cannot come."""
    _, _, evk = keys
    top, lo = pool
    fe = HEFrontend(PARAMS, evk, mesh=_mesh(), batch=2, workers=2)
    for w in fe.workers:
        w.transport.kill()
    _submit_stream(fe, top, lo, n_each=1)
    with pytest.raises(NoLiveWorkersError) as ei:
        fe.drain()
    assert "no live workers" in str(ei.value)
    fe.close()


def test_heartbeat_timeout_declares_death_and_requeues(
        keys, pool, reference, tmp_path):
    """A worker whose heartbeat goes stale past the timeout is dead to
    the frontend: its in-flight batch requeues, and after the (test
    harness) revival the stream still serves bitwise. Pure fake clock —
    the test never sleeps."""
    _, _, evk = keys
    top, lo = pool
    clock = FakeClock()
    fe = HEFrontend(PARAMS, evk, mesh=_mesh(), batch=2, workers=2,
                    clock=clock, heartbeat_dir=str(tmp_path),
                    heartbeat_timeout=5.0)
    rids = _submit_stream(fe, top, lo)
    got = dict(fe.poll(flush=True))       # one batch lands on worker 0
    assert fe.workers[0].pending is not None

    clock.advance(6.0)                    # both beats now stale
    fe.check_workers()
    fr = fe.stats()["frontend"]
    assert fr["alive"] == 0 and fr["deaths"] == 2
    assert fr["requeued_requests"] == 2   # worker 0's in-flight batch

    # revive (in-process harness), re-beat on the advanced clock, and
    # the requeued stream must complete bitwise
    fe.revive_workers()
    for w in fe.workers:
        w.transport.worker._beat()
    res = fe.drain()
    res.update(got)
    assert all(_bitwise(res[r], ref) for r, ref in zip(rids, reference))
    fe.close()


def test_transport_kill_mid_batch_drops_computed_reply(keys, pool):
    """The in-process transport's kill() models death-after-compute:
    the reply exists, then vanishes — recv must raise WorkerDied."""
    _, _, evk = keys
    top, _ = pool
    fe = HEFrontend(PARAMS, evk, mesh=_mesh(), batch=2, workers=1)
    fe.submit_mul(top[0], top[1])
    fe.submit_mul(top[1], top[2])
    fe.poll(flush=True)                   # dispatch (reply buffered)
    w = fe.workers[0]
    assert w.pending is not None
    w.transport.kill()
    with pytest.raises(WorkerDied):
        w.transport.recv()
    fe.close()


# --------------------------------------------------------------------------
# subprocess transport (a real process boundary)
# --------------------------------------------------------------------------

def test_subprocess_workers_need_an_explicit_platform(keys):
    """A chip belongs to one process: the worker platform is never
    defaulted, so a subprocess frontend without one is refused before
    any process starts."""
    _, _, evk = keys
    with pytest.raises(ValueError, match="worker_platform"):
        HEFrontend(PARAMS, evk, transport="subprocess", workers=1)


def test_parent_on_cpu_does_not_count_as_holding_a_chip():
    from repro.hserve.transport import _parent_holds_accelerator
    assert jax.devices()[0].platform == "cpu"
    assert not _parent_holds_accelerator()


def test_subprocess_workers_serve_bitwise(keys, pool, reference):
    """One spawned worker process, frames over stdin/stdout: the same
    stream (muls at two levels + a rotate through an init-shipped key)
    must serve bitwise identical to the monolith."""
    sk, _, evk = keys
    top, lo = pool
    rk = {1: rot_keygen(PARAMS, sk, 1)}
    ref_srv = HEServer(PARAMS, evk, rot_keys=rk, mesh=_mesh(), batch=2)
    fe = HEFrontend(PARAMS, evk, rot_keys=rk, transport="subprocess",
                    worker_platform="cpu",
                    workers=1, batch=2)
    try:
        rids = _submit_stream(fe, top, lo, n_each=2)
        rot_rid = fe.submit_rotate(top[0], 1)
        res = fe.drain()

        ref_rids = _submit_stream(ref_srv, top, lo, n_each=2)
        ref_rot = ref_srv.submit_rotate(top[0], 1)
        ref_res = ref_srv.drain()
        assert all(_bitwise(res[r], ref_res[rr])
                   for r, rr in zip(rids, ref_rids))
        assert _bitwise(res[rot_rid], ref_res[ref_rot])
        assert fe.stats()["frontend"]["transport"] == "subprocess"
    finally:
        fe.close()


def test_subprocess_worker_respawn_restores_full_strength(keys, pool):
    """Worker restart/rejoin (the ROADMAP open item): a REAL subprocess
    worker is SIGKILLed mid-drain (after its first dispatch — the batch
    was computed but never delivered), the stream must complete on the
    survivor via requeue, and `revive_workers()` must respawn the dead
    process, replay the key/table init frame, and return the fleet to
    full strength — with the re-served stream bitwise identical and the
    respawned worker (blank interpreter, cold engine) really serving."""
    sk, _, evk = keys
    top, lo = pool
    rk = {1: rot_keygen(PARAMS, sk, 1)}

    ref_srv = HEServer(PARAMS, evk, rot_keys=rk, mesh=_mesh(), batch=2)
    ref_rids = _submit_stream(ref_srv, top, lo, n_each=2)
    ref_rot = ref_srv.submit_rotate(top[0], 1)
    ref_res = ref_srv.drain()

    fe = HEFrontend(PARAMS, evk, rot_keys=rk, transport="subprocess",
                    worker_platform="cpu",
                    workers=2, batch=2,
                    injector=FailureInjector(kill_worker_at={0: 1}))
    try:
        dead_proc = fe.workers[0].transport.proc
        rids = _submit_stream(fe, top, lo, n_each=2)
        res = fe.drain()                     # worker 0 dies mid-drain
        assert dead_proc.poll() is not None, "process still alive"
        fr = fe.stats()["frontend"]
        assert fr["deaths"] == 1 and fr["alive"] == 1
        assert fr["requeued_requests"] > 0
        assert all(_bitwise(res[r], ref_res[rr])
                   for r, rr in zip(rids, ref_rids))

        fe.revive_workers()
        assert fe.stats()["frontend"]["alive"] == 2
        w0 = fe.workers[0]
        assert w0.transport.proc is not dead_proc     # a NEW process
        assert w0.transport.alive
        assert w0.keys_warm == set()         # blank interpreter again

        rids = _submit_stream(fe, top, lo, n_each=2)
        rot_rid = fe.submit_rotate(top[0], 1)   # init replay shipped rk
        res = fe.drain()
        assert all(_bitwise(res[r], ref_res[rr])
                   for r, rr in zip(rids, ref_rids))
        assert _bitwise(res[rot_rid], ref_res[ref_rot])
        # full strength means the respawned worker actually served
        assert w0.keys_warm, "respawned worker never took a batch"
    finally:
        fe.close()


# --------------------------------------------------------------------------
# 8-device mesh: worker-death requeue on a sharded (2, 4) fleet
# --------------------------------------------------------------------------

def test_worker_death_requeue_on_8_device_mesh(run_in_8dev_subprocess):
    """The mid-batch kill contract on the sharded harness: a (2, 4)
    mesh frontend with two workers, worker 0 killed after its first
    dispatch — requeued stream bitwise identical to the monolith on
    the same mesh."""
    res = run_in_8dev_subprocess("""
        from repro.core import heaan as H
        from repro.core import test_params
        from repro.core.keys import keygen
        from repro.hserve import HEFrontend, HEServer
        from repro.runtime.failures import FailureInjector

        params = test_params(logN=5, beta_bits=32)
        sk, pk, evk = keygen(params, seed=0)
        mesh = make_mesh((2, 4), ("data", "model"))
        rng = np.random.default_rng(0)
        n = params.n_slots_max
        pool = [H.encrypt_message(
            rng.normal(size=n) + 1j * rng.normal(size=n), pk, params,
            seed=i + 1) for i in range(4)]
        lo = [H.he_mod_down(c, params, params.logQ - params.logp)
              for c in pool]

        def stream(srv):
            rids = []
            for i in range(4):
                rids.append(srv.submit_mul(pool[i % 4],
                                           pool[(i + 1) % 4]))
                rids.append(srv.submit_mul(lo[i % 4], lo[(i + 1) % 4]))
            return rids

        ref_srv = HEServer(params, evk, mesh=mesh, batch=2)
        ref_rids = stream(ref_srv)
        ref_res = ref_srv.drain()

        fe = HEFrontend(params, evk, mesh=mesh, batch=2, workers=2,
                        injector=FailureInjector(kill_worker_at={0: 1}))
        rids = stream(fe)
        res = fe.drain()
        ok = all(
            bool((np.asarray(res[r].ax)
                  == np.asarray(ref_res[rr].ax)).all()
                 and (np.asarray(res[r].bx)
                      == np.asarray(ref_res[rr].bx)).all())
            for r, rr in zip(rids, ref_rids))
        fr = fe.stats()["frontend"]
        print(json.dumps({
            "ok": ok, "devices": len(jax.devices()),
            "deaths": fr["deaths"],
            "requeued": fr["requeued_requests"],
            "alive": fr["alive"]}))
    """)
    assert res["devices"] == 8
    assert res["ok"], "requeued stream diverged on the 8-device mesh"
    assert res["deaths"] == 1
    assert res["requeued"] == 2
    assert res["alive"] == 1


# --------------------------------------------------------------------------
# telemetry merging under multi-publisher collisions
# --------------------------------------------------------------------------

def test_merge_snapshots_namespaces_colliding_labels():
    """Two workers both counting worker.batches (and both sourcing an
    "engine" sub-doc) must survive a merge without either clobbering
    the other."""
    r0, r1 = MetricsRegistry(), MetricsRegistry()
    r0.counter("worker.batches").inc(3)
    r1.counter("worker.batches").inc(5)
    r0.gauge("depth").set(1.0)
    r1.gauge("depth").set(2.0)
    r0.histogram("wall_s").add(0.1)
    r0.add_source("engine", lambda: {"steps_compiled": 1})
    r1.add_source("engine", lambda: {"steps_compiled": 7})

    doc = merge_snapshots({"worker0": r0.snapshot(),
                           "worker1": r1.snapshot()})
    assert doc["counters"]["worker0.worker.batches"] == 3
    assert doc["counters"]["worker1.worker.batches"] == 5
    assert doc["gauges"]["worker0.depth"] == 1.0
    assert doc["gauges"]["worker1.depth"] == 2.0
    assert "worker0.wall_s" in doc["histograms"]
    assert doc["worker0.engine"]["steps_compiled"] == 1
    assert doc["worker1.engine"]["steps_compiled"] == 7
    # top-level shape matches a single registry's snapshot
    assert set(doc) >= {"counters", "gauges", "histograms"}


def test_step_monitor_per_worker_children_are_independent():
    """One shared StepMonitor fed by two workers must not mix their
    step-time distributions: a straggling worker 1 may never make
    worker 0's normal steps read as breaches (or vice versa)."""
    mon = StepMonitor(warmup_steps=1, slack=2.0)
    # worker 0 runs 10ms steps, worker 1 runs 1s steps — wildly
    # different baselines that would poison a shared EMA
    for step in range(8):
        assert not mon.record(step, 0.010, worker=0)
        assert not mon.record(step, 1.0, worker=1)
    assert mon.for_worker(0).ema == pytest.approx(0.010, rel=1e-6)
    assert mon.for_worker(1).ema == pytest.approx(1.0, rel=1e-6)
    # a real breach still fires per publisher
    assert mon.record(99, 0.1, worker=0)
    assert not mon.record(99, 1.1, worker=1)
    # the shared baseline saw nothing
    assert mon.ema is None and mon.count == 0


def test_heartbeat_merges_multi_publisher_metrics(tmp_path):
    """A Heartbeat handed {publisher: registry} must namespace the
    embedded snapshot per publisher (and always write its first beat,
    whatever the interval)."""
    r0, r1 = MetricsRegistry(), MetricsRegistry()
    r0.counter("worker.batches").inc(2)
    r1.counter("worker.batches").inc(9)
    clock = FakeClock(100.0)
    hb = Heartbeat(str(tmp_path / "hb.json"), interval=10.0,
                   metrics={"worker0": r0, "worker1": r1}, clock=clock)
    hb.beat(step=0)                       # first beat always fires
    assert Heartbeat.is_alive(hb.path, timeout=5.0, now=100.1)
    assert not Heartbeat.is_alive(hb.path, timeout=5.0, now=200.0)

    import json as _json
    with open(hb.path) as f:
        doc = _json.load(f)
    assert doc["metrics"]["counters"]["worker0.worker.batches"] == 2
    assert doc["metrics"]["counters"]["worker1.worker.batches"] == 9
    # interval gating holds on the same clock
    r0.counter("worker.batches").inc()
    clock.advance(1.0)
    hb.beat(step=1)
    with open(hb.path) as f:
        assert _json.load(f)["step"] == 0   # gated: too soon
    clock.advance(10.0)
    hb.beat(step=2)
    with open(hb.path) as f:
        assert _json.load(f)["step"] == 2


def test_requeue_preserves_rids_and_fifo_order(pool):
    """RequestQueue.requeue puts the EXACT request objects back on
    their bucket (rids, t_submit, bookkeeping untouched)."""
    from repro.hserve import RequestQueue
    top, _ = pool
    q = RequestQueue()
    rids = [q.submit("mul", (top[i % 2], top[(i + 1) % 2]))
            for i in range(3)]
    key = ("mul", PARAMS.logQ, None)
    popped = q.pop_bucket(key, 3)
    assert [r.rid for r in popped] == rids
    submitted_before = q.submitted
    q.requeue(popped)
    assert q.submitted == submitted_before    # not re-counted
    again = q.pop_bucket(key, 3)
    assert [r.rid for r in again] == rids
    assert again[0] is popped[0]              # same objects, not copies
