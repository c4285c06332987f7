"""repro.obs tests: bounded reservoirs, the metrics registry, tracer
span semantics under a fake clock, StageTimer attribution, StepMonitor
re-anchoring, the offline report, and the traced+profiled serving path
(bitwise vs plain serving, all eight lifecycle phases, schema-valid
trace events).

The 8-device lifecycle check runs through the shared
run_in_8dev_subprocess harness (tests/conftest.py): a fresh interpreter
with XLA_FLAGS=--xla_force_host_platform_device_count=8.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax

from repro.core import heaan as H
from repro.core import test_params as small_params
from repro.core.keys import keygen
from repro.core.rotate import rot_keygen
from repro.hserve import HEServer, ServeMetrics
from repro.obs import MetricsRegistry, Reservoir, StageTimer, Tracer
from repro.obs.report import analyze, format_report, load_events
from repro.obs.trace import _NULL_SPAN
from repro.runtime.monitor import Heartbeat, StepMonitor
from repro.launch.mesh import make_mesh

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PARAMS = small_params(logN=4, beta_bits=32)   # N=16, n_slots=8, L=5

EVENT_KEYS = ("pid", "tid", "ts", "dur", "name", "cat")
LIFECYCLE = {"submit", "enqueue", "bucket_wait", "flush",
             "batch_assemble", "dispatch", "device_wall", "complete"}


@pytest.fixture(scope="module")
def keys():
    sk, pk, evk = keygen(PARAMS, seed=0)
    return sk, pk, evk, {1: rot_keygen(PARAMS, sk, 1)}


def _enc(pk, seed, n=8):
    rng = np.random.default_rng(seed)
    z = rng.normal(size=n) + 1j * rng.normal(size=n)
    return H.encrypt_message(z, pk, PARAMS, seed=seed)


def _bitwise(a, b):
    return bool((np.asarray(a.ax) == np.asarray(b.ax)).all()
                and (np.asarray(a.bx) == np.asarray(b.bx)).all())


class _FakeClock:
    """Deterministic clock: advances by `tick` on every read."""

    def __init__(self, tick=1.0):
        self.t = 0.0
        self.tick = tick

    def __call__(self):
        t, self.t = self.t, self.t + self.tick
        return t


# --------------------------------------------------------------------------
# Reservoir: bounded memory, exact moments, sampled quantiles
# --------------------------------------------------------------------------

def test_reservoir_bounded_with_exact_moments_and_close_quantiles():
    """50k lognormal samples through a 4096-slot reservoir: memory stays
    at capacity, count/total/min/max are EXACT, p50/p99 land within a
    few percent of the exact numpy percentiles."""
    rng = np.random.default_rng(0)
    xs = rng.lognormal(mean=0.0, sigma=0.75, size=50_000)
    r = Reservoir(capacity=4096)
    r.extend(xs)
    assert r.sample_size == 4096                 # the memory ceiling
    assert r.count == 50_000
    assert r.min == xs.min() and r.max == xs.max()
    np.testing.assert_allclose(r.total, xs.sum())
    np.testing.assert_allclose(r.mean, xs.mean())
    assert abs(r.percentile(50) / np.percentile(xs, 50) - 1) < 0.05
    assert abs(r.percentile(99) / np.percentile(xs, 99) - 1) < 0.10
    s = r.summary()
    assert s["count"] == 50_000 and s["max"] == xs.max()


def test_reservoir_under_capacity_is_exact_and_deterministic():
    xs = [5.0, 1.0, 3.0, 2.0, 4.0]
    r = Reservoir(capacity=16)
    r.extend(xs)
    assert r.sample_size == 5
    assert r.percentile(50) == np.percentile(xs, 50)
    assert r.percentile(99) == np.percentile(xs, 99)
    # fixed seed: two identical streams summarize identically even past
    # capacity (telemetry must not jitter between identical runs)
    a, b = Reservoir(capacity=8), Reservoir(capacity=8)
    stream = list(np.random.default_rng(1).normal(size=1000))
    a.extend(stream)
    b.extend(stream)
    assert a.summary() == b.summary()
    with pytest.raises(ValueError):
        Reservoir(capacity=0)


def test_serve_metrics_memory_is_bounded():
    """Regression for the unbounded-list leak: ServeMetrics used to
    keep every latency and queue-depth sample forever. Stream far more
    than the reservoir capacity and pin the retained footprint."""
    m = ServeMetrics()
    lat = [0.001 * (i % 7 + 1) for i in range(8)]
    for i in range(3000):                        # 24k latency samples
        m.record_batch("mul", 240, 8, 0, 0.01, lat)
        m.record_depth(i % 50)
    for i in range(2000):
        m.record_depth(i)
    st = m._ops["mul"].latencies
    assert st.count == 24_000
    assert st.sample_size <= st.capacity == 4096
    assert m._depths.count == 5000
    assert m._depths.sample_size <= m._depths.capacity
    s = m.summary()
    assert s["per_op"]["mul"]["requests"] == 24_000
    # max latency is exact even though the sample is bounded
    assert s["per_op"]["mul"]["latency_ms"]["max"] == \
        pytest.approx(1e3 * max(lat))


# --------------------------------------------------------------------------
# StepMonitor: breach-streak re-anchoring (degrade then stabilize)
# --------------------------------------------------------------------------

def test_step_monitor_degrades_then_stabilizes():
    """A permanent 10× degradation: alerts fire, then after 8
    consecutive breaches the baseline re-anchors in CAPPED stages
    (4× per jump) until the new normal stops breaching — with every
    re-anchor logged for the launcher's escalation policy."""
    mon = StepMonitor(ema_alpha=0.1, slack=2.0, warmup_steps=3,
                      reanchor_after=8, reanchor_cap=4.0)
    step = 0
    for _ in range(3):                           # warmup → ema = 1.0
        step += 1
        assert not mon.record(step, 1.0)
    assert mon.ema == 1.0

    breaches = []
    for _ in range(20):                          # the pod now runs at 10×
        step += 1
        breaches.append(mon.record(step, 10.0))
    # first 8 breach → re-anchor capped at 4×·1.0 = 4.0 (not straight
    # to 10.0: one jump may never absorb an unbounded regression)
    assert mon.reanchors[0][1:] == (1.0, 4.0)
    # next 8 still breach (10 > 2·4) → second re-anchor reaches the
    # streak minimum, the true new normal
    assert mon.reanchors[1][1:] == (4.0, 10.0)
    assert len(mon.reanchors) == 2
    assert sum(breaches) == 16                   # then the alerts quiesce
    assert not breaches[-1]

    step += 1
    assert not mon.record(step, 10.0)            # stabilized at the new normal
    step += 1
    assert mon.record(step, 25.0)                # ...but still alerts on fresh
    assert len(mon.reanchors) == 2               # degradation, no re-anchor


def test_step_monitor_transient_breach_resets_streak():
    mon = StepMonitor(ema_alpha=0.1, slack=2.0, warmup_steps=3,
                      reanchor_after=8)
    for i in range(3):
        mon.record(i, 1.0)
    for i in range(5):                           # transient: under the streak
        assert mon.record(10 + i, 5.0)
    assert mon.record(20, 1.0) is False          # recovery resets the streak
    for i in range(7):
        assert mon.record(30 + i, 5.0)
    assert mon.reanchors == []                   # 5 + 7 but never 8 in a row
    assert mon.ema == pytest.approx(1.0)         # EMA froze during breaches


# --------------------------------------------------------------------------
# Tracer: span nesting, schema, disabled fast path
# --------------------------------------------------------------------------

def test_tracer_span_nesting_under_fake_clock():
    clk = _FakeClock(tick=1.0)                   # t0 = 0
    tr = Tracer(clock=clk)
    with tr.span("outer", cat="test", lane="a"):          # opens at t=1
        with tr.span("inner", cat="test", lane="a"):      # opens at t=2
            pass                                          # closes at t=3
    xs = [e for e in tr.events if e["ph"] == "X"]
    assert [e["name"] for e in xs] == ["inner", "outer"]  # inner closes first
    inner, outer = xs
    assert inner["ts"] == pytest.approx(2e6)     # µs relative to t0
    assert inner["dur"] == pytest.approx(1e6)
    assert outer["ts"] == pytest.approx(1e6)
    assert outer["dur"] == pytest.approx(3e6)    # envelops the inner span
    assert inner["tid"] == outer["tid"]          # one lane, one tid


def test_tracer_every_event_carries_the_full_key_set():
    """Schema contract: EVERY element of traceEvents — including "M"
    thread_name metadata — has pid/tid/ts/dur/name/cat."""
    tr = Tracer(clock=_FakeClock())
    tr.instant("i", cat="test", lane="a")
    with tr.span("s", cat="test", lane="b", args={"k": 1}):
        pass
    tr.event("e", cat="test", lane="a", ts=0.5, dur=0.25)
    doc = tr.to_chrome()
    assert doc["displayTimeUnit"] == "ms"
    assert len(doc["traceEvents"]) == 5          # 3 events + 2 lane metadata
    for e in doc["traceEvents"]:
        assert all(k in e for k in EVENT_KEYS), e
        assert e["ph"] in ("X", "M")
    # lanes intern to stable small-int tids with exactly one metadata
    # record each
    metas = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    assert sorted(m["args"]["name"] for m in metas) == ["a", "b"]
    assert {m["tid"] for m in metas} == {0, 1}


def test_disabled_tracer_allocates_nothing():
    """The no-trace serving default: span() hands back one shared
    singleton (no per-request Span objects) and records nothing."""
    tr = Tracer(enabled=False)
    spans = [tr.span(f"s{i}", cat="c", lane="l") for i in range(100)]
    assert all(s is _NULL_SPAN for s in spans)   # identity, not equality
    for s in spans:
        with s:
            pass
        s.end(extra=1)                           # no-op, no error
    tr.instant("i", cat="c", lane="l")
    tr.event("e", cat="c", lane="l", ts=0.0)
    assert len(tr) == 0 and tr.events == []


def test_tracer_caps_retained_events():
    tr = Tracer(clock=_FakeClock(), max_events=3)
    for i in range(5):
        tr.instant(f"e{i}", cat="c", lane="l")
    assert len(tr) == 3                          # 1 lane metadata + 2 events
    assert tr.dropped == 3
    tr.clear()
    assert len(tr) == 0 and tr.dropped == 0
    tr.instant("fresh", cat="c", lane="l")       # records again after clear
    assert len(tr) == 2


def test_obs_package_imports_without_jax():
    """Import contract: the frontend metrics path must be loadable on a
    jax-free host (jax only loads lazily inside StageTimer.timed)."""
    code = ("import sys; import repro.obs; "
            "print('jax' in sys.modules)")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", code],
                         capture_output=True, text=True, env=env,
                         timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip() == "False"


# --------------------------------------------------------------------------
# StageTimer: attribution scoping, pausing, tracer coupling
# --------------------------------------------------------------------------

def test_stage_timer_attribution_and_regions():
    clk = _FakeClock(tick=0.5)
    tr = Tracer(clock=clk)
    st = StageTimer(tracer=tr, clock=clk)
    with st.op("mul"):
        assert st.timed("crt", lambda: 7) == 7   # returns the thunk's value
        st.timed("ntt", lambda: None)
        with st.region("region1"):
            st.timed("modmul", lambda: None)
    with st.op("rotate"):
        st.timed("ntt", lambda: None)
    s = st.summary()
    # every timed() call spans exactly two clock reads → 0.5 s each
    assert s["stages"]["mul"] == {"crt": 0.5, "ntt": 0.5,
                                  "modmul": 0.5, "icrt": 0.0}
    assert s["calls"]["mul"]["crt"] == 1
    assert s["stages"]["rotate"]["ntt"] == 0.5
    assert st.stage_total("mul") == pytest.approx(1.5)
    assert st.stage_total("absent") == 0.0
    # the region envelops its inner stage (region wall > stage wall)
    assert s["regions"]["mul"]["region1"] >= 0.5
    # stage spans landed on the tracer's "stage" lane, tagged by op
    stage_evs = [e for e in tr.events
                 if e["ph"] == "X" and e["cat"] == "stage"]
    assert {(e["name"], e["args"]["op"]) for e in stage_evs} == {
        ("crt", "mul"), ("ntt", "mul"), ("modmul", "mul"),
        ("region1", "mul"), ("ntt", "rotate")}
    with pytest.raises(ValueError):
        st.timed("keyswitch", lambda: None)
    st.reset()
    assert st.summary() == {"stages": {}, "calls": {}, "regions": {}}


def test_stage_timer_pause_suppresses_recording():
    st = StageTimer(clock=_FakeClock())
    with st.op("mul"), st.pause():               # warm-up runs book nothing
        assert st.timed("crt", lambda: 3) == 3
        with st.region("region1"):
            pass
    assert st.stage_total("mul") == 0.0
    assert st.summary()["regions"] == {}


# --------------------------------------------------------------------------
# MetricsRegistry + heartbeat embedding
# --------------------------------------------------------------------------

def test_registry_snapshot_instruments_and_sources():
    reg = MetricsRegistry(histogram_capacity=8)
    reg.counter("serve.polls").inc()
    reg.counter("serve.polls").inc(4)            # same name → same handle
    reg.gauge("serve.queue.depth").set(7)
    h = reg.histogram("serve.batch.wall_s")
    h.extend([0.1, 0.2, 0.3])
    reg.add_source("cache", lambda: {"hits": 3})
    snap = reg.snapshot()
    assert snap["counters"] == {"serve.polls": 5}
    assert snap["gauges"] == {"serve.queue.depth": 7.0}
    assert snap["histograms"]["serve.batch.wall_s"]["count"] == 3
    assert snap["cache"] == {"hits": 3}
    # replacement is deliberate (reset_metrics re-registers): last wins
    reg.add_source("cache", lambda: {"hits": 0})
    assert reg.snapshot()["cache"] == {"hits": 0}
    reg.remove_source("cache")
    assert "cache" not in reg.snapshot()


def test_registry_snapshot_captures_source_failures_inline():
    """A raising source must not poison the whole health snapshot."""
    reg = MetricsRegistry()

    def bad():
        raise RuntimeError("stats exploded")

    reg.add_source("bad", bad)
    reg.add_source("good", lambda: {"ok": True})
    snap = reg.snapshot()
    assert snap["good"] == {"ok": True}
    assert snap["bad"] == {"error": "RuntimeError: stats exploded"}


def test_heartbeat_embeds_registry_snapshot(tmp_path):
    reg = MetricsRegistry()
    reg.counter("serve.requests").inc(9)
    path = str(tmp_path / "hb.json")
    hb = Heartbeat(path, interval=0.0, metrics=reg)
    hb.beat(3, payload={"loss": 0.5})
    with open(path) as f:
        doc = json.load(f)
    assert doc["step"] == 3 and doc["loss"] == 0.5
    assert doc["metrics"]["counters"]["serve.requests"] == 9
    assert Heartbeat.is_alive(path, timeout=60.0)


# --------------------------------------------------------------------------
# offline report
# --------------------------------------------------------------------------

def test_report_aggregates_stage_and_lifecycle_events(tmp_path):
    def ev(name, cat, dur_s, **args):
        return {"pid": 1, "tid": 0, "ts": 0.0, "dur": dur_s * 1e6,
                "name": name, "cat": cat, "ph": "X", "args": args}

    doc = {"traceEvents": [
        {"pid": 1, "tid": 0, "ts": 0.0, "dur": 0.0, "name": "thread_name",
         "cat": "__metadata", "ph": "M", "args": {"name": "stage"}},
        ev("crt", "stage", 0.010, op="mul"),
        ev("ntt", "stage", 0.030, op="mul"),
        ev("ntt", "stage", 0.020, op="mul"),     # fwd + inverse both book
        ev("modmul", "stage", 0.015, op="mul"),
        ev("icrt", "stage", 0.005, op="mul"),
        ev("region2", "stage", 0.040, op="mul"),
        ev("bucket_wait", "lifecycle", 0.200, op="mul"),
        ev("device_wall", "lifecycle", 0.090, op="mul"),
        ev("complete", "lifecycle", 0.0, op="mul", latency_s=0.3),
        ev("complete", "lifecycle", 0.0, op="mul", latency_s=0.1),
    ], "displayTimeUnit": "ms"}
    path = str(tmp_path / "trace.json")
    with open(path, "w") as f:
        json.dump(doc, f)

    events = load_events(path)
    assert all(e["ph"] == "X" for e in events)   # metadata filtered out
    a = analyze(events)
    assert a["stages"]["mul"] == pytest.approx(
        {"crt": 0.010, "ntt": 0.050, "modmul": 0.015, "icrt": 0.005})
    assert a["regions"]["mul"]["region2"] == pytest.approx(0.040)
    assert a["queue_wait"]["mul"] == {
        "total_s": pytest.approx(0.2), "n": 1}
    assert a["device_wall"]["mul"]["batches"] == 1
    assert a["complete"]["mul"]["n"] == 2
    assert a["complete"]["mul"]["latency_total_s"] == pytest.approx(0.4)
    rep = format_report(a)
    assert "Fig. 3 stage attribution" in rep
    assert "queue wait vs device wall" in rep
    assert "mul" in rep


# --------------------------------------------------------------------------
# end to end: traced + stage-profiled serving
# --------------------------------------------------------------------------

def _drive(server, pk):
    cts = [_enc(pk, i) for i in range(1, 5)]
    rids = [server.submit_mul(cts[0], cts[1]),
            server.submit_mul(cts[2], cts[3]),
            server.submit_rotate(cts[0], 1)]
    res = server.drain()
    return [res[r] for r in rids]


def test_traced_profiled_serving_is_bitwise_with_full_lifecycle(keys):
    """`tracer + profile_stages` serving returns bit-identical
    ciphertexts to the plain fused path, records every lifecycle phase
    with schema-valid events, books Fig. 3 stage time for every staged
    op, and snapshots the whole stack through one registry."""
    _, pk, evk, rks = keys
    mesh = make_mesh((1, 1), ("data", "model"))
    tr = Tracer()
    srv = HEServer(PARAMS, evk, rks, mesh=mesh, batch=2,
                   tracer=tr, profile_stages=True)
    outs = _drive(srv, pk)
    plain = HEServer(PARAMS, evk, rks, mesh=mesh, batch=2)
    outs0 = _drive(plain, pk)
    assert all(_bitwise(a, b) for a, b in zip(outs, outs0))

    xs = [e for e in tr.events if e["ph"] == "X"]
    names = {e["name"] for e in xs}
    assert LIFECYCLE <= names                    # all eight phases
    assert all(all(k in e for k in EVENT_KEYS) for e in tr.events)

    st = srv.engine.stage_timer
    summ = st.summary()
    per_op = srv.metrics.summary()["per_op"]
    for op in ("mul", "rotate"):
        assert st.stage_total(op) > 0.0
        assert st.stage_total(op) <= per_op[op]["wall_s"]
    # mul books both Fig. 2 regions and all four Fig. 3 buckets
    assert set(summ["regions"]["mul"]) == {"region1", "region2"}
    assert all(v > 0.0 for v in summ["stages"]["mul"].values())
    # rotate has no ciphertext-product region and no region-1 modmul
    assert summ["stages"]["rotate"]["modmul"] > 0.0   # key switch only

    snap = srv.registry.snapshot()
    for key in ("counters", "gauges", "histograms", "serve", "cache",
                "scheduler", "engine"):
        assert key in snap, key
    assert snap["counters"]["serve.requests"] == 3
    assert snap["histograms"]["serve.batch.wall_s"]["count"] >= 2
    # the server's stats() surface carries the stage summary too
    assert srv.stats()["stages"]["stages"]["mul"]["ntt"] > 0.0


def test_trace_roundtrips_through_the_offline_report(tmp_path, keys):
    _, pk, evk, rks = keys
    mesh = make_mesh((1, 1), ("data", "model"))
    tr = Tracer()
    srv = HEServer(PARAMS, evk, rks, mesh=mesh, batch=2,
                   tracer=tr, profile_stages=True)
    _drive(srv, pk)
    path = str(tmp_path / "trace.json")
    n = tr.write(path)
    assert n == len(tr.events)
    a = analyze(load_events(path))
    assert a["stages"]["mul"]["ntt"] > 0.0
    assert a["complete"]["mul"]["n"] == 2
    assert a["device_wall"]["mul"]["batches"] >= 1
    assert a["queue_wait"]["mul"]["n"] == 2
    assert "mul" in format_report(a)


def test_session_publishes_client_counters(keys):
    from repro.client import HESession
    sk, pk, evk = keygen(PARAMS, seed=0)
    mesh = make_mesh((1, 1), ("data", "model"))
    s = HESession(PARAMS, sk, pk, evk, mesh=mesh, batch=2)
    x = s.encrypt(0.5 * np.ones(8), seed=3)
    f = s.run([x * x])[0]
    f.result()
    snap = s.server.registry.snapshot()
    assert snap["counters"]["client.runs"] == 1
    assert snap["counters"]["client.circuits"] == 1


# --------------------------------------------------------------------------
# 8-device mesh: full lifecycle under sharded serving
# --------------------------------------------------------------------------

def test_traced_serving_on_8_device_mesh_records_all_phases(
        run_in_8dev_subprocess):
    """Sharded (2, 4)-mesh serving with the tracer and stage profiler
    on: results stay bitwise vs the core references, every one of the
    eight lifecycle phases lands in the trace, every event carries the
    full key set, and mul books stage time."""
    res = run_in_8dev_subprocess("""
        from repro.core import heaan as H
        from repro.core import test_params
        from repro.core.keys import keygen
        from repro.core.rotate import he_rotate, rot_keygen
        from repro.hserve import HEServer
        from repro.obs import Tracer

        params = test_params(logN=5, beta_bits=32)
        sk, pk, evk = keygen(params, seed=0)
        rks = {1: rot_keygen(params, sk, 1)}
        mesh = make_mesh((2, 4), ("data", "model"))
        tr = Tracer()
        server = HEServer(params, evk, rks, mesh=mesh, batch=2,
                          tracer=tr, profile_stages=True)

        rng = np.random.default_rng(7)
        def enc(seed):
            z = rng.normal(size=16) + 1j * rng.normal(size=16)
            return H.encrypt_message(z, pk, params, seed=seed)

        c1, c2, c3 = enc(1), enc(2), enc(3)
        rid_m = server.submit_mul(c1, c2)
        rid_r = server.submit_rotate(c3, 1)
        res = server.drain()
        ok_mul = res[rid_m]
        ok_rot = res[rid_r]
        ref_mul = H.he_mul(c1, c2, evk, params)
        ref_rot = he_rotate(c3, 1, rks[1], params)
        def bitwise(a, b):
            return bool((np.asarray(a.ax) == np.asarray(b.ax)).all()
                        and (np.asarray(a.bx) == np.asarray(b.bx)).all())
        keys = ("pid", "tid", "ts", "dur", "name", "cat")
        st = server.engine.stage_timer
        print(json.dumps({
            "devices": jax.device_count(),
            "bitwise": bitwise(ok_mul, ref_mul) and bitwise(ok_rot,
                                                            ref_rot),
            "names": sorted({e["name"] for e in tr.events
                             if e["ph"] == "X"}),
            "bad_events": sum(1 for e in tr.events
                              if not all(k in e for k in keys)),
            "stage_mul_s": st.stage_total("mul"),
        }))
    """)
    assert res["devices"] == 8
    assert res["bitwise"] is True
    assert res["bad_events"] == 0
    assert LIFECYCLE <= set(res["names"])
    assert res["stage_mul_s"] > 0.0
