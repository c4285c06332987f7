"""repro.obs tests: bounded reservoirs, the metrics registry, tracer
span semantics under a fake clock, the Fig. 3 stage scopes of the
compiled mul step, the profiler mirror of the server's spans,
StepMonitor re-anchoring, the offline report, and the traced serving
path (bitwise vs plain serving, all eight lifecycle phases, the spans of
one batch, schema-valid trace events).

The 8-device lifecycle check runs through the shared
run_in_8dev_subprocess harness (tests/conftest.py): a fresh interpreter
with XLA_FLAGS=--xla_force_host_platform_device_count=8.
"""

import contextlib
import glob
import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

import jax

from repro.core import heaan as H
from repro.core import test_params as small_params
from repro.core.context import make_context
from repro.core.keys import keygen
from repro.core.rotate import rot_keygen
from repro.dist.he_pipeline import (he_input_specs, he_static,
                                    make_he_mul_step, runtime_tables)
from repro.hserve import HEServer, ServeMetrics
from repro.obs import MetricsRegistry, Reservoir, Tracer
from repro.obs.report import analyze, format_report, load_events
from repro.obs.trace import _NULL_SPAN
from repro.runtime.monitor import Heartbeat, StepMonitor
from repro.launch.mesh import make_mesh

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PARAMS = small_params(logN=4, beta_bits=32)   # N=16, n_slots=8, L=5

EVENT_KEYS = ("pid", "tid", "ts", "dur", "name", "cat")
LIFECYCLE = {"submit", "enqueue", "bucket_wait", "flush",
             "batch_assemble", "dispatch", "device_wall", "complete"}
# the spans of one batch through HEServer.poll on the synchronous path
BATCH_SPANS = {"poll", "batch_assemble", "dispatch", "h2d", "launch",
               "wait", "retire"}


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
    jax-free host (the tracer's profiler mirror uses jax only once
    something else has imported it)."""
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
# Fig. 3 stage scopes in the compiled step; the profiler mirror of spans
# --------------------------------------------------------------------------

def _compiled_mul_hlo():
    """Optimized HLO text of the toy-size fused mul step (CPU)."""
    _, _, evk = keygen(PARAMS, seed=0)
    st = he_static(PARAMS, PARAMS.logQ)
    mesh = make_mesh((1, 1), ("data", "model"))
    t1, t2, ek = runtime_tables(make_context(PARAMS, PARAMS.logQ), evk)
    return jax.jit(make_he_mul_step(st, mesh)).lower(
        t1, t2, ek, *he_input_specs(st, 2)).compile().as_text()


@pytest.fixture(scope="module")
def mul_hlo():
    return _compiled_mul_hlo()


@pytest.mark.parametrize("stage", ["crt", "ntt", "intt", "modmul", "icrt"])
def test_stage_scope_in_compiled_mul_step(mul_hlo, stage):
    """Each Fig. 3 stage scope reaches the op_name metadata of the
    compiled mul step's ops, inside one of the two Fig. 2 regions."""
    names = re.findall(r'op_name="([^"]*)"', mul_hlo)
    scoped = [n for n in names if f"he.{stage}/" in n + "/"]
    assert scoped, f"no op of the compiled step is under he.{stage}"
    assert all("he.region1/" in n or "he.region2/" in n for n in scoped)


def test_stage_scopes_leave_the_compiled_program_unchanged(mul_hlo,
                                                           monkeypatch):
    """Scopes are metadata only: with named_scope a no-op the compiled
    step is the same program, instruction for instruction."""
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare = _compiled_mul_hlo()

    def program(text):
        body = text[text.index("\n%"):]        # after the frame tables
        return re.sub(r", metadata=\{[^}]*\}", "", body)

    assert "he.crt" not in bare and "he.crt" in mul_hlo
    assert program(bare) == program(mul_hlo)


def test_server_spans_are_mirrored_into_the_profiler(tmp_path, keys):
    """A CPU jax.profiler trace of one served batch holds the tracer's
    spans as hserve.* host events, on the profiler's clock."""
    from jax.profiler import ProfileData
    _, pk, evk, _ = keys
    mesh = make_mesh((1, 1), ("data", "model"))
    srv = HEServer(PARAMS, evk, mesh=mesh, batch=2, tracer=Tracer())
    c1, c2 = _enc(pk, 1), _enc(pk, 2)
    srv.submit_mul(c1, c2)
    srv.drain()                                  # warm: compile outside
    srv.submit_mul(c1, c2)
    srv.submit_mul(c2, c1)
    jax.profiler.start_trace(str(tmp_path))
    assert len(srv.poll()) == 2
    jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    host = {e.name for p in ProfileData.from_file(path).planes
            if p.name.startswith("/host:") for ln in p.lines
            for e in ln.events}
    want = {"hserve." + n for n in BATCH_SPANS}
    assert want <= host, sorted(want - host)
    # explicit-timestamp lifecycle events stay JSON-only
    assert "hserve.device_wall" not in host


def test_h2d_bytes_counts_the_placed_arrays(keys):
    """engine.h2d_bytes grows by exactly the bytes of the batch arrays
    handed to device_put."""
    _, pk, evk, _ = keys
    mesh = make_mesh((1, 1), ("data", "model"))
    srv = HEServer(PARAMS, evk, mesh=mesh, batch=2)
    c1, c2 = _enc(pk, 1), _enc(pk, 2)
    srv.submit_mul(c1, c2)
    srv.drain()
    counter = srv.registry.counter("engine.h2d_bytes")
    before = counter.value
    srv.submit_mul(c1, c2)
    b = srv._pop_assemble(srv.queue.any_key(), "drain")
    placed = srv.engine._place(b)
    assert counter.value - before == sum(
        np.asarray(v).nbytes for v in placed.values())
    assert counter.value - before == 4 * 2 * PARAMS.N \
        * PARAMS.qlimbs(PARAMS.logQ) * 4
    assert srv.registry.snapshot()["counters"]["engine.h2d_bytes"] \
        == counter.value


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
         "cat": "__metadata", "ph": "M", "args": {"name": "server"}},
        ev("poll", "server", 0.300),             # spans: not in the table
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
    assert set(a) == {"queue_wait", "device_wall", "complete"}
    assert a["queue_wait"]["mul"] == {
        "total_s": pytest.approx(0.2), "n": 1}
    assert a["device_wall"]["mul"]["batches"] == 1
    assert a["complete"]["mul"]["n"] == 2
    assert a["complete"]["mul"]["latency_total_s"] == pytest.approx(0.4)
    rep = format_report(a)
    assert "Fig. 3" not in rep
    assert "queue wait vs device wall" in rep
    assert "mul" in rep


# --------------------------------------------------------------------------
# end to end: traced serving
# --------------------------------------------------------------------------

def _drive(server, pk):
    cts = [_enc(pk, i) for i in range(1, 5)]
    rids = [server.submit_mul(cts[0], cts[1]),
            server.submit_mul(cts[2], cts[3]),
            server.submit_rotate(cts[0], 1)]
    res = server.drain()
    return [res[r] for r in rids]


def test_traced_profiled_serving_is_bitwise_with_full_lifecycle(keys):
    """Traced serving returns bit-identical ciphertexts to the untraced
    path, records every lifecycle phase and the spans of each batch
    through poll with schema-valid events, and snapshots the whole stack
    through one registry."""
    _, pk, evk, rks = keys
    mesh = make_mesh((1, 1), ("data", "model"))
    tr = Tracer()
    srv = HEServer(PARAMS, evk, rks, mesh=mesh, batch=2, tracer=tr)
    outs = _drive(srv, pk)
    plain = HEServer(PARAMS, evk, rks, mesh=mesh, batch=2)
    outs0 = _drive(plain, pk)
    assert all(_bitwise(a, b) for a, b in zip(outs, outs0))

    xs = [e for e in tr.events if e["ph"] == "X"]
    names = {e["name"] for e in xs}
    assert LIFECYCLE <= names                    # all eight phases
    assert BATCH_SPANS | {"warm_compile"} <= names
    assert all(all(k in e for k in EVENT_KEYS) for e in tr.events)

    # one poll, launch, wait and retire span per batch (three batches:
    # mul, mul, rotate at batch 2), nested inside their poll
    per_op = srv.metrics.summary()["per_op"]
    n_batches = sum(d["batches"] for d in per_op.values())
    by_name = {}
    for e in xs:
        by_name.setdefault(e["name"], []).append(e)
    for name in ("launch", "wait", "retire"):
        assert len(by_name[name]) == n_batches, name
    polls = by_name["poll"]
    for e in by_name["retire"] + by_name["h2d"]:
        assert any(p["ts"] <= e["ts"] and e["ts"] + e["dur"]
                   <= p["ts"] + p["dur"] for p in polls)

    snap = srv.registry.snapshot()
    for key in ("counters", "gauges", "histograms", "serve", "cache",
                "scheduler", "engine"):
        assert key in snap, key
    assert snap["counters"]["serve.requests"] == 3
    assert snap["histograms"]["serve.batch.wall_s"]["count"] >= 2
    assert snap["counters"]["engine.h2d_bytes"] > 0
    assert "stages" not in srv.stats()


def test_trace_roundtrips_through_the_offline_report(tmp_path, keys):
    _, pk, evk, rks = keys
    mesh = make_mesh((1, 1), ("data", "model"))
    tr = Tracer()
    srv = HEServer(PARAMS, evk, rks, mesh=mesh, batch=2, tracer=tr)
    _drive(srv, pk)
    path = str(tmp_path / "trace.json")
    n = tr.write(path)
    assert n == len(tr.events)
    events = load_events(path)
    assert BATCH_SPANS <= {e["name"] for e in events}
    a = analyze(events)
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
    """Sharded (2, 4)-mesh serving with the tracer on: results stay
    bitwise vs the core references, every one of the eight lifecycle
    phases and the spans of each batch through poll land in the trace,
    and every event carries the full key set."""
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
                          tracer=tr)

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
        print(json.dumps({
            "devices": jax.device_count(),
            "bitwise": bitwise(ok_mul, ref_mul) and bitwise(ok_rot,
                                                            ref_rot),
            "names": sorted({e["name"] for e in tr.events
                             if e["ph"] == "X"}),
            "bad_events": sum(1 for e in tr.events
                              if not all(k in e for k in keys)),
        }))
    """)
    assert res["devices"] == 8
    assert res["bitwise"] is True
    assert res["bad_events"] == 0
    assert LIFECYCLE <= set(res["names"])
    assert BATCH_SPANS <= set(res["names"])
