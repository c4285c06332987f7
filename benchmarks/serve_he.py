"""Steady-state HE serving benchmark over the repro.hserve runtime.

Drives HEServer with a mixed mul/rotate request stream at paper-shaped
parameters and emits BENCH_serve_he.json — the repo's serving perf
trajectory: steady-state mul/s and rotate/s, p50/p99 request latency,
padding fraction, the resident table-cache footprint, plus (this PR's
additions, schema documented in docs/SERVING.md):

  - "trickle": p50/p99 request latency when the arrival rate is BELOW
    the batch size and only the age-based flush policy (max_age_s) gets
    requests served at all — the continuous-batching SLO path;
  - "overlap": drain wall time for the same mul stream with the
    double-buffered host↔device pipeline off vs on, and the speedup;
  - "plain": steady-state mul_plain/add_plain throughput — the
    plaintext-operand ops (encode-only operand, region 1 only, NO key
    switch) encrypted-inference affine layers ride;
  - "scheduler": the circuit-aware scheduler A/B — two degree-4
    circuits submitted one engine batch out of phase, drained with
    scheduling off vs on: cross-circuit co-batch rate, mul padding
    fraction, deferral/prefetch counts, and a bitwise-identical guard
    (scheduling must never change a result bit);
  - "client": the repro.client traced-session A/B — the same
    (x·w)·x + x circuit submitted as hand-built CircuitOp lists (one
    client-side encode of w PER circuit) vs. traced handles through
    HESession.run (w encodes once; later circuits ship hash-only and
    hit the server's (hash, level) plaintext cache): drain walls,
    mul pad fraction, cross-circuit co-batch rate, cache hit rate, and
    a bitwise-identical guard (the frontend must never change a bit);
  - "analysis": the repro.analysis cost-model A/B — the scheduler's
    deferral gate consulting a CostModel calibrated from THIS record's
    own throughputs vs deferring unconditionally, on the same staggered
    degree-4 pair: drain walls, batch counts, mul padding, deferral /
    cost-skip counts, the model's estimated device-seconds per circuit,
    and a bitwise-identical guard (cost-gated scheduling must never
    change a result bit);
  - "boot": the repro.boot batched-bootstrapping A/B — one bootstrap
    per drain vs two concurrent pipelines co-draining on the reference
    small-param bootstrap config: per-bootstrap latency, cross-circuit
    co-batch rate (> 0 is gated by check_docs — the batched payoff),
    and the error contract (max_err ≤ the documented plan bound,
    precision_bits in/out — bootstrap is approximate, never bitwise);
  - "obs": the repro.obs tracing overhead A/B — the same mul stream
    drained with the request-lifecycle Tracer detached vs attached,
    interleaved min-of-3: drain walls, overhead fraction (gated ≤2% by
    tools/check_docs.py — always-on tracing must be production-safe),
    trace event count, and a bitwise-identical guard.

    PYTHONPATH=src python benchmarks/serve_he.py                # quick
    PYTHONPATH=src python benchmarks/serve_he.py --full         # Table III
    PYTHONPATH=src python benchmarks/serve_he.py --logn 14 --logq 600

Request payloads reuse a small pool of pre-encrypted ciphertexts (setup
cost), so the measured loop is exactly the serving path: queue → batch
assembly → resident-table engine step → result wrap. A warm-up pass
compiles every (op, level) signature and the metrics window is reset
before the measured stream, so BOTH throughput and latency percentiles
are steady state (compile time is reported separately).
"""

from __future__ import annotations

import argparse
import json
import math
import sys
import time


def run(params, *, batch: int, mul_requests: int, rot_requests: int,
        levels: int, model_shards: int, use_kernels: bool,
        trickle_requests: int = 6, trickle_max_age_s: float = 0.02,
        overlap_muls: int = 0) -> dict:
    import numpy as np

    from repro.core import heaan as H
    from repro.core.keys import keygen
    from repro.core.rotate import conj_keygen, rot_keygen
    from repro.hserve import HEServer, degree4_demo_circuit
    from repro.launch.mesh import make_host_mesh

    t0 = time.perf_counter()
    sk, pk, evk = keygen(params, seed=0)
    rot_keys = {1: rot_keygen(params, sk, 1)} if rot_requests else {}
    conj_key = conj_keygen(params, sk)    # the degree-4 scheduler A/B
    keygen_s = time.perf_counter() - t0

    server = HEServer(params, evk, rot_keys, conj_key,
                      mesh=make_host_mesh(model=model_shards),
                      batch=batch, use_kernels=use_kernels)

    # a small ciphertext pool; requests cycle through it
    rng = np.random.default_rng(0)
    n = params.n_slots_max
    t0 = time.perf_counter()
    pool = [H.encrypt_message(
        rng.normal(size=n) + 1j * rng.normal(size=n), pk, params,
        seed=i + 1) for i in range(min(4, 2 * batch))]
    logqs = [params.logQ - i * params.logp for i in range(levels)]
    by_level = {
        lq: [c if lq == params.logQ else H.he_mod_down(c, params, lq)
             for c in pool] for lq in logqs}
    encrypt_s = time.perf_counter() - t0

    # warm-up: compile every (op, level) signature the stream will hit,
    # then reset the measurement window — reported latency/throughput
    # are steady state (compile_s is reported separately)
    for i in range(levels):
        cs = by_level[logqs[i]]
        if mul_requests:
            server.submit_mul(cs[0], cs[1 % len(cs)])
        if rot_requests:
            server.submit_rotate(cs[0], 1)
    server.drain()
    server.reset_metrics()

    for i in range(mul_requests):
        cs = by_level[logqs[i % levels]]
        server.submit_mul(cs[i % len(cs)], cs[(i + 1) % len(cs)])
    for i in range(rot_requests):
        cs = by_level[logqs[i % levels]]
        server.submit_rotate(cs[i % len(cs)], 1)

    t0 = time.perf_counter()
    results = server.drain()
    drain_s = time.perf_counter() - t0

    stats = server.stats()
    per_op = stats["per_op"]

    # ---- overlap on/off: same mul stream, double buffering toggled ------
    overlap_muls = overlap_muls or 2 * batch * max(1, levels)
    top = by_level[params.logQ]

    def overlap_drain(on: bool) -> float:
        server.overlap = on
        for i in range(overlap_muls):
            cs = by_level[logqs[i % levels]]
            server.submit_mul(cs[i % len(cs)], cs[(i + 1) % len(cs)])
        t0 = time.perf_counter()
        server.drain()
        return time.perf_counter() - t0

    off_s = overlap_drain(False)
    on_s = overlap_drain(True)
    server.overlap = False

    # ---- plaintext-operand ops: region-1-only throughput ----------------
    server.reset_metrics()
    plain_requests = 2 * batch
    pts = [H.encode_plain(
        np.asarray(rng.normal(size=n) + 1j * rng.normal(size=n)),
        params, params.logQ) for _ in range(2)]
    for i in range(plain_requests):
        ct = top[i % len(top)]
        server.submit_mul_plain(ct, pts[i % 2])
        server.submit_add_plain(ct, pts[i % 2])
    server.drain()
    pl = server.stats()["per_op"]

    # ---- scheduler A/B: two degree-4 circuits, one batch out of phase --
    ops4, _ = degree4_demo_circuit(params)

    def staggered_circuits(schedule: bool):
        server.schedule = schedule
        server.reset_metrics()    # new window (zeroes scheduler counters)
        # baseline AFTER the reset, so the deltas stay per-phase even if
        # reset_metrics ever stops zeroing the scheduler counters
        d0, p0 = server.scheduler.deferrals, server.scheduler.prefetches
        res = {}
        c1 = server.submit_circuit(ops4, {"x": top[0]})
        res.update(dict(server.poll(flush=True)))   # desync the pair
        c2 = server.submit_circuit(ops4, {"x": top[1 % len(top)]})
        t0 = time.perf_counter()
        res.update(server.drain())
        wall = time.perf_counter() - t0
        s = server.stats()
        return {
            "drain_s": round(wall, 4),
            "batches": sum(d["batches"] for d in s["per_op"].values()),
            "mul_pad_frac": s["per_op"]["mul"]["pad_frac"],
            "cross_circuit_batches":
                s["cobatch"]["cross_circuit_batches"],
            "cross_circuit_rate": s["cobatch"]["cross_circuit_rate"],
            "deferrals": server.scheduler.deferrals - d0,
            "prefetches": server.scheduler.prefetches - p0,
        }, (res[c1], res[c2])

    # warm pass runs SCHEDULED on the cold circuit levels, so the table
    # prefetches it reports are the real cold-cache ones (hidden behind
    # in-flight batches); the timed A/B that follows is fully warm
    warm, _ = staggered_circuits(True)
    unsched, outs_u = staggered_circuits(False)
    sched, outs_s = staggered_circuits(True)
    server.schedule = False
    sched["prefetches_cold"] = warm["prefetches"]
    bitwise = all(
        bool((np.asarray(a.ax) == np.asarray(b.ax)).all()
             and (np.asarray(a.bx) == np.asarray(b.bx)).all())
        for a, b in zip(outs_u, outs_s))
    assert bitwise, "scheduling changed a result bit"

    # ---- client: traced session vs hand-built circuits -----------------
    from repro.client import HESession
    from repro.core.encoding import message_hash
    from repro.hserve import CircuitOp

    session = HESession(params, sk=sk, pk=pk, evk=evk, server=server)
    k = max(2, min(4, len(top)))
    wz = rng.normal(size=n) + 1j * rng.normal(size=n)
    lq1, lq2 = params.logQ - params.logp, params.logQ - 2 * params.logp

    def hand_ops():
        # what PR-4 clients wrote by hand for (x·w)·x + x: explicit
        # level management, integer node refs, and a fresh client-side
        # encode of w for EVERY circuit
        pt = np.asarray(H.encode_plain(wz, params, params.logQ))
        return [
            CircuitOp("mul_plain", ("in0",), pt=pt,
                      pt_logp=params.log_delta),
            CircuitOp("rescale", (0,), dlogp=params.logp),
            CircuitOp("mod_down", ("in0",), logq2=lq1),
            CircuitOp("mul", (1, 2)),
            CircuitOp("rescale", (3,), dlogp=params.logp),
            CircuitOp("mod_down", ("in0",), logq2=lq2),
            CircuitOp("add", (4, 5)),
        ]

    # warm pass compiles the circuit's (op, level) signatures so BOTH
    # phases are steady state (same methodology as the main stream)
    server.submit_circuit(hand_ops(), {"in0": top[0]})
    server.drain()

    server.reset_metrics()
    t0 = time.perf_counter()
    hand_cids = [server.submit_circuit(hand_ops(),
                                       {"in0": top[i % len(top)]})
                 for i in range(k)]
    hand_res = server.drain()
    hand_s = time.perf_counter() - t0
    hand_stats = server.stats()

    server.reset_metrics()
    h0, m0 = server.cache.plain_hits, server.cache.plain_misses
    t0 = time.perf_counter()
    exprs = []
    for i in range(k):
        x = session.input(top[i % len(top)])
        exprs.append((x * wz) * x + x)
    tfuts = session.run(exprs)          # w encodes ONCE; rest hash-only
    session.drain()
    traced_s = time.perf_counter() - t0
    tr_stats = server.stats()
    hits = server.cache.plain_hits - h0
    total = hits + server.cache.plain_misses - m0
    client_bitwise = all(
        bool((np.asarray(hand_res[c].ax) == np.asarray(f.result().ax))
             .all()
             and (np.asarray(hand_res[c].bx)
                  == np.asarray(f.result().bx)).all())
        for c, f in zip(hand_cids, tfuts))
    assert client_bitwise, "the traced frontend changed a result bit"

    # ---- analysis: cost-model-gated scheduler A/B -----------------------
    # calibrate repro.analysis.CostModel from the throughputs measured
    # ABOVE (the record being emitted is its own calibration source),
    # then drain the same staggered degree-4 pair with the scheduler's
    # deferral gate consulting the model vs not. At serving params a
    # full-depth mul bucket clears defer_min_s (defer: co-batching
    # pays) while add/rescale buckets cost ~µs (cost_skips: flush now)
    from repro.analysis import CostModel

    cm = CostModel.from_bench({
        "params": {"logN": params.logN, "logQ": params.logQ,
                   "logp": params.logp, "beta_bits": params.beta_bits},
        "levels": logqs,
        "mul_per_s": per_op.get("mul", {}).get("ops_per_s", 0.0),
        "rotate_per_s": per_op.get("rotate", {}).get("ops_per_s", 0.0),
        "plain": {"mul_plain_per_s": pl["mul_plain"]["ops_per_s"],
                  "add_plain_per_s": pl["add_plain"]["ops_per_s"]},
    }, params=params)
    est_s, _ = cm.estimate_circuit(ops4, {"x": (params.logQ, params.logp)})

    def costed_circuits(cost_model):
        server.schedule = True
        server.scheduler.cost_model = cost_model
        server.reset_metrics()
        d0 = server.scheduler.deferrals
        k0 = server.scheduler.cost_skips
        res = {}
        c1 = server.submit_circuit(ops4, {"x": top[0]})
        res.update(dict(server.poll(flush=True)))   # desync the pair
        c2 = server.submit_circuit(ops4, {"x": top[1 % len(top)]})
        t0 = time.perf_counter()
        res.update(server.drain())
        wall = time.perf_counter() - t0
        s = server.stats()
        return {
            "drain_s": round(wall, 4),
            "batches": sum(d["batches"] for d in s["per_op"].values()),
            "mul_pad_frac": s["per_op"]["mul"]["pad_frac"],
            "deferrals": server.scheduler.deferrals - d0,
            "cost_skips": server.scheduler.cost_skips - k0,
        }, (res[c1], res[c2])

    nocost, outs_n = costed_circuits(None)
    withcost, outs_c = costed_circuits(cm)
    server.schedule = False
    server.scheduler.cost_model = None
    an_bitwise = all(
        bool((np.asarray(a.ax) == np.asarray(b.ax)).all()
             and (np.asarray(a.bx) == np.asarray(b.bx)).all())
        for a, b in zip(outs_n, outs_c))
    assert an_bitwise, "cost-model scheduling changed a result bit"

    # ---- obs: lifecycle-tracing overhead A/B ----------------------------
    # the same mul stream drained with the repro.obs Tracer detached vs
    # attached (every submit/flush/dispatch/complete event recorded).
    # Interleaved min-of-3 so one GC pause or turbo transition cannot
    # poison either arm; the gate (tools/check_docs.py OBS_SCHEMA) is
    # ≤2% overhead and bitwise-identical results — always-on tracing
    # must be safe to leave enabled in production serving.
    from repro.obs import Tracer

    obs_muls = overlap_muls

    def obs_drain(tracer):
        server.tracer = tracer
        for i in range(obs_muls):
            cs = by_level[logqs[i % levels]]
            server.submit_mul(cs[i % len(cs)], cs[(i + 1) % len(cs)])
        t0 = time.perf_counter()
        res = server.drain()
        server.tracer = None
        return time.perf_counter() - t0, [res[r] for r in sorted(res)]

    off_walls, on_walls = [], []
    trace_events = 0
    obs_bitwise = True
    for _ in range(3):
        w_off, outs_off = obs_drain(None)
        tr_on = Tracer()
        w_on, outs_on = obs_drain(tr_on)
        off_walls.append(w_off)
        on_walls.append(w_on)
        trace_events = len(tr_on)
        obs_bitwise &= all(
            bool((np.asarray(a.ax) == np.asarray(b.ax)).all()
                 and (np.asarray(a.bx) == np.asarray(b.bx)).all())
            for a, b in zip(outs_off, outs_on))
    assert obs_bitwise, "tracing changed a result bit"
    obs_off_s, obs_on_s = min(off_walls), min(on_walls)

    # ---- multihost: frontend/worker scaling + worker-death requeue ------
    # the same mul stream served through the disaggregated tier
    # (HEFrontend routing batches to W in-process worker engines) for
    # W in 1/2/4. All workers share this host's devices, so wall time
    # cannot scale here; the scaling signal is VIRTUAL time — each
    # worker's busy_s is the device-seconds it actually computed, and
    # makespan_W = max_w busy_s models W hosts running concurrently.
    # efficiency(W) = busy_total(1) / (W · makespan_W): 1.0 is perfect
    # load balance, < 0.7 at W=4 fails the check_docs gate. A second
    # pass kills one worker mid-batch via the FailureInjector and
    # verifies the requeue path re-serves bitwise identically.
    from repro.hserve import HEFrontend
    from repro.runtime.failures import FailureInjector

    mh_muls = 8 * batch

    def mh_submit(srv):
        rids = []
        for i in range(mh_muls):
            cs = by_level[logqs[i % levels]]
            rids.append(srv.submit_mul(cs[i % len(cs)],
                                       cs[(i + 1) % len(cs)]))
        return rids

    ref_rids = mh_submit(server)
    ref_res = server.drain()
    ref_outs = [ref_res[r] for r in ref_rids]

    def mh_bitwise_vs_ref(rids, res):
        return all(
            bool((np.asarray(a.ax) == np.asarray(res[r].ax)).all()
                 and (np.asarray(a.bx) == np.asarray(res[r].bx)).all())
            for a, r in zip(ref_outs, rids))

    mesh = make_host_mesh(model=model_shards)
    per_workers = {}
    mh_bitwise = True
    for W in (1, 2, 4):
        fe = HEFrontend(params, evk, mesh=mesh, batch=batch, workers=W)
        # warm every worker on every (mul, level) signature (W batches
        # per level spread over the W idle workers), then zero busy_s —
        # the measured sweep is steady state, like the monolith's
        for lq in logqs:
            cs = by_level[lq]
            for i in range(W * batch):
                fe.submit_mul(cs[i % len(cs)], cs[(i + 1) % len(cs)])
        fe.drain()
        fe.reset_metrics()
        rids = mh_submit(fe)
        res = fe.drain()
        mh_bitwise &= mh_bitwise_vs_ref(rids, res)
        busy = [w.busy_s for w in fe.workers]
        makespan = max(busy)
        per_workers[str(W)] = {
            "busy_s": round(sum(busy), 4),
            "makespan_s": round(makespan, 4),
            "mul_per_s": round(mh_muls / makespan, 3) if makespan else 0.0,
        }
        fe.close()
    assert mh_bitwise, "multi-host serving changed a result bit"
    busy_1 = per_workers["1"]["busy_s"]
    mh_eff4 = round(busy_1 / (4 * per_workers["4"]["makespan_s"]), 3) \
        if per_workers["4"]["makespan_s"] else 0.0

    # requeue A/B: worker 0 dies right after its second dispatch (the
    # batch is computed but never delivered); the frontend must detect
    # the death, requeue the in-flight requests, and re-serve them on
    # the surviving worker — bitwise identically
    fe = HEFrontend(params, evk, mesh=mesh, batch=batch, workers=2,
                    injector=FailureInjector(kill_worker_at={0: 2}))
    rids = mh_submit(fe)
    res = fe.drain()
    rq_bitwise = mh_bitwise_vs_ref(rids, res)
    assert rq_bitwise, "worker-death requeue changed a result bit"
    fr = fe.stats()["frontend"]
    fe.close()

    # ---- boot: the batched-bootstrapping A/B -----------------------------
    # served CKKS bootstrapping (repro.boot) on its OWN server at the
    # reference small-param config (the pipeline needs logQ = 14·logp,
    # independent of this record's params). A = one bootstrap per
    # drain; B = two concurrent bootstraps in one drain — the batched
    # payoff is the circuit scheduler co-batching their aligned
    # rotation/mul stages ACROSS the two pipelines (cross_circuit_rate
    # > 0 is gated by tools/check_docs.py, as is the error contract:
    # bootstrap is approximate, max_err must stay ≤ the documented
    # plan.error_bound()).
    from repro.boot import boot_params, bootstrap_circuit

    bp = boot_params()
    bsk, bpk, bevk = keygen(bp, seed=0)
    brot = {r: rot_keygen(bp, bsk, r) for r in (1, 2, 3, 4)}
    bsrv = HEServer(bp, bevk, brot, conj_keygen(bp, bsk),
                    mesh=make_host_mesh(), batch=batch, schedule=True)
    plan = bootstrap_circuit(bp, logq_in=bp.logp,
                             plain_lookup=bsrv.cache.has_plain)
    brng = np.random.default_rng(99)
    bn = bp.n_slots_max

    def bmsg():
        z = brng.uniform(-1, 1, bn) + 1j * brng.uniform(-1, 1, bn)
        return z * (plan.msg_bound / np.max(np.abs(z)))

    bmsgs = [bmsg() for _ in range(2)]
    bcts = [H.he_mod_down(H.encrypt_message(z, bpk, bp, seed=200 + i),
                          bp, bp.logp) for i, z in enumerate(bmsgs)]
    err_in = max(float(np.max(np.abs(
        H.decrypt_message(ct, bsk, bp) - z)))
        for ct, z in zip(bcts, bmsgs))

    # warm-up bootstrap compiles every pipeline (op, level) cell
    bsrv.submit_bootstrap(bcts[0], plan=plan)
    bsrv.drain()
    boot_compile_s = bsrv.engine.compile_s

    bsrv.reset_metrics()                      # A: solo
    t0 = time.perf_counter()
    bsrv.submit_bootstrap(bcts[0], plan=plan)
    bsrv.drain()
    solo_s = time.perf_counter() - t0

    bsrv.reset_metrics()                      # B: 2 concurrent
    t0 = time.perf_counter()
    bcids = [bsrv.submit_bootstrap(ct, plan=plan) for ct in bcts]
    bres = bsrv.drain()
    pair_s = time.perf_counter() - t0
    bcb = bsrv.stats()["cobatch"]
    bouts = [bres[c] for c in bcids]
    err_out = max(float(np.max(np.abs(
        H.decrypt_message(o, bsk, bp) - z)))
        for o, z in zip(bouts, bmsgs))
    assert err_out <= plan.error_bound(), \
        f"bootstrap error {err_out:.3e} breached the documented " \
        f"bound {plan.error_bound():.3e}"
    assert all(o.logq == plan.out_logq for o in bouts)

    # ---- trickle: arrival rate < batch; only the age policy flushes.
    # adaptive_target is disabled here on purpose: with it on, a trickle
    # is released the moment the target shrinks to the arrival rate and
    # the age deadline never fires — this phase isolates the SLO path
    # (age_flushes == trickle_requests when it works).
    server.max_age_s = trickle_max_age_s
    server.adaptive_target = False
    server.reset_metrics()
    for i in range(trickle_requests):
        server.submit_mul(top[i % len(top)], top[(i + 1) % len(top)])
        while not server.poll():          # poll until the age deadline
            time.sleep(trickle_max_age_s / 10)   # fires (no full bucket)
    tr = server.stats()
    server.max_age_s = None
    server.adaptive_target = True
    return {
        "params": {"logN": params.logN, "logQ": params.logQ,
                   "logp": params.logp, "beta_bits": params.beta_bits,
                   "np1_top": params.np_region1(params.logQ),
                   "np2_top": params.np_region2(params.logQ)},
        "batch": batch,
        "levels": logqs,
        "use_kernels": use_kernels,
        "mesh": stats["mesh"],
        "requests": {"mul": mul_requests, "rotate": rot_requests,
                     "completed": len(results)},
        "mul_per_s": per_op.get("mul", {}).get("ops_per_s", 0.0),
        "rotate_per_s": per_op.get("rotate", {}).get("ops_per_s", 0.0),
        "latency_ms": {
            op: per_op[op]["latency_ms"] for op in per_op},
        "pad_frac": {op: per_op[op]["pad_frac"] for op in per_op},
        "queue_depth": stats["queue_depth"],
        "cache": stats["cache"],
        "compile_s": stats["engine"]["compile_s"],
        "steps_compiled": stats["engine"]["steps_compiled"],
        "setup_s": {"keygen": round(keygen_s, 3),
                    "encrypt_pool": round(encrypt_s, 3)},
        "drain_wall_s": round(drain_s, 3),
        "trickle": {
            "requests": trickle_requests,
            "max_age_s": trickle_max_age_s,
            "p50_ms": tr["per_op"]["mul"]["latency_ms"]["p50"],
            "p99_ms": tr["per_op"]["mul"]["latency_ms"]["p99"],
            "age_flushes": tr["flushes"]["age"],
        },
        "overlap": {
            "muls": overlap_muls,
            "off_drain_s": round(off_s, 4),
            "on_drain_s": round(on_s, 4),
            "speedup": round(off_s / on_s, 3) if on_s > 0 else 0.0,
        },
        "plain": {
            "requests": 2 * plain_requests,
            "mul_plain_per_s": pl["mul_plain"]["ops_per_s"],
            "add_plain_per_s": pl["add_plain"]["ops_per_s"],
            "mul_plain_vs_mul": round(
                pl["mul_plain"]["ops_per_s"]
                / per_op["mul"]["ops_per_s"], 3)
            if per_op.get("mul", {}).get("ops_per_s") else 0.0,
        },
        "scheduler": {
            "circuits": 2,
            "lookahead": server.scheduler.lookahead,
            "unscheduled": unsched,
            "scheduled": sched,
            "bitwise_identical": bitwise,
        },
        "client": {
            "circuits": k,
            "hand_drain_s": round(hand_s, 4),
            "traced_drain_s": round(traced_s, 4),
            "hand_mul_pad_frac":
                hand_stats["per_op"]["mul"]["pad_frac"],
            "traced_mul_pad_frac":
                tr_stats["per_op"]["mul"]["pad_frac"],
            "cross_circuit_rate":
                tr_stats["cobatch"]["cross_circuit_rate"],
            "plain_cache_hits": hits,
            "plain_cache_hit_rate":
                round(hits / total, 3) if total else 0.0,
            "bitwise_identical": client_bitwise,
        },
        "analysis": {
            "circuits": 2,
            "calibrated_from": "self",
            "est_circuit_s": round(est_s, 6),
            "nocost": nocost,
            "cost": withcost,
            "bitwise_identical": an_bitwise,
        },
        "obs": {
            "muls": obs_muls,
            "off_drain_s": round(obs_off_s, 4),
            "on_drain_s": round(obs_on_s, 4),
            "overhead_frac": round(obs_on_s / obs_off_s - 1.0, 4),
            "trace_events": trace_events,
            "bitwise_identical": obs_bitwise,
        },
        "boot": {
            "params": {"logN": bp.logN, "logQ": bp.logQ,
                       "logp": bp.logp},
            "concurrent": 2,
            "pipeline_ops": len(plan.ops),
            "logq_in": plan.logq_in,
            "out_logq": plan.out_logq,
            "levels_gained": plan.levels_gained,
            "compile_s": round(boot_compile_s, 3),
            "solo_latency_s": round(solo_s, 4),
            "concurrent_drain_s": round(pair_s, 4),
            "latency_s_per_bootstrap": round(pair_s / 2, 4),
            "cobatch_speedup": round(2 * solo_s / pair_s, 3)
            if pair_s > 0 else 0.0,
            "cross_circuit_batches": bcb["cross_circuit_batches"],
            "cross_circuit_rate": bcb["cross_circuit_rate"],
            "max_err": err_out,
            "error_bound": plan.error_bound(),
            "precision_bits_in": round(-math.log2(err_in), 2)
            if err_in > 0 else float(bp.logp),
            "precision_bits_out": round(-math.log2(err_out), 2)
            if err_out > 0 else float(bp.logp),
        },
        "multihost": {
            "muls": mh_muls,
            "batch": batch,
            "transport": "inproc",
            "workers_swept": [1, 2, 4],
            "per_workers": per_workers,
            "scaling_efficiency_at_4": mh_eff4,
            "requeue": {
                "worker_deaths": fr["deaths"],
                "requeued_requests": fr["requeued_requests"],
                "bitwise_identical": rq_bitwise,
            },
            "bitwise_identical": mh_bitwise,
        },
    }


def main(argv=None):
    from repro.configs.heaan_mul import CONFIG
    from repro.core.params import HEParams
    from repro.launch.compile_cache import enable_compile_cache

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--full", action="store_true",
                    help="paper Table III params (logN=16, logQ=1200) — "
                         "hours on CPU; the TPU target's configuration")
    ap.add_argument("--logn", type=int, default=8)
    ap.add_argument("--logq", type=int, default=240)
    ap.add_argument("--logp", type=int, default=30)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--muls", type=int, default=12)
    ap.add_argument("--rotations", type=int, default=8)
    ap.add_argument("--levels", type=int, default=2)
    ap.add_argument("--model-shards", type=int, default=1)
    ap.add_argument("--kernels", action="store_true")
    ap.add_argument("--out", default="BENCH_serve_he.json")
    args = ap.parse_args(argv)

    enable_compile_cache()
    if args.full:
        params = CONFIG
    else:
        params = HEParams(logN=args.logn, logQ=args.logq, logp=args.logp,
                          log_delta=args.logp, beta_bits=32,
                          h=min(64, (1 << args.logn) // 2))

    out = run(params, batch=args.batch, mul_requests=args.muls,
              rot_requests=args.rotations, levels=args.levels,
              model_shards=args.model_shards, use_kernels=args.kernels)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=2)
        f.write("\n")
    print(json.dumps(out, indent=2))
    print(f"\nwrote {args.out}", file=sys.stderr)


if __name__ == "__main__":
    main()
