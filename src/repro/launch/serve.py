"""Serving drivers: batched LM prefill + greedy decode, and the paper's
own workload — a batched multi-level HE request stream — over the
repro.hserve runtime (queue → level-aware table cache → sharded engine).

    PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b \
        --preset smoke --batch 4 --prompt-len 32 --gen 16
    PYTHONPATH=src python -m repro.launch.serve --he --batch 8 \
        --requests 24 --levels 3 --rotations 4 --conjugations 2 \
        [--plain-frac 0.5] [--circuit] [--schedule] [--max-age-s 0.05] \
        [--overlap] [--kernels] [--full]

Both paths place their state with repro.dist.sharding rules on the host
mesh (whatever devices this process has), so the same driver scales from
1 CPU device to a pod slice unchanged.
"""

from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import decode_step, init_params, prefill
from repro.models.config import ModelConfig


def generate(params, cfg: ModelConfig, tokens, gen_steps: int,
             max_len: int, batch_extra=None):
    """Greedy generation. tokens: (B, L) prompt. Returns (B, gen_steps)."""
    B, L = tokens.shape
    batch = {"tokens": tokens, **(batch_extra or {})}
    logits, cache = prefill(params, batch, cfg, max_len)
    step_fn = jax.jit(
        lambda p, c, t, i: decode_step(p, c, t, i, cfg),
        static_argnames=())
    out = []
    tok = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
    for i in range(gen_steps):
        out.append(tok)
        logits, cache = step_fn(params, cache, tok, L + i)
        tok = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
    return jnp.concatenate(out, axis=1)


def core_reference(op: str, operands, params, keys: dict):
    """One served op recomputed by the single-device `core` functions on
    the CPU backend, from host copies of its operands and keys.

    keys: "evk" (EvalKey), and the served "rot" (r = 1) / "conj" key
    pytrees where the op needs them. Returns a Ciphertext of numpy
    arrays.
    """
    from repro.core import heaan as H
    from repro.core.cipher import Ciphertext, EvalKey
    from repro.core.rotate import he_conjugate, he_rotate

    def host(x):
        if isinstance(x, Ciphertext):
            return dataclasses.replace(x, ax=np.asarray(x.ax),
                                       bx=np.asarray(x.bx))
        if isinstance(x, EvalKey):
            return EvalKey(*(np.asarray(getattr(x, f.name))
                             for f in dataclasses.fields(x)))
        if isinstance(x, dict):                   # served key pytree
            return EvalKey(**{k: np.asarray(v) for k, v in x.items()})
        return np.asarray(x)

    args = [host(x) for x in operands]
    with jax.default_device(jax.devices("cpu")[0]):
        if op == "mul":
            out = H.he_mul(*args, host(keys["evk"]), params)
        elif op == "mul_plain":
            out = H.he_mul_plain(*args, params)
        elif op == "add_plain":
            out = H.he_add_plain(*args, params)
        elif op == "rotate":
            out = he_rotate(*args, 1, host(keys["rot"]), params)
        elif op == "conjugate":
            out = he_conjugate(*args, host(keys["conj"]), params)
        elif op == "rescale":
            out = H.rescale(*args, params)
        else:
            raise ValueError(f"no core reference for op {op!r}")
        return host(out)


def serve_he(batch: int, requests: int = 0, levels: int = 1,
             rotations: int = 0, conjugations: int = 0,
             plain_frac: float = 0.0, model_shards: int = 1,
             use_kernels: bool = False, max_age_s: float | None = None,
             overlap: bool = False, circuit: bool = False,
             schedule: bool = False, traced: int = 0,
             check: str = "off", seed: int = 0,
             trace: str | None = None, profile_dir: str | None = None,
             metrics: str | None = None, workers: int = 0,
             bootstrap: int = 0, params=None) -> dict:
    """Batched multi-level HE serving, driven through a `repro.client`
    HESession (the session owns keygen, encrypt/decrypt, and the
    HEServer; the raw per-op stream rides `session.server`).

    Submits a mixed stream of HE-Mul / rotate / conjugate requests
    spread over `levels` moduli — `plain_frac` of the mul share served
    as the key-switch-free mul_plain/add_plain plaintext-operand ops —
    plus, with `circuit`, a whole degree-4 encrypted polynomial circuit
    via submit_circuit (TWO staggered copies under `schedule`,
    exercising the circuit-aware scheduler's cross-circuit co-batching
    and table prefetch), plus, with `traced` > 0, that many TRACED
    client expressions (every handle op, no explicit level management —
    the compile pass inserts it) sharing one weight vector so every
    expression after the first ships hash-only plaintext operands and
    hits the server's (hash, level) cache. Drains the queue with padded
    batching and verifies every decrypted result. Returns the server
    stats dict plus a max_err field (printed by main).

    Observability (repro.obs): `trace` writes a Chrome trace-event JSON
    of the request lifecycle + engine spans to that path (load it in
    Perfetto, or run `python -m repro.obs report PATH`);
    `profile_dir` records a `jax.profiler` trace of the served stream
    into that directory with the tracer on, so its ``hserve.*`` host
    spans sit beside the device ops, which the ``he.*`` named scopes
    assign to the paper's Fig. 3 stages (read it in xprof or Perfetto);
    `metrics` dumps the registry snapshot (serving telemetry plane) as
    JSON to that path.

    `workers` > 0 serves the same stream through the multi-host tier:
    an :class:`repro.hserve.HEFrontend` routing batches to that many
    in-process worker engines (docs/SERVING.md "Multi-host serving").
    Bitwise identical to the single-server path.

    `bootstrap` > 0 additionally serves that many CONCURRENT bootstrap
    pipelines (`repro.boot`, docs/BOOTSTRAP.md) over level-exhausted
    ciphertexts — the whole run switches to the reference bootstrap
    params (`boot_params()`: logQ=336, h=2) so the pipeline fits the
    modulus chain. Bootstrap results verify against the plan's
    documented error bound (approximate, not bitwise); the returned
    stats gain a "bootstrap" block with the measured error, the bound,
    and the cross-circuit co-batch rate the concurrent pipelines hit.

    `params` picks the HEAAN parameter set (default `configs.heaan_mul`
    SMOKE; `CONFIG` is the paper's Table III). Every mul and mul_plain
    result is rescaled by the server before it is decrypted.

    Besides decrypting, the first request of every (op, level) bucket of
    the raw per-op stream is recomputed by the single-device `core`
    functions on the CPU backend (``jax.devices("cpu")``) and must equal
    the served result bit for bit; "bitwise_checked" in the returned
    stats counts the results so compared.
    """
    from repro.client import HESession
    from repro.configs.heaan_mul import SMOKE
    from repro.core import heaan as H
    from repro.core.keys import keygen
    from repro.hserve import HEFrontend, degree4_demo_circuit
    from repro.launch.mesh import make_host_mesh
    from repro.obs import Tracer

    if bootstrap:
        from repro.boot import boot_params
        if params is not None:
            raise ValueError("--bootstrap runs at boot_params(); "
                             "it takes no other parameter set")
        params = boot_params()
    elif params is None:
        params = SMOKE
    requests = requests or 2 * batch + 1   # force >1 batch and padding
    # the lowest level logq = logp is excluded: mul results there cannot
    # rescale (ciphertext exhausted), and verification rescales every mul
    if not 1 <= levels <= params.L - 1:
        raise ValueError(f"--levels must be in [1, {params.L - 1}]")
    if not 0.0 <= plain_frac <= 1.0:
        raise ValueError("--plain-frac must be in [0, 1]")
    tracer = Tracer() if trace or profile_dir else None
    if workers > 0:
        if overlap:
            raise ValueError(
                "--overlap is a single-server knob; the multi-host "
                "frontend pipelines across workers instead of "
                "double-buffering one engine")
        sk, pk, evk = keygen(params, seed=0)
        frontend = HEFrontend(
            params, evk, mesh=make_host_mesh(model=model_shards),
            batch=batch, workers=workers, use_kernels=use_kernels,
            max_age_s=max_age_s, schedule=schedule, tracer=tracer)
        session = HESession(params, sk=sk, pk=pk, evk=evk,
                            server=frontend)
    else:
        session = HESession(params, seed=0,
                            mesh=make_host_mesh(model=model_shards),
                            batch=batch, use_kernels=use_kernels,
                            max_age_s=max_age_s, overlap=overlap,
                            schedule=schedule, tracer=tracer)
    server = session.server
    if profile_dir:
        jax.profiler.start_trace(profile_dir)
    if rotations:
        session.ensure_rotation_keys([1])
    if conjugations or circuit:
        session.ensure_conj_key()

    rng = np.random.default_rng(seed)
    n = params.n_slots_max
    logqs = [params.logQ - i * params.logp for i in range(levels)]
    expect = {}   # rid -> (op, expected slots)
    probes = {}   # (op, logq) -> (rid, operands): the reference's inputs
    n_mul = requests - rotations - conjugations
    if n_mul < 0:
        raise ValueError(
            "--rotations + --conjugations cannot exceed --requests")
    n_plain = int(round(plain_frac * n_mul))
    for i in range(requests):
        logq = logqs[i % levels]
        z = rng.normal(size=n) + 1j * rng.normal(size=n)
        ct = session.encrypt(z, seed=2 * i + 1).ciphertext
        if logq < params.logQ:
            ct = H.he_mod_down(ct, params, logq)
        if i < n_plain:
            # plaintext-operand ops: encode-only operand, region-1
            # product / bx add — no key switch, no key material
            w = rng.normal(size=n) + 1j * rng.normal(size=n)
            pt = H.encode_plain(w, params, logq)
            if i % 2 == 0:
                op, rid = "mul_plain", server.submit_mul_plain(ct, pt)
                expect[rid] = (op, z * w)
            else:
                op, rid = "add_plain", server.submit_add_plain(ct, pt)
                expect[rid] = (op, z + w)
            operands = (ct, pt)
        elif i < n_mul:
            z2 = rng.normal(size=n) + 1j * rng.normal(size=n)
            c2 = session.encrypt(z2, seed=2 * i + 2).ciphertext
            if logq < params.logQ:
                c2 = H.he_mod_down(c2, params, logq)
            op, rid = "mul", server.submit_mul(ct, c2)
            expect[rid] = (op, z * z2)
            operands = (ct, c2)
        elif i < n_mul + rotations:
            op, rid = "rotate", server.submit_rotate(ct, 1)
            expect[rid] = (op, np.roll(z, -1))
            operands = (ct,)
        else:
            op, rid = "conjugate", server.submit_conjugate(ct)
            expect[rid] = (op, np.conj(z))
            operands = (ct,)
        probes.setdefault((op, logq), (rid, operands))

    if circuit:
        # a degree-4 encrypted polynomial, evaluated WHOLLY server-side:
        # conj(x⁴) + x — muls, rescales, a mod-down alignment, conjugate,
        # and an add, all through one submit_circuit round trip. Under
        # --schedule a second, STAGGERED copy rides along so the
        # scheduler's cross-circuit co-batching is exercised end-to-end.
        ops, _ = degree4_demo_circuit(params)
        if check != "off":
            # hslint the hand-built circuit before submitting it (the
            # traced path runs the same analyzer inside session.run)
            from repro.analysis import analyze_circuit
            report = analyze_circuit(
                ops, {"x": (params.logQ, params.logp)}, params,
                input_nslots={"x": n})
            print(report.render("degree4 circuit"))
            if check == "error" and not report.ok:
                raise ValueError("static analysis rejected the demo "
                                 "circuit: " + "; ".join(
                                     d.format() for d in report.errors))
        n_circ = 2 if schedule else 1
        results = {}
        for j in range(n_circ):
            zc = rng.normal(size=n) + 1j * rng.normal(size=n)
            x = session.encrypt(zc, seed=7777 + j).ciphertext
            cid = server.submit_circuit(ops, inputs={"x": x})
            expect[cid] = ("circuit", np.conj(zc ** 4) + zc)
            if schedule and j == 0:       # desync the two circuits (the
                results.update(           # poll may complete plain reqs)
                    dict(server.poll(flush=True)))
    else:
        results = {}

    tfuts = []
    if traced:
        # the session API end to end: every traced op, NO explicit
        # rescale/mod_down (the compile pass inserts level management),
        # one shared weight vector — every expression after the first
        # compiles to hash-only plain operands (server-cache hits)
        wz = 0.5 * (rng.normal(size=n) + 1j * rng.normal(size=n))
        for j in range(traced):
            zt = 0.5 * (rng.normal(size=n) + 1j * rng.normal(size=n))
            x = session.encrypt(zt, seed=5555 + j)
            tfuts.append(
                (session.run([((x * x) * wz + x)
                              .rotate(1).conj().slot_sum()],
                             check=check)[0],
                 np.full(n, np.conj(np.roll(zt * zt * wz + zt,
                                            -1)).sum())))

    bfuts = []
    if bootstrap:
        # N concurrent bootstrap pipelines over level-exhausted inputs:
        # their aligned stage nodes co-batch ACROSS circuits (and with
        # the plain request stream) through the same queue
        for j in range(bootstrap):
            zb = rng.uniform(-1, 1, n) + 1j * rng.uniform(-1, 1, n)
            zb *= 2.0 ** -5 / np.max(np.abs(zb))
            ct = session.encrypt(zb, seed=8888 + j).ciphertext
            ct = H.he_mod_down(ct, params, params.logp)  # exhausted
            bfuts.append((session.bootstrap(ct), zb))

    # session.drain (not server.drain) so traced futures resolve while
    # the raw per-op/circuit results come back as {rid: ct}
    results.update(session.drain())
    # products come back at scale Δ²: the server rescales them too
    rescaled = {rid: server.submit_rescale(results[rid])
                for rid, (op, _) in expect.items()
                if op in ("mul", "mul_plain")}
    results.update(session.drain())
    if profile_dir:
        jax.profiler.stop_trace()
    for rid, rrid in rescaled.items():
        probes.setdefault(("rescale", results[rid].logq),
                          (rrid, (results[rid],)))
    errs = []
    for rid, (op, want) in expect.items():
        got = session.decrypt(results[rescaled.get(rid, rid)])
        errs.append(float(np.abs(got - want).max()))
    for fut, want in tfuts:
        errs.append(float(np.abs(session.decrypt(fut.result())
                                 - want).max()))
    stats = server.stats()
    stats["devices"] = len(jax.devices())
    stats["max_err"] = max(errs)
    keys = {"evk": session.evk}
    if rotations:
        keys["rot"] = server.cache.rot_key(1)
    if conjugations:
        keys["conj"] = server.cache.conj_key()
    for (op, logq), (rid, operands) in sorted(probes.items()):
        want = core_reference(op, operands, params, keys)
        got = results[rid]
        if not (got.logq == want.logq and got.logp == want.logp
                and np.array_equal(np.asarray(got.ax), want.ax)
                and np.array_equal(np.asarray(got.bx), want.bx)):
            raise AssertionError(
                f"served {op} at logq={logq} differs from core")
    stats["bitwise_checked"] = len(probes)
    if bootstrap:
        # approximate-op contract: error-BOUND gate, not bitwise
        plan = next(iter(session._boot_plans.values()))
        berrs = []
        for fut, want in bfuts:
            out = fut.result()
            assert out.logq == plan.out_logq, (out.logq, plan.out_logq)
            berrs.append(
                float(np.abs(session.decrypt(out) - want).max()))
        bound = plan.error_bound()
        if max(berrs) > bound:
            raise AssertionError(
                f"bootstrap error {max(berrs):.3e} exceeds the "
                f"documented bound {bound:.3e}")
        if schedule and bootstrap >= 2 \
                and stats["cobatch"]["cross_circuit_batches"] == 0:
            raise AssertionError(
                "concurrent bootstraps never co-batched across "
                "circuits — the scheduler lost the batched-"
                "bootstrapping payoff")
        stats["bootstrap"] = {
            "n": bootstrap,
            "max_err": max(berrs),
            "error_bound": bound,
            "logq_in": plan.logq_in,
            "out_logq": plan.out_logq,
            "cross_circuit_rate":
                stats["cobatch"]["cross_circuit_rate"],
        }
    if trace:
        stats["trace_events"] = tracer.write(trace)
    if metrics:
        import json
        with open(metrics, "w") as f:
            json.dump(server.registry.snapshot(), f, indent=2)
    return stats


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--preset", default="smoke", choices=["smoke", "full"])
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--he", action="store_true",
                    help="serve a batched multi-level HE request stream "
                         "(queue → level-aware table cache → sharded "
                         "mul/rotate engine) instead of an LM")
    ap.add_argument("--requests", type=int, default=0,
                    help="HE requests to stream (default 2·batch+1, which "
                         "exercises multi-batch assembly and padding)")
    ap.add_argument("--levels", type=int, default=1,
                    help="number of moduli to spread HE requests over "
                         "(level i serves logq = logQ − i·logp from the "
                         "resident table cache)")
    ap.add_argument("--rotations", type=int, default=0,
                    help="how many of the HE requests are rotate(r=1) "
                         "instead of mul")
    ap.add_argument("--conjugations", type=int, default=0,
                    help="how many of the HE requests are conjugate "
                         "(σ₋₁ through the same key-switch machinery)")
    ap.add_argument("--plain-frac", type=float, default=0.0,
                    help="serve this fraction of the mul share as "
                         "plaintext-operand ops (mul_plain/add_plain: "
                         "encode-only operand, NO key switch — the "
                         "encrypted-inference affine-layer fast path)")
    ap.add_argument("--circuit", action="store_true",
                    help="also submit a degree-4 encrypted polynomial "
                         "circuit (mul → rescale → mod-down → conjugate "
                         "→ add) via submit_circuit and verify it "
                         "(two staggered copies under --schedule)")
    ap.add_argument("--schedule", action="store_true",
                    help="circuit-aware scheduling: co-batch same-"
                         "(op, level) nodes across circuits via "
                         "lookahead deferral and prefetch next-level "
                         "table slices behind the in-flight batch")
    ap.add_argument("--traced", type=int, default=0,
                    help="also run this many TRACED repro.client "
                         "expressions (every handle op, auto level "
                         "management) through the session; they share "
                         "one weight vector, so runs after the first "
                         "hit the server's plaintext-operand cache")
    ap.add_argument("--check", default="off",
                    choices=["off", "warn", "error"],
                    help="static-analyze circuits before submission "
                         "(repro.analysis): 'warn' prints findings, "
                         "'error' refuses to submit on errors/warnings")
    ap.add_argument("--max-age-s", type=float, default=None,
                    help="continuous-batching SLO: flush a bucket once "
                         "its oldest request has waited this long "
                         "(default: drain-only flushing)")
    ap.add_argument("--overlap", action="store_true",
                    help="double-buffer batch assembly + device_put "
                         "against the in-flight engine step")
    ap.add_argument("--kernels", action="store_true",
                    help="route HE stages through the repro.kernels "
                         "Pallas paths (interpret mode off-TPU)")
    ap.add_argument("--model-shards", type=int, default=1,
                    help="size of the model axis of the host mesh")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="write a Chrome trace-event JSON of the request "
                         "lifecycle (submit → enqueue → bucket-wait → "
                         "flush → assemble → dispatch → device-wall → "
                         "complete) + engine spans; open in Perfetto or "
                         "run `python -m repro.obs report PATH`")
    ap.add_argument("--profile-dir", default=None, metavar="DIR",
                    help="record a jax.profiler trace of the served "
                         "stream into DIR with the tracer on: hserve.* "
                         "host spans beside the device ops, which the "
                         "he.crt/ntt/intt/modmul/icrt scopes assign to "
                         "the paper's Fig. 3 stages (open in xprof or "
                         "Perfetto)")
    ap.add_argument("--workers", type=int, default=0,
                    help="serve through the multi-host tier: an "
                         "HEFrontend routing batches by (op, level) "
                         "affinity to this many in-process worker "
                         "engines, with heartbeat health and worker-"
                         "death requeue (0 = single HEServer)")
    ap.add_argument("--bootstrap", type=int, nargs="?", const=2,
                    default=0, metavar="N",
                    help="also serve N concurrent CKKS bootstrap "
                         "pipelines (repro.boot) over level-exhausted "
                         "ciphertexts; bare --bootstrap means N=2 so "
                         "cross-circuit co-batching is exercised. "
                         "Switches the run to the reference bootstrap "
                         "params (logQ=336, h=2); results verify "
                         "against the documented error bound")
    ap.add_argument("--full", action="store_true",
                    help="serve at the paper's Table III params "
                         "(configs.heaan_mul.CONFIG: logN=16, "
                         "logQ=1200) instead of the logN=5 smoke set — "
                         "the TPU target's configuration")
    ap.add_argument("--metrics", default=None, metavar="PATH",
                    help="dump the unified MetricsRegistry snapshot "
                         "(serve/cache/scheduler/engine/client planes) "
                         "as JSON after the drain")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.he:
        from repro.configs.heaan_mul import CONFIG
        stats = serve_he(args.batch, requests=args.requests,
                         levels=args.levels, rotations=args.rotations,
                         conjugations=args.conjugations,
                         plain_frac=args.plain_frac,
                         model_shards=args.model_shards,
                         use_kernels=args.kernels,
                         max_age_s=args.max_age_s, overlap=args.overlap,
                         circuit=args.circuit, schedule=args.schedule,
                         traced=args.traced, check=args.check,
                         trace=args.trace,
                         profile_dir=args.profile_dir,
                         metrics=args.metrics, workers=args.workers,
                         bootstrap=args.bootstrap,
                         params=CONFIG if args.full else None)
        ops = ", ".join(
            f"{op}: {d['requests']} reqs @ {d['ops_per_s']}/s "
            f"(p50 {d['latency_ms']['p50']}ms, "
            f"p99 {d['latency_ms']['p99']}ms, pad {d['pad_frac']})"
            for op, d in stats["per_op"].items())
        print(f"hserve batch={stats['batch']} on {stats['devices']} "
              f"device(s) {stats['mesh']} levels={stats['levels_served']} "
              f"steps_compiled={stats['engine']['steps_compiled']} "
              f"(compile {stats['engine']['compile_s']}s)")
        print(f"  {ops}")
        if args.workers:
            fr = stats["frontend"]
            print(f"  frontend: {fr['workers']} {fr['transport']} "
                  f"worker(s), {fr['alive']} alive, "
                  f"{fr['deaths']} death(s), "
                  f"{fr['requeued_requests']} requeued")
        if args.schedule:
            sch, cb = stats["scheduler"], stats["cobatch"]
            print(f"  scheduler: lookahead={sch['lookahead']} "
                  f"deferrals={sch['deferrals']} "
                  f"prefetched_levels={sch['prefetched_levels']} "
                  f"cross_circuit_rate={cb['cross_circuit_rate']}")
        if args.traced:
            c = stats["cache"]
            print(f"  plaintext cache: {c['plain_hits']} hits / "
                  f"{c['plain_misses']} misses "
                  f"({c['plain_entries']} entries)")
        if args.profile_dir:
            print(f"  profiler trace -> {args.profile_dir}")
        if args.bootstrap:
            bs = stats["bootstrap"]
            print(f"  bootstrap: {bs['n']} concurrent pipeline(s) "
                  f"logq {bs['logq_in']} -> {bs['out_logq']}, "
                  f"max_err {bs['max_err']:.2e} "
                  f"(bound {bs['error_bound']:.2e}), "
                  f"cross_circuit_rate {bs['cross_circuit_rate']}")
        if args.trace:
            print(f"  trace: {stats['trace_events']} events -> "
                  f"{args.trace}")
        if args.metrics:
            print(f"  metrics snapshot -> {args.metrics}")
        print(f"  bitwise vs core (CPU): {stats['bitwise_checked']} "
              "results equal")
        print(f"  max_err {stats['max_err']:.2e}")
        assert stats["max_err"] < 1e-2, "HE serving pipeline diverged"
        return

    from repro.configs.registry import get_arch
    from repro.dist.sharding import batch_spec, param_sharding_rules
    from repro.launch.mesh import make_host_mesh
    cfg = get_arch(args.arch)
    if args.preset == "smoke":
        cfg = cfg.reduced()
    mesh = make_host_mesh(model=args.model_shards)  # validates divisibility
    rng = np.random.default_rng(0)
    params = init_params(cfg, jax.random.key(0))
    # tensor-parallel only: FSDP-sharded weights would re-gather on every
    # decode step, and serving has no gradients to shard for
    params = jax.device_put(
        params, param_sharding_rules(params, mesh, fsdp_params=False))
    tokens = jnp.asarray(
        rng.integers(0, cfg.vocab_size, size=(args.batch, args.prompt_len)),
        jnp.int32)
    if args.batch % mesh.shape["data"] == 0:
        tokens = jax.device_put(tokens, batch_spec(mesh))
    extra = {}
    if cfg.enc_dec:
        extra["frames"] = jnp.asarray(rng.normal(
            size=(args.batch, 2 * args.prompt_len, cfg.d_model)),
            jnp.float32)
    if cfg.frontend == "vision":
        extra["patch_embeds"] = jnp.asarray(rng.normal(
            size=(args.batch, cfg.n_frontend_tokens, cfg.d_model)),
            jnp.float32)
    t0 = time.time()
    out = generate(params, cfg, tokens,
                   args.gen, args.prompt_len + args.gen + 8,
                   batch_extra=extra)
    dt = time.time() - t0
    tps = args.batch * args.gen / dt
    print(f"arch={args.arch} generated {out.shape} in {dt:.2f}s "
          f"({tps:.1f} tok/s, incl. compile)")


if __name__ == "__main__":
    main()
