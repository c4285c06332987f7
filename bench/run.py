#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as the last line.

    python3 bench/run.py --workload t3_mul_sat --seed 7 --seconds 30 --trace 0

The cell (an entry of ``workloads`` in ``BENCHMARK.json``) names a
deployment (``bench/configs/<config>.json``: CKKS parameters, serving
batch, chips) and a traffic mix (``bench/traffic/<traffic>.json``, read
by ``bench/generator.py``). One run:

1. keeps JAX's compilation cache in ``<checkout>/.jax_cache``;
2. refuses to run (exit 3, no result line) without a TPU with as many
   chips as the cell asks for, or on a device missing from
   ``bench/peaks.json``;
3. draws the keys and a small operand pool from ``--seed`` on the device
   and builds an `HEServer` with the deployment's settings (every other
   setting is the program's default);
4. warms the cell's own (op, level) buckets through the served path;
5. drives the window through ``HEServer.submit_*`` and ``poll``;
6. compares sampled answers of the window, word for word, with the
   plain reference in ``bench/reference/``;
7. prints one JSON line: ``correct``, ``attempted``, ``failed``,
   ``metrics`` (end-to-end ones, or with ``--trace 1`` the per-layer
   ones, read by ``bench/metrics/<name>.py``), ``device`` and, last,
   ``checks``: each number compared with its limit.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Callable, Dict, List, Tuple  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import numpy as np  # noqa: E402

from bench import generator as G  # noqa: E402
from bench import opcount  # noqa: E402

CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_out" / "trace"

# the generator sleeps at most this long between polls, so an age flush
# is never held back by more than this
POLL_TICK_S = 0.002


class NoChip(RuntimeError):
    """The machine cannot run this cell: nothing is measured."""


def info(msg: str) -> None:
    print(f"# {msg}", flush=True)


# ---------------------------------------------------------------------------
# the cell, from BENCHMARK.json and the files it names
# ---------------------------------------------------------------------------

def load_cell(name: str, root: Path = ROOT) -> dict:
    """The workload entry with its config, traffic and metric entries."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]

    def applies(m):
        return "workloads" not in m or name in m["workloads"]

    return {
        "name": name,
        "chips": int(w["chips"]),
        "config": json.loads((root / conf["file"]).read_text()),
        "traffic": json.loads(
            (root / "bench" / "traffic" / f"{w['traffic']}.json")
            .read_text()),
        "end_to_end": [m for m in spec["end_to_end"] if applies(m)],
        "per_layer": [m for m in spec["per_layer"] if applies(m)],
        "root": root,
    }


def metric_reader(name: str, root: Path = ROOT) -> Callable:
    """``read(record) -> float | None`` from bench/metrics/<name>.py."""
    path = root / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def make_params(conf: dict):
    from repro.core.params import HEParams
    return HEParams(logN=conf["logN"], logQ=conf["logQ"], logp=conf["logp"],
                    log_delta=conf["log_delta"],
                    beta_bits=conf["beta_bits"], sigma=conf["sigma"],
                    h=conf["h"])


def chip_devices(chips: int, require_tpu: bool, root: Path = ROOT):
    """The devices the cell runs on; NoChip where the machine lacks them."""
    import jax
    devs = jax.devices()
    if require_tpu:
        if devs[0].platform != "tpu":
            raise NoChip(f"jax finds no TPU (platform "
                         f"{devs[0].platform!r}); nothing measured")
        peaks = json.loads((root / "bench" / "peaks.json").read_text())
        if devs[0].device_kind not in peaks["devices"]:
            raise NoChip(f"device {devs[0].device_kind!r} is not in "
                         "bench/peaks.json")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chip(s), jax sees "
                     f"{len(devs)}")
    return devs[:chips]


# ---------------------------------------------------------------------------
# set-up: keys, operand pool, server, warm buckets
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Material:
    """What the seed makes: keys (mod Q², coefficient words) on the
    device and on the host, and per level a pool of ciphertexts and
    plaintexts (host words, as a client would send them)."""
    keys_dev: Dict
    keys_host: Dict
    cts: Dict[Tuple[int, int], object]      # (level, i) -> Ciphertext
    cts_sq: Dict[Tuple[int, int], object]   # same words at scale Δ²
    pts: Dict[Tuple[int, int], np.ndarray]


def _words(bits: int) -> int:
    return -(-bits // 32)


def make_material(params, traffic: dict, seed: int) -> Material:
    """One jitted call draws every key and pool word from the seed."""
    import jax
    import jax.numpy as jnp
    from repro.core.cipher import Ciphertext

    N, logQ, logp = params.N, params.logQ, params.logp
    shapes = {"evk": (2, N, _words(2 * logQ), 2 * logQ)}
    if traffic["mix"].get("rotate", 0) > 0:
        shapes[("rot", traffic.get("rotate_by", 1))] = \
            (2, N, _words(2 * logQ), 2 * logQ)
    n = traffic["pool"]
    for lv in traffic["levels"]:
        bits = logQ - lv * logp
        shapes[("ct", lv)] = (n, 2, N, _words(bits), bits)
        if traffic["mix"].get("mul_plain", 0) > 0:
            shapes[("pt", lv)] = (n, N, _words(bits), bits)
    names = list(shapes)

    @jax.jit
    def draw(key):
        out = []
        for i, name in enumerate(names):
            *shape, bits = shapes[name]
            w = jax.random.bits(jax.random.fold_in(key, i), tuple(shape),
                                jnp.uint32)
            if bits % 32:
                top = jnp.uint32((1 << (bits % 32)) - 1)
                w = w.at[..., -1].set(w[..., -1] & top)
            out.append(w)
        return out

    key = jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)
    drawn = dict(zip(names, draw(key)))
    keys_dev = {k: (v[0], v[1]) for k, v in drawn.items()
                if k == "evk" or k[0] == "rot"}
    keys_host = {k: (np.asarray(a), np.asarray(b))
                 for k, (a, b) in keys_dev.items()}
    cts, cts_sq, pts = {}, {}, {}
    for lv in traffic["levels"]:
        logq = logQ - lv * logp
        host = np.asarray(drawn[("ct", lv)])
        for i in range(n):
            for store, lp in ((cts, params.log_delta),
                              (cts_sq, 2 * params.log_delta)):
                store[(lv, i)] = Ciphertext(ax=host[i, 0], bx=host[i, 1],
                                            logq=logq, logp=lp,
                                            n_slots=params.n_slots_max)
        if ("pt", lv) in drawn:
            ph = np.asarray(drawn[("pt", lv)])
            for i in range(n):
                pts[(lv, i)] = ph[i]
    return Material(keys_dev, keys_host, cts, cts_sq, pts)


def eval_key(params, ax, bx):
    """The program's evaluation-key form of a key given mod Q² as words:
    region-2 CRT + NTT and Shoup companions, as `core.keys.keygen` makes
    them."""
    import jax.numpy as jnp
    from repro.core import rns
    from repro.core.cipher import EvalKey
    from repro.core.context import _shoup_vec, build_global_tables

    g = build_global_tables(params)
    np2 = params.np_region2(params.logQ)
    primes = np.asarray(g.primes[:np2])
    ax_ev = rns.to_eval(jnp.asarray(ax), np2, g)
    bx_ev = rns.to_eval(jnp.asarray(bx), np2, g)
    return EvalKey(
        ax_ev=ax_ev,
        ax_ev_shoup=jnp.asarray(_shoup_vec(np.asarray(ax_ev), primes,
                                           params.beta_bits)),
        bx_ev=bx_ev,
        bx_ev_shoup=jnp.asarray(_shoup_vec(np.asarray(bx_ev), primes,
                                           params.beta_bits)))


def build_server(params, conf: dict, traffic: dict, mat: Material,
                 devices):
    from repro.hserve import HEServer
    from repro.launch.mesh import make_mesh

    model = int(conf["model_shards"])
    mesh = make_mesh((len(devices) // model, model), devices=devices)
    evk = eval_key(params, *mat.keys_dev["evk"])
    rot = {k[1]: eval_key(params, *v) for k, v in mat.keys_dev.items()
           if k != "evk"}
    return HEServer(params, evk, rot_keys=rot or None, mesh=mesh,
                    batch=int(conf["batch"]),
                    max_age_s=traffic.get("max_age_s"))


class Submitter:
    """Sends one generator request through the server's submit_* calls."""

    def __init__(self, server, params, traffic: dict, mat: Material):
        self.server = server
        self.params = params
        self.r = traffic.get("rotate_by", 1)
        self.mat = mat

    def __call__(self, req: G.Request) -> int:
        s, m, lv = self.server, self.mat, req.level
        i = req.operands[0]
        if req.op == "mul":
            return s.submit_mul(m.cts[(lv, i)], m.cts[(lv, req.operands[1])])
        if req.op == "mul_plain":
            return s.submit_mul_plain(m.cts[(lv, i)], m.pts[(lv, i)])
        if req.op == "rotate":
            return s.submit_rotate(m.cts[(lv, i)], self.r)
        if req.op == "rescale":
            return s.submit_rescale(m.cts_sq[(lv, i)])
        raise ValueError(f"unknown op {req.op!r}")


def warm(server, submit: Submitter, traffic: dict, batch: int
         ) -> Dict[str, float]:
    """One full batch per (op, level) bucket of the cell, through the
    served path (compile, first run, and each lane's result slice)."""
    out = {}
    for op, lv in G.bucket_list(traffic):
        t = time.perf_counter()
        for k in range(batch):
            ops = tuple((k + j) % traffic["pool"]
                        for j in range(G.ARITY[op]))
            submit(G.Request(0.0, op, lv, ops))
        got = 0
        while got < batch:
            got += len(server.poll(flush=True))
        out[f"{op}@{lv}"] = time.perf_counter() - t
    return out


# ---------------------------------------------------------------------------
# the window
# ---------------------------------------------------------------------------

class CompileCounter:
    """Counts the executables JAX builds (or loads from its cache) while
    `on` is set."""

    def __init__(self):
        import jax
        self.on = False
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, name, _secs, **_kw):
        if self.on and name == "/jax/core/compile/backend_compile_duration":
            self.n += 1


@dataclasses.dataclass
class Sample:
    req: G.Request
    lane: int
    ct: object              # the served Ciphertext (device arrays)


@dataclasses.dataclass
class WindowRecord:
    t0: float
    window_s: float
    attempted: int
    completed: int
    failed: int
    # (op, level, n_valid, seconds after the window opened)
    batches: List[Tuple[str, int, int, float]]
    latencies_s: List[float] = dataclasses.field(default_factory=list)
    lateness_s: List[float] = dataclasses.field(default_factory=list)
    due_by_rid: Dict[int, float] = dataclasses.field(default_factory=dict)
    buckets_served: set = dataclasses.field(default_factory=set)


def _annotate(on: bool):
    if not on:
        return lambda name: contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation


class Sampler:
    """Which answers of the window are checked, drawn from the seed.

    Per (op, level) bucket one answer, at lane 0 of a batch for half of
    the buckets and at the last lane of a batch with at least two
    answers for the other half; a closed loop with one bucket takes two
    answers of two early full batches, one in each half of the batch.
    The most recent answer of each bucket is kept as a fallback.
    """

    def __init__(self, traffic: dict, batch: int, seed: int):
        rng = np.random.default_rng([int(seed) & (2**63 - 1), 3])
        self.buckets = G.bucket_list(traffic)
        self.targets: Dict[Tuple[str, int], List[Tuple[int, str]]] = {}
        if len(self.buckets) == 1:
            j = sorted(rng.choice(np.arange(1, 6), 2, replace=False))
            half = max(1, batch // 2)
            lanes = (int(rng.integers(0, half)),
                     int(rng.integers(half, max(half + 1, batch))))
            self.targets[self.buckets[0]] = [(int(j[0]), lanes[0]),
                                             (int(j[1]), lanes[1])]
        else:
            flip = int(rng.integers(2))
            for k, b in enumerate(self.buckets):
                lane = "last" if (k + flip) % 2 else "first"
                self.targets[b] = [(int(rng.integers(0, 3)), lane)]
        self.seen: Dict[Tuple[str, int], int] = {}
        self.taken: List[Sample] = []
        self.fallback: Dict[Tuple[str, int], Sample] = {}

    def offer(self, reqs: List[G.Request], cts: list) -> None:
        b = (reqs[0].op, reqs[0].level)
        j = self.seen.get(b, 0)
        self.seen[b] = j + 1
        self.fallback[b] = Sample(reqs[0], 0, cts[0])
        left = []
        for tj, lane in self.targets.get(b, []):
            if isinstance(lane, int):
                if tj == j and lane < len(reqs):
                    self.taken.append(Sample(reqs[lane], lane, cts[lane]))
                    continue
            elif j >= tj and (lane == "first" or len(reqs) >= 2):
                ln = 0 if lane == "first" else len(reqs) - 1
                self.taken.append(Sample(reqs[ln], ln, cts[ln]))
                continue
            left.append((tj, lane))
        self.targets[b] = left

    def samples(self) -> List[Sample]:
        """The targets met, plus a fallback for each bucket served whose
        targets the window never reached."""
        covered = {(s.req.op, s.req.level) for s in self.taken}
        return self.taken + [s for b, s in self.fallback.items()
                             if b not in covered]


def run_closed(server, submit: Submitter, traffic: dict, batch: int,
               seconds: float, seed: int, sampler: Sampler, trace: bool
               ) -> WindowRecord:
    """Closed loop: outstanding_batches × batch requests stay in the
    server; each answer is replaced at once. The window closes with the
    first batch that ends at or after `seconds`: the rate is every
    request completed over the time to that batch's end."""
    ann = _annotate(trace)
    gen = G.closed_loop(traffic, seed)
    pending: Dict[int, G.Request] = {}
    batches = []
    done = 0
    with ann("bench.window"):
        t0 = time.perf_counter()
        with ann("bench.submit"):
            for _ in range(traffic["outstanding_batches"] * batch):
                req = next(gen)
                pending[submit(req)] = req
        while True:
            with ann("bench.poll"):
                res = server.poll()
            t = time.perf_counter()
            if res:
                with ann("bench.results"):
                    reqs = [pending.pop(rid) for rid, _ in res]
                    done += len(res)
                    batches.append((reqs[0].op, reqs[0].level, len(res),
                                    t - t0))
                    sampler.offer(reqs, [ct for _, ct in res])
            if t - t0 >= seconds:
                break
            with ann("bench.submit"):
                for _ in res:
                    req = next(gen)
                    pending[submit(req)] = req
    return WindowRecord(t0=t0, window_s=t - t0, attempted=done,
                        completed=done, failed=0, batches=batches,
                        buckets_served={b[:2] for b in batches})


def run_open(server, submit: Submitter, traffic: dict, seconds: float,
             seed: int, sampler: Sampler, trace: bool) -> WindowRecord:
    """Open loop: the generator's Poisson arrivals, sent when due
    whatever the server does. A latency runs from the due time to the
    poll that returned the answer; an answer missing `grace_s` after the
    window closed is a failure."""
    ann = _annotate(trace)
    reqs = G.open_loop(traffic, seconds, seed)
    grace = float(traffic["grace_s"])
    pending: Dict[int, G.Request] = {}
    due_by_rid: Dict[int, float] = {}
    lat: Dict[int, float] = {}
    lateness, batches = [], []
    i = 0

    def step(now: float, t0: float) -> bool:
        nonlocal i
        if i < len(reqs) and t0 + reqs[i].due <= now:
            with ann("bench.submit"):
                while i < len(reqs) and t0 + reqs[i].due <= now:
                    due = t0 + reqs[i].due
                    rid = submit(reqs[i])
                    pending[rid] = reqs[i]
                    due_by_rid[rid] = due
                    lateness.append(time.perf_counter() - due)
                    i += 1
        with ann("bench.poll"):
            res = server.poll()
        if res:
            t = time.perf_counter()
            with ann("bench.results"):
                got = [pending.pop(rid) for rid, _ in res]
                for rid, _ in res:
                    lat[rid] = t - due_by_rid[rid]
                batches.append((got[0].op, got[0].level, len(res),
                                t - t0))
                sampler.offer(got, [ct for _, ct in res])
            return True
        return False

    def idle(now: float, t0: float) -> None:
        nxt = t0 + reqs[i].due if i < len(reqs) else now + POLL_TICK_S
        with ann("bench.sleep"):
            time.sleep(max(0.0, min(nxt - time.perf_counter(),
                                    POLL_TICK_S)))

    with ann("bench.window"):
        t0 = time.perf_counter()
        while True:
            now = time.perf_counter()
            if now - t0 >= seconds:
                break
            if not step(now, t0):
                idle(now, t0)
    end = t0 + seconds
    while pending and time.perf_counter() < end + grace:
        now = time.perf_counter()
        if not step(now, t0):
            idle(now, t0)
    failed = len(pending)
    latencies = list(lat.values()) + [end + grace - due_by_rid[r]
                                      for r in pending]
    return WindowRecord(t0=t0, window_s=seconds, attempted=len(reqs),
                        completed=len(lat), failed=failed, batches=batches,
                        latencies_s=latencies, lateness_s=lateness,
                        due_by_rid=due_by_rid,
                        buckets_served={b[:2] for b in batches})


# ---------------------------------------------------------------------------
# correctness: sampled answers against the plain reference
# ---------------------------------------------------------------------------

def expected(ref, params, traffic: dict, mat: Material, req: G.Request):
    """The reference's answer to one request."""
    from bench.reference.heaan import Ct
    lv, i = req.level, req.operands[0]

    def ct(c):
        return Ct(np.asarray(c.ax), np.asarray(c.bx), c.logq, c.logp)

    if req.op == "mul":
        return ref.mul(ct(mat.cts[(lv, i)]),
                       ct(mat.cts[(lv, req.operands[1])]))
    if req.op == "mul_plain":
        return ref.mul_plain(ct(mat.cts[(lv, i)]), mat.pts[(lv, i)],
                             params.log_delta)
    if req.op == "rotate":
        return ref.rotate(ct(mat.cts[(lv, i)]), traffic.get("rotate_by", 1))
    if req.op == "rescale":
        return ref.rescale(ct(mat.cts_sq[(lv, i)]), params.logp)
    raise ValueError(req.op)


def compare(got, want) -> Tuple[int, int]:
    """(mismatched words, mismatched level fields) of one answer."""
    words = 0
    for g, w in ((got.ax, want.ax), (got.bx, want.bx)):
        g = np.asarray(g)
        words += (int(np.count_nonzero(g != w)) if g.shape == w.shape
                  else w.size)
    meta = int(got.logq != want.logq) + int(got.logp != want.logp)
    return words, meta


def reference_for(params, mat: Material, cdtype=np.complex128):
    from bench.reference.heaan import Reference
    return Reference(params.logQ, mat.keys_host, cdtype=cdtype)


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def _pct(values: List[float], q: float) -> float:
    """Nearest-rank percentile."""
    v = sorted(values)
    return v[max(0, math.ceil(q / 100 * len(v)) - 1)]


def op_work(params, traffic: dict) -> Dict[str, float]:
    """Table IV work per request of the mix (NTTs, CRT, iCRT ops)."""
    share = sum(traffic["mix"].values())
    tot = {"transforms": 0.0, "CRT": 0.0, "NTT": 0.0, "iNTT": 0.0,
           "iCRT": 0.0}
    for op, s in traffic["mix"].items():
        for lv in traffic["levels"]:
            logq = params.logQ - lv * params.logp
            nps = {1: params.np_region1(logq), 2: params.np_region2(logq)}
            pl = {r: params.limbs_for_bits(int(sum(
                math.log2(p) for p in params.primes[:n])))
                for r, n in nps.items()}
            c = opcount.op_counts(op, params.N, params.logN,
                                  params.qlimbs(logq), nps, pl)
            for k in tot:
                tot[k] += c[k] * s / share / len(traffic["levels"])
    return tot


@dataclasses.dataclass
class Setup:
    """A cell ready for its window: the server warm, its metrics reset."""
    params: object
    traffic: dict
    batch: int
    devices: list
    mat: Material
    server: object
    submit: Submitter
    parts: Dict[str, float]


def setup(cell: dict, seed: int, *, require_tpu: bool = True) -> Setup:
    """Everything before the window: keys and pool from the seed, the
    server, and a warm-up of the cell's buckets."""
    import jax
    devices = chip_devices(cell["chips"], require_tpu, cell["root"])
    conf, traffic = cell["config"], cell["traffic"]
    params = make_params(conf)
    batch = int(conf["batch"])
    parts = {"process_and_jax_init_s": time.perf_counter() - T_START}
    info(f"cell {cell['name']} seed {seed} on {len(devices)}x "
         f"{devices[0].device_kind}")
    w = op_work(params, traffic)
    info("Table IV work per request of the mix: "
         f"{w['transforms']:.0f} length-N NTTs, CRT {w['CRT']:.4g} ops, "
         f"NTT {w['NTT']:.4g}, iNTT {w['iNTT']:.4g}, iCRT {w['iCRT']:.4g}")
    t = time.perf_counter()
    mat = make_material(params, traffic, seed)
    jax.block_until_ready(list(mat.keys_dev.values()))
    parts["keys_and_pool_s"] = time.perf_counter() - t
    t = time.perf_counter()
    server = build_server(params, conf, traffic, mat, devices)
    parts["server_and_key_tables_s"] = time.perf_counter() - t
    submit = Submitter(server, params, traffic, mat)
    warm_s = warm(server, submit, traffic, batch)
    parts.update({f"warm_{k}_s": v for k, v in warm_s.items()})
    if traffic.get("max_age_s"):
        # let the arrival-rate estimate forget the warm-up burst
        time.sleep(server._RATE_DECAY_WINDOWS * traffic["max_age_s"])
    server.reset_metrics()
    return Setup(params, traffic, batch, devices, mat, server, submit,
                 parts)


def run_cell(cell: dict, seed: int, seconds: float, trace: bool, *,
             require_tpu: bool = True) -> dict:
    """Set up, measure, check; returns the result line's object."""
    import jax
    su = setup(cell, seed, require_tpu=require_tpu)
    params, traffic, batch, devices = (su.params, su.traffic, su.batch,
                                       su.devices)
    mat, server, submit, parts = su.mat, su.server, su.submit, su.parts
    del su
    info(f"window {seconds} s, trace {int(trace)}")
    tracer_clock = None
    if trace:
        from repro.obs import Tracer
        tracer_clock = _FirstReading()
        server.tracer = Tracer(clock=tracer_clock)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(str(TRACE_DIR))
    sampler = Sampler(traffic, batch, seed)
    counter = CompileCounter()
    counter.on = True
    if traffic["loop"] == "closed":
        rec = run_closed(server, submit, traffic, batch, seconds, seed,
                         sampler, trace)
    else:
        rec = run_open(server, submit, traffic, seconds, seed, sampler,
                       trace)
    counter.on = False
    setup_s = rec.t0 - T_START
    summary = server.metrics.summary()
    if trace:
        jax.profiler.stop_trace()
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    info("set-up parts (s): " + ", ".join(f"{k} {v:.3f}"
                                          for k, v in parts.items()))
    info(f"setup_s {setup_s:.3f}; compilations inside the window: "
         f"{counter.n}")
    if rec.lateness_s:
        info(f"generator lateness: mean {1e3 * np.mean(rec.lateness_s):.3f}"
             f" ms, p95 {1e3 * _pct(rec.lateness_s, 95):.3f} ms, max "
             f"{1e3 * max(rec.lateness_s):.3f} ms over "
             f"{len(rec.lateness_s)} requests")
    pad = sum(batch - b[2] for b in rec.batches)
    info(f"window {rec.window_s:.3f} s: {rec.completed} of "
         f"{rec.attempted} requests answered in {len(rec.batches)} batches,"
         f" {pad} padded slots; flushes {summary.get('flushes')}")

    # per-layer readings, then free the program's state
    record = {"window_s": rec.window_s, "batch": batch,
              "batches": rec.batches, "serve": summary,
              "latencies_s": rec.latencies_s, "due_by_rid": rec.due_by_rid,
              "trace": None, "lifecycle": None}
    if trace:
        record["lifecycle"] = _lifecycle(server.tracer, tracer_clock)
    samples = [dataclasses.replace(
        s, ct=dataclasses.replace(s.ct, ax=np.asarray(s.ct.ax),
                                  bx=np.asarray(s.ct.bx)))
        for s in sampler.samples()]
    del server, submit, sampler
    gc.collect()

    result: dict = {"correct": False, "attempted": rec.attempted,
                    "failed": rec.failed, "metrics": {}}
    dev = devices[0]
    result["device"] = {"platform": dev.platform, "kind": dev.device_kind,
                        "device_kind": dev.device_kind,
                        "count": len(devices), "memory_peak_bytes": peak}
    if trace:
        from bench.trace_reduce import find_xplane, load, reduce_trace
        red = reduce_trace(load(find_xplane(str(TRACE_DIR))))
        record["trace"] = red
        result["device"]["busy_s"] = red["busy_s"]
        result["device"]["window_s"] = red["window_s"]
        result["breakdown"] = {"device_ops": red["device_ops"],
                               "idle_gaps": red["idle_gaps"]}
        info(f"device busy {red['busy_s']:.4f} s of "
             f"{red['window_s']:.4f} s (idle share "
             f"{red['idle_share']:.4f}); idle by host span: "
             + json.dumps(red["idle_by_span"]))
        for m in cell["per_layer"]:
            v = metric_reader(m["name"], cell["root"])(record)
            if v is not None:
                result["metrics"][m["name"]] = {"value": v,
                                                "unit": m["unit"]}
    else:
        e2e = {"setup_s": setup_s}
        if traffic["loop"] == "closed":
            e2e["ops_per_s"] = rec.completed / rec.window_s
        else:
            e2e["latency_p95_ms"] = 1e3 * _pct(rec.latencies_s, 95)
            e2e["latency_p50_ms"] = 1e3 * _pct(rec.latencies_s, 50)
            lim = traffic["latency_limit_ms"] / 1e3
            met = sum(1 for x in rec.latencies_s if x <= lim)
            info(f"{met} of {len(rec.latencies_s)} requests answered "
                 f"within {traffic['latency_limit_ms']} ms")
        for m in cell["end_to_end"]:
            if m["name"] in e2e:
                result["metrics"][m["name"]] = {"value": e2e[m["name"]],
                                                "unit": m["unit"]}

    # correctness
    t = time.perf_counter()
    ref = reference_for(params, mat)
    words = meta = 0
    for s in samples:
        wd, mt = compare(s.ct, expected(ref, params, traffic, mat, s.req))
        words += wd
        meta += mt
        info(f"checked {s.req.op}@{s.req.level} lane {s.lane}: "
             f"{wd} words differ")
    checked = {(s.req.op, s.req.level) for s in samples}
    info(f"reference took {time.perf_counter() - t:.3f} s for "
         f"{len(samples)} answers ({len({s.lane for s in samples})} lane "
         "positions)")
    checks = {
        "mismatched_words": {"value": words, "limit": 0},
        "mismatched_levels": {"value": meta, "limit": 0},
        "unanswered": {"value": rec.failed, "limit": 0},
        "unchecked_buckets": {"value": len(rec.buckets_served - checked),
                              "limit": 0},
    }
    result["correct"] = all(c["value"] <= c["limit"]
                            for c in checks.values())
    result["checks"] = checks
    return result


class _FirstReading:
    """perf_counter, remembering its first reading: the program's Tracer
    takes its time origin from its clock's first call."""

    def __init__(self):
        self.first = None

    def __call__(self) -> float:
        t = time.perf_counter()
        if self.first is None:
            self.first = t
        return t


def _lifecycle(tracer, clock: _FirstReading) -> Dict[int, float]:
    """rid -> the time its batch left the queue (end of its bucket_wait
    span), on the host's perf_counter clock."""
    out = {}
    for e in tracer.events:
        if e.get("name") == "bucket_wait":
            out[e["args"]["rid"]] = clock.first + (e["ts"] + e["dur"]) / 1e6
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    try:
        cell = load_cell(args.workload)
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
