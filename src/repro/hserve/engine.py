"""Jit-once sharded op engine: the full ciphertext-level op set.

One compiled step per trace signature ``(op, logq[, extra])``, each built
from `dist.he_pipeline`'s stage bundle so every op shares the same mesh
placement (batch → "data", CRT primes → "model") and the same table
pytrees out of :class:`repro.hserve.tables.TableCache`:

  - ``mul``      — `dist.he_pipeline.make_he_mul_step` unchanged
    (paper Fig. 2, both regions).
  - ``rotate``   — σ_{5^r} as a baked coefficient permutation + the SAME
    region-2 key switch HE Mul uses (`make_keyswitch_step`), so sharded
    rotations ride the pipeline for free (paper Fig. 2; HEAX lanes).
  - ``conjugate``— σ₋₁ (k = 2N−1) through the identical rotate step with
    the conjugation key; the automorphism index is the only difference.
  - ``slot_sum`` — the log₂(n)-rotation all-slots sum (the primitive
    encrypted dot products need), fused into one step: each round
    rotates by doubling powers and he_adds in place.
  - ``rescale`` / ``mod_down`` — the paper §III-A level-management ops.
    Because q is a power of two, both are batched shift/slice steps over
    the limb axis (no NTT, no key switch): rescale is a centered
    rounding shift by dlogp, mod-down a mask + limb slice. They reuse
    `core.heaan.rescale_poly` / `mod_down_poly` verbatim — the core and
    served paths share one implementation.
  - ``add`` / ``sub`` — §III-B limb adds with mod-q masking; cheap, but
    served so an entire encrypted circuit runs without a client
    round-trip between levels (the HEAX/Medha argument).
  - ``mul_plain`` / ``add_plain`` — the plaintext-operand ops encrypted
    inference's affine layers want: the operand is an ENCODED polynomial
    riding the batch (the "pt" array), so mul_plain is Fig. 2's region 1
    alone — CRT→NTT, one pointwise product per component, iNTT→iCRT —
    and add_plain a bare limb add into bx. NO region-2 key switch, no
    key material, no key-switch collectives: `launch.dryrun` lowers both
    and the HLO analysis shows zero collective bytes where mul pays the
    full region-2 traffic.

Every step is bitwise identical to its single-device `core` reference
(`core.heaan.he_mul`/`he_add`/`rescale`/`he_mod_down`,
`core.rotate.he_rotate`/`he_conjugate`, and the he_add/he_rotate
composition) — integer limb arithmetic partitions exactly across the
mesh, so sharding and batching never change a bit (tests/test_hserve.py,
including the 8-device mesh harness).

Double buffering: :meth:`OpEngine.dispatch` launches a step WITHOUT
blocking (JAX dispatch is async; `device_put` of the next batch and the
in-flight step overlap), returning an :class:`Inflight` handle that
:meth:`OpEngine.wait` later blocks on. `HEServer` uses the pair to
assemble batch n+1 while batch n runs, so the engine never waits on the
frontend; :meth:`OpEngine.run` is the synchronous dispatch→wait
composition.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import bigint
from repro.core.cipher import Ciphertext
from repro.core.heaan import mod_down_poly, mod_raise_poly, rescale_poly
from repro.core.params import HEParams
from repro.core.rotate import automorphism_poly, conjugation_k, rotation_k
from repro.dist.he_pipeline import (
    HEStatic, he_static, make_he_mul_step, make_keyswitch_step,
    make_stage_fns,
)
from repro.dist.sharding import he_limb_sharding
from repro.hserve.queue import Batch
from repro.hserve.tables import TableCache
from repro.obs.registry import Counter
from repro.obs.trace import span

__all__ = ["slot_sum_rotations", "make_he_rotate_step",
           "make_slot_sum_step", "make_rescale_step", "make_mod_down_step",
           "make_mod_raise_step", "make_addsub_step", "make_mul_plain_step",
           "make_add_plain_step",
           "Inflight", "OpEngine"]


def slot_sum_rotations(n_slots: int) -> Tuple[int, ...]:
    """Doubling rotation amounts (1, 2, 4, …) that sum n_slots slots."""
    out, r = [], 1
    while r < n_slots:
        out.append(r)
        r *= 2
    return tuple(out)


def _make_automorphism_b(st: HEStatic, k: int) -> Callable:
    """Batched σ_k on (B, N, qlimbs) mod-q limb polynomials — exactly
    core.rotate.automorphism_poly, vmapped over the batch axis (one
    source of truth for the permute+negate semantics)."""
    params, logq = st.params, st.logq

    def auto_b(x: jnp.ndarray) -> jnp.ndarray:
        return jax.vmap(
            lambda p: automorphism_poly(p, params, k, logq))(x)

    return auto_b


def make_he_rotate_step(st: HEStatic, mesh, k: int, **knobs):
    """Build step(t2, rk, ax, bx) -> (ax', bx') for the automorphism σ_k.

    Batched/sharded `core.rotate._apply_galois`: permute coefficients,
    then region-2 key-switch against the Galois key (same table pytree
    shape as the evk). Serves both "rotate" (k = 5^r) and "conjugate"
    (k = 2N−1) — the step is automorphism-index-generic. knobs are
    make_stage_fns' (use_kernels, …).
    """
    sf = make_stage_fns(st, mesh, **knobs)
    keyswitch = make_keyswitch_step(st, sf)
    auto_b = _make_automorphism_b(st, k)
    logq = st.logq

    def step(t2, rk, ax, bx):
        ax_r = auto_b(ax)
        bx_r = auto_b(bx)
        ks_ax, ks_bx = keyswitch(t2, rk, ax_r)
        ax3 = bigint.mask_bits(ks_ax, logq)
        bx3 = bigint.mask_bits(bigint.add(bx_r, ks_bx), logq)
        return sf.out(ax3), sf.out(bx3)

    return step


def make_slot_sum_step(st: HEStatic, mesh, n_slots: int, **knobs):
    """Build step(t2, rks, ax, bx) summing all n_slots slots into every
    slot: acc ← acc + rotate(acc, r) for r = 1, 2, 4, … — log₂(n) fused
    rotate+add rounds, one key switch each. `rks` is a tuple of rotation
    key pytrees in slot_sum_rotations(n_slots) order."""
    sf = make_stage_fns(st, mesh, **knobs)
    keyswitch = make_keyswitch_step(st, sf)
    params = st.params
    autos = [_make_automorphism_b(st, rotation_k(params, r))
             for r in slot_sum_rotations(n_slots)]
    logq = st.logq

    def addmask_f(a, b):
        return bigint.mask_bits(bigint.add(a, b), logq)

    def step(t2, rks, ax, bx):
        for auto_b, rk in zip(autos, rks):
            ax_r = auto_b(ax)
            bx_r = auto_b(bx)
            ks_ax, ks_bx = keyswitch(t2, rk, ax_r)
            rot_ax = bigint.mask_bits(ks_ax, logq)
            rot_bx = addmask_f(bx_r, ks_bx)
            ax = addmask_f(ax, rot_ax)
            bx = addmask_f(bx, rot_bx)
        return sf.out(ax), sf.out(bx)

    return step


def make_rescale_step(st: HEStatic, mesh, dlogp: int, **knobs):
    """Build step(ax, bx) -> (ax', bx') dividing by 2^dlogp (§III-A).

    A pure batched shift/slice over the limb axis — q is a power of two,
    so rescaling never touches the RNS side. Output arrays are
    (B, N, qlimbs') at logq' = logq − dlogp. The body IS
    `core.heaan.rescale_poly` (batch axes pass through), so served
    rescale is bitwise `core.rescale` by construction.
    """
    sf = make_stage_fns(st, mesh, **knobs)
    params, logq = st.params, st.logq

    def step(ax, bx):
        return (sf.out(rescale_poly(ax, params, logq, dlogp)),
                sf.out(rescale_poly(bx, params, logq, dlogp)))

    return step


def make_mod_down_step(st: HEStatic, mesh, logq2: int, **knobs):
    """Build step(ax, bx) -> (ax', bx') switching to modulus 2^logq2:
    mask + slice to qlimbs(logq2) limbs (`core.heaan.mod_down_poly`
    batched; level alignment before add/mul across depths)."""
    sf = make_stage_fns(st, mesh, **knobs)
    params = st.params

    def step(ax, bx):
        return (sf.out(mod_down_poly(ax, params, logq2)),
                sf.out(mod_down_poly(bx, params, logq2)))

    return step


def make_mod_raise_step(st: HEStatic, mesh, logq2: int, **knobs):
    """Build step(ax, bx) -> (ax', bx') raising to modulus 2^logq2 —
    the bootstrap's first stage (`core.heaan.mod_raise_poly` batched):
    zero-pad the limb axis to qlimbs(logq2), center at the OLD logq
    boundary (sign extension), re-mask at logq2. Pure limb arithmetic,
    no NTT and no key switch, so like rescale/mod_down it predicts zero
    key-switch collectives (shardlint pins this on HLO)."""
    sf = make_stage_fns(st, mesh, **knobs)
    params, logq = st.params, st.logq

    def step(ax, bx):
        return (sf.out(mod_raise_poly(ax, params, logq, logq2)),
                sf.out(mod_raise_poly(bx, params, logq, logq2)))

    return step


def make_addsub_step(st: HEStatic, mesh, op: str, **knobs):
    """Build step(ax1, bx1, ax2, bx2) for "add"/"sub" — §III-B limb
    arithmetic + mod-q masking, batched and placed on the mesh."""
    if op not in ("add", "sub"):             # not assert: gone under -O
        raise ValueError(f"addsub step takes op 'add' or 'sub', "
                         f"got {op!r}")
    sf = make_stage_fns(st, mesh, **knobs)
    fn = bigint.add if op == "add" else bigint.sub
    logq = st.logq

    def step(ax1, bx1, ax2, bx2):
        return (sf.out(bigint.mask_bits(fn(ax1, ax2), logq)),
                sf.out(bigint.mask_bits(fn(bx1, bx2), logq)))

    return step


def make_mul_plain_step(st: HEStatic, mesh, **knobs):
    """Build step(t1, ax, bx, pt) -> (ax', bx') for ciphertext ×
    plaintext — paper Fig. 2's region 1 ONLY, no key switch.

    The encoded operand pt is batch data ((B, N, qlimbs) mod-q limbs),
    lifted to the region-1 eval domain once and multiplied pointwise
    into both components. np₁ covers 2N·q² (region1_target_bits), the
    same bound `core.heaan.he_mul_plain` uses, and iCRT reconstructs the
    exact integer product — so the served step is bitwise the core
    reference. The absence of region 2 is the op's whole point: affine
    layers of encrypted inference skip the key-switch collectives
    entirely (launch.dryrun lowers this cell to prove it on HLO).
    """
    sf = make_stage_fns(st, mesh, **knobs)
    logq, qlimbs = st.logq, st.qlimbs

    def mask_f(x):
        return bigint.mask_bits(x, logq)

    def step(t1, ax, bx, pt):
        ept = sf.to_eval(pt, t1)
        da = sf.from_eval(sf.mont_mul(sf.to_eval(ax, t1), ept, t1),
                          t1, st.icrt1, qlimbs)
        db = sf.from_eval(sf.mont_mul(sf.to_eval(bx, t1), ept, t1),
                          t1, st.icrt1, qlimbs)
        return sf.out(mask_f(da)), sf.out(mask_f(db))

    return step


def make_add_plain_step(st: HEStatic, mesh, **knobs):
    """Build step(ax, bx, pt) -> (ax, bx') adding an encoded plaintext
    into bx (mask at logq); ax passes through untouched — no NTT, no key
    switch, no collectives (`core.heaan.he_add_plain` batched)."""
    sf = make_stage_fns(st, mesh, **knobs)
    logq = st.logq

    def step(ax, bx, pt):
        return (sf.out(ax),
                sf.out(bigint.mask_bits(bigint.add(bx, pt), logq)))

    return step


@dataclasses.dataclass
class Inflight:
    """A dispatched-but-not-awaited engine step (double-buffer handle).

    ax/bx are the step's async output arrays; the host is free to
    assemble and `device_put` the next batch while the device works.
    """

    batch: Batch
    ax: jnp.ndarray
    bx: jnp.ndarray
    t0: float
    wall: Optional[float] = None     # dispatch→ready, once blocked on


class OpEngine:
    """Compile-once executor for assembled batches.

    Steps are cached by batch bucket key; tables come from the level-aware
    TableCache, so a new level costs one trace + slice views, never a
    table rebuild. `dispatch` places operands on the mesh's data axis and
    launches the step asynchronously; `block` waits for it and returns
    the measured device wall time; `wait` blocks (once) and re-wraps the
    valid rows as Ciphertexts with the op's output level metadata. `run`
    = wait(dispatch(batch)).

    registry: the `repro.obs.MetricsRegistry` that holds the engine's
        ``engine.h2d_bytes`` counter (bytes handed to `device_put`);
        without one the counter is private to the engine.
    """

    def __init__(self, params: HEParams, mesh, cache: TableCache, *,
                 use_kernels: bool = False, crt_strategy: str = "acc3",
                 icrt_strategy: str = "gemm8",
                 modified_shoup: bool = False, tracer=None, registry=None):
        self.params = params
        self.mesh = mesh
        self.cache = cache
        self.tracer = tracer
        self.h2d_bytes = registry.counter("engine.h2d_bytes") \
            if registry is not None else Counter()
        self._knobs = dict(use_kernels=use_kernels,
                           crt_strategy=crt_strategy,
                           icrt_strategy=icrt_strategy,
                           modified_shoup=modified_shoup)
        self._steps: Dict[Tuple, Callable] = {}
        self._static: Dict[int, HEStatic] = {}
        self._warmed: set = set()
        self.compile_s = 0.0
        # warm (trace + compile + first run) seconds per bucket key
        self.compile_s_by_key: Dict[Tuple, float] = {}

    def _st(self, logq: int) -> HEStatic:
        if logq not in self._static:
            self._static[logq] = he_static(self.params, logq)
        return self._static[logq]

    def _step_for(self, key: Tuple) -> Callable:
        """step caches compile once per (op, logq, extra); returns a
        runner(arrays) -> (ax, bx) closing over the right tables."""
        if key in self._steps:
            return self._steps[key]
        op, logq, extra = key
        st = self._st(logq)
        t1, t2 = self.cache.level_tables(logq)
        if op == "mul":
            step = jax.jit(make_he_mul_step(st, self.mesh, **self._knobs))
            ek = self.cache.evk()

            def runner(a):
                return step(t1, t2, ek, a["ax1"], a["bx1"],
                            a["ax2"], a["bx2"])
        elif op == "rotate":
            k = rotation_k(self.params, extra)
            step = jax.jit(
                make_he_rotate_step(st, self.mesh, k, **self._knobs))
            rk = self.cache.rot_key(extra)

            def runner(a):
                return step(t2, rk, a["ax1"], a["bx1"])
        elif op == "conjugate":
            step = jax.jit(make_he_rotate_step(
                st, self.mesh, conjugation_k(self.params),
                **self._knobs))
            ck = self.cache.conj_key()

            def runner(a):
                return step(t2, ck, a["ax1"], a["bx1"])
        elif op == "slot_sum":
            step = jax.jit(
                make_slot_sum_step(st, self.mesh, extra, **self._knobs))
            rks = tuple(self.cache.rot_key(r)
                        for r in slot_sum_rotations(extra))

            def runner(a):
                return step(t2, rks, a["ax1"], a["bx1"])
        elif op == "rescale":
            step = jax.jit(
                make_rescale_step(st, self.mesh, extra, **self._knobs))

            def runner(a):
                return step(a["ax1"], a["bx1"])
        elif op == "mod_down":
            step = jax.jit(
                make_mod_down_step(st, self.mesh, extra, **self._knobs))

            def runner(a):
                return step(a["ax1"], a["bx1"])
        elif op == "mod_raise":
            step = jax.jit(
                make_mod_raise_step(st, self.mesh, extra, **self._knobs))

            def runner(a):
                return step(a["ax1"], a["bx1"])
        elif op in ("add", "sub"):
            step = jax.jit(
                make_addsub_step(st, self.mesh, op, **self._knobs))

            def runner(a):
                return step(a["ax1"], a["bx1"], a["ax2"], a["bx2"])
        elif op == "mul_plain":
            step = jax.jit(
                make_mul_plain_step(st, self.mesh, **self._knobs))

            def runner(a):
                return step(t1, a["ax1"], a["bx1"], a["pt"])
        elif op == "add_plain":
            step = jax.jit(
                make_add_plain_step(st, self.mesh, **self._knobs))

            def runner(a):
                return step(a["ax1"], a["bx1"], a["pt"])
        else:
            raise ValueError(f"unknown op {op!r}")
        self._steps[key] = runner
        return runner

    @property
    def n_compiled(self) -> int:
        return len(self._steps)

    def _place(self, batch: Batch) -> Dict[str, jnp.ndarray]:
        sh = he_limb_sharding(self.mesh, batch=batch.size)
        nbytes = sum(v.nbytes for v in batch.arrays.values())
        self.h2d_bytes.inc(nbytes)
        # H2D span: device_put is async, so this measures enqueue — on
        # the overlap path that is exactly the host-side transfer work
        # hidden behind the in-flight batch.
        with span(self.tracer, "h2d", cat="engine", lane="engine",
                  args=None if self.tracer is None else
                  {"op": batch.op, "batch": batch.size, "bytes": nbytes}):
            return {k: jax.device_put(v, sh)
                    for k, v in batch.arrays.items()}

    def warm_batch(self, batch: Batch) -> None:
        """Trace + compile + one throwaway run for the batch's signature
        (no-op once warm); the elapsed time lands in `compile_s` so
        callers can time steady state cleanly.

        Deliberate trade-off: the first batch of a signature executes
        twice (once here, once timed in `run`) — one extra batch per
        (op, level) over the server's lifetime, amortized to nothing in
        steady-state serving. Reusing the warm outputs instead would
        record a ~0s wall for that batch and inflate reported
        throughput; AOT lower().compile() would avoid the re-run but must
        then reproduce the committed input shardings exactly.
        """
        if batch.key in self._warmed:
            return
        runner = self._step_for(batch.key)
        t0 = time.perf_counter()
        with span(self.tracer, "warm_compile", cat="engine", lane="engine",
                  args=None if self.tracer is None else
                  {"op": batch.op, "logq": batch.logq}):
            jax.block_until_ready(runner(self._place(batch)))
        elapsed = time.perf_counter() - t0
        self.compile_s += elapsed
        self.compile_s_by_key[batch.key] = elapsed
        self._warmed.add(batch.key)

    # ---- async execution (double buffering) ------------------------------

    def dispatch(self, batch: Batch) -> Inflight:
        """Place + launch one batch WITHOUT blocking on the result.

        A cold (op, level) signature is warmed first (`warm_batch`), so
        steady-state metrics never include compilation. The returned
        handle's arrays are async — the caller overlaps the next batch's
        assembly and `device_put` against this step, then `wait`s.
        """
        self.warm_batch(batch)
        runner = self._step_for(batch.key)
        arrays = self._place(batch)
        t0 = time.perf_counter()
        with span(self.tracer, "launch", cat="engine", lane="engine"):
            ax, bx = runner(arrays)
        return Inflight(batch=batch, ax=ax, bx=bx, t0=t0)

    def block(self, inflight: Inflight) -> float:
        """Block on a dispatched batch (once: later calls return the
        same reading); returns the dispatch→ready wall time AS OBSERVED
        BY THE HOST. On the synchronous run() path that is the device
        wall; on the overlapped path it additionally includes any host
        time between dispatch and this call (an upper bound on device
        time — HEServer.poll retires an idle in-flight batch eagerly, so
        the slack is bounded by the caller's poll cadence). Per-op
        ops_per_s under overlap is therefore host-observed; use drain
        wall clocks (benchmarks/serve_he.py "overlap") to quantify the
        overlap win."""
        if inflight.wall is not None:
            return inflight.wall
        with span(self.tracer, "wait", cat="engine", lane="engine"):
            jax.block_until_ready((inflight.ax, inflight.bx))
        wall = inflight.wall = time.perf_counter() - inflight.t0
        if self.tracer is not None:
            b = inflight.batch
            self.tracer.event(
                "device_wall", cat="lifecycle", lane="engine",
                ts=inflight.t0, dur=wall,
                args={"op": b.op, "logq": b.logq, "batch": b.size,
                      "n_valid": b.n_valid})
        return wall

    def wait(self, inflight: Inflight
             ) -> Tuple[List[Ciphertext], float]:
        """`block`, then the n_valid outputs in request order (padded
        lanes computed and discarded): (outputs, wall_s)."""
        wall = self.block(inflight)
        return self._wrap(inflight.batch, inflight.ax, inflight.bx), wall

    def run(self, batch: Batch) -> List[Ciphertext]:
        """Synchronous dispatch→wait (kept for callers that don't
        pipeline); returns the n_valid outputs in request order."""
        outs, _ = self.wait(self.dispatch(batch))
        return outs

    def _wrap(self, batch: Batch, ax, bx) -> List[Ciphertext]:
        """Re-wrap step outputs as Ciphertexts with each op's output
        level metadata (the server-side level tracking contract):

          mul          logq,          logp₁ + logp₂
          mul_plain    logq,          logp + pt_logp
          add/sub/add_plain           logq, logp (equality checked at
                                      submit)
          rotate/conjugate/slot_sum   unchanged
          rescale      logq − dlogp,  logp − dlogp
          mod_down     logq2,         logp
          mod_raise    logq2,         logp
        """
        op = batch.op
        out = []
        for i, req in enumerate(batch.requests):
            c0 = req.cts[0]
            logq, logp = batch.logq, c0.logp
            if op == "mul":
                logp = c0.logp + req.cts[1].logp
            elif op == "mul_plain":
                logp = c0.logp + req.pt_logp
            elif op == "rescale":
                logq -= req.dlogp
                logp -= req.dlogp
            elif op in ("mod_down", "mod_raise"):
                logq = req.logq2
            out.append(Ciphertext(ax=ax[i], bx=bx[i], logq=logq,
                                  logp=logp, n_slots=c0.n_slots))
        return out
