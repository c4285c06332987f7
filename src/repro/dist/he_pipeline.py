"""The paper's Fig. 2 two-region HE Mul as one jit-able, mesh-sharded step.

This is `core.heaan.he_mul` restructured for a device mesh:

  - a BATCH of ciphertext pairs (the unit a privacy-preserving serving
    system schedules) rides the "data" mesh axis;
  - the np CRT primes ride the "model" axis — the paper's §V-A pinning of
    primes to threads (and HEAX's per-modulus hardware lanes) expressed as
    GSPMD sharding, so CRT/NTT/pointwise/iNTT stages are embarrassingly
    parallel and only iCRT's cross-prime accumulation communicates;
  - every table is passed as a pytree argument (not baked as constants),
    so the whole step traces ONCE and re-runs for any batch with the same
    static shape.

Bitwise contract: the step reuses the exact `core` stage functions (crt,
ntt, mont pointwise, intt, icrt, BigInt combine) in the same order as
`core.heaan.he_mul`, and sharding is expressed only through placement
constraints — integer limb arithmetic partitions exactly, iCRT's f32
piece sums stay below 2^24, and its quotient estimate is followed by
exact ±1 corrections — so the sharded output equals the single-device
reference bit for bit (tests/test_dist.py).

The batched stage wrappers are factored into a :class:`StageFns` bundle
(``make_stage_fns``) plus a region-2 key-switch factory
(``make_keyswitch_step``) so `repro.hserve.engine` can lift Galois
rotations, conjugations, and slot-sum reductions onto the same table
pytrees — every ciphertext op that key-switches shares Fig. 2's region 2
verbatim — and every stage can route through the repro.kernels Pallas
paths (``use_kernels``; the kernels are exact integer drop-ins, so the
bitwise contract holds on either path).

Table pytree note: ``quot_fix`` (in REGION_TABLE_KEYS since the Pallas
routing landed) is ⌊β²/p_j⌋ as two β-bit limbs per prime — the
fixed-point reciprocal the served "gemm8" iCRT and the TPU iCRT kernel
use for their quotient estimate in place of the f64 multiply of the
other strategies (TPUs have no f64). It is built by
``build_icrt_tables`` but depends only on the prime, so
`repro.hserve.tables.TableCache` row-slices it from one resident copy
like the prime-pool tables, not per-np like the other iCRT entries. See ``IcrtTables.quot_fix`` in `core/context.py` and
`kernels/icrt/icrt.py`.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import bigint
from repro.core.cipher import EvalKey
from repro.core.context import (
    HEContext, IcrtTables, build_icrt_tables,
)
from repro.core.crt import crt, icrt
from repro.core.ntt import intt, ntt, pointwise_shoup_scale
from repro.core.params import HEParams
from repro.core.wordops import modadd, modsub, mont_modmul
from repro.dist.sharding import data_axes, he_eval_sharding

__all__ = [
    "HEStatic", "he_static", "region_tables", "evk_tables",
    "runtime_tables", "he_table_specs", "he_input_specs",
    "StageFns", "make_stage_fns", "make_keyswitch_step",
    "make_he_mul_step",
]

# Keys of a region-table pytree, in the order region_tables emits them.
REGION_TABLE_KEYS = (
    "primes", "psi_rev", "psi_rev_shoup", "ipsi_rev", "ipsi_rev_shoup",
    "n_inv", "n_inv_shoup", "pprime", "r2", "crt_tb", "crt_tb_shoup",
    "inv_P", "inv_P_shoup", "pdivp", "P_limbs", "P_half_limbs", "p_inv_f64",
    "quot_fix",
)

EVK_TABLE_KEYS = ("ax_ev", "ax_ev_shoup", "bx_ev", "bx_ev_shoup")


@dataclasses.dataclass(frozen=True)
class HEStatic:
    """Everything shape-static about one HE-Mul level: prime counts, limb
    widths, and the iCRT accumulator tables' static metadata. Cheap to
    build (no NTT twiddles) — dry-run lowering needs only this."""

    params: HEParams
    logq: int
    qlimbs: int
    np1: int
    np2: int
    np2_max: int          # rows of the stored evk (region 2 at logQ)
    ks_limbs: int         # key-switch product width before ÷Q
    icrt1: IcrtTables
    icrt2: IcrtTables

    @property
    def N(self) -> int:
        return self.params.N

    @property
    def dtype(self):
        return np.uint32 if self.params.beta_bits == 32 else np.uint64


def he_static(params: HEParams, logq: int) -> HEStatic:
    """Static shape/table metadata for an HE Mul at modulus 2^logq."""
    np1 = params.np_region1(logq)
    np2 = params.np_region2(logq)
    return HEStatic(
        params=params,
        logq=logq,
        qlimbs=params.qlimbs(logq),
        np1=np1,
        np2=np2,
        np2_max=params.np_region2(params.logQ),
        ks_limbs=params.limbs_for_bits(logq + params.logQ) + 1,
        icrt1=build_icrt_tables(params, np1),
        icrt2=build_icrt_tables(params, np2),
    )


# --------------------------------------------------------------------------
# table pytrees
# --------------------------------------------------------------------------

def region_tables(ctx: HEContext, region: int) -> Dict[str, np.ndarray]:
    """All tables one region's CRT→NTT→iNTT→iCRT chain consumes, as a flat
    dict of host arrays (callers jnp.asarray / device_put them; the step
    takes them as arguments so nothing is baked into the jaxpr)."""
    assert region in (1, 2)
    g = ctx.tables
    npn = ctx.np1 if region == 1 else ctx.np2
    tabs = ctx.icrt1 if region == 1 else ctx.icrt2
    K = ctx.qlimbs
    return {
        "primes": g.primes[:npn],
        "psi_rev": g.psi_rev[:npn],
        "psi_rev_shoup": g.psi_rev_shoup[:npn],
        "ipsi_rev": g.ipsi_rev[:npn],
        "ipsi_rev_shoup": g.ipsi_rev_shoup[:npn],
        "n_inv": g.n_inv[:npn],
        "n_inv_shoup": g.n_inv_shoup[:npn],
        "pprime": g.pprime[:npn],
        "r2": g.r2[:npn],
        "crt_tb": g.crt_tb[:npn, :K],
        "crt_tb_shoup": g.crt_tb_shoup[:npn, :K],
        "inv_P": tabs.inv_P,
        "inv_P_shoup": tabs.inv_P_shoup,
        "pdivp": tabs.pdivp,
        "P_limbs": tabs.P_limbs,
        "P_half_limbs": tabs.P_half_limbs,
        "p_inv_f64": g.p_inv_f64[:npn],
        # ⌊β²/p_j⌋, the fixed-point quotient reciprocal of "gemm8" and the
        # TPU kernel (the no-f64 stand-in for p_inv_f64); per-prime, not
        # per-P — see the module docstring
        "quot_fix": tabs.quot_fix,
    }


def evk_tables(evk: EvalKey) -> Dict[str, jnp.ndarray]:
    """The evaluation key as a flat pytree (already eval-domain + Shoup;
    the step slices rows [:np2] for the current level)."""
    return {
        "ax_ev": evk.ax_ev,
        "ax_ev_shoup": evk.ax_ev_shoup,
        "bx_ev": evk.bx_ev,
        "bx_ev_shoup": evk.bx_ev_shoup,
    }


def runtime_tables(ctx: HEContext, evk: EvalKey) -> Tuple[Dict, Dict, Dict]:
    """Device-ready (t1, t2, ek) pytrees for running the step (the runtime
    counterpart of he_table_specs; tables replicate across the mesh)."""
    t1 = {k: jnp.asarray(v) for k, v in region_tables(ctx, 1).items()}
    t2 = {k: jnp.asarray(v) for k, v in region_tables(ctx, 2).items()}
    ek = {k: jnp.asarray(v) for k, v in evk_tables(evk).items()}
    return t1, t2, ek


def _region_spec(st: HEStatic, npn: int, tabs: IcrtTables) -> Dict:
    dt = st.dtype
    N = st.N
    sds = jax.ShapeDtypeStruct
    return {
        "primes": sds((npn,), dt),
        "psi_rev": sds((npn, N), dt),
        "psi_rev_shoup": sds((npn, N), dt),
        "ipsi_rev": sds((npn, N), dt),
        "ipsi_rev_shoup": sds((npn, N), dt),
        "n_inv": sds((npn,), dt),
        "n_inv_shoup": sds((npn,), dt),
        "pprime": sds((npn,), dt),
        "r2": sds((npn,), dt),
        "crt_tb": sds((npn, st.qlimbs), dt),
        "crt_tb_shoup": sds((npn, st.qlimbs), dt),
        "inv_P": sds((npn,), dt),
        "inv_P_shoup": sds((npn,), dt),
        "pdivp": sds((npn, tabs.plimbs), dt),
        "P_limbs": sds((tabs.accum_limbs,), dt),
        "P_half_limbs": sds((tabs.accum_limbs,), dt),
        "p_inv_f64": sds((npn,), np.float64),
        "quot_fix": sds((npn, 2), dt),
    }


def he_table_specs(st: HEStatic) -> Tuple[Dict, Dict, Dict]:
    """Abstract (t1, t2, ek) pytrees for lowering without building the
    multi-second NTT twiddle tables (the dry-run path)."""
    t1 = _region_spec(st, st.np1, st.icrt1)
    t2 = _region_spec(st, st.np2, st.icrt2)
    sds = jax.ShapeDtypeStruct
    ek = {k: sds((st.np2_max, st.N), st.dtype) for k in EVK_TABLE_KEYS}
    return t1, t2, ek


def he_input_specs(st: HEStatic, batch: int) -> Tuple:
    """Abstract (ax1, bx1, ax2, bx2) ciphertext-batch operands."""
    sds = jax.ShapeDtypeStruct((batch, st.N, st.qlimbs), st.dtype)
    return (sds, sds, sds, sds)


# --------------------------------------------------------------------------
# batched stage wrappers (value-identical to the per-item core stages)
# --------------------------------------------------------------------------
#
# Pallas routing folds the batch into whichever axis the kernel treats as
# independent rows: CRT/iCRT/pointwise are per-coefficient (batch folds
# into N), NTT/iNTT butterflies mix across N but rows are per-prime (batch
# tiles the row axis, twiddles riding along). All kernels are exact
# integer drop-ins (tests/test_kernels.py), so either path is bitwise
# identical to the core stages.

def _fold_np(x: jnp.ndarray) -> jnp.ndarray:
    """(B, np, N) -> (np, B·N): concatenate the batch into the coefficient
    axis (legal wherever the op is per-coefficient)."""
    B, npn, N = x.shape
    return jnp.moveaxis(x, 1, 0).reshape(npn, B * N)


def _unfold_np(x: jnp.ndarray, B: int) -> jnp.ndarray:
    npn = x.shape[0]
    return jnp.moveaxis(x.reshape(npn, B, -1), 0, 1)


def _crt_b(x: jnp.ndarray, t: Dict, strategy: str,
           use_kernels: bool = False) -> jnp.ndarray:
    """(B, N, K) limbs -> (B, np, N) residues. CRT rows are independent
    per coefficient, so batching folds into the row dimension exactly."""
    B, N, K = x.shape
    if use_kernels:
        from repro.kernels.crt.ops import crt_op
        kstrat = strategy if strategy in ("acc3", "mod2", "mod4") else "acc3"
        res = crt_op(x.reshape(B * N, K), t["crt_tb"], t["crt_tb_shoup"],
                     t["primes"], strategy=kstrat)
    else:
        res = crt(x.reshape(B * N, K), t["crt_tb"], t["crt_tb_shoup"],
                  t["primes"], strategy=strategy)
    return jnp.moveaxis(res.reshape(res.shape[0], B, N), 0, 1)


def _ntt_b(r: jnp.ndarray, t: Dict, modified: bool,
           use_kernels: bool = False) -> jnp.ndarray:
    if use_kernels:
        from repro.kernels.ntt.ops import ntt_op
        B, npn, N = r.shape
        return ntt_op(r.reshape(B * npn, N),
                      jnp.tile(t["psi_rev"], (B, 1)),
                      jnp.tile(t["psi_rev_shoup"], (B, 1)),
                      jnp.tile(t["primes"], B),
                      modified=modified).reshape(B, npn, N)
    return jax.vmap(lambda rr: ntt(
        rr, t["psi_rev"], t["psi_rev_shoup"], t["primes"],
        modified=modified))(r)


def _intt_b(r: jnp.ndarray, t: Dict, modified: bool,
            use_kernels: bool = False) -> jnp.ndarray:
    if use_kernels:
        from repro.kernels.ntt.ops import intt_op
        B, npn, N = r.shape
        return intt_op(r.reshape(B * npn, N),
                       jnp.tile(t["ipsi_rev"], (B, 1)),
                       jnp.tile(t["ipsi_rev_shoup"], (B, 1)),
                       jnp.tile(t["n_inv"], B),
                       jnp.tile(t["n_inv_shoup"], B),
                       jnp.tile(t["primes"], B),
                       modified=modified).reshape(B, npn, N)
    return jax.vmap(lambda rr: intt(
        rr, t["ipsi_rev"], t["ipsi_rev_shoup"], t["n_inv"],
        t["n_inv_shoup"], t["primes"], modified=modified))(r)


def _icrt_b(r: jnp.ndarray, t: Dict, tabs: IcrtTables, out_limbs: int,
            strategy: str, use_kernels: bool = False) -> jnp.ndarray:
    if use_kernels:
        from repro.core.crt import finalize_accum
        from repro.kernels.icrt.icrt import icrt_accum_pallas
        B = r.shape[0]
        accum, s = icrt_accum_pallas(
            _fold_np(r), t["inv_P"], t["inv_P_shoup"], t["pdivp"],
            t["quot_fix"], t["primes"], accum_limbs=tabs.accum_limbs)
        out = finalize_accum(accum, s, t["P_limbs"], t["P_half_limbs"],
                             out_limbs)
        return out.reshape(B, -1, out_limbs)
    return jax.vmap(lambda rr: icrt(
        rr, tabs, t["primes"], t["inv_P"], t["inv_P_shoup"], t["pdivp"],
        t["P_limbs"], t["P_half_limbs"], t["p_inv_f64"],
        out_limbs=out_limbs, strategy=strategy, quot_fix=t["quot_fix"]))(r)


def _mont_mul_b(a: jnp.ndarray, b: jnp.ndarray, t: Dict,
                use_kernels: bool = False) -> jnp.ndarray:
    if use_kernels:
        from repro.kernels.modmul.ops import pointwise_mont_op
        B = a.shape[0]
        return _unfold_np(pointwise_mont_op(
            _fold_np(a), _fold_np(b), t["primes"], t["pprime"], t["r2"]), B)
    return mont_modmul(a, b, t["primes"][:, None], t["pprime"][:, None],
                       t["r2"][:, None])


# --------------------------------------------------------------------------
# stage bundles and the steps built from them
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class StageFns:
    """Mesh-constrained batched stage bundle for one parameter level.

    `to_eval`/`from_eval` are the paper's CRT→NTT and iNTT→iCRT chains
    over (B, ·, ·) batches with placement constraints applied between
    stages; `mont_mul` is the region-1 pointwise product. Shared by
    make_he_mul_step and the repro.hserve rotate/slot-sum engine.
    """

    to_eval: Callable[[jnp.ndarray, Dict], jnp.ndarray]
    from_eval: Callable[[jnp.ndarray, Dict, IcrtTables, int], jnp.ndarray]
    mont_mul: Callable[[jnp.ndarray, jnp.ndarray, Dict], jnp.ndarray]
    shoup_mul: Callable[..., jnp.ndarray]          # region-2 key product
    ev: Callable[[jnp.ndarray], jnp.ndarray]       # eval-domain placement
    out: Callable[[jnp.ndarray], jnp.ndarray]      # output placement
    modified_shoup: bool


def make_stage_fns(st: HEStatic, mesh: Mesh, *,
                   crt_strategy: str = "acc3",
                   icrt_strategy: str = "gemm8",
                   modified_shoup: bool = False,
                   reduce_scatter_icrt: bool = False,
                   use_kernels: bool = False) -> StageFns:
    """Bind strategy knobs + mesh placements into a reusable stage bundle.

    `use_kernels` routes CRT/NTT/iNTT/iCRT/pointwise through the
    repro.kernels Pallas paths (β = 2^32 only; interpret mode off-TPU).

    Every stage call runs under a `jax.named_scope` of the paper's
    Fig. 3 taxonomy — ``he.crt``, ``he.ntt``, ``he.intt``, ``he.modmul``
    (Montgomery and Shoup pointwise) and ``he.icrt`` — so the device ops
    of the fused step carry their stage in their `op_name` metadata and
    a profiler trace splits the step's device time by stage. Scopes are
    metadata only: the compiled program and its results are unchanged.
    """
    if use_kernels:
        assert st.params.beta_bits == 32, \
            "Pallas kernels are β=2^32 (TPU-native)"
    batch_axes = data_axes(mesh)
    b_ax = batch_axes if batch_axes else None
    ev_sh = he_eval_sharding(mesh)
    model = "model" if "model" in mesh.axis_names else None
    limb_sh = NamedSharding(
        mesh, P(b_ax, None, model if reduce_scatter_icrt else None))
    out_sh = NamedSharding(mesh, P(b_ax))

    def ev(x):
        return jax.lax.with_sharding_constraint(x, ev_sh)

    def limbs(x):
        return jax.lax.with_sharding_constraint(x, limb_sh)

    def out(x):
        return jax.lax.with_sharding_constraint(x, out_sh)

    def to_eval(x, t):
        with jax.named_scope("he.crt"):
            r = _crt_b(x, t, crt_strategy, use_kernels)
        with jax.named_scope("he.ntt"):
            return ev(_ntt_b(ev(r), t, modified_shoup, use_kernels))

    def from_eval(e, t, tabs, out_limbs):
        with jax.named_scope("he.intt"):
            res = _intt_b(e, t, modified_shoup, use_kernels)
        with jax.named_scope("he.icrt"):
            return limbs(_icrt_b(ev(res), t, tabs, out_limbs,
                                 icrt_strategy, use_kernels))

    def mont_mul(a, b, t):
        with jax.named_scope("he.modmul"):
            return _mont_mul_b(a, b, t, use_kernels)

    def shoup_mul(e, w, w_shoup, primes):
        with jax.named_scope("he.modmul"):
            return pointwise_shoup_scale(e, w, w_shoup, primes,
                                         modified=modified_shoup)

    return StageFns(to_eval=to_eval, from_eval=from_eval,
                    mont_mul=mont_mul, shoup_mul=shoup_mul, ev=ev, out=out,
                    modified_shoup=modified_shoup)


def make_keyswitch_step(st: HEStatic, sf: StageFns):
    """Region-2 key switch: ks(t2, ek, d) -> (ks_ax, ks_bx) at qlimbs.

    The shared tail of HE Mul (d = d2) and every Galois operation
    (d = σ_k(ax)) — paper Fig. 2's region 2: CRT→NTT at np₂ primes,
    two Shoup pointwise products against the (rotation/evaluation) key,
    iNTT→iCRT at ks_limbs, then the ÷Q rounding shift.
    """
    np2, ks_limbs = st.np2, st.ks_limbs
    logQ, qlimbs = st.params.logQ, st.qlimbs

    def shift_f(x):
        return bigint.shift_right_round(x, logQ, out_limbs=qlimbs)

    def ks(t2, ek, d):
        with jax.named_scope("he.region2"):
            e2 = sf.to_eval(d, t2)
            p2 = t2["primes"]
            ks_ax = sf.from_eval(
                sf.shoup_mul(e2, ek["ax_ev"][:np2],
                             ek["ax_ev_shoup"][:np2], p2),
                t2, st.icrt2, ks_limbs)
            ks_bx = sf.from_eval(
                sf.shoup_mul(e2, ek["bx_ev"][:np2],
                             ek["bx_ev_shoup"][:np2], p2),
                t2, st.icrt2, ks_limbs)
            ks_ax = shift_f(ks_ax)
            ks_bx = shift_f(ks_bx)
        return ks_ax, ks_bx

    return ks


def make_he_mul_step(st: HEStatic, mesh: Mesh, *,
                     crt_strategy: str = "acc3",
                     icrt_strategy: str = "gemm8",
                     modified_shoup: bool = False,
                     reduce_scatter_icrt: bool = False,
                     use_kernels: bool = False):
    """Build step(t1, t2, ek, ax1, bx1, ax2, bx2) -> (ax3, bx3).

    Operands are (B, N, qlimbs) limb batches; outputs likewise. Strategy
    knobs select the paper's optimization ladder per stage (benchmarks/
    hillclimb.py sweeps them); `reduce_scatter_icrt` additionally shards
    the post-iCRT limb axis on "model" so the partitioner can lower the
    cross-prime reduction as reduce-scatter instead of all-reduce;
    `use_kernels` routes every stage through the repro.kernels Pallas
    paths (β = 2^32), keeping the bitwise contract.
    """
    logq, qlimbs = st.logq, st.qlimbs
    sf = make_stage_fns(st, mesh, crt_strategy=crt_strategy,
                        icrt_strategy=icrt_strategy,
                        modified_shoup=modified_shoup,
                        reduce_scatter_icrt=reduce_scatter_icrt,
                        use_kernels=use_kernels)
    keyswitch = make_keyswitch_step(st, sf)

    def comb_f(d, ks):
        return bigint.mask_bits(bigint.add(d, ks), logq)

    def step(t1, t2, ek, ax1, bx1, ax2, bx2):
        p1 = t1["primes"][:, None]
        # ---- region 1: 4×(CRT→NTT), 3 pointwise, 3×(iNTT→iCRT) ----------
        with jax.named_scope("he.region1"):
            ea1 = sf.to_eval(ax1, t1)
            eb1 = sf.to_eval(bx1, t1)
            ea2 = sf.to_eval(ax2, t1)
            eb2 = sf.to_eval(bx2, t1)

            d0_ev = sf.mont_mul(eb1, eb2, t1)
            d2_ev = sf.mont_mul(ea1, ea2, t1)
            d1_ev = sf.mont_mul(modadd(ea1, eb1, p1),
                                modadd(ea2, eb2, p1), t1)
            d1_ev = modsub(modsub(d1_ev, d0_ev, p1), d2_ev, p1)

            d0 = sf.from_eval(d0_ev, t1, st.icrt1, qlimbs)
            d1 = sf.from_eval(d1_ev, t1, st.icrt1, qlimbs)
            d2 = bigint.mask_bits(sf.from_eval(d2_ev, t1, st.icrt1, qlimbs),
                                  logq)

        # ---- region 2: key switching against the evk --------------------
        ks_ax, ks_bx = keyswitch(t2, ek, d2)

        # ---- combine ----------------------------------------------------
        ax3 = comb_f(d1, ks_ax)
        bx3 = comb_f(d0, ks_bx)
        return sf.out(ax3), sf.out(bx3)

    return step
