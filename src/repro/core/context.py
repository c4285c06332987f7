"""Precomputed tables for the HE Mul pipeline (paper Table V).

The paper's functions consume precomputed data:
  - CRT:  TB_CRT[j,k] = β^k mod p_j, plus Shoup companions.
  - NTT:  TB_W = powers of the 2N-th root ψ in bit-reversed order (+Shoup).
  - iNTT: inverse-ψ powers (+Shoup) and N⁻¹ mod p.
  - iCRT: (P/p_j)⁻¹ mod p_j (+Shoup), limbs of P/p_j, and P itself.

Tables are built host-side with exact python-int arithmetic, vectorized with
numpy where the word size allows, and cached:

  - :class:`GlobalTables` — everything that depends only on the prime pool
    (built once per parameter set; sliced per level).
  - :class:`IcrtTables` — everything that depends on P = ∏ first-np primes
    (cached per np, shared between regions/levels that use the same np).
  - :class:`HEContext` — a cheap per-(params, logq) view bundling both
    regions' slices.
"""

from __future__ import annotations

import dataclasses
import math
from functools import lru_cache

import numpy as np

from repro.core.params import HEParams
from repro.nt.primes import bit_reverse_indices, primitive_2nth_root
from repro.nt.residue import int_to_limbs


def _np_dtype(beta_bits: int):
    return np.uint32 if beta_bits == 32 else np.uint64


def _pow_table_vec(bases: np.ndarray, primes: np.ndarray, n: int,
                   beta_bits: int) -> np.ndarray:
    """powers[j, k] = bases[j]^k mod primes[j], k in [0, n). Exact."""
    npn = len(primes)
    out = np.empty((npn, n), dtype=object)
    if beta_bits == 32:
        # vectorized: products < 2^60 fit u64
        b = bases.astype(np.uint64)
        p = primes.astype(np.uint64)
        col = np.ones(npn, dtype=np.uint64)
        res = np.empty((npn, n), dtype=np.uint64)
        for k in range(n):
            res[:, k] = col
            col = (col * b) % p
        return res.astype(np.uint32)
    # u64 primes: python-int per prime (exact, one-time)
    res = np.empty((npn, n), dtype=np.uint64)
    for j in range(npn):
        pj = int(primes[j])
        bj = int(bases[j])
        c = 1
        for k in range(n):
            res[j, k] = c
            c = (c * bj) % pj
    return res


def _shoup_vec(vals: np.ndarray, primes: np.ndarray, beta_bits: int
               ) -> np.ndarray:
    """floor(vals·β / p); vals is (np,) or (np, K), primes is (np,). Exact."""
    p_b = primes.reshape(-1, *([1] * (vals.ndim - 1)))
    if beta_bits == 32:
        return ((vals.astype(np.uint64) << np.uint64(32))
                // p_b.astype(np.uint64)).astype(np.uint32)
    out = np.empty_like(vals, dtype=np.uint64)
    flat_v = vals.reshape(-1)
    flat_p = np.broadcast_to(p_b, vals.shape).reshape(-1)
    flat_o = out.reshape(-1)
    for i in range(flat_v.size):
        flat_o[i] = (int(flat_v[i]) << 64) // int(flat_p[i])
    return out


@dataclasses.dataclass(frozen=True)
class GlobalTables:
    """Prime-pool-wide tables; slice rows [:np] for a given level/region."""

    params: HEParams
    primes: np.ndarray            # (np_max,)
    psi_rev: np.ndarray           # (np_max, N)   ψ^brv(k)
    psi_rev_shoup: np.ndarray
    ipsi_rev: np.ndarray          # (np_max, N)   ψ^-brv(k)
    ipsi_rev_shoup: np.ndarray
    n_inv: np.ndarray             # (np_max,)     N⁻¹ mod p
    n_inv_shoup: np.ndarray
    pprime: np.ndarray            # (np_max,)     -p⁻¹ mod β  (Montgomery)
    r2: np.ndarray                # (np_max,)     β² mod p    (Montgomery)
    crt_tb: np.ndarray            # (np_max, max_in_limbs)  β^k mod p
    crt_tb_shoup: np.ndarray
    betak: np.ndarray             # (np_max, 3)   β^k mod p, k<3 (accum fold)
    betak_shoup: np.ndarray
    p_inv_f64: np.ndarray         # (np_max,)     1/p as float64

    @property
    def max_in_limbs(self) -> int:
        return self.crt_tb.shape[1]


@lru_cache(maxsize=8)
def build_global_tables(params: HEParams) -> GlobalTables:
    beta = params.beta_bits
    dt = _np_dtype(beta)
    N = params.N
    np_max = params.max_np
    primes_py = params.primes[:np_max]
    primes = np.array(primes_py, dtype=dt)

    # --- NTT twiddles ------------------------------------------------------
    psis = np.array(
        [primitive_2nth_root(p, N) for p in primes_py], dtype=dt)
    ipsis = np.array(
        [pow(int(w), int(p) - 2, int(p)) for w, p in zip(psis, primes_py)],
        dtype=dt)
    pow_psi = _pow_table_vec(psis, primes, N, beta)      # ψ^k natural order
    pow_ipsi = _pow_table_vec(ipsis, primes, N, beta)
    brv = np.array(bit_reverse_indices(N), dtype=np.int64)
    psi_rev = np.ascontiguousarray(pow_psi[:, brv])
    ipsi_rev = np.ascontiguousarray(pow_ipsi[:, brv])
    n_inv = np.array(
        [pow(N, int(p) - 2, int(p)) for p in primes_py], dtype=dt)

    # --- Montgomery constants ---------------------------------------------
    R = 1 << beta
    pprime = np.array([(-pow(p, -1, R)) % R for p in primes_py], dtype=dt)
    r2 = np.array([(R * R) % p for p in primes_py], dtype=dt)

    # --- CRT table: β^k mod p ---------------------------------------------
    max_in_limbs = params.limbs_for_bits(2 * params.logQ) + 1
    beta_mod = np.array([R % p for p in primes_py], dtype=dt)
    crt_tb = _pow_table_vec(beta_mod, primes, max_in_limbs, beta)
    betak = crt_tb[:, :3].copy()

    return GlobalTables(
        params=params,
        primes=primes,
        psi_rev=psi_rev,
        psi_rev_shoup=_shoup_vec(psi_rev, primes, beta),
        ipsi_rev=ipsi_rev,
        ipsi_rev_shoup=_shoup_vec(ipsi_rev, primes, beta),
        n_inv=n_inv,
        n_inv_shoup=_shoup_vec(n_inv, primes, beta),
        pprime=pprime,
        r2=r2,
        crt_tb=crt_tb,
        crt_tb_shoup=_shoup_vec(crt_tb, primes, beta),
        betak=betak,
        betak_shoup=_shoup_vec(betak, primes, beta),
        p_inv_f64=1.0 / primes.astype(np.float64),
    )


@dataclasses.dataclass(frozen=True)
class IcrtTables:
    """Tables depending on P = ∏_{j<np} p_j (paper Algo 5/6 inputs)."""

    np_count: int
    P_int: int                    # exact P (host-side)
    P_bits: int
    plimbs: int                   # limbs of the largest P/p_j
    accum_limbs: int              # limbs covering np·P (the accumulator)
    inv_P: np.ndarray             # (np,)  (P/p_j)⁻¹ mod p_j
    inv_P_shoup: np.ndarray
    pdivp: np.ndarray             # (np, plimbs)  limbs of P/p_j
    P_limbs: np.ndarray           # (accum_limbs,)
    P_half_limbs: np.ndarray      # (accum_limbs,)  floor(P/2)
    quot_fix: np.ndarray          # (np, 2)  floor(β²/p_j) — the fixed-point
    #                               quotient of "gemm8" and the TPU kernel


@lru_cache(maxsize=None)
def build_icrt_tables(params: HEParams, np_count: int) -> IcrtTables:
    beta = params.beta_bits
    dt = _np_dtype(beta)
    primes_py = params.primes[:np_count]
    P = 1
    for p in primes_py:
        P *= p
    P_bits = P.bit_length()
    plimbs = params.limbs_for_bits((P // min(primes_py)).bit_length())
    # +2 limbs of assembly headroom: the 3-word accumulators are placed at
    # limb offsets 0..2 before the final carry propagation.
    accum_limbs = params.limbs_for_bits(
        P_bits + math.ceil(math.log2(np_count)) + 1) + 2

    inv_P = np.array(
        [pow(P // p, -1, p) for p in primes_py], dtype=dt)
    primes = np.array(primes_py, dtype=dt)
    pdivp = np.zeros((np_count, plimbs), dtype=dt)
    for j, p in enumerate(primes_py):
        pdivp[j] = int_to_limbs(P // p, plimbs, beta)
    quot_fix = np.zeros((np_count, 2), dtype=dt)
    for j, p in enumerate(primes_py):
        quot_fix[j] = int_to_limbs((1 << (2 * beta)) // p, 2, beta)

    return IcrtTables(
        np_count=np_count,
        P_int=P,
        P_bits=P_bits,
        plimbs=plimbs,
        accum_limbs=accum_limbs,
        inv_P=inv_P,
        inv_P_shoup=_shoup_vec(inv_P, primes, beta),
        pdivp=pdivp,
        P_limbs=int_to_limbs(P, accum_limbs, beta),
        P_half_limbs=int_to_limbs(P // 2, accum_limbs, beta),
        quot_fix=quot_fix,
    )


@dataclasses.dataclass(frozen=True)
class HEContext:
    """Per-(params, logq) bundle: region-1 and region-2 table views.

    Region 1 multiplies two log q-bit polys (P₁ > 2N·q²); region 2 multiplies
    a log q-bit poly with the log Q²-bit evk (P₂ > 2N·q·Q²). Paper Fig. 2.
    """

    params: HEParams
    logq: int
    tables: GlobalTables
    np1: int
    np2: int
    icrt1: IcrtTables
    icrt2: IcrtTables

    @property
    def qlimbs(self) -> int:
        return self.params.qlimbs(self.logq)

    @property
    def N(self) -> int:
        return self.params.N


@lru_cache(maxsize=None)
def make_context(params: HEParams, logq: int) -> HEContext:
    tables = build_global_tables(params)
    np1 = params.np_region1(logq)
    np2 = params.np_region2(logq)
    return HEContext(
        params=params,
        logq=logq,
        tables=tables,
        np1=np1,
        np2=np2,
        icrt1=build_icrt_tables(params, np1),
        icrt2=build_icrt_tables(params, np2),
    )
