"""CRT (paper Algo 1) and iCRT (Algo 5 → reordered Algo 6).

CRT strategies (paper Table VIII ladder, all selectable):
  - "shoup"  : per-term Shoup modmul, modulo every iteration (≈ GPU-Mod1,
               but division-free).
  - "mod2"/"mod4" : raw wide products accumulated, hardware remainder every
               2/4 iterations (GPU-Mod2/GPU-Mod4; β=2³² only — the wide
               accumulator is u64).
  - "acc3"   : three-word accumulation with synthesized ADC, single fold at
               the end through Shoup multiplies by β^k mod p (GPU-C; the
               paper's CPU path does the same with accum spanning ≤3 limbs).
  - "matmul" : the whole stage-1 sum as two integer matrix-matrix multiplies
               on 16-bit input halves (β=2³² only). This is the loop-
               reordering insight of §V-A applied to CRT itself — XLA gets a
               dense integer GEMM instead of a scan. Beyond-paper.

iCRT strategies:
  - "naive"  : Algo 5 — scalar×BigInt accumulation per coefficient
               (N-degree parallelism only). Kept as the measurable baseline.
  - "acc3"   : Algo 6 loop-reordered with 3-word accumulators.
  - "sum16"  : Algo 6 loop-reordered as four exact word-sized sums over the
               prime axis (the half-word pieces of each product). Limb
               dtype only, so it compiles for every backend, and a
               model-sharded prime axis lowers to all-reduces.
  - "matmul" : Algo 6 realized as integer GEMMs on 16-bit table halves
               (β=2³² only) — N·PLimbs parallelism handed to the MXU/BLAS.
  - "gemm8"  : Algo 6 and the quotient as one exact bf16 GEMM on 8-bit
               pieces with f32 accumulation (β=2³² only; u64 words fall
               back to "sum16"). The MXU does the sums over primes, and the
               quotient comes from the fixed-point ⌊β²/p_j⌋ table, so no
               f64 op is left. The served default.

The "matmul" strategies run their GEMMs in u64, which the TPU compiler
refuses; they stay for the Table VII–IX ladders. The served defaults are
"acc3" CRT and "gemm8" iCRT; "sum16" is kept as its bitwise oracle.

All paths are exact; tests cross-check every strategy against python-int
oracles and against each other.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core import bigint
from repro.core.context import IcrtTables
from repro.core.wordops import (
    acc3_add_product, cond_reduce, modadd, mul_wide, shoup_modmul,
)

__all__ = ["crt", "icrt", "finalize_accum"]


# --------------------------------------------------------------------------
# CRT: (N, K) BigInt limbs -> (np, N) residues
# --------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("strategy",))
def crt(x: jnp.ndarray, tb: jnp.ndarray, tb_shoup: jnp.ndarray,
        primes: jnp.ndarray, *, strategy: str = "acc3") -> jnp.ndarray:
    """mod(Σ_k x[n,k]·β^k, p_j) for every coefficient n and prime j.

    x: (N, K) limbs; tb/tb_shoup: (np, K) = β^k mod p_j; primes: (np,).
    Returns (np, N).
    """
    if x.dtype == jnp.uint64 and strategy in ("matmul", "mod2", "mod4"):
        strategy = "acc3"   # wide accumulators unavailable at β=2^64
    npn, K = tb.shape
    N = x.shape[0]
    assert x.shape[1] == K

    if strategy == "matmul":
        mask16 = jnp.uint64(0xFFFF)
        xl = (x.astype(jnp.uint64) & mask16)
        xh = (x.astype(jnp.uint64) >> jnp.uint64(16))
        tbT = tb.astype(jnp.uint64).T                      # (K, np)
        s_lo = xl @ tbT                                    # < K·2^46 exact
        s_hi = xh @ tbT
        p64 = primes.astype(jnp.uint64)[None, :]
        v = (s_lo + ((s_hi % p64) << jnp.uint64(16))) % p64
        return v.astype(x.dtype).T

    if strategy == "shoup":
        def step(acc, k):
            xk = jax.lax.dynamic_index_in_dim(x, k, 1, keepdims=False)
            term = shoup_modmul(xk[None, :], tb[:, k, None],
                                tb_shoup[:, k, None], primes[:, None])
            return modadd(acc, term, primes[:, None]), None
        acc0 = jnp.zeros((npn, N), x.dtype)
        acc, _ = jax.lax.scan(step, acc0, jnp.arange(K))
        return acc

    if strategy in ("mod2", "mod4"):
        every = int(strategy[3:])
        p64 = primes.astype(jnp.uint64)[:, None]
        acc = jnp.zeros((npn, N), jnp.uint64)
        for k in range(K):                      # K ≤ ~76: unrolled in trace
            prod = tb.astype(jnp.uint64)[:, k, None] * \
                x.astype(jnp.uint64)[None, :, k]
            acc = acc + prod
            if (k + 1) % every == 0:
                acc = acc % p64
        return (acc % p64).astype(x.dtype)

    if strategy == "acc3":
        zeros = jnp.zeros((npn, N), x.dtype)

        def step(carry, k):
            a2, a1, a0 = carry
            xk = jax.lax.dynamic_index_in_dim(x, k, 1, keepdims=False)
            a2, a1, a0 = acc3_add_product(
                a2, a1, a0, jnp.broadcast_to(xk[None, :], (npn, N)),
                jnp.broadcast_to(tb[:, k, None], (npn, N)))
            return (a2, a1, a0), None

        (a2, a1, a0), _ = jax.lax.scan(
            step, (zeros, zeros, zeros), jnp.arange(K))
        return _fold3(a0, a1, a2, tb, tb_shoup, primes)

    raise ValueError(f"unknown CRT strategy {strategy!r}")


def _fold3(a0, a1, a2, tb, tb_shoup, primes):
    """Reduce a 3-word accumulator via Shoup multiplies by β^k mod p.

    This is the paper's 'Shoup's ModMul on accum spanning up to 3 limbs,
    using precomputed Y_shoup on Y = {1, β, β²}' (§IV).
    """
    p = primes[:, None]
    # Y = 1 (= β^0 mod p): Shoup reduces an arbitrary word mod p in one shot.
    r0 = shoup_modmul(a0, tb[:, 0, None], tb_shoup[:, 0, None], p)
    r1 = shoup_modmul(a1, tb[:, 1, None], tb_shoup[:, 1, None], p)
    r2 = shoup_modmul(a2, tb[:, 2, None], tb_shoup[:, 2, None], p)
    return cond_reduce(r0 + r1 + r2, p, 4)


# --------------------------------------------------------------------------
# iCRT: (np, N) residues -> (N, out_limbs) two's-complement centered BigInt
# --------------------------------------------------------------------------

def icrt(r: jnp.ndarray, tabs: IcrtTables, primes: jnp.ndarray,
         inv_P: jnp.ndarray, inv_P_shoup: jnp.ndarray,
         pdivp: jnp.ndarray, P_limbs: jnp.ndarray, P_half: jnp.ndarray,
         p_inv_f64: jnp.ndarray, out_limbs: int,
         *, strategy: str = "gemm8",
         quot_fix: jnp.ndarray | None = None) -> jnp.ndarray:
    """Reconstruct centered BigInts from RNS residues (paper Algo 5/6).

    r: (np, N). Returns (N, out_limbs) two's-complement (low limbs of the
    centered value — callers mask to mod-q or shift for key-switching).
    `quot_fix` (np, 2) is ⌊β²/p_j⌋, which "gemm8" reads in place of
    `p_inv_f64`; it defaults to `tabs.quot_fix`.
    """
    if r.dtype == jnp.uint64 and strategy == "matmul":
        strategy = "acc3"
    if r.dtype == jnp.uint64 and strategy == "gemm8":
        strategy = "sum16"      # 8-bit pieces of a 32-bit word only
    if quot_fix is None:
        quot_fix = jnp.asarray(tabs.quot_fix)
    return _icrt_jit(r, primes, inv_P, inv_P_shoup, pdivp, P_limbs, P_half,
                     p_inv_f64, quot_fix, out_limbs=out_limbs,
                     accum_limbs=tabs.accum_limbs, strategy=strategy)


@partial(jax.jit,
         static_argnames=("out_limbs", "accum_limbs", "strategy"))
def _icrt_jit(r, primes, inv_P, inv_P_shoup, pdivp, P_limbs, P_half,
              p_inv_f64, quot_fix, *, out_limbs: int, accum_limbs: int,
              strategy: str):
    npn, N = r.shape
    dt = r.dtype

    # (1) Hadamard: temp[j,n] = mod(r[j,n]·(P/p_j)⁻¹, p_j)   [Shoup]
    temp = shoup_modmul(r, inv_P[:, None], inv_P_shoup[:, None],
                        primes[:, None])

    if strategy == "gemm8":
        # (2)+(3) accum and the fixed-point quotient from one GEMM
        accum, s = _accum_gemm8(temp, pdivp, quot_fix, accum_limbs)
        return finalize_accum(accum, s, P_limbs, P_half, out_limbs)

    # (2) accum[n] = Σ_j temp[j,n]·(P/p_j)  — strategy-dependent
    if strategy == "sum16":
        accum = _accum_sum16(temp, pdivp, accum_limbs)
    elif strategy == "matmul":
        accum = _accum_matmul_u32(temp, pdivp, accum_limbs)
    elif strategy == "acc3":
        accum = _accum_acc3(temp, pdivp, accum_limbs)
    elif strategy == "naive":
        accum = _accum_naive(temp, pdivp, accum_limbs)
    else:
        raise ValueError(f"unknown iCRT strategy {strategy!r}")

    # (3) mod P via the float-quotient trick: accum/P = Σ_j temp_j/p_j
    # exactly; f64 error ≪ 1, so ±1 conditional corrections make it exact.
    s_f = temp.astype(jnp.float64).T @ p_inv_f64     # (N,)
    s = jnp.floor(s_f).astype(dt)
    return finalize_accum(accum, s, P_limbs, P_half, out_limbs)


def finalize_accum(accum, s, P_limbs, P_half, out_limbs: int):
    """accum − s·P with ±1 quotient corrections, center-lift, truncate.

    Shared by the pure-JAX iCRT and the Pallas iCRT tail. `s` may come from
    the f64 quotient or the fixed-point integer quotient ("gemm8", the TPU
    kernel); both are exact after the correction ladder.
    """
    N, accum_limbs = accum.shape
    sp = bigint.mul_word(jnp.broadcast_to(P_limbs, (N, accum_limbs)), s)
    red = bigint.sub(accum, sp)
    for _ in range(2):   # s may be off by one in either direction
        neg = bigint.sign_bit(red)
        red = bigint.select(neg, bigint.add(red, P_limbs), red)
        too_big = bigint.compare_ge(red, P_limbs) & ~neg
        red = bigint.select(too_big, bigint.sub(red, P_limbs), red)

    # center-lift: v >= P/2  ⇒  v -= P  (two's complement wrap is fine)
    high = bigint.compare_ge(red, P_half)
    red = bigint.select(high, bigint.sub(red, P_limbs), red)

    return red[:, :out_limbs] if out_limbs <= accum_limbs else _sext(
        red, out_limbs)


def _sext(a, out_limbs):
    sign = bigint.sign_bit(a)
    pad = jnp.where(sign[..., None], jnp.asarray(~jnp.zeros((), a.dtype)),
                    jnp.zeros((), a.dtype))
    pad = jnp.broadcast_to(pad, a.shape[:-1] + (out_limbs - a.shape[-1],))
    return jnp.concatenate([a, pad.astype(a.dtype)], axis=-1)


def _accum_sum16(temp, pdivp, accum_limbs):
    """Loop-reordered Algo 6 as four word-sized sums over the prime axis.

    Each word product temp[j,n]·pdivp[j,k] = hi·β + lo is cut into its
    four 16-bit pieces, and every piece is summed over j on its own: a
    sum of np < 2^16 pieces below 2^16 fits one word, so all four sums
    are exact in the limb dtype. They are plain reductions over primes,
    which GSPMD lowers to all-reduces when primes are model-sharded.
    A single carry scan then places the pieces at bit offsets 0, 16, 32
    and 48 of limb k.
    """
    npn, N = temp.shape
    dt = temp.dtype
    h = jnp.dtype(dt).itemsize * 4
    mask = jnp.asarray((1 << h) - 1, dt)
    assert npn < (1 << h), "piece sums would overflow a word"
    hi, lo = mul_wide(temp[:, :, None], pdivp[:, None, :])  # (np, N, PL)

    def total(x):
        return jnp.sum(x, axis=0, dtype=dt)                  # (N, PL)

    return _carry16([total(lo & mask), total(lo >> h), total(hi & mask),
                     total(hi >> h)], accum_limbs)


def _accum_gemm8(temp, pdivp, quot_fix, accum_limbs, chunk: int = 8192):
    """Algo 6's accumulation and its quotient as one exact GEMM on the MXU.

    Both are sums over primes of temp[j,n] times a table word: the limbs
    of P/p_j (`pdivp`, PL columns) for accum = Σ_j temp_j·(P/p_j), and
    the two limbs of ⌊β²/p_j⌋ (`quot_fix`) for the quotient, the
    fixed-point stand-in for the f64 Σ_j temp_j/p_j that the Pallas
    kernel also uses. Exactness, with β = 2^32:

      - temp and the W = PL + 2 table columns are cut into 8-bit pieces,
        temp = Σ_a t_a·2^(8a) and T[j,k] = Σ_b T_b[j,k]·2^(8b), a, b < 4.
        Integers 0..255 are exact in bf16 (8 significant bits), so the
        operands are fed as bf16: no matmul-precision setting can round
        them.
      - one dot_general contracts the prime axis: out[a,n,b,k] =
        Σ_j t_a[j,n]·T_b[j,k], accumulated in f32. Each product is below
        2^16, and a sum of np of them is at most np·255² < 2^24 for
        np ≤ 258 (the largest np served is 122), so every partial sum
        is an integer f32 holds exactly, in any order of addition.
      - on a mesh whose model axis shards the primes, the partitioner
        splits the contraction into per-shard partial sums and
        all-reduces them in f32. The partial sums are non-negative and
        bounded by the total, below 2^24, so the all-reduce is exact too.
      - converted to u32, the pieces that share a bit offset
        32k + 8c (c = a + b ≤ 6) are added: at most four terms below
        2^23, so each group sum G_c is below 2^25. The odd groups are
        split at a byte so that everything lands on the 16-bit offsets
        0, 16, 32, 48 of column k, four word sums below 2^26, and the
        `_carry16` pass that `_accum_sum16` uses places them in limbs.
      - the quotient is word 2 of Σ_j temp_j·⌊β²/p_j⌋ (< np·β² < β³),
        which is ⌊Σ_j temp_j/p_j⌋ or one less; `finalize_accum`'s ±1
        ladder makes the reduction exact either way.

    The GEMM's f32 output is 16·W words per coefficient, so it runs over
    `chunk` coefficients at a time (`lax.map`): at Table III, batch 8,
    a whole region-2 call would hold 3.9 GB of it at once.

    Returns (accum (N, accum_limbs), s (N,)).
    """
    npn, N = temp.shape
    PL = pdivp.shape[1]
    dt = temp.dtype
    assert dt == jnp.uint32, "8-bit pieces of a 32-bit word"
    assert npn * 255 * 255 < (1 << 24), "f32 piece sums would round"
    table = jnp.concatenate([pdivp, quot_fix], axis=1)        # (np, W)
    W = table.shape[1]
    rhs = jnp.moveaxis(_bytes_bf16(table), 0, 1).reshape(npn, 4 * W)
    if N <= chunk or N % chunk:
        sums = _gemm8_sums(temp, rhs)
    else:
        parts = jax.lax.map(
            lambda t: _gemm8_sums(t, rhs),
            jnp.moveaxis(temp.reshape(npn, N // chunk, chunk), 1, 0))
        sums = jnp.moveaxis(parts, 0, 1).reshape(4, N, W)
    accum = _carry16(sums[:, :, :PL], accum_limbs)
    quot = _carry16(sums[:, :, PL:], 3)
    return accum, quot[:, 2]


def _bytes_bf16(x):
    """(...) u32 words -> (4, ...) bf16 bytes, least significant first."""
    shifts = jnp.arange(0, 32, 8, dtype=x.dtype).reshape((4,) + (1,) * x.ndim)
    return ((x[None] >> shifts) & jnp.asarray(0xFF, x.dtype)).astype(
        jnp.bfloat16)


def _gemm8_sums(temp, rhs):
    """(np, n) u32 and the (np, 4W) byte planes of the table -> (4, n, W)
    word sums at bit offsets 0, 16, 32, 48 of each column."""
    dt = temp.dtype
    n = temp.shape[1]
    W = rhs.shape[1] // 4
    out = jax.lax.dot_general(
        _bytes_bf16(temp), rhs, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)              # (4, n, 4W)
    out = out.astype(dt).reshape(4, n, 4, W)
    g = [sum(out[a, :, c - a] for a in range(max(0, c - 3), min(c, 3) + 1))
         for c in range(7)]                              # G_c, < 2^25
    byte = jnp.asarray(0xFF, dt)
    return jnp.stack([g[0] + ((g[1] & byte) << 8),
                      g[2] + (g[1] >> 8) + ((g[3] & byte) << 8),
                      g[4] + (g[3] >> 8) + ((g[5] & byte) << 8),
                      g[6] + (g[5] >> 8)])


def _carry16(sums, accum_limbs):
    """Place four (N, PL) word sums at bit offsets 0, 16, 32 and 48 of
    limb k, and propagate the carries: -> (N, accum_limbs) BigInt."""
    N = sums[0].shape[0]
    dt = sums[0].dtype
    h = jnp.dtype(dt).itemsize * 4
    mask = jnp.asarray((1 << h) - 1, dt)
    sums = [_placed(s, 0, accum_limbs) for s in sums]

    def carry_step(carry, col):
        # limb t collects s0[t] + s1[t]·2^h (low half), the high half of
        # s1[t-1], s2[t-1], s3[t-1]·2^h (low half) and the high half of
        # s3[t-2]: at most 6 terms, so the carry out stays below 8
        c_in, s1_1, s2_1, s3_1, s3_2 = carry
        s0, s1, s2, s3 = col
        acc, c = c_in, jnp.zeros_like(c_in)
        for term in (s0, (s1 & mask) << h, s1_1 >> h, s2_1,
                     (s3_1 & mask) << h, s3_2 >> h):
            nxt = acc + term
            c = c + (nxt < term).astype(dt)
            acc = nxt
        return (c, s1, s2, s3, s3_1), acc

    zero = jnp.zeros((N,), dt)
    _, limbs = jax.lax.scan(carry_step, (zero,) * 5,
                            tuple(jnp.moveaxis(s, -1, 0) for s in sums))
    return jnp.moveaxis(limbs, 0, -1)


def _accum_matmul_u32(temp, pdivp, accum_limbs):
    """Loop-reordered Algo 6 as two u64 GEMMs on 16-bit table halves."""
    npn, N = temp.shape
    PL = pdivp.shape[1]
    mask16 = jnp.uint64(0xFFFF)
    t64 = temp.astype(jnp.uint64).T                       # (N, np)
    pl = pdivp.astype(jnp.uint64) & mask16                # (np, PL)
    ph = pdivp.astype(jnp.uint64) >> jnp.uint64(16)
    s_lo = t64 @ pl                                       # (N, PL) < 2^54
    s_hi = t64 @ ph
    # value_k = s_lo + s_hi·2^16 contributes to limbs k and k+1.
    m32 = jnp.uint64(0xFFFFFFFF)
    lo_part = (s_lo & m32) + ((s_hi << jnp.uint64(16)) & m32)   # < 2^33
    hi_part = (s_lo >> jnp.uint64(32)) + (s_hi >> jnp.uint64(16))
    acc = jnp.zeros((N, accum_limbs), jnp.uint64)
    acc = acc.at[:, :PL].add(lo_part)
    acc = acc.at[:, 1: PL + 1].add(hi_part)

    def carry_step(carry, col):
        v = col + carry
        return v >> jnp.uint64(32), (v & m32).astype(jnp.uint32)

    _, limbs = jax.lax.scan(carry_step, jnp.zeros((N,), jnp.uint64),
                            jnp.moveaxis(acc, -1, 0))
    return jnp.moveaxis(limbs, 0, -1)


def _accum_acc3(temp, pdivp, accum_limbs):
    """Algo 6 with per-(n,k) 3-word accumulators (paper's GPU-C flavour)."""
    npn, N = temp.shape
    PL = pdivp.shape[1]
    dt = temp.dtype
    zeros = jnp.zeros((N, PL), dt)

    def step(carry, j):
        a2, a1, a0 = carry
        tj = jax.lax.dynamic_index_in_dim(temp, j, 0, keepdims=False)
        pj = jax.lax.dynamic_index_in_dim(pdivp, j, 0, keepdims=False)
        a2, a1, a0 = acc3_add_product(
            a2, a1, a0,
            jnp.broadcast_to(tj[:, None], (N, PL)),
            jnp.broadcast_to(pj[None, :], (N, PL)))
        return (a2, a1, a0), None

    (a2, a1, a0), _ = jax.lax.scan(step, (zeros, zeros, zeros),
                                   jnp.arange(npn))
    # assemble Σ_k (a0 + a1β + a2β²)_k · β^k with three shifted adds
    acc = jnp.zeros((N, accum_limbs), dt)
    acc = bigint.add(acc, _placed(a0, 0, accum_limbs))
    acc = bigint.add(acc, _placed(a1, 1, accum_limbs))
    acc = bigint.add(acc, _placed(a2, 2, accum_limbs))
    return acc


def _accum_naive(temp, pdivp, accum_limbs):
    """Paper Algo 5: scan over primes, BigInt accumulate (N-parallel only).

    Deliberately the slow baseline: each step is a word×BigInt multiply and
    a full-width BigInt add per coefficient.
    """
    npn, N = temp.shape
    PL = pdivp.shape[1]
    dt = temp.dtype

    def step(acc, j):
        tj = jax.lax.dynamic_index_in_dim(temp, j, 0, keepdims=False)
        pj = jax.lax.dynamic_index_in_dim(pdivp, j, 0, keepdims=False)
        row = _placed(jnp.zeros((N, PL), dt) + pj[None, :], 0, accum_limbs)
        prod = bigint.mul_word(row, tj)
        return bigint.add(acc, prod), None

    acc0 = jnp.zeros((N, accum_limbs), dt)
    acc, _ = jax.lax.scan(step, acc0, jnp.arange(npn))
    return acc


def _placed(words, offset, accum_limbs):
    """(N, PL) words -> (N, accum_limbs) BigInt shifted by `offset` limbs.

    Words beyond the accumulator width are provably zero (each non-negative
    component is bounded by the total Σ < β^accum_limbs) and are dropped.
    """
    N, PL = words.shape
    keep = min(PL, accum_limbs - offset)
    out = jnp.zeros((N, accum_limbs), words.dtype)
    return out.at[:, offset: offset + keep].set(words[:, :keep])
