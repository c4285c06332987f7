"""RNS pipeline composition: the paper's Fig. 2 stages as reusable pieces.

    limbs --CRT--> residues --NTT--> eval domain
    eval  --iNTT--> residues --iCRT--> centered limbs

Strategy flags select the paper's optimization ladder (see core.crt/ntt).
The HEAAN scheme (core.heaan) and the benchmarks compose these; the Pallas
kernels provide drop-in replacements for each stage (repro.kernels).
"""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np

from repro.core.context import GlobalTables, build_icrt_tables
from repro.core.crt import crt, icrt
from repro.core.ntt import intt, ntt, pointwise_shoup_scale
from repro.core.params import HEParams
from repro.core.wordops import modadd, modsub, mont_modmul

__all__ = ["PipelineConfig", "to_eval", "to_eval_small", "from_eval",
           "eval_mul", "eval_add", "eval_sub", "eval_mul_shoup",
           "poly_mul", "small_ints_to_limbs", "limbs_to_centered_ints"]


@dataclasses.dataclass(frozen=True)
class PipelineConfig:
    """Paper optimization toggles (§V). Defaults = the served path, which
    compiles for every backend (the TPU refuses the u64 matmul GEMMs)."""
    crt_strategy: str = "acc3"        # acc3 | shoup | mod2 | mod4 | matmul
    icrt_strategy: str = "gemm8"      # gemm8 | sum16 | acc3 | naive | matmul
    modified_shoup: bool = False      # paper's 3-half-mul Shoup variant
    use_kernels: bool = False         # route stages through Pallas kernels


DEFAULT = PipelineConfig()


def to_eval(x: jnp.ndarray, npn: int, g: GlobalTables,
            cfg: PipelineConfig = DEFAULT) -> jnp.ndarray:
    """(N, K) limbs -> (npn, N) eval-domain residues (CRT then NTT)."""
    K = x.shape[1]
    if cfg.use_kernels:
        from repro.kernels.crt.ops import crt_op
        from repro.kernels.ntt.ops import ntt_op
        res = crt_op(x, jnp.asarray(g.crt_tb[:npn, :K]),
                     jnp.asarray(g.crt_tb_shoup[:npn, :K]),
                     jnp.asarray(g.primes[:npn]))
        return ntt_op(res, jnp.asarray(g.psi_rev[:npn]),
                      jnp.asarray(g.psi_rev_shoup[:npn]),
                      jnp.asarray(g.primes[:npn]))
    res = crt(x, jnp.asarray(g.crt_tb[:npn, :K]),
              jnp.asarray(g.crt_tb_shoup[:npn, :K]),
              jnp.asarray(g.primes[:npn]), strategy=cfg.crt_strategy)
    return ntt(res, jnp.asarray(g.psi_rev[:npn]),
               jnp.asarray(g.psi_rev_shoup[:npn]),
               jnp.asarray(g.primes[:npn]), modified=cfg.modified_shoup)


def to_eval_small(s: jnp.ndarray, npn: int, g: GlobalTables,
                  cfg: PipelineConfig = DEFAULT) -> jnp.ndarray:
    """Small signed ints (N,) (e.g. ternary secrets) -> eval domain."""
    primes = jnp.asarray(g.primes[:npn])
    s64 = jnp.asarray(s, jnp.int64)
    res = jnp.where(s64[None, :] >= 0,
                    s64[None, :].astype(primes.dtype) %
                    primes[:, None],
                    primes[:, None]
                    - ((-s64[None, :]).astype(primes.dtype)
                       % primes[:, None]))
    res = jnp.where(res == primes[:, None], 0, res).astype(primes.dtype)
    if cfg.use_kernels:
        from repro.kernels.ntt.ops import ntt_op
        return ntt_op(res, jnp.asarray(g.psi_rev[:npn]),
                      jnp.asarray(g.psi_rev_shoup[:npn]), primes)
    return ntt(res, jnp.asarray(g.psi_rev[:npn]),
               jnp.asarray(g.psi_rev_shoup[:npn]), primes,
               modified=cfg.modified_shoup)


def from_eval(ev: jnp.ndarray, params: HEParams, out_limbs: int,
              g: GlobalTables, cfg: PipelineConfig = DEFAULT) -> jnp.ndarray:
    """(npn, N) eval residues -> (N, out_limbs) centered two's complement."""
    npn = ev.shape[0]
    tabs = build_icrt_tables(params, npn)
    primes = jnp.asarray(g.primes[:npn])
    if cfg.use_kernels:
        from repro.kernels.ntt.ops import intt_op
        from repro.kernels.icrt.ops import icrt_op
        res = intt_op(ev, jnp.asarray(g.ipsi_rev[:npn]),
                      jnp.asarray(g.ipsi_rev_shoup[:npn]),
                      jnp.asarray(g.n_inv[:npn]),
                      jnp.asarray(g.n_inv_shoup[:npn]), primes)
        return icrt_op(res, tabs, g, out_limbs)
    res = intt(ev, jnp.asarray(g.ipsi_rev[:npn]),
               jnp.asarray(g.ipsi_rev_shoup[:npn]),
               jnp.asarray(g.n_inv[:npn]), jnp.asarray(g.n_inv_shoup[:npn]),
               primes, modified=cfg.modified_shoup)
    return icrt(res, tabs, primes,
                jnp.asarray(tabs.inv_P), jnp.asarray(tabs.inv_P_shoup),
                jnp.asarray(tabs.pdivp), jnp.asarray(tabs.P_limbs),
                jnp.asarray(tabs.P_half_limbs),
                jnp.asarray(g.p_inv_f64[:npn]),
                out_limbs=out_limbs, strategy=cfg.icrt_strategy)


def eval_mul(a: jnp.ndarray, b: jnp.ndarray, g: GlobalTables,
             cfg: PipelineConfig = DEFAULT) -> jnp.ndarray:
    """Pointwise a⊙b mod p (unknown×unknown → Montgomery)."""
    npn = a.shape[0]
    if cfg.use_kernels:
        from repro.kernels.modmul.ops import pointwise_mont_op
        return pointwise_mont_op(a, b, jnp.asarray(g.primes[:npn]),
                                 jnp.asarray(g.pprime[:npn]),
                                 jnp.asarray(g.r2[:npn]))
    return mont_modmul(a, b, jnp.asarray(g.primes[:npn])[:, None],
                       jnp.asarray(g.pprime[:npn])[:, None],
                       jnp.asarray(g.r2[:npn])[:, None])


def eval_mul_shoup(a: jnp.ndarray, b: jnp.ndarray, b_shoup: jnp.ndarray,
                   g: GlobalTables, cfg: PipelineConfig = DEFAULT
                   ) -> jnp.ndarray:
    """Pointwise a⊙b mod p where b has precomputed Shoup companions (evk)."""
    npn = a.shape[0]
    return pointwise_shoup_scale(a, b, b_shoup,
                                 jnp.asarray(g.primes[:npn]),
                                 modified=cfg.modified_shoup)


def eval_add(a, b, g: GlobalTables):
    return modadd(a, b, jnp.asarray(g.primes[: a.shape[0]])[:, None])


def eval_sub(a, b, g: GlobalTables):
    return modsub(a, b, jnp.asarray(g.primes[: a.shape[0]])[:, None])


def poly_mul(x: jnp.ndarray, y: jnp.ndarray, x_bits: int, y_bits: int,
             params: HEParams, g: GlobalTables, out_limbs: int,
             cfg: PipelineConfig = DEFAULT) -> jnp.ndarray:
    """General negacyclic poly product of two canonical limb polys.

    Chooses np from the exact coefficient bound |c| < N·2^(x_bits+y_bits).
    Returns centered two's complement at out_limbs.
    """
    npn = params.np_for_bits(
        params.primes, x_bits + y_bits + params.logN + 2)
    ex = to_eval(x, npn, g, cfg)
    ey = to_eval(y, npn, g, cfg)
    return from_eval(eval_mul(ex, ey, g, cfg), params, out_limbs, g, cfg)


# ---- host/limb conversions -------------------------------------------------

def small_ints_to_limbs(v: np.ndarray, n_limbs: int, beta_bits: int
                        ) -> jnp.ndarray:
    """Signed small ints (N,) -> (N, L) two's complement limb arrays."""
    dt = jnp.uint32 if beta_bits == 32 else jnp.uint64
    v64 = jnp.asarray(np.asarray(v, dtype=np.int64))
    out = []
    x = v64.astype(jnp.int64)
    for k in range(n_limbs):
        if beta_bits == 32:
            out.append((x & 0xFFFFFFFF).astype(dt))
            x = x >> 32
        else:
            out.append(x.astype(jnp.uint64))
            x = x >> 63 >> 1   # arithmetic sign fill
    return jnp.stack(out, axis=-1)


def limbs_to_centered_ints(a: np.ndarray, beta_bits: int, logq: int
                           ) -> list:
    """(N, L) mod-q limbs -> centered python ints in [-q/2, q/2)."""
    from repro.nt.residue import limbs_to_int
    q = 1 << logq
    out = []
    for row in np.asarray(a):
        v = limbs_to_int(row, beta_bits) % q
        out.append(v - q if v >= q // 2 else v)
    return out
