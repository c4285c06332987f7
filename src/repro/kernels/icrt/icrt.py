"""Loop-reordered iCRT Pallas kernel (paper Algo 6).

The paper's key algorithmic move (§V-A): iCRT's scalar×BigInt accumulation
becomes an (np × PLimbs) matrix product per coefficient, exposing N·PLimbs
parallelism. The kernel fuses, per N-block:

  (1) the Hadamard step  temp[j,n] = mod(r[j,n]·(P/p_j)⁻¹, p_j)   [Shoup]
  (2) the reordered matmul  Σ_j temp[j,n]·(P/p_j)[limb k]  into 3-word
      accumulators (synthesized ADC)
  (3) limb assembly with carry propagation  -> accum (nb, A)
  (4) the fixed-point quotient  s ≈ Σ_j temp[j,n]·⌊β²/p_j⌋ / β²  — the TPU
      replacement for the f64 quotient (no f64 on TPU; ±1 error is fixed by
      the shared correction ladder in core.crt.finalize_accum).

Outputs: accum limbs (N, A) and the quotient estimate (N, 1). The cheap
O(N·A) tail (−s·P, corrections, center-lift) runs in plain JAX (ops.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.wordops import acc3_add_product, shoup_modmul
from repro.kernels.common import ZERO, pick_block, use_interpret


def _icrt_kernel(r_ref, invp_ref, invp_sh_ref, pdivp_ref, qfix_ref, p_ref,
                 acc_out_ref, s_out_ref):
    npn, nb = r_ref.shape
    PL = pdivp_ref.shape[1]
    A = acc_out_ref.shape[1]
    dt = r_ref.dtype

    # (1) Hadamard (Shoup)
    temp = shoup_modmul(r_ref[...], invp_ref[...], invp_sh_ref[...],
                        p_ref[...])                       # (np, nb)
    pdivp = pdivp_ref[...]                                # (np, PL)
    qfix = qfix_ref[...]                                  # (np, 2)

    # (2) reordered matmul into 3-word accumulators (nb, PL)
    zeros = jnp.zeros((nb, PL), dt)
    a2, a1, a0 = zeros, zeros, zeros
    # and the fixed-point quotient accumulator (nb,)
    z1 = jnp.zeros((nb,), dt)
    s2, s1, s0 = z1, z1, z1
    for j in range(npn):                    # static unroll over primes
        tj = temp[j]                        # (nb,)
        a2, a1, a0 = acc3_add_product(
            a2, a1, a0, jnp.broadcast_to(tj[:, None], (nb, PL)),
            jnp.broadcast_to(pdivp[j][None, :], (nb, PL)))
        s2, s1, s0 = acc3_add_product(s2, s1, s0, tj,
                                      jnp.broadcast_to(qfix[j, 0], (nb,)))
        hi, lo = _mul_wide_vec(tj, qfix[j, 1])
        # qfix[j,1] is the β¹ word: product lands one word higher
        ns1 = s1 + lo
        c = (ns1 < lo).astype(dt)
        s1 = ns1
        s2 = s2 + hi + c
    # quotient = word 2 of Σ t_j·⌊β²/p_j⌋ (value/β²), error ∈ {0, -1}
    s_out_ref[...] = s2[:, None]

    # (3) limb assembly: Σ_k (a0 + a1β + a2β²)_k β^k with carry chains
    carry = jnp.zeros((nb,), dt)
    for t in range(A):
        w0 = a0[:, t] if t < PL else jnp.zeros((nb,), dt)
        w1 = a1[:, t - 1] if 0 <= t - 1 < PL else jnp.zeros((nb,), dt)
        w2 = a2[:, t - 2] if 0 <= t - 2 < PL else jnp.zeros((nb,), dt)
        v0 = w0 + w1
        c0 = (v0 < w1).astype(dt)
        v1 = v0 + w2
        c1 = (v1 < w2).astype(dt)
        v2 = v1 + carry
        c2 = (v2 < carry).astype(dt)
        acc_out_ref[:, t] = v2
        carry = c0 + c1 + c2            # ≤ 3: absorbed next limb

    # NOTE: carry after the top limb is provably zero (Σ < β^A).


def _mul_wide_vec(a, b):
    from repro.core.wordops import mul_wide
    return mul_wide(a, jnp.broadcast_to(b, a.shape))


@functools.partial(jax.jit, static_argnames=("accum_limbs", "interpret"))
def icrt_accum_pallas(r, inv_P, inv_P_shoup, pdivp, quot_fix, primes, *,
                      accum_limbs: int, interpret=None):
    """(np, N) residues -> (accum (N, A), s_estimate (N,))."""
    npn, N = r.shape
    PL = pdivp.shape[1]
    nb = pick_block(N, 128)
    interp = use_interpret() if interpret is None else interpret
    col = pl.BlockSpec((npn, 1), lambda i: (ZERO, ZERO))
    acc, s = pl.pallas_call(
        _icrt_kernel,
        grid=(N // nb,),
        in_specs=[
            pl.BlockSpec((npn, nb), lambda i: (ZERO, i)),
            col, col,
            pl.BlockSpec((npn, PL), lambda i: (ZERO, ZERO)),
            pl.BlockSpec((npn, 2), lambda i: (ZERO, ZERO)),
            col,
        ],
        out_specs=[
            pl.BlockSpec((nb, accum_limbs), lambda i: (i, ZERO)),
            pl.BlockSpec((nb, 1), lambda i: (i, ZERO)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((N, accum_limbs), r.dtype),
            jax.ShapeDtypeStruct((N, 1), r.dtype),
        ],
        interpret=interp,
    )(r, inv_P[:, None], inv_P_shoup[:, None], pdivp, quot_fix,
      primes[:, None])
    return acc, s[:, 0]
