"""Blocked CRT Pallas kernel (paper Algo 1, GPU-C accumulation).

out[j, n] = mod(Σ_k in[n, k]·(β^k mod p_j), p_j)

Tiling: grid (np/npb, N/nb); each step loads a transposed input tile
(K, nb), the table tile (npb, K) and produces (npb, nb) residues. The
input is transposed outside the kernel so limb k is a sublane row and
the table entry a lane column: both broadcast to (npb, nb) from static
slices. Accumulation follows
the paper's winning CRT strategy (Table VIII "GPU-C"): raw 16-bit-split
products into a 3-word accumulator with synthesized ADC, ONE fold at the
end through Shoup multiplies by {1, β, β²} mod p — no per-iteration modulo.
A delayed-modulo variant ("modx", Table VIII Mod-2/Mod-4) is provided for
the benchmark ladder.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.wordops import (
    acc3_add_product, cond_reduce, mul_wide, shoup_modmul,
)
from repro.kernels.common import (
    SUBLANES, ZERO, pad_rows, pick_block, use_interpret,
)


def _crt_kernel_acc3(x_ref, tb_ref, tb_sh_ref, p_ref, o_ref):
    npb, K = tb_ref.shape
    nb = x_ref.shape[1]
    x = x_ref[...]                      # (K, nb)
    tb = tb_ref[...]                    # (npb, K)
    tb_sh = tb_sh_ref[...]
    p = p_ref[...]                      # (npb, 1)
    zeros = jnp.zeros((npb, nb), x.dtype)
    a2, a1, a0 = zeros, zeros, zeros
    for k in range(K):                  # static unroll; K ≤ ~76
        a2, a1, a0 = acc3_add_product(
            a2, a1, a0,
            jnp.broadcast_to(x[k:k + 1, :], (npb, nb)),
            jnp.broadcast_to(tb[:, k:k + 1], (npb, nb)))
    # fold 3-word accumulator: Shoup by β^k mod p (k = 0,1,2); tb[:,0] = 1.
    r0 = shoup_modmul(a0, tb[:, 0:1], tb_sh[:, 0:1], p)
    r1 = shoup_modmul(a1, tb[:, 1:2], tb_sh[:, 1:2], p)
    r2 = shoup_modmul(a2, tb[:, 2:3], tb_sh[:, 2:3], p)
    o_ref[...] = cond_reduce(r0 + r1 + r2, p, 4)


def _crt_kernel_modx(x_ref, tb_ref, tb_sh_ref, p_ref, o_ref, *, every):
    """Delayed-modulo ladder (Table VIII Mod-x): Shoup-fold every x terms."""
    npb, K = tb_ref.shape
    nb = x_ref.shape[1]
    x = x_ref[...]                      # (K, nb)
    tb = tb_ref[...]
    tb_sh = tb_sh_ref[...]
    p = p_ref[...]
    acc_hi = jnp.zeros((npb, nb), x.dtype)
    acc_lo = jnp.zeros((npb, nb), x.dtype)
    out = jnp.zeros((npb, nb), x.dtype)

    def fold(out, acc_hi, acc_lo):
        r0 = shoup_modmul(acc_lo, tb[:, 0:1], tb_sh[:, 0:1], p)
        r1 = shoup_modmul(acc_hi, tb[:, 1:2], tb_sh[:, 1:2], p)
        return cond_reduce(out + r0 + r1, p, 4)

    for k in range(K):
        hi, lo = mul_wide(jnp.broadcast_to(x[k:k + 1, :], (npb, nb)),
                          jnp.broadcast_to(tb[:, k:k + 1], (npb, nb)))
        new_lo = acc_lo + lo
        carry = (new_lo < lo).astype(x.dtype)
        acc_hi = acc_hi + hi + carry    # safe: ≤ `every` products, hi < β-1
        acc_lo = new_lo
        if (k + 1) % every == 0 or k == K - 1:
            out = fold(out, acc_hi, acc_lo)
            acc_hi = jnp.zeros_like(acc_hi)
            acc_lo = jnp.zeros_like(acc_lo)
    o_ref[...] = out


@functools.partial(jax.jit,
                   static_argnames=("strategy", "interpret"))
def crt_pallas(x, tb, tb_shoup, primes, *, strategy: str = "acc3",
               interpret=None):
    """(N, K) limbs -> (np, N) residues."""
    N, K = x.shape
    npn = tb.shape[0]
    nb = pick_block(N, 256)
    npb = SUBLANES                      # primes padded to whole row tiles
    rows = -(-npn // npb) * npb
    interp = use_interpret() if interpret is None else interpret
    if strategy == "acc3":
        kern = _crt_kernel_acc3
    elif strategy.startswith("mod"):
        kern = functools.partial(_crt_kernel_modx, every=int(strategy[3:]))
    else:
        raise ValueError(f"unknown kernel CRT strategy {strategy!r}")
    return pl.pallas_call(
        kern,
        grid=(rows // npb, N // nb),
        in_specs=[
            pl.BlockSpec((K, nb), lambda j, i: (ZERO, i)),
            pl.BlockSpec((npb, K), lambda j, i: (j, ZERO)),
            pl.BlockSpec((npb, K), lambda j, i: (j, ZERO)),
            pl.BlockSpec((npb, 1), lambda j, i: (j, ZERO)),
        ],
        out_specs=pl.BlockSpec((npb, nb), lambda j, i: (j, i)),
        out_shape=jax.ShapeDtypeStruct((rows, N), x.dtype),
        interpret=interp,
    )(x.T, pad_rows(tb), pad_rows(tb_shoup),
      pad_rows(primes[:, None]))[:npn]
