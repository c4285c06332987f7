"""Pointwise Montgomery modmul Pallas kernel (β = 2^32).

The eval-domain ciphertext⊙ciphertext products (paper Fig. 2 white circles)
are unknown×unknown, so Shoup does not apply; Montgomery REDC (2 REDCs,
domain-free) replaces hardware division. Trivially parallel: grid over
(np, N) tiles.
"""

from __future__ import annotations

import functools

import jax
from jax.experimental import pallas as pl

from repro.core.wordops import mont_modmul
from repro.kernels.common import (
    SUBLANES, ZERO, pad_rows, pick_block, use_interpret,
)


def _modmul_kernel(a_ref, b_ref, p_ref, pp_ref, r2_ref, o_ref):
    o_ref[...] = mont_modmul(a_ref[...], b_ref[...], p_ref[...],
                             pp_ref[...], r2_ref[...])


@functools.partial(jax.jit, static_argnames=("interpret",))
def pointwise_mont_pallas(a, b, primes, pprime, r2, *, interpret=None):
    npn, N = a.shape
    nb = pick_block(N, 2048)
    npb = SUBLANES                      # primes padded to whole row tiles
    rows = -(-npn // npb) * npb
    interp = use_interpret() if interpret is None else interpret
    tile = pl.BlockSpec((npb, nb), lambda j, i: (j, i))
    col = pl.BlockSpec((npb, 1), lambda j, i: (j, ZERO))
    out = pl.pallas_call(
        _modmul_kernel,
        grid=(rows // npb, N // nb),
        in_specs=[tile, tile, col, col, col],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((rows, N), a.dtype),
        interpret=interp,
    )(*(pad_rows(v) for v in (a, b, primes[:, None], pprime[:, None],
                              r2[:, None])))
    return out[:npn]
