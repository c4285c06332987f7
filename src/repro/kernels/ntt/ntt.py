"""VMEM-resident negacyclic NTT / iNTT Pallas kernels (β = 2^32).

TPU adaptation of the paper's high-radix NTT (§V-C, Table IX): on a GPU the
paper raises the radix to cut HBM round trips of the (np, N) working set
from log₂N to log_kN. TPU VMEM (~16 MiB/core) holds an entire N-point row
(N = 2^16 → 256 KiB of u32), so the kernel streams one prime's row ONCE,
runs ALL log₂N butterfly stages on-chip, and writes ONCE — radix-N in the
paper's terms, the logical limit of its argument.

Layout: one grid step per prime. Its row x (length N = R·L, L = min(N,
128)) is handed to the kernel as the (L, R) tile X[l, r] = x[r·L + l], so
a butterfly of distance t ≥ L pairs lanes t/L apart and one of distance
t < L pairs sublanes t apart. Every stage is then whole-tile arithmetic:
the partner comes from a roll along one axis (`pltpu.roll`) and an iota
mask picks which half of the butterfly each element keeps. No stage
reshapes the lane axis, which Mosaic cannot lower.

Twiddles ride along per prime as a (S, R) table gathered from ψ_rev, one
row per lane-axis stage (its twiddle depends on r only) and L/2t rows per
sublane-axis stage (row g holds the twiddles of sublanes [2tg, 2tg + 2t)),
S = log₂R + L − 1 rows in all — about N entries.

All modmuls are Shoup (paper Algo 2) built on 16-bit-split mulhi
(DESIGN.md §2 — no widening multiply on TPU VPUs).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.wordops import (
    modadd, modsub, shoup_modmul, shoup_modmul_modified,
)
from repro.kernels.common import ZERO, use_interpret

LANES = 128


@functools.lru_cache(maxsize=None)
def _plan(N: int):
    """Static stage list and twiddle gather index for an N-point row.

    Returns (L, R, stages, idx): stages in forward (Cooley-Tukey) order,
    each (axis, dist, row, groups) of the (L, R) tile — `dist` apart on
    `axis`, twiddles in table rows [row, row + groups); idx (S, R) picks
    those table entries out of a ψ_rev row. A butterfly (i, i + t) of the
    stage with m = N/2t blocks uses ψ_rev[m + i // 2t] in both directions.
    """
    L = min(N, LANES)
    R = N // L
    r = np.arange(R)
    stages, rows = [], []
    t = N // 2
    while t >= 1:
        m = N // (2 * t)
        if t >= L:                      # lanes t/L apart: depends on r only
            k = t // L
            stages.append((1, k, len(rows), 1))
            rows.append(m + r // (2 * k))
        else:                           # sublanes t apart: group g = l // 2t
            groups = L // (2 * t)
            stages.append((0, t, len(rows), groups))
            rows.extend(m + r * groups + g for g in range(groups))
        t //= 2
    return L, R, tuple(stages), np.stack(rows).astype(np.int32)


def _twiddles(w_ref, row: int, groups: int, dist: int, shape):
    """The (L, R) twiddle tile of one stage: element (l, r) gets the
    twiddle of the butterfly it belongs to."""
    if groups == 1:
        return jnp.broadcast_to(w_ref[0, row:row + 1, :], shape)
    # int32 constants throughout: with x64 on, a bare int would promote
    gid = (jax.lax.broadcasted_iota(jnp.int32, shape, 0)
           >> np.int32((2 * dist).bit_length() - 1))
    w = jnp.broadcast_to(w_ref[0, row:row + 1, :], shape)
    for g in range(1, groups):
        w = jnp.where(gid == np.int32(g),
                      jnp.broadcast_to(w_ref[0, row + g:row + g + 1, :],
                                       shape), w)
    return w


def _low_half(shape, axis: int, dist: int):
    """True where an element is the first (u) operand of its butterfly."""
    iota = jax.lax.broadcasted_iota(jnp.int32, shape, axis)
    return (iota & np.int32(dist)) == ZERO


def _roll(x, shift: int, axis: int):
    """jnp.roll semantics on the TPU's rotate unit (shift as int32)."""
    return pltpu.roll(x, np.int32(shift), axis)


def _ntt_kernel(x_ref, w_ref, wsh_ref, p_ref, o_ref, *, stages, modified):
    mm = shoup_modmul_modified if modified else shoup_modmul
    x = x_ref[0]                        # (L, R)
    p = p_ref[0]                        # (1, 1)
    for axis, dist, row, groups in stages:      # log₂N stages, all in VMEM
        n = x.shape[axis]
        w = _twiddles(w_ref, row, groups, dist, x.shape)
        w_sh = _twiddles(wsh_ref, row, groups, dist, x.shape)
        vv = mm(x, w, w_sh, p)          # w·v, read where v sits
        lo = modadd(x, _roll(vv, n - dist, axis), p)       # u + w·v
        hi = modsub(_roll(x, dist, axis), vv, p)           # u − w·v
        x = jnp.where(_low_half(x.shape, axis, dist), lo, hi)
    o_ref[0] = x


def _intt_kernel(x_ref, w_ref, wsh_ref, ninv_ref, ninv_sh_ref, p_ref,
                 o_ref, *, stages, modified):
    mm = shoup_modmul_modified if modified else shoup_modmul
    x = x_ref[0]
    p = p_ref[0]
    for axis, dist, row, groups in reversed(stages):    # Gentleman-Sande
        n = x.shape[axis]
        w = _twiddles(w_ref, row, groups, dist, x.shape)
        w_sh = _twiddles(wsh_ref, row, groups, dist, x.shape)
        lo = modadd(x, _roll(x, n - dist, axis), p)        # u + v
        hi = mm(modsub(_roll(x, dist, axis), x, p), w, w_sh, p)
        x = jnp.where(_low_half(x.shape, axis, dist), lo, hi)
    # final elementwise ·N⁻¹ (paper §IV)
    o_ref[0] = mm(x, ninv_ref[0], ninv_sh_ref[0], p)


def _call(kernel, x, tables, scalars, interpret):
    """Run `kernel` over one prime per grid step; x (np, N) natural
    layout, tables (np, N) ψ rows, scalars (np,) per-prime values."""
    npn, N = x.shape
    L, R, stages, idx = _plan(N)
    S = idx.shape[0]
    interp = use_interpret() if interpret is None else interpret
    tile = pl.BlockSpec((1, L, R), lambda i: (i, ZERO, ZERO))
    table = pl.BlockSpec((1, S, R), lambda i: (i, ZERO, ZERO))
    scalar = pl.BlockSpec((1, 1, 1), lambda i: (i, ZERO, ZERO))
    out = pl.pallas_call(
        functools.partial(kernel, stages=stages),
        grid=(npn,),
        in_specs=[tile] + [table] * len(tables) + [scalar] * len(scalars),
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((npn, L, R), x.dtype),
        interpret=interp,
    )(jnp.swapaxes(x.reshape(npn, R, L), 1, 2),
      *(t[:, idx] for t in tables),
      *(s[:, None, None] for s in scalars))
    return jnp.swapaxes(out, 1, 2).reshape(npn, N)


@functools.partial(jax.jit, static_argnames=("modified", "interpret"))
def ntt_pallas(x, psi_rev, psi_rev_shoup, primes, *, modified=False,
               interpret=None):
    """(np, N) natural-order residues -> bit-reversed eval domain."""
    return _call(functools.partial(_ntt_kernel, modified=modified), x,
                 (psi_rev, psi_rev_shoup), (primes,), interpret)


@functools.partial(jax.jit, static_argnames=("modified", "interpret"))
def intt_pallas(x, ipsi_rev, ipsi_rev_shoup, n_inv, n_inv_shoup, primes, *,
                modified=False, interpret=None):
    """(np, N) bit-reversed eval domain -> natural-order residues."""
    return _call(functools.partial(_intt_kernel, modified=modified), x,
                 (ipsi_rev, ipsi_rev_shoup), (n_inv, n_inv_shoup, primes),
                 interpret)
