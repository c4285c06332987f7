"""NTT/iNTT and CRT/iCRT vs exact python-int oracles (paper Algos 1,3,5,6)."""

import numpy as np
import pytest

import jax.numpy as jnp

import random

from repro.core import test_params as small_params
from repro.core import make_context
from repro.core import crt as C
from repro.core import ntt as T
from repro.core.wordops import mont_modmul
from repro.nt.residue import limbs_to_int, ints_to_limb_array


def _ctx(beta, logN=4, logQ=120, logp=24):
    p = small_params(logN=logN, beta_bits=beta, logQ=logQ, logp=logp)
    return p, make_context(p, p.logQ)


def _negacyclic_ref(a, b, q):
    """Exact negacyclic convolution of int lists mod q (python ints)."""
    n = len(a)
    out = [0] * n
    for i in range(n):
        for j in range(n):
            k = i + j
            if k < n:
                out[k] += a[i] * b[j]
            else:
                out[k - n] -= a[i] * b[j]
    return [v % q for v in out]


@pytest.mark.parametrize("beta", [32, 64])
def test_ntt_roundtrip(beta):
    p, ctx = _ctx(beta)
    g = ctx.tables
    npn = ctx.np1
    N = ctx.N
    rng = np.random.default_rng(1)
    primes = np.asarray(g.primes[:npn]).astype(np.uint64)
    x = (rng.integers(0, 1 << 62, size=(npn, N)).astype(np.uint64)
         % primes[:, None]).astype(g.primes.dtype)
    xj = jnp.asarray(x)
    fwd = T.ntt(xj, jnp.asarray(g.psi_rev[:npn]),
                jnp.asarray(g.psi_rev_shoup[:npn]),
                jnp.asarray(g.primes[:npn]))
    back = T.intt(fwd, jnp.asarray(g.ipsi_rev[:npn]),
                  jnp.asarray(g.ipsi_rev_shoup[:npn]),
                  jnp.asarray(g.n_inv[:npn]), jnp.asarray(g.n_inv_shoup[:npn]),
                  jnp.asarray(g.primes[:npn]))
    np.testing.assert_array_equal(np.asarray(back), x)


@pytest.mark.parametrize("beta", [32, 64])
@pytest.mark.parametrize("modified", [False, True])
def test_ntt_negacyclic_convolution(beta, modified):
    """pointwise-in-eval-domain == negacyclic convolution (the real check)."""
    p, ctx = _ctx(beta)
    g = ctx.tables
    npn, N = ctx.np1, ctx.N
    rng = np.random.default_rng(2)
    a = [int(v) for v in rng.integers(0, 1 << 20, size=N)]
    b = [int(v) for v in rng.integers(0, 1 << 20, size=N)]
    primes_py = [int(v) for v in np.asarray(g.primes[:npn])]

    ra = np.stack([[ai % pj for ai in a] for pj in primes_py]).astype(
        g.primes.dtype)
    rb = np.stack([[bi % pj for bi in b] for pj in primes_py]).astype(
        g.primes.dtype)

    def fwd(x):
        return T.ntt(jnp.asarray(x), jnp.asarray(g.psi_rev[:npn]),
                     jnp.asarray(g.psi_rev_shoup[:npn]),
                     jnp.asarray(g.primes[:npn]), modified=modified)

    ea, eb = fwd(ra), fwd(rb)
    prod = mont_modmul(ea, eb, jnp.asarray(g.primes[:npn])[:, None],
                       jnp.asarray(g.pprime[:npn])[:, None],
                       jnp.asarray(g.r2[:npn])[:, None])
    back = T.intt(prod, jnp.asarray(g.ipsi_rev[:npn]),
                  jnp.asarray(g.ipsi_rev_shoup[:npn]),
                  jnp.asarray(g.n_inv[:npn]), jnp.asarray(g.n_inv_shoup[:npn]),
                  jnp.asarray(g.primes[:npn]), modified=modified)
    back = np.asarray(back)
    for j, pj in enumerate(primes_py):
        expect = _negacyclic_ref(a, b, pj)
        np.testing.assert_array_equal(back[j], np.array(expect, dtype=np.uint64)
                                      .astype(back.dtype), err_msg=f"prime {j}")


@pytest.mark.parametrize("beta", [32, 64])
@pytest.mark.parametrize("strategy", ["matmul", "shoup", "mod2", "mod4", "acc3"])
def test_crt_strategies(beta, strategy):
    if beta == 64 and strategy in ("matmul", "mod2", "mod4"):
        pytest.skip("wide-accumulator strategies are β=2^32 only")
    p, ctx = _ctx(beta)
    g = ctx.tables
    npn = ctx.np2
    K = ctx.qlimbs
    N = ctx.N
    pr = random.Random(3)
    vals = [pr.getrandbits(p.logQ) for _ in range(N)]
    x = ints_to_limb_array(vals, K, beta)
    out = C.crt(jnp.asarray(x), jnp.asarray(g.crt_tb[:npn, :K]),
                jnp.asarray(g.crt_tb_shoup[:npn, :K]),
                jnp.asarray(g.primes[:npn]), strategy=strategy)
    out = np.asarray(out)
    primes_py = [int(v) for v in np.asarray(g.primes[:npn])]
    for j, pj in enumerate(primes_py):
        expect = np.array([v % pj for v in vals], dtype=np.uint64)
        np.testing.assert_array_equal(out[j].astype(np.uint64), expect,
                                      err_msg=f"prime {j} strategy {strategy}")


@pytest.mark.parametrize("beta", [32, 64])
@pytest.mark.parametrize("strategy", ["gemm8", "sum16", "matmul", "acc3",
                                      "naive"])
def test_crt_icrt_roundtrip_centered(beta, strategy):
    """CRT → iCRT returns the centered value (two's complement truncation)."""
    if beta == 64 and strategy == "matmul":
        pytest.skip("matmul iCRT is β=2^32 only")
    p, ctx = _ctx(beta)
    g = ctx.tables
    npn = ctx.np1
    tabs = ctx.icrt1
    K = ctx.qlimbs
    N = ctx.N
    pr = random.Random(4)
    # signed values with magnitude < P/2 (and < 2^(K·β-1) for truncation)
    mag = min(tabs.P_int // 2, 1 << (K * beta - 2))
    vals = [pr.randrange(-mag, mag) for _ in range(N)]
    vals[:3] = [0, 1, -1]  # boundary cases near the float-quotient edge
    res = np.stack([
        np.array([v % pj for v in vals], dtype=np.uint64)
        for pj in [int(q) for q in np.asarray(g.primes[:npn])]
    ]).astype(g.primes.dtype)
    out = C.icrt(jnp.asarray(res), tabs,
                 jnp.asarray(g.primes[:npn]),
                 jnp.asarray(tabs.inv_P), jnp.asarray(tabs.inv_P_shoup),
                 jnp.asarray(tabs.pdivp), jnp.asarray(tabs.P_limbs),
                 jnp.asarray(tabs.P_half_limbs),
                 jnp.asarray(g.p_inv_f64[:npn]),
                 out_limbs=K, strategy=strategy)
    out = np.asarray(out)
    W_ = 1 << (K * beta)
    for n in range(N):
        got = limbs_to_int(out[n], beta)
        if got >= W_ // 2:
            got -= W_
        assert got == vals[n], (n, got, vals[n])
