"""The "gemm8" iCRT (the sums over primes as an exact byte-piece GEMM).

Bitwise checks at the benchmark deployments' real region shapes (Table
III and the n=2^15 standard ring, regions 1 and 2) on a slice of
coefficients: the accumulation Σ_j temp_j·(P/p_j) against `sum16` and
against Python integers, the fixed-point quotient against its exact
definition, and the whole iCRT against `sum16` — including the worst
case of the f32 bound, every temp at p_j − 1. A structural check lowers
the served mul step and asserts that its ``he.icrt`` scope holds a bf16
dot and no f64 op, so a later edit cannot fall back to the VPU quietly.
"""

from __future__ import annotations

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import crt as C
from repro.core.context import build_icrt_tables
from repro.core.params import HEParams, paper_params
from repro.nt.residue import limbs_to_int

# the benchmark's two deployments (bench/configs/heaan_t3.json and
# heaan_s15.json); the saturated mul cells serve at logq = logQ
DEPLOYMENTS = {"t3": paper_params(32),
               "s15": HEParams(logN=15, logQ=870, logp=30, log_delta=30,
                               beta_bits=32)}
SHAPES = [pytest.param(d, region, id=f"{d}-region{region}")
          for d in DEPLOYMENTS for region in (1, 2)]
N_SLICE = 256


def _tables(dep: str, region: int):
    params = DEPLOYMENTS[dep]
    npn = (params.np_region1 if region == 1 else params.np_region2)(
        params.logQ)
    return params, build_icrt_tables(params, npn)


def _temp(params, tabs, case: str) -> np.ndarray:
    primes = np.array(params.primes[:tabs.np_count], np.uint64)
    if case == "worst":                    # the largest temp every prime
        t = np.broadcast_to((primes - 1)[:, None], (len(primes), N_SLICE))
    else:
        rng = np.random.default_rng(14)
        t = rng.integers(0, primes[:, None], (len(primes), N_SLICE))
        t[:, :2] = [0, 1]
    return np.ascontiguousarray(t, np.uint32)


def test_real_shapes_match_the_deployments():
    """The parametrised shapes are the cells' np (81/122 at Table III,
    59/88 at logN=15), so the K bound is checked where it is served."""
    got = {d: tuple(_tables(d, r)[1].np_count for r in (1, 2))
           for d in DEPLOYMENTS}
    assert got == {"t3": (81, 122), "s15": (59, 88)}


@pytest.mark.parametrize("case", ["random", "worst"])
@pytest.mark.parametrize("dep,region", SHAPES)
def test_gemm8_accum_and_quotient_bitwise(dep, region, case):
    params, tabs = _tables(dep, region)
    temp = _temp(params, tabs, case)
    pdivp, qfix = jnp.asarray(tabs.pdivp), jnp.asarray(tabs.quot_fix)
    # chunk 64 runs the lax.map over coefficient chunks, as at N=2^16
    for chunk in (N_SLICE, 64):
        accum, s = jax.jit(C._accum_gemm8, static_argnums=(3, 4))(
            jnp.asarray(temp), pdivp, qfix, tabs.accum_limbs, chunk)
        accum, s = np.asarray(accum), np.asarray(s)
        ref16 = np.asarray(C._accum_sum16(jnp.asarray(temp), pdivp,
                                          tabs.accum_limbs))
        np.testing.assert_array_equal(accum, ref16)

    primes = params.primes[:tabs.np_count]
    pdivp_int = [tabs.P_int // p for p in primes]
    qfix_int = [(1 << 64) // p for p in primes]
    for n in range(N_SLICE):
        col = [int(v) for v in temp[:, n]]
        exact = sum(t * d for t, d in zip(col, pdivp_int))
        assert limbs_to_int(accum[n], 32) == exact, n
        # word 2 of Σ_j temp_j·⌊β²/p_j⌋, and within one below ⌊accum/P⌋
        q = sum(t * f for t, f in zip(col, qfix_int)) >> 64
        assert int(s[n]) == q, n
        assert exact // tabs.P_int - q in (0, 1), n


@pytest.mark.parametrize("dep,region", SHAPES)
def test_gemm8_icrt_matches_sum16(dep, region):
    """Whole iCRT on residues (random, and p_j − 1, the value −1)."""
    params, tabs = _tables(dep, region)
    primes = np.array(params.primes[:tabs.np_count], np.uint64)
    rng = np.random.default_rng(15)
    r = rng.integers(0, primes[:, None], (len(primes), N_SLICE))
    r[:, 0] = primes - 1
    r = jnp.asarray(r.astype(np.uint32))
    p_inv = jnp.asarray(1.0 / primes.astype(np.float64))
    out_limbs = params.qlimbs(params.logQ)

    def run(strategy):
        return np.asarray(C.icrt(
            r, tabs, jnp.asarray(primes.astype(np.uint32)),
            jnp.asarray(tabs.inv_P), jnp.asarray(tabs.inv_P_shoup),
            jnp.asarray(tabs.pdivp), jnp.asarray(tabs.P_limbs),
            jnp.asarray(tabs.P_half_limbs), p_inv, out_limbs,
            strategy=strategy))

    got = run("gemm8")
    np.testing.assert_array_equal(got, run("sum16"))
    assert (got[0] == np.uint32(0xFFFFFFFF)).all()          # −1


def test_served_mul_step_icrt_is_a_bf16_gemm_without_f64():
    """Lower and compile the toy served mul step on the CPU: `he.icrt`
    holds dots on bf16 operands, and nothing in it is f64 (the quotient
    is fixed-point)."""
    from repro.core.params import test_params
    from repro.dist import he_pipeline as hp
    from repro.launch.mesh import make_mesh

    st = hp.he_static(test_params(logN=4, beta_bits=32), 120)
    mesh = make_mesh((1, 1), ("data", "model"))
    t1, t2, ek = hp.he_table_specs(st)
    ct = jax.ShapeDtypeStruct((2, st.N, st.qlimbs), st.dtype)
    hlo = jax.jit(hp.make_he_mul_step(st, mesh)).lower(
        t1, t2, ek, ct, ct, ct, ct).compile().as_text()
    dtype_of = dict(re.findall(r"%(\S+) = (\w+)\[", hlo))
    icrt = [ln for ln in hlo.splitlines() if "/he.icrt/" in ln]
    dots = [re.search(r" dot\(([^)]*)\)", ln) for ln in icrt]
    dots = [m.group(1) for m in dots if m]
    assert len(dots) >= 5, "one dot per iCRT call of a mul (3 + 2)"
    for operands in dots:
        names = re.findall(r"%(\S+?)(?:,|$)", operands)
        assert [dtype_of[n] for n in names] == ["bf16", "bf16"], operands
    assert not [ln for ln in icrt if re.search(r"\bf64\[", ln)]
