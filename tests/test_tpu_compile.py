"""Compiles for a described TPU v5e, at the paper's Table III widths.

Nothing here runs on a chip: the TPU compiler, which is installed with
jax, compiles for a `v5e:2x2` topology that is described, not attached.
That catches what the interpreter and XLA:CPU cannot — block shapes the
TPU tiling refuses, kernels Mosaic cannot lower, u64 GEMMs, programs
that do not fit a chip's memory — at no chip time. The topology is only
described inside a fixture, never at import, so every test worker
collects the same tests and only the one that runs this file loads the
TPU library.
"""

from __future__ import annotations

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.configs.heaan_mul import CONFIG
from repro.dist import he_pipeline as hp
from repro.dist.sharding import he_limb_sharding
from repro.kernels.crt.crt import crt_pallas
from repro.kernels.icrt.icrt import icrt_accum_pallas
from repro.kernels.modmul.modmul import pointwise_mont_pallas
from repro.kernels.ntt.ntt import intt_pallas, ntt_pallas
from repro.launch.mesh import make_mesh

V5E_HBM_BYTES = 16 * 10**9        # Google Cloud "TPU v5e": 16 GB per chip
ST = hp.he_static(CONFIG, CONFIG.logQ)     # np1=81, np2=122, 38 limbs


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                 # noqa: BLE001 — any failure
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip cannot be read back from the
    # persistent cache, so keep it out of the cache
    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", old)


def _u32(sharding, *shape):
    return jax.ShapeDtypeStruct(shape, jnp.uint32, sharding=sharding)


@pytest.mark.parametrize("npn", [ST.np1, ST.np2])
def test_crt_kernel_compiles_at_table3(one_chip, npn):
    s = _u32
    c = jax.jit(lambda x, t, ts, p: crt_pallas(x, t, ts, p, interpret=False)
                ).lower(s(one_chip, ST.N, ST.qlimbs),
                        s(one_chip, npn, ST.qlimbs),
                        s(one_chip, npn, ST.qlimbs),
                        s(one_chip, npn)).compile()
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("npn", [ST.np1, ST.np2])
def test_modmul_kernel_compiles_at_table3(one_chip, npn):
    s = _u32
    c = jax.jit(lambda a, b, p, pp, r2: pointwise_mont_pallas(
        a, b, p, pp, r2, interpret=False)).lower(
        s(one_chip, npn, ST.N), s(one_chip, npn, ST.N),
        s(one_chip, npn), s(one_chip, npn), s(one_chip, npn)).compile()
    assert "tpu_custom_call" in c.as_text()


def test_icrt_kernel_compiles_at_table3(one_chip):
    s, npn, tabs = _u32, ST.np1, ST.icrt1
    c = jax.jit(lambda r, i, ish, pd, qf, p: icrt_accum_pallas(
        r, i, ish, pd, qf, p, accum_limbs=tabs.accum_limbs,
        interpret=False)).lower(
        s(one_chip, npn, ST.N), s(one_chip, npn), s(one_chip, npn),
        s(one_chip, npn, tabs.plimbs), s(one_chip, npn, 2),
        s(one_chip, npn)).compile()
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("kernel", ["ntt_pallas", "intt_pallas"])
def test_ntt_kernel_compiles_at_table3(one_chip, kernel):
    s, npn = _u32, ST.np2
    rows = [s(one_chip, npn, ST.N)] * 3 + [s(one_chip, npn)] * (
        1 if kernel == "ntt_pallas" else 3)
    fn = ntt_pallas if kernel == "ntt_pallas" else intt_pallas
    c = jax.jit(lambda *a: fn(*a, interpret=False)).lower(*rows).compile()
    assert "tpu_custom_call" in c.as_text()


def _compile_mul_step(topo, batch: int, **knobs):
    """The served mul step at Table III, compiled for one v5e chip."""
    mesh = make_mesh((1, 1), devices=topo.devices[:1])
    rep = NamedSharding(mesh, P())
    t1, t2, ek = (jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=rep), t)
        for t in hp.he_table_specs(ST))
    ct = jax.ShapeDtypeStruct((batch, ST.N, ST.qlimbs), ST.dtype,
                              sharding=he_limb_sharding(mesh, batch=batch))
    step = jax.jit(hp.make_he_mul_step(ST, mesh, **knobs))
    return step.lower(t1, t2, ek, ct, ct, ct, ct).compile()


def _device_bytes(compiled) -> int:
    mem = compiled.memory_analysis()
    return (mem.temp_size_in_bytes + mem.argument_size_in_bytes
            + mem.output_size_in_bytes)


def test_served_mul_step_fits_one_chip_at_table3_batch8(topo, one_chip):
    """The default-knob mul step (CRT acc3, iCRT gemm8, no kernels)
    compiles for one v5e chip at batch 8 and fits its HBM, with room to
    spare: the byte-piece GEMM runs over coefficient chunks, so its f32
    output stays a fraction of a region. Its `he.icrt` ops hold no f64
    and contract bf16 pieces on the MXU (a convolution)."""
    compiled = _compile_mul_step(topo, 8)
    total = _device_bytes(compiled)
    assert total < V5E_HBM_BYTES, total
    assert total < V5E_HBM_BYTES // 2, total
    icrt = [ln for ln in compiled.as_text().splitlines()
            if "/he.icrt/" in ln]
    assert sum(" convolution(" in ln for ln in icrt) >= 5
    assert not [ln for ln in icrt if "f64[" in ln]


def test_kernel_mul_step_fits_one_chip_at_table3_batch8(topo, one_chip,
                                                        monkeypatch):
    """use_kernels=True: all four Pallas kernels inside the served mul
    step lower with Mosaic, and the step fits one chip at batch 8."""
    import importlib
    # the kernels pick interpret mode from the attached backend (the
    # CPU here); the step is compiled for the described chip instead
    for mod in ("crt.crt", "icrt.icrt", "modmul.modmul", "ntt.ntt"):
        monkeypatch.setattr(importlib.import_module(f"repro.kernels.{mod}"),
                            "use_interpret", lambda: False)
    compiled = _compile_mul_step(topo, 8, use_kernels=True)
    assert "tpu_custom_call" in compiled.as_text()
    total = _device_bytes(compiled)
    assert total < V5E_HBM_BYTES, total
