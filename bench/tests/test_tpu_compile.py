"""Compile rehearsals of the cells' served steps for a described v5e.

Nothing runs on a chip: the TPU compiler compiles each (op, level) step
a cell serves, at the deployment's real sizes, for one chip of a
`v5e:2x2` topology that is described, not attached, and checks that the
step with the resident tables and keys fits the chip. The topology is
described inside a fixture, never at import.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests/test_tpu_compile.py
"""

from __future__ import annotations

import json
from pathlib import Path

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

ROOT = Path(__file__).resolve().parents[2]
# usable bytes of one v5e chip as the runtime reports them
# (memory_stats()["bytes_limit"], 15.75 GiB)
V5E_LIMIT = int(15.75 * 2**30)


def _cell(name):
    from bench.run import load_cell, make_params
    cell = load_cell(name, ROOT)
    return cell, make_params(cell["config"])


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:                 # noqa: BLE001 — any failure
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache, so keep it out of the cache
    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield t
    jax.config.update("jax_enable_compilation_cache", old)


def _nbytes(tree) -> int:
    return sum(a.size * jnp.dtype(a.dtype).itemsize
               for a in jax.tree.leaves(tree))


def _compile(topo, params, op: str, logq: int, batch: int):
    """One served step, compiled for one chip; returns (compiled, bytes
    of the resident tables and keys it reads)."""
    from repro.core.rotate import rotation_k
    from repro.dist import he_pipeline as hp
    from repro.dist.sharding import he_limb_sharding
    from repro.hserve import engine as E
    from repro.launch.mesh import make_mesh

    st = hp.he_static(params, logq)
    mesh = make_mesh((1, 1), devices=topo.devices[:1])
    rep = NamedSharding(mesh, P())
    t1, t2, ek = (jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=rep), t)
        for t in hp.he_table_specs(st))
    ct = jax.ShapeDtypeStruct((batch, st.N, st.qlimbs), st.dtype,
                              sharding=he_limb_sharding(mesh, batch=batch))
    if op == "mul":
        step, args = hp.make_he_mul_step(st, mesh), (t1, t2, ek, ct, ct,
                                                     ct, ct)
    elif op == "rotate":
        step = E.make_he_rotate_step(st, mesh, rotation_k(params, 1))
        args = (t2, ek, ct, ct)
    elif op == "mul_plain":
        step, args = E.make_mul_plain_step(st, mesh), (t1, ct, ct, ct)
    else:
        step, args = E.make_rescale_step(st, mesh, params.logp), (ct, ct)
    return jax.jit(step).lower(*args).compile(), _nbytes((t1, t2, ek))


def _buckets(name, traffic=None):
    from bench.generator import bucket_list
    cell, params = _cell(name)
    if traffic is not None:
        cell["traffic"] = json.loads(
            (Path(__file__).parent / traffic).read_text())
    return [(name, op, params.logQ - lv * params.logp)
            for op, lv in bucket_list(cell["traffic"])]


# the cells' own buckets, and every bucket of the open-loop Table III mix
# (mix.json) at the t3 deployment's batch
CASES = sorted(set(_buckets("t3_mul_sat") + _buckets("s15_mul_sat")
                   + _buckets("t3_mul_sat", "mix.json")))


@pytest.mark.parametrize("name,op,logq", CASES,
                         ids=[f"{c}-{o}-{q}" for c, o, q in CASES])
def test_cell_step_fits_one_chip(topo, name, op, logq):
    cell, params = _cell(name)
    batch = cell["config"]["batch"]
    compiled, resident = _compile(topo, params, op, logq, batch)
    mem = compiled.memory_analysis()
    total = (mem.temp_size_in_bytes + mem.argument_size_in_bytes
             + mem.output_size_in_bytes)
    print(f"{name} {op}@{logq} batch {batch}: temp "
          f"{mem.temp_size_in_bytes} args {mem.argument_size_in_bytes} "
          f"out {mem.output_size_in_bytes} resident {resident}")
    # the step's arguments include one copy of the tables and keys; the
    # server keeps a second key (rotation) and other levels' slices
    assert total + resident < V5E_LIMIT, (total, resident)
