"""repro.hserve tests: queue invariants, level-slice table equality,
engine bitwise parity vs the single-device core references, metrics, and
the composed server loop.

The 8-device mesh parity check (sharded rotate/mul/slot-sum) runs
through the shared run_in_8dev_subprocess harness (tests/conftest.py):
a fresh interpreter with
XLA_FLAGS=--xla_force_host_platform_device_count=8.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import heaan as H
from repro.core import test_params as small_params
from repro.core.context import make_context
from repro.core.keys import keygen
from repro.core.rotate import conj_keygen, he_conjugate, he_rotate, \
    rot_keygen
from repro.dist import he_pipeline as hp
from repro.hserve import (
    BatchAssembler, CircuitOp, CircuitScheduler, HEServer, RequestQueue,
    ServeMetrics, TableCache, circuit_schedule, degree4_demo_circuit,
    slot_sum_rotations, validate_circuit,
)
from repro.launch.mesh import make_mesh

PARAMS = small_params(logN=4, beta_bits=32)   # N=16, n_slots=8, L=5


@pytest.fixture(scope="module")
def keys():
    sk, pk, evk = keygen(PARAMS, seed=0)
    rks = {r: rot_keygen(PARAMS, sk, r) for r in (1, 2, 4)}
    return sk, pk, evk, rks


@pytest.fixture(scope="module")
def ck(keys):
    return conj_keygen(PARAMS, keys[0])


def _enc(pk, seed, n=8):
    rng = np.random.default_rng(seed)
    z = rng.normal(size=n) + 1j * rng.normal(size=n)
    return z, H.encrypt_message(z, pk, PARAMS, seed=seed)


# --------------------------------------------------------------------------
# queue: bucketing and padding invariants
# --------------------------------------------------------------------------

def test_queue_buckets_by_op_level_and_r(keys):
    _, pk, _, _ = keys
    q = RequestQueue()
    _, c1 = _enc(pk, 1)
    _, c2 = _enc(pk, 2)
    low = H.he_mod_down(c1, PARAMS, PARAMS.logQ - PARAMS.logp)
    low2 = H.he_mod_down(c2, PARAMS, PARAMS.logQ - PARAMS.logp)
    r0 = q.submit("mul", (c1, c2))
    r1 = q.submit("mul", (c1, c2))
    q.submit("mul", (low, low2))            # different level, new bucket
    q.submit("rotate", (c1,), r=1)
    q.submit("rotate", (c1,), r=2)          # different r, new bucket
    q.submit("slot_sum", (c1,))
    assert q.depth == 6
    assert len(q.bucket_depths()) == 5
    # oldest bucket with >= 2 requests is the top-level mul bucket
    key = q.ready_key(2)
    assert key == ("mul", PARAMS.logQ, None)
    got = q.pop_bucket(key, 2)
    assert [r.rid for r in got] == [r0, r1]   # FIFO within the bucket
    assert q.ready_key(2) is None             # no other bucket is full
    assert q.any_key() is not None            # but work remains for flush


def test_server_rejects_unserveable_requests_at_submit(keys):
    """A request the engine cannot serve must never enter the queue —
    otherwise it fails mid-drain after being popped, taking the rest of
    the queued work down with it."""
    _, pk, evk, rks = keys
    mesh = make_mesh((1, 1), ("data", "model"))
    _, c1 = _enc(pk, 1)
    server = HEServer(PARAMS, evk, {1: rks[1]}, mesh=mesh, batch=2)
    with pytest.raises(KeyError):
        server.submit_rotate(c1, 3)           # no key for r=3
    with pytest.raises(KeyError):
        server.submit_slot_sum(c1)            # needs r=2,4 too
    no_evk = HEServer(PARAMS, rot_keys=rks, mesh=mesh, batch=2)
    with pytest.raises(ValueError):
        no_evk.submit_mul(c1, c1)             # no evaluation key
    assert no_evk.submit_slot_sum(c1) == 0    # rotations fully keyed
    assert server.queue.depth == 0


def test_queue_rejects_bad_requests(keys):
    _, pk, _, _ = keys
    q = RequestQueue()
    _, c1 = _enc(pk, 1)
    low = H.he_mod_down(c1, PARAMS, PARAMS.logQ - PARAMS.logp)
    with pytest.raises(ValueError):
        q.submit("frobnicate", (c1,))
    with pytest.raises(ValueError):
        q.submit("mul", (c1,))                # arity
    with pytest.raises(ValueError):
        q.submit("mul", (c1, low))            # level mismatch
    with pytest.raises(ValueError):
        q.submit("rotate", (c1,), r=0)        # no rotation amount


def test_assembler_pads_to_fixed_shape(keys):
    _, pk, _, _ = keys
    q = RequestQueue()
    _, c1 = _enc(pk, 1)
    _, c2 = _enc(pk, 2)
    for _ in range(3):
        q.submit("mul", (c1, c2))
    asm = BatchAssembler(batch=4)
    b = asm.assemble(q.pop_bucket(("mul", PARAMS.logQ, None), 4))
    assert b.size == 4 and b.n_valid == 3 and b.n_pad == 1
    assert set(b.arrays) == {"ax1", "bx1", "ax2", "bx2"}
    for v in b.arrays.values():
        assert v.shape == (4, PARAMS.N, PARAMS.qlimbs(PARAMS.logQ))
        assert not np.asarray(v[3]).any()     # padded lane is zeros
    # valid lanes carry the submitted operands, in request order
    np.testing.assert_array_equal(np.asarray(b.arrays["ax1"][0]),
                                  np.asarray(c1.ax))
    np.testing.assert_array_equal(np.asarray(b.arrays["bx2"][2]),
                                  np.asarray(c2.bx))
    # rotate batches carry one operand only
    q.submit("rotate", (c1,), r=1)
    b = asm.assemble(q.pop_bucket(("rotate", PARAMS.logQ, 1), 4))
    assert set(b.arrays) == {"ax1", "bx1"}
    assert b.n_valid == 1 and b.n_pad == 3


def test_assembler_rejects_mixed_and_oversize(keys):
    _, pk, _, _ = keys
    q = RequestQueue()
    _, c1 = _enc(pk, 1)
    _, c2 = _enc(pk, 2)
    low = H.he_mod_down(c1, PARAMS, PARAMS.logQ - PARAMS.logp)
    low2 = H.he_mod_down(c2, PARAMS, PARAMS.logQ - PARAMS.logp)
    q.submit("mul", (c1, c2))
    q.submit("mul", (low, low2))
    reqs = (q.pop_bucket(("mul", PARAMS.logQ, None), 4)
            + q.pop_bucket(("mul", PARAMS.logQ - PARAMS.logp, None), 4))
    asm = BatchAssembler(batch=4)
    with pytest.raises(ValueError):
        asm.assemble(reqs)                    # mixed buckets
    with pytest.raises(ValueError):
        BatchAssembler(batch=1).assemble(reqs[:1] * 2)  # oversize
    with pytest.raises(ValueError):
        asm.assemble([])


# --------------------------------------------------------------------------
# tables: level slices == freshly built per-level tables
# --------------------------------------------------------------------------

def test_table_cache_level_slices_match_fresh_tables(keys):
    """The resident-slice pytrees must be value-identical to
    region_tables built from a fresh per-level context at EVERY level —
    the whole bitwise-serving argument rests on this."""
    _, _, evk, _ = keys
    cache = TableCache(PARAMS, evk)
    for i in range(3):
        logq = PARAMS.logQ - i * PARAMS.logp
        t1, t2 = cache.level_tables(logq)
        ctx = make_context(PARAMS, logq)
        for region, cached in ((1, t1), (2, t2)):
            fresh = hp.region_tables(ctx, region)
            assert set(cached) == set(fresh) == set(hp.REGION_TABLE_KEYS)
            for k in fresh:
                np.testing.assert_array_equal(
                    np.asarray(cached[k]), np.asarray(jnp.asarray(fresh[k])),
                    err_msg=f"level {logq} region {region} table {k}")
    st = cache.stats()
    assert len(st["levels_materialized"]) == 3
    # second hit serves from cache
    before = cache.hits
    cache.level_tables(PARAMS.logQ)
    assert cache.hits == before + 1


def test_table_cache_keys_and_stats(keys):
    _, _, evk, rks = keys
    cache = TableCache(PARAMS, evk, {1: rks[1]})
    assert set(cache.evk()) == set(hp.EVK_TABLE_KEYS)
    assert set(cache.rot_key(1)) == set(hp.EVK_TABLE_KEYS)
    with pytest.raises(KeyError):
        cache.rot_key(2)
    cache.add_rot_key(2, rks[2])
    assert cache.rotation_amounts == [1, 2]
    assert cache.stats()["resident_mib"] > 0
    with pytest.raises(ValueError):
        TableCache(PARAMS).evk()


# --------------------------------------------------------------------------
# engine parity vs core, through the composed server (1-device mesh)
# --------------------------------------------------------------------------

def _server(keys, conj_key=None, **kw):
    _, _, evk, rks = keys
    mesh = make_mesh((1, 1), ("data", "model"))
    return HEServer(PARAMS, evk, rks, conj_key, mesh=mesh, batch=2, **kw)


def test_served_mul_bitwise_equals_core_at_two_levels(keys):
    sk, pk, evk, _ = keys
    server = _server(keys)
    cases = []
    for i, logq in enumerate((PARAMS.logQ, PARAMS.logQ - PARAMS.logp)):
        _, c1 = _enc(pk, 10 + 2 * i)
        _, c2 = _enc(pk, 11 + 2 * i)
        if logq < PARAMS.logQ:
            c1 = H.he_mod_down(c1, PARAMS, logq)
            c2 = H.he_mod_down(c2, PARAMS, logq)
        rid = server.submit_mul(c1, c2)
        cases.append((rid, H.he_mul(c1, c2, evk, PARAMS)))
    res = server.drain()
    for rid, ref in cases:
        out = res[rid]
        assert out.logq == ref.logq and out.logp == ref.logp
        np.testing.assert_array_equal(np.asarray(out.ax),
                                      np.asarray(ref.ax))
        np.testing.assert_array_equal(np.asarray(out.bx),
                                      np.asarray(ref.bx))


def test_served_rotate_bitwise_equals_core(keys):
    sk, pk, _, rks = keys
    server = _server(keys)
    _, ct = _enc(pk, 42)
    low = H.he_mod_down(ct, PARAMS, PARAMS.logQ - PARAMS.logp)
    cases = [(server.submit_rotate(ct, 1),
              he_rotate(ct, 1, rks[1], PARAMS)),
             (server.submit_rotate(low, 2),
              he_rotate(low, 2, rks[2], PARAMS))]
    res = server.drain()
    for rid, ref in cases:
        out = res[rid]
        np.testing.assert_array_equal(np.asarray(out.ax),
                                      np.asarray(ref.ax))
        np.testing.assert_array_equal(np.asarray(out.bx),
                                      np.asarray(ref.bx))


def test_served_slot_sum_bitwise_equals_core_composition(keys):
    sk, pk, _, rks = keys
    server = _server(keys)
    z, ct = _enc(pk, 77)
    rid = server.submit_slot_sum(ct)
    # reference: acc ← he_add(acc, he_rotate(acc, r)) for doubling r
    acc = ct
    for r in slot_sum_rotations(ct.n_slots):
        acc = H.he_add(acc, he_rotate(acc, r, rks[r], PARAMS))
    out = server.drain()[rid]
    np.testing.assert_array_equal(np.asarray(out.ax), np.asarray(acc.ax))
    np.testing.assert_array_equal(np.asarray(out.bx), np.asarray(acc.bx))
    got = H.decrypt_message(out, sk, PARAMS)
    np.testing.assert_allclose(got.real, np.full(8, z.real.sum()),
                               atol=1e-2)


def test_served_mul_with_kernels_bitwise(keys):
    """The Pallas-routed engine path (satellite: use_kernels through the
    batched stage wrappers) keeps the bitwise contract."""
    _, pk, evk, _ = keys
    server = _server(keys, use_kernels=True)
    _, c1 = _enc(pk, 91)
    _, c2 = _enc(pk, 92)
    rid = server.submit_mul(c1, c2)
    ref = H.he_mul(c1, c2, evk, PARAMS)
    out = server.drain()[rid]
    np.testing.assert_array_equal(np.asarray(out.ax), np.asarray(ref.ax))
    np.testing.assert_array_equal(np.asarray(out.bx), np.asarray(ref.bx))


# --------------------------------------------------------------------------
# level-management ops (this PR): bitwise parity vs core
# --------------------------------------------------------------------------

def test_queue_validates_level_management_ops(keys):
    _, pk, _, _ = keys
    q = RequestQueue()
    _, c1 = _enc(pk, 1)
    low = H.he_mod_down(c1, PARAMS, PARAMS.logQ - PARAMS.logp)
    resc = H.rescale(c1, PARAMS)              # different logp than c1
    with pytest.raises(ValueError):
        q.submit("rescale", (c1,), dlogp=0)   # needs a positive dlogp
    with pytest.raises(ValueError):
        q.submit("rescale", (c1,), dlogp=PARAMS.logQ)   # exhausted
    with pytest.raises(ValueError):
        q.submit("mod_down", (c1,), logq2=0)
    with pytest.raises(ValueError):
        q.submit("mod_down", (c1,), logq2=PARAMS.logQ + 1)
    with pytest.raises(ValueError):
        q.submit("add", (low, resc))          # scale mismatch
    # distinct extras land in distinct buckets (trace signatures)
    q.submit("rescale", (c1,), dlogp=PARAMS.logp)
    q.submit("rescale", (c1,), dlogp=2 * PARAMS.logp)
    q.submit("mod_down", (c1,), logq2=PARAMS.logQ - PARAMS.logp)
    q.submit("conjugate", (c1,))
    q.submit("add", (c1, c1))
    q.submit("sub", (c1, c1))
    assert len(q.bucket_depths()) == 6


def test_served_level_ops_bitwise_equal_core(keys, ck):
    """conjugate / rescale / mod_down / add / sub through the server are
    bitwise identical to the single-device core references, with the
    right output (logq, logp) metadata."""
    _, pk, _, _ = keys
    server = _server(keys, ck)
    _, c1 = _enc(pk, 50)
    _, c2 = _enc(pk, 51)
    logq2 = PARAMS.logQ - PARAMS.logp
    cases = [
        (server.submit_conjugate(c1), he_conjugate(c1, ck, PARAMS)),
        (server.submit_rescale(c1), H.rescale(c1, PARAMS)),
        (server.submit_mod_down(c1, logq2),
         H.he_mod_down(c1, PARAMS, logq2)),
        (server.submit_add(c1, c2), H.he_add(c1, c2)),
        (server.submit_sub(c1, c2), H.he_sub(c1, c2)),
    ]
    res = server.drain()
    for rid, ref in cases:
        out = res[rid]
        assert out.logq == ref.logq and out.logp == ref.logp
        np.testing.assert_array_equal(np.asarray(out.ax),
                                      np.asarray(ref.ax))
        np.testing.assert_array_equal(np.asarray(out.bx),
                                      np.asarray(ref.bx))


def test_conjugate_requires_key(keys):
    _, pk, _, _ = keys
    server = _server(keys)                    # no conjugation key
    _, c1 = _enc(pk, 1)
    with pytest.raises(ValueError):
        server.submit_conjugate(c1)
    assert server.queue.depth == 0


# --------------------------------------------------------------------------
# plaintext-operand ops (this PR): region-1-only mul_plain / add_plain
# --------------------------------------------------------------------------

def _plain(seed, logq, n=8):
    rng = np.random.default_rng(seed)
    w = rng.normal(size=n) + 1j * rng.normal(size=n)
    return w, H.encode_plain(w, PARAMS, logq)


def test_served_plain_ops_bitwise_equal_core_at_every_level(keys):
    """mul_plain / add_plain through the server are bitwise identical to
    core.heaan.he_mul_plain / he_add_plain at every served level, with
    the right output (logq, logp) metadata — and they need NO keys."""
    sk, pk, _, _ = keys
    # a server with NO evk / rotation / conjugation keys at all: the
    # plaintext ops must still serve (no key switch is their point)
    mesh = make_mesh((1, 1), ("data", "model"))
    server = HEServer(PARAMS, mesh=mesh, batch=2)
    cases = []
    for i in range(3):
        logq = PARAMS.logQ - i * PARAMS.logp
        z, ct = _enc(pk, 80 + i)
        if logq < PARAMS.logQ:
            ct = H.he_mod_down(ct, PARAMS, logq)
        w, pt = _plain(90 + i, logq)
        cases.append((server.submit_mul_plain(ct, pt),
                      H.he_mul_plain(ct, pt, PARAMS), ("mul", z * w)))
        cases.append((server.submit_add_plain(ct, pt),
                      H.he_add_plain(ct, pt, PARAMS), ("add", z + w)))
    res = server.drain()
    for rid, ref, (kind, want) in cases:
        out = res[rid]
        assert out.logq == ref.logq and out.logp == ref.logp
        np.testing.assert_array_equal(np.asarray(out.ax),
                                      np.asarray(ref.ax))
        np.testing.assert_array_equal(np.asarray(out.bx),
                                      np.asarray(ref.bx))
        dec = H.rescale(out, PARAMS) if kind == "mul" else out
        got = H.decrypt_message(dec, sk, PARAMS)
        np.testing.assert_allclose(got, want, atol=1e-2)


def test_plain_ops_validation(keys):
    _, pk, _, _ = keys
    q = RequestQueue()
    _, c1 = _enc(pk, 1)
    _, pt = _plain(2, PARAMS.logQ)
    with pytest.raises(ValueError, match="plaintext"):
        q.submit("mul_plain", (c1,))              # no operand
    with pytest.raises(ValueError, match="pt_logp"):
        q.submit("mul_plain", (c1,), pt=pt)       # no scale
    with pytest.raises(ValueError, match="scales differ"):
        q.submit("add_plain", (c1,), pt=pt,
                 pt_logp=c1.logp + 1)             # scale mismatch
    with pytest.raises(ValueError, match="does not cover"):
        q.submit("mul_plain", (c1,), pt=np.asarray(pt)[:, :1],
                 pt_logp=PARAMS.log_delta)        # too few limbs
    q.submit("mul_plain", (c1,), pt=pt, pt_logp=PARAMS.log_delta)
    q.submit("add_plain", (c1,), pt=pt)           # pt_logp 0 → ct.logp
    assert len(q.bucket_depths()) == 2            # distinct buckets


def test_plain_ops_as_circuit_nodes_bitwise(keys):
    """An affine-layer-shaped circuit — mul_plain → rescale → add_plain
    — served via submit_circuit, bitwise equal to the composed core
    references (and the same under the circuit-aware scheduler)."""
    sk, pk, _, _ = keys
    _, x = _enc(pk, 70)
    w, pt = _plain(71, PARAMS.logQ)
    logq1 = PARAMS.logQ - PARAMS.logp
    _, pt2 = _plain(72, logq1)
    ops = [
        CircuitOp("mul_plain", ("x",), pt=pt),
        CircuitOp("rescale", (0,)),
        CircuitOp("add_plain", (1,), pt=pt2),
    ]
    ref = H.he_add_plain(
        H.rescale(H.he_mul_plain(x, pt, PARAMS), PARAMS), pt2, PARAMS)
    for schedule in (False, True):
        server = _server(keys, schedule=schedule)
        cid = server.submit_circuit(ops, {"x": x})
        out = server.drain()[cid]
        assert out.logq == ref.logq and out.logp == ref.logp
        np.testing.assert_array_equal(np.asarray(out.ax),
                                      np.asarray(ref.ax))
        np.testing.assert_array_equal(np.asarray(out.bx),
                                      np.asarray(ref.bx))


def test_circuit_validates_plain_ops(keys):
    _, pk, _, _ = keys
    _, x = _enc(pk, 1)
    meta = {"x": (x.logq, x.logp)}
    _, pt = _plain(2, PARAMS.logQ)
    with pytest.raises(ValueError, match="plaintext"):
        validate_circuit([CircuitOp("mul_plain", ("x",))], meta, PARAMS)
    # a plaintext encoded at a LOWER level than the node's input must be
    # rejected up front — otherwise queue.submit raises mid-drain from
    # _submit_ready, stranding the circuit with siblings already served
    _, pt_low = _plain(3, PARAMS.logQ - 3 * PARAMS.logp)
    with pytest.raises(ValueError, match="does not cover"):
        validate_circuit([CircuitOp("mul", ("x", "x")),
                          CircuitOp("rescale", (0,)),
                          CircuitOp("mul_plain", (1,), pt=pt_low)],
                         meta, PARAMS)
    with pytest.raises(ValueError, match="scales differ"):
        validate_circuit([CircuitOp("add_plain", ("x",), pt=pt,
                                    pt_logp=x.logp + 1)], meta, PARAMS)
    # negative pt_logp must fail HERE, not from queue.submit mid-drain
    with pytest.raises(ValueError, match="negative mul_plain"):
        validate_circuit([CircuitOp("mul_plain", ("x",), pt=pt,
                                    pt_logp=-1)], meta, PARAMS)
    out = validate_circuit(
        [CircuitOp("mul_plain", ("x",), pt=pt),
         CircuitOp("rescale", (0,))], meta, PARAMS)
    # mul_plain doubles the scale (pt at log_delta), rescale drops one
    assert out[0] == (PARAMS.logQ, x.logp + PARAMS.log_delta)
    assert out[1] == (PARAMS.logQ - PARAMS.logp,
                      x.logp + PARAMS.log_delta - PARAMS.logp)


# --------------------------------------------------------------------------
# circuits: server-side op-DAG walk with level tracking
# --------------------------------------------------------------------------

def _degree4_reference(x, evk, ck):
    r0 = H.rescale(H.he_mul(x, x, evk, PARAMS), PARAMS)
    r1 = H.rescale(H.he_mul(r0, r0, evk, PARAMS), PARAMS)
    logq_md = PARAMS.logQ - 3 * PARAMS.logp
    r2 = he_conjugate(H.he_mod_down(r1, PARAMS, logq_md), ck, PARAMS)
    return H.he_add(r2, H.he_mod_down(x, PARAMS, logq_md))


def test_circuit_degree4_bitwise_equals_core(keys, ck):
    """The acceptance circuit: a degree-4 encrypted polynomial submitted
    ONCE via submit_circuit, evaluated wholly server-side, decrypting
    bitwise-identical to the composed single-device core reference."""
    sk, pk, evk, _ = keys
    server = _server(keys, ck)
    z, x = _enc(pk, 99)
    ops, _ = degree4_demo_circuit(PARAMS)
    cid = server.submit_circuit(ops, {"x": x})
    out = server.drain()[cid]
    ref = _degree4_reference(x, evk, ck)
    assert out.logq == ref.logq and out.logp == ref.logp
    np.testing.assert_array_equal(np.asarray(out.ax), np.asarray(ref.ax))
    np.testing.assert_array_equal(np.asarray(out.bx), np.asarray(ref.bx))
    got = H.decrypt_message(out, sk, PARAMS)
    np.testing.assert_allclose(got, np.conj(z ** 4) + z, atol=0.3)
    assert not server._circuits                # bookkeeping fully drained
    assert not server._node_of_rid


def test_concurrent_circuits_batch_together(keys, ck):
    """Two identical circuits submitted together share (op, level)
    signatures node-for-node, so their nodes batch pairwise (batch=2):
    no padded lanes anywhere."""
    _, pk, evk, _ = keys
    server = _server(keys, ck)
    _, x1 = _enc(pk, 60)
    _, x2 = _enc(pk, 61)
    ops, _ = degree4_demo_circuit(PARAMS)
    c1 = server.submit_circuit(ops, {"x": x1})
    c2 = server.submit_circuit(ops, {"x": x2})
    res = server.drain()
    for cid, x in ((c1, x1), (c2, x2)):
        ref = _degree4_reference(x, evk, ck)
        np.testing.assert_array_equal(np.asarray(res[cid].ax),
                                      np.asarray(ref.ax))
    for op, d in server.stats()["per_op"].items():
        assert d["pad_frac"] == 0.0, f"{op} padded despite lockstep"


def test_circuit_validation_rejects_before_enqueue(keys, ck):
    """Level tracking catches ill-formed circuits up front — nothing may
    enter the queue for a circuit that cannot complete."""
    _, pk, _, _ = keys
    server = _server(keys, ck)
    _, x = _enc(pk, 1)
    meta = {"x": (x.logq, x.logp)}
    # static validator: level/scale propagation
    with pytest.raises(ValueError, match="exhausts"):
        validate_circuit([CircuitOp("rescale", ("x",),
                                    dlogp=PARAMS.logQ)], meta, PARAMS)
    with pytest.raises(ValueError, match="levels differ"):
        validate_circuit([CircuitOp("mod_down", ("x",),
                                    logq2=PARAMS.logQ - PARAMS.logp),
                          CircuitOp("add", (0, "x"))], meta, PARAMS)
    with pytest.raises(ValueError, match="scales differ"):
        validate_circuit([CircuitOp("mul", ("x", "x")),
                          CircuitOp("add", (0, "x"))], meta, PARAMS)
    with pytest.raises(ValueError, match="not an earlier node"):
        validate_circuit([CircuitOp("conjugate", (1,)),
                          CircuitOp("conjugate", (0,))], meta, PARAMS)
    with pytest.raises(ValueError, match="unknown input"):
        validate_circuit([CircuitOp("conjugate", ("y",))], meta, PARAMS)
    with pytest.raises(ValueError, match="negative rescale"):
        validate_circuit([CircuitOp("rescale", ("x",), dlogp=-8)],
                         meta, PARAMS)
    # the server wires metadata + key checks into submit_circuit
    for bad in ([CircuitOp("mul", ("x", "x")),
                 CircuitOp("add", (0, "x"))],       # scale mismatch
                [CircuitOp("rotate", ("x",), r=3)]):  # no key for r=3
        with pytest.raises((ValueError, KeyError)):
            server.submit_circuit(bad, {"x": x})
    # slot_sum key availability is checked up front too — through node
    # references (n_slots propagates), and before ANY sibling enqueues
    no_keys = _server((keys[0], keys[1], keys[2], {}))  # evk, no rot keys
    with pytest.raises(KeyError, match="slot_sum"):
        no_keys.submit_circuit(
            [CircuitOp("mod_down", ("x",),
                       logq2=PARAMS.logQ - PARAMS.logp),
             CircuitOp("slot_sum", (0,))], {"x": x})
    assert server.queue.depth == 0
    assert no_keys.queue.depth == 0
    assert not no_keys._circuits


# --------------------------------------------------------------------------
# circuit-aware scheduler (this PR's tentpole): lookahead co-batching,
# prefetch, and the drain-vs-circuit deadlock regression
# --------------------------------------------------------------------------

def test_circuit_schedule_predicts_actual_bucket_keys(keys):
    """The schedule the scheduler looks ahead at must be EXACTLY the
    bucket keys the nodes' requests land in — key drift would defer
    buckets for siblings that never arrive."""
    _, pk, _, _ = keys
    _, x = _enc(pk, 1)
    _, pt = _plain(2, PARAMS.logQ)
    lq = PARAMS.logQ - 2 * PARAMS.logp
    ops = [
        CircuitOp("mul", ("x", "x")),
        CircuitOp("rescale", (0,)),
        CircuitOp("mul_plain", (1,), pt=np.asarray(pt)[
            :, :PARAMS.qlimbs(PARAMS.logQ - PARAMS.logp)],
            pt_logp=x.logp),
        CircuitOp("rescale", (2,)),
        CircuitOp("mod_down", ("x",), logq2=lq),
        CircuitOp("rotate", (4,), r=1),
        CircuitOp("slot_sum", (5,)),
        CircuitOp("conjugate", (6,)),
        CircuitOp("add", (3, 7)),
    ]
    meta = {"x": (x.logq, x.logp)}
    _, predicted, nslots = circuit_schedule(ops, meta, {"x": x.n_slots},
                                            PARAMS)
    assert nslots == [8] * 9
    # replay every node through a real queue as its operands would
    # resolve, and compare the actual bucket keys (metadata-faithful
    # zero ciphertexts stand in for node outputs)
    node_meta = validate_circuit(ops, meta, PARAMS)
    from repro.core.cipher import Ciphertext as CT
    values = {"x": x}
    q = RequestQueue()
    for i, node in enumerate(ops):
        cts = tuple(values[a] for a in node.args)
        dlogp = node.dlogp or (PARAMS.logp if node.op == "rescale" else 0)
        rid = q.submit(node.op, cts, r=node.r, dlogp=dlogp,
                       logq2=node.logq2, pt=node.pt,
                       pt_logp=node.pt_logp
                       or (PARAMS.log_delta
                           if node.op == "mul_plain" else 0))
        (key, reqs), = ((k, d) for k, d in q._buckets.items()
                        if any(r.rid == rid for r in d))
        assert key == predicted[i], (i, node.op, key, predicted[i])
        q.pop_bucket(key, 8)
        lq_i, lp_i = node_meta[i]
        k = PARAMS.qlimbs(lq_i)
        z = jnp.zeros((PARAMS.N, k), dtype=np.asarray(x.ax).dtype)
        values[i] = CT(ax=z, bx=z, logq=lq_i, logp=lp_i, n_slots=8)


def test_scheduler_lookahead_expectations():
    """Unit-level: expectations count pending same-key nodes within the
    horizon, shrink as nodes enqueue/complete, and vanish when the
    circuit finishes (dangling nodes must not defer buckets forever)."""
    s = CircuitScheduler(lookahead=2)
    K0, K1 = ("mul", 120, None), ("rescale", 120, 30)
    # chain: n0 -> n1 -> n2 (n0/n2 share K0), n3 dangling on n0
    s.register(7, [K0, K1, K0, K1], [(), (0,), (1,), (0,)])
    # n0 is 1 step away (source, not yet enqueued); n2 is 3 away (> 2)
    assert s.expected_within(K0) == 1
    s.on_enqueued(7, 0)
    assert s.expected_within(K0) == 1      # n2 is 2 batches away
    assert s.expected_within(K0, horizon=1) == 0
    assert s.expected_within(K1) == 2      # n1 (1 away) + n3 (1 away)
    s.on_completed(7, 0)
    s.on_enqueued(7, 1)
    assert s.expected_within(K0, horizon=1) == 1   # n2 now 1 away
    s.on_completed(7, 1)
    s.on_enqueued(7, 2)
    assert s.expected_within(K0) == 0
    s.on_completed(7, 2)
    s.on_finished(7)                        # n3 never ran (dangling)
    assert s.expected_within(K1) == 0
    assert s.stats()["circuits_tracked"] == 0


def test_drain_completes_2deep_samekey_circuit_regression(keys):
    """The drain-vs-circuit deadlock: in [mul(x,x), mul(0,0)] BOTH nodes
    share one bucket key, so the only non-empty bucket 'expects a
    sibling' whose parent is the bucket itself — a deferral policy
    without the progress guarantee never serves it and drain() spins.
    Submitted right before drain(), under the scheduler, it must
    complete (and stay bitwise): fails on the pre-PR server."""
    _, pk, evk, _ = keys
    for overlap in (False, True):
        server = _server(keys, schedule=True, overlap=overlap)
        _, x = _enc(pk, 31)
        cid = server.submit_circuit(
            [CircuitOp("mul", ("x", "x")), CircuitOp("mul", (0, 0))],
            {"x": x})
        res = server.drain()
        assert server._inflight is None and not server._circuits
        r0 = H.he_mul(x, x, evk, PARAMS)
        ref = H.he_mul(r0, r0, evk, PARAMS)
        np.testing.assert_array_equal(np.asarray(res[cid].ax),
                                      np.asarray(ref.ax))
        np.testing.assert_array_equal(np.asarray(res[cid].bx),
                                      np.asarray(ref.bx))
        assert server.scheduler.deferrals >= 1   # it DID defer, once,
        # then the progress guarantee flushed the bucket anyway


def test_scheduler_cobatches_staggered_circuits_and_stays_bitwise(keys, ck):
    """Two degree-4 circuits submitted one engine batch out of phase:
    unscheduled they trail each other with padded batches; scheduled,
    the lookahead deferral re-syncs them (cross-circuit co-batch rate
    up, mul padding no worse) without changing a single bit."""
    _, pk, _, _ = keys
    ops, _ = degree4_demo_circuit(PARAMS)
    outs, cob, pads = {}, {}, {}
    for schedule in (False, True):
        server = _server(keys, ck, schedule=schedule)
        _, x1 = _enc(pk, 60)
        _, x2 = _enc(pk, 61)
        c1 = server.submit_circuit(ops, {"x": x1})
        server.poll(flush=True)               # desync the pair
        c2 = server.submit_circuit(ops, {"x": x2})
        res = server.drain()
        s = server.stats()
        outs[schedule] = (res[c1], res[c2])
        cob[schedule] = s["cobatch"]
        pads[schedule] = s["per_op"]["mul"]["pad_frac"]
    # scheduled == unscheduled == the composed single-device core refs
    refs = [_degree4_reference(_enc(pk, s)[1], keys[2], ck)
            for s in (60, 61)]
    for got in (outs[False], outs[True]):
        for out, ref in zip(got, refs):
            np.testing.assert_array_equal(np.asarray(out.ax),
                                          np.asarray(ref.ax))
            np.testing.assert_array_equal(np.asarray(out.bx),
                                          np.asarray(ref.bx))
    assert cob[True]["cross_circuit_batches"] > \
        cob[False]["cross_circuit_batches"]
    assert cob[True]["cross_circuit_rate"] > cob[False]["cross_circuit_rate"]
    assert pads[True] <= pads[False]


def test_scheduler_prefetches_next_levels(keys, ck):
    """Dispatching a level-dropping batch prefetches the successor
    levels' table slices while the batch is in flight — the cache rows
    exist BEFORE the successor node's step ever runs."""
    _, pk, _, _ = keys
    server = _server(keys, ck, schedule=True)
    _, x = _enc(pk, 62)
    lq = PARAMS.logQ - PARAMS.logp
    cid = server.submit_circuit(
        [CircuitOp("mul", ("x", "x")), CircuitOp("rescale", (0,)),
         CircuitOp("conjugate", (1,))], {"x": x})
    assert not server.cache.has_level(lq)
    server.poll(flush=True)                   # runs the mul; prefetches
    assert server.cache.has_level(lq)         # before rescale/conj run
    assert server.scheduler.prefetches >= 1
    assert lq in server.scheduler.prefetched_levels
    res = server.drain()
    assert cid in res


# --------------------------------------------------------------------------
# continuous batching: age-based flush under a trickle (fake clock)
# --------------------------------------------------------------------------

def test_poll_trickle_regression_without_age_policy(keys):
    """The PR-2 bug this PR's policy subsumes: with drain-only flushing,
    a sub-batch trickle sits in the queue forever under poll()."""
    _, pk, _, _ = keys
    server = _server(keys)                    # max_age_s=None
    _, c1 = _enc(pk, 5)
    _, c2 = _enc(pk, 6)
    server.submit_mul(c1, c2)
    for _ in range(5):
        assert server.poll() == []            # never served
    assert server.queue.depth == 1


def test_trickle_served_within_age_deadline_fake_clock(keys):
    """With max_age_s set, a lone request is flushed (padded) the moment
    its age crosses the deadline — deterministic via an injected clock."""
    _, pk, _, _ = keys
    now = [0.0]
    server = _server(keys, max_age_s=5.0, adaptive_target=False,
                     clock=lambda: now[0])
    _, c1 = _enc(pk, 5)
    _, c2 = _enc(pk, 6)
    rid = server.submit_mul(c1, c2)           # t_submit = 0.0
    assert server.poll() == []                # age 0 < 5: keep waiting
    now[0] = 4.9
    assert server.poll() == []                # still under the deadline
    now[0] = 5.0
    done = server.poll()                      # deadline hit: padded flush
    assert [r for r, _ in done] == [rid]
    s = server.stats()
    assert s["flushes"] == {"full": 0, "age": 1, "drain": 0}
    assert s["per_op"]["mul"]["pad_frac"] == 0.5
    # latency is measured on the same clock: submit 0.0 → complete 5.0
    assert s["per_op"]["mul"]["latency_ms"]["p50"] == pytest.approx(5000.0)


def test_queue_submit_stamps_with_injected_clock(keys):
    """Bugfix regression: RequestQueue.submit's default t_submit must
    come from the queue's (injected) clock, not a module-level time
    call — direct queue submits on a fake-clock server otherwise stamp
    wall-clock times and skew every age-based flush decision. Fails on
    the pre-PR code (t_submit was time.perf_counter())."""
    _, pk, _, _ = keys
    now = [123.0]
    server = _server(keys, clock=lambda: now[0])
    _, c1 = _enc(pk, 5)
    _, c2 = _enc(pk, 6)
    server.queue.submit("mul", (c1, c2))      # direct, no t_submit
    rid2 = server.submit_mul(c1, c2)          # via the server
    reqs = server.queue.pop_bucket(("mul", PARAMS.logQ, None), 4)
    assert [r.t_submit for r in reqs] == [123.0, 123.0]
    assert reqs[1].rid == rid2
    # a standalone queue with its own injected clock behaves the same
    q = RequestQueue(clock=lambda: 7.0)
    q.submit("mul", (c1, c2))
    assert q.pop_bucket(("mul", PARAMS.logQ, None), 1)[0].t_submit == 7.0


def test_arrival_rate_decays_after_idle_gap():
    """Bugfix regression (queue level): with `now` and a decay window,
    arrivals older than the window are dropped, so the estimate reflects
    current traffic; one in-window arrival reports the sparse floor."""
    q = RequestQueue()
    for i in range(64):
        q._arrivals.append(i * 0.5)           # 2/s burst ending at 31.5
    assert q.arrival_rate() == pytest.approx(2.0)
    # idle gap: at t=50 with a 16 s window the burst is stale
    assert q.arrival_rate(now=50.0, window_s=16.0) is None
    assert len(q._arrivals) == 0              # window physically decayed
    q._arrivals.append(50.0)
    assert q.arrival_rate(now=50.0, window_s=16.0) \
        == pytest.approx(1 / 16.0)            # sparse-traffic floor
    # two arrivals on one (coarse/fake) clock tick must still count —
    # span == 0 on the decayed path may not fall back to None, or the
    # target re-inflates to a full batch with MORE traffic evidence
    q._arrivals.append(50.0)
    assert q.arrival_rate(now=50.0, window_s=16.0) \
        == pytest.approx(2 / 16.0)
    q._arrivals.append(54.0)
    assert q.arrival_rate(now=54.0, window_s=16.0) == pytest.approx(0.5)


def test_post_idle_trickle_flushes_at_adapted_target(keys):
    """Bugfix regression (server level): after a burst and an idle gap,
    a trickle request must flush at the adapted target immediately —
    pre-PR the arrival window kept the burst forever, the target stayed
    inflated, and every post-idle trickle request waited the full
    max_age_s before the age deadline flushed it."""
    _, _, evk, rks = keys
    mesh = make_mesh((1, 1), ("data", "model"))
    now = [0.0]
    server = HEServer(PARAMS, evk, rks, mesh=mesh, batch=4,
                      max_age_s=2.0, clock=lambda: now[0])
    _, c1 = _enc(keys[1], 5)
    _, c2 = _enc(keys[1], 6)
    # burst: 64 requests at 2/s (span 31.5 s), all drained
    for i in range(64):
        now[0] = i * 0.5
        server.submit_mul(c1, c2)
    server.drain()
    server.reset_metrics()
    # idle gap, then a lone trickle request at t=50: the decayed rate
    # puts the target at 1, so it flushes on the next poll as "full" —
    # NOT after the 2 s age deadline
    now[0] = 50.0
    rid = server.submit_mul(c1, c2)
    assert server._bucket_target() == 1
    done = server.poll()
    assert [r for r, _ in done] == [rid]
    s = server.stats()
    assert s["flushes"]["age"] == 0
    # latency: served at submit time, not submit + max_age_s
    assert s["per_op"]["mul"]["latency_ms"]["max"] < 2000.0


def test_adaptive_bucket_target_flushes_below_batch(keys):
    """At a low observed arrival rate the full-bucket target shrinks to
    rate × max_age_s, so a bucket that will never fill stops waiting."""
    _, _, evk, rks = keys
    mesh = make_mesh((1, 1), ("data", "model"))
    now = [0.0]
    server = HEServer(PARAMS, evk, rks, mesh=mesh, batch=4,
                      max_age_s=2.0, clock=lambda: now[0])
    _, c1 = _enc(keys[1], 5)
    _, c2 = _enc(keys[1], 6)
    server.submit_mul(c1, c2)                 # t = 0
    now[0] = 1.0
    server.submit_mul(c1, c2)                 # t = 1 → rate 1/s
    # target = ceil(1/s × 2s) = 2 < batch=4: the 2-deep bucket is "full"
    assert server._bucket_target() == 2
    done = server.poll()
    assert len(done) == 2
    assert server.stats()["flushes"]["full"] == 1


# --------------------------------------------------------------------------
# double buffering: overlap mode stays bitwise and drains clean
# --------------------------------------------------------------------------

def test_overlap_drain_bitwise_and_clean(keys):
    """overlap=True returns results one poll late but drain() retires
    everything; outputs stay bitwise identical to core."""
    _, pk, evk, _ = keys
    server = _server(keys, overlap=True)
    cases = []
    for i in range(5):                        # 3 batches at batch=2 (pad 1)
        _, c1 = _enc(pk, 70 + 2 * i)
        _, c2 = _enc(pk, 71 + 2 * i)
        cases.append((server.submit_mul(c1, c2),
                      H.he_mul(c1, c2, evk, PARAMS)))
    res = server.drain()
    assert server._inflight is None
    assert len(res) == 5
    for rid, ref in cases:
        np.testing.assert_array_equal(np.asarray(res[rid].ax),
                                      np.asarray(ref.ax))
        np.testing.assert_array_equal(np.asarray(res[rid].bx),
                                      np.asarray(ref.bx))


# --------------------------------------------------------------------------
# metrics
# --------------------------------------------------------------------------

def test_metrics_roundtrip():
    m = ServeMetrics()
    m.record_depth(3)
    m.record_depth(1)
    m.record_batch("mul", 120, n_valid=3, n_pad=1, wall_s=0.5,
                   latencies_s=[0.1, 0.2, 0.3])
    m.record_batch("mul", 96, n_valid=4, n_pad=0, wall_s=0.5,
                   latencies_s=[0.4] * 4)
    m.record_batch("rotate", 120, n_valid=1, n_pad=3, wall_s=0.25,
                   latencies_s=[0.9])
    s = m.summary()
    mul = s["per_op"]["mul"]
    assert mul["batches"] == 2 and mul["requests"] == 7
    assert mul["ops_per_s"] == pytest.approx(7.0)
    assert mul["pad_frac"] == pytest.approx(1 / 8)
    assert mul["latency_ms"]["p50"] == pytest.approx(400.0)
    assert mul["latency_ms"]["p99"] <= mul["latency_ms"]["max"] == 400.0
    assert s["per_op"]["rotate"]["pad_frac"] == pytest.approx(0.75)
    assert s["levels_served"] == [96, 120]
    assert s["queue_depth"]["max"] == 3
    assert s["queue_depth"]["samples"] == 2


def test_server_stats_shape(keys):
    _, pk, _, _ = keys
    server = _server(keys)
    _, c1 = _enc(pk, 5)
    _, c2 = _enc(pk, 6)
    server.submit_mul(c1, c2)
    assert server.poll() == []                # batch=2 not yet full
    server.submit_mul(c1, c2)
    done = server.poll()                      # full bucket runs
    assert len(done) == 2
    st = server.stats()
    assert st["submitted"] == 2
    assert st["engine"]["steps_compiled"] == 1
    assert st["per_op"]["mul"]["pad_frac"] == 0.0


# --------------------------------------------------------------------------
# 8-device mesh parity (subprocess harness, as tests/test_dist.py)
# --------------------------------------------------------------------------

def test_hserve_ops_bitwise_on_8_device_mesh(run_in_8dev_subprocess):
    """Sharded hserve mul + rotate + conjugate + slot_sum — and the
    whole degree-4 submit_circuit chain (mul → rescale → mod-down →
    conjugate → add) — on a (2, 4) mesh are bitwise identical to the
    core references across the served levels."""
    res = run_in_8dev_subprocess("""
        from repro.core import heaan as H
        from repro.core import test_params
        from repro.core.keys import keygen
        from repro.core.rotate import conj_keygen, he_conjugate, \
            he_rotate, rot_keygen
        from repro.hserve import HEServer, slot_sum_rotations

        params = test_params(logN=5, beta_bits=32)
        sk, pk, evk = keygen(params, seed=0)
        rks = {r: rot_keygen(params, sk, r) for r in (1, 2, 4, 8)}
        ckey = conj_keygen(params, sk)
        mesh = make_mesh((2, 4), ("data", "model"))
        server = HEServer(params, evk, rks, ckey, mesh=mesh, batch=2)

        rng = np.random.default_rng(7)
        n = 16
        def enc(seed):
            z = rng.normal(size=n) + 1j * rng.normal(size=n)
            return H.encrypt_message(z, pk, params, seed=seed)

        logq2 = params.logQ - params.logp
        cases = []
        for i in range(2):                       # two mul levels
            c1, c2 = enc(10 + 2 * i), enc(11 + 2 * i)
            if i:
                c1 = H.he_mod_down(c1, params, logq2)
                c2 = H.he_mod_down(c2, params, logq2)
            cases.append((server.submit_mul(c1, c2),
                          H.he_mul(c1, c2, evk, params)))
        ct = enc(30)
        cases.append((server.submit_rotate(ct, 1),
                      he_rotate(ct, 1, rks[1], params)))
        low = H.he_mod_down(ct, params, logq2)
        cases.append((server.submit_rotate(low, 2),
                      he_rotate(low, 2, rks[2], params)))
        cases.append((server.submit_conjugate(ct),
                      he_conjugate(ct, ckey, params)))
        cs = enc(40)
        acc = cs
        for r in slot_sum_rotations(cs.n_slots):
            acc = H.he_add(acc, he_rotate(acc, r, rks[r], params))
        cases.append((server.submit_slot_sum(cs), acc))

        # plaintext-operand ops: region-1-only, sharded, bitwise
        zp = rng.normal(size=n) + 1j * rng.normal(size=n)
        pt = H.encode_plain(zp, params, params.logQ)
        cp = enc(45)
        cases.append((server.submit_mul_plain(cp, pt),
                      H.he_mul_plain(cp, pt, params)))
        cases.append((server.submit_add_plain(cp, pt),
                      H.he_add_plain(cp, pt, params)))

        # degree-4 polynomial circuit, wholly server-side on the mesh
        # (the same shared acceptance circuit serve --circuit runs)
        from repro.hserve import degree4_demo_circuit
        x = enc(50)
        ops, lq = degree4_demo_circuit(params)
        cid = server.submit_circuit(ops, inputs={"x": x})
        r0 = H.rescale(H.he_mul(x, x, evk, params), params)
        r1 = H.rescale(H.he_mul(r0, r0, evk, params), params)
        r2 = he_conjugate(H.he_mod_down(r1, params, lq), ckey, params)
        cases.append((cid, H.he_add(
            r2, H.he_mod_down(x, params, lq))))

        res = server.drain()

        # the SAME degree-4 circuit under the circuit-aware scheduler
        # (co-batch deferral + table prefetch) must be bitwise identical
        # to the unscheduled serve above — scheduling reorders flushes,
        # never results. Same warm server: no recompilation.
        server.schedule = True
        x2 = enc(51)
        cid2a = server.submit_circuit(ops, inputs={"x": x2})
        server.poll(flush=True)                  # desync the pair
        cid2b = server.submit_circuit(ops, inputs={"x": x2})
        res2 = server.drain()
        sr0 = H.rescale(H.he_mul(x2, x2, evk, params), params)
        sr1 = H.rescale(H.he_mul(sr0, sr0, evk, params), params)
        sr2 = he_conjugate(H.he_mod_down(sr1, params, lq), ckey, params)
        sref = H.he_add(sr2, H.he_mod_down(x2, params, lq))
        sched_ok = all(
            bool((np.asarray(res2[c].ax) == np.asarray(sref.ax)).all()
                 and (np.asarray(res2[c].bx) == np.asarray(sref.bx)).all())
            for c in (cid2a, cid2b))

        ok = all(
            bool((np.asarray(res[rid].ax) == np.asarray(ref.ax)).all()
                 and (np.asarray(res[rid].bx) == np.asarray(ref.bx)).all())
            for rid, ref in cases)
        st = server.stats()
        print(json.dumps({
            "ok": ok, "sched_ok": sched_ok,
            "cross_circuit": st["cobatch"]["cross_circuit_batches"],
            "devices": len(jax.devices()),
            "levels": st["levels_served"],
            "steps": st["engine"]["steps_compiled"]}))
    """)
    assert res["devices"] == 8
    assert res["steps"] >= 10
    assert len(res["levels"]) >= 3
    assert res["ok"], "sharded hserve op diverged from core reference"
    assert res["sched_ok"], \
        "scheduled circuit diverged from the unscheduled/core reference"
    assert res["cross_circuit"] > 0, \
        "staggered circuits never co-batched under the scheduler"
