"""repro.client tests: lazy tracing + constant folding, the compile
pass (auto level alignment, CSE, hand-written-circuit equivalence), the
server-side plaintext-operand cache, futures/co-batching, and the
(2, 4) 8-device mesh harness for the acceptance expression.

The acceptance contract (ISSUE 5): a traced expression using every op
(mul, mul_plain, add, rotate, conjugate, slot_sum) with NO explicit
rescale/mod_down compiles to a valid level-aligned circuit and decrypts
bitwise-identical to (1) the hand-written CircuitOp list and (2) the
composed core.heaan references, on the 1-device and 8-device harnesses.
"""

import numpy as np
import pytest

import jax

from repro.client import (
    CipherHandle, HESession, PlainHandle, compile_handle,
)
from repro.client.testing import random_expr
from repro.core import heaan as H
from repro.core import test_params as small_params
from repro.core.encoding import message_hash
from repro.core.rotate import conj_keygen, he_conjugate, he_rotate, \
    rot_keygen
from repro.hserve import CircuitOp, HEServer
from repro.hserve.circuit import execute_circuit_reference
from repro.launch.mesh import make_mesh

# logp=24 over logQ=120 leaves L=5: depth-2 traces keep two spare levels
PARAMS = small_params(logN=4, beta_bits=32, logQ=120, logp=24)


@pytest.fixture(scope="module")
def session():
    mesh = make_mesh((1, 1), ("data", "model"))
    return HESession(PARAMS, seed=0, mesh=mesh, batch=2)


@pytest.fixture(scope="module")
def galois(session):
    """Reference-side Galois keys — rot_keygen/conj_keygen are
    deterministic in (sk, r), so these are bit-identical to the keys
    HESession.ensure_keys loads into the server."""
    rks = {r: rot_keygen(PARAMS, session.sk, r) for r in (1, 2, 4)}
    return rks, conj_keygen(PARAMS, session.sk)


def _msg(seed, n=8, scale=0.5):
    rng = np.random.default_rng(seed)
    return scale * (rng.normal(size=n) + 1j * rng.normal(size=n))


def _bitwise(a, b):
    return bool((np.asarray(a.ax) == np.asarray(b.ax)).all()
                and (np.asarray(a.bx) == np.asarray(b.bx)).all())


# --------------------------------------------------------------------------
# tracing: laziness, folding, trace-time validation
# --------------------------------------------------------------------------

def test_trace_is_lazy_and_plain_arithmetic_folds(session):
    x = session.encrypt(_msg(1), seed=1)
    y = ((x * x) + x).rotate(1).conj().slot_sum() - 0.25
    assert isinstance(y, CipherHandle)
    assert session.server.queue.submitted == 0   # nothing reached the
    assert not session.server._circuits          # server while tracing
    # plain-plain arithmetic never traces: it folds eagerly in numpy
    p = (session.plain(2.0) + 1.0) * session.plain([1j] * 8)
    assert isinstance(p, PlainHandle)
    np.testing.assert_allclose(p.z, np.full(8, 3j))
    q = session.plain(np.arange(8.0)).rotate(2).conj().slot_sum()
    np.testing.assert_allclose(q.z, np.full(8, 28.0))


def test_trace_time_validation():
    mesh = make_mesh((1, 1), ("data", "model"))
    s1 = HESession(PARAMS, seed=0, mesh=mesh, batch=2)
    s2 = HESession(PARAMS, seed=1, mesh=mesh, batch=2)
    x1, x2 = s1.encrypt(_msg(1), seed=1), s2.encrypt(_msg(2), seed=2)
    with pytest.raises(ValueError, match="different sessions"):
        x1 * x2
    with pytest.raises(ValueError, match="positive left-rotation"):
        x1.rotate(0)
    with pytest.raises(ValueError, match="slots"):
        x1 + np.ones(4)                    # 4 plain slots vs 8
    with pytest.raises(TypeError, match="plain - cipher"):
        1.0 - x1
    with pytest.raises(ValueError, match="only input handles"):
        (x1 * x1).ciphertext


# --------------------------------------------------------------------------
# the compile pass: hand-written-circuit equivalence, CSE, alignment
# --------------------------------------------------------------------------

def _every_op_expr(x, w):
    """The acceptance expression: every traced op, no explicit level
    management anywhere."""
    return ((x * x) * w + x).rotate(1).conj().slot_sum()


def _every_op_shadow(z, w):
    return np.full(len(z), np.conj(np.roll(z * z * w + z, -1)).sum())


def test_compile_matches_hand_written_circuit(session):
    """The compiler must emit EXACTLY the CircuitOp list an expert would
    hand-write for the acceptance expression — rescale after each mul,
    one mod_down aligning x into the add, same bucket-relevant params."""
    z, w = _msg(3), _msg(4)
    x = session.encrypt(z, seed=3)
    cc = compile_handle(_every_op_expr(x, w), PARAMS)   # no cache lookup
    lq1 = PARAMS.logQ - PARAMS.logp                     # 96
    lq2 = lq1 - PARAMS.logp                             # 72
    hand = [
        CircuitOp("mul", ("in0", "in0")),
        CircuitOp("rescale", (0,), dlogp=PARAMS.logp),
        CircuitOp("mul_plain", (1,), pt_logp=PARAMS.log_delta,
                  pt_hash=message_hash(w, PARAMS.log_delta)),
        CircuitOp("rescale", (2,), dlogp=PARAMS.logp),
        CircuitOp("mod_down", ("in0",), logq2=lq2),
        CircuitOp("add", (3, 4)),
        CircuitOp("rotate", (5,), r=1),
        CircuitOp("conjugate", (6,)),
        CircuitOp("slot_sum", (7,)),
    ]
    assert cc.ops == hand          # pt is compare=False; pt_hash compares
    assert cc.ops[2].pt is not None
    assert (cc.out_logq, cc.out_logp) == (lq2, PARAMS.logp)
    assert ("evk",) in cc.requires and ("conj",) in cc.requires
    assert {("rot", 1), ("rot", 2), ("rot", 4)} <= cc.requires


def test_traced_bitwise_equals_hand_circuit_and_core(session, galois):
    """Acceptance: traced path == hand-submitted CircuitOp list ==
    composed core references, bitwise, and ≈ the plaintext shadow."""
    rks, ck = galois
    z, w = _msg(5), _msg(6)
    x = session.encrypt(z, seed=5)
    y = _every_op_expr(x, w)
    cc = compile_handle(y, PARAMS)          # materialized pts for the
    ref = execute_circuit_reference(        # reference + hand paths
        cc.ops, cc.inputs, PARAMS, evk=session.evk, rot_keys=rks,
        conj_key=ck)
    session.ensure_keys(cc.requires)
    hand_cid = session.server.submit_circuit(cc.ops, cc.inputs)
    (fut,) = session.run([y])               # co-batches with the hand one
    hand = session.drain()[hand_cid]
    traced = fut.result()
    assert _bitwise(traced, ref)
    assert _bitwise(traced, hand)
    got = session.decrypt(traced)
    np.testing.assert_allclose(got, _every_op_shadow(z, w), atol=1e-4)


def test_cse_dedupes_identical_subexpressions(session):
    x = session.encrypt(_msg(9), seed=9)
    y = (x * x) + (x * x)                  # distinct handles, same term
    cc = session.compile(y)
    assert [o.op for o in cc.ops] == ["mul", "rescale", "add"]
    assert cc.ops[2].args == (1, 1)
    # symmetric ops canonicalize operand order: x*y CSEs with y*x
    x2 = session.encrypt(_msg(10), seed=10)
    cc2 = session.compile((x * x2) + (x2 * x))
    assert [o.op for o in cc2.ops] == ["mul", "rescale", "add"]


def test_auto_mod_down_alignment_for_uneven_depths(session):
    """(x*x)*x: the second mul's operands live at different levels, so
    the compiler must mod_down x — verified structurally and by value."""
    z = _msg(11)
    x = session.encrypt(z, seed=11)
    cc = session.compile((x * x) * x)
    assert [o.op for o in cc.ops] == \
        ["mul", "rescale", "mod_down", "mul", "rescale"]
    assert cc.ops[2].args == ("in0",)
    got = session.decrypt((x * x) * x)
    np.testing.assert_allclose(got, z ** 3, atol=1e-4)


def test_level_alignment_for_sub(session):
    """sub of a deeper term against a shallow one: the compiler aligns
    levels with one mod_down on the shallow side (scales already match —
    the rescale-after-mul discipline keeps every scale at Δ)."""
    x = session.encrypt(_msg(12), seed=12)
    cc = session.compile((x * x) - x)
    ops = [o.op for o in cc.ops]
    assert ops == ["mul", "rescale", "mod_down", "sub"]
    assert cc.ops[3].args == (1, 2)        # sub is NOT re-ordered


def test_compile_rejects_over_deep_traces(session):
    x = session.encrypt(_msg(13), seed=13)
    y = x
    for _ in range(PARAMS.L):
        y = y * y
    with pytest.raises(ValueError, match="exhausts the modulus"):
        session.compile(y)


def test_run_is_atomic_on_compile_errors(session):
    """A compile error on ANY handle must leave zero circuits enqueued —
    otherwise earlier handles' futures are orphaned and their results
    unrecoverable."""
    x = session.encrypt(_msg(15), seed=15)
    too_deep = x
    for _ in range(PARAMS.L):
        too_deep = too_deep * too_deep
    before = session.server.queue.submitted
    with pytest.raises(ValueError, match="exhausts the modulus"):
        session.run([x * x, too_deep])
    assert session.server.queue.submitted == before
    assert not session.server._circuits
    assert not session._futures


def test_default_encrypt_seeds_are_fresh(session):
    """Two default-seeded encryptions must never share encryption
    randomness (identical ax would leak the message difference)."""
    z = _msg(16)
    c1 = session.encrypt(z).ciphertext
    c2 = session.encrypt(z).ciphertext
    assert not (np.asarray(c1.ax) == np.asarray(c2.ax)).all()


def test_rejected_plain_operand_does_not_poison_cache():
    """A pt that fails queue validation must NOT be registered — a
    later hash-only circuit would resolve the bad resident and fail
    mid-drain."""
    mesh = make_mesh((1, 1), ("data", "model"))
    server = HEServer(PARAMS, mesh=mesh, batch=2)
    s = HESession(PARAMS, seed=0, server=server)
    ct = s.encrypt(_msg(17), seed=17).ciphertext
    bad = np.zeros((4, 1), dtype=np.uint32)        # wrong shape
    with pytest.raises(ValueError, match="does not cover"):
        server.submit_mul_plain(ct, bad, pt_hash="h17")
    assert not server.cache.has_plain("h17", ct.logq)
    with pytest.raises(ValueError, match="no cached plaintext"):
        server.submit_circuit(
            [CircuitOp("mul_plain", ("x",), pt_logp=PARAMS.log_delta,
                       pt_hash="h17")], {"x": ct})


def test_run_submit_failure_leaves_results_recoverable():
    """If a LATER handle's submit fails (missing Galois key, pk-only
    session), already-enqueued circuits must not vanish into
    unreachable futures — their results come back from drain()."""
    mesh = make_mesh((1, 1), ("data", "model"))
    from repro.core.keys import keygen
    sk, pk, evk = keygen(PARAMS, seed=0)
    server = HEServer(PARAMS, evk, mesh=mesh, batch=2)
    s = HESession(PARAMS, sk=None, pk=pk, evk=evk, server=server)
    z = _msg(18)
    x = s.input(H.encrypt_message(z, pk, PARAMS, seed=18))
    with pytest.raises(KeyError):           # no rotation key, no sk
        s.run([x * x, x.rotate(1)])
    assert not s._futures                   # nothing orphaned
    raw = s.drain()                         # the x*x circuit completed
    (out,) = raw.values()
    ref = H.rescale(H.he_mul(x.ciphertext, x.ciphertext, evk, PARAMS),
                    PARAMS)
    assert _bitwise(out, ref)


def test_duplicate_plain_operand_encodes_once_per_trace(session):
    """One weight vector applied to several ciphertexts in ONE trace
    carries exactly one materialized encoding; repeats ship hash-only
    (the lower-index node registers at submission, before later nodes
    resolve)."""
    w = _msg(19)
    x1 = session.encrypt(_msg(80), seed=80)
    x2 = session.encrypt(_msg(81), seed=81)
    cc = compile_handle((x1 * w) + (x2 * w), PARAMS)
    plains = [(i, o) for i, o in enumerate(cc.ops)
              if o.op == "mul_plain"]
    assert len(plains) == 2
    assert sum(o.pt is not None for _, o in plains) == 1
    assert plains[0][1].pt is not None      # lowest index materializes
    assert len({o.pt_hash for _, o in plains}) == 1
    # and it serves correctly end to end
    got = session.run([(x1 * w) + (x2 * w)])[0].result()
    assert got is not None


def test_plain_cache_lru_eviction():
    """The plaintext cache is LRU-bounded: one-shot operands age out,
    counters record evictions, and re-registering is legal."""
    from repro.hserve import TableCache
    entry_bytes = np.zeros(
        (PARAMS.N, PARAMS.qlimbs(PARAMS.logQ)), np.uint32).nbytes
    cache = TableCache(PARAMS,
                       plain_cache_mib=2.5 * entry_bytes / 2**20)
    pts = [np.full((PARAMS.N, PARAMS.qlimbs(PARAMS.logQ)), i,
                   np.uint32) for i in range(3)]
    for i, pt in enumerate(pts):
        cache.put_plain(f"h{i}", PARAMS.logQ, pt)
    st = cache.stats()
    assert st["plain_evictions"] == 1
    assert st["plain_entries"] == 2
    assert not cache.has_plain("h0", PARAMS.logQ)   # oldest evicted
    with pytest.raises(KeyError):
        cache.get_plain("h0", PARAMS.logQ)
    cache.put_plain("h0", PARAMS.logQ, pts[0])      # re-register OK
    assert cache.has_plain("h0", PARAMS.logQ)       # (evicting h1)
    assert not cache.has_plain("h1", PARAMS.logQ)
    # LRU, not FIFO: touching h2 makes h0 the next victim
    cache.get_plain("h2", PARAMS.logQ)
    cache.put_plain("h3", PARAMS.logQ, pts[0])
    assert cache.has_plain("h2", PARAMS.logQ)
    assert not cache.has_plain("h0", PARAMS.logQ)


def test_run_rematerializes_after_lru_eviction_race():
    """A sibling's registration inside one run() can evict the entry a
    later handle compiled hash-only against; run() must re-materialize
    and serve correctly instead of raising."""
    mesh = make_mesh((1, 1), ("data", "model"))
    entry_mib = np.zeros(
        (PARAMS.N, PARAMS.qlimbs(PARAMS.logQ)), np.uint32).nbytes / 2**20
    server = HEServer(PARAMS, mesh=mesh, batch=2,
                      plain_cache_mib=1.5 * entry_mib)
    s = HESession(PARAMS, seed=0, server=server)
    z, w1, w2 = _msg(82), _msg(83), _msg(84)
    x = s.encrypt(z, seed=82)
    s.run([x * w1])[0].result()             # w1 cached
    f2, f1 = s.run([x * w2, x * w1])        # w2's registration evicts w1
    got1 = f1.result()
    ref = H.rescale(H.he_mul_plain(
        x.ciphertext, np.asarray(H.encode_plain(w1, PARAMS,
                                                x.ciphertext.logq)),
        PARAMS), PARAMS)
    assert _bitwise(got1, ref)
    assert f2.done()


def test_bare_input_needs_no_round_trip(session):
    x = session.encrypt(_msg(14), seed=14)
    (fut,) = session.run([x])
    assert fut.done() and fut.result() is x.ciphertext
    assert session.server.queue.depth == 0


# --------------------------------------------------------------------------
# the server-side plaintext-operand cache
# --------------------------------------------------------------------------

def test_plain_cache_hits_across_requests():
    """Affine-layer contract: the same weights at the same level encode
    and ship ONCE — the second traced run compiles to hash-only nodes
    and the server serves the operand from its (hash, level) cache."""
    mesh = make_mesh((1, 1), ("data", "model"))
    s = HESession(PARAMS, seed=0, mesh=mesh, batch=2)
    w = _msg(20)
    for i, expected_pt in ((0, True), (1, False)):
        x = s.encrypt(_msg(21 + i), seed=21 + i)
        cc = s.compile(x * w)
        assert (cc.ops[0].pt is not None) == expected_pt
        s.run([x * w])
    s.drain()
    st = s.stats()["cache"]
    assert st["plain_entries"] == 1
    assert st["plain_misses"] == 1
    assert st["plain_hits"] >= 1


def test_plain_cache_standalone_submit_and_unknown_hash():
    mesh = make_mesh((1, 1), ("data", "model"))
    server = HEServer(PARAMS, mesh=mesh, batch=2)   # keyless: plain ops only
    s = HESession(PARAMS, seed=0, server=server)
    ct = s.encrypt(_msg(30), seed=30).ciphertext
    w = _msg(31)
    pt = H.encode_plain(w, PARAMS, ct.logq)
    h = message_hash(w, PARAMS.log_delta)
    r1 = server.submit_mul_plain(ct, pt, pt_hash=h)      # registers
    r2 = server.submit_mul_plain(ct, pt_hash=h)          # hash-only hit
    res = server.drain()
    assert _bitwise(res[r1], res[r2])
    assert server.cache.stats()["plain_hits"] == 1
    with pytest.raises(KeyError, match="no cached plaintext"):
        server.submit_mul_plain(ct, pt_hash="deadbeef")
    # a circuit referencing an unknown hash rejects BEFORE enqueue
    with pytest.raises(ValueError, match="no cached plaintext"):
        server.submit_circuit(
            [CircuitOp("mul_plain", ("x",), pt_logp=PARAMS.log_delta,
                       pt_hash="deadbeef")], {"x": ct})
    assert server.queue.depth == 0
    # ... and the same hash at a DIFFERENT level is a different entry
    low = H.he_mod_down(ct, PARAMS, ct.logq - PARAMS.logp)
    with pytest.raises(ValueError, match="no cached plaintext"):
        server.submit_circuit(
            [CircuitOp("mul_plain", ("x",), pt_logp=PARAMS.log_delta,
                       pt_hash=h)], {"x": low})


def test_plain_cache_bitwise_vs_core():
    """A cache-served mul_plain is bitwise the core reference (the
    cached buffer IS the encoding the client would have sent)."""
    mesh = make_mesh((1, 1), ("data", "model"))
    s = HESession(PARAMS, seed=0, mesh=mesh, batch=2)
    z, w = _msg(32), _msg(33)
    x = s.encrypt(z, seed=32)
    first = (x * w).result()                 # registers the operand
    second = (x * w).result()                # served from the cache
    pt = H.encode_plain(w, PARAMS, x.ciphertext.logq)
    ref = H.rescale(H.he_mul_plain(x.ciphertext, pt, PARAMS), PARAMS)
    assert _bitwise(first, ref) and _bitwise(second, ref)


# --------------------------------------------------------------------------
# futures and co-batching
# --------------------------------------------------------------------------

def test_futures_cobatch_in_one_drain(session):
    """run([...]) submits without draining: two same-shape circuits
    co-batch node-for-node (batch=2 → zero padded lanes for mul)."""
    session.server.reset_metrics()
    z1, z2, w = _msg(40), _msg(41), _msg(42)
    x1 = session.encrypt(z1, seed=40)
    x2 = session.encrypt(z2, seed=41)
    f1, f2 = session.run([_every_op_expr(x1, w), _every_op_expr(x2, w)])
    assert not f1.done() and not f2.done()
    r1 = f1.result()                        # one drain resolves both
    assert f2.done()
    np.testing.assert_allclose(session.decrypt(r1),
                               _every_op_shadow(z1, w), atol=1e-4)
    np.testing.assert_allclose(f2.decrypt(),
                               _every_op_shadow(z2, w), atol=1e-4)
    st = session.stats()
    assert st["per_op"]["mul"]["pad_frac"] == 0.0
    assert st["cobatch"]["cross_circuit_batches"] > 0


def test_future_triggered_drain_buffers_raw_results(session):
    """A fut.result() that drains internally must NOT lose raw
    server-submit results — they stay buffered for the next explicit
    session.drain()."""
    z1, z2 = _msg(70), _msg(71)
    c1 = session.encrypt(z1, seed=70).ciphertext
    c2 = session.encrypt(z2, seed=71).ciphertext
    rid = session.server.submit_mul(c1, c2)
    x = session.encrypt(z1, seed=72)
    (fut,) = session.run([x * x])
    out = fut.result()                      # drains; raw result buffered
    assert out is not None
    raw = session.drain()
    assert rid in raw
    assert _bitwise(raw[rid], H.he_mul(c1, c2, session.evk, PARAMS))


def test_explicit_server_loads_passed_galois_keys():
    """rot_keys/conj_key passed alongside server= must load into that
    server's cache (a pk-only session cannot regenerate them)."""
    mesh = make_mesh((1, 1), ("data", "model"))
    from repro.core.keys import keygen
    sk, pk, evk = keygen(PARAMS, seed=0)
    server = HEServer(PARAMS, evk, mesh=mesh, batch=2)
    rk = rot_keygen(PARAMS, sk, 1)
    ck = conj_keygen(PARAMS, sk)
    s = HESession(PARAMS, sk=None, pk=pk, evk=evk,
                  rot_keys={1: rk}, conj_key=ck, server=server)
    assert server.cache.rotation_amounts == [1]
    assert server.cache.has_conj_key
    z = _msg(73)
    x = s.input(H.encrypt_message(z, pk, PARAMS, seed=73))
    got = x.rotate(1).conj().result()       # no sk: keys must be loaded
    ref = he_conjugate(he_rotate(x.ciphertext, 1, rk, PARAMS), ck, PARAMS)
    assert _bitwise(got, ref)


def test_plain_cache_resident_is_read_only_and_aliased():
    """Cache-resolved operands alias the read-only resident buffer (no
    per-request copy) while caller-provided arrays are still copied."""
    mesh = make_mesh((1, 1), ("data", "model"))
    server = HEServer(PARAMS, mesh=mesh, batch=2)
    s = HESession(PARAMS, seed=0, server=server)
    ct = s.encrypt(_msg(74), seed=74).ciphertext
    w = _msg(75)
    # np.array: a WRITEABLE caller buffer (np.asarray of a jax array is
    # read-only), so the anti-aliasing copy path is what's exercised
    pt = np.array(H.encode_plain(w, PARAMS, ct.logq))
    h = message_hash(w, PARAMS.log_delta)
    server.submit_mul_plain(ct, pt, pt_hash=h)
    resident = server.cache.get_plain(h, ct.logq)
    assert not resident.flags.writeable
    rid = server.submit_mul_plain(ct, pt_hash=h)
    req = next(r for d in server.queue._buckets.values() for r in d
               if r.rid == rid)
    assert not req.pt.flags.writeable       # aliased, not re-copied
    assert np.shares_memory(req.pt, resident)
    # mutating the original caller buffer must not reach queued requests
    pt[0, 0] += 1
    res = server.drain()
    ref = H.he_mul_plain(ct, np.asarray(
        H.encode_plain(w, PARAMS, ct.logq)), PARAMS)
    assert _bitwise(res[rid], ref)


def test_random_traced_exprs_bitwise_vs_reference(session, galois):
    """Seeded random-walk traces (every op kind reachable) through the
    REAL server: bitwise == the composed core references on the same
    compiled circuit, and ≈ the plaintext shadow."""
    rks, ck = galois
    for seed in (0, 1, 2):
        rng = np.random.default_rng(seed)
        z1, z2 = _msg(50 + seed), _msg(60 + seed)
        leaves = [(session.encrypt(z1, seed=50 + seed), z1),
                  (session.encrypt(z2, seed=60 + seed), z2)]
        y, shadow = random_expr(rng, leaves, n_ops=4, max_depth=2)
        cc = compile_handle(y, PARAMS)
        ref = execute_circuit_reference(
            cc.ops, cc.inputs, PARAMS, evk=session.evk, rot_keys=rks,
            conj_key=ck)
        got = session.run([y])[0].result()
        assert _bitwise(got, ref), f"seed {seed} diverged from core"
        tol = 1e-3 * max(1.0, float(np.abs(shadow).max()))
        np.testing.assert_allclose(session.decrypt(got), shadow,
                                   atol=tol)


# --------------------------------------------------------------------------
# 8-device mesh harness (subprocess, as tests/test_hserve.py)
# --------------------------------------------------------------------------

def test_traced_client_bitwise_on_8_device_mesh(run_in_8dev_subprocess):
    """The acceptance expression AND seeded random traces, served by an
    HESession on a (2, 4) mesh: bitwise == composed core references,
    ≈ shadows, with a plaintext-cache hit on the repeated run."""
    res = run_in_8dev_subprocess("""
        from repro.client import HESession, compile_handle
        from repro.client.testing import random_expr
        from repro.core import test_params
        from repro.core.rotate import conj_keygen, rot_keygen
        from repro.hserve.circuit import execute_circuit_reference

        params = test_params(logN=5, beta_bits=32)
        mesh = make_mesh((2, 4), ("data", "model"))
        session = HESession(params, seed=0, mesh=mesh, batch=2)
        rks = {r: rot_keygen(params, session.sk, r) for r in (1, 2, 4, 8)}
        ck = conj_keygen(params, session.sk)
        n = params.n_slots_max

        def msg(seed):
            r = np.random.default_rng(seed)
            return 0.4 * (r.normal(size=n) + 1j * r.normal(size=n))

        checks, errs = [], []
        def run_one(y, shadow):
            cc = compile_handle(y, params)
            ref = execute_circuit_reference(
                cc.ops, cc.inputs, params, evk=session.evk,
                rot_keys=rks, conj_key=ck)
            got = session.run([y])[0].result()
            checks.append(bool(
                (np.asarray(got.ax) == np.asarray(ref.ax)).all()
                and (np.asarray(got.bx) == np.asarray(ref.bx)).all()))
            errs.append(float(np.abs(session.decrypt(got)
                                     - shadow).max()))

        # acceptance: every op, no explicit level management — TWICE
        # with the same weights (second run hits the plaintext cache)
        z, w = msg(1), msg(2)
        for seed in (1, 3):
            x = session.encrypt(z, seed=seed)
            run_one(((x * x) * w + x).rotate(1).conj().slot_sum(),
                    np.full(n, np.conj(np.roll(z * z * w + z,
                                               -1)).sum()))

        for seed in (0, 1):
            rng = np.random.default_rng(seed)
            z1, z2 = msg(10 + seed), msg(20 + seed)
            leaves = [(session.encrypt(z1, seed=10 + seed), z1),
                      (session.encrypt(z2, seed=20 + seed), z2)]
            y, shadow = random_expr(rng, leaves, n_ops=3, max_depth=1)
            run_one(y, shadow)

        st = session.stats()
        print(json.dumps({
            "ok": all(checks), "max_err": max(errs),
            "devices": len(jax.devices()),
            "plain_hits": st["cache"]["plain_hits"],
            "levels": st["levels_served"]}))
    """)
    assert res["devices"] == 8
    assert res["ok"], "traced client diverged from core on the 8-dev mesh"
    assert res["max_err"] < 1e-2
    assert res["plain_hits"] >= 1, "repeated weights never hit the cache"
    assert len(res["levels"]) >= 2
