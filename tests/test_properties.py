"""Hypothesis property tests on system invariants (deliverable c)."""

import numpy as np
import pytest

import jax.numpy as jnp

pytest.importorskip("hypothesis", reason="property tests need hypothesis "
                    "(pip install -e .[dev])")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core import test_params as small_params
from repro.core import make_context
from repro.core import rns
from repro.core.context import build_global_tables
from repro.nt.residue import limbs_to_int
from repro.launch.mesh import make_mesh


PARAMS = small_params(logN=4, beta_bits=32)
# the traced-client property tests: logp=24 over logQ=120 leaves L=5,
# so depth-2 random traces keep two spare levels
TRACE_PARAMS = small_params(logN=4, beta_bits=32, logQ=120, logp=24)
CTX = make_context(PARAMS, PARAMS.logQ)
G = build_global_tables(PARAMS)


@given(st.lists(st.integers(min_value=-(2**100), max_value=2**100),
                min_size=16, max_size=16))
@settings(max_examples=20, deadline=None)
def test_to_eval_from_eval_roundtrip_is_centered_identity(vals):
    """from_eval(to_eval(x)) == x for any |x| < min(P/2, 2^(K·β-1))."""
    npn = CTX.np1
    K = CTX.qlimbs
    lim = min(CTX.icrt1.P_int // 2, 1 << (K * 32 - 2)) - 1
    vals = [max(-lim, min(lim, v)) for v in vals]
    enc = np.zeros((16, K), dtype=np.uint32)
    for i, v in enumerate(vals):
        vv = v % (1 << (K * 32))
        for k in range(K):
            enc[i, k] = (vv >> (32 * k)) & 0xFFFFFFFF
    ev = rns.to_eval(jnp.asarray(enc), npn, G)
    back = rns.from_eval(ev, PARAMS, K, G)
    W = 1 << (K * 32)
    for i, v in enumerate(vals):
        got = limbs_to_int(np.asarray(back[i]), 32)
        if got >= W // 2:
            got -= W
        assert got == v, (i, got, v)


@given(st.integers(min_value=0, max_value=2**120 - 1),
       st.integers(min_value=0, max_value=2**120 - 1))
@settings(max_examples=20, deadline=None)
def test_poly_mul_degree0_matches_int_mul(a, b):
    """Multiplying constant polynomials == BigInt multiplication mod q."""
    K = PARAMS.qlimbs(PARAMS.logQ)
    N = PARAMS.N

    def enc(v):
        out = np.zeros((N, K), dtype=np.uint32)
        for k in range(K):
            out[0, k] = (v >> (32 * k)) & 0xFFFFFFFF
        return jnp.asarray(out)

    prod = rns.poly_mul(enc(a), enc(b), 120, 120, PARAMS, G,
                        PARAMS.limbs_for_bits(242))
    got = limbs_to_int(np.asarray(prod[0]), 32)
    W = 1 << (PARAMS.limbs_for_bits(242) * 32)
    if got >= W // 2:
        got -= W
    assert got == a * b
    # every other coefficient must be exactly zero
    rest = np.asarray(prod[1:])
    assert (rest == 0).all()


@given(st.lists(st.integers(min_value=0, max_value=2**119), min_size=2,
                max_size=2))
@settings(max_examples=10, deadline=None)
def test_eval_domain_add_is_homomorphic(pair):
    """to_eval(x) ⊕ to_eval(y) == to_eval(x + y mod q) (RNS congruence)."""
    from repro.core import bigint
    a, b = pair
    K = CTX.qlimbs
    npn = CTX.np1

    def enc(v):
        out = np.zeros((PARAMS.N, K), dtype=np.uint32)
        rngv = v
        for k in range(K):
            out[0, k] = (rngv >> (32 * k)) & 0xFFFFFFFF
        return jnp.asarray(out)

    ea = rns.to_eval(enc(a), npn, G)
    eb = rns.to_eval(enc(b), npn, G)
    s_limbs = bigint.mask_bits(bigint.add(enc(a), enc(b)), PARAMS.logQ)
    lhs = rns.eval_add(ea, eb, G)
    rhs = rns.to_eval(s_limbs, npn, G)
    # additive homomorphism holds exactly when no q-overflow occurred
    if a + b < (1 << PARAMS.logQ):
        np.testing.assert_array_equal(np.asarray(lhs), np.asarray(rhs))


# --------------------------------------------------------------------------
# circuit-aware scheduler invariants (repro.hserve): co-batching never
# merges bucket keys, and per-circuit execution order stays topological
# --------------------------------------------------------------------------

def _fake_hserver(schedule: bool, batch: int):
    """A real HEServer whose OpEngine is replaced by a metadata-faithful
    fake: outputs are zero ciphertexts with each op's (logq, logp) rules
    applied, so queue + scheduler + server logic runs EXACTLY as in
    production with no jit compiles. The fake asserts the co-batch
    invariant (one bucket key per dispatched batch) and logs execution
    order as (cid, node) tags."""
    import jax as _jax

    from repro.core.cipher import Ciphertext
    from repro.core.keys import keygen
    from repro.core.rotate import conj_keygen
    from repro.hserve import HEServer, Inflight

    if not hasattr(_fake_hserver, "_keys"):
        sk, pk, evk = keygen(PARAMS, seed=0)
        _fake_hserver._keys = (sk, pk, evk, conj_keygen(PARAMS, sk))
    sk, pk, evk, ck = _fake_hserver._keys
    mesh = make_mesh((1, 1), ("data", "model"))
    server = HEServer(PARAMS, evk, None, ck, mesh=mesh, batch=batch,
                      schedule=schedule, prefetch=False)

    class FakeEngine:
        n_compiled = 0
        compile_s = 0.0

        def __init__(self):
            self.batches = []        # [(key, [tag-or-None, ...])]

        def dispatch(self, b):
            assert all(r.bucket_key == b.key for r in b.requests), \
                "co-batching merged requests with different bucket keys"
            return Inflight(batch=b, ax=None, bx=None, t0=0.0)

        def block(self, inf):
            return 0.0

        def wait(self, inf):
            b = inf.batch
            # the rid->node map is popped in _complete, AFTER wait
            self.batches.append(
                (b.key, [server._node_of_rid.get(r.rid)
                         for r in b.requests]))
            outs = []
            for r in b.requests:
                c0 = r.cts[0]
                logq, logp = c0.logq, c0.logp
                if r.op == "mul":
                    logp += r.cts[1].logp
                elif r.op == "mul_plain":
                    logp += r.pt_logp
                elif r.op == "rescale":
                    logq, logp = logq - r.dlogp, logp - r.dlogp
                elif r.op == "mod_down":
                    logq = r.logq2
                z = jnp.zeros((PARAMS.N, PARAMS.qlimbs(logq)),
                              dtype=np.uint32)
                outs.append(Ciphertext(ax=z, bx=z, logq=logq, logp=logp,
                                       n_slots=c0.n_slots))
            return outs, 0.0

    server.engine = FakeEngine()
    return server, pk


_CHAIN_OPS = st.lists(st.sampled_from(["mul", "rescale", "mod_down",
                                       "conjugate", "mul_plain"]),
                      min_size=1, max_size=6)


def _build_chain(chain, z, pt_top):
    """Lower a random op-kind chain to a level-legal CircuitOp list
    (level-changing ops degrade to conjugate at the modulus floor;
    plaintext operands are encoded once per level into `pt_top`)."""
    from repro.core import heaan as H
    from repro.hserve import CircuitOp

    ops, logq = [], PARAMS.logQ
    for kind in chain:
        prev = len(ops) - 1 if ops else "x"
        if kind == "rescale" and logq - PARAMS.logp <= 0:
            kind = "conjugate"
        if kind == "mod_down" and logq - PARAMS.logp <= 0:
            kind = "conjugate"
        if kind == "mul":
            ops.append(CircuitOp("mul", (prev, prev)))
        elif kind == "mul_plain":
            if logq not in pt_top:
                pt_top[logq] = H.encode_plain(z, PARAMS, logq)
            ops.append(CircuitOp("mul_plain", (prev,),
                                 pt=pt_top[logq]))
        elif kind == "rescale":
            ops.append(CircuitOp("rescale", (prev,)))
            logq -= PARAMS.logp
        elif kind == "mod_down":
            ops.append(CircuitOp("mod_down", (prev,),
                                 logq2=logq - PARAMS.logp))
            logq -= PARAMS.logp
        else:
            ops.append(CircuitOp("conjugate", (prev,)))
    return ops


@given(chains=st.lists(_CHAIN_OPS, min_size=2, max_size=4),
       staggers=st.lists(st.integers(min_value=0, max_value=2),
                         min_size=2, max_size=4),
       batch=st.integers(min_value=2, max_value=4),
       schedule=st.booleans())
@settings(max_examples=20, deadline=None)
def test_scheduler_never_merges_keys_and_preserves_topo_order(
        chains, staggers, batch, schedule):
    """For random circuit chains submitted with random stagger, under
    both flush policies: (a) every dispatched batch holds ONE bucket
    key, (b) each circuit's nodes execute in topological order, and
    (c) drain() terminates with every circuit completed (the scheduler's
    progress guarantee — a deferral policy without it deadlocks on
    same-key parent/child chains)."""
    from repro.core import heaan as H

    server, pk = _fake_hserver(schedule, batch)
    rng = np.random.default_rng(0)
    z = rng.normal(size=8) + 1j * rng.normal(size=8)
    x = H.encrypt_message(z, pk, PARAMS, seed=1)
    pt_top = {}

    cids, results, built = [], {}, {}
    for chain, stagger in zip(chains, staggers):
        ops = _build_chain(chain, z, pt_top)
        cid = server.submit_circuit(ops, {"x": x})
        cids.append(cid)
        built[cid] = ops
        for _ in range(stagger):
            results.update(dict(server.poll(flush=True)))
    # bounded drain: a deadlock shows as exhaustion, not a hang
    for _ in range(300):
        if not (server.queue.depth or server._inflight is not None
                or server._circuits):
            break
        results.update(dict(server.poll(flush=True)))
    assert not server._circuits, "drain did not complete every circuit"
    assert server.queue.depth == 0
    assert all(cid in results for cid in cids)
    # per-circuit topological order over the logged execution tags
    done = [t for _key, tags in server.engine.batches
            for t in tags if t is not None]
    pos = {t: i for i, t in enumerate(done)}
    for cid, ops in built.items():
        for i, node in enumerate(ops):
            if (cid, i) not in pos:
                continue                  # padded-out / never-needed
            for a in node.args:
                if isinstance(a, int):
                    assert (cid, a) in pos, \
                        f"node ({cid},{i}) ran but its arg {a} never did"
                    assert pos[(cid, a)] < pos[(cid, i)], \
                        f"node ({cid},{i}) ran before its arg {a}"


# --------------------------------------------------------------------------
# repro.client compile pass (ISSUE 5): a RANDOM traced expression — every
# op kind reachable, no explicit rescale/mod_down anywhere — compiles to a
# level-aligned circuit that (a) the real server serves bitwise-identical
# to the composed core.heaan references run over the same CircuitOp list,
# and (b) decrypts to the plaintext shadow of the traced arithmetic
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def trace_session():
    """One warm HESession + reference-side Galois keys (deterministic in
    sk, so bit-identical to what auto_keys loads into the server)."""
    import jax

    from repro.client import HESession
    from repro.core.rotate import conj_keygen, rot_keygen

    mesh = make_mesh((1, 1), ("data", "model"))
    s = HESession(TRACE_PARAMS, seed=0, mesh=mesh, batch=2)
    rks = {r: rot_keygen(TRACE_PARAMS, s.sk, r) for r in (1, 2, 4)}
    return s, rks, conj_keygen(TRACE_PARAMS, s.sk)


@given(seed=st.integers(min_value=0, max_value=2**31 - 1),
       n_ops=st.integers(min_value=1, max_value=4))
@settings(max_examples=8, deadline=None)
def test_random_traced_expr_bitwise_vs_core_and_shadow(
        trace_session, seed, n_ops):
    from repro.client import compile_handle
    from repro.client.testing import random_expr
    from repro.hserve.circuit import execute_circuit_reference

    session, rks, ck = trace_session
    rng = np.random.default_rng(seed)
    n = TRACE_PARAMS.n_slots_max
    zs = [0.5 * (rng.normal(size=n) + 1j * rng.normal(size=n))
          for _ in range(2)]
    leaves = [(session.encrypt(z, seed=1000 + seed + i), z)
              for i, z in enumerate(zs)]
    y, shadow = random_expr(rng, leaves, n_ops=n_ops, max_depth=2)
    cc = compile_handle(y, TRACE_PARAMS)      # materialized operands
    ref = execute_circuit_reference(
        cc.ops, cc.inputs, TRACE_PARAMS, evk=session.evk, rot_keys=rks,
        conj_key=ck)
    got = session.run([y])[0].result()
    assert bool((np.asarray(got.ax) == np.asarray(ref.ax)).all()
                and (np.asarray(got.bx) == np.asarray(ref.bx)).all()), \
        "traced serving diverged from the composed core reference"
    tol = 1e-3 * max(1.0, float(np.abs(shadow).max()))
    np.testing.assert_allclose(session.decrypt(got), shadow, atol=tol)


# --------------------------------------------------------------------------
# multi-host frontend (ISSUE 8): random circuits through an HEFrontend
# with K in [1, 4] metadata-faithful fake workers under random
# worker-death schedules — co-batching stays key-pure on every worker,
# each node is DELIVERED exactly once (re-executions match the requeue
# counter exactly), per-circuit topological order holds across the whole
# fleet, and the bounded drain terminates
# --------------------------------------------------------------------------

def _fake_frontend(workers, batch, schedule, injector, log):
    """A real HEFrontend over in-process workers whose OpEngines are
    replaced by the same metadata-faithful fake as `_fake_hserver` —
    queue, scheduler, routing, transport framing, death/requeue, and
    request rebuild on the worker side all run EXACTLY as in
    production, with no jit. Executions append (wid, key, [rid]) to
    `log`."""
    import jax as _jax

    from repro.core.cipher import Ciphertext
    from repro.core.keys import keygen
    from repro.core.rotate import conj_keygen
    from repro.hserve.frontend import HEFrontend

    if not hasattr(_fake_hserver, "_keys"):
        sk, pk, evk = keygen(PARAMS, seed=0)
        _fake_hserver._keys = (sk, pk, evk, conj_keygen(PARAMS, sk))
    sk, pk, evk, ck = _fake_hserver._keys
    mesh = make_mesh((1, 1), ("data", "model"))
    fe = HEFrontend(PARAMS, evk, None, ck, mesh=mesh, batch=batch,
                    workers=workers, schedule=schedule,
                    injector=injector)

    class FakeWorkerEngine:
        n_compiled = 0
        compile_s = 0.0

        def __init__(self, wid):
            self.wid = wid

        def dispatch(self, b):
            assert all(r.bucket_key == b.key for r in b.requests), \
                "co-batching merged requests with different bucket keys"
            return b

        def wait(self, b):
            log.append((self.wid, b.key,
                        [r.rid for r in b.requests]))
            outs = []
            for r in b.requests:
                c0 = r.cts[0]
                logq, logp = c0.logq, c0.logp
                if r.op == "mul":
                    logp += r.cts[1].logp
                elif r.op == "mul_plain":
                    logp += r.pt_logp
                elif r.op == "rescale":
                    logq, logp = logq - r.dlogp, logp - r.dlogp
                elif r.op == "mod_down":
                    logq = r.logq2
                z = np.zeros((PARAMS.N, PARAMS.qlimbs(logq)),
                             dtype=np.uint32)
                outs.append(Ciphertext(ax=z, bx=z, logq=logq, logp=logp,
                                       n_slots=c0.n_slots))
            return outs, 0.0

    for w in fe.workers:
        w.transport.worker.engine = FakeWorkerEngine(w.wid)
    return fe, pk


@given(chains=st.lists(_CHAIN_OPS, min_size=2, max_size=4),
       workers=st.integers(min_value=1, max_value=4),
       batch=st.integers(min_value=2, max_value=3),
       schedule=st.booleans(),
       kills=st.lists(st.tuples(st.integers(min_value=0, max_value=3),
                                st.integers(min_value=1, max_value=3)),
                      max_size=2))
@settings(max_examples=15, deadline=None)
def test_multihost_serves_every_node_once_in_topo_order_under_deaths(
        chains, workers, batch, schedule, kills):
    """Random circuits through the multi-host frontend with a random
    worker count and a random kill schedule (always leaving >= 1
    survivor): (a) every dispatched batch reaching ANY worker holds one
    bucket key, (b) each circuit node is delivered exactly once — the
    only re-executions are the requeued in-flight requests of dead
    workers, counted exactly by the requeue counter, (c) first-execution
    order respects every circuit's topology even when nodes of one
    circuit land on different workers, and (d) the bounded drain
    completes every circuit."""
    from repro.core import heaan as H
    from repro.runtime.failures import FailureInjector

    # at most workers-1 distinct victims, so routing always has a
    # survivor (all-dead drain is a separate typed-error test)
    sched = {}
    for wid_raw, after in kills:
        wid = wid_raw % workers
        if wid not in sched and len(sched) < workers - 1:
            sched[wid] = after
    injector = FailureInjector(kill_worker_at=sched) if sched else None

    log = []
    fe, pk = _fake_frontend(workers, batch, schedule, injector, log)
    rng = np.random.default_rng(0)
    z = rng.normal(size=8) + 1j * rng.normal(size=8)
    x = H.encrypt_message(z, pk, PARAMS, seed=1)
    pt_top = {}
    cids, built, results, tags = [], {}, {}, {}
    for chain in chains:
        ops = _build_chain(chain, z, pt_top)
        cid = fe.submit_circuit(ops, {"x": x})
        cids.append(cid)
        built[cid] = ops
    # bounded drain, snapshotting the rid->node map BEFORE each poll
    # (the server pops it at completion; children enqueued during a
    # poll cannot be dispatched before the next one)
    for _ in range(400):
        if not (fe.queue.depth or fe._work_pending() or fe._circuits):
            break
        tags.update(fe._node_of_rid)
        results.update(dict(fe.poll(flush=True)))
    assert not fe._circuits, "drain did not complete every circuit"
    assert fe.queue.depth == 0
    assert all(cid in results for cid in cids)

    # every node executed; re-executions == requeued requests exactly
    served = [rid for _wid, _key, rids in log for rid in rids]
    fr = fe.stats()["frontend"]
    assert len(served) - len(set(served)) == fr["requeued_requests"], \
        "a request was re-served without a matching worker-death requeue"
    if injector is not None:
        assert fr["deaths"] == len(injector.killed_workers)
    pos = {}
    for _wid, _key, rids in log:
        for rid in rids:
            t = tags.get(rid)
            if t is not None and t not in pos:
                pos[t] = len(pos)
    want = {(cid, i) for cid, ops in built.items()
            for i in range(len(ops))}
    assert set(pos) == want, "a circuit node was never served"
    for cid, ops in built.items():
        for i, node in enumerate(ops):
            for a in node.args:
                if isinstance(a, int):
                    assert pos[(cid, a)] < pos[(cid, i)], \
                        f"node ({cid},{i}) ran before its arg {a}"


# --------------------------------------------------------------------------
# multi-host REAL serving (ISSUE 8): the traced-client property of the
# previous section, re-run through an HEFrontend with two real workers
# and a randomized single-worker death mid-stream — requeue + re-route
# must keep the served result bitwise identical to the composed core
# reference (ops are deterministic integer arithmetic)
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def mh_trace_session():
    """One warm frontend-backed HESession (two in-process workers) +
    reference-side Galois keys, reused across hypothesis examples —
    workers are revived and the kill schedule reset per example."""
    import jax

    from repro.client import HESession
    from repro.core.keys import keygen
    from repro.core.rotate import conj_keygen, rot_keygen
    from repro.hserve.frontend import HEFrontend

    mesh = make_mesh((1, 1), ("data", "model"))
    sk, pk, evk = keygen(TRACE_PARAMS, seed=0)
    fe = HEFrontend(TRACE_PARAMS, evk, mesh=mesh, batch=2, workers=2)
    s = HESession(TRACE_PARAMS, sk=sk, pk=pk, evk=evk, server=fe)
    rks = {r: rot_keygen(TRACE_PARAMS, sk, r) for r in (1, 2, 4)}
    return s, fe, rks, conj_keygen(TRACE_PARAMS, sk)


@given(seed=st.integers(min_value=0, max_value=2**31 - 1),
       n_ops=st.integers(min_value=1, max_value=3),
       kill=st.sampled_from([-1, 0, 1]),
       kill_after=st.integers(min_value=1, max_value=2))
@settings(max_examples=6, deadline=None)
def test_random_traced_expr_multihost_bitwise_under_worker_death(
        mh_trace_session, seed, n_ops, kill, kill_after):
    """A random traced expression served by the two-worker frontend —
    with worker `kill` scheduled to die `kill_after` dispatches into
    the example (kill=-1: no death) — is bitwise identical to the
    composed core.heaan reference over the compiled CircuitOp list."""
    from repro.client import compile_handle
    from repro.client.testing import random_expr
    from repro.hserve.circuit import execute_circuit_reference
    from repro.runtime.failures import FailureInjector

    session, fe, rks, ck = mh_trace_session
    fe.revive_workers()
    if kill >= 0:
        fe.injector = FailureInjector(kill_worker_at={
            kill: fe.workers[kill].batches + kill_after})
    try:
        rng = np.random.default_rng(seed)
        n = TRACE_PARAMS.n_slots_max
        zs = [0.5 * (rng.normal(size=n) + 1j * rng.normal(size=n))
              for _ in range(2)]
        leaves = [(session.encrypt(z, seed=2000 + seed + i), z)
                  for i, z in enumerate(zs)]
        y, _shadow = random_expr(rng, leaves, n_ops=n_ops, max_depth=2)
        cc = compile_handle(y, TRACE_PARAMS)
        ref = execute_circuit_reference(
            cc.ops, cc.inputs, TRACE_PARAMS, evk=session.evk,
            rot_keys=rks, conj_key=ck)
        got = session.run([y])[0].result()
    finally:
        fe.injector = None
        fe.revive_workers()
    assert bool((np.asarray(got.ax) == np.asarray(ref.ax)).all()
                and (np.asarray(got.bx) == np.asarray(ref.bx)).all()), \
        "multi-host serving diverged from the composed core reference"
