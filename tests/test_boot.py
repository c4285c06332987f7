"""repro.boot: the served CKKS bootstrapping pipeline.

Bootstrap is the repo's first APPROXIMATE served operation, so the
contract splits in two:

  - the pipeline itself is gated by an error bound
    (``BootstrapPlan.error_bound`` — documented in docs/BOOTSTRAP.md),
    property-tested over seeded random messages and plan shapes;
  - everything AROUND it stays bitwise: the mod_raise engine step pins
    against ``core.heaan.he_mod_raise`` (1-dev and the (2, 4) 8-dev
    mesh), and the refreshed ciphertext must run further muls bitwise
    identical to the core references at the raised level.

The served tests share one module-scoped server at the reference
small-param config (`boot_params`): the engine compile for the
pipeline's (op, level) cells is paid once, every drain after that is
steady state.
"""

import dataclasses
import math

import numpy as np
import pytest

import jax

import repro.core  # noqa: F401
from repro.analysis.dataflow import CircuitError
from repro.analysis.noise import estimate_noise
from repro.boot import (BOOT_STAGES, BootConfig, boot_params,
                        bootstrap_circuit, raise_target)
from repro.boot.modraise import interval_bound
from repro.boot.pipeline import _auto_r
from repro.core import heaan as H
from repro.core.keys import keygen
from repro.core.rotate import conj_keygen, rot_keygen
from repro.hserve import HEServer
from repro.obs import Tracer
from repro.launch.mesh import make_mesh

PARAMS = boot_params()              # logN=4, logQ=336, logp=24, h=2


def _msg(rng, bound, n=None):
    n = n or PARAMS.n_slots_max
    z = rng.uniform(-1, 1, n) + 1j * rng.uniform(-1, 1, n)
    return z * (bound / np.max(np.abs(z)))


def _exhausted(z, pk, seed):
    """Encrypt z and walk it down to logq == logp — the level-exhausted
    position auto-insertion targets (q_s = 1)."""
    ct = H.encrypt_message(z, pk, PARAMS, seed=seed)
    return H.he_mod_down(ct, PARAMS, PARAMS.logp)


class BootEnv:
    def __init__(self):
        self.sk, self.pk, self.evk = keygen(PARAMS, seed=0)
        self.rot = {r: rot_keygen(PARAMS, self.sk, r)
                    for r in (1, 2, 3, 4)}
        self.conj = conj_keygen(PARAMS, self.sk)
        self.tracer = Tracer()
        self.server = HEServer(
            PARAMS, self.evk, self.rot, self.conj,
            mesh=make_mesh((1, 1), ("data", "model")),
            batch=2, schedule=True, tracer=self.tracer)
        self.plan = bootstrap_circuit(
            PARAMS, logq_in=PARAMS.logp,
            plain_lookup=self.server.cache.has_plain)

        # ---- the canonical concurrent run: two seeded bootstraps in
        # one drain (compiles every pipeline cell; later tests reuse)
        rng = np.random.default_rng(7)
        self.msgs = [_msg(rng, self.plan.msg_bound) for _ in range(2)]
        cts = [_exhausted(z, self.pk, seed=11 + i)
               for i, z in enumerate(self.msgs)]
        cids = [self.server.submit_bootstrap(ct, plan=self.plan)
                for ct in cts]
        res = self.server.drain()
        self.refreshed = [res[c] for c in cids]
        self.stats = self.server.stats()

    def decrypt(self, ct):
        return H.decrypt_message(ct, self.sk, PARAMS)


@pytest.fixture(scope="module")
def env():
    return BootEnv()


# ------------------------------------------------------- plan structure

def test_plan_stages_levels_and_requirements():
    plan = bootstrap_circuit(PARAMS, logq_in=PARAMS.logp)
    assert len(plan.ops) == len(plan.meta) == len(plan.stages)
    assert plan.ops[0].op == "mod_raise"
    assert plan.ops[0].logq2 == PARAMS.logQ
    assert tuple(dict.fromkeys(plan.stages)) == BOOT_STAGES
    # the refreshed ciphertext gains whole levels at the plan's scale
    assert plan.out_logp == PARAMS.logp
    assert plan.levels_gained >= 2
    assert plan.out_logq == PARAMS.logp \
        + plan.levels_gained * PARAMS.logp
    # Galois requirements: conjugation (Re/Im split) + the BSGS strides
    assert ("conj",) in plan.requires
    assert {t[1] for t in plan.requires if t[0] == "rot"} \
        == {1, 2, 3, 4}
    # the error contract is meaningful: bounded, and well above the
    # fixed-point floor
    b = plan.error_bound()
    assert 0 < b < 2.0 ** -6
    assert b >= 4.0 * PARAMS.N * 2.0 ** -PARAMS.logp


def test_auto_r_covers_interval_and_config_overrides():
    plan = bootstrap_circuit(PARAMS, logq_in=PARAMS.logp)
    theta = 2 * math.pi * interval_bound(PARAMS, plan.msg_bound)
    assert plan.r == _auto_r(PARAMS, plan.msg_bound)
    assert theta / 2.0 ** plan.r <= 1.1
    deeper = bootstrap_circuit(PARAMS, logq_in=PARAMS.logp,
                               config=BootConfig(r=plan.r + 1))
    assert deeper.r == plan.r + 1
    # one more squaring costs one more level
    assert deeper.out_logq == plan.out_logq - PARAMS.logp
    # the bound is monotone in the message contract
    assert plan.error_bound(2.0 ** -4) > plan.error_bound(2.0 ** -6)


def test_full_slots_required():
    with pytest.raises(ValueError, match="full slots"):
        bootstrap_circuit(PARAMS, logq_in=PARAMS.logp,
                          n_slots=PARAMS.n_slots_max // 2)


def test_chain_too_short_is_a_circuit_error():
    small = dataclasses.replace(PARAMS, logQ=8 * PARAMS.logp)
    with pytest.raises(CircuitError):
        bootstrap_circuit(small, logq_in=small.logp)


def test_raise_target_validates_range():
    with pytest.raises(ValueError, match="cannot mod-raise"):
        raise_target(PARAMS, PARAMS.logQ)


def test_resolved_ops_backfills_hash_only_diagonals():
    plan = bootstrap_circuit(PARAMS, logq_in=PARAMS.logp)
    hashed = [n for n in plan.ops if n.pt_hash is not None]
    assert hashed, "no cached plaintext operands in the plan?"
    # cross-stage dedup ships repeats hash-only (pt=None)...
    assert any(n.pt is None for n in hashed)
    # ...and resolved_ops() materializes every one of them for the
    # cacheless reference path
    assert all(n.pt is not None for n in plan.resolved_ops()
               if n.pt_hash is not None)


def test_repeat_plan_against_cache_ships_fully_hash_only():
    plan = bootstrap_circuit(PARAMS, logq_in=PARAMS.logp)
    regs = set(plan.plain_registers)
    again = bootstrap_circuit(PARAMS, logq_in=PARAMS.logp,
                              plain_lookup=lambda h, lq: (h, lq) in regs)
    assert all(n.pt is None for n in again.ops if n.pt_hash is not None)


# ------------------------------------------- queue / scheduler plumbing

def test_queue_rejects_non_raising_mod_raise(env):
    ct = _exhausted(env.msgs[0], env.pk, seed=50)
    with pytest.raises(ValueError, match="must exceed"):
        env.server.submit_mod_raise(ct, ct.logq)


def test_scheduler_prefetch_walks_up_through_mod_raise():
    from repro.hserve.scheduler import CircuitScheduler
    lv = CircuitScheduler.levels_for_key(("mod_raise", PARAMS.logp,
                                          PARAMS.logQ))
    assert lv == {PARAMS.logp, PARAMS.logQ}
    # descending ops still walk down
    assert CircuitScheduler.levels_for_key(("rescale", 72, 24)) \
        == {72, 48}


# ----------------------------------- the served pipeline (module server)

def test_served_error_contract_and_raised_level(env):
    bound = env.plan.error_bound()
    for z, out in zip(env.msgs, env.refreshed):
        assert (out.logq, out.logp) \
            == (env.plan.out_logq, env.plan.out_logp)
        err = float(np.max(np.abs(env.decrypt(out) - z)))
        assert err <= bound, f"{err:.3e} > documented bound {bound:.3e}"


def test_concurrent_bootstraps_cobatch_across_circuits(env):
    cb = env.stats["cobatch"]
    assert cb["circuit_nodes"] >= 2 * len(env.plan.ops)
    assert cb["cross_circuit_batches"] > 0
    assert cb["cross_circuit_rate"] > 0.0


def test_scheduler_prefetched_the_raised_level_tail(env):
    # the bootstrap's post-raise nodes live ABOVE logq_in: without the
    # mod_raise-aware prefetch they would all cold-miss the TableCache
    warmed = env.server.scheduler.prefetched_levels
    assert any(lv > env.plan.logq_in for lv in warmed), warmed


def test_boot_spans_attribute_all_four_stages(env):
    ev = [e for e in env.tracer.events if e.get("cat") == "boot"]
    assert {e["name"] for e in ev} \
        == {f"boot.{s}" for s in BOOT_STAGES}
    assert all(e["args"]["nodes"] >= 1 for e in ev)


def test_served_mod_raise_is_bitwise_vs_core(env):
    ct = _exhausted(env.msgs[0], env.pk, seed=60)
    rid = env.server.submit_mod_raise(ct, PARAMS.logQ)
    got = env.server.drain()[rid]
    ref = H.he_mod_raise(ct, PARAMS, PARAMS.logQ)
    np.testing.assert_array_equal(np.asarray(got.ax), np.asarray(ref.ax))
    np.testing.assert_array_equal(np.asarray(got.bx), np.asarray(ref.bx))
    assert got.logq == PARAMS.logQ


def test_refreshed_ciphertext_runs_two_muls_bitwise_vs_core(env):
    """The error contract covers the bootstrap itself; AFTER it the
    refreshed ciphertext is an ordinary ciphertext — two further served
    muls (with rescales) must pin bitwise against the core references
    at the raised levels."""
    out = env.refreshed[0]
    srv = env.server
    r1 = srv.submit_mul(out, out)
    sq = srv.drain()[r1]
    ref_sq = H.he_mul(out, out, env.evk, PARAMS)
    np.testing.assert_array_equal(np.asarray(sq.ax),
                                  np.asarray(ref_sq.ax))
    r2 = srv.submit_rescale(sq)
    sq = srv.drain()[r2]
    ref_sq = H.rescale(ref_sq, PARAMS)
    np.testing.assert_array_equal(np.asarray(sq.bx),
                                  np.asarray(ref_sq.bx))
    r3 = srv.submit_mul(sq, sq)
    q4 = srv.drain()[r3]
    ref_q4 = H.he_mul(ref_sq, ref_sq, env.evk, PARAMS)
    np.testing.assert_array_equal(np.asarray(q4.ax),
                                  np.asarray(ref_q4.ax))
    np.testing.assert_array_equal(np.asarray(q4.bx),
                                  np.asarray(ref_q4.bx))
    # and the refreshed level really affords both muls
    assert ref_q4.logq - PARAMS.logp >= PARAMS.logp
    # the squared message is still the squared message
    z2 = env.msgs[0] ** 2
    err = float(np.max(np.abs(H.decrypt_message(
        H.rescale(q4, PARAMS), env.sk, PARAMS) - z2 * z2)))
    assert err < 1e-3


def test_session_auto_insertion_serves_past_native_depth(env):
    """run(bootstrap="auto"): a mul on a level-exhausted input compiles
    with the pipeline spliced in front and the served result is the
    product — depth beyond the native budget, within the bound."""
    from repro.client.session import HESession
    s = HESession(PARAMS, env.sk, env.pk, env.evk, server=env.server)
    rng = np.random.default_rng(21)
    z = _msg(rng, env.plan.msg_bound)
    x = s.input(_exhausted(z, env.pk, seed=70))

    with pytest.raises(CircuitError, match="needs bootstrapping"):
        s.compile(x * x)
    cc = s.compile(x * x, bootstrap="auto")
    assert len(cc.bootstraps) == 1
    assert any(n.op == "mod_raise" for n in cc.ops)

    fut = s.run([x * x], bootstrap="auto")[0]
    got = s.decrypt(fut)
    # one bootstrap (≤ bound on the message) then an exact mul: the
    # product error is ~2·|z|·bound at first order
    tol = 4.0 * env.plan.msg_bound * env.plan.error_bound()
    assert float(np.max(np.abs(got - z * z))) <= tol


def test_auto_insertion_bootstraps_shared_operand_once(env):
    from repro.client.session import HESession
    s = HESession(PARAMS, env.sk, env.pk, env.evk, server=env.server)
    rng = np.random.default_rng(22)
    x = s.input(_exhausted(_msg(rng, env.plan.msg_bound),
                           env.pk, seed=71))
    cc = s.compile((x * x) + (x * 0.5), bootstrap="auto")
    assert len(cc.bootstraps) == 1          # x refreshed once, shared
    assert sum(n.op == "mod_raise" for n in cc.ops) == 1


# ------------------------- the noise estimator's upper-bound contract

N_RANDOM_PLANS = 50
SERVED_EVERY = 10       # every 10th plan also runs served


def test_noise_upper_bound_contract_on_50_random_boot_circuits(env):
    """50 seeded random circuits containing a bootstrap (random message
    bound / squaring count → different plan DAGs: the squarings change
    the EvalMod chain and the level schedule). Statically, the
    analyzer's noise propagation must stay finite and the TOTAL
    documented contract — arithmetic noise bound + the plan's
    approximation bound — must promise usable precision. Every
    SERVED_EVERY-th plan is also served end to end, and the measured
    error must respect that total bound."""
    rng = np.random.default_rng(1234)
    served = []
    for k in range(N_RANDOM_PLANS):
        mb = 2.0 ** -int(rng.integers(5, 8))
        cfg = BootConfig(r=int(_auto_r(PARAMS, mb) + rng.integers(0, 2)))
        plan = bootstrap_circuit(PARAMS, logq_in=PARAMS.logp,
                                 msg_bound=mb, config=cfg,
                                 plain_lookup=env.server.cache.has_plain)
        noise = estimate_noise(
            plan.ops, {plan.in_name: (plan.logq_in, plan.logp)}, PARAMS,
            input_bounds=mb, pt_bounds=plan.pt_bounds,
            input_nslots={plan.in_name: plan.n_slots}, meta=plan.meta)
        assert all(np.isfinite(nn.nu) and nn.nu > 0 for nn in noise)
        total = 2.0 ** noise[-1].error_bits + plan.error_bound()
        assert total < 2.0 ** -6, (
            f"plan {k}: contract {total:.3e} promises no precision")
        if k % SERVED_EVERY == 0:
            z = _msg(rng, mb)
            ct = _exhausted(z, env.pk, seed=300 + k)
            cid = env.server.submit_bootstrap(ct, plan=plan)
            served.append((k, z, cid, total))
    res = env.server.drain()
    for k, z, cid, total in served:
        err = float(np.max(np.abs(env.decrypt(res[cid]) - z)))
        assert err <= total, (
            f"plan {k}: measured {err:.3e} > contract {total:.3e}")


# ------------------------------------------------- the (2, 4) 8-dev mesh

def test_bootstrap_cobatch_and_mod_raise_on_8_device_mesh(
        run_in_8dev_subprocess):
    """The acceptance gate's 8-dev half: on a (2, 4) mesh, two
    concurrent bootstraps must co-batch across circuits (cross-circuit
    rate > 0) and land within the error bound — and the mod_raise
    engine step must stay bitwise vs core on the sharded mesh."""
    res = run_in_8dev_subprocess("""
        from repro.boot import boot_params, bootstrap_circuit
        from repro.core import heaan as H
        from repro.core.keys import keygen
        from repro.core.rotate import conj_keygen, rot_keygen
        from repro.hserve import HEServer

        params = boot_params()
        sk, pk, evk = keygen(params, seed=0)
        rot = {r: rot_keygen(params, sk, r) for r in (1, 2, 3, 4)}
        conj = conj_keygen(params, sk)
        mesh = make_mesh((2, 4), ("data", "model"))
        srv = HEServer(params, evk, rot, conj, mesh=mesh, batch=2,
                       schedule=True)
        plan = bootstrap_circuit(params, logq_in=params.logp,
                                 plain_lookup=srv.cache.has_plain)

        rng = np.random.default_rng(7)
        n = params.n_slots_max
        zs, cts = [], []
        for i in range(2):
            z = rng.uniform(-1, 1, n) + 1j * rng.uniform(-1, 1, n)
            z *= plan.msg_bound / np.max(np.abs(z))
            ct = H.encrypt_message(z, pk, params, seed=11 + i)
            zs.append(z)
            cts.append(H.he_mod_down(ct, params, params.logp))
        cids = [srv.submit_bootstrap(ct, plan=plan) for ct in cts]
        res = srv.drain()
        errs = [float(np.max(np.abs(
            H.decrypt_message(res[c], sk, params) - z)))
            for c, z in zip(cids, zs)]
        cb = srv.stats()["cobatch"]

        rid = srv.submit_mod_raise(cts[0], params.logQ)
        got = srv.drain()[rid]
        ref = H.he_mod_raise(cts[0], params, params.logQ)
        mr_bitwise = bool(
            (np.asarray(got.ax) == np.asarray(ref.ax)).all()
            and (np.asarray(got.bx) == np.asarray(ref.bx)).all())
        print(json.dumps({
            "devices": len(jax.devices()),
            "max_err": max(errs), "bound": plan.error_bound(),
            "out_logq": [res[c].logq for c in cids],
            "cross_rate": cb["cross_circuit_rate"],
            "cross_batches": cb["cross_circuit_batches"],
            "mr_bitwise": mr_bitwise}))
    """)
    assert res["devices"] == 8
    assert res["max_err"] <= res["bound"]
    assert all(lq > boot_params().logp for lq in res["out_logq"])
    assert res["cross_batches"] > 0 and res["cross_rate"] > 0.0
    assert res["mr_bitwise"], "sharded mod_raise diverged from core"
