"""Distribution layer tests.

Multi-device behaviours (sharded HE pipeline correctness, compressed-DP
all-reduce, sharding-rule placement) run through the shared
``run_in_8dev_subprocess`` harness (tests/conftest.py): a fresh
interpreter with XLA_FLAGS=--xla_force_host_platform_device_count=8 —
the flag must be set before jax initializes, and the main test process
has already done so.
"""


def test_he_pipeline_matches_core_on_mesh(run_in_8dev_subprocess):
    """Sharded HE Mul (batch→data, np→model) == core.heaan.he_mul, bitwise,
    on a (2, 4) mesh of 8 placeholder devices."""
    res = run_in_8dev_subprocess("""
        from repro.core import test_params
        from repro.core import heaan as H
        from repro.core.keys import keygen
        from repro.core.context import make_context
        from repro.dist import he_pipeline as hp
        from repro.dist.sharding import he_limb_sharding

        params = test_params(logN=5, beta_bits=32)
        sk, pk, evk = keygen(params, seed=0)
        rng = np.random.default_rng(1)
        B = 4
        cts = []
        for i in range(2 * B):
            z = rng.normal(size=8) + 1j * rng.normal(size=8)
            cts.append(H.encrypt_message(z, pk, params, seed=10 + i))
        ref = [H.he_mul(cts[2*i], cts[2*i+1], evk, params)
               for i in range(B)]

        mesh = make_mesh((2, 4), ("data", "model"))
        st = hp.he_static(params, params.logQ)
        step = jax.jit(hp.make_he_mul_step(st, mesh))
        ctx = make_context(params, params.logQ)
        t1, t2, ek = hp.runtime_tables(ctx, evk)
        stack = lambda xs: jnp.stack(xs)
        sh = he_limb_sharding(mesh)
        ax1 = jax.device_put(stack([cts[2*i].ax for i in range(B)]), sh)
        bx1 = jax.device_put(stack([cts[2*i].bx for i in range(B)]), sh)
        ax2 = jax.device_put(stack([cts[2*i+1].ax for i in range(B)]), sh)
        bx2 = jax.device_put(stack([cts[2*i+1].bx for i in range(B)]), sh)
        ax3, bx3 = jax.jit(step)(t1, t2, ek, ax1, bx1, ax2, bx2)
        ok = all(
            bool((np.asarray(ax3[i]) == np.asarray(ref[i].ax)).all()
                 and (np.asarray(bx3[i]) == np.asarray(ref[i].bx)).all())
            for i in range(B))
        print(json.dumps({"ok": ok, "devices": len(jax.devices())}))
    """)
    assert res["devices"] == 8
    assert res["ok"], "sharded HE Mul diverged from core he_mul"


def test_compressed_dp_grads_close_to_exact(run_in_8dev_subprocess):
    res = run_in_8dev_subprocess("""
        from jax.experimental.shard_map import shard_map
        from jax.sharding import PartitionSpec as P
        from repro.dist.collectives import compressed_psum_grads

        mesh = make_mesh((8,), ("data",))
        rng = np.random.default_rng(0)
        g_all = jnp.asarray(rng.normal(size=(8, 4, 333)).astype(np.float32))

        def local(g, key):
            out = compressed_psum_grads({"w": g[0]}, ("data",), key[0])
            return out["w"][None]

        fn = shard_map(local, mesh=mesh, in_specs=(P("data"), P()),
                       out_specs=P("data"), check_rep=False)
        keys = jax.random.split(jax.random.key(0), 1)
        out = fn(g_all, keys)
        exact = np.asarray(g_all).mean(axis=0)
        approx = np.asarray(out)[0]
        # every replica holds the same result
        same = all(np.array_equal(np.asarray(out)[i], approx)
                   for i in range(8))
        scale = np.abs(np.asarray(g_all)).max() / 127.0
        err = np.abs(approx - exact).max()
        print(json.dumps({"same": bool(same), "err": float(err),
                          "tol": float(3 * scale)}))
    """)
    assert res["same"], "replicas diverged after compressed all-reduce"
    assert res["err"] <= res["tol"], (res["err"], res["tol"])


def test_param_sharding_rules_place_and_divide(run_in_8dev_subprocess):
    res = run_in_8dev_subprocess("""
        from repro.configs.registry import get_arch
        from repro.dist.sharding import param_sharding_rules
        from repro.models import init_params

        cfg = get_arch("llama3.2-1b").reduced(
            d_model=64, n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128,
            vocab_size=512)
        mesh = make_mesh((2, 4), ("data", "model"))
        params = init_params(cfg, jax.random.key(0))
        shardings = param_sharding_rules(params, mesh)
        placed = jax.device_put(params, shardings)
        leaves = jax.tree.leaves(placed)
        n_sharded = sum(
            1 for l in leaves
            if not l.sharding.is_fully_replicated)
        print(json.dumps({"n_leaves": len(leaves),
                          "n_sharded": int(n_sharded)}))
    """)
    assert res["n_sharded"] >= res["n_leaves"] // 2, res
