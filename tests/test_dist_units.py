"""Single-process unit tests for repro.dist (no 8-device subprocess).

The multi-device behaviours live in tests/test_dist.py; these catch
regressions in the table pytrees, spec builders, sharding rule engines,
pipeline numerics, and compressed collectives on whatever devices the
test process already has.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.experimental.shard_map import shard_map
from jax.sharding import PartitionSpec as P

from repro.core import heaan as H
from repro.core import test_params as small_params
from repro.core.context import make_context
from repro.core.keys import keygen
from repro.dist import he_pipeline as hp
from repro.dist.collectives import compressed_psum_grads
from repro.dist.sharding import (
    batch_spec, cache_sharding_rules, he_limb_sharding,
    param_sharding_rules, zero1_opt_sharding,
)
from repro.launch.mesh import make_mesh

PARAMS = small_params(logN=4, beta_bits=32)


def _mesh11():
    return make_mesh((1, 1), ("data", "model"))


# --------------------------------------------------------------------------
# table pytrees and abstract specs
# --------------------------------------------------------------------------

def test_region_tables_match_table_specs():
    """region_tables/evk_tables produce exactly the pytree he_table_specs
    promises — shapes, dtypes, and key sets (the dry-run lowers against
    the specs, the runtime feeds the tables; they must agree)."""
    st = hp.he_static(PARAMS, PARAMS.logQ)
    ctx = make_context(PARAMS, PARAMS.logQ)
    t1s, t2s, eks = hp.he_table_specs(st)
    for region, spec in ((1, t1s), (2, t2s)):
        tabs = hp.region_tables(ctx, region)
        assert set(tabs) == set(spec) == set(hp.REGION_TABLE_KEYS)
        for k in tabs:
            assert tabs[k].shape == spec[k].shape, (region, k)
            assert tabs[k].dtype == spec[k].dtype, (region, k)
    _, _, evk = keygen(PARAMS, seed=0)
    ek = hp.evk_tables(evk)
    assert set(ek) == set(eks) == set(hp.EVK_TABLE_KEYS)
    for k in ek:
        assert ek[k].shape == eks[k].shape
        assert ek[k].dtype == eks[k].dtype


def test_he_static_region_sizes():
    st = hp.he_static(PARAMS, PARAMS.logQ)
    # region 2 covers log q + 2 log Q bits vs region 1's 2 log q: more primes
    assert st.np2 > st.np1 >= 1
    assert st.np2_max == st.np2            # top level
    assert st.qlimbs == PARAMS.qlimbs(PARAMS.logQ)
    assert st.ks_limbs > st.qlimbs
    assert st.icrt1.np_count == st.np1
    assert st.icrt2.np_count == st.np2


def test_input_specs_shapes():
    st = hp.he_static(PARAMS, PARAMS.logQ)
    specs = hp.he_input_specs(st, batch=6)
    assert len(specs) == 4
    for s in specs:
        assert s.shape == (6, PARAMS.N, st.qlimbs)
        assert s.dtype == np.uint32


# --------------------------------------------------------------------------
# pipeline numerics on a trivial mesh
# --------------------------------------------------------------------------

def test_sharded_he_mul_bitwise_on_one_device():
    """make_he_mul_step == core.heaan.he_mul, bitwise, on a (1,1) mesh.

    The 8-device version lives in tests/test_dist.py; this in-process
    check catches numerics regressions without the subprocess harness.
    """
    params = small_params(logN=4, beta_bits=32)
    sk, pk, evk = keygen(params, seed=3)
    rng = np.random.default_rng(5)
    B = 2
    cts = []
    for i in range(2 * B):
        z = rng.normal(size=4) + 1j * rng.normal(size=4)
        cts.append(H.encrypt_message(z, pk, params, seed=20 + i))
    ref = [H.he_mul(cts[2 * i], cts[2 * i + 1], evk, params)
           for i in range(B)]

    mesh = _mesh11()
    st = hp.he_static(params, params.logQ)
    ctx = make_context(params, params.logQ)
    t1, t2, ek = hp.runtime_tables(ctx, evk)
    sh = he_limb_sharding(mesh, batch=B)
    args = [jax.device_put(jnp.stack(x), sh) for x in (
        [c.ax for c in cts[0::2]], [c.bx for c in cts[0::2]],
        [c.ax for c in cts[1::2]], [c.bx for c in cts[1::2]])]
    step = jax.jit(hp.make_he_mul_step(st, mesh))
    ax3, bx3 = step(t1, t2, ek, *args)
    for i in range(B):
        np.testing.assert_array_equal(np.asarray(ax3[i]),
                                      np.asarray(ref[i].ax))
        np.testing.assert_array_equal(np.asarray(bx3[i]),
                                      np.asarray(ref[i].bx))


# --------------------------------------------------------------------------
# compressed collectives on a 1-device mesh
# --------------------------------------------------------------------------

def test_compressed_psum_grads_single_device():
    mesh = make_mesh((1,), ("data",))
    rng = np.random.default_rng(0)
    g = jnp.asarray(rng.normal(size=(8, 130)).astype(np.float32))

    def local(g, key):
        return compressed_psum_grads({"w": g[0]}, ("data",),
                                     key[0])["w"][None]

    fn = shard_map(local, mesh=mesh, in_specs=(P("data"), P()),
                   out_specs=P("data"), check_rep=False)
    out = fn(g[None], jax.random.split(jax.random.key(0), 1))[0]
    scale = np.abs(np.asarray(g)).max() / 127.0
    # world of 1: the "mean" is just quantize→dequantize of g itself
    assert np.abs(np.asarray(out) - np.asarray(g)).max() <= 1.5 * scale


def test_trainer_compress_dp_runs_and_replays_bit_identical(tmp_path):
    """The --compress-dp Trainer path (shard_map over "data" with
    compressed_psum_grads, per-step fold_in quantization key) trains,
    and two runs from the same seed produce bit-identical params — the
    determinism the fault-tolerance replay contract needs."""
    from repro.configs.registry import get_arch
    from repro.launch.train import TrainConfig, Trainer

    cfg = get_arch("llama3.2-1b").reduced()
    tc = TrainConfig(batch=2, seq_len=16, steps=3, ckpt_every=1000)

    def run():
        tr = Trainer(cfg, tc, compress_dp=True)
        out = tr.run()
        return tr.params, out["history"]

    p1, h1 = run()
    p2, h2 = run()
    assert len(h1) == 3 and np.isfinite(h1[-1]["loss"])
    assert [h["loss"] for h in h1] == [h["loss"] for h in h2]
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_compressed_psum_preserves_structure_and_dtype():
    mesh = make_mesh((1,), ("data",))
    tree = {"a": jnp.ones((3, 7), jnp.float32),
            "b": {"c": jnp.full((300,), 0.25, jnp.float32)}}

    def local(t, key):
        return compressed_psum_grads(t, ("data",), key)

    fn = shard_map(local, mesh=mesh, in_specs=(P(), P()), out_specs=P(),
                   check_rep=False)
    out = fn(tree, jax.random.key(1))
    assert jax.tree.structure(out) == jax.tree.structure(tree)
    for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(tree)):
        assert a.shape == b.shape and a.dtype == b.dtype


# --------------------------------------------------------------------------
# sharding rule engines (placement logic only — no multi-device needed)
# --------------------------------------------------------------------------

def test_param_rules_orientation():
    from repro.configs.registry import get_arch
    from repro.models import init_params
    cfg = get_arch("llama3.2-1b").reduced(
        d_model=64, n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128,
        vocab_size=512)
    mesh = _mesh11()
    params = jax.eval_shape(lambda k: init_params(cfg, k),
                            jax.random.key(0))
    sh = param_sharding_rules(params, mesh)
    flat = {"/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                     for k in path): s.spec
            for path, s in jax.tree_util.tree_flatten_with_path(sh)[0]}
    # column-parallel: output dim on model; row-parallel: input dim
    wq = next(v for k, v in flat.items() if k.endswith("attn/wq/w"))
    wo = next(v for k, v in flat.items() if k.endswith("attn/wo/w"))
    assert wq[-1] == "model" and wq[0] == "data"
    assert wo[0] == "model"
    emb = flat["tok_embed"]
    assert emb[0] == "model"
    # norms replicate
    ln = next(v for k, v in flat.items() if k.endswith("ln_f/scale"))
    assert all(a is None for a in ln)


def test_model_dim_orientation_helper():
    """Name-tagged orientation: column-parallel shards the output dim,
    row-parallel the input dim, embeddings the vocab dim; unknown ≥2-d
    leaves fall back to their largest dim; vectors are never sharded."""
    from repro.dist.sharding import _model_dim
    assert _model_dim(["layers", "attn", "wq", "w"], (64, 64)) == 1
    assert _model_dim(["layers", "attn", "wo", "w"], (64, 64)) == 0
    assert _model_dim(["tok_embed"], (512, 64)) == 0
    assert _model_dim(["moe", "wi"], (8, 64, 128)) == 2    # expert stacks
    assert _model_dim(["moe", "wo"], (8, 128, 64)) == 1
    assert _model_dim(["ssm", "A_log"], (128, 16)) == 0    # largest-dim
    assert _model_dim(["ln_f", "scale"], (64,)) is None


def test_cache_rules_batch_dim_offset():
    mesh = _mesh11()
    cache = {
        "stacked": {"k": jnp.zeros((2, 8, 16, 4, 32))},   # (L, B, S, H, hd)
        "list": [{"k": jnp.zeros((8, 16, 4, 32))}],       # (B, S, H, hd)
    }
    sh = cache_sharding_rules(cache, mesh)
    assert sh["stacked"]["k"].spec[1] in ("data", None)
    assert sh["stacked"]["k"].spec[0] is None              # layer axis local
    assert sh["list"][0]["k"].spec[0] in ("data", None)


def test_zero1_adds_data_axis():
    mesh = _mesh11()
    params = {"w": jnp.ones((4, 6))}
    p_sh = param_sharding_rules(params, mesh, fsdp_params=False)
    assert "data" not in p_sh["w"].spec          # params: model-parallel only
    m_sh = zero1_opt_sharding(p_sh, params, mesh)
    assert jax.tree.structure(m_sh) == jax.tree.structure(p_sh)
    spec = m_sh["w"].spec
    assert "data" in spec                        # moments gained the DP shard
    assert "model" in spec                       # and kept the param sharding


def test_batch_and_limb_specs():
    mesh = _mesh11()
    assert batch_spec(mesh).spec == P(("data",))
    assert he_limb_sharding(mesh).spec == P(("data",))
    # indivisible batch falls back to replicated
    sh = he_limb_sharding(mesh, batch=3)
    assert sh.spec == P(("data",)) or sh.is_fully_replicated


def test_he_limb_sharding_rejects_odd_batch_on_wide_mesh():
    devs = jax.devices()
    if len(devs) < 2:
        pytest.skip("needs >1 device to exercise the divisibility check")
    mesh = make_mesh((2, len(devs) // 2), ("data", "model"))
    assert he_limb_sharding(mesh, batch=3).is_fully_replicated
