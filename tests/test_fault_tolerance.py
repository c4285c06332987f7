"""Fault tolerance: crash/restart bitwise-identity, straggler flags,
checkpoint atomicity + GC + elastic reshard."""

import os

import numpy as np

import jax
import jax.numpy as jnp

import repro.core  # noqa: F401
from repro.ckpt import CheckpointManager
from repro.configs.registry import get_arch
from repro.launch.train import TrainConfig, Trainer, run_with_restarts
from repro.runtime import FailureInjector, StepMonitor
from repro.launch.mesh import make_mesh


def _cfg():
    return get_arch("llama3.2-1b").reduced(n_layers=2, d_model=64,
                                           n_heads=2, n_kv_heads=2,
                                           head_dim=32, d_ff=128,
                                           vocab_size=256)


def _tc(**kw):
    base = dict(batch=2, seq_len=16, steps=8, ckpt_every=2, warmup_steps=2)
    base.update(kw)
    return TrainConfig(**base)


def test_crash_restart_bitwise_identical(tmp_path):
    cfg = _cfg()
    # uninterrupted reference run
    ref = Trainer(cfg, _tc(), ckpt_dir=str(tmp_path / "ref"))
    ref.run()

    # crashing run: dies at steps 3 and 6, restarts from latest checkpoint
    ck = str(tmp_path / "crash")
    inj = FailureInjector(fail_at_steps=[3, 6])
    trainer, out, restarts = run_with_restarts(
        lambda: Trainer(cfg, _tc(), ckpt_dir=ck, injector=inj),
        total_steps=8)
    assert restarts == 2

    for (ka, a), (kb, b) in zip(
            jax.tree_util.tree_flatten_with_path(ref.params)[0],
            jax.tree_util.tree_flatten_with_path(trainer.params)[0]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=str(ka))


def test_straggler_monitor_flags_slow_steps():
    mon = StepMonitor(slack=2.0, warmup_steps=2)
    flags = [mon.record(i, 0.1) for i in range(6)]
    assert not any(flags)
    assert mon.record(6, 0.5) is True       # 5× EMA -> breach
    assert mon.record(7, 0.1) is False      # recovery


def test_straggler_injection_is_flagged(tmp_path):
    cfg = _cfg()
    inj = FailureInjector(straggle_at_steps=[6], straggle_seconds=1.5)
    tr = Trainer(cfg, _tc(), ckpt_dir=str(tmp_path / "s"), injector=inj)
    out = tr.run()
    assert any(h["straggler"] for h in out["history"]), \
        "injected straggler step was not flagged"


def test_checkpoint_atomicity_and_gc(tmp_path):
    m = CheckpointManager(str(tmp_path), keep=2, async_save=False)
    tree = {"a": jnp.arange(8, dtype=jnp.float32),
            "b": {"c": jnp.ones((3, 3), jnp.bfloat16)}}
    for s in (1, 2, 3, 4):
        m.save(s, tree, block=True)
    assert m.all_steps() == [3, 4]          # keep-2 GC
    out = m.restore(4, tree)
    np.testing.assert_array_equal(np.asarray(out["a"]), np.asarray(tree["a"]))
    assert out["b"]["c"].dtype == jnp.bfloat16
    # no stray .tmp directories (atomicity)
    assert not [d for d in os.listdir(tmp_path) if d.endswith(".tmp")]


def test_elastic_reshard_restore(tmp_path):
    """Restore under a different mesh: full-array ckpt + sharding_fn."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    m = CheckpointManager(str(tmp_path), async_save=False)
    tree = {"w": jnp.arange(16, dtype=jnp.float32).reshape(4, 4)}
    m.save(1, tree, block=True)
    mesh = make_mesh((1,), ("data",))

    def shard(key, arr):
        return jax.device_put(arr, NamedSharding(mesh, P("data")))

    out = m.restore(1, tree, sharding_fn=shard)
    np.testing.assert_array_equal(np.asarray(out["w"]),
                                  np.asarray(tree["w"]))
    assert out["w"].sharding.mesh.shape["data"] == 1


def test_loss_decreases_on_synthetic_data(tmp_path):
    cfg = _cfg()
    tr = Trainer(cfg, _tc(steps=60, batch=8, seq_len=32, ckpt_every=1000,
                          warmup_steps=5, peak_lr=3e-3), ckpt_dir=None)
    out = tr.run()
    losses = [h["loss"] for h in out["history"]]
    head = sum(losses[:5]) / 5
    tail = sum(losses[-5:]) / 5
    assert tail < head * 0.8, (head, tail)
