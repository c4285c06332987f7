import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run (deliverable e): lower + compile EVERY
(architecture × input-shape × mesh) cell on the production meshes and
record memory/cost/collective analysis for the roofline (deliverable g).

The two lines above MUST run before any jax import — jax locks the device
count at first init. 512 placeholder CPU devices back the (16,16) and
(2,16,16) meshes.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun                  # all cells
    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2.5-32b \
        --shape train_4k --multipod
    PYTHONPATH=src python -m repro.launch.dryrun --he             # HE cells
    ... --out results.jsonl

Each cell appends a JSON record: per-device HLO FLOPs / bytes accessed /
collective-operand bytes (parsed from the optimized HLO), peak/argument
memory where the backend reports it, and wall compile time.
"""

import argparse
import json
import sys
import time
import traceback

import jax
import jax.numpy as jnp

import repro.core  # noqa: F401  (x64 for the HE cells)
from repro.configs.registry import ARCHS, SHAPES, get_arch, get_shapes
from repro.data import make_batch_specs
from repro.dist.sharding import (
    batch_spec, cache_sharding_rules, param_sharding_rules,
)
# re-exports: the parsers live in hlo_analysis (no import side effects);
# hillclimb and older callers still reach them through this module.
from repro.launch.hlo_analysis import (  # noqa: F401
    analyze_compiled as _analyze, collective_bytes_from_hlo,
)
from repro.launch.mesh import make_production_mesh
from repro.models import (
    decode_step, forward_train, init_cache, init_params, loss_fn, prefill,
)
from repro.optim import adamw_init, adamw_update, warmup_cosine


# --------------------------------------------------------------------------
# LM cells
# --------------------------------------------------------------------------

def make_train_step(cfg):
    def train_step(params, opt, batch):
        (loss, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, batch, cfg)
        lr = warmup_cosine(opt.step, peak_lr=3e-4, warmup_steps=100,
                           total_steps=10000)
        params, opt, _ = adamw_update(grads, opt, params, lr=lr)
        return params, opt, loss
    return train_step


def _abstract_params(cfg):
    return jax.eval_shape(lambda k: init_params(cfg, k), jax.random.key(0))


def lower_lm_cell(arch: str, shape_name: str, mesh, *,
                  cost_correct: bool = True, overrides: dict | None = None,
                  opt_dtype=None, sharding_mode: str = "fsdp") -> dict:
    """Compile the full (scanned) cell; correct HLO costs for scan-body
    once-counting via the layer-delta method (see EXPERIMENTS.md §Roofline
    methodology): C(L) = C(u) + (L-u)/u · (C(2u) - C(u)) with u = one
    pattern unit, computed from 1- and 2-unit unrolled variants.

    overrides/opt_dtype: §Perf hillclimb knobs (model-config fields /
    optimizer moments dtype)."""
    import dataclasses as _dc
    cfg = get_arch(arch)
    if overrides:
        cfg = _dc.replace(cfg, **overrides)
    out = _lower_lm_variant(cfg, shape_name, mesh, opt_dtype=opt_dtype,
                            sharding_mode=sharding_mode)
    if not cost_correct or cfg.enc_dec or not cfg.scan_layers:
        out["corrected"] = {k: out.get(k) for k in
                            ("flops", "bytes_accessed")}
        out["corrected"]["collective_bytes"] = \
            out["collectives"]["total_bytes"]
        out["correction"] = "none (stack already unrolled)"
        return out
    u = len(cfg.layer_pattern) if cfg.layer_pattern else 1
    v1 = _lower_lm_variant(
        _dc.replace(cfg, n_layers=u, scan_layers=False), shape_name, mesh,
        opt_dtype=opt_dtype, sharding_mode=sharding_mode)
    v2 = _lower_lm_variant(
        _dc.replace(cfg, n_layers=2 * u, scan_layers=False), shape_name,
        mesh, opt_dtype=opt_dtype, sharding_mode=sharding_mode)
    L = cfg.n_layers
    scale = (L - u) / u

    def corr(a, b):
        if a is None or b is None:
            return None
        return a + scale * (b - a)

    out["corrected"] = {
        "flops": corr(v1["flops"], v2["flops"]),
        "bytes_accessed": corr(v1["bytes_accessed"], v2["bytes_accessed"]),
        "collective_bytes": corr(v1["collectives"]["total_bytes"],
                                 v2["collectives"]["total_bytes"]),
    }
    out["correction"] = (f"layer-delta: unit={u}, C1={v1['flops']}, "
                         f"C2={v2['flops']}")
    return out


def _lower_lm_variant(cfg, shape_name: str, mesh, opt_dtype=None,
                      sharding_mode: str = "fsdp") -> dict:
    kind, seq_len, global_batch = SHAPES[shape_name]
    params_abs = _abstract_params(cfg)
    p_sh = param_sharding_rules(params_abs, mesh,
                                fsdp_params=sharding_mode == "fsdp")
    b_sh = batch_spec(mesh)

    def sds(tree, shardings=None):
        if shardings is None:
            return jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)
        return jax.tree.map(
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
            tree, shardings)

    enc_len = seq_len if cfg.enc_dec else None
    t0 = time.time()
    if kind == "train":
        import functools as _ft
        init_opt = _ft.partial(adamw_init, moments_dtype=opt_dtype) \
            if opt_dtype is not None else adamw_init
        opt_abs = jax.eval_shape(init_opt, params_abs)
        # moments shard like params (fsdp) or data-upgraded (zero1)
        from repro.dist.sharding import zero1_opt_sharding
        from repro.optim.adamw import OptState
        from jax.sharding import NamedSharding, PartitionSpec as P
        m_sh = zero1_opt_sharding(p_sh, params_abs, mesh) \
            if sharding_mode == "zero1" else p_sh
        opt_sh = OptState(step=NamedSharding(mesh, P()),
                          mu=m_sh, nu=m_sh)
        batch_specs = make_batch_specs(cfg, global_batch, seq_len,
                                       enc_len=enc_len)
        batch_abs = {k: jax.ShapeDtypeStruct(v.shape, v.dtype,
                                             sharding=b_sh)
                     for k, v in batch_specs.items()}
        fn = jax.jit(make_train_step(cfg),
                     in_shardings=(p_sh, opt_sh, None),
                     out_shardings=(p_sh, opt_sh, None),
                     donate_argnums=(0, 1))
        lowered = fn.lower(sds(params_abs, p_sh), sds(opt_abs, opt_sh),
                           batch_abs)
    elif kind == "prefill":
        batch_specs = make_batch_specs(cfg, global_batch, seq_len,
                                       enc_len=enc_len)
        batch_abs = {k: jax.ShapeDtypeStruct(v.shape, v.dtype,
                                             sharding=b_sh)
                     for k, v in batch_specs.items()}
        fn = jax.jit(lambda p, b: prefill(p, b, cfg, seq_len),
                     in_shardings=(p_sh, None))
        lowered = fn.lower(sds(params_abs, p_sh), batch_abs)
    elif kind == "decode":
        cache_abs = jax.eval_shape(
            lambda: init_cache(cfg, global_batch, seq_len,
                               enc_len=seq_len if cfg.enc_dec else 0))
        c_sh = cache_sharding_rules(cache_abs, mesh)
        tok = jax.ShapeDtypeStruct((global_batch, 1), jnp.int32)
        t_spec = jax.ShapeDtypeStruct((), jnp.int32)
        fn = jax.jit(
            lambda p, c, tk, t: decode_step(p, c, tk, t, cfg),
            in_shardings=(p_sh, c_sh, None, None),
            donate_argnums=(1,))
        lowered = fn.lower(sds(params_abs, p_sh), sds(cache_abs, c_sh),
                           tok, t_spec)
    else:
        raise ValueError(kind)

    compiled = lowered.compile()
    return _analyze(lowered, compiled, time.time() - t0)


# --------------------------------------------------------------------------
# HE cells (the paper's workload)
# --------------------------------------------------------------------------

def lower_he_cell(batch: int, mesh, *, logq=None) -> dict:
    from repro.configs.heaan_mul import CONFIG as HEP
    from repro.dist import he_pipeline as hp
    from repro.dist.sharding import he_limb_sharding
    logq = HEP.logQ if logq is None else logq
    st = hp.he_static(HEP, logq)
    step = hp.make_he_mul_step(st, mesh)
    t1, t2, ek = hp.he_table_specs(st)
    cts = hp.he_input_specs(st, batch)
    ct_sh = he_limb_sharding(mesh, batch=batch)
    cts = tuple(jax.ShapeDtypeStruct(c.shape, c.dtype, sharding=ct_sh)
                for c in cts)
    t0 = time.time()
    fn = jax.jit(step)
    lowered = fn.lower(t1, t2, ek, *cts)
    compiled = lowered.compile()
    return _analyze(lowered, compiled, time.time() - t0)


# the FULL served op table (analysis.dataflow.OPS — mul, add, sub,
# rotate, conjugate, slot_sum, rescale, mod_down, mul_plain, add_plain);
# the lowering itself lives in launch.cells (no import side effects, so
# tests and repro.analysis.xla use it in-process) and is re-exported
# here for the dry-run drivers and older callers.
from repro.launch.cells import (  # noqa: F401, E402
    HE_SERVING_OPS, serving_op_levels,
    lower_he_serving_cell as _lower_serving,
)


def lower_he_serving_cell(op: str, batch: int, mesh, *, logq=None,
                          params=None) -> dict:
    """Lower + compile one hserve engine step with abstract tables and
    return its analysis record (`launch.cells.lower_he_serving_cell`
    does the lowering; see its docstring for the per-op contracts)."""
    t0 = time.time()
    lowered = _lower_serving(op, batch, mesh, logq=logq, params=params)
    compiled = lowered.compile()
    return _analyze(lowered, compiled, time.time() - t0)


# --------------------------------------------------------------------------
# driver
# --------------------------------------------------------------------------

def run_cells(archs, shapes, *, multipod: bool, he: bool, he_batches,
              out_path: str):
    mesh = make_production_mesh(multi_pod=multipod)
    mesh_name = "pod2x16x16" if multipod else "pod16x16"
    results = []
    with open(out_path, "a") as f:
        def emit(rec):
            results.append(rec)
            f.write(json.dumps(rec) + "\n")
            f.flush()
            status = "OK" if rec.get("ok") else "FAIL"
            print(f"[{status}] {rec['cell']} ({mesh_name}) "
                  f"flops={rec.get('analysis', {}).get('flops')} "
                  f"coll={rec.get('analysis', {}).get('collectives', {}).get('total_bytes')} "
                  f"t={rec.get('analysis', {}).get('compile_seconds')}s",
                  flush=True)

        if he:
            for b in he_batches:
                rec = {"cell": f"heaan_mul/he_mul_b{b}", "mesh": mesh_name}
                try:
                    rec["analysis"] = lower_he_cell(b, mesh)
                    rec["ok"] = True
                except Exception as e:
                    rec["ok"] = False
                    rec["error"] = f"{type(e).__name__}: {e}"
                    rec["traceback"] = traceback.format_exc()[-2000:]
                emit(rec)
            # the serving engine's op set (one batch size is enough for
            # the collective matrix; slot_sum is log2(N/2) key switches)
            for op in HE_SERVING_OPS:
                rec = {"cell": f"heaan_mul/he_{op}_b{he_batches[0]}",
                       "mesh": mesh_name}
                try:
                    rec["analysis"] = lower_he_serving_cell(
                        op, he_batches[0], mesh)
                    rec["ok"] = True
                except Exception as e:
                    rec["ok"] = False
                    rec["error"] = f"{type(e).__name__}: {e}"
                    rec["traceback"] = traceback.format_exc()[-2000:]
                emit(rec)
        for arch in archs:
            valid = get_shapes(arch)
            for shape in shapes:
                if shape not in SHAPES:
                    continue
                if shape not in valid:
                    emit({"cell": f"{arch}/{shape}", "mesh": mesh_name,
                          "ok": True, "skipped": True,
                          "reason": "architecturally unsupported "
                                    "(DESIGN.md §6)"})
                    continue
                rec = {"cell": f"{arch}/{shape}", "mesh": mesh_name}
                try:
                    # roofline cost-correction variants: single-pod only
                    # (the roofline table is single-pod per the assignment)
                    rec["analysis"] = lower_lm_cell(
                        arch, shape, mesh, cost_correct=not multipod)
                    rec["ok"] = True
                except Exception as e:
                    rec["ok"] = False
                    rec["error"] = f"{type(e).__name__}: {e}"
                    rec["traceback"] = traceback.format_exc()[-2000:]
                emit(rec)
    n_fail = sum(1 for r in results if not r.get("ok"))
    print(f"\n{mesh_name}: {len(results) - n_fail}/{len(results)} cells OK")
    return n_fail


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multipod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--he", action="store_true",
                    help="include the HEAAN HE-Mul cells")
    ap.add_argument("--he-only", action="store_true")
    ap.add_argument("--he-batches", default="16,64")
    ap.add_argument("--out", default="dryrun_results.jsonl")
    args = ap.parse_args()

    archs = [args.arch] if args.arch else list(ARCHS)
    shapes = [args.shape] if args.shape else list(SHAPES)
    if args.he_only:
        archs, shapes = [], []
    he_batches = [int(b) for b in args.he_batches.split(",")]
    include_he = args.he or args.he_only

    fails = run_cells(archs, shapes, multipod=args.multipod,
                      he=include_he, he_batches=he_batches,
                      out_path=args.out)
    if args.both_meshes:
        fails += run_cells(archs, shapes, multipod=not args.multipod,
                           he=include_he, he_batches=he_batches,
                           out_path=args.out)
    sys.exit(1 if fails else 0)


if __name__ == "__main__":
    main()
