"""NamedSharding rule engines for every distributed surface of the repo.

All rules are *placement hints*: they never change values, only where XLA
puts them, so every sharded computation stays bitwise identical to its
single-device reference (integer limb arithmetic partitions exactly; iCRT's
f32 piece sums stay below 2^24, and its quotient estimate is followed by
exact ±1 corrections).

Axis convention (DESIGN.md §5, mirrors the paper's §V thread mapping):
  - "data":  batches — ciphertext pairs per HE-Mul step, LM examples.
  - "model": the np CRT primes of the HE pipeline (HEAX's per-modulus
             lanes), and tensor-parallel dims of LM weights.
  - "pod":   optional outer data axis on multi-pod meshes.
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def data_axes(mesh: Mesh) -> Tuple[str, ...]:
    """Batch axes of a mesh: ("pod", "data") on multi-pod, else ("data",)."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def _axis_size(mesh: Mesh, names) -> int:
    if names is None:
        return 1
    if isinstance(names, str):
        names = (names,)
    size = 1
    for n in names:
        size *= mesh.shape[n]
    return size


# --------------------------------------------------------------------------
# HE pipeline placements
# --------------------------------------------------------------------------

def he_limb_sharding(mesh: Mesh, batch: Optional[int] = None
                     ) -> NamedSharding:
    """Placement for batched ciphertext limb arrays (B, N, qlimbs).

    The batch goes on the data axes; N and the limb axis stay local — the
    pipeline re-shards its eval-domain intermediates (B, np, N) with np on
    "model" internally. When `batch` is given and does not divide across
    the data axes, falls back to replicated (correct, just not scaled).
    """
    axes = data_axes(mesh)
    if not axes:
        return NamedSharding(mesh, P())
    if batch is not None and batch % _axis_size(mesh, axes) != 0:
        return NamedSharding(mesh, P())
    return NamedSharding(mesh, P(axes))


def he_eval_sharding(mesh: Mesh) -> NamedSharding:
    """Placement for eval-domain residue tensors (B, np, N): batch on the
    data axes, the CRT primes on "model" (the paper's prime-per-thread
    pinning, §V-A)."""
    axes = data_axes(mesh)
    model = "model" if "model" in mesh.axis_names else None
    return NamedSharding(mesh, P(axes if axes else None, model))


# --------------------------------------------------------------------------
# HE collective predictions (what the placements above IMPLY on the wire)
# --------------------------------------------------------------------------

# iCRT cross-prime reductions per served op, split by Fig. 2 region:
# (region-1 reductions at np1 primes, region-2 reductions at np2 primes).
# mul: from_eval for d0/d1/d2 in region 1 + the key switch's ks_ax/ks_bx
# in region 2; rotate/conjugate: the key switch only; slot_sum: one key
# switch (2 outputs) per doubling round; mul_plain: region 1 only (da,
# db); the limb-linear ops never leave the coefficient domain.
_HE_ICRT_REDUCTIONS = {
    "mul": (3, 2),
    "rotate": (0, 2),
    "conjugate": (0, 2),
    "mul_plain": (2, 0),
}


def _slot_sum_rounds(n_slots: int) -> int:
    """Doubling rounds of the slot_sum ladder (1, 2, 4, … < n_slots)."""
    rounds, r = 0, 1
    while r < n_slots:
        rounds += 1
        r *= 2
    return rounds


def mesh_collective_groups(mesh: Mesh) -> dict:
    """Device-id replica groups a collective over each named mesh axis
    would use — the oracle shardlint classifies measured HLO replica
    groups against (a group set matching no axis = layout churn)."""
    import numpy as np
    ids = np.vectorize(lambda d: d.id)(mesh.devices)
    out = {}
    for i, name in enumerate(mesh.axis_names):
        moved = np.moveaxis(ids, i, -1).reshape(-1, ids.shape[i])
        out[str(name)] = sorted(tuple(int(x) for x in row)
                                for row in moved)
    return out


def he_expected_collectives(op: str, mesh: Mesh, params, logq: int, *,
                            batch: int, n_slots: Optional[int] = None
                            ) -> dict:
    """Predicted collective schedule of one served (op, level) cell under
    the placements above, with the default "gemm8" iCRT strategy at
    β = 2^32.

    Only iCRT's cross-prime accumulation communicates: every residue
    tensor is (B, np, N) with np on "model", every stage before iCRT is
    prime-pointwise, and the batch axes make every op batch-pointwise —
    so each iCRT reduction lowers to EXACTLY one all-reduced tensor over
    the model-axis groups (XLA may combine several into fewer tuple
    instructions; `launch.hlo_analysis` counts tensors):

      f32[B_local, 4, N, 4·(plimbs + 2)]   the partial sums of the
          byte-piece GEMM (`core.crt._accum_gemm8`): 4 pieces of x_j
          against 4 pieces of each table column, the plimbs limbs of
          P/p_j (from `core.context.build_icrt_tables`) and the two
          limbs of ⌊β²/p_j⌋ that give the quotient.

    Wire bytes use the same ring model as `launch.hlo_analysis`
    (all-reduce = 2·S·(g−1)/g per device); B_local is the per-data-shard
    batch (the full batch when it doesn't divide — `he_limb_sharding`
    falls back to replicated). With model-axis size 1 the partitioner
    elides every reduction: zero collectives of any kind.

    One tolerated side channel: below logQ, key-switch ops slice the
    stored (np2_max, N) evk/Galois tables to [:np2] rows, and GSPMD
    rebalances the model-sharded row axis with small collective-permutes
    — exactly 4 per consumed key table (ax/bx × value/shoup), each
    moving at most one destination shard of rows (⌈np2/g⌉·N limbs). The
    returned "allowed" block bounds them so shardlint can wave them
    through without opening the door to real resharding regressions.
    """
    from repro.core.context import build_icrt_tables
    g = mesh.shape.get("model", 1)
    dsize = _axis_size(mesh, data_axes(mesh))
    b_local = batch // dsize if dsize and batch % dsize == 0 else batch
    rounds = _slot_sum_rounds(n_slots if n_slots else params.n_slots_max)
    if op == "slot_sum":
        red = (0, 2 * rounds)
    else:
        red = _HE_ICRT_REDUCTIONS.get(op, (0, 0))
    n_red = sum(red)
    n_keys = {"mul": 1, "rotate": 1, "conjugate": 1,
              "slot_sum": rounds}.get(op, 0)
    np2, np2_max = params.np_region2(logq), params.np_region2(params.logQ)
    allowed = {}
    if n_keys and g > 1 and np2 < np2_max:
        limb_bytes = 4 if params.beta_bits <= 32 else 8
        allowed["collective-permute"] = {
            "max_count": 4 * n_keys,
            "max_bytes_each": -(-np2 // g) * params.N * limb_bytes,
        }
    if g <= 1 or n_red == 0:
        return {"kinds": [], "counts": {}, "wire_bytes": 0.0,
                "n_reductions": n_red, "axis": "model", "group_size": g,
                "allowed": {}}

    def ring(size: float) -> float:
        return 2.0 * size * (g - 1) / g

    per_region = []
    total = 0.0
    for n_r, npn in zip(red, (params.np_region1(logq),
                              params.np_region2(logq))):
        if not n_r:
            continue
        plimbs = build_icrt_tables(params, npn).plimbs
        one = ring(b_local * 4 * params.N * 4 * (plimbs + 2) * 4)
        per_region.append({"reductions": n_r, "np": npn,
                           "plimbs": plimbs, "bytes_per_reduction": one})
        total += n_r * one
    return {"kinds": ["all-reduce"], "counts": {"all-reduce": n_red},
            "wire_bytes": total, "n_reductions": n_red, "axis": "model",
            "group_size": g, "per_region": per_region, "allowed": allowed}


def batch_spec(mesh: Mesh) -> NamedSharding:
    """LM batch placement: leading (batch) dim over the data axes."""
    axes = data_axes(mesh)
    return NamedSharding(mesh, P(axes if axes else None))


# --------------------------------------------------------------------------
# LM parameter / cache / optimizer placements
# --------------------------------------------------------------------------

# Leaf or parent names whose weights are column-parallel (output dim on
# "model") vs row-parallel (input dim on "model", megatron-style so the
# matmul pair needs one collective, not two).
_COL_PARALLEL = frozenset({
    "wq", "wk", "wv", "wi", "wg", "in_proj", "in_x", "in_y", "x_proj",
    "dt_proj", "gate_a", "gate_x", "router", "lm_head",
})
_ROW_PARALLEL = frozenset({"wo", "out_proj", "out"})
_EMBED = frozenset({"tok_embed"})


def _path_names(path) -> list:
    out = []
    for k in path:
        if hasattr(k, "key"):
            out.append(str(k.key))
        elif hasattr(k, "name"):
            out.append(str(k.name))
        elif hasattr(k, "idx"):
            out.append(str(k.idx))
        else:
            out.append(str(k))
    return out


def _model_dim(names: list, shape: Tuple[int, ...]) -> Optional[int]:
    """Which dim of this leaf carries the tensor-parallel "model" axis."""
    if len(shape) < 2:
        return None
    tagged = [n for n in names if n in _COL_PARALLEL | _ROW_PARALLEL
              | _EMBED]
    if tagged:
        tag = tagged[-1]
        if tag in _ROW_PARALLEL:
            return len(shape) - 2
        if tag in _EMBED:
            return len(shape) - 2      # vocab dim of (V, D)
        return len(shape) - 1          # column-parallel: output dim
    # Unknown ≥2-d leaf (conv filters, SSM A_log, ...): largest dim.
    return max(range(len(shape)), key=lambda d: shape[d])


def param_sharding_rules(params: Any, mesh: Mesh, *,
                         fsdp_params: bool = True) -> Any:
    """Pytree of NamedShardings for model params.

    Tensor-parallel dim (by name orientation, falling back to largest-dim)
    goes on "model"; with `fsdp_params`, the largest remaining divisible
    dim goes on "data" (FSDP). Scalars, vectors, and non-divisible dims
    stay replicated — placement never fails, it only degrades.
    """
    msize = mesh.shape.get("model", 1)
    dsize = mesh.shape.get("data", 1)

    def rule(path, leaf):
        shape = leaf.shape
        spec: list = [None] * len(shape)
        if len(shape) >= 2:
            md = _model_dim(_path_names(path), shape)
            if md is not None and shape[md] % msize == 0 \
                    and shape[md] >= msize and shape[md] > 1:
                spec[md] = "model"
            if fsdp_params:
                free = [d for d in range(len(shape)) if spec[d] is None
                        and shape[d] % dsize == 0 and shape[d] >= dsize
                        and shape[d] > 1]
                if free:
                    spec[max(free, key=lambda d: shape[d])] = "data"
        return NamedSharding(mesh, P(*spec))

    return jax.tree_util.tree_map_with_path(rule, params)


def cache_sharding_rules(cache: Any, mesh: Mesh) -> Any:
    """Pytree of NamedShardings for KV / recurrent decode caches.

    The batch dim (0, or 1 under a stacked/scanned layer axis) goes on
    "data"; of the remaining dims, prefer the head dim (-2) and otherwise
    the largest divisible dim for "model".
    """
    msize = mesh.shape.get("model", 1)
    dsize = mesh.shape.get("data", 1)

    def rule(path, leaf):
        shape = leaf.shape
        names = _path_names(path)
        spec: list = [None] * len(shape)
        bdim = 1 if names and names[0] in ("stacked", "groups") else 0
        if len(shape) > bdim and shape[bdim] % dsize == 0 \
                and shape[bdim] >= dsize and shape[bdim] > 1:
            spec[bdim] = "data"
        cands = [d for d in range(bdim + 1, len(shape))
                 if spec[d] is None and shape[d] % msize == 0
                 and shape[d] >= msize and shape[d] > 1]
        if cands:
            head = len(shape) - 2
            spec[head if head in cands else
                 max(cands, key=lambda d: shape[d])] = "model"
        return NamedSharding(mesh, P(*spec))

    return jax.tree_util.tree_map_with_path(rule, cache)


def zero1_opt_sharding(p_sh: Any, params: Any, mesh: Mesh) -> Any:
    """ZeRO-1 moment placement: params' sharding plus the "data" axis on
    the largest still-unsharded divisible dim (optimizer state is never
    needed unsharded, so moments can always be FSDP'd even when params
    are kept gathered for compute)."""
    dsize = mesh.shape.get("data", 1)

    def rule(sh, leaf):
        spec = list(sh.spec) + [None] * (leaf.ndim - len(sh.spec))
        used = {a for s in spec if s is not None
                for a in ((s,) if isinstance(s, str) else s)}
        if "data" not in used:
            free = [d for d in range(leaf.ndim) if spec[d] is None
                    and leaf.shape[d] % dsize == 0 and leaf.shape[d] >= dsize
                    and leaf.shape[d] > 1]
            if free:
                spec[max(free, key=lambda d: leaf.shape[d])] = "data"
        return NamedSharding(mesh, P(*spec))

    return jax.tree_util.tree_map(rule, p_sh, params)
