"""Level-aware resident table cache for the HE serving runtime.

A multi-level circuit touches many moduli logq < logQ, and a naive server
rebuilds + re-uploads `region_tables` per level. But almost everything in
a region-table pytree is prime-pool state (twiddles, Montgomery/Shoup
constants, CRT rows): at level logq those arrays are STRICT row/column
slices of the top level's — the table set Medha keeps resident on chip.
So this cache:

  - materializes the prime-pool tables ONCE on device, at full
    (max_np, ·) shapes (the `resident` pytree), and serves every level's
    region-1/2 tables as row slices ``[:np]`` (plus a column slice
    ``[:qlimbs]`` for the CRT rows);
  - caches the few genuinely per-np entries (the iCRT tables, which
    depend on P = ∏ first-np primes) keyed by np — shared across every
    level and region that lands on the same prime count;
  - holds the evaluation key, any rotation keys, and the conjugation key
    as device pytrees in `dist.he_pipeline.evk_tables` form (the engine
    slices key rows ``[:np2]`` per level inside the step). This is
    Medha's resident-key design: every Galois key is just another
    evk-shaped pytree riding the same region-2 machinery.

The sliced pytrees are value-identical to a freshly built
``runtime_tables(make_context(params, logq), evk)`` at every level
(tests/test_hserve.py asserts array equality), so serving from the cache
cannot change a single output bit.

A note on ``quot_fix`` (present in the region tables since the Pallas
kernel routing landed): it is the table of ⌊β²/p_j⌋ as two β-bit limbs,
one row per prime — the fixed-point reciprocal the served "gemm8" iCRT
and the Pallas iCRT kernel use to estimate the accumulator quotient
where the other strategies use an f64 multiply (TPUs have no f64; see
`core/crt.py`, `kernels/icrt/icrt.py` and `IcrtTables.quot_fix` in
`core/context.py`). Although it is built by
``build_icrt_tables``, it depends only on the prime — not on
P = ∏ primes — so unlike the other iCRT entries it row-slices from the
resident set exactly like the prime-pool tables (``_ROW_KEYS`` below),
and one resident copy serves every level and region.
"""

from __future__ import annotations

from collections import OrderedDict
from typing import Dict, Optional, Tuple

import numpy as np

import jax.numpy as jnp

from repro.core.cipher import EvalKey
from repro.core.context import build_global_tables, build_icrt_tables
from repro.core.params import HEParams
from repro.dist.he_pipeline import evk_tables

__all__ = ["PlainCache", "TableCache"]


class PlainCache:
    """LRU cache of encoded plaintext operands keyed by (hash, logq).

    Extracted from TableCache so the multi-host frontend — which owns
    the plain-operand cache but NO device tables (those live in the
    workers) — can hold one without materializing a table set. The
    ROADMAP "plaintext operand caching" story: affine-layer weights
    encode once, every later request references the hash.
    LRU-bounded (cap_mib; None = unbounded): a server fed per-request
    one-shot operands must not grow without limit.
    """

    def __init__(self, cap_mib: Optional[float] = 256.0):
        self._plain: "OrderedDict[Tuple[str, int], np.ndarray]" = \
            OrderedDict()
        self._cap = None if cap_mib is None else int(cap_mib * 2**20)
        self._bytes = 0
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    def put(self, h: str, logq: int, pt) -> np.ndarray:
        """Cache an encoded operand under (hash, logq); returns the
        resident copy. An existing entry wins (and counts a hit — the
        client re-sent an operand the server already held). The resident
        array is marked read-only, so the request queue can alias it
        instead of re-copying the (N, qlimbs) buffer on every submit
        that resolves from the cache."""
        key = (h, int(logq))
        if key in self._plain:
            self.hits += 1
            self._plain.move_to_end(key)
        else:
            self.misses += 1
            if isinstance(pt, np.ndarray) and not pt.flags.writeable \
                    and pt.base is None:
                arr = pt       # adopt an owned immutable buffer as-is
            else:              # (base check: a read-only VIEW can have
                arr = np.array(pt)            # a writeable base)
                arr.setflags(write=False)
            self._plain[key] = arr
            self._bytes += arr.nbytes
            # LRU eviction (never the entry just inserted). In-flight
            # circuits resolved their arrays at submit and keep their
            # own references, so eviction cannot break queued work —
            # only a LATER hash-only reference to an evicted key fails
            # (and re-registering it is always legal).
            while self._cap is not None and len(self._plain) > 1 \
                    and self._bytes > self._cap:
                _, old = self._plain.popitem(last=False)
                self._bytes -= old.nbytes
                self.evictions += 1
        return self._plain[key]

    def get(self, h: str, logq: int) -> np.ndarray:
        """The cached encoded operand for (hash, logq); KeyError (before
        anything is enqueued) when the client references a hash the
        server never saw at this level."""
        key = (h, int(logq))
        if key not in self._plain:
            raise KeyError(
                f"no cached plaintext for hash {h!r} at logq={logq}; "
                f"send the encoded operand once (pt=..., pt_hash=...) "
                f"before referencing it by hash alone")
        self.hits += 1
        self._plain.move_to_end(key)
        return self._plain[key]

    def has(self, h: str, logq: int) -> bool:
        return (h, int(logq)) in self._plain

    def __len__(self) -> int:
        return len(self._plain)

    @property
    def nbytes(self) -> int:
        return self._bytes

# Resident (prime-pool) entries: rows slice by np; crt rows also slice
# their limb column by the level's qlimbs.
_ROW_KEYS = ("primes", "psi_rev", "psi_rev_shoup", "ipsi_rev",
             "ipsi_rev_shoup", "n_inv", "n_inv_shoup", "pprime", "r2",
             "p_inv_f64", "quot_fix")
_ROWCOL_KEYS = ("crt_tb", "crt_tb_shoup")
# Per-np entries (depend on P = ∏ first-np primes; cached by np).
_ICRT_KEYS = ("inv_P", "inv_P_shoup", "pdivp", "P_limbs", "P_half_limbs")


class TableCache:
    """One resident device table set; per-level views by slicing."""

    def __init__(self, params: HEParams, evk: Optional[EvalKey] = None,
                 rot_keys: Optional[Dict[int, EvalKey]] = None,
                 conj_key: Optional[EvalKey] = None,
                 plain_cache_mib: Optional[float] = 256.0):
        self.params = params
        g = build_global_tables(params)
        top = build_icrt_tables(params, params.max_np)
        self._resident: Dict[str, jnp.ndarray] = {
            "primes": jnp.asarray(g.primes),
            "psi_rev": jnp.asarray(g.psi_rev),
            "psi_rev_shoup": jnp.asarray(g.psi_rev_shoup),
            "ipsi_rev": jnp.asarray(g.ipsi_rev),
            "ipsi_rev_shoup": jnp.asarray(g.ipsi_rev_shoup),
            "n_inv": jnp.asarray(g.n_inv),
            "n_inv_shoup": jnp.asarray(g.n_inv_shoup),
            "pprime": jnp.asarray(g.pprime),
            "r2": jnp.asarray(g.r2),
            "crt_tb": jnp.asarray(g.crt_tb),
            "crt_tb_shoup": jnp.asarray(g.crt_tb_shoup),
            "p_inv_f64": jnp.asarray(g.p_inv_f64),
            # ⌊β²/p⌋ depends only on the prime, so despite living in
            # IcrtTables it row-slices like the pool tables do
            "quot_fix": jnp.asarray(top.quot_fix),
        }
        self._icrt_dev: Dict[int, Dict[str, jnp.ndarray]] = {}
        self._levels: Dict[int, Tuple[Dict, Dict]] = {}
        self._ek = {k: jnp.asarray(v) for k, v in evk_tables(evk).items()} \
            if evk is not None else None
        self._rot = {
            int(r): {k: jnp.asarray(v) for k, v in evk_tables(rk).items()}
            for r, rk in (rot_keys or {}).items()}
        self._conj = {k: jnp.asarray(v)
                      for k, v in evk_tables(conj_key).items()} \
            if conj_key is not None else None
        self.hits = 0
        self.misses = 0
        # repro.obs.Tracer (optional): cold level_tables misses emit
        # "tables.level_slice" engine spans — the host-side build the
        # scheduler's prefetch hides behind the in-flight batch.
        self.tracer = None
        # encoded plaintext operands keyed by (message hash, logq) —
        # see PlainCache (extracted so the multi-host frontend can own
        # one without any device tables)
        self.plain = PlainCache(cap_mib=plain_cache_mib)

    # ---- per-level region tables ----------------------------------------

    def level_tables(self, logq: int) -> Tuple[Dict, Dict]:
        """(t1, t2) region-table pytrees for modulus 2^logq, as slices of
        the resident set. Cached per level; cheap on miss (no host
        rebuild, no re-upload of pool tables)."""
        if logq in self._levels:
            self.hits += 1
            return self._levels[logq]
        self.misses += 1
        span = self.tracer.span("tables.level_slice", cat="engine",
                                lane="engine", args={"logq": logq}) \
            if self.tracer is not None else None
        p = self.params
        K = p.qlimbs(logq)
        t1 = self._region_view(p.np_region1(logq), K)
        t2 = self._region_view(p.np_region2(logq), K)
        self._levels[logq] = (t1, t2)
        if span is not None:
            span.end()
        return t1, t2

    def has_level(self, logq: int) -> bool:
        """Whether 2^logq's slice views are already materialized — the
        circuit-aware scheduler's prefetch asks before warming a level
        behind the in-flight batch (`CircuitScheduler.prefetch_levels`)."""
        return logq in self._levels

    def _region_view(self, npn: int, K: int) -> Dict[str, jnp.ndarray]:
        t = {k: self._resident[k][:npn] for k in _ROW_KEYS}
        t.update({k: self._resident[k][:npn, :K] for k in _ROWCOL_KEYS})
        t.update(self._icrt(npn))
        return t

    def _icrt(self, npn: int) -> Dict[str, jnp.ndarray]:
        if npn not in self._icrt_dev:
            tabs = build_icrt_tables(self.params, npn)
            self._icrt_dev[npn] = {
                k: jnp.asarray(getattr(tabs, k)) for k in _ICRT_KEYS}
        return self._icrt_dev[npn]

    # ---- plaintext operands ----------------------------------------------

    def put_plain(self, h: str, logq: int, pt) -> np.ndarray:
        """Cache an encoded plaintext operand under (hash, logq); see
        :meth:`PlainCache.put`."""
        return self.plain.put(h, logq, pt)

    def get_plain(self, h: str, logq: int) -> np.ndarray:
        """The cached encoded operand for (hash, logq); see
        :meth:`PlainCache.get`."""
        return self.plain.get(h, logq)

    def has_plain(self, h: str, logq: int) -> bool:
        """Whether (hash, logq) is cached — `repro.client`'s compile pass
        asks this to skip the client-side encode entirely on reuse."""
        return self.plain.has(h, logq)

    @property
    def plain_hits(self) -> int:
        return self.plain.hits

    @property
    def plain_misses(self) -> int:
        return self.plain.misses

    @property
    def plain_evictions(self) -> int:
        return self.plain.evictions

    # ---- keys ------------------------------------------------------------

    def evk(self) -> Dict[str, jnp.ndarray]:
        if self._ek is None:
            raise ValueError("no evaluation key loaded (mul unavailable)")
        return self._ek

    def rot_key(self, r: int) -> Dict[str, jnp.ndarray]:
        try:
            return self._rot[int(r)]
        except KeyError:
            raise KeyError(
                f"no rotation key for r={r}; loaded: "
                f"{sorted(self._rot)}") from None

    def add_rot_key(self, r: int, rk: EvalKey) -> None:
        self._rot[int(r)] = {
            k: jnp.asarray(v) for k, v in evk_tables(rk).items()}

    def conj_key(self) -> Dict[str, jnp.ndarray]:
        if self._conj is None:
            raise ValueError(
                "no conjugation key loaded (conjugate unavailable)")
        return self._conj

    def add_conj_key(self, ck: EvalKey) -> None:
        self._conj = {k: jnp.asarray(v) for k, v in evk_tables(ck).items()}

    @property
    def has_conj_key(self) -> bool:
        return self._conj is not None

    @property
    def rotation_amounts(self):
        return sorted(self._rot)

    # ---- accounting ------------------------------------------------------

    def stats(self) -> dict:
        res_b = sum(int(v.size) * v.dtype.itemsize
                    for v in self._resident.values())
        icrt_b = sum(int(v.size) * v.dtype.itemsize
                     for d in self._icrt_dev.values() for v in d.values())
        key_b = sum(int(v.size) * v.dtype.itemsize
                    for d in ([self._ek] if self._ek else [])
                    + ([self._conj] if self._conj else [])
                    + list(self._rot.values()) for v in d.values())
        return {
            "levels_materialized": sorted(self._levels),
            "np_sets": sorted(self._icrt_dev),
            "rot_keys": self.rotation_amounts,
            "conj_key": self.has_conj_key,
            "hits": self.hits,
            "misses": self.misses,
            "plain_entries": len(self.plain),
            "plain_hits": self.plain.hits,
            "plain_misses": self.plain.misses,
            "plain_evictions": self.plain.evictions,
            "resident_mib": round(res_b / 2**20, 3),
            "icrt_mib": round(icrt_b / 2**20, 3),
            "keys_mib": round(key_b / 2**20, 3),
            "plain_mib": round(self.plain.nbytes / 2**20, 3),
        }
