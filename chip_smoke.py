#!/usr/bin/env python3
"""Serve the paper's batched HE Mul at Table III on one TPU chip.

    python3 chip_smoke.py              # one chip
    python3 chip_smoke.py --chips 4    # primes sharded over a (1, 4) mesh

Runs `repro.launch.serve.serve_he` — HESession → HEServer → OpEngine —
at `configs.heaan_mul.CONFIG` (logN=16, logQ=1200, β=2^32) with keys from
a fixed seed. One chip serves mul at logQ and one level below, one
rotate, one mul_plain, and the server-side rescale of every product; four
chips serve the mul stream alone. One result per (op, level) bucket must
equal `core` run on the CPU backend bit for bit, and every product must
decrypt within serve --he's 1e-2 gate. The last stdout line is a JSON
object naming the device; it is printed only when every phase passed.

Exits non-zero without that line when jax finds no TPU.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

GiB = 1 << 30


def serve_and_compare(params, batch: int, *, model_shards: int = 1,
                      mul_only: bool = False) -> dict:
    """Serve the smoke stream and check it; returns serve_he's stats.

    The stream: one batch of muls at logQ and one at logQ − logp, plus
    (unless `mul_only`) one mul_plain and one rotate; every product is
    rescaled by the server. serve_he compares one result per (op, level)
    bucket bitwise with `core` on the CPU backend and raises on a
    mismatch; this adds the 1e-2 decryption gate.
    """
    from repro.launch.serve import serve_he

    extra = 0 if mul_only else 2               # one mul_plain, one rotate
    requests = 2 * batch + extra
    stats = serve_he(batch, requests=requests, levels=2,
                     rotations=0 if mul_only else 1,
                     plain_frac=0.0 if mul_only else 1 / (requests - 1),
                     model_shards=model_shards, params=params)
    if stats["max_err"] >= 1e-2:
        raise AssertionError(
            f"decrypted products off by {stats['max_err']:.3e} (gate 1e-2)")
    return stats


def stream_bytes(params, mesh, batch: int) -> int:
    """Device bytes that serving the smoke stream at `batch` needs at once.

    The compiled mul step at logQ (arguments, outputs, temporaries), the
    rest of the resident TableCache (one more evk-sized key for the
    rotation, a second level's tables), and the stream's ciphertexts,
    which the session keeps on the device: at most four per request (two
    operands, the product, its rescale).
    """
    import jax

    from repro.dist import he_pipeline as hp
    from repro.dist.sharding import he_limb_sharding

    st = hp.he_static(params, params.logQ)
    # tables unplaced, as the engine passes them: the program is then the
    # one the engine compiles, and the persistent cache can serve it
    t1, t2, ek = hp.he_table_specs(st)
    ct = jax.ShapeDtypeStruct((batch, st.N, st.qlimbs), st.dtype,
                              sharding=he_limb_sharding(mesh, batch=batch))
    step = jax.jit(hp.make_he_mul_step(st, mesh))
    mem = step.lower(t1, t2, ek, ct, ct, ct, ct).compile().memory_analysis()

    def nbytes(tree):
        return sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(tree))

    cts = 4 * (2 * batch + 2) * 2 * nbytes(ct) // batch
    return (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes + nbytes(ek) + nbytes((t1, t2)) + cts)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: serve the mul stream with primes sharded "
                         "over a (1, 4) mesh, and nothing else")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (jax found {devices[0].platform}); "
              "nothing measured", file=sys.stderr)
        return 2
    if len(devices) % args.chips:
        print(f"chip_smoke: --chips {args.chips} does not divide the "
              f"{len(devices)} device(s) jax sees", file=sys.stderr)
        return 2

    from repro.configs.heaan_mul import CONFIG, HE_SHAPES
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import make_host_mesh

    print(f"compile cache: {enable_compile_cache()}")
    dev = devices[0]
    print(f"device_kind: {dev.device_kind} x{len(devices)}")
    big = HE_SHAPES["he_mul_b16"]["batch"]
    if args.chips == 1:
        limit = dev.memory_stats()["bytes_limit"]
        need = stream_bytes(CONFIG, make_host_mesh(), big)
        batch = big if need <= limit else big // 2
        print(f"batch {batch}: serving at batch {big} needs "
              f"{need / GiB:.2f} GiB of {limit / GiB:.2f} GiB")
    else:
        # the stream one chip serves (its batch-16 step does not fit one
        # chip); sharded over four it needs far less per device
        batch = big // 2
        print(f"batch {batch} over {args.chips} chips")

    t0 = time.perf_counter()
    stats = serve_and_compare(CONFIG, batch, model_shards=args.chips,
                              mul_only=args.chips > 1)
    print(f"mesh {stats['mesh']}; serve_he took "
          f"{time.perf_counter() - t0:.1f} s on the host clock (keygen, "
          "encryption, compiles, serving, decryption, CPU reference)")
    for bucket, s in stats["engine"]["compile_s_by_bucket"].items():
        print(f"compile {bucket}: {s} s")
    for op, d in stats["per_op"].items():
        print(f"{op}: {d['requests']} requests, pad {d['pad_frac']}")
    peaks = [d.memory_stats()["peak_bytes_in_use"] for d in devices]
    print("peak_bytes_in_use: " + ", ".join(str(p) for p in peaks))
    if min(peaks) < 0.25 * max(peaks):
        raise AssertionError(
            f"work did not spread over {len(devices)} devices: {peaks}")
    print(f"bitwise vs core on CPU: {stats['bitwise_checked']} results equal")
    print(f"max_err {stats['max_err']:.3e}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
