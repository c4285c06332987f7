#!/usr/bin/env python3
"""Find the knee of an open-loop cell: the highest offered rate that the
server sustains without a growing backlog.

    python3 bench/sweep.py --workload t3_mul_sat \\
        --traffic bench/tests/mix.json --seed 5 --seconds 20 \\
        --rates 1 2 3 4 --max-age 0.1 0.25

One process sets the cell up once (with ``--traffic``, the cell's
deployment under another open-loop traffic file), then offers each rate (for each
flush deadline) for one window and prints a JSON line: the answered
count, latency p50 and p95, the requests still pending when the window
closed, and the seconds they took to drain. Run once, by hand, to set
the cell's rate, flush deadline and latency limit; the benchmark's own
runs do not run it.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import run as R  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--traffic", help="an open-loop traffic file to "
                    "serve in place of the cell's own")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--max-age", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    import jax
    jax.config.update("jax_compilation_cache_dir", str(R.CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    cell = R.load_cell(args.workload)
    if args.traffic:
        cell["traffic"] = json.loads(Path(args.traffic).read_text())
    su = R.setup(cell, args.seed)
    for age in args.max_age:
        su.server.max_age_s = age
        for rate in args.rates:
            traffic = dict(su.traffic, rate_per_s=rate, max_age_s=age,
                           grace_s=120)
            su.server.reset_metrics()
            sampler = R.Sampler(traffic, su.batch, args.seed)
            t = time.perf_counter()
            rec = R.run_open(su.server, su.submit, traffic, args.seconds,
                             args.seed, sampler, False)
            end = rec.t0 + rec.window_s
            late = [b for b in rec.batches if b[3] > rec.window_s]
            lat = sorted(rec.latencies_s)
            print(json.dumps({
                "rate_per_s": rate, "max_age_s": age,
                "attempted": rec.attempted, "failed": rec.failed,
                "p50_ms": 1e3 * R._pct(lat, 50),
                "p95_ms": 1e3 * R._pct(lat, 95),
                "max_ms": 1e3 * lat[-1],
                "batches": len(rec.batches),
                "batches_after_close": len(late),
                "drain_s": (late[-1][3] - rec.window_s) if late else 0.0,
                "mean_valid": sum(b[2] for b in rec.batches)
                / max(1, len(rec.batches)),
                "lateness_p95_ms": 1e3 * R._pct(rec.lateness_s, 95),
                "seconds": time.perf_counter() - t,
                "window_end": end - rec.t0,
            }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
