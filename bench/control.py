#!/usr/bin/env python3
"""The benchmark's control: the plain reference put in the program's
place, one precision lower, must come out not correct.

    python3 bench/control.py --workload t3_mul_sat --seeds 11 12 13

For each seed it draws the cell's keys and pool as a run does, takes as
many requests per (op, level) bucket as a run checks (the first of each
bucket in the seed's traffic), answers them with the reference computed
in complex64 (float32) instead of complex128 (float64), and compares the
answers with the float64 reference word for word, as a run compares the
program's. It prints one JSON line per seed. The benchmark's own runs do
not run it.
"""

from __future__ import annotations

import argparse
import itertools
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import numpy as np  # noqa: E402

from bench import generator as G  # noqa: E402
from bench import run as R  # noqa: E402


def checked_requests(traffic: dict, seed: int):
    """As many requests per bucket as a run checks, from the seed's
    traffic: two of a single-bucket mix, else one of each bucket."""
    buckets = G.bucket_list(traffic)
    per = 2 if len(buckets) == 1 else 1
    stream = (G.closed_loop(traffic, seed) if traffic["loop"] == "closed"
              else iter(G.open_loop(traffic, 60.0, seed)))
    got = {b: [] for b in buckets}
    for req in itertools.islice(stream, 100000):
        b = (req.op, req.level)
        if len(got[b]) < per:
            got[b].append(req)
        if all(len(v) == per for v in got.values()):
            break
    return [r for b in buckets for r in got[b]]


def readings(cell: dict, seed: int) -> dict:
    params = R.make_params(cell["config"])
    traffic = cell["traffic"]
    mat = R.make_material(params, traffic, seed)
    ref = R.reference_for(params, mat)
    ctl = R.reference_for(params, mat, cdtype=np.complex64)
    words = meta = total = 0
    for req in checked_requests(traffic, seed):
        want = R.expected(ref, params, traffic, mat, req)
        got = R.expected(ctl, params, traffic, mat, req)
        w, m = R.compare(got, want)
        words += w
        meta += m
        total += want.ax.size + want.bx.size
    return {"seed": seed, "mismatched_words": words,
            "mismatched_levels": meta, "compared_words": total}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = R.load_cell(args.workload)
    for seed in args.seeds:
        t = time.perf_counter()
        out = readings(cell, seed)
        out["seconds"] = time.perf_counter() - t
        print(json.dumps({"workload": args.workload, **out}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
