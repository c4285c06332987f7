"""Work per served op, from the paper's Table IV operation counts.

Copied from `benchmarks/opcount_model.py` (function_op_counts), so that
the benchmark's yardstick does not move with the program. Counts are per
polynomial transform:

  CRT : N·qLimbs·np mul + N·np modmul + N·qLimbs·np ADC
  NTT : np·(N/2)·logN modmul + np·N·logN add/sub
  iNTT: np·((N/2)·logN + N) modmul + np·N·logN add/sub
  iCRT: N·np·PLimbs mul + 2·N·np modmul + N·np·PLimbs ADC

Per op, the transforms are (region 1 with np1 primes, region 2 with np2):

  mul       4 CRT+NTT, 3 iNTT+iCRT at np1; 1 CRT+NTT, 2 iNTT+iCRT at np2
  rotate    1 CRT+NTT, 2 iNTT+iCRT at np2
  mul_plain 3 CRT+NTT, 2 iNTT+iCRT at np1
  rescale   none (a limb shift)
"""

from __future__ import annotations

from typing import Dict

__all__ = ["TRANSFORMS", "function_op_counts", "op_counts"]

# op -> ((region, n_crt_ntt, n_intt_icrt), ...)
TRANSFORMS = {
    "mul": ((1, 4, 3), (2, 1, 2)),
    "rotate": ((2, 1, 2),),
    "mul_plain": ((1, 3, 2),),
    "rescale": (),
}


def function_op_counts(N: int, logN: int, qlimbs: int, npn: int,
                       plimbs: int) -> Dict[str, Dict[str, float]]:
    return {
        "CRT": {"mul": N * qlimbs * npn, "modmul": N * npn,
                "adc": N * qlimbs * npn, "addsub": 0},
        "NTT": {"mul": 0, "modmul": npn * (N // 2) * logN, "adc": 0,
                "addsub": npn * N * logN},
        "iNTT": {"mul": 0, "modmul": npn * ((N // 2) * logN + N), "adc": 0,
                 "addsub": npn * N * logN},
        "iCRT": {"mul": N * npn * plimbs, "modmul": 2 * N * npn,
                 "adc": N * npn * plimbs, "addsub": 0},
    }


def op_counts(op: str, N: int, logN: int, qlimbs: int,
              np_by_region: Dict[int, int],
              plimbs_by_region: Dict[int, int]) -> Dict[str, float]:
    """Operations of one served op, summed per function (CRT, NTT, iNTT,
    iCRT) over its transforms, and the NTT count (forward + inverse)."""
    out = {"CRT": 0.0, "NTT": 0.0, "iNTT": 0.0, "iCRT": 0.0,
           "transforms": 0}
    for region, fwd, inv in TRANSFORMS[op]:
        c = function_op_counts(N, logN, qlimbs, np_by_region[region],
                               plimbs_by_region[region])
        for fn, times in (("CRT", fwd), ("NTT", fwd), ("iNTT", inv),
                          ("iCRT", inv)):
            out[fn] += times * sum(c[fn].values())
        out["transforms"] += (fwd + inv) * np_by_region[region]
    return out
