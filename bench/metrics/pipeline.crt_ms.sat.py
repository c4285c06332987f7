"""Device time of the CRT stage (limbs to RNS residues), in ms per HE Mul
batch.

Own time of the mul step's device ops labelled ``he.crt`` in the traced
window, over the window's mul batches (the denominator of
`engine.mul_batch_ms.sat`). `dist.he_pipeline` wraps each stage call in
a `jax.named_scope`; `bench/program_trace.py` reads the scopes from the
op_name metadata of the step's HLO, which the trace holds.
"""

from bench import program_trace

STAGE = "crt"


def read(rec):
    m = rec["serve"]["per_op"].get("mul")
    prog = program_trace.for_record(rec)
    sc = prog["device_by_scope"] if prog else None
    if not m or not m["batches"] or not sc \
            or not any(sc[k] for k in ("crt", "ntt", "modmul", "icrt")):
        return None
    return 1e3 * sc[STAGE] / m["batches"]
