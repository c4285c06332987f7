"""Bytes handed to `device_put` per HE Mul batch, in MB (10^6 bytes).

The ``bytes`` of the program's ``hserve.h2d`` spans in the traced window
(`OpEngine._place` counts them where the copy is made, into the span and
into the ``engine.h2d_bytes`` registry counter; `bench/program_trace.py`
reads the span's) over the window's mul batches, the denominator of
`engine.mul_batch_ms.sat`.
"""

from bench import program_trace


def read(rec):
    m = rec["serve"]["per_op"].get("mul")
    prog = program_trace.for_record(rec)
    if not m or not m["batches"] or not prog or not prog["h2d_bytes"]:
        return None
    return prog["h2d_bytes"] / 1e6 / m["batches"]
