"""Host time in batch assembly, in ms per HE Mul batch: popping the bucket
and stacking the requests' operands into the fixed-shape batch.

Total seconds of the program's ``hserve.batch_assemble`` spans in the
traced window (`repro.obs.Tracer` mirrors them into the profiler's
trace, on the device's clock; `bench/program_trace.py` reads them) over
the window's mul batches, the denominator of `engine.mul_batch_ms.sat`.
"""

from bench import program_trace

SPAN = "hserve.batch_assemble"


def read(rec):
    m = rec["serve"]["per_op"].get("mul")
    prog = program_trace.for_record(rec)
    s = prog["program_spans"].get(SPAN) if prog else None
    if not m or not m["batches"] or s is None:
        return None
    return 1e3 * s["s"] / m["batches"]
