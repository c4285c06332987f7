"""Host time handing a batch's operands to `device_put`, in ms per HE Mul
batch (`OpEngine._place`; the copy is asynchronous, so this is the part
of it the host does at once).

Total seconds of the program's ``hserve.h2d`` spans in the traced window
(`repro.obs.Tracer` mirrors them into the profiler's trace, on the
device's clock; `bench/program_trace.py` reads them) over the window's
mul batches, the denominator of `engine.mul_batch_ms.sat`.
"""

from bench import program_trace

SPAN = "hserve.h2d"


def read(rec):
    m = rec["serve"]["per_op"].get("mul")
    prog = program_trace.for_record(rec)
    s = prog["program_spans"].get(SPAN) if prog else None
    if not m or not m["batches"] or s is None:
        return None
    return 1e3 * s["s"] / m["batches"]
