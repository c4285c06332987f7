"""Mean dispatch→ready wall of one HE Mul batch in the window, in ms.

The engine's own timing (`ServeMetrics` wall_s over batches for op
"mul"). On the synchronous path, the program's default, that is the
device time of one fused mul step plus its launch.
"""


def read(rec):
    m = rec["serve"]["per_op"].get("mul")
    if not m or not m["batches"]:
        return None
    return 1e3 * m["wall_s"] / m["batches"]
