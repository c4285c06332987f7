"""Host time per HE Mul batch outside the device step, in ms.

(window seconds − the mul batches' dispatch→ready walls) / mul batches:
batch assembly, the host-to-device copy, result slicing and the
benchmark's own bookkeeping, in a window where every batch is a full
mul batch.
"""


def read(rec):
    m = rec["serve"]["per_op"].get("mul")
    if not m or not m["batches"]:
        return None
    return 1e3 * (rec["window_s"] - m["wall_s"]) / m["batches"]
