"""Padded slots over all slots of the batches dispatched in the window.

Counted by the benchmark from the batches it got back: each poll that
answers returns one batch, whose answers fill n_valid of its `batch`
lanes.
"""


def read(rec):
    b = [x for x in rec["batches"] if x[3] <= rec["window_s"]]
    if not b:
        return None
    return sum(rec["batch"] - x[2] for x in b) / (rec["batch"] * len(b))
