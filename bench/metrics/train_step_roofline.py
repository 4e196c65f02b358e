"""Share of the roofline over the device's busy time in training: the
least time the window's learn steps require (work.py: each step reads
and writes the joint trace once and does its products), over the chips
used, divided by the device time in which an operation ran."""
from bench import work


def read(r):
    w = r.work.get("train_steps")
    if w is None or r.trace is None or r.trace.busy_s <= 0:
        return None
    return 100.0 * work.roofline_s(w, r.peak, r.chips) / r.trace.busy_s
