"""Share of the roofline over the device's busy time in serving: the
least time the window's served microbatches and feedback folds require
(work.py: each group reads the weights once), divided by the device
time in which an operation ran."""
from bench import work


def read(r):
    served, folds = r.work.get("served"), r.work.get("folds")
    if served is None or r.trace is None or r.trace.busy_s <= 0:
        return None
    return 100.0 * work.roofline_s(served + folds, r.peak, r.chips) \
        / r.trace.busy_s
