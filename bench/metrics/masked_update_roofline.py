"""Roofline share of the update kernel (``kernels/bcpnn_update.py``)
with the patchy mask on: the least time its calls in the window require
(``bench/work_struct.py``: the joint trace read and written once, the
(Hi, Hj) HC mask read once, not the expanded unit mask, and the
co-activation product; the readout's own calls of the kernel as
``work.learn`` counts them), over the kernel's device time."""
from bench import work


def read(r):
    w = r.work.get("bcpnn_update_pallas")
    if w is None or r.trace is None:
        return None
    t = r.trace.kernel_s.get("bcpnn_update_pallas", 0.0)
    if t <= 0:
        return None
    return 100.0 * work.roofline_s(w, r.peak, r.chips) / t
