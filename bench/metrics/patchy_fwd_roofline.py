"""Roofline share of the patchy forward kernel (``kernels/patchy.py::
patchy_forward``): the least time its calls in the window require
(``bench/work_struct.py``: the live weights and the input rows read
once, the rates written once, 2 B K Nj operations a call; the calls a
learn step makes are read from the program's own trace), over the
kernel's device time."""
from bench import work


def read(r):
    w = r.work.get("patchy_forward")
    if w is None or r.trace is None:
        return None
    t = r.trace.kernel_s.get("patchy_forward", 0.0)
    if t <= 0:
        return None
    return 100.0 * work.roofline_s(w, r.peak, r.chips) / t
