"""Share of the traced serving window in which no operation ran on the
device (1 - busy union / window), averaged over the chips used."""


def read(r):
    if r.trace is None or r.trace.busy_s <= 0:
        return None
    return 100.0 * (1.0 - r.trace.busy_s / r.trace.window_s)
