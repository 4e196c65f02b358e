"""Share of the served microbatches' slots that held a genuine request,
from the engine's counters (occupied / (occupied + padded))."""


def read(r):
    occ = r.counters.get("occupied_slots", 0.0)
    total = occ + r.counters.get("padded_slots", 0.0)
    if total <= 0:
        return None
    return 100.0 * occ / total
