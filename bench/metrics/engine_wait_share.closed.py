"""Share of the traced serving window in which the engine's worker
found no work and blocked (the ``engine.wait`` spans of
``repro.spans``)."""


def read(r):
    try:
        from repro import spans
    except ImportError:             # a program without host spans
        return None
    recs = spans.recorded()
    if not recs or r.window_s <= 0:
        return None
    return 100.0 * spans.total(recs, "engine.wait") / r.window_s
