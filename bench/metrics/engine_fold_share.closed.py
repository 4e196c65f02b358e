"""Share of the traced serving window the engine's worker spent folding
labelled feedback into the served state (the ``engine.fold`` spans of
``repro.spans``: the fold program, its post-fold check and the repack)."""


def read(r):
    try:
        from repro import spans
    except ImportError:             # a program without host spans
        return None
    recs = spans.recorded()
    if not recs or r.window_s <= 0:
        return None
    return 100.0 * spans.total(recs, "engine.fold") / r.window_s
