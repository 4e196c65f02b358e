"""Host time of the serving engine's worker per served image: the
``engine.schedule``, ``engine.pad``, ``engine.dispatch`` and
``engine.complete`` spans over the images of the ``engine.group`` spans
(``repro.spans``), in microseconds."""


def read(r):
    try:
        from repro import spans
    except ImportError:             # a program without host spans
        return None
    recs = spans.recorded()
    n = spans.arg_total(recs, "engine.group", "n")
    if n <= 0:
        return None
    return 1e6 * spans.total(recs, "engine.schedule", "engine.pad",
                             "engine.dispatch", "engine.complete") / n
