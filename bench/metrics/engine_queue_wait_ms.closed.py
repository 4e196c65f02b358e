"""Admission wait per served image: each request's time from its
admission to its group's dispatch, summed by the ``engine.group`` spans
(``wait_s``, ``repro.spans``) over the images they served, in
milliseconds."""


def read(r):
    try:
        from repro import spans
    except ImportError:             # a program without host spans
        return None
    recs = spans.recorded()
    n = spans.arg_total(recs, "engine.group", "n")
    if n <= 0:
        return None
    return 1e3 * spans.arg_total(recs, "engine.group", "wait_s") / n
