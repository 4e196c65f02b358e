"""Share of the traced training window in which the trainer's thread
was inside ``Trainer.fit`` and not blocked on the device: the
``trainer.fit`` spans less the ``trainer.block`` spans inside them
(``repro.spans``), over the window."""


def read(r):
    try:
        from repro import spans
    except ImportError:             # a program without host spans
        return None
    recs = spans.recorded()
    fit = spans.total(recs, "trainer.fit")
    if fit <= 0 or r.window_s <= 0:
        return None
    blocked = spans.total(recs, "trainer.block", under="trainer.fit")
    return 100.0 * (fit - blocked) / r.window_s
