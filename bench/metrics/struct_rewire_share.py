"""Share of the traced training window spent in structural plasticity's
rewires: the mean length of the unsupervised ``trainer.epoch`` spans
whose learn steps held a rewire (their ``rewires`` arg, counted by the
trainer from the trace clock) less that of those that held none, per
rewire, times the rewires in the window, over the window
(``repro.spans``).  Nothing on a program whose epoch spans do not count
rewires."""


def read(r):
    try:
        from repro import spans
    except ImportError:             # a program without host spans
        return None
    epochs = [s for s in spans.recorded()
              if s.name == "trainer.epoch" and "rewires" in s.args
              and str(s.args.get("tag", "")).startswith("unsup/")]
    held = [s for s in epochs if s.args["rewires"] >= 1]
    none = [s for s in epochs if s.args["rewires"] == 0]
    if not held or not none or r.window_s <= 0:
        return None

    def mean(xs):
        return sum(xs) / len(xs)

    per_rewire = ((mean([s.dt for s in held]) - mean([s.dt for s in none]))
                  / mean([s.args["rewires"] for s in held]))
    rewires = sum(s.args["rewires"] for s in held)
    return 100.0 * per_rewire * rewires / r.window_s
