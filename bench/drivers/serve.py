"""Online-learning serving: single-image requests and labelled feedback
through ``BCPNNService``, in a closed loop (a fixed number of requests in
flight).

Traffic parameters (``bench/traffic/<mix>.json``):

* ``in_flight``       -- requests kept outstanding;
* ``feedback_share``  -- chance that an arrival also sends one labelled
                         image from the feedback pool;
* ``pool``, ``feedback_pool`` -- seeded images the requests and the
                         feedback are drawn from, uniformly;
* ``setup_fit_images``, ``setup_fit_epochs``, ``batch`` -- the
                         ``Trainer.fit`` that builds the served state;
* ``engine``          -- ``BCPNNService`` arguments;
* ``warmup_s``        -- the same traffic before the window, so every
                         program (each bucket, the fold) has run once;
* ``sample``          -- requests of the window compared with the
                         reference.

Latency runs from each request's submission to its result in hand; a
request that fails or never completes counts as infinitely late.
"""
from __future__ import annotations

import collections
import time

import numpy as np

from bench import data, harness, work
from bench.checks import change_gaps, pred_gap, worst_stats
from bench.drivers.train_fit import program_config, snapshot
from bench.references import bcpnn as ref

COUNTERS = ("submitted", "completed", "batches", "occupied_slots",
            "padded_slots", "learn_steps", "learn_samples", "failed", "shed",
            "rejected", "crashes", "bisects", "feedback_dropped")
RESULT_WAIT_S = 60.0


class Log:
    """Per-request record of one loop, in preallocated arrays (a list of
    Python objects per request would grow the heap that the interpreter's
    collector walks, and stall the engine's thread with it)."""

    FIELDS = {"idx": np.int32, "start": np.float64, "done": np.float64,
              "pred": np.int32, "folds_at_submit": np.int32,
              "folds_at_result": np.int32}

    def __init__(self, cap: int = 1 << 16):
        self.n = 0
        self._a = {k: np.empty(cap, t) for k, t in self.FIELDS.items()}

    def add(self, i: int, t: float, folds: int) -> int:
        """Record a submission; returns its row."""
        if self.n == len(self._a["idx"]):
            self._a = {k: np.concatenate([v, np.empty_like(v)])
                       for k, v in self._a.items()}
        k = self.n
        for name, v in (("idx", i), ("start", t), ("folds_at_submit", folds),
                        ("done", np.inf),
                        ("pred", -1), ("folds_at_result", folds)):
            self._a[name][k] = v
        self.n += 1
        return k

    def __getattr__(self, name):
        if name in Log.FIELDS:
            return self._a[name][:self.n]
        raise AttributeError(name)


class Driver:
    def __init__(self, ctx: harness.Context):
        self.ctx = ctx
        self.cfg = ctx.config
        self.tf = ctx.traffic
        self.fb_sent = []      # feedback stream, in submission order

    # ------------------------------------------------------------ set-up --
    def setup(self) -> None:
        from repro.core import Trainer
        from repro.serve import BCPNNService

        cfg, tf = self.cfg, self.tf
        self.rng = np.random.default_rng(self.ctx.seed)
        self.x_fit, self.y_fit = data.encoded(cfg, tf["setup_fit_images"],
                                              self.rng)
        self.pool, _ = data.encoded(cfg, tf["pool"], self.rng)
        self.fb_x, self.fb_y = data.encoded(cfg, tf["feedback_pool"],
                                            self.rng)
        trainer = Trainer(program_config(cfg), seed=self.ctx.seed)
        with harness.span("bench.fit"):
            trainer.fit(self.x_fit, self.y_fit,
                        epochs=tf["setup_fit_epochs"], batch=tf["batch"])
        with self.ctx.excluded():
            self.served_from = snapshot(trainer.state)
        self.svc = BCPNNService(trainer.state, program_config(cfg),
                                **tf["engine"])
        self.svc.start()
        fb_batch = tf["engine"]["feedback_batch"]
        t_end = time.perf_counter() + tf["warmup_s"]
        # Warm up until every program has run: the buckets, and at least
        # one fold (with its post-fold checks).
        while (time.perf_counter() < t_end
               or self.svc.metrics.learn_steps < 1):
            self.closed_loop(min(1.0, tf["warmup_s"]), fb_min=fb_batch)

    # ------------------------------------------------------------- loops --
    def _draw(self, n: int):
        """Image index, feedback coin and feedback index of ``n`` ticks."""
        return (self.rng.integers(0, len(self.pool), n),
                self.rng.random(n) < self.tf["feedback_share"],
                self.rng.integers(0, len(self.fb_x), n))

    def _feedback(self, j: int) -> None:
        with harness.span("bench.feedback"):
            self.svc.feedback(self.fb_x[j], int(self.fb_y[j]))
        self.fb_sent.append(j)

    def _submit(self, log: Log, i: int, t: float):
        """Submit pool image ``i``; returns (row, request id)."""
        folds = self.svc.metrics.learn_steps
        with harness.span("bench.submit"):
            rid = self.svc.submit(self.pool[i])
        return log.add(i, t, folds), rid

    def _collect(self, log: Log, k: int, rid: int) -> None:
        try:
            with harness.span("bench.result"):
                r = self.svc.result(rid, timeout=RESULT_WAIT_S)
            log.done[k] = time.perf_counter()
            log.pred[k] = r.pred
        except Exception:  # failed, shed or lost: infinitely late
            pass
        log.folds_at_result[k] = self.svc.metrics.learn_steps

    def closed_loop(self, seconds: float, fb_min: int = 0) -> Log:
        """One client keeps ``in_flight`` requests outstanding; each
        completion releases the next submission."""
        in_flight = self.tf["in_flight"]
        n = 1 << 16
        log = Log(n)
        inflight = collections.deque()
        t0 = time.perf_counter()
        sent_fb = 0
        i = 0
        while True:
            if i % n == 0:
                idx, coin, fb = self._draw(n)
            if (time.perf_counter() - t0 >= seconds and sent_fb >= fb_min):
                break
            while len(inflight) < in_flight:
                j = i % n
                inflight.append(self._submit(log, int(idx[j]),
                                             time.perf_counter()))
                if coin[j] or sent_fb < fb_min:
                    self._feedback(int(fb[j]))
                    sent_fb += 1
                i += 1
                if i % n == 0:
                    idx, coin, fb = self._draw(n)
            self._collect(log, *inflight.popleft())
        while inflight:
            self._collect(log, *inflight.popleft())
        return log

    # ------------------------------------------------------------ window --
    def counters(self) -> dict:
        m = self.svc.metrics
        return {k: float(getattr(m, k)) for k in COUNTERS}

    def window(self, seconds: float) -> harness.WindowResult:
        c0 = self.counters()
        t0 = time.perf_counter()
        with harness.span("bench.window"):
            log = self.closed_loop(seconds)
            t_close = time.perf_counter()
            c1 = self.counters()
        self.log = log
        delta = {k: c1[k] - c0[k] for k in COUNTERS}
        done = np.asarray(log.done)
        start = np.asarray(log.start)
        lat_ms = (done - start) * 1e3
        answered = np.isfinite(done)
        cfg = self.cfg
        ni = cfg["input_hc"] * cfg["input_mc"]
        nj = cfg["hidden_hc"] * cfg["hidden_mc"]
        k = cfg["n_classes"]
        groups, images = delta["batches"], delta["occupied_slots"]
        served = work.served(ni, nj, k, groups, images, cfg["infer_dtype"])
        folds = work.sup_step(ni, nj, k, self.tf["engine"]["feedback_batch"])
        elapsed = t_close - t0
        in_window = int(np.sum(done <= t0 + seconds))
        metrics = {"serve_images_per_s": in_window / seconds}
        lines = [f"[bench] {len(done)} requests, {int(answered.sum())} "
                  f"answered, latency ms p50 "
                  f"{float(np.percentile(lat_ms[answered], 50))!r} p95 "
                  f"{float(np.percentile(lat_ms, 95))!r} p99 "
                  f"{float(np.percentile(lat_ms, 99))!r}",
                  f"[bench] engine counters over the window: {delta}",
                  f"[bench] feedback sent so far: {len(self.fb_sent)}"]
        return harness.WindowResult(
            metrics=metrics, attempted=len(done),
            failed=int(np.sum(~answered)), window_s=elapsed,
            work={"served": served, "folds": folds * delta["learn_steps"]},
            model_flops=images * work.model_flops_served(ni, nj, k),
            counters=delta, log=lines)

    def release(self) -> None:
        self.svc.stop()
        self.final = snapshot(self.svc.model_state())
        del self.svc

    # ------------------------------------------------------------- check --
    def check(self) -> list:
        """A seeded sample of the window's requests, each served class
        against the reference under every fold that may have served it
        (``pred_gap``); the final folded readout traces against the
        reference's replay of the whole feedback stream (``fold_gap_p99``,
        the worst leaf's 99th-percentile gap); and every request answered.
        The widest fold gap, the change-norm gap of the folds and the gaps
        of the set-up state are printed beside them, not compared."""
        import jax

        cfg, tf = self.cfg, self.tf
        states = replay(cfg, tf, self.ctx.seed, self.x_fit, self.y_fit,
                        self.fb_x, self.fb_y, self.fb_sent, "highest")
        log = self.log
        pick = np.random.default_rng(self.ctx.seed + 1).choice(
            log.n, size=min(log.n, tf["sample"]), replace=False)
        hidden = ref.hidden_of(states["state"], cfg, self.pool[log.idx[pick]])
        pgap = 0.0
        for row, k in enumerate(pick):
            if not np.isfinite(log.done[k]):
                continue
            lo = log.folds_at_submit[k]
            hi = max(lo, log.folds_at_result[k])
            p = ref.readout_probs(hidden[row:row + 1], states["w"][lo:hi + 1],
                                  states["b"][lo:hi + 1],
                                  cfg["n_classes"])[:, 0]   # (folds, K)
            pgap = max(pgap, float(np.min(pred_gap(
                p, np.full(len(p), log.pred[k])))))
        final = jax.tree_util.tree_map(np.asarray, states["final"])
        setup = jax.tree_util.tree_map(np.asarray, states["setup"])
        leaves = ("pi", "pj", "pij")
        fold = worst_stats(self.final, final, leaves, ("readout",))
        served = worst_stats(self.served_from, setup, leaves)
        change = change_gaps(
            {"readout": self.final["readout"]}, {"readout": final["readout"]},
            {"readout": self.served_from["readout"]},
            {"readout": setup["readout"]}, leaves)
        return ([harness.Check("unanswered",
                               float(np.sum(~np.isfinite(log.done))), None),
                 harness.Check("pred_gap", pgap, None),
                 harness.Check("fold_gap_p99", fold["p99"], None)]
                + [harness.Check(f"fold_gap_{k}", fold[k], None, False)
                   for k in ("max", "p999", "share")]
                + [harness.Check("fold_change_gap", max(change.values()),
                                 None, False)]
                + [harness.Check(f"setup_gap_{k}", served[k], None, False)
                   for k in ("max", "p99", "p999", "share")])


def fold_batches(items: list, batch: int) -> list:
    """The engine's fold compositions for a feedback stream folded in
    full batches, in order, the last one short and padded by cycling."""
    out = []
    for i in range(0, len(items), batch):
        chunk = items[i:i + batch]
        out.append([chunk[j % len(chunk)] for j in range(batch)])
    return out


def replay(cfg: dict, tf: dict, seed: int, x_fit, y_fit, fb_x, fb_y,
           fb_sent: list, prec: str) -> dict:
    """Reference replay: the set-up fit from the seed, then every fold of
    the feedback stream.  Returns the hidden projection, the readout's
    weights and biases after 0, 1, ... folds (``w``: (F+1, Nj, K)) and
    the final readout traces."""
    import jax
    import jax.numpy as jnp

    state = ref.init(jax.random.PRNGKey(seed), ref.geometry(cfg), cfg["eps"])
    state = ref.fit(state, cfg, x_fit, y_fit, tf["setup_fit_epochs"],
                    tf["batch"], prec)
    setup = state
    ws = [state["readout"]["w"]]
    bs = [state["readout"]["b"]]
    hp = ref.fold_hp(cfg)
    for chunk in fold_batches(fb_sent, tf["engine"]["feedback_batch"]):
        state = ref.fold(state, jnp.asarray(fb_x[chunk]),
                         jnp.asarray(fb_y[chunk]), hp,
                         prec)
        ws.append(state["readout"]["w"])
        bs.append(state["readout"]["b"])
    return {"state": state, "w": np.asarray(jnp.stack(ws)),
            "b": np.asarray(jnp.stack(bs)),
            "setup": setup, "final": state}
