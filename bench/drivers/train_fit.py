"""Training job: ``Trainer.fit`` of a Table-1 network, called back to
back with its state carried over.

Traffic parameters (``bench/traffic/<mix>.json``):

* ``batch``         -- rows per learn step;
* ``setup_fits``    -- fits run in set-up (the first compiles); the
                       reference replays exactly these;
* ``data_parallel`` -- chips of the ``data`` mesh axis (1: no mesh).

The configuration gives the dataset, its size (``n_train`` genuine
images, ``n_test`` probe images for the class-probability comparison)
and ``epochs``.  Inputs and initial weights are drawn from ``--seed``.

The window runs whole fits until ``--seconds`` have passed; the rate
counts every genuine image once for each epoch of each greedy phase it
passes through, over the time from the window's start to the end of the
last fit.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np

from bench import data, harness, work
from bench.checks import change_gaps, probs_gap, worst_stats
from bench.references import bcpnn as ref

LEAVES = ("pi", "pj", "pij")


def program_config(cfg: dict):
    """The program's ``BCPNNConfig`` for a configuration file."""
    from repro.core.network import BCPNNConfig

    names = {f.name for f in dataclasses.fields(BCPNNConfig)}
    return BCPNNConfig(**{k: v for k, v in cfg.items() if k in names})


def snapshot(state) -> dict:
    """Host copy of the learned state, in the reference's layout."""
    def proj(p):
        return {"pi": np.asarray(p.traces.pi), "pj": np.asarray(p.traces.pj),
                "pij": np.asarray(p.traces.pij), "w": np.asarray(p.w),
                "b": np.asarray(p.b)}
    return {"hidden": proj(state.projs[0]), "readout": proj(state.readout)}


def batch_rows(n: int, batch: int) -> list:
    """Genuine rows of each batch of an ``n``-image epoch."""
    return [min(batch, n - i) for i in range(0, n, batch)]


class Driver:
    def __init__(self, ctx: harness.Context):
        self.ctx = ctx
        self.cfg = ctx.config
        self.tf = ctx.traffic
        self.snaps = {}

    def setup(self) -> None:
        import jax

        from repro.core import Trainer

        cfg, tf = self.cfg, self.tf
        rng = np.random.default_rng(self.ctx.seed)
        self.x, self.y = data.encoded(cfg, cfg["n_train"], rng)
        self.probe, _ = data.encoded(cfg, cfg["n_test"], rng)
        mesh = None
        if tf["data_parallel"] > 1:
            from repro.distributed.fault import elastic_mesh

            mesh = elastic_mesh((tf["data_parallel"],), ("data",))
        self.trainer = Trainer(program_config(cfg), seed=self.ctx.seed,
                               mesh=mesh)
        for i in range(1, tf["setup_fits"] + 1):
            self._fit()
            if i in (1, tf["setup_fits"]):
                with self.ctx.excluded():
                    self.snaps[i] = snapshot(self.trainer.state)
        jax.block_until_ready(self.trainer.state)

    def _fit(self) -> None:
        with harness.span("bench.fit"):
            self.trainer.fit(self.x, self.y, epochs=self.cfg["epochs"],
                             batch=self.tf["batch"])

    def window(self, seconds: float) -> harness.WindowResult:
        fits = 0
        t0 = time.perf_counter()
        with harness.span("bench.window"):
            while True:
                self._fit()
                fits += 1
                if time.perf_counter() - t0 >= seconds:
                    break
        elapsed = time.perf_counter() - t0
        cfg = self.cfg
        n, epochs = cfg["n_train"], cfg["epochs"]
        ni = cfg["input_hc"] * cfg["input_mc"]
        nj = cfg["hidden_hc"] * cfg["hidden_mc"]
        k = cfg["n_classes"]
        rows = batch_rows(n, self.tf["batch"])
        per_fit = (sum((work.unsup_step(ni, nj, b) for b in rows),
                       work.Work()) * epochs
                   + sum((work.sup_step(ni, nj, k, b) for b in rows),
                         work.Work()))
        unsup_f, sup_f = work.model_flops_train(ni, nj, k)
        images = fits * n * (epochs + 1)
        return harness.WindowResult(
            metrics={"train_images_per_s": images / elapsed},
            attempted=fits, failed=0, window_s=elapsed,
            work={"train_steps": per_fit * fits},
            model_flops=fits * n * (epochs * unsup_f + sup_f),
            log=[f"[bench] {fits} fits of {n} images x {epochs + 1} "
                 f"greedy epochs in {elapsed!r} s"])

    def release(self) -> None:
        del self.trainer

    def check(self) -> list:
        """Replay the set-up fits on the reference and compare the learned
        traces after the first and the last, and the class probabilities
        the last state gives on the probe images (``compare``)."""
        import jax

        cfg, tf = self.cfg, self.tf
        state = ref.init(jax.random.PRNGKey(self.ctx.seed),
                         ref.geometry(cfg), cfg["eps"])
        start = jax.tree_util.tree_map(np.asarray, state)
        want = {}
        for i in range(1, tf["setup_fits"] + 1):
            state = ref.fit(state, cfg, self.x, self.y, cfg["epochs"],
                            tf["batch"])
            if i in self.snaps:
                want[i] = jax.tree_util.tree_map(np.asarray, state)
        return compare(self.snaps, want, cfg, self.probe, start)


def compare(got: dict, want: dict, cfg: dict, probe: np.ndarray,
            start: dict) -> list:
    """The training cell's numbers after the first and the last replayed
    fit: the worst leaf's 99th-percentile trace gap (compared), its
    widest gap, 99.9th percentile, share off by 1e-3 and change-norm gap
    (printed), and the widest class-probability gap on the probe images
    (compared)."""
    out = []
    for i in sorted(got):
        st = worst_stats(got[i], want[i], LEAVES)
        ch = change_gaps(got[i], want[i], start, start, LEAVES)
        out.append(harness.Check(f"trace_gap_p99.fit{i}", st["p99"], None))
        out += [harness.Check(f"trace_gap_{k}.fit{i}", st[k], None, False)
                for k in ("max", "p999", "share")]
        out.append(harness.Check(f"change_gap.fit{i}", max(ch.values()),
                                 None, False))
    last = max(got)
    out.append(harness.Check(
        f"probs_gap.fit{last}",
        probs_gap(ref.class_probs(got[last], cfg, probe),
                  ref.class_probs(want[last], cfg, probe)), None))
    return out
