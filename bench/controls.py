"""Readings of a cell's correctness numbers for the control and the
planted faults, on the chip at the cell's own size.

    python3 bench/controls.py --workload m3-train --seeds 1,2,3

The control is the plain reference put in the program's place and
computed one precision step below the configuration's (``high``: three
bfloat16 passes, for float32 at ``highest``).  The faults are planted in
the reference put in the program's place:

* training: ``unchanged`` (a fit that returns its state as it was),
  ``half_batch`` (only the first half of each batch's rows counted, the
  mean taken over them), and, for a data-parallel cell, ``no_exchange``
  (the co-activation partials of the other chips never arrive: only the
  first shard's post columns learn);
* serving: ``altered`` (each answer's class moved to the next one where
  it is produced).

Each seed also runs the program itself (a training cell's set-up fits; a
serving cell's set-up and a window of ``--seconds``), and its readings
are printed beside the control's, every number the cell's comparison
reads or prints.  One JSON line per seed and variant.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def train_readings(ctx, seed: int, program: bool = True,
                   controls: bool = True) -> dict:
    import jax

    from bench import data
    from bench.drivers import train_fit
    from bench.references import bcpnn as ref

    cfg, tf = ctx.config, ctx.traffic
    out = {}
    if program:
        drv = train_fit.Driver(ctx)
        drv.setup()
        drv.release()
        out["program"] = {c.name: c.value for c in drv.check()}
    if not controls:
        return out
    rng = np.random.default_rng(seed)
    x, y = data.encoded(cfg, cfg["n_train"], rng)
    probe, _ = data.encoded(cfg, cfg["n_test"], rng)
    fits = tf["setup_fits"]
    init = ref.init(jax.random.PRNGKey(seed), ref.geometry(cfg), cfg["eps"])

    def run(**kw):
        state, got = init, {}
        for i in range(1, fits + 1):
            state = ref.fit(state, cfg, x, y, cfg["epochs"], tf["batch"],
                            **kw)
            if i in (1, fits):
                got[i] = jax.tree_util.tree_map(np.asarray, state)
        return got

    want = run()
    start = jax.tree_util.tree_map(np.asarray, init)
    variants = {"control_high": run(prec="high"),
                "half_batch": run(keep_rows=0.5),
                "unchanged": {i: jax.tree_util.tree_map(np.asarray, init)
                              for i in want}}
    if tf["data_parallel"] > 1:
        variants["no_exchange"] = run(keep_cols=1.0 / tf["data_parallel"])
    for name, got in variants.items():
        out[name] = {c.name: c.value for c in train_fit.compare(
            got, want, cfg, probe, start)}
    return out


def serve_readings(ctx, seed: int, seconds: float,
                   controls: bool = True) -> dict:
    import jax

    from bench.checks import pred_gap, worst_stats
    from bench.drivers import serve as drv_mod
    from bench.references import bcpnn as ref

    drv = drv_mod.Driver(ctx)
    drv.setup()
    drv.window(seconds)
    drv.release()
    out = {"program": {c.name: c.value for c in drv.check()}}
    if not controls:
        return out
    cfg, tf = ctx.config, ctx.traffic
    args = (cfg, tf, seed, drv.x_fit, drv.y_fit, drv.fb_x, drv.fb_y,
            drv.fb_sent)
    want = drv_mod.replay(*args, "highest")
    ctrl = drv_mod.replay(*args, "high")
    log = drv.log
    pick = np.random.default_rng(seed + 1).choice(
        len(log.idx), size=min(len(log.idx), tf["sample"]), replace=False)
    x = drv.pool[log.idx[pick]]
    h_want = ref.hidden_of(want["state"], cfg, x, "highest")
    h_ctrl = ref.hidden_of(ctrl["state"], cfg, x, "high")
    gaps = {"control_high": 0.0, "altered": 0.0}
    for row, k in enumerate(pick):
        lo = log.folds_at_submit[k]
        hi = max(lo, log.folds_at_result[k])
        pw = ref.readout_probs(h_want[row:row + 1], want["w"][lo:hi + 1],
                               want["b"][lo:hi + 1], cfg["n_classes"])[:, 0]
        pc = ref.readout_probs(h_ctrl[row:row + 1], ctrl["w"][lo:hi + 1],
                               ctrl["b"][lo:hi + 1], cfg["n_classes"],
                               "high")[:, 0]
        for name, p in (("control_high", pc),
                        ("altered", np.roll(pw, 1, axis=-1))):
            gaps[name] = max(gaps[name], float(np.min(pred_gap(
                pw, np.argmax(p, axis=-1)))))
    leaves = ("pi", "pj", "pij")
    final_want = jax.tree_util.tree_map(np.asarray, want["final"])
    final_ctrl = jax.tree_util.tree_map(np.asarray, ctrl["final"])
    fold = worst_stats(final_ctrl, final_want, leaves, ("readout",))
    served = worst_stats(jax.tree_util.tree_map(np.asarray, ctrl["setup"]),
                         jax.tree_util.tree_map(np.asarray, want["setup"]),
                         leaves)
    out["control_high"] = dict(
        {"unanswered": 0.0, "pred_gap": gaps["control_high"]},
        **{f"fold_gap_{k}": v for k, v in fold.items()},
        **{f"setup_gap_{k}": v for k, v in served.items()})
    out["altered"] = dict(out["program"], pred_gap=gaps["altered"])
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--controls", type=int, default=None,
                    help="read the control and faults on the first N seeds "
                         "only (default: every seed)")
    ap.add_argument("--program", type=int, choices=(0, 1), default=1,
                    help="0: training cells read the reference side only "
                         "(a data-parallel cell on fewer chips)")
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    sys.path.insert(0, ROOT)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax

    from bench import harness

    ctx = harness.resolve(ROOT, args.workload)
    harness.device_info(1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_default_matmul_precision",
                      ctx.config["matmul_precision"])
    seeds = [int(s) for s in args.seeds.split(",")]
    for n, seed in enumerate(seeds):
        ctx.seed = seed
        t0 = time.perf_counter()
        ctl = args.controls is None or n < args.controls
        if ctx.traffic["kind"] == "train_fit":
            readings = train_readings(ctx, seed, bool(args.program), ctl)
        else:
            readings = serve_readings(ctx, seed, args.seconds, ctl)
        for name, nums in readings.items():
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "variant": name, "numbers": nums,
                              "seconds": time.perf_counter() - t0}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
