"""Readings of a structural-plasticity training cell's correctness
numbers for the control and the planted faults, on the chip at the
cell's own size (``bench/controls.py`` for the cells of
``train_fit_struct``).

    python3 bench/controls_struct.py --workload m3s-train --seeds 1,2,3

The control is the plain reference (``bench/references/bcpnn_struct.py``)
put in the program's place and computed one precision step below the
configuration's (``high``: three bfloat16 passes).  The faults are
planted in that reference: ``rewire_never`` (the rewire never runs),
``rewire_lowest`` (the rewire keeps the lowest-MI pre-HCs) and
``mask_ignored`` (the forward reads the unmasked weights); and, as in
``bench/controls.py``, ``unchanged`` (a fit that returns its state as
it was), which the class probabilities are held against.  Each seed
also runs the program itself (the cell's set-up fits).  One JSON line
per seed and variant, every number the cell's comparison reads or
prints.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VARIANTS = {"control_high": dict(prec="high"),
            "rewire_never": dict(rewire="never"),
            "rewire_lowest": dict(rewire="lowest"),
            "mask_ignored": dict(mask_forward=False)}


def readings(ctx, seed: int, controls: bool = True) -> dict:
    import jax

    from bench import data
    from bench.drivers import train_fit_struct as drv_mod
    from bench.references import bcpnn_struct as ref

    cfg, tf = ctx.config, ctx.traffic
    drv = drv_mod.Driver(ctx)
    drv.setup()
    drv.release()
    out = {"program": {c.name: c.value for c in drv.check()}}
    if not controls:
        return out
    rng = np.random.default_rng(seed)
    x, y = data.encoded(cfg, cfg["n_train"], rng)
    probe, _ = data.encoded(cfg, cfg["n_test"], rng)
    fits = tf["setup_fits"]
    init = ref.init(jax.random.PRNGKey(seed), ref.geometry(cfg), cfg["eps"])
    start = jax.tree_util.tree_map(np.asarray, init)

    def run(**kw):
        state, got = init, {}
        for i in range(1, fits + 1):
            state = ref.fit(state, cfg, x, y, cfg["epochs"], tf["batch"],
                            **kw)
            if i in (1, fits):
                got[i] = jax.tree_util.tree_map(np.asarray, state)
        return got

    want = run()
    variants = {name: run(**kw) for name, kw in VARIANTS.items()}
    variants["unchanged"] = {i: start for i in want}
    for name, got in variants.items():
        out[name] = {c.name: c.value for c in drv_mod.compare(
            got, want, cfg, probe, start)}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", type=int, default=None,
                    help="read the control and faults on the first N seeds "
                         "only (default: every seed)")
    args = ap.parse_args(argv)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".jax_cache"))
    sys.path.insert(0, ROOT)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax

    from bench import harness

    ctx = harness.resolve(ROOT, args.workload)
    harness.device_info(1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_default_matmul_precision",
                      ctx.config["matmul_precision"])
    for n, seed in enumerate(int(s) for s in args.seeds.split(",")):
        ctx.seed = seed
        t0 = time.perf_counter()
        ctl = args.controls is None or n < args.controls
        for name, nums in readings(ctx, seed, ctl).items():
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "variant": name, "numbers": nums,
                              "seconds": time.perf_counter() - t0}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
