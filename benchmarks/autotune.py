"""Block-size autotuner for the Pallas BCPNN kernels.

Sweeps candidate block sizes per (kernel, geometry) on the ACTIVE jax
backend, times each candidate end-to-end (pad + kernel + unpad, jit'd,
best-of-``--iters``), and persists the winners into the autotune cache
(src/repro/kernels/tuning.py) that ``kernels/ops.py`` consults — so
Model-1/2/3-scale geometries run on measured blocks instead of guessed
defaults.  On TPU the numbers are Mosaic wall-clock; on CPU they time the
interpreter (useful for exercising the machinery — CI runs ``--smoke`` —
not for picking TPU blocks).

    PYTHONPATH=src python -m benchmarks.autotune --models model1-mnist
    PYTHONPATH=src python -m benchmarks.autotune --smoke   # CI: tiny sweep

Cache location: ``$REPRO_AUTOTUNE_CACHE`` or ``--out`` (see DESIGN.md §7
for the file format).
"""
from __future__ import annotations

import argparse
import itertools
import time

import jax
import jax.numpy as jnp

from repro.kernels import tuning
from repro.kernels.bcpnn_fwd import bcpnn_fwd_pallas
from repro.kernels.bcpnn_update import bcpnn_update_pallas
from repro.kernels.hc_softmax import hc_softmax_pallas
from repro.kernels.ops import _interpret
from repro.kernels.patchy import (compact_forward, compact_update,
                                  patchy_forward, patchy_update)

# Geometry per model (Table 1 shapes): hi*mi pre units, hj*mj post units,
# nact the struct-variant connectivity budget.
GEOMS = {
    "model1-mnist": dict(b=128, hi=28 * 28, mi=2, hj=32, mj=128, nact=128),
    "model2-pneumonia": dict(b=128, hi=28 * 28, mi=2, hj=32, mj=256, nact=128),
    "model3-breast": dict(b=128, hi=64 * 64, mi=2, hj=32, mj=128, nact=128),
    "smoke": dict(b=32, hi=49, mi=2, hj=4, mj=10, nact=8),
}

FULL_CANDIDATES = {
    "hc_softmax": {"block_b": (128, 256), "block_h": (4, 8, 16)},
    "bcpnn_fwd": {"block_b": (128, 256), "block_j": (256, 512, 1024),
                  "block_k": (256, 512)},
    "bcpnn_update": {"block_i": (256, 512), "block_j": (256, 512, 1024),
                     "block_k": (64, 128)},
    "patchy_forward": {"block_b": (128, 256), "block_k": (256, 512)},
    "patchy_update": {"block_i": (256, 512), "block_k": (64, 128)},
    "compact_forward": {"block_b": (128, 256), "block_k": (256, 512)},
    "compact_update": {"block_i": (256, 512), "block_k": (64, 128)},
}
# The interpreter pays per-tile Python overhead, so a wide sweep is slow
# and meaningless off-TPU; exercise the machinery with two points each.
SMOKE_CANDIDATES = {
    "hc_softmax": {"block_b": (32, 64)},
    "bcpnn_fwd": {"block_j": (64, 128)},
    "bcpnn_update": {"block_i": (64, 128)},
    "patchy_forward": {"block_b": (16, 32)},
    "patchy_update": {"block_i": (16, 32)},
    "compact_forward": {"block_b": (16, 32)},
    "compact_update": {"block_i": (16, 32)},
}


def _time(fn, iters: int) -> float:
    out = fn()
    jax.block_until_ready(out)
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best


def _make_operands(g: dict):
    k = jax.random.split(jax.random.PRNGKey(0), 6)
    ni, nj = g["hi"] * g["mi"], g["hj"] * g["mj"]
    x = jax.random.uniform(k[0], (g["b"], ni))
    y = jax.random.uniform(k[1], (g["b"], nj))
    w = jax.random.normal(k[2], (ni, nj)) * 0.1
    bias = jax.random.normal(k[3], (nj,))
    pij = jax.random.uniform(k[4], (ni, nj)) * 0.01 + 1e-5
    from repro.core.bcpnn_layer import topk_mask
    from repro.core.compact import build_table, gather_dense, unit_indices
    nact = min(g["nact"], g["hi"])
    mask_hc = topk_mask(jax.random.uniform(k[5], (g["hi"], g["hj"])), nact)
    table = build_table(mask_hc, nact)
    ui = unit_indices(table, g["mi"], sentinel=ni)
    lpi = jnp.log(jnp.full((ni,), 0.5))
    lpj = jnp.log(jnp.full((nj,), 1.0 / g["mj"]))
    alpha = jnp.asarray(0.01)
    return dict(x=x, y=y, w=w, bias=bias, pij=pij, mask_hc=mask_hc,
                table=table, w_c=gather_dense(w, ui, g["hj"], g["mj"]),
                pij_c=gather_dense(pij, ui, g["hj"], g["mj"]),
                lpi=lpi, lpj=lpj, alpha=alpha)


def _calls(g: dict, ops: dict, interpret: bool):
    """kernel name -> (dims-for-cache-key, candidate-kwargs -> thunk)."""
    b, hi, mi, hj, mj = g["b"], g["hi"], g["mi"], g["hj"], g["mj"]
    nact = min(g["nact"], hi)
    ni, nj = hi * mi, hj * mj
    k_units = nact * mi
    return {
        "hc_softmax": (dict(b=b, n_hc=hj, n_mc=mj), lambda kw: lambda:
                       hc_softmax_pallas(ops["y"], hj, mj,
                                         interpret=interpret, **kw)),
        "bcpnn_fwd": (dict(b=b, ni=ni, n_hc=hj, n_mc=mj), lambda kw: lambda:
                      bcpnn_fwd_pallas(ops["x"], ops["w"], ops["bias"], hj,
                                       mj, interpret=interpret, **kw)),
        "bcpnn_update": (dict(b=b, ni=ni, nj=nj), lambda kw: lambda:
                         bcpnn_update_pallas(
                             ops["pij"], ops["lpi"], ops["lpj"], ops["x"],
                             ops["y"], ops["mask_hc"], ops["alpha"],
                             interpret=interpret, **kw)),
        "patchy_forward": (dict(b=b, k=k_units, hj=hj, mj=mj), lambda kw:
                           lambda: patchy_forward(
                               ops["x"], ops["w"], ops["bias"],
                               ops["table"], mi, hj, mj,
                               interpret=interpret, **kw)),
        "patchy_update": (dict(b=b, k=k_units, hj=hj, mj=mj), lambda kw:
                          lambda: patchy_update(
                              ops["pij"], ops["lpi"], ops["lpj"], ops["x"],
                              ops["y"], ops["table"], ops["alpha"],
                              mi, hj, mj, interpret=interpret, **kw)),
        "compact_forward": (dict(b=b, k=k_units, hj=hj, mj=mj), lambda kw:
                            lambda: compact_forward(
                                ops["x"], ops["w_c"], ops["bias"],
                                ops["table"], mi,
                                interpret=interpret, **kw)),
        "compact_update": (dict(b=b, k=k_units, hj=hj, mj=mj), lambda kw:
                           lambda: compact_update(
                               ops["pij_c"], ops["lpi"], ops["lpj"],
                               ops["x"], ops["y"], ops["table"],
                               ops["alpha"], mi,
                               interpret=interpret, **kw)),
    }


def autotune(models, candidates, iters: int, out=None, verbose=True):
    interpret = _interpret()
    entries, report = {}, []
    for name in models:
        g = GEOMS[name]
        ops = _make_operands(g)
        for kernel, (dims, make) in _calls(g, ops, interpret).items():
            grid = candidates[kernel]
            keys = sorted(grid)
            best_kw, best_t = None, float("inf")
            for combo in itertools.product(*(grid[k] for k in keys)):
                kw = dict(zip(keys, combo))
                t = _time(make(kw), iters)
                if verbose:
                    print(f"autotune,{t*1e6:.0f},{name}.{kernel}."
                          + "_".join(f"{k}{v}" for k, v in kw.items()))
                if t < best_t:
                    best_kw, best_t = kw, t
            entries[tuning.entry_key(kernel, **dims)] = best_kw
            report.append((name, kernel, best_kw, best_t))
    path = tuning.save_entries(entries, out)
    if verbose:
        for name, kernel, kw, t in report:
            print(f"autotune_winner,{t*1e6:.0f},{name}.{kernel}={kw}")
        print(f"autotune: {len(entries)} entries -> {path}")
    return entries, path


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--models", default="model1-mnist",
                    help="comma-separated geometry names "
                         f"(choices: {', '.join(GEOMS)})")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny geometry + 2-point sweep; asserts the cache "
                         "round-trips through kernels.ops (what CI runs)")
    ap.add_argument("--iters", type=int, default=3)
    ap.add_argument("--out", default=None,
                    help="cache file (default: $REPRO_AUTOTUNE_CACHE or "
                         "autotune.json at the checkout root)")
    args = ap.parse_args()
    if args.smoke:
        entries, path = autotune(["smoke"], SMOKE_CANDIDATES, iters=1,
                                 out=args.out)
        # the cache must be consultable exactly as ops.py will ask for it
        import os
        os.environ[tuning.ENV_CACHE] = path
        g = GEOMS["smoke"]
        tuned = tuning.lookup("bcpnn_fwd", b=g["b"], ni=g["hi"] * g["mi"],
                              n_hc=g["hj"], n_mc=g["mj"])
        assert tuned, "smoke autotune produced no consultable bcpnn_fwd entry"
        assert len(entries) == len(SMOKE_CANDIDATES)
        print(f"autotune --smoke OK: bcpnn_fwd -> {tuned}")
        return
    autotune([m.strip() for m in args.models.split(",")],
             FULL_CANDIDATES, iters=args.iters, out=args.out)


if __name__ == "__main__":
    main()
