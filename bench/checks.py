"""The numbers that decide ``correct``: gaps between what the timed path
produced and what the plain reference computes."""
from __future__ import annotations

from typing import Dict, Iterable

import numpy as np


def gap_stats(got, want, tol: float = 1e-3) -> Dict[str, float]:
    """Elementwise gaps relative to the reference leaf's largest
    magnitude: the widest (``max``), the 99th and 99.9th percentiles
    (``p99``, ``p999``), and the share of elements off by more than
    ``tol`` (``share``).  A competitive learner can hand a few units to
    different winners on a rounding difference; the widest gap reads
    those few, the percentiles read the bulk of the state."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    bad = {"max": float("inf"), "p99": float("inf"), "p999": float("inf"),
           "share": 1.0}
    if got.shape != want.shape:
        return bad
    scale = float(np.max(np.abs(want))) or 1.0
    d = (np.abs(got - want) / scale).ravel()
    if not np.all(np.isfinite(d)):
        return bad
    p99, p999 = np.quantile(d, [0.99, 0.999])
    return {"max": float(d.max()), "p99": float(p99), "p999": float(p999),
            "share": float(np.mean(d > tol))}


def worst_stats(got: dict, want: dict, leaves: Iterable[str],
                projs: Iterable[str] = ("hidden", "readout")
                ) -> Dict[str, float]:
    """Each of ``gap_stats``' numbers at its worst over the named trace
    leaves of the named projections."""
    out: Dict[str, float] = {}
    for proj in projs:
        for leaf in leaves:
            for k, v in gap_stats(got[proj][leaf], want[proj][leaf]).items():
                out[k] = max(out.get(k, 0.0), v)
    return out


def probs_gap(got, want) -> float:
    """Widest absolute gap between two sets of class probabilities."""
    d = np.abs(np.asarray(got, np.float64) - np.asarray(want, np.float64))
    return float(np.max(d)) if np.all(np.isfinite(d)) else float("inf")


def pred_gap(probs_ref, pred) -> np.ndarray:
    """Per row, how far the reference probability of the served class lies
    below the reference's best class (0 where they agree)."""
    probs_ref = np.asarray(probs_ref, np.float64)
    pred = np.asarray(pred)
    ok = (pred >= 0) & (pred < probs_ref.shape[-1])
    chosen = np.take_along_axis(probs_ref, np.clip(pred, 0, None)[..., None],
                                axis=-1)[..., 0]
    return np.where(ok, probs_ref.max(axis=-1) - chosen, np.inf)


def change_gaps(got: dict, want: dict, got_start: dict, want_start: dict,
                leaves: Iterable[str]) -> Dict[str, float]:
    """Per leaf, the gap between the norms of the program's change (from
    ``got_start``) and the reference's (from ``want_start``), against the
    reference's norm of that leaf's change or the median leaf's,
    whichever is larger."""
    norms = {}
    for proj in (p for p in ("hidden", "readout") if p in got):
        for leaf in leaves:
            def change(state, start):
                return float(np.linalg.norm(
                    np.asarray(state[proj][leaf], np.float64)
                    - np.asarray(start[proj][leaf], np.float64)))
            norms[f"{proj}.{leaf}"] = (change(got, got_start),
                                       change(want, want_start))
    median = float(np.median([w for _, w in norms.values()]))
    return {k: abs(g - w) / max(w, median, 1e-30)
            for k, (g, w) in norms.items()}

