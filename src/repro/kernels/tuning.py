"""Autotuned block-size cache consulted by the kernel wrappers.

``benchmarks/autotune.py`` sweeps block sizes per (kernel, geometry,
backend) and persists the winners as a small JSON cache; the public
wrappers in ``kernels/ops.py`` consult it so Model-1/2/3-scale geometries
run on measured blocks instead of guessed defaults.  Explicit ``block_*``
kwargs always win over the cache.

Cache format (DESIGN.md §7):

    {"version": 1,
     "entries": {"<backend>|<kernel>|k1=v1,k2=v2": {"block_b": 128, ...}}}

where the dims are the wrapper's shape-defining integers in sorted-key
order.  Location: ``$REPRO_AUTOTUNE_CACHE`` if set, else
``autotune.json`` at the root of the checkout -- a tuned cache is part
of the program it tunes, so it lives with the code and git keeps it;
nothing outside the checkout changes block sizes.  Lookups are memoized
per file mtime, so a fresh autotune run is picked up without
restarting, and a missing/corrupt cache degrades to the defaults
silently.
"""
from __future__ import annotations

import functools
import json
import os
import pathlib
from typing import Dict, Optional

import jax

ENV_CACHE = "REPRO_AUTOTUNE_CACHE"
VERSION = 1

_BLOCK_KEYS = ("block_b", "block_h", "block_i", "block_j", "block_k")


# src/repro/kernels/tuning.py -> the checkout root, three levels up
DEFAULT_CACHE = str(
    pathlib.Path(__file__).resolve().parents[3] / "autotune.json")


def cache_path() -> str:
    return os.environ.get(ENV_CACHE) or DEFAULT_CACHE


def entry_key(kernel: str, backend: Optional[str] = None, **dims: int) -> str:
    backend = backend or jax.default_backend()
    flat = ",".join(f"{k}={dims[k]}" for k in sorted(dims))
    return f"{backend}|{kernel}|{flat}"


@functools.lru_cache(maxsize=8)
def _load(path: str, mtime: float) -> Dict[str, dict]:
    del mtime  # part of the key only: invalidates on rewrite
    try:
        with open(path) as f:
            data = json.load(f)
        if data.get("version") != VERSION:
            return {}
        return dict(data.get("entries", {}))
    except (OSError, ValueError):
        return {}


def load_cache() -> Dict[str, dict]:
    path = cache_path()
    try:
        mtime = os.stat(path).st_mtime
    except OSError:
        return {}
    return _load(path, mtime)


def lookup(kernel: str, **dims: int) -> Dict[str, int]:
    """Tuned ``block_*`` kwargs for this call site, or {} if untuned."""
    entry = load_cache().get(entry_key(kernel, **dims), {})
    return {k: int(v) for k, v in entry.items() if k in _BLOCK_KEYS}


def save_entries(entries: Dict[str, dict], path: Optional[str] = None) -> str:
    """Merge ``entries`` into the cache file (used by the autotuner)."""
    path = path or cache_path()
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    merged = {}
    try:
        with open(path) as f:
            data = json.load(f)
        if data.get("version") == VERSION:
            merged.update(data.get("entries", {}))
    except (OSError, ValueError):
        pass
    merged.update(entries)
    with open(path, "w") as f:
        json.dump({"version": VERSION, "entries": merged}, f, indent=2,
                  sort_keys=True)
    return path
