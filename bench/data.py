"""Seeded surrogate images with the Table-1 datasets' shapes.

The arithmetic is that of the repository's offline surrogate (smooth
random class prototypes, pixel noise, small random translations), kept
here so that the benchmark's inputs do not depend on the program under
test.  The prototypes -- the task -- are fixed per dataset name; the
samples are drawn from the run's ``--seed``, and only as many as a cell
uses are made.
"""
from __future__ import annotations

import zlib

import numpy as np

def _smooth(img: np.ndarray, iters: int) -> np.ndarray:
    for _ in range(iters):
        img = (img + np.roll(img, 1, -2) + np.roll(img, -1, -2)
               + np.roll(img, 1, -1) + np.roll(img, -1, -1)) / 5.0
    return img


def prototypes(name: str, side: int, n_classes: int) -> np.ndarray:
    """(classes, side, side) contrast-stretched smooth prototypes."""
    rng = np.random.default_rng(zlib.crc32(name.encode()))
    protos = _smooth(rng.random((n_classes, side, side)).astype(np.float32), 3)
    mu = protos.mean(axis=(1, 2), keepdims=True)
    sd = protos.std(axis=(1, 2), keepdims=True) + 1e-9
    return np.clip(0.5 + 0.35 * (protos - mu) / sd, 0.0, 1.0)


def images(name: str, side: int, n_classes: int, n: int,
           rng: np.random.Generator, noise: float = 0.15, max_shift: int = 2):
    """``n`` labelled images (x: (n, side, side) in [0, 1], y: (n,) int32)."""
    protos = prototypes(name, side, n_classes)
    y = rng.integers(0, protos.shape[0], size=n).astype(np.int32)
    x = protos[y].copy()
    shifts = rng.integers(-max_shift, max_shift + 1, size=(n, 2))
    for i in range(n):
        x[i] = np.roll(x[i], tuple(shifts[i]), axis=(0, 1))
    x += rng.normal(0.0, noise, x.shape).astype(np.float32)
    return np.clip(x, 0.0, 1.0), y


def encode(x: np.ndarray) -> np.ndarray:
    """(n, side, side) -> (n, 2 * side * side) complement-pair HC rates."""
    flat = x.reshape(x.shape[0], -1)
    return np.stack([flat, 1.0 - flat], axis=-1).reshape(
        x.shape[0], -1).astype(np.float32)


def encoded(cfg: dict, n: int, rng: np.random.Generator):
    """``n`` encoded labelled images of a configuration's dataset."""
    x, y = images(cfg["dataset"], cfg["image_side"], cfg["n_classes"], n, rng)
    return encode(x), y
