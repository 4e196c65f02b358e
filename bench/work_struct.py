"""Operations and bytes that a learn step of a network with structural
plasticity requires (Table 1's nactHi: each hidden HC reads ``nact``
input HCs), counted from the algorithm's shapes as ``work.py`` counts
the dense network's, so that a share computed from them cannot pass
100%.  ``K = nact * Mi`` is the number of input units a hidden HC reads.

* the patchy forward reads the live weights (K x Nj) and the input rows
  once, writes the rates once, and does 2 B K Nj operations;
* the learn step keeps dense joint traces (every pair's p_ij, so every
  pair can be scored): the dense learn of ``work.learn``; the update
  kernel of a masked projection also reads its (Hi, Hj) HC mask;
* a rewire scores every (pre-HC, post-HC) pair by mutual information,
  which reads the joint trace once; the masked weights are a function of
  that trace and, as in ``work.py``, not counted; the MI sums and the
  top-nact selection are O(Ni Nj) and O(Hi Hj) elementwise work, not
  counted.
"""
from __future__ import annotations

from bench import work
from bench.work import F32, Work


def patchy_forward(ni: int, nj: int, k_units: int, b: int) -> Work:
    """One patchy activation over ``b`` genuine rows."""
    return Work(flops=2.0 * b * k_units * nj,
                bytes=F32 * (k_units * nj + b * (ni + nj)))


def masked_learn(ni: int, nj: int, hi: int, hj: int, b: int) -> Work:
    """The update kernel's step on a masked projection."""
    return work.learn(ni, nj, b) + Work(bytes=F32 * hi * hj)


def rewire(ni: int, nj: int) -> Work:
    """One structural-plasticity rewire of an (Ni, Nj) projection."""
    return Work(bytes=F32 * ni * nj)


def unsup_step(ni: int, nj: int, k_units: int, b: int) -> Work:
    """Unsupervised step: the dense-trace learn plus the noisy forward's
    product over the live weights (which come out of the learn's own
    trace pass, as in ``work.unsup_step``)."""
    return work.learn(ni, nj, b) + Work(flops=2.0 * b * k_units * nj)


def sup_step(ni: int, nj: int, k_units: int, k: int, b: int) -> Work:
    """Supervised readout step: the patchy hidden forward, then the
    readout learn."""
    return patchy_forward(ni, nj, k_units, b) + work.learn(nj, k, b)


def rewire_points(t0: int, steps: int, every: int) -> int:
    """Rewires among learn steps t0+1 .. t0+steps of a trace clock that
    rewires when it reaches a multiple of ``every``."""
    return (t0 + steps) // every - t0 // every if every > 0 else 0


def model_flops_train(ni: int, nj: int, k_units: int, k: int) -> tuple:
    """Model operations per image: (unsupervised epoch, readout pass)."""
    return (2.0 * ni * nj + 2.0 * k_units * nj,
            2.0 * k_units * nj + 2.0 * nj * k)
