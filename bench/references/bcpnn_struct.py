"""Plain float32 ``jax.numpy`` reference of a Table-1 BCPNN network whose
hidden receptive fields are found by structural plasticity
(arXiv:2503.01561 Table 1, nactHi; Fig. 5).

It is the dense reference (``bench/references/bcpnn.py``, whose products,
softmax, readout step, batching and initial traces it uses) with these
additions, and no import of the program under test:

* connectivity: an (Hi, Hj) 0/1 mask with exactly ``nact`` active
  pre-HCs per post-HC; the hidden weights are ``log(p_ij / (p_i p_j))``
  times the mask of their (pre-HC, post-HC) block, the biases are not
  masked;
* initial mask: the ``nact`` highest of uniform(0, 1) scores per post-HC,
  drawn from the second key of the split that gives the hidden
  projection its trace noise;
* joint traces are dense: every p_ij follows the batch-mean EMA, silent
  pairs included, so that mutual information can be scored for every
  pair (the repository's dense-trace, HC-masked layout);
* rewire: after a hidden learn step whose trace clock (steps taken,
  counted after the step) is a multiple of ``struct_every``, score every
  (pre-HC, post-HC) pair by

      MI_ij = sum_{m in i, n in j} p_mn log(p_mn / (p_m p_n))

  (DESIGN.md §2), keep the ``nact`` highest pre-HCs per post-HC, the
  lowest index first among equal scores, and recompute the masked
  weights from the traces;
* the supervised readout epoch reads the hidden rates through the
  masked weights.

Departures, stated as such: the probabilities in MI carry the floors the
weights carry everywhere in this repository (p_m, p_n >= eps, p_mn >=
eps^2); top-``nact`` is a stable sort of the negated scores (with -0.0
read as 0.0), not a partial selection.

Planted faults for the comparison's readings (``fit``'s ``rewire`` and
``mask_forward``): the rewire never runs, the rewire keeps the
lowest-MI pre-HCs, or the forward reads the unmasked weights.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.references import bcpnn as dense

REWIRES = ("top", "never", "lowest")


def geometry(cfg: dict) -> tuple:
    """(Hi, Mi, Hj, Mj, K, nact) of a configuration file."""
    return dense.geometry(cfg) + (cfg["nact_hi"],)


def top_mask(scores, nact: int):
    """(Hi, Hj) scores -> 0/1 mask of the ``nact`` highest per column,
    the lowest row index first among equal scores."""
    order = jnp.argsort(-scores + 0.0, axis=0, stable=True)
    rank = jnp.argsort(order, axis=0)
    return (rank < nact).astype(jnp.float32)


def unit_mask(mask, mi: int, mj: int):
    """(Hi, Hj) HC mask -> (Ni, Nj) unit mask."""
    return jnp.repeat(jnp.repeat(mask, mi, axis=0), mj, axis=1)


def weights(p: dict, eps: float, mask_units=None):
    """w_ij = log(p_ij / (p_i p_j)) with the floors, masked when a unit
    mask is given; and b_j = log p_j."""
    lpi = jnp.log(jnp.clip(p["pi"], eps, 1.0))
    lpj = jnp.log(jnp.clip(p["pj"], eps, 1.0))
    w = jnp.log(jnp.clip(p["pij"], eps * eps, 1.0)) - (lpi[:, None]
                                                        + lpj[None, :])
    return (w if mask_units is None else w * mask_units), lpj


def mutual_information(p: dict, geom: tuple, eps: float):
    """(Hi, Hj) mutual information between input HC i and hidden HC j."""
    hi, mi, hj, mj = geom[:4]
    w, _ = weights(p, eps)
    contrib = jnp.clip(p["pij"], eps * eps, 1.0) * w
    return jnp.sum(contrib.reshape(hi, mi, hj, mj), axis=(1, 3))


@functools.partial(jax.jit, static_argnames=("geom", "eps"))
def init(seed_key, geom: tuple, eps: float) -> dict:
    hi, mi, hj, mj, _, nact = geom
    state = dense.init(seed_key, geom[:5], eps)
    _, k_mask = jax.random.split(jax.random.split(seed_key, 3)[0])
    mask = top_mask(jax.random.uniform(k_mask, (hi, hj)), nact)
    h = state["hidden"]
    w, _ = weights(h, eps, unit_mask(mask, mi, mj))
    return {**state, "hidden": {**h, "w": w, "mask": mask,
                                "mi": jnp.zeros((hi, hj), jnp.float32)}}


def unsup_step(state: dict, x, v, hp: tuple, prec, rewire: str,
               mask_forward: bool) -> dict:
    (geom, alpha, eps, noise, noise_steps, every) = hp
    hi, mi, hj, mj, _, nact = geom
    key, sub = jax.random.split(state["key"])
    h = state["hidden"]
    s = dense.support(h, x, prec)
    amp = noise * jnp.maximum(
        0.0, 1.0 - h["t"].astype(jnp.float32) / max(1, noise_steps))
    s = s + amp * jax.random.normal(sub, s.shape, jnp.float32)
    y = dense.hc_softmax(s, mj)
    new = dense._learn(h, x, y, v, alpha, eps, prec)
    mask, scores = h["mask"], h["mi"]
    if rewire != "never":
        def rewired(p):
            mi_ = mutual_information(p, geom, eps)
            return top_mask(mi_ if rewire == "top" else -mi_, nact), mi_
        mask, scores = jax.lax.cond(new["t"] % every == 0, rewired,
                                    lambda p: (mask, scores), new)
    w, b = weights(new, eps,
                   unit_mask(mask, mi, mj) if mask_forward else None)
    return {**state, "hidden": {**new, "w": w, "b": b, "mask": mask,
                                "mi": scores},
            "key": key}


@functools.partial(jax.jit, static_argnames=("epochs", "hp", "k", "prec",
                                             "rewire", "mask_forward"))
def _fit(state, xs, ys, vs, epochs: int, hp: tuple, k: int, prec,
         rewire: str, mask_forward: bool):
    def unsup_epoch(_, st):
        return jax.lax.scan(
            lambda s, xv: (unsup_step(s, xv[0], xv[1], hp, prec, rewire,
                                      mask_forward), None),
            st, (xs, vs))[0]
    state = jax.lax.fori_loop(0, epochs, unsup_epoch, state)
    geom, alpha, eps = hp[:3]
    sup_hp = (geom[3], alpha, eps, k)
    return jax.lax.scan(
        lambda s, b: (dense.sup_step(s, b[0], b[1], b[2], sup_hp, prec),
                      None),
        state, (xs, ys, vs))[0]


def fit(state: dict, cfg: dict, x: np.ndarray, y: np.ndarray, epochs: int,
        batch: int, prec: str = "highest", rewire: str = "top",
        mask_forward: bool = True) -> dict:
    """One layerwise-greedy fit.  ``rewire`` ``"never"`` or ``"lowest"``
    and ``mask_forward=False`` plant the faults of the module's
    docstring."""
    if rewire not in REWIRES:
        raise ValueError(f"unknown rewire {rewire!r}; expected {REWIRES}")
    xs, ys, vs = dense.batchify(x, y, batch)
    hp = (geometry(cfg), cfg["alpha"], cfg["eps"], cfg["support_noise"],
          cfg["noise_steps"], cfg["struct_every"])
    return _fit(state, jnp.asarray(xs), jnp.asarray(ys), jnp.asarray(vs),
                epochs, hp, cfg["n_classes"], prec, rewire, mask_forward)
