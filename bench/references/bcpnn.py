"""Plain float32 ``jax.numpy`` reference of the Table-1 BCPNN network.

Written from the paper's equations (arXiv:2503.01561 §3, the
Hebbian-Bayesian rule of Ravichandran et al.) and from the repository's
documented protocol, with no import of the program under test:

* a three-population network, input (Hi x Mi) -> hidden (Hj x Mj) ->
  output (1 x K), densely connected;
* activation: support s_j = b_j + sum_i x_i w_ij, then a softmax within
  each hypercolumn;
* plasticity: exponential traces p_i, p_j, p_ij of the batch means with
  smoothing a = max(1 / (t + 1), alpha), then w_ij = log(p_ij / (p_i p_j))
  and b_j = log p_j, probabilities floored at eps (eps^2 for p_ij);
* initial state: uniform traces, the joint trace perturbed by
  exp(0.1 * N(0, 1)); the random stream is split from the seed as the
  deployment's checkpoints record it (three keys: hidden, readout, noise);
* training (layerwise greedy): ``epochs`` unsupervised epochs on the
  hidden projection, its post rates drawn from the support plus
  exploration noise of amplitude ``support_noise * max(0, 1 - t /
  noise_steps)``, then one supervised epoch on the readout with the
  label one-hots as post rates.  A dataset that does not fill its last
  batch is zero-padded and every batch statistic divides by the genuine
  row count;
* online learning (served feedback): the supervised readout step on a
  batch of labelled samples.

Every matrix product takes its precision from the caller: ``highest``
(float32) is the reference; ``high`` (three bfloat16 passes, the step
below float32 at ``highest``) and ``bf16`` (one pass) are controls that
the comparison has to reject.  The lower precisions are spelled out
operand by operand, so they mean the same on every platform.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

PRECISIONS = ("highest", "high", "bf16")


def _bf16(a):
    return a.astype(jnp.bfloat16).astype(jnp.float32)


def mm(a, b, prec: str):
    """a @ b in float32 at ``prec``."""
    exact = functools.partial(jnp.matmul, precision=jax.lax.Precision.HIGHEST)
    if prec == "highest":
        return exact(a, b)
    a_hi, b_hi = _bf16(a), _bf16(b)
    if prec == "bf16":
        return exact(a_hi, b_hi)
    if prec == "high":
        a_lo, b_lo = _bf16(a - a_hi), _bf16(b - b_hi)
        return exact(a_hi, b_hi) + (exact(a_hi, b_lo) + exact(a_lo, b_hi))
    raise ValueError(f"unknown precision {prec!r}; expected {PRECISIONS}")


def geometry(cfg: dict) -> tuple:
    """(Hi, Mi, Hj, Mj, K) of a configuration file."""
    return (cfg["input_hc"], cfg["input_mc"], cfg["hidden_hc"],
            cfg["hidden_mc"], cfg["n_classes"])


def _proj_init(key, ni: int, nj: int, mi: int, mj: int, eps: float):
    k_tr, _ = jax.random.split(key)
    pi = jnp.full((ni,), 1.0 / mi, jnp.float32)
    pj = jnp.full((nj,), 1.0 / mj, jnp.float32)
    pij = jnp.full((ni, nj), 1.0 / (mi * mj), jnp.float32)
    pij = pij * jnp.exp(0.1 * jax.random.normal(k_tr, (ni, nj), jnp.float32))
    return _with_weights({"pi": pi, "pj": pj, "pij": pij,
                          "t": jnp.zeros((), jnp.int32)}, eps)


def _with_weights(p: dict, eps: float) -> dict:
    lpi = jnp.log(jnp.clip(p["pi"], eps, 1.0))
    lpj = jnp.log(jnp.clip(p["pj"], eps, 1.0))
    w = jnp.log(jnp.clip(p["pij"], eps * eps, 1.0)) - (lpi[:, None]
                                                        + lpj[None, :])
    return {**p, "w": w, "b": lpj}


@functools.partial(jax.jit, static_argnames=("geom", "eps"))
def init(seed_key, geom: tuple, eps: float) -> dict:
    hi, mi, hj, mj, k = geom
    keys = jax.random.split(seed_key, 3)
    return {"hidden": _proj_init(keys[0], hi * mi, hj * mj, mi, mj, eps),
            "readout": _proj_init(keys[1], hj * mj, k, mj, k, eps),
            "key": keys[2]}


def hc_softmax(s, m: int):
    lead = s.shape[:-1]
    s = s.reshape(*lead, -1, m)
    s = s - jnp.max(s, axis=-1, keepdims=True)
    e = jnp.exp(s)
    return (e / jnp.sum(e, axis=-1, keepdims=True)).reshape(*lead, -1)


def support(p: dict, x, prec):
    return p["b"][None, :] + mm(x, p["w"], prec)


def _learn(p: dict, x, y, v, alpha: float, eps: float, prec,
           cols=None) -> dict:
    xv = x * v[:, None]
    yv = y * v[:, None]
    n = jnp.maximum(jnp.sum(v), 1.0)
    co = mm(xv.T, yv, prec) / n
    if cols is not None:
        co = co * cols[None, :]
    a = jnp.maximum(1.0 / (p["t"].astype(jnp.float32) + 1.0), alpha)
    new = {"pi": (1.0 - a) * p["pi"] + a * (jnp.sum(xv, axis=0) / n),
           "pj": (1.0 - a) * p["pj"] + a * (jnp.sum(yv, axis=0) / n),
           "pij": (1.0 - a) * p["pij"] + a * co,
           "t": p["t"] + 1}
    return _with_weights(new, eps)


def hidden_rates(state: dict, x, mj: int, prec):
    return hc_softmax(support(state["hidden"], x, prec), mj)


def unsup_step(state: dict, x, v, hp: tuple, prec, cols=None) -> dict:
    mj, alpha, eps, noise, noise_steps = hp
    key, sub = jax.random.split(state["key"])
    h = state["hidden"]
    s = support(h, x, prec)
    amp = noise * jnp.maximum(
        0.0, 1.0 - h["t"].astype(jnp.float32) / max(1, noise_steps))
    s = s + amp * jax.random.normal(sub, s.shape, jnp.float32)
    y = hc_softmax(s, mj)
    return {**state, "hidden": _learn(h, x, y, v, alpha, eps, prec, cols),
            "key": key}


def sup_step(state: dict, x, labels, v, hp: tuple, prec) -> dict:
    mj, alpha, eps, k = hp
    h = hidden_rates(state, x, mj, prec)
    y = jax.nn.one_hot(labels, k, dtype=jnp.float32)
    return {**state, "readout": _learn(state["readout"], h, y, v, alpha,
                                       eps, prec)}


def _hp(cfg: dict) -> tuple:
    return (cfg["hidden_mc"], cfg["alpha"], cfg["eps"],
            cfg["support_noise"], cfg["noise_steps"])


@functools.partial(jax.jit, static_argnames=("epochs", "hp", "k", "prec"))
def _fit(state, xs, ys, vs, cols, epochs: int, hp: tuple, k: int, prec):
    def unsup_epoch(_, st):
        return jax.lax.scan(
            lambda s, xv: (unsup_step(s, xv[0], xv[1], hp, prec, cols),
                           None),
            st, (xs, vs))[0]
    state = jax.lax.fori_loop(0, epochs, unsup_epoch, state)
    sup_hp = (hp[0], hp[1], hp[2], k)
    return jax.lax.scan(
        lambda s, b: (sup_step(s, b[0], b[1], b[2], sup_hp, prec), None),
        state, (xs, ys, vs))[0]


def batchify(x: np.ndarray, y: np.ndarray, batch: int):
    """Zero-padded (nb, batch, ...) batches with their 0/1 validity."""
    n = len(x)
    nb = -(-n // batch)
    pad = nb * batch - n
    xp = np.concatenate([x, np.zeros((pad, x.shape[1]), x.dtype)])
    yp = np.concatenate([y, np.zeros(pad, y.dtype)])
    v = (np.arange(nb * batch) < n).astype(np.float32)
    return (xp.reshape(nb, batch, -1), yp.reshape(nb, batch),
            v.reshape(nb, batch))


def fit(state: dict, cfg: dict, x: np.ndarray, y: np.ndarray, epochs: int,
        batch: int, prec: str = "highest", keep_rows: float = 1.0,
        keep_cols: float = 1.0) -> dict:
    """One layerwise-greedy fit.  ``keep_rows`` < 1 and ``keep_cols`` < 1
    plant faults: only that leading share of each batch's genuine rows
    counts, or only that leading share of the hidden projection's post
    columns gets its co-activation (the other columns' partials never
    arrive, as when a data-parallel step skips its all-reduce)."""
    xs, ys, vs = batchify(x, y, batch)
    if keep_rows < 1.0:
        vs = vs * (np.arange(batch) < int(batch * keep_rows))[None, :]
    nj = cfg["hidden_hc"] * cfg["hidden_mc"]
    cols = (np.arange(nj) < int(nj * keep_cols)).astype(np.float32)
    return _fit(state, jnp.asarray(xs), jnp.asarray(ys), jnp.asarray(vs),
                jnp.asarray(cols), epochs, _hp(cfg), cfg["n_classes"], prec)


@functools.partial(jax.jit, static_argnames=("hp", "prec"))
def fold(state: dict, x, labels, hp: tuple, prec) -> dict:
    """One online-learning fold: the supervised readout step."""
    return sup_step(state, x, labels, jnp.ones(x.shape[0], jnp.float32), hp,
                    prec)


def fold_hp(cfg: dict) -> tuple:
    return (cfg["hidden_mc"], cfg["alpha"], cfg["eps"], cfg["n_classes"])


@functools.partial(jax.jit, static_argnames=("mj", "k", "prec"))
def _probs(hidden_w, hidden_b, ro_w, ro_b, x, mj: int, k: int, prec):
    h = hc_softmax(hidden_b[None, :] + mm(x, hidden_w, prec), mj)
    return hc_softmax(ro_b[None, :] + mm(h, ro_w, prec), k)


def class_probs(state: dict, cfg: dict, x, prec: str = "highest",
                block: int = 512) -> np.ndarray:
    """Class probabilities of ``state`` on ``x``, in blocks of rows."""
    out = []
    for i in range(0, len(x), block):
        out.append(np.asarray(_probs(
            state["hidden"]["w"], state["hidden"]["b"],
            state["readout"]["w"], state["readout"]["b"],
            jnp.asarray(x[i:i + block]), cfg["hidden_mc"],
            cfg["n_classes"], prec)))
    return np.concatenate(out)


@functools.partial(jax.jit, static_argnames=("mj", "prec"))
def _hidden(hidden_w, hidden_b, x, mj: int, prec):
    return hc_softmax(hidden_b[None, :] + mm(x, hidden_w, prec), mj)


def readout_probs(hidden: np.ndarray, ro_w, ro_b, k: int,
                  prec: str = "highest") -> np.ndarray:
    """Class probabilities from precomputed hidden rates (n, Nj) under one
    or many readout states: ro_w (..., Nj, K), ro_b (..., K)."""
    s = ro_b[..., None, :] + mm(jnp.asarray(hidden), jnp.asarray(ro_w),
                                        prec)
    return np.asarray(hc_softmax(s, k))


def hidden_of(state: dict, cfg: dict, x, prec: str = "highest",
              block: int = 512) -> np.ndarray:
    return np.concatenate([
        np.asarray(_hidden(state["hidden"]["w"], state["hidden"]["b"],
                           jnp.asarray(x[i:i + block]), cfg["hidden_mc"],
                           prec))
        for i in range(0, len(x), block)])
