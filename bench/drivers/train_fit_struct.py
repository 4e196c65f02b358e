"""Training job of a network with structural plasticity: ``Trainer.fit``
of a Table-1 network whose hidden receptive fields (``nact_hi`` input
HCs per hidden HC) are rewired every ``struct_every`` hidden learn
steps, called back to back with its state carried over.

The traffic parameters, the window and the rate are ``train_fit``'s.
The required work counts the struct step (``bench/work_struct.py``): the
dense-trace learn, the patchy forward and a rewire at every
``struct_every``-th step of the hidden projection's trace clock.  The
comparison replays the set-up fits on the structural-plasticity
reference (``bench/references/bcpnn_struct.py``) and reads, beside the
trace and class-probability gaps of ``train_fit``, how far from the
rank-``nact`` boundary the (pre-HC, post-HC) mask entries on which the
two disagree lie in the reference's mutual-information scores.
"""
from __future__ import annotations

from collections import Counter

import numpy as np

from bench import data, harness, work, work_struct
from bench.drivers import train_fit
from bench.references import bcpnn_struct as ref


def snapshot(state) -> dict:
    """``train_fit.snapshot`` with the hidden mask."""
    snap = train_fit.snapshot(state)
    snap["hidden"]["mask"] = np.asarray(state.projs[0].mask)
    return snap


def kernel_calls(fn, *args) -> Counter:
    """Calls of each kernel (by the name of the jitted function around
    its ``pallas_call``) in one trace of ``fn``; empty where the program
    cannot be traced so."""
    import jax

    def eqns(jaxpr, scope=""):
        for eqn in jaxpr.eqns:
            yield eqn, scope
            if eqn.primitive.name == "pallas_call":
                continue
            inner = (eqn.params.get("name", scope)
                     if eqn.primitive.name in ("jit", "pjit") else scope)
            for val in eqn.params.values():
                for sub in (val if isinstance(val, (list, tuple)) else [val]):
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        yield from eqns(sub, inner)

    try:
        jaxpr = jax.make_jaxpr(fn)(*args).jaxpr
    except (AttributeError, TypeError, ValueError):
        return Counter()
    return Counter(scope for e, scope in eqns(jaxpr)
                   if e.primitive.name == "pallas_call")


def fit_rewired() -> list:
    """The ``rewired`` arg of each recorded ``trainer.fit`` span; empty
    on a program whose spans do not carry it, or when none recorded."""
    try:
        from repro import spans
    except ImportError:
        return []
    return [s.args["rewired"] for s in spans.recorded()
            if s.name == "trainer.fit" and "rewired" in s.args]


class Driver(train_fit.Driver):
    def setup(self) -> None:
        import jax

        from repro.core import Trainer

        cfg, tf = self.cfg, self.tf
        rng = np.random.default_rng(self.ctx.seed)
        self.x, self.y = data.encoded(cfg, cfg["n_train"], rng)
        self.probe, _ = data.encoded(cfg, cfg["n_test"], rng)
        self.trainer = Trainer(train_fit.program_config(cfg),
                               seed=self.ctx.seed)
        with self.ctx.excluded():
            self.calls = self._calls_per_step()
        for i in range(1, tf["setup_fits"] + 1):
            self._fit()
            if i in (1, tf["setup_fits"]):
                with self.ctx.excluded():
                    self.snaps[i] = snapshot(self.trainer.state)
        jax.block_until_ready(self.trainer.state)

    def _calls_per_step(self) -> dict:
        """Kernel calls of one unsupervised and one supervised learn step
        of the program, from its own trace at the cell's shapes."""
        import jax
        import jax.numpy as jnp

        from repro.core import network

        spec, b = self.trainer.spec, self.tf["batch"]
        state = self.trainer.state
        h = jax.ShapeDtypeStruct((b, spec.input_geom.N), jnp.float32)
        v = jax.ShapeDtypeStruct((b,), jnp.float32)
        lab = jax.ShapeDtypeStruct((b,), jnp.int32)
        return {
            "unsup": kernel_calls(
                lambda st, h, v: network.train_projection_step(
                    st, spec, h, 0, valid=v), state, h, v),
            "sup": kernel_calls(
                lambda st, x, y, v: network.supervised_readout_step(
                    st, spec, x, y, valid=v), state, h, lab, v)}

    def window(self, seconds: float) -> harness.WindowResult:
        res = super().window(seconds)
        cfg, tf = self.cfg, self.tf
        fits, epochs = res.attempted, cfg["epochs"]
        hi, mi = cfg["input_hc"], cfg["input_mc"]
        ni, nj = hi * mi, cfg["hidden_hc"] * cfg["hidden_mc"]
        k, ku = cfg["n_classes"], cfg["nact_hi"] * mi
        rows = train_fit.batch_rows(cfg["n_train"], tf["batch"])
        steps = len(rows) * epochs        # hidden learn steps a fit
        t0 = tf["setup_fits"] * steps     # the hidden trace clock
        rewires = work_struct.rewire_points(t0, fits * steps,
                                            cfg["struct_every"])
        unsup = sum((work_struct.unsup_step(ni, nj, ku, b) for b in rows),
                    work.Work()) * (epochs * fits)
        sup = sum((work_struct.sup_step(ni, nj, ku, k, b) for b in rows),
                  work.Work()) * fits
        res.work["train_steps"] = (unsup + sup
                                   + work_struct.rewire(ni, nj) * rewires)
        hj = cfg["hidden_hc"]
        # the work of one call of each kernel on b genuine rows, in an
        # unsupervised and in a supervised step
        per_call = {
            "patchy_forward": (
                lambda b: work_struct.patchy_forward(ni, nj, ku, b),) * 2,
            "bcpnn_update_pallas": (
                lambda b: work_struct.masked_learn(ni, nj, hi, hj, b),
                lambda b: work.learn(nj, k, b))}
        for name, (w_unsup, w_sup) in per_call.items():
            n_unsup = self.calls["unsup"].get(name, 0)
            n_sup = self.calls["sup"].get(name, 0)
            if n_unsup or n_sup:
                res.work[name] = sum(
                    (w_unsup(b) * (n_unsup * epochs) + w_sup(b) * n_sup
                     for b in rows), work.Work()) * fits
        unsup_f, sup_f = work_struct.model_flops_train(ni, nj, ku, k)
        res.model_flops = fits * cfg["n_train"] * (epochs * unsup_f + sup_f)
        res.log.append(f"[bench] {rewires} rewires in the window; kernel "
                       f"calls a learn step {dict(self.calls['unsup'])} "
                       f"(unsupervised), {dict(self.calls['sup'])} "
                       f"(supervised)")
        rewired = fit_rewired()
        if rewired:
            res.log.append(f"[bench] mask entries rewired a fit (the "
                           f"trainer.fit spans' rewired): {rewired}")
        return res

    def check(self) -> list:
        """Replay the set-up fits on the structural-plasticity reference
        and compare (``compare``)."""
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


def mask_margin(got_mask, want: dict, nact: int) -> float:
    """Where the masks differ, the widest distance of the reference's
    last rewire scores from its column's rank-``nact`` boundary (the
    midpoint of the nact-th and the next score), over the column's score
    range: 0 where the masks agree, small where only near-ties flipped."""
    diff = np.asarray(got_mask) != np.asarray(want["mask"])
    if not diff.any():
        return 0.0
    s = np.asarray(want["mi"], np.float64)
    top = -np.sort(-s, axis=0)
    edge = 0.5 * (top[nact - 1] + top[nact])
    span = np.maximum(top[0] - top[-1], 1e-30)
    return float(np.max((np.abs(s - edge) / span)[diff]))


def compare(got: dict, want: dict, cfg: dict, probe: np.ndarray,
            start: dict) -> list:
    """``train_fit.compare``'s numbers (the class probabilities read
    through the masked weights), and after each compared fit the rewiring
    read through mutual information: ``mask_margin`` (compared), since a
    near-tie at rank ``nact`` can flip between two summation orders of
    the same scores, and the share of mask entries that differ
    (``mask_gap``, printed)."""
    out = train_fit.compare(got, want, cfg, probe, start)
    for i in sorted(got):
        g, w = got[i]["hidden"]["mask"], want[i]["hidden"]["mask"]
        out.append(harness.Check(
            f"mask_margin.fit{i}",
            mask_margin(g, want[i]["hidden"], cfg["nact_hi"]), None))
        out.append(harness.Check(f"mask_gap.fit{i}", float(np.mean(
            np.asarray(g) != np.asarray(w))), None, False))
    return out
