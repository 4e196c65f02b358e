"""Structural plasticity on the dense-trace, HC-masked layout (Table 1's
nactHi; DESIGN.md §2, §7): the kernels the learn steps run, and the
trainer's spans that count rewires."""
import jax
import numpy as np
import pytest

from repro import spans
from repro.core import Trainer
from repro.core.network import (
    BCPNNConfig, supervised_readout_step, train_projection_step)

# 12x12x2 inputs, hidden 4x16, 16 of 144 input HCs per hidden HC
SMALL = dict(input_hc=144, input_mc=2, hidden_hc=4, hidden_mc=16,
             n_classes=2, nact_hi=16, alpha=2e-3, support_noise=3.0,
             noise_steps=30, struct_every=4)


def _data(n=40, seed=5):
    rng = np.random.default_rng(seed)
    return (rng.random((n, 288)).astype(np.float32),
            rng.integers(0, 2, n).astype(np.int32))


def _program_kernels(fn, *args) -> list:
    """Names of the jitted functions around each Pallas call of ``fn``."""
    def walk(jaxpr, scope):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                yield scope
                continue
            inner = (eqn.params.get("name", scope)
                     if eqn.primitive.name in ("jit", "pjit") else scope)
            for val in eqn.params.values():
                for sub in (val if isinstance(val, (list, tuple)) else [val]):
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        yield from walk(sub, inner)
    return sorted(walk(jax.make_jaxpr(fn)(*args).jaxpr, ""))


def test_struct_learn_steps_run_the_masked_update_and_patchy_forward():
    """Every learn step of the struct network updates through the fused
    kernel, the hidden one with its mask; the supervised step's hidden
    forward streams only the live blocks (``patchy_forward``).  The
    unsupervised step's noisy support is the masked dense product, and
    the rewire stays jnp."""
    spec = BCPNNConfig(**SMALL, backend="pallas").network_spec()
    state = Trainer(spec, seed=0).state
    h, labels = _data(16)
    valid = np.ones(16, np.float32)
    unsup = _program_kernels(
        lambda st, h, v: train_projection_step(st, spec, h, 0, valid=v),
        state, h, valid)
    sup = _program_kernels(
        lambda st, x, y, v: supervised_readout_step(st, spec, x, y,
                                                    valid=v),
        state, h, labels, valid)
    assert unsup == ["bcpnn_update_pallas", "hc_softmax_pallas"]
    assert sup == ["bcpnn_update_pallas", "patchy_forward"]


def _profiled_fit(trainer, x, y, epochs, tmp_path):
    spans.clear()
    jax.profiler.start_trace(str(tmp_path))
    try:
        trainer.fit(x, y, epochs=epochs, batch=16)
    finally:
        jax.profiler.stop_trace()
    return spans.recorded()


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_trainer_spans_count_rewires(backend, tmp_path):
    """``trainer.epoch`` carries the rewire points of its learn steps,
    worked out on the host from the trace clock, and ``trainer.fit`` the
    mask entries that changed over the fit."""
    x, y = _data()                       # 3 batches an epoch (16, 16, 8)
    tr = Trainer(BCPNNConfig(**SMALL, backend=backend), seed=3)
    tr.fit(x, y, epochs=1, batch=16)     # clock at 3, untraced
    before = np.asarray(tr.state.projs[0].mask)
    recs = _profiled_fit(tr, x, y, 5, tmp_path)
    after = np.asarray(tr.state.projs[0].mask)
    epochs = [r for r in recs if r.name == "trainer.epoch"]
    assert [r.args["rewires"] for r in epochs] == [
        # clock 3 -> 6 -> 9 -> 12 -> 15 -> 18, rewires at multiples of 4
        1, 1, 1, 0, 1, 0]
    assert epochs[-1].args["tag"] == "sup/readout"
    assert int(tr.state.projs[0].traces.t) == 18
    (fit,) = [r for r in recs if r.name == "trainer.fit"]
    assert fit.args["rewired"] == int(np.sum(before != after)) > 0


def test_dense_network_fit_span_carries_no_rewired(tmp_path):
    x, y = _data()
    cfg = BCPNNConfig(**dict(SMALL, nact_hi=144))
    recs = _profiled_fit(Trainer(cfg, seed=3), x, y, 1, tmp_path)
    (fit,) = [r for r in recs if r.name == "trainer.fit"]
    assert "rewired" not in fit.args
    assert [r.args["rewires"] for r in recs
            if r.name == "trainer.epoch"] == [0, 0]
