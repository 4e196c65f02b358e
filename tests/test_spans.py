"""Host spans (``repro.spans``): off, they cost one check and record
nothing; under a profiler session the serving engine and the trainer
record their spans, nested, in the buffer and on the trace's host
plane."""
import glob
import tracemalloc

import jax
import numpy as np
import pytest

from repro import spans
from repro.configs.bcpnn_models import deep_synth_spec
from repro.core import Trainer, init_deep
from repro.core.network import infer_packed, supervised_readout_step
from repro.serve import BCPNNService

ENGINE = {"engine.wait", "engine.schedule", "engine.group", "engine.pad",
          "engine.dispatch", "engine.readback", "engine.complete",
          "engine.fold", "engine.fold.learn", "engine.fold.check",
          "engine.fold.repack"}


@pytest.fixture
def profiled(tmp_path):
    """A profiler session around the test body; yields the trace's
    directory, written when the session stops."""
    spans.clear()
    jax.profiler.start_trace(str(tmp_path))
    try:
        yield str(tmp_path)
    finally:
        if jax.profiler.TraceAnnotation.is_enabled():
            jax.profiler.stop_trace()


def host_names(trace_dir: str) -> set:
    (path,) = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    return {e.name for p in data.planes if p.name == "/host:CPU"
            for line in p.lines for e in line.events}


def nested(recs) -> None:
    """Every record lies inside the record its ``parent`` names."""
    by_id = {r.id: r for r in recs}
    for r in recs:
        if r.parent >= 0:
            p = by_id[r.parent]
            assert p.start <= r.start <= r.end <= p.end, (p, r)


def test_off_span_is_the_shared_noop_and_records_nothing():
    spans.clear()
    assert spans.span("engine.pad") is spans.OFF
    assert spans.span("engine.group", cpu=True, n=3) is spans.OFF
    for _ in range(100):
        with spans.span("warm"):
            pass
    tracemalloc.start()
    try:
        before, _ = tracemalloc.get_traced_memory()
        tracemalloc.reset_peak()
        for _ in range(10_000):
            with spans.span("engine.pad", cpu=True) as sp:
                sp.set(n=1)
        _, peak = tracemalloc.get_traced_memory()
    finally:
        tracemalloc.stop()
    assert peak - before < 1024          # no allocation per call
    t = spans.timed("engine.group")
    with t:
        pass
    assert t.dt >= 0.0 and not t.on
    assert spans.recorded() == [] and spans.dropped() == 0


def test_buffer_is_bounded_and_counts_what_it_drops(profiled, monkeypatch):
    monkeypatch.setattr(spans, "CAPACITY", 3)
    for i in range(5):
        with spans.span("s", i=i):
            pass
    jax.profiler.stop_trace()
    assert [r.args["i"] for r in spans.recorded()] == [0, 1, 2]
    assert spans.dropped() == 2
    spans.clear()
    assert spans.recorded() == [] and spans.dropped() == 0


def test_serving_engine_spans(profiled):
    spec = deep_synth_spec(side=6, depth=1, n_classes=3, hidden_hc=4,
                           hidden_mc=8, backend="jnp")
    state = init_deep(spec, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    xs = rng.random((40, spec.input_geom.N)).astype(np.float32)
    svc = BCPNNService(state, spec, max_batch=8, online_learning=True,
                       feedback_batch=4, feedback_eager=False).start()
    try:
        ids = []
        for i, x in enumerate(xs):
            ids.append(svc.submit(x))
            svc.feedback(x, i % 3)
        for rid in ids:
            svc.result(rid, timeout=60.0)
    finally:
        svc.stop()
    jax.profiler.stop_trace()
    recs = spans.recorded()
    names = {r.name for r in recs}
    assert ENGINE - {"engine.wait"} <= names
    groups = [r for r in recs if r.name == "engine.group"]
    assert sum(r.args["n"] for r in groups) == len(xs)
    assert sorted(r.args["seq"] for r in groups) == list(
        range(1, len(groups) + 1))
    for g in groups:
        assert g.args["model"] == "default"
        assert g.args["rid_lo"] <= g.args["rid_hi"]
        assert g.args["wait_s"] >= 0.0
        kids = [r.name for r in recs if r.parent == g.id]
        assert kids == ["engine.pad", "engine.dispatch", "engine.readback",
                        "engine.complete"]
    for r in recs:
        if r.name in ("engine.schedule", "engine.pad", "engine.complete"):
            assert 0.0 <= r.args["cpu_s"]
    folds = [r for r in recs if r.name == "engine.fold"]
    assert [r.args["fold"] for r in folds] == list(
        range(1, len(xs) // 4 + 1))
    assert all(r.args["n"] == 4 for r in folds)
    for f in folds:
        assert [r.name for r in recs if r.parent == f.id] == [
            "engine.fold.learn", "engine.fold.check", "engine.fold.repack"]
    nested(recs)
    assert names <= host_names(profiled)


def test_trainer_spans(profiled):
    spec = deep_synth_spec(side=4, depth=2, n_classes=2, hidden_hc=2,
                           hidden_mc=8, backend="jnp")
    rng = np.random.default_rng(1)
    x = rng.random((40, spec.input_geom.N)).astype(np.float32)
    y = rng.integers(0, 2, 40)
    stats = Trainer(spec, seed=0).fit(x, y, epochs=2, batch=16)
    jax.profiler.stop_trace()
    assert set(stats) == {"train_ms_per_img", "straggler_events"}
    recs = spans.recorded()
    (fit,) = [r for r in recs if r.name == "trainer.fit"]
    assert fit.args == {"images": 40, "epochs": 2, "batch": 16}
    top = [r.name for r in recs if r.parent == fit.id]
    assert top == ["trainer.prepare"] + ["trainer.epoch"] * 2 + [
        "trainer.propagate"] + ["trainer.epoch"] * 3
    epochs = [r for r in recs if r.name == "trainer.epoch"]
    assert [r.args["tag"] for r in epochs] == [
        "unsup/L0/e0", "unsup/L0/e1", "unsup/L1/e0", "unsup/L1/e1",
        "sup/readout"]
    for e in epochs:
        assert e.args["batches"] == 3
        assert e.args["learn"] == "jnp"
        assert [r.name for r in recs if r.parent == e.id] == [
            "trainer.dispatch", "trainer.block"]
    nested(recs)
    assert {r.name for r in recs} <= host_names(profiled)


@pytest.mark.parametrize("n", [40, 32], ids=["padded-tail", "whole-batch"])
def test_trainer_epoch_span_names_the_fused_learn(profiled, n):
    """On the pallas backend every epoch program of a dense network, the
    masked ones of a fit with a padded tail included, learns through the
    fused update kernel, and its span says so."""
    spec = deep_synth_spec(side=4, depth=1, n_classes=2, hidden_hc=2,
                           hidden_mc=8, backend="pallas")
    rng = np.random.default_rng(3)
    x = rng.random((n, spec.input_geom.N)).astype(np.float32)
    Trainer(spec, seed=0).fit(x, rng.integers(0, 2, n), epochs=1, batch=16)
    jax.profiler.stop_trace()
    epochs = [r for r in spans.recorded() if r.name == "trainer.epoch"]
    assert [(r.args["tag"], r.args["learn"]) for r in epochs] == [
        ("unsup/L0/e0", "bcpnn_update"), ("sup/readout", "bcpnn_update")]


def test_trainer_checkpoint_spans(profiled, tmp_path):
    spec = deep_synth_spec(side=4, depth=1, n_classes=2, hidden_hc=2,
                           hidden_mc=8, backend="jnp")
    rng = np.random.default_rng(2)
    x = rng.random((32, spec.input_geom.N)).astype(np.float32)
    y = rng.integers(0, 2, 32)
    Trainer(spec, seed=0).fit(x, y, epochs=1, batch=16,
                              ckpt_dir=str(tmp_path / "ck"),
                              ckpt_every_batches=1)
    jax.profiler.stop_trace()
    recs = spans.recorded()
    epochs = [r for r in recs if r.name == "trainer.epoch"]
    assert len(epochs) == 4             # two chunks of one batch per epoch
    for e in epochs:
        assert [r.name for r in recs if r.parent == e.id] == [
            "trainer.dispatch", "trainer.block", "trainer.checkpoint"]


def test_serving_programs_are_named_and_unchanged():
    """The engine's programs carry stable names; the programs are those
    of the anonymous functions they replace."""
    spec = deep_synth_spec(side=4, depth=1, n_classes=3, hidden_hc=2,
                           hidden_mc=8, backend="jnp")
    state = init_deep(spec, jax.random.PRNGKey(0))
    svc = BCPNNService(state, spec, max_batch=8, online_learning=True,
                       feedback_batch=4)
    slot = svc._slot(None)
    ni = spec.input_geom.N
    f32 = jax.ShapeDtypeStruct((8, ni), np.float32)
    v = jax.ShapeDtypeStruct((8,), np.float32)
    y = jax.ShapeDtypeStruct((4,), np.int32)
    fb = jax.ShapeDtypeStruct((4, ni), np.float32)
    infer = slot.infer_fn.lower(slot.pack, f32, v).as_text()
    fold = slot.learn_fn.lower(slot.state, fb, y).as_text()
    assert "jit_serve_infer" in infer and "jit_serve_fold" in fold
    anon_infer = jax.jit(lambda pk, x, v: infer_packed(
        pk, slot.spec, x, valid=v)).lower(slot.pack, f32, v).as_text()
    anon_fold = jax.jit(lambda st, x, y: supervised_readout_step(
        st, slot.spec, x, y)).lower(slot.state, fb, y).as_text()
    assert infer.replace("jit_serve_infer", "jit__lambda") == anon_infer
    assert fold.replace("jit_serve_fold", "jit__lambda") == anon_fold
