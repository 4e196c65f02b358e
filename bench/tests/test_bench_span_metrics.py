"""The per-layer metrics that read the program's host spans
(``repro.spans``): each on a hand-built recording, on an empty one, on a
program without spans, and in a traced run of a tiny cell."""
import json
import os
import shutil
import sys

import pytest

from bench import harness
from repro import spans
from repro.spans import Record

from . import tiny

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SERVING = ["engine_host_us_per_image.closed", "engine_wait_share.closed",
           "engine_queue_wait_ms.closed", "engine_fold_share.closed"]
TRAINING = ["trainer_host_share"]


def reader(name: str):
    return harness.load_module(harness.part(ROOT, "metrics", name, ".py"),
                               "span_metric_" + name.replace(".", "_"))


def reading(window_s: float) -> harness.Reading:
    return harness.Reading(e2e={}, window_s=window_s, work={},
                           model_flops=0.0, counters={}, trace=None, chips=1,
                           peak={})


def rec(i, name, start, end, parent=-1, **args):
    return Record(name, start, end, parent, args, i)


# Two served groups of 3 and 5 images and one fold, in a 2 s window.
SERVE = [
    rec(0, "engine.wait", 0.00, 0.10),
    rec(1, "engine.schedule", 0.10, 0.11, cpu_s=0.01),
    rec(3, "engine.pad", 0.11, 0.13, 2, cpu_s=0.02),
    rec(4, "engine.dispatch", 0.13, 0.14, 2),
    rec(5, "engine.readback", 0.14, 0.40, 2),
    rec(6, "engine.complete", 0.40, 0.42, 2, cpu_s=0.02),
    rec(2, "engine.group", 0.11, 0.42, seq=1, n=3, wait_s=0.3),
    rec(7, "engine.schedule", 0.42, 0.44),
    rec(9, "engine.pad", 0.44, 0.45, 8),
    rec(10, "engine.dispatch", 0.45, 0.47, 8),
    rec(11, "engine.readback", 0.47, 0.90, 8),
    rec(12, "engine.complete", 0.90, 0.91, 8),
    rec(8, "engine.group", 0.44, 0.91, seq=2, n=5, wait_s=0.5),
    rec(14, "engine.fold.learn", 0.91, 1.00, 13),
    rec(13, "engine.fold", 0.91, 1.21, model="default", n=32, fold=1),
    rec(15, "engine.wait", 1.21, 1.51),
]
# Two fits; the second has a block outside any fit beside it.
TRAIN = [
    rec(1, "trainer.prepare", 0.0, 0.2, 0),
    rec(3, "trainer.dispatch", 0.2, 0.3, 2),
    rec(4, "trainer.block", 0.3, 1.0, 2),
    rec(2, "trainer.epoch", 0.2, 1.0, 0, tag="unsup/L0/e0", batches=5),
    rec(0, "trainer.fit", 0.0, 1.1, images=546, epochs=1, batch=128),
    rec(7, "trainer.block", 1.2, 2.0, 6),
    rec(6, "trainer.epoch", 1.1, 2.0, 5),
    rec(5, "trainer.fit", 1.1, 2.1),
    rec(8, "trainer.block", 2.1, 2.5),
]
WANT = {
    # (schedule 0.01 + 0.02, pad 0.02 + 0.01, dispatch 0.01 + 0.02,
    # complete 0.02 + 0.01) s over 8 images
    "engine_host_us_per_image.closed": 1e6 * 0.12 / 8,
    "engine_wait_share.closed": 100.0 * 0.4 / 2.0,
    "engine_queue_wait_ms.closed": 1e3 * 0.8 / 8,
    "engine_fold_share.closed": 100.0 * 0.3 / 2.0,
    # fits 2.1 s less the 1.5 s blocked inside them, over 3 s
    "trainer_host_share": 100.0 * 0.6 / 3.0,
}


@pytest.mark.parametrize("name", SERVING + TRAINING)
def test_reader_on_a_hand_built_recording(name, monkeypatch):
    recs, window = (SERVE, 2.0) if name in SERVING else (TRAIN, 3.0)
    monkeypatch.setattr(spans, "recorded", lambda: list(recs))
    assert reader(name).read(reading(window)) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", SERVING + TRAINING)
def test_reader_on_an_empty_recording_reads_nothing(name, monkeypatch):
    monkeypatch.setattr(spans, "recorded", lambda: [])
    assert reader(name).read(reading(2.0)) is None


@pytest.mark.parametrize("name", SERVING + TRAINING)
def test_reader_on_a_program_without_spans_reads_nothing(name, monkeypatch):
    import repro

    monkeypatch.delattr(repro, "spans")
    monkeypatch.setitem(sys.modules, "repro.spans", None)
    assert reader(name).read(reading(2.0)) is None


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """The tiny tree with the span metrics beside its own, on its serving
    and training cells."""
    root = tiny.write(str(tmp_path_factory.mktemp("bench-tree")))
    path = os.path.join(root, "BENCHMARK.json")
    spec = json.load(open(path))
    for names, cell, moves in ((SERVING, "t-closed", "serve_images_per_s"),
                               (TRAINING, "t-train", "train_images_per_s")):
        for name in names:
            shutil.copy(harness.part(ROOT, "metrics", name, ".py"),
                        os.path.join(root, "bench", "metrics"))
            spec["per_layer"].append(
                {"name": name, "unit": "%", "better": "lower",
                 "source": "program_counter", "layer": "test",
                 "moves": moves, "workloads": [cell]})
    json.dump(spec, open(path, "w"))
    return root


@pytest.mark.parametrize("cell,names", [("t-closed", SERVING),
                                        ("t-train", TRAINING)])
def test_traced_run_reports_the_span_metrics(root, tmp_path, cell, names):
    spans.clear()
    out = tiny.run(root, cell, trace=True, trace_dir=str(tmp_path))
    assert out["correct"] is True
    for name in names:
        assert out["metrics"][name]["value"] >= 0.0
    names_seen = {r.name for r in spans.recorded()}
    assert not any(n.startswith("bench.") for n in names_seen)
    if cell == "t-train":
        share = out["metrics"]["trainer_host_share"]["value"]
        assert 0.0 < share <= 100.0
