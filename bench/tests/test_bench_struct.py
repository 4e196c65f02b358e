"""The structural-plasticity training cell at CPU size: the program
against its plain reference (``bench/references/bcpnn_struct.py``), the
cell through the harness with a fault planted under it, the control and
the faults of ``bench/controls_struct.py``, the work counts of
``bench/work_struct.py`` and the three per-layer readers."""
import dataclasses
import json
import os
import shutil

import jax
import numpy as np
import pytest

from bench import data, harness, reduce, work, work_struct
from bench.checks import worst_stats
from bench.references import bcpnn_struct as ref
from repro import spans
from repro.spans import Record

from . import tiny

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
# 12x12x2 inputs, hidden 4x16, 16 of 144 input HCs per hidden HC, a rewire
# every 4 hidden learn steps: 3 batches an epoch, 3 rewires a fit
STRUCT = dict(tiny.NET, dataset="breast", n_classes=2, epochs=4, n_train=40,
              n_test=16, image_side=12, input_hc=144, hidden_hc=4,
              hidden_mc=16, nact_hi=16, struct_every=4, patchy_traces=False,
              compact=False, reference="bench/references/bcpnn_struct.py")
# Limits at this size: the program reads trace gaps up to 4.2e-6 and no
# mask gap on the CPU, the control (the reference at ``high``) 1e-4 and
# more; the rewiring limit is the chip cell's.
LIMITS = {"trace_gap_p99.fit1": 2e-5, "trace_gap_p99.fit3": 2e-5,
          "probs_gap.fit3": 1e-3, "mask_margin.fit1": 1e-3,
          "mask_margin.fit3": 1e-3}
METRICS = ("struct_rewire_share", "patchy_fwd_roofline",
           "masked_update_roofline")
LEAVES = ("pi", "pj", "pij")


def proj_snapshot(state) -> dict:
    def proj(p):
        return {"pi": p.traces.pi, "pj": p.traces.pj, "pij": p.traces.pij,
                "mask": p.mask}
    return {"hidden": proj(state.projs[0]), "readout": proj(state.readout)}


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_program_follows_the_reference_through_rewires(backend):
    """Two fits through ``Trainer`` against the reference: the traces
    within 1e-5 of the largest element, the masks identical after each
    fit, the masks rewired away from the initial draw."""
    from repro.core import Trainer
    from bench.drivers.train_fit import program_config

    cfg = dict(STRUCT, backend=backend)
    rng = np.random.default_rng(7)
    x, y = data.encoded(cfg, cfg["n_train"], rng)
    tr = Trainer(program_config(cfg), seed=11)
    st = ref.init(jax.random.PRNGKey(11), ref.geometry(cfg), cfg["eps"])
    mask0 = np.asarray(st["hidden"]["mask"])
    np.testing.assert_array_equal(np.asarray(tr.state.projs[0].mask), mask0)
    for fit in (1, 2):
        tr.fit(x, y, epochs=cfg["epochs"], batch=16)
        st = ref.fit(st, cfg, x, y, cfg["epochs"], 16)
        got = proj_snapshot(tr.state)
        assert worst_stats(got, st, LEAVES)["max"] < 1e-5
        np.testing.assert_array_equal(np.asarray(got["hidden"]["mask"]),
                                      np.asarray(st["hidden"]["mask"]))
        assert int(st["hidden"]["t"]) == 12 * fit     # 3 rewires a fit
    assert np.any(np.asarray(st["hidden"]["mask"]) != mask0)


def test_reference_rewire_keeps_the_top_scores_lowest_index_first():
    scores = np.array([[0.5, 0.1], [0.9, 0.1], [0.5, 0.3], [0.2, 0.1]],
                      np.float32)
    got = np.asarray(ref.top_mask(scores, 2))
    np.testing.assert_array_equal(got, [[1, 1], [1, 0], [0, 1], [0, 0]])


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """The tiny tree with a struct cell, ``t-struct``, and the three
    readers on it."""
    root = tiny.write(str(tmp_path_factory.mktemp("bench-tree")))

    def put(rel, obj):
        with open(os.path.join(root, rel), "w") as f:
            json.dump(obj, f)

    put("bench/configs/tiny-struct.json", dict(STRUCT, name="tiny-struct"))
    put("bench/traffic/fit-struct.json",
        {"kind": "train_fit_struct", "batch": 16, "setup_fits": 3,
         "data_parallel": 1})
    put("bench/limits/t-struct.json",
        {"checks": {k: {"limit": v} for k, v in LIMITS.items()}})
    path = os.path.join(root, "BENCHMARK.json")
    spec = json.load(open(path))
    spec["workloads"].append({"name": "t-struct", "config": "tiny-struct",
                              "traffic": "fit-struct", "chips": 1,
                              "why": "test"})
    spec["end_to_end"][0]["workloads"].append("t-struct")
    for name in METRICS:
        shutil.copy(harness.part(ROOT, "metrics", name, ".py"),
                    os.path.join(root, "bench", "metrics"))
        spec["per_layer"].append(
            {"name": name, "unit": "%", "better": "lower",
             "source": "program_counter", "layer": "test",
             "moves": "train_images_per_s", "workloads": ["t-struct"]})
    json.dump(spec, open(path, "w"))
    return root


@pytest.fixture
def fresh_programs():
    """Programs traced by an earlier test must not hide a planted fault."""
    jax.clear_caches()
    yield
    jax.clear_caches()


def test_struct_cell_sound_run_is_correct_and_counts_rewires(
        root, tmp_path, fresh_programs):
    spans.clear()
    out = tiny.run(root, "t-struct", trace=True, trace_dir=str(tmp_path))
    assert out["correct"] is True, out["checks"]
    assert out["checks"]["mask_margin.fit3"]["value"] == 0.0
    assert out["metrics"]["struct_rewire_share"]["unit"] == "%"
    epochs = [r for r in spans.recorded() if r.name == "trainer.epoch"]
    assert sum(r.args["rewires"] for r in epochs) > 0
    assert any(r.args.get("rewired", 0) > 0 for r in spans.recorded()
               if r.name == "trainer.fit")


def _never(proj, spec):
    return proj


def _lowest(real):
    return lambda *a, **k: -real(*a, **k)


@pytest.mark.parametrize("fault", ["rewire_never", "rewire_lowest",
                                   "mask_ignored"])
def test_struct_cell_fault_under_the_program_is_not_correct(
        root, monkeypatch, fresh_programs, fault):
    from repro.core import bcpnn_layer, network

    if fault == "rewire_never":
        monkeypatch.setattr(network, "maybe_rewire", _never)
    elif fault == "rewire_lowest":
        monkeypatch.setattr(bcpnn_layer, "mutual_information",
                            _lowest(bcpnn_layer.mutual_information))
    else:
        monkeypatch.setattr(bcpnn_layer, "apply_hc_mask",
                            lambda w, mask, spec: w)
    out = tiny.run(root, "t-struct")
    assert out["correct"] is False, out["checks"]


def test_control_and_faults_read_above_the_limits(root):
    """The control -- the reference at the next precision down -- and each
    fault planted in the reference read above a limit that the program
    meets."""
    from bench import controls_struct

    ctx = harness.resolve(root, "t-struct")
    ctx.seed = 2**31 + 5
    readings = controls_struct.readings(ctx, ctx.seed)
    lim = harness.limits(root, "t-struct")
    assert all(readings["program"][k] <= v for k, v in lim.items())
    assert set(readings) == {"program", "unchanged",
                             *controls_struct.VARIANTS}
    for name in set(readings) - {"program"}:
        assert any(readings[name][k] > v for k, v in lim.items()), name
    for name in ("rewire_never", "rewire_lowest"):
        assert readings[name]["mask_gap.fit1"] > 0.05
        assert readings[name]["mask_margin.fit1"] > lim["mask_margin.fit1"]


def test_mask_margin_reads_how_far_a_flip_lies_from_the_boundary():
    """One column, keep 2 of 4: swapping the near-tie at rank 2 reads a
    small margin, keeping the lowest score a large one."""
    from bench.drivers.train_fit_struct import mask_margin

    want = {"mi": np.array([[1.0], [0.501], [0.499], [0.0]]),
            "mask": np.array([[1.0], [1.0], [0.0], [0.0]])}
    assert mask_margin(want["mask"], want, 2) == 0.0
    near = mask_margin(np.array([[1.0], [0.0], [1.0], [0.0]]), want, 2)
    far = mask_margin(np.array([[1.0], [1.0], [0.0], [1.0]]), want, 2)
    assert near == pytest.approx(0.001)
    assert far == pytest.approx(0.5)


# ------------------------------------------------------------ work ----

def test_struct_work_by_hand():
    ni, nj, ku, b = 8192, 4096, 256, 128
    pf = work_struct.patchy_forward(ni, nj, ku, b)
    assert (pf.flops, pf.bytes) == (2 * b * ku * nj,
                                    4 * (ku * nj + b * (ni + nj)))
    ml = work_struct.masked_learn(ni, nj, 4096, 32, b)
    assert ml.bytes == work.learn(ni, nj, b).bytes + 4 * 4096 * 32
    assert work_struct.rewire(ni, nj).bytes == 4 * ni * nj
    assert work_struct.unsup_step(ni, nj, ku, b).flops == (
        2 * b * ni * nj + 2 * b * ku * nj)
    assert work_struct.sup_step(ni, nj, ku, 2, b).flops == (
        2 * b * ku * nj + 2 * b * nj * 2)
    assert work_struct.model_flops_train(ni, nj, ku, 2) == (
        2 * ni * nj + 2 * ku * nj, 2 * ku * nj + 2 * nj * 2)


@pytest.mark.parametrize("t0,steps,every,want", [
    (0, 500, 8, 62), (500, 500, 8, 63), (1000, 500, 8, 62), (3, 3, 4, 1),
    (12, 3, 4, 0), (0, 10, 0, 0)])
def test_rewire_points(t0, steps, every, want):
    assert work_struct.rewire_points(t0, steps, every) == want


# --------------------------------------------------------- readers ----

def reader(name: str):
    return harness.load_module(harness.part(ROOT, "metrics", name, ".py"),
                               "struct_metric_" + name)


def reading(window_s=2.0, work_=None, trace=None) -> harness.Reading:
    return harness.Reading(
        e2e={}, window_s=window_s, work=work_ or {}, model_flops=0.0,
        counters={}, trace=trace, chips=1,
        peak=work.peaks("TPU v5 lite"))


def rec(i, name, start, end, parent=-1, **args):
    return Record(name, start, end, parent, args, i)


# Five unsupervised epoch calls, three with a rewire (0.30 s against
# 0.20 s), and the supervised one, in a 2 s window.
EPOCHS = [
    rec(1, "trainer.epoch", 0.0, 0.3, 0, tag="unsup/L0/e0", rewires=1),
    rec(2, "trainer.epoch", 0.3, 0.5, 0, tag="unsup/L0/e1", rewires=0),
    rec(3, "trainer.epoch", 0.5, 0.8, 0, tag="unsup/L0/e2", rewires=1),
    rec(4, "trainer.epoch", 0.8, 1.0, 0, tag="unsup/L0/e3", rewires=0),
    rec(5, "trainer.epoch", 1.0, 1.3, 0, tag="unsup/L0/e4", rewires=1),
    rec(6, "trainer.epoch", 1.3, 1.9, 0, tag="sup/readout", rewires=0),
    rec(0, "trainer.fit", 0.0, 1.9, rewired=7),
]


def test_rewire_share_on_a_hand_built_recording(monkeypatch):
    monkeypatch.setattr(spans, "recorded", lambda: list(EPOCHS))
    got = reader("struct_rewire_share").read(reading())
    assert got == pytest.approx(100.0 * 0.1 * 3 / 2.0)


@pytest.mark.parametrize("recs", [
    [], [r for r in EPOCHS if r.args.get("rewires") != 1],
    [rec(1, "trainer.epoch", 0.0, 0.3, 0, tag="unsup/L0/e0")]],
    ids=["empty", "no-rewire", "no-count"])
def test_rewire_share_reads_nothing_without_both_kinds(monkeypatch, recs):
    monkeypatch.setattr(spans, "recorded", lambda: list(recs))
    assert reader("struct_rewire_share").read(reading()) is None


def test_rewire_share_on_a_program_without_spans(monkeypatch):
    import sys

    import repro

    monkeypatch.delattr(repro, "spans")
    monkeypatch.setitem(sys.modules, "repro.spans", None)
    assert reader("struct_rewire_share").read(reading()) is None


@pytest.fixture(scope="module")
def summary():
    tr = reduce.load(os.path.join(os.path.dirname(__file__), "data",
                                  "small.xplane.pb"))
    return reduce.summarize(tr, tr.spans[0][1], tr.spans[-1][2])


def test_masked_update_roofline_on_a_recorded_trace(summary):
    """The small trace's four Model-1 learn steps through
    ``bcpnn_update_pallas`` (a dense projection's: no mask to read)."""
    w = work.learn(1568, 4096, 128) * 4
    got = reader("masked_update_roofline").read(
        reading(work_={"bcpnn_update_pallas": w}, trace=summary))
    want = (100.0 * work.roofline_s(w, work.peaks("TPU v5 lite"))
            / summary.kernel_s["bcpnn_update_pallas"])
    assert got == pytest.approx(want)
    assert 0.0 < got <= 100.0


def test_patchy_fwd_roofline_reads_the_kernel_time(summary):
    w = work_struct.patchy_forward(8192, 4096, 256, 128) * 10
    assert reader("patchy_fwd_roofline").read(
        reading(work_={"patchy_forward": w}, trace=summary)) is None
    timed = dataclasses.replace(
        summary, kernel_s=dict(summary.kernel_s, patchy_forward=0.01))
    got = reader("patchy_fwd_roofline").read(
        reading(work_={"patchy_forward": w}, trace=timed))
    assert got == pytest.approx(
        100.0 * work.roofline_s(w, work.peaks("TPU v5 lite")) / 0.01)


@pytest.mark.parametrize("name", METRICS[1:])
def test_rooflines_read_nothing_without_work_or_trace(name, summary):
    assert reader(name).read(reading(trace=summary)) is None
    assert reader(name).read(reading(
        work_={"patchy_forward": work.Work(1.0, 1.0),
               "bcpnn_update_pallas": work.Work(1.0, 1.0)})) is None
