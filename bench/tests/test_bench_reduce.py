"""The trace reduction on a small trace recorded on a TPU v5e: one
unsupervised epoch of Table-1 Model 1 (4 batches of 128, the
``bcpnn_update`` and ``hc_softmax`` kernels) inside a ``bench.fit`` span,
then three served forwards of 64 images (``bcpnn_fwd``) inside
``bench.serve``."""
import os

import pytest

from bench import reduce

TRACE = os.path.join(os.path.dirname(__file__), "data", "small.xplane.pb")


@pytest.fixture(scope="module")
def trace():
    return reduce.load(TRACE)


@pytest.fixture(scope="module")
def summary(trace):
    return reduce.summarize(trace, trace.spans[0][1], trace.spans[-1][2])


def test_loads_device_ops_modules_and_spans(trace):
    assert trace.devices == [0]
    assert len(trace.ops) == 232
    assert [m.module for m in trace.modules] == [
        "jit__train_projection_epoch"] + ["jit__lambda"] * 3
    assert [s[0] for s in trace.spans] == ["bench.fit", "bench.serve"]


def test_device_runs_inside_the_host_spans_that_launched_it(trace):
    fit, serve = trace.spans
    first, *served = trace.modules
    assert fit[1] <= first.start and first.end <= fit[2]
    assert all(serve[1] <= m.start and m.end <= serve[2] for m in served)


def test_busy_idle_and_kernel_times(summary):
    assert summary.window_s == pytest.approx(0.005488909, abs=1e-12)
    assert summary.busy_s == pytest.approx(0.001468293, abs=1e-9)
    assert summary.kernel_s["bcpnn_update_pallas"] == pytest.approx(
        0.000265192, abs=1e-9)
    assert summary.kernel_s["bcpnn_fwd_pallas"] == pytest.approx(
        0.000085157, abs=1e-9)
    assert summary.kernel_s["hc_softmax_pallas"] == pytest.approx(
        0.000018166, abs=1e-9)
    assert summary.module_s["jit__train_projection_epoch"] == pytest.approx(
        0.001284714, abs=1e-9)
    assert summary.collective_s == {0: 0}


def test_breakdown(summary):
    name, seconds = summary.device_ops[0]
    assert name == "jit__train_projection_epoch/slice.83"
    assert seconds == pytest.approx(0.000315316, abs=1e-9)
    assert len(summary.device_ops) == 10
    assert [g[0] for g in summary.idle_gaps[:3]] == [
        "bench.fit", "bench.serve", "bench.serve"]
    assert summary.idle_gaps[0][1] == pytest.approx(0.001368234, abs=1e-9)
    gaps = sum(s for _, s in summary.idle_gaps)
    assert gaps <= summary.window_s - summary.busy_s + 1e-12


def test_containers_are_not_leaves():
    ops = [reduce.Op(0, "m", "while.1", 0.0, 10.0),
           reduce.Op(0, "m", "fusion.1", 1.0, 2.0),
           reduce.Op(0, "m", "fusion.2", 3.0, 4.0)]
    assert [o.name for o in reduce._leaves(ops)] == ["fusion.1", "fusion.2"]


def test_names():
    assert reduce.op_name("%bcpnn_fwd_pallas.1 = f32[64,4096] custom-call(x)"
                          ) == "bcpnn_fwd_pallas.1"
    assert reduce.kernel_of("bcpnn_fwd_pallas.1") == "bcpnn_fwd_pallas"
    assert reduce.module_name("jit_step(123)") == "jit_step"
    # A TPU trace names a data-parallel all-reduce after its psum.
    assert [n for n in ("psum.7", "all-reduce.1", "all-gather-start.2",
                        "bitcast_multiply_fusion.2")
            if reduce.COLLECTIVE.search(n)] == [
        "psum.7", "all-reduce.1", "all-gather-start.2"]
    assert reduce.span_at([("bench.window", 0.0, 10.0),
                           ("bench.fit", 1.0, 2.0)], 1.2, 1.5) == "bench.fit"
    assert reduce.span_at([], 0.0, 1.0) == "outside bench calls"
    # The window's own span names nothing: a gap inside it and outside
    # every call span is outside bench calls.
    assert reduce.span_at([("bench.window", 0.0, 10.0),
                           ("bench.fit", 1.0, 2.0)], 3.0, 4.0
                          ) == "outside bench calls"
    assert reduce.span_at([("bench.window", 0.0, 10.0),
                           ("bench.submit", 1.0, 1.1),
                           ("bench.result", 1.1, 1.9)], 1.0, 2.0
                          ) == "bench.result"
