"""Training cells at CPU size, with the timed path broken underneath:
each fault turns ``correct`` false; the sound run and the control."""
import jax
import numpy as np
import pytest

from . import tiny


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.write(str(tmp_path_factory.mktemp("bench-tree")))


@pytest.fixture(autouse=True)
def fresh_programs():
    """Programs traced by an earlier test must not hide a planted fault."""
    jax.clear_caches()
    yield
    jax.clear_caches()


def test_training_sound_run_is_correct(root):
    out = tiny.run(root, "t-train")
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] > 0


def test_training_step_that_returns_its_state_unchanged(root, monkeypatch):
    from repro.core import trainer

    monkeypatch.setattr(trainer.Trainer, "fit", lambda self, *a, **k: {})
    out = tiny.run(root, "t-train")
    assert out["correct"] is False
    assert out["checks"]["trace_gap_p99.fit1"]["value"] > 0.1


def test_training_half_the_batch_left_out(root, monkeypatch):
    from repro.core import network

    real = network.learn_masked

    def half(proj, spec, x, y, valid):
        keep = (jax.numpy.arange(valid.shape[0]) < valid.shape[0] // 2)
        return real(proj, spec, x, y, valid * keep.astype(valid.dtype))

    monkeypatch.setattr(network, "learn_masked", half)
    out = tiny.run(root, "t-train")
    assert out["correct"] is False


def test_control_fails_where_the_program_passes(root):
    """The control -- the reference at the next precision down, put in
    the program's place -- reads above the limits that sound runs meet."""
    from bench import controls, harness

    ctx = harness.resolve(root, "t-train")
    readings = controls.train_readings(ctx, 7)
    lim = harness.limits(root, "t-train")
    assert all(readings["program"][k] <= v for k, v in lim.items())
    assert any(readings["control_high"][k] > v for k, v in lim.items())
    for fault in ("half_batch", "unchanged"):
        assert any(readings[fault][k] > v for k, v in lim.items())
    np.testing.assert_array_less(
        0.0, [readings["unchanged"][k] for k in lim])


def test_training_control_in_the_program_place_is_not_correct(root,
                                                              monkeypatch):
    """The control put in the program's place through a whole run: the
    program's fits with every product at three bfloat16 passes."""
    tiny.lower_program_precision(monkeypatch)
    out = tiny.run(root, "t-train")
    assert out["correct"] is False, out["checks"]
