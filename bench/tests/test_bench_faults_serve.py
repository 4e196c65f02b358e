"""Serving cells at CPU size, with the timed path broken underneath:
each fault turns ``correct`` false; the sound run is correct."""
import jax
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


def test_serving_sound_run_is_correct(root):
    out = tiny.run(root, "t-closed")
    assert out["correct"] is True, out["checks"]
    assert out["failed"] == 0


def test_serving_answer_altered_where_it_is_produced(root, monkeypatch):
    from repro.serve import engine

    real = engine.infer_packed

    def altered(params, spec, x, valid=None):
        probs, pred = real(params, spec, x, valid=valid)
        k = probs.shape[-1]
        return jax.numpy.roll(probs, 1, axis=-1), (pred + 1) % k

    monkeypatch.setattr(engine, "infer_packed", altered)
    out = tiny.run(root, "t-closed")
    assert out["correct"] is False
    assert out["checks"]["pred_gap"]["value"] > 1e-3


def test_serving_fold_that_learns_nothing(root, monkeypatch):
    from repro.serve import engine

    monkeypatch.setattr(engine, "supervised_readout_step",
                        lambda st, spec, x, y: st)
    out = tiny.run(root, "t-closed")
    assert out["correct"] is False


def test_serving_control_in_the_program_place_is_not_correct(root,
                                                             monkeypatch):
    """The control: the program with every product at three bfloat16
    passes (the step below float32 at ``highest``) serves and folds; the
    comparison with the reference has to reject it."""
    tiny.lower_program_precision(monkeypatch)
    out = tiny.run(root, "t-closed")
    assert out["correct"] is False, out["checks"]
    assert (out["checks"]["fold_gap_p99"]["value"]
            > tiny.SERVE_LIMITS["fold_gap_p99"])
