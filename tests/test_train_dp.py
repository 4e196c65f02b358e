"""Trainer-driven fault-tolerant data-parallel training (DESIGN.md §12).

Three bug classes are pinned here:
  * the tail-batch fix — ``Trainer.fit`` used to silently DROP up to
    ``batch - 1`` trailing samples; now they are zero-padded and masked,
    with stats divided by the REAL row count (``learn_masked``);
  * DP-fit exactness — a fit driven through the shard_map
    scan-over-batches epoch programs must be bit-for-bit what the
    single-device fit produces, for dense, patchy-held and
    compact-resident projections, on whole-batch AND padded-tail data;
  * elastic kill-resume — a fit interrupted by ``WorkerLost`` resumes
    from its checkpoint cursor on a rebuilt (possibly smaller) mesh and
    lands bit-identical to the uninterrupted run.

Runs on the 2-device host CPU mesh set up by conftest.py.
"""
import numpy as np
import pytest

import jax

from repro.core import FitCursor, Trainer, learn
from repro.core.bcpnn_layer import learn_masked
from repro.core.hypercolumns import LayerGeom
from repro.core.network import init_deep, make_network_spec
from repro.distributed.fault import (StepTimer, WorkerLost, elastic_mesh,
                                     fit_mesh_shape, order_devices_host_major)

needs_mesh = pytest.mark.skipif(
    len(jax.devices()) < 2, reason="needs the 2-device CPU mesh (conftest "
    "sets --xla_force_host_platform_device_count=2)")


def _spec(kind="dense", depth1=True, backend="jnp"):
    kw = dict(alpha=1e-2, backend=backend, support_noise=2.0, noise_steps=50)
    layers = [(6, 8)] if depth1 else [(6, 8), (4, 4)]
    if kind == "dense":
        return make_network_spec(LayerGeom(12, 2), layers, 3, **kw)
    if kind == "patchy":
        return make_network_spec(LayerGeom(12, 2), layers, 3,
                                 nact=[4] * len(layers), patchy_traces=True,
                                 **kw)
    assert kind == "compact"
    return make_network_spec(LayerGeom(12, 2), layers, 3,
                             nact=[4] * len(layers), patchy_traces=True,
                             compact=True, **kw)


def _data(n, seed=0, n_classes=3, dim=24):
    rng = np.random.default_rng(seed)
    return (rng.random((n, dim)).astype(np.float32),
            rng.integers(0, n_classes, n).astype(np.int32))


def _assert_states_equal(got, want, context=""):
    flat_g, _ = jax.tree_util.tree_flatten_with_path(got)
    flat_w, _ = jax.tree_util.tree_flatten_with_path(want)
    assert len(flat_g) == len(flat_w)
    for (path, g), (_, w) in zip(flat_g, flat_w):
        assert np.array_equal(np.asarray(g), np.asarray(w)), (
            f"{context}: leaf {jax.tree_util.keystr(path)} diverged")


def _states_differ(a, b):
    return any(not np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(jax.tree_util.tree_leaves(a),
                               jax.tree_util.tree_leaves(b)))


# ------------------------------------------------------ tail-batch fix --

def test_tail_samples_now_train_the_network():
    """Regression: 41 samples at batch=16 used to fit on only 32 — the
    fit was bit-identical to one that never saw the last 9 samples."""
    spec = _spec("dense")
    x, y = _data(41)
    t_all = Trainer(spec, seed=0)
    t_all.fit(x, y, epochs=2, batch=16)
    t_trim = Trainer(spec, seed=0)
    t_trim.fit(x[:32], y[:32], epochs=2, batch=16)
    assert _states_differ(t_all.state, t_trim.state), (
        "the 9 tail samples left no trace in the learned state — they "
        "are still being dropped")


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_learn_masked_divides_by_real_row_count(backend):
    """The masked learner on a zero-padded batch must match the unmasked
    learner on just the genuine rows: stats divide by the REAL count, not
    the padded batch size (which would dilute every trace).  On the
    pallas backend the count reaches the fused update kernel at run
    time."""
    spec = _spec("dense", backend=backend)
    state = init_deep(spec, jax.random.PRNGKey(0))
    proj, pspec = state.projs[0], spec.projs[0]
    rng = np.random.default_rng(7)
    n, b = 11, 16
    x = np.zeros((b, pspec.pre.N), np.float32)
    y = np.zeros((b, pspec.post.N), np.float32)
    x[:n] = rng.random((n, pspec.pre.N))
    y[:n] = rng.random((n, pspec.post.N))
    valid = (np.arange(b) < n).astype(np.float32)
    got = learn_masked(proj, pspec, x, y, valid)
    want = learn(proj, pspec, x[:n], y[:n])
    # Tolerances absorb fp reduction-order noise only (~1e-7); the bug
    # this pins — dividing by the padded batch size — would shrink every
    # stat by the factor n/b = 11/16, far outside any of these bounds.
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-4, atol=1e-5)


def test_pallas_learn_masked_on_whole_batch_equals_learn():
    """With every row genuine the masked kernel step is the unmasked one,
    bit for bit: ``x * 1`` is exact and the runtime count is the batch."""
    spec = _spec("dense", backend="pallas")
    state = init_deep(spec, jax.random.PRNGKey(0))
    proj, pspec = state.projs[0], spec.projs[0]
    rng = np.random.default_rng(3)
    x = rng.random((16, pspec.pre.N)).astype(np.float32)
    y = rng.random((16, pspec.post.N)).astype(np.float32)
    got = learn_masked(proj, pspec, x, y, np.ones(16, np.float32))
    _assert_states_equal(got, learn(proj, pspec, x, y), "valid all ones")


def _program_eqns(jaxpr, scope=""):
    """``(eqn, jit)`` for every equation of a jaxpr, through call, scan and
    cond bodies but not into Pallas kernel bodies (those run on tiles in
    VMEM); ``jit`` names the innermost jitted function around it."""
    for eqn in jaxpr.eqns:
        yield eqn, scope
        if eqn.primitive.name == "pallas_call":
            continue
        inner = eqn.params.get("name", scope) if eqn.primitive.name in (
            "jit", "pjit") else scope
        for val in eqn.params.values():
            for sub in (val if isinstance(val, (list, tuple)) else [val]):
                sub = getattr(sub, "jaxpr", sub)   # ClosedJaxpr -> Jaxpr
                if hasattr(sub, "eqns"):
                    yield from _program_eqns(sub, inner)


@pytest.mark.parametrize("nact", [None, [4]], ids=["dense", "patchy"])
def test_masked_epoch_program_takes_the_update_kernel(nact):
    """The pallas masked epoch program runs the fused update kernel and
    builds no (Ni, Nj) f32 unit mask for it: a dense projection passes no
    mask, and a dense-resident patchy one passes its HC mask widened along
    rows only, (Ni, Hj), which the kernel widens to unit columns per tile.
    ``pij`` is the kernel's one (Ni, Nj) operand."""
    from repro.core.trainer import _train_projection_epoch_masked

    spec = make_network_spec(LayerGeom(12, 2), [(6, 8)], 3, alpha=1e-2,
                             backend="pallas", nact=nact, support_noise=2.0,
                             noise_steps=50)
    state = init_deep(spec, jax.random.PRNGKey(0))
    ni, nj = spec.projs[0].pre.N, spec.projs[0].post.N
    hj = spec.projs[0].post.H
    hs = np.zeros((2, 16, ni), np.float32)
    valid = np.ones((2, 16), np.float32)
    eqns = list(_program_eqns(jax.make_jaxpr(
        lambda st, h, v: _train_projection_epoch_masked(st, spec, h, v, 0))(
            state, hs, valid).jaxpr))
    kernels = [e for e, jit in eqns if e.primitive.name == "pallas_call"
               and jit == "bcpnn_update_pallas"]
    mask_broadcasts = [
        e for e, _ in eqns if e.primitive.name == "broadcast_in_dim"
        and e.outvars[0].aval.dtype == np.float32
        and int(np.prod(e.outvars[0].aval.shape)) == ni * nj]
    assert len(kernels) == 1
    assert not mask_broadcasts
    shapes = [tuple(v.aval.shape) for v in kernels[0].invars]
    assert shapes.count((ni, nj)) == 1           # pij, nothing beside it
    assert ((ni, hj) in shapes) == (nact is not None)


def test_whole_batch_fit_keeps_the_unmasked_program():
    """Data that divides the batch takes the exact pre-fix epoch program:
    masked and unmasked fits on whole-batch data are bit-identical, i.e.
    the masked path only ever engages when a pad exists."""
    spec = _spec("dense")
    x, y = _data(32)
    t = Trainer(spec, seed=0)
    t.fit(x, y, epochs=2, batch=16)
    assert ("unsup", 0, True) not in t._epoch_cache
    assert ("unsup", 0, False) in t._epoch_cache


# ------------------------------------------- DP fit vs single-device --

@needs_mesh
@pytest.mark.parametrize("kind", ["dense", "patchy", "compact"])
@pytest.mark.parametrize("n", [48, 41], ids=["whole-batch", "padded-tail"])
def test_dp_fit_matches_single_device_bitwise(kind, n):
    spec = _spec(kind)
    x, y = _data(n)
    t1 = Trainer(spec, seed=0)
    t1.fit(x, y, epochs=2, batch=16)
    t2 = Trainer(spec, seed=0, mesh=jax.make_mesh((2,), ("data",)))
    t2.fit(x, y, epochs=2, batch=16)
    _assert_states_equal(t2.state, t1.state, context=f"{kind} n={n}")


@needs_mesh
def test_dp_fit_rejects_unshardable_batch():
    t = Trainer(_spec("dense"), seed=0, mesh=jax.make_mesh((2,), ("data",)))
    x, y = _data(34)
    with pytest.raises(ValueError, match="cannot shard"):
        t.fit(x, y, epochs=1, batch=17)


# ------------------------------------------------- elastic kill-resume --

@needs_mesh
def test_kill_resume_is_bit_exact_across_mesh_sizes(tmp_path):
    """The full recovery ladder: chunked+checkpointed DP fit equals the
    unchunked single-device fit; a fit killed mid-schedule by WorkerLost
    resumes from its cursor — on the SAME mesh and on a SHRUNK 1-way
    elastic mesh — and both land bit-identical to the uninterrupted run."""
    spec = _spec("dense", depth1=False)
    x, y = _data(41, seed=1)
    mesh2 = jax.make_mesh((2,), ("data",))

    t_ref = Trainer(spec, seed=0)
    t_ref.fit(x, y, epochs=2, batch=16)

    d_full = tmp_path / "full"
    t_a = Trainer(spec, seed=0, mesh=mesh2)
    stats = t_a.fit(x, y, epochs=2, batch=16, ckpt_dir=str(d_full),
                    ckpt_every_batches=2)
    _assert_states_equal(t_a.state, t_ref.state, context="chunked DP")
    assert "straggler_events" in stats

    def interrupted_dir(name, kill_at):
        d = tmp_path / name
        calls = {"n": 0}

        def killer(cur):
            calls["n"] += 1
            if calls["n"] == kill_at:
                raise WorkerLost(f"simulated loss at {cur}")

        t = Trainer(spec, seed=0, mesh=mesh2)
        with pytest.raises(WorkerLost):
            t.fit(x, y, epochs=2, batch=16, ckpt_dir=str(d),
                  ckpt_every_batches=2, on_chunk=killer)
        return d

    # Same-mesh resume.
    d1 = interrupted_dir("same", kill_at=3)
    t_same = Trainer(spec, seed=0, mesh=mesh2)
    t_same.fit(x, y, epochs=2, batch=16, ckpt_dir=str(d1),
               ckpt_every_batches=2, resume=True)
    _assert_states_equal(t_same.state, t_a.state, context="same-mesh resume")

    # Worker lost: rebuild the largest mesh from one surviving device.
    d2 = interrupted_dir("elastic", kill_at=3)
    mesh1 = elastic_mesh((2,), ("data",), devices=jax.devices()[:1])
    assert dict(mesh1.shape) == {"data": 1}
    t_el = Trainer(spec, seed=0, mesh=mesh1)
    t_el.fit(x, y, epochs=2, batch=16, ckpt_dir=str(d2),
             ckpt_every_batches=2, resume=True)
    _assert_states_equal(t_el.state, t_a.state, context="1-way elastic resume")
    assert t_el.evaluate(x, y, batch=16) == t_ref.evaluate(x, y, batch=16)


def test_resume_requires_a_cursor_checkpoint(tmp_path):
    """A final artifact saved by ``Trainer.save`` has no schedule cursor;
    ``fit(resume=True)`` must refuse it with a pointed error instead of
    silently restarting the schedule from zero on a trained state."""
    spec = _spec("dense")
    x, y = _data(32)
    t = Trainer(spec, seed=0)
    t.save(str(tmp_path))
    with pytest.raises(ValueError, match="no fit cursor"):
        t.fit(x, y, epochs=1, batch=16, ckpt_dir=str(tmp_path), resume=True)
    with pytest.raises(ValueError, match="requires ckpt_dir"):
        t.fit(x, y, epochs=1, batch=16, resume=True)


def test_fit_cursor_roundtrip():
    c = FitCursor("supervised", layer=2, epoch=1, batch=5)
    assert FitCursor.from_dict(c.to_dict()) == c


@pytest.mark.slow
@needs_mesh
def test_chaos_kill_resume_soak(tmp_path):
    """Nightly chaos soak: random kill points and data seeds; every
    interrupted fit, resumed on a randomly shrunk-or-same mesh, must land
    bit-identical to its uninterrupted run with equal eval accuracy."""
    rng = np.random.default_rng(0)
    spec = _spec("dense", depth1=False)
    mesh2 = jax.make_mesh((2,), ("data",))
    for trial in range(3):
        x, y = _data(41, seed=int(rng.integers(1 << 30)))
        t_ref = Trainer(spec, seed=0, mesh=mesh2)
        t_ref.fit(x, y, epochs=2, batch=16)

        kill_at = int(rng.integers(1, 9))
        d = tmp_path / f"trial{trial}"
        calls = {"n": 0}

        def killer(cur):
            calls["n"] += 1
            if calls["n"] == kill_at:
                raise WorkerLost(f"chaos kill at {cur}")

        t_k = Trainer(spec, seed=0, mesh=mesh2)
        with pytest.raises(WorkerLost):
            t_k.fit(x, y, epochs=2, batch=16, ckpt_dir=str(d),
                    ckpt_every_batches=2, on_chunk=killer)

        n_dev = int(rng.integers(1, 3))
        mesh_r = elastic_mesh((2,), ("data",),
                              devices=jax.devices()[:n_dev])
        t_r = Trainer(spec, seed=0, mesh=mesh_r)
        t_r.fit(x, y, epochs=2, batch=16, ckpt_dir=str(d),
                ckpt_every_batches=2, resume=True)
        _assert_states_equal(
            t_r.state, t_ref.state,
            context=f"trial {trial} kill@{kill_at} resume@{n_dev}dev")
        assert t_r.evaluate(x, y) == t_ref.evaluate(x, y)


# ------------------------------------------------------------ fault.py --

def test_step_timer_memory_is_bounded_by_window():
    """Regression: ``_times`` grew one entry per step forever (the window
    was only applied at read time) — a leak on multi-day fits.  It must
    stay trimmed, with ``median`` computed over exactly the retained
    window."""
    t = StepTimer(window=10)
    recorded = []
    for i in range(100):
        t.start()
        recorded.append(t.stop(step=i))
    assert len(t._times) == 10
    assert t._times == recorded[-10:]
    assert t.median == float(np.median(recorded[-10:]))


def test_step_timer_attributes_injected_straggler():
    t = StepTimer(window=20, threshold=3.0)
    t._times = [0.01] * 19
    t._t0 = -1e9  # forces a huge dt for this stop
    t.stop(step=42, tag="unsup/L0/e1")
    assert t.events and t.events[-1]["step"] == 42
    assert t.events[-1]["tag"] == "unsup/L0/e1"
    assert len(t._times) == 20  # trimmed even across the event path


class _StubDev:
    def __init__(self, pid, did):
        self.process_index, self.id = pid, did

    def __repr__(self):
        return f"dev(p{self.process_index},d{self.id})"


def test_order_devices_host_major():
    devs = [_StubDev(1, 0), _StubDev(0, 3), _StubDev(1, 2), _StubDev(0, 1)]
    got = order_devices_host_major(devs)
    assert [(d.process_index, d.id) for d in got] == [
        (0, 1), (0, 3), (1, 0), (1, 2)]


def test_fit_mesh_shape_shrinks_data_axis_only():
    assert fit_mesh_shape((4,), 4) == [4]
    assert fit_mesh_shape((4,), 3) == [3]      # lost one device
    assert fit_mesh_shape((2, 4), 4) == [1, 4]  # lost a whole host row
    with pytest.raises(RuntimeError, match="cannot build mesh"):
        fit_mesh_shape((1, 8), 4)  # model axis never shrinks


@needs_mesh
def test_elastic_mesh_shrinks_and_reports_domains():
    from repro.distributed.fault import describe_failure_domains

    m = elastic_mesh((4,), ("data",))  # only 2 devices exist
    assert dict(m.shape) == {"data": 2}
    m1 = elastic_mesh((4,), ("data",), devices=jax.devices()[:1])
    assert dict(m1.shape) == {"data": 1}
    dom = describe_failure_domains(m)
    assert dom["n_devices"] == 2 and dom["axis_names"] == ["data"]
    m2 = elastic_mesh((2, 2), ("data", "model"))  # 4 wanted, 2 exist
    assert dict(m2.shape) == {"data": 1, "model": 2}  # data axis shrank
    with pytest.raises(RuntimeError, match="cannot build mesh"):
        elastic_mesh((1, 4), ("data", "model"))  # model axis never shrinks
