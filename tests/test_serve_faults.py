"""Serving robustness tests (DESIGN.md §10): admission control +
deadlines, typed Overloaded rejection, poison-request bisection, worker
supervision and dead-worker detection, learning-state quarantine, the
deterministic fault-injection harness itself, and a seeded chaos soak
(slow marker) that drives all four fault classes under Poisson load and
asserts zero lost/hung requests."""
import threading
import time

import jax
import numpy as np
import pytest

from repro.configs.bcpnn_models import deep_synth_spec
from repro.core import infer, init_deep
from repro.serve import (
    BCPNNService, CompileFailed, DeadlineExceeded, Fault, FaultInjected, FaultInjector,
    Overloaded, Quarantined, WorkerDied, run_open_loop,
)
from repro.serve.engine import _state_finite


def _small_net(seed=0, side=6, n_classes=3):
    spec = deep_synth_spec(side=side, depth=1, n_classes=n_classes,
                           hidden_hc=4, hidden_mc=8, backend="jnp")
    return spec, init_deep(spec, jax.random.PRNGKey(seed))


def _x(spec, seed=0, n=1):
    x = np.asarray(jax.random.uniform(jax.random.PRNGKey(seed),
                                      (n, spec.input_geom.N)), np.float32)
    return x[0] if n == 1 else x


class _Blocker(FaultInjector):
    """Test-controlled injector: the worker blocks at the slow-batch
    point until released, so a test can deterministically build a
    backlog behind an in-flight microbatch."""

    def __init__(self, **kw):
        super().__init__(**kw)
        self.entered = threading.Event()
        self.release = threading.Event()

    def maybe(self, point):
        if point == "slow-batch":
            self.entered.set()
            assert self.release.wait(30.0), "blocker never released"
        return super().maybe(point)


# ------------------------------------------------------ fault injector ----

def test_injector_rejects_unknown_points():
    with pytest.raises(ValueError):
        FaultInjector(rates={"no-such-point": 0.5})
    with pytest.raises(ValueError):
        FaultInjector(schedule={"also-bad": {0}})


def test_injector_schedule_fires_exact_invocations():
    inj = FaultInjector(seed=7, schedule={"infer-raise": {0, 2}})
    fired = [inj.maybe("infer-raise") is not None for _ in range(4)]
    assert fired == [True, False, True, False]
    assert inj.counts()["infer-raise"] == 2
    assert [f.index for f in inj.events] == [0, 2]
    with pytest.raises(FaultInjected):
        inj2 = FaultInjector(seed=7, schedule={"fold-raise": {0}})
        inj2.raise_if("fold-raise")


def test_injector_rate_stream_is_seed_deterministic():
    a = FaultInjector(seed=3, rates={"infer-raise": 0.3, "slow-batch": 0.3})
    b = FaultInjector(seed=3, rates={"infer-raise": 0.3, "slow-batch": 0.3})
    # interleave differently: per-point streams must not cross-talk
    seq_a = [a.maybe("infer-raise") is not None for _ in range(40)]
    _ = [a.maybe("slow-batch") for _ in range(11)]
    _ = [b.maybe("slow-batch") for _ in range(3)]
    seq_b = [b.maybe("infer-raise") is not None for _ in range(40)]
    assert seq_a == seq_b and any(seq_a)


def test_injector_corrupt_state_flips_sentinel():
    _, state = _small_net()
    assert _state_finite(state)
    assert not _state_finite(FaultInjector.corrupt_state(state))


def test_fault_dataclass_is_frozen():
    f = Fault(point="infer-raise", index=0)
    with pytest.raises(Exception):
        f.index = 1


# --------------------------------------------------- admission control ----

def test_overloaded_at_queue_bound():
    spec, state = _small_net()
    blk = _Blocker()
    svc = BCPNNService(state, spec, max_batch=4, max_queue=3,
                       fault_injector=blk).start()
    try:
        x = _x(spec)
        first = svc.submit(x)           # worker takes it and blocks
        assert blk.entered.wait(10.0)
        backlog = [svc.submit(x) for _ in range(3)]   # fills the bound
        with pytest.raises(Overloaded) as ei:
            svc.submit(x)
        assert "3/3" in str(ei.value)
        snap = svc.snapshot()
        assert snap["rejected"] == 1.0
        blk.release.set()
        for rid in [first] + backlog:   # everything admitted still serves
            svc.result(rid, timeout=30.0)
        assert svc.snapshot()["completed"] == 4.0
    finally:
        blk.release.set()
        svc.stop()


def test_deadline_expired_request_is_shed_at_dequeue():
    spec, state = _small_net()
    blk = _Blocker()
    svc = BCPNNService(state, spec, max_batch=4,
                       fault_injector=blk).start()
    try:
        x = _x(spec)
        first = svc.submit(x)           # occupies the worker
        assert blk.entered.wait(10.0)
        doomed = svc.submit(x, deadline_s=0.05)
        ok = svc.submit(x)              # no deadline: must still serve
        time.sleep(0.12)                # deadline passes while queued
        blk.release.set()
        svc.result(first, timeout=30.0)
        with pytest.raises(DeadlineExceeded) as ei:
            svc.result(doomed, timeout=30.0)
        assert f"request {doomed}" in str(ei.value)
        svc.result(ok, timeout=30.0)
        snap = svc.snapshot()
        assert snap["shed"] == 1.0
        assert snap["completed"] == 2.0
        # accounting closes: nothing silently dropped
        assert snap["submitted"] == snap["completed"] + snap["shed"]
    finally:
        blk.release.set()
        svc.stop()


def test_engine_default_deadline_applies_to_every_submit():
    spec, state = _small_net()
    blk = _Blocker()
    svc = BCPNNService(state, spec, max_batch=4, default_deadline_s=0.05,
                       fault_injector=blk).start()
    try:
        x = _x(spec)
        first = svc.submit(x)
        assert blk.entered.wait(10.0)
        doomed = svc.submit(x)          # inherits the engine default
        time.sleep(0.12)
        blk.release.set()
        svc.result(first, timeout=30.0)
        with pytest.raises(DeadlineExceeded):
            svc.result(doomed, timeout=30.0)
    finally:
        blk.release.set()
        svc.stop()


# ------------------------------------------------------------ bisection ----

def test_poison_bisection_isolates_exactly_the_bad_request():
    spec, state = _small_net()
    blk = _Blocker()
    svc = BCPNNService(state, spec, max_batch=8,
                       fault_injector=blk).start()
    try:
        xs = _x(spec, seed=5, n=6)
        first = svc.submit(_x(spec))    # occupies the worker
        assert blk.entered.wait(10.0)
        rids = [svc.submit(xs[i]) for i in range(6)]   # one future group
        blk.poison(rids[2])
        blk.release.set()
        svc.result(first, timeout=30.0)
        with pytest.raises(FaultInjected) as ei:
            svc.result(rids[2], timeout=30.0)
        assert str(rids[2]) in str(ei.value)
        # groupmates of the poison request still serve GENUINE results
        probs_direct, pred_direct = infer(state, spec, xs)
        for i, rid in enumerate(rids):
            if i == 2:
                continue
            res = svc.result(rid, timeout=30.0)
            assert res.pred == int(np.asarray(pred_direct)[i])
            np.testing.assert_allclose(res.probs,
                                       np.asarray(probs_direct)[i],
                                       atol=1e-6)
        snap = svc.snapshot()
        assert snap["failed"] == 1.0
        assert snap["bisects"] >= 1.0
        assert snap["crashes"] >= 1.0
        assert snap["completed"] == 6.0    # first + 5 groupmates
    finally:
        blk.release.set()
        svc.stop()


def test_transient_infer_raise_costs_a_retry_not_the_batch():
    spec, state = _small_net()
    # invocation 0 is the blocker-held single; invocation 1 hits the
    # 4-group, whose bisected halves (invocations 2, 3) then succeed —
    # a TRANSIENT group failure serves everyone after the retry
    blk = _Blocker(seed=0, schedule={"infer-raise": {1}})
    svc = BCPNNService(state, spec, max_batch=8,
                       fault_injector=blk).start()
    try:
        first = svc.submit(_x(spec))
        assert blk.entered.wait(10.0)
        rids = [svc.submit(_x(spec, seed=3 + i)) for i in range(4)]
        blk.release.set()
        svc.result(first, timeout=30.0)
        for rid in rids:
            assert svc.result(rid, timeout=30.0).pred >= 0
        snap = svc.snapshot()
        assert snap["failed"] == 0.0          # everyone served after retry
        assert snap["bisects"] >= 1.0
        assert snap["crashes"] >= 1.0
        assert snap["completed"] == 5.0
    finally:
        blk.release.set()
        svc.stop()


# ----------------------------------------------------------- quarantine ----

def test_quarantine_rolls_back_and_degrades_to_inference_only():
    spec, state = _small_net()
    inj = FaultInjector(seed=0, schedule={"nan-state": {1}})
    svc = BCPNNService(state, spec, max_batch=4, online_learning=True,
                       feedback_batch=2, feedback_eager=False,
                       fault_injector=inj).start()
    try:
        rng = np.random.default_rng(0)
        ni = spec.input_geom.N
        fb = lambda: svc.feedback(rng.random(ni).astype(np.float32),
                                  int(rng.integers(0, spec.n_classes)))
        fb(), fb()                         # fold 0: clean
        _wait(lambda: svc.snapshot()["learn_steps"] >= 1)
        good = jax.tree_util.tree_map(np.asarray, svc.model_state())
        fb(), fb()                         # fold 1: nan-injected
        _wait(lambda: svc.snapshot()["quarantined"] == 1.0)
        # (a) bitwise rollback to the last-good state
        after = jax.tree_util.tree_map(np.asarray, svc.model_state())
        for g, a in zip(jax.tree_util.tree_leaves(good),
                        jax.tree_util.tree_leaves(after)):
            np.testing.assert_array_equal(g, a)
        # (b) inference-only degradation: serving continues from the
        # rolled-back pack, feedback is refused typed
        x = _x(spec, seed=9)
        res = svc.classify(x, timeout=30.0)
        probs_d, pred_d = infer(svc.model_state(), spec, x[None, :])
        assert res.pred == int(np.asarray(pred_d)[0])
        np.testing.assert_allclose(res.probs, np.asarray(probs_d)[0],
                                   atol=1e-6)
        with pytest.raises(Quarantined):
            fb()
        snap = svc.snapshot()
        assert snap["quarantine_events"] == 1.0
        assert snap["feedback_dropped"] >= 2.0
        assert snap["learn_steps"] == 1.0   # the corrupted fold never landed
        # (c) revalidate() re-arms learning from the last-good snapshot
        svc.revalidate()
        assert svc.snapshot()["quarantined"] == 0.0
        fb(), fb()
        _wait(lambda: svc.snapshot()["learn_steps"] >= 2)
        assert _state_finite(svc.model_state())
    finally:
        svc.stop()


def test_fold_raise_is_survived_and_counted():
    spec, state = _small_net()
    inj = FaultInjector(seed=0, schedule={"fold-raise": {0}})
    svc = BCPNNService(state, spec, max_batch=4, online_learning=True,
                       feedback_batch=2, feedback_eager=False,
                       fault_injector=inj).start()
    try:
        rng = np.random.default_rng(0)
        ni = spec.input_geom.N
        for i in range(2):
            svc.feedback(rng.random(ni).astype(np.float32), i % 2)
        _wait(lambda: svc.snapshot()["feedback_dropped"] >= 2.0)
        snap = svc.snapshot()
        assert snap["crashes"] >= 1.0
        assert snap["learn_steps"] == 0.0
        # the worker is alive and still serves
        res = svc.classify(_x(spec), timeout=30.0)
        assert res.pred >= 0
        # the NEXT fold (injector invocation 1) lands cleanly
        for i in range(2):
            svc.feedback(rng.random(ni).astype(np.float32), i % 2)
        _wait(lambda: svc.snapshot()["learn_steps"] >= 1)
    finally:
        svc.stop()


# ------------------------------------------------------ worker death ----

@pytest.mark.filterwarnings(
    "ignore::pytest.PytestUnhandledThreadExceptionWarning")
def test_dead_worker_fails_futures_and_raises_everywhere():
    # the worker re-raises its killer after _die (so thread tooling sees
    # the real exception) — pytest reports that as an unhandled thread
    # exception, which is exactly the behavior under test
    spec, state = _small_net()
    blk = _Blocker()
    svc = BCPNNService(state, spec, max_batch=4,
                       fault_injector=blk).start()
    slot = svc._slot(None)

    def _boom(*a, **k):
        raise KeyboardInterrupt("injected terminal failure")

    x = _x(spec)
    first = svc.submit(x)               # worker blocks at slow-batch
    assert blk.entered.wait(10.0)
    pending = svc.submit(x)             # will be in flight at death
    for b in slot.infer_exe:            # next batch kills the worker
        slot.infer_exe[b] = _boom
    blk.release.set()
    # every pending future completes exceptionally — nothing hangs
    with pytest.raises(WorkerDied):
        svc.result(first, timeout=30.0)
    with pytest.raises(WorkerDied):
        svc.result(pending, timeout=30.0)
    # admission, restart and stop all surface the death typed
    with pytest.raises(WorkerDied):
        svc.submit(x)
    with pytest.raises(WorkerDied) as ei:
        svc.stop()
    assert "KeyboardInterrupt" in str(ei.value)
    with pytest.raises(WorkerDied):
        svc.start()


def _refuse(*args):
    raise ValueError("kernel refused by the compiler")


def test_compile_failure_raises_from_warmup():
    spec, state = _small_net()
    svc = BCPNNService(state, spec, max_batch=4)
    svc._slot(None).infer_fn = jax.jit(_refuse)
    with pytest.raises(CompileFailed, match="failed to compile"):
        svc.start()


def test_compile_failure_in_worker_kills_engine_instead_of_bisecting():
    """A program that cannot compile fails every request alike: the
    worker dies loudly rather than bisecting it into per-request
    failures and serving on."""
    spec, state = _small_net()
    svc = BCPNNService(state, spec, max_batch=4)
    svc._slot(None).infer_fn = jax.jit(_refuse)
    svc.start(warmup=False)
    rid = svc.submit(_x(spec))
    with pytest.raises(WorkerDied, match="CompileFailed"):
        svc.result(rid, timeout=30.0)
    with pytest.raises(WorkerDied, match="CompileFailed"):
        svc.submit(_x(spec))
    snap = svc.snapshot()
    assert snap["bisects"] == 0 and snap["failed"] == 0
    assert snap["crashes"] == 0
    with pytest.raises(WorkerDied, match="CompileFailed"):
        svc.stop()


def test_stop_timeout_raises_instead_of_hanging():
    spec, state = _small_net()
    blk = _Blocker()
    svc = BCPNNService(state, spec, max_batch=4,
                       fault_injector=blk).start()
    svc.submit(_x(spec))
    assert blk.entered.wait(10.0)       # worker wedged mid-batch
    t0 = time.perf_counter()
    with pytest.raises(RuntimeError, match="failed to drain"):
        svc.stop(timeout_s=0.3)
    assert time.perf_counter() - t0 < 10.0
    blk.release.set()                   # let the daemon thread finish


# ------------------------------------------------------------ stragglers --

def test_injected_slow_batch_surfaces_as_attributed_straggler():
    """The injected delay sleeps inside the group's timed span, so the
    batch that drew it is an event of at least that delay, attributed
    to its model, whatever other events the machine's load adds."""
    spec, state = _small_net()
    inj = FaultInjector(seed=0, schedule={"slow-batch": {10}},
                        slow_ms=150.0)
    svc = BCPNNService(state, spec, max_batch=4,
                       fault_injector=inj).start()
    try:
        x = _x(spec)
        for _ in range(14):             # serial singles: one batch each
            svc.classify(x, timeout=30.0)
        snap = svc.snapshot()
        assert snap["straggler_events"] >= 1.0
        (fired,) = [f for f in inj.events if f.point == "slow-batch"]
        # one slow-batch draw per microbatch; batch sequence numbers
        # start at 1
        seq = fired.index + 1
        ev = [e for e in svc.step_timer.events if e["step"] == seq]
        assert len(ev) == 1
        assert ev[0]["tag"] == "default"
        assert ev[0]["time"] >= fired.delay_s == 0.15
    finally:
        svc.stop()


# ------------------------------------------------------------ chaos soak --

@pytest.mark.slow
def test_chaos_soak_zero_lost_requests():
    """Poisson load + all four fault classes from a seeded schedule:
    every submitted id resolves (result or typed error, never a hang),
    the worker survives, a NaN-injected fold leaves the served state at
    its last-good value, and tail latency stays bounded."""
    spec, state = _small_net(side=6)
    inj = FaultInjector(seed=42, slow_ms=30.0,
                        rates={"infer-raise": 0.05, "fold-raise": 0.10,
                               "nan-state": 0.05, "slow-batch": 0.05})
    svc = BCPNNService(state, spec, max_batch=8, online_learning=True,
                       feedback_batch=8, max_queue=128,
                       fault_injector=inj).start()
    n = 400
    xs = _x(spec, seed=1, n=64)
    ys = np.asarray(jax.random.randint(jax.random.PRNGKey(2), (64,), 0,
                                       spec.n_classes))
    rep = run_open_loop(svc, xs, ys, n_requests=n, rate_hz=400.0,
                        seed=11, feedback_frac=0.3, timeout_s=60.0,
                        deadline_s=10.0)
    assert not svc._dead.is_set(), "worker died during the soak"
    svc.stop()
    # zero lost/hung ids: every submit resolved as a result or typed error
    assert len(rep.results) + len(rep.errors) + rep.n_rejected == n
    assert "TimeoutError" not in rep.error_counts(), rep.error_counts()
    snap = svc.snapshot()
    assert snap["submitted"] == snap["completed"] + snap["shed"] + \
        snap["failed"], f"request accounting leaks: {snap}"
    # faults actually fired (the soak exercised every class)
    counts = inj.counts()
    assert counts["infer-raise"] > 0 and counts["slow-batch"] > 0
    assert counts["fold-raise"] > 0 or counts["nan-state"] > 0
    # the served state never went non-finite (quarantine rolled back any
    # poisoned fold), so post-soak inference is at last-good quality
    assert _state_finite(svc.model_state())
    probs, _ = infer(svc.model_state(), spec, xs[:8])
    assert np.isfinite(np.asarray(probs)).all()
    # bounded tail: generous CPU bound, catches only collapse
    assert snap["p99_ms"] < 30_000.0


def _wait(cond, timeout_s: float = 30.0) -> None:
    deadline = time.perf_counter() + timeout_s
    while not cond():
        assert time.perf_counter() < deadline, "condition never held"
        time.sleep(0.002)
