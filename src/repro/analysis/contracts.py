"""Runtime jaxpr/contract checks — the sanitizer layer of repro.analysis.

Where the AST rules (rules.py) catch violations at the source level,
these checks run tiny real programs and inspect what jax actually built:

* ``recompile-sentinel`` — serves a smoke workload through
  ``BCPNNService`` and asserts every per-(model, bucket) jit compiled
  EXACTLY once during warmup and never again (``_cache_size()`` on the
  slot jits): any cache-key churn — a spec that stopped being hashable,
  a shape leak past the bucket padding — shows up as a growing count.
* ``dp-seams`` — canonicalizes the ``optimization_barrier`` equations of
  the single-device step jaxpr and the shard_map data-parallel step
  jaxpr and asserts the PR 4 seam set is present in both (the
  precondition for the bit-exact DP equivalence; see
  core/traces.py, core/network.py, distributed/data_parallel.py).
* ``masked-seams`` — same discipline for the masked tail-batch learn
  (DESIGN.md §12): the ``masked_inputs`` pin (x, y, valid) and the
  masked-product pins must appear in the single-device masked step AND
  in the shard_map masked epoch program (where the product pin carries
  the column-sharded ``yv_l`` as well) — the precondition for padded
  fits staying bit-exact across meshes.
* ``donation-guard`` — replays the PR 6 bug: a ``cached_table`` result
  whose buffer is consumed by a donating jit must be REBUILT on the next
  call, never returned dead (core/compact.py's ``_deleted`` guard).
* ``pallas-plans`` — the kernel pad-plan/shape/accumulator audit
  (plans.py).
* ``router-exactly-once`` — kills an engine under a live
  ``BCPNNRouter`` and asserts every router-issued id resolves EXACTLY
  once (result or typed error — never lost, never twice), accounting
  closes, and the reroute budget bounds admission attempts
  (DESIGN.md §11).
* ``replica-merge`` — the disjoint-support merge of agreeing replica
  states is bit-identical to each replica on a REAL folded model state,
  and a diverged replica set cannot merge clean (serve/reconcile.py).

Every check returns a list of problem strings; empty means the contract
holds.  ``run_contracts`` drives any subset by name.
"""
from __future__ import annotations

import os
from typing import Any, Callable, Dict, Iterator, List, Optional, Sequence

from .plans import check_pallas_plans


def ensure_host_devices(n: int = 2) -> None:
    """Give the process an ``n``-device CPU mesh if jax is not yet
    initialized (the DP-seam check needs >= 2 devices; tests get this
    from conftest.py, the CLI from here)."""
    import sys
    if "jax" in sys.modules:
        return  # too late to change platform flags — use what exists
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}").strip()


# ------------------------------------------------- recompile sentinel ----

def check_recompile_sentinel() -> List[str]:
    """Per-(model, bucket) compile counts stay fixed across a serving
    smoke: warmup compiles every bucket (plus one learn shape), and no
    request, feedback fold, or drain may add an entry."""
    import jax.numpy as jnp  # noqa: F401 — ensures jax is importable first
    import numpy as np
    from ..core.network import init_network, make_network_spec
    from ..serve.engine import BCPNNService
    import jax

    buckets = (1, 2, 4)
    spec = make_network_spec((2, 2), [(1, 4)], 2, backend="jnp")
    state = init_network(spec, jax.random.PRNGKey(0))
    svc = BCPNNService(state, spec, buckets=buckets, max_wait_ms=0.5,
                      online_learning=True, feedback_batch=4,
                      adaptive_buckets=True)
    slot = svc._slot(None)

    problems: List[str] = []
    svc.start(warmup=True)
    try:
        n_infer0 = len(slot.infer_exe)
        n_learn0 = int(slot.learn_exe is not None)
        if n_infer0 != len(buckets):
            problems.append(
                f"warmup compiled {n_infer0} infer programs for "
                f"{len(buckets)} buckets — bucket set and compile set "
                f"disagree")
        if n_learn0 != 1:
            problems.append(f"warmup compiled {n_learn0} learn programs, "
                            f"expected exactly 1 (the feedback_batch shape)")
        rng = np.random.default_rng(0)
        ni = spec.input_geom.N
        # mixed singles/bursts so every bucket actually serves traffic
        ids = [svc.submit(rng.random(ni).astype(np.float32))
               for _ in range(17)]
        for rid in ids:
            svc.result(rid, timeout=30.0)
        for i in range(9):
            svc.feedback(rng.random(ni).astype(np.float32), i % 2)
    finally:
        svc.stop()
    n_infer1 = len(slot.infer_exe)
    n_learn1 = int(slot.learn_exe is not None)
    if n_infer1 != n_infer0:
        problems.append(
            f"infer program compiled during serving: {n_infer0} -> "
            f"{n_infer1} programs — a request escaped its shape bucket")
    if n_learn1 != n_learn0:
        problems.append(
            f"learn program compiled during serving: {n_learn0} -> "
            f"{n_learn1} programs — a feedback fold escaped the "
            f"fixed feedback_batch shape")
    return problems


# --------------------------------------------------------- DP seams ----

def _barrier_signatures(closed_jaxpr: Any) -> List[tuple]:
    """Every ``optimization_barrier`` equation in a jaxpr (recursing
    through call/scan/cond/shard_map sub-jaxprs), canonicalized as the
    sorted tuple of its outputs' "dtype[shape]" strings — a seam identity
    that survives variable renaming and eqn reordering."""
    out: List[tuple] = []
    seen = set()

    def walk(jx: Any) -> None:
        if id(jx) in seen:
            return
        seen.add(id(jx))
        for eqn in jx.eqns:
            if eqn.primitive.name == "optimization_barrier":
                out.append(tuple(sorted(
                    f"{v.aval.dtype}[{','.join(str(d) for d in v.aval.shape)}]"
                    for v in eqn.outvars)))
            for val in eqn.params.values():
                for sub in _subjaxprs(val):
                    walk(sub)
    walk(closed_jaxpr.jaxpr)
    return out


def _subjaxprs(val: Any) -> Iterator[Any]:
    if hasattr(val, "eqns"):            # open Jaxpr
        yield val
    elif hasattr(val, "jaxpr"):         # ClosedJaxpr
        yield val.jaxpr
    elif isinstance(val, (list, tuple)):
        for v in val:
            yield from _subjaxprs(v)


def _require(problems: List[str], sigs: List[tuple], want: tuple,
             count: int, program: str, seam: str) -> None:
    have = sigs.count(want)
    if have < count:
        problems.append(
            f"{program}: expected >= {count} optimization_barrier seam(s) "
            f"{seam} with outputs {list(want)}, found {have} — the "
            f"bit-exactness pin was removed or reshaped")


def check_dp_seams() -> List[str]:
    """The PR 4 barrier seams are present in BOTH the single-device
    unsupervised step and its shard_map data-parallel equivalent."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh
    from ..core.network import (
        init_network, make_network_spec, unsupervised_layer_step,
    )
    from ..distributed.data_parallel import (
        make_data_parallel_unsupervised_step,
    )

    b, n_shards = 8, 2
    spec = make_network_spec((4, 3), [(4, 5)], 3, backend="jnp")
    ni, nj = spec.input_geom.N, spec.projs[0].post.N          # 12, 20
    state = init_network(spec, jax.random.PRNGKey(0))
    x = jnp.zeros((b, ni), jnp.float32)

    problems: List[str] = []
    single = jax.make_jaxpr(
        lambda st, xx: unsupervised_layer_step(st, spec, xx, 0))(state, x)
    sigs_1 = _barrier_signatures(single)

    def shape(*dims: int) -> str:
        return f"float32[{','.join(str(d) for d in dims)}]"

    noise = (shape(b, nj),)
    learn_xy = tuple(sorted((shape(b, ni), shape(b, nj))))
    stats = tuple(sorted((shape(ni), shape(nj), shape(ni, nj))))
    _require(problems, sigs_1, noise, 2, "single-device step",
             "(noise draw + scaled-noise pins, core/network._noisy_rates)")
    _require(problems, sigs_1, learn_xy, 1, "single-device step",
             "(learn-input pin, core/traces.update_traces)")
    _require(problems, sigs_1, stats, 1, "single-device step",
             "(batch-stats pin, core/traces.update_traces_from_stats)")

    if len(jax.devices()) < n_shards:
        problems.append(
            f"dp step: needs >= {n_shards} devices, found "
            f"{len(jax.devices())} — run with XLA_FLAGS="
            f"--xla_force_host_platform_device_count={n_shards} (the CLI "
            f"sets this automatically when jax is not yet imported)")
        return problems

    mesh = Mesh(jax.devices()[:n_shards], ("data",))
    dp_step = make_data_parallel_unsupervised_step(spec, mesh)
    sigs_n = _barrier_signatures(jax.make_jaxpr(dp_step)(state, x))
    nj_l = nj // n_shards
    _require(problems, sigs_n, noise, 1, "data-parallel step",
             "(full-batch noise pin mirroring _noisy_rates)")
    _require(problems, sigs_n, (shape(b, nj_l),), 1, "data-parallel step",
             "(column-sliced scaled-noise pin)")
    _require(problems, sigs_n, learn_xy, 1, "data-parallel step",
             "(learn-input pin, distributed._learn_sharded)")
    _require(problems, sigs_n,
             tuple(sorted((shape(b, ni), shape(b, nj_l)))), 1,
             "data-parallel step",
             "(trace all-reduce pin, distributed._co_allreduce_dense)")
    _require(problems, sigs_n, stats, 1, "data-parallel step",
             "(batch-stats pin — the all-reduced stats fold)")
    return problems


def check_masked_seams() -> List[str]:
    """The masked tail-learning barrier seams (PR 10) are present in both
    the single-device masked step and the shard_map masked epoch."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh
    from ..core.network import (
        init_network, make_network_spec, train_projection_step,
    )
    from ..distributed.data_parallel import (
        make_data_parallel_projection_epoch,
    )

    b, n_shards, nb = 8, 2, 2
    spec = make_network_spec((4, 3), [(4, 5)], 3, backend="jnp")
    ni, nj = spec.input_geom.N, spec.projs[0].post.N          # 12, 20
    state = init_network(spec, jax.random.PRNGKey(0))
    x = jnp.zeros((b, ni), jnp.float32)
    v = jnp.zeros((b,), jnp.float32)

    def shape(*dims: int) -> str:
        return f"float32[{','.join(str(d) for d in dims)}]"

    masked_in = tuple(sorted((shape(b, ni), shape(b, nj), shape(b))))
    problems: List[str] = []
    single = jax.make_jaxpr(
        lambda st, xx, vv: train_projection_step(st, spec, xx, 0, valid=vv)
    )(state, x, v)
    sigs_1 = _barrier_signatures(single)
    _require(problems, sigs_1, masked_in, 1, "single-device masked step",
             "(masked-input pin, core/bcpnn_layer.masked_inputs)")
    _require(problems, sigs_1,
             tuple(sorted((shape(b, ni), shape(b, nj)))), 1,
             "single-device masked step",
             "(masked-product pin, core/bcpnn_layer.learn_masked)")

    if len(jax.devices()) < n_shards:
        problems.append(
            f"masked dp epoch: needs >= {n_shards} devices, found "
            f"{len(jax.devices())} — run with XLA_FLAGS="
            f"--xla_force_host_platform_device_count={n_shards}")
        return problems

    mesh = Mesh(jax.devices()[:n_shards], ("data",))
    dp_epoch = make_data_parallel_projection_epoch(spec, mesh, masked=True)
    hs = jnp.zeros((nb, b, ni), jnp.float32)
    valid = jnp.zeros((nb, b), jnp.float32)
    sigs_n = _barrier_signatures(jax.make_jaxpr(dp_epoch)(state, hs, valid))
    nj_l = nj // n_shards
    _require(problems, sigs_n, masked_in, 1, "data-parallel masked epoch",
             "(masked-input pin mirroring core/bcpnn_layer.masked_inputs)")
    _require(problems, sigs_n,
             tuple(sorted((shape(b, ni), shape(b, nj), shape(b, nj_l)))), 1,
             "data-parallel masked epoch",
             "(masked sharded-product pin, distributed._learn_sharded)")
    return problems


# ---------------------------------------------------- donation guard ----

def check_donation_guard() -> List[str]:
    """The PR 6 regression, as a live check: consume a memoized index
    table's buffer the way a ``donate_argnums`` jit does and assert
    ``cached_table`` rebuilds instead of returning the dead array."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from ..core.compact import build_table, cached_table

    nact = 2
    mask = jnp.asarray(np.array([[1, 0], [1, 1], [0, 1], [0, 0]],
                                np.float32))  # (Hi=4, Hj=2), exactly-nact
    expected = np.asarray(build_table(mask, nact))

    problems: List[str] = []
    t1 = cached_table(mask, nact)
    # what a donating jit (Trainer's train steps donate the state, and
    # the compact state carries its table as a leaf) does to the buffer:
    consume = jax.jit(lambda t: t + 1, donate_argnums=0)
    consume(t1)
    # repro: suppress[donated-reuse] — deliberate use-after-donate probe
    if not t1.is_deleted():
        problems.append("donation probe failed to consume the table "
                        "buffer — the check cannot exercise the guard")
        return problems
    t2 = cached_table(mask, nact)
    if t2.is_deleted():
        problems.append(
            "cached_table returned a DELETED buffer after its memoized "
            "table was consumed by a donating jit — the core/compact "
            "_deleted() guard is broken (PR 6 bug class)")
        return problems
    if not np.array_equal(np.asarray(t2), expected):
        problems.append("cached_table rebuilt a WRONG table after "
                        "donation — guard rebuilt from stale content")
    # content-level memo must also refuse the dead buffer: a different
    # mask object with identical content hits the content cache.
    mask_copy = jnp.asarray(np.asarray(mask))
    t3 = cached_table(mask_copy, nact)
    if t3.is_deleted() or not np.array_equal(np.asarray(t3), expected):
        problems.append("content-level cached_table memo served a dead or "
                        "wrong table after donation")
    return problems


# --------------------------------------------- quarantine rollback ----

def check_quarantine_rollback() -> List[str]:
    """Live check of the serving quarantine contract (DESIGN.md §10): a
    fold whose output fails the non-finite sentinel must (a) leave the
    slot's state BIT-IDENTICAL to the last-good pre-fold state, (b) flip
    the slot to inference-only (``Quarantined`` on feedback, surfaced in
    ``snapshot()``), and (c) re-arm through ``revalidate()``."""
    import time
    import jax
    import numpy as np
    from ..core.network import init_network, make_network_spec
    from ..serve.engine import BCPNNService
    from ..serve.errors import Quarantined
    from ..serve.faultinject import FaultInjector

    spec = make_network_spec((2, 2), [(1, 4)], 2, backend="jnp")
    state = init_network(spec, jax.random.PRNGKey(0))
    # fold invocation 0 stays clean (establishes a non-trivial last-good
    # snapshot), invocation 1 is corrupted; feedback_eager=False makes
    # the invocation -> batch-composition mapping deterministic (folds
    # fire only on FULL feedback batches, never on idle polls)
    inj = FaultInjector(seed=0, schedule={"nan-state": {1}})
    svc = BCPNNService(state, spec, buckets=(1, 2), max_wait_ms=0.5,
                       online_learning=True, feedback_batch=2,
                       feedback_eager=False, fault_injector=inj)
    problems: List[str] = []
    svc.start(warmup=True)
    try:
        rng = np.random.default_rng(0)
        ni = spec.input_geom.N
        deadline = time.perf_counter() + 30.0
        for i in range(2):
            svc.feedback(rng.random(ni).astype(np.float32), i % 2)
        while svc.snapshot()["learn_steps"] < 1:
            if time.perf_counter() > deadline:
                problems.append("clean fold never landed")
                return problems
            time.sleep(0.002)
        good = jax.tree_util.tree_map(np.asarray, svc._slot(None).state)
        # the corrupted fold: must quarantine, not commit
        for i in range(2):
            svc.feedback(rng.random(ni).astype(np.float32), i % 2)
        while not svc._slot(None).quarantined:
            if time.perf_counter() > deadline:
                problems.append("nan-injected fold never quarantined")
                return problems
            time.sleep(0.002)
        after = jax.tree_util.tree_map(np.asarray, svc._slot(None).state)
        flat_g = jax.tree_util.tree_leaves(good)
        flat_a = jax.tree_util.tree_leaves(after)
        for g, a in zip(flat_g, flat_a):
            if g.dtype != a.dtype or not np.array_equal(g, a):
                problems.append(
                    "quarantine rollback is not bit-identical to the "
                    "last-good state — a corrupted fold leaked into the "
                    "served state")
                break
        if svc.snapshot().get("quarantined") != 1.0:
            problems.append("quarantine not surfaced in snapshot()")
        try:
            svc.feedback(rng.random(ni).astype(np.float32), 0)
            problems.append("quarantined slot accepted feedback "
                            "(expected Quarantined)")
        except Quarantined:
            pass
        svc.revalidate()
        if svc._slot(None).quarantined:
            problems.append("revalidate() failed to re-arm a finite "
                            "rolled-back slot")
    finally:
        svc.stop()
    return problems


# ------------------------------------------- router exactly-once ----

def check_router_exactly_once() -> List[str]:
    """Live check of the router failure ladder (DESIGN.md §11): with an
    engine killed under load, every router-issued id resolves EXACTLY
    once — a result or one typed error, never a hang, never a second
    resolution — router accounting closes, and a submit against a tier
    with no healthy replica rejects within the reroute budget."""
    import jax
    import numpy as np
    from ..core.network import init_network, make_network_spec
    from ..serve import BCPNNRouter, NoHealthyReplica, ServeError

    spec = make_network_spec((2, 2), [(1, 4)], 2, backend="jnp")
    state = init_network(spec, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    ni = spec.input_geom.N
    problems: List[str] = []

    r = BCPNNRouter.local(2, max_batch=4, max_queue=256)
    r.add_model("m", state, spec, replicas=2)
    r.start()
    try:
        ids = [r.submit(rng.random(ni).astype(np.float32), model="m")
               for _ in range(16)]
        victim = r.placement("m")["replicas"][0]
        r._engines[victim].kill("contract-probe")
        resolved = 0
        for rid in ids:
            try:
                r.result(rid, timeout=30.0)
                resolved += 1
            except ServeError:
                resolved += 1  # typed failure IS a resolution
            except TimeoutError:
                problems.append(f"router id {rid} hung past its engine's "
                                f"death — an in-flight future was lost")
        if resolved != len(ids) and not problems:
            problems.append(f"{len(ids) - resolved} of {len(ids)} router "
                            f"ids vanished without a typed resolution")
        try:
            r.result(ids[0], timeout=1.0)
            problems.append("an already-resolved router id resolved a "
                            "SECOND time — exactly-once is broken")
        except KeyError:
            pass
        snap = r.metrics.snapshot()
        if snap["submitted"] != snap["completed"] + snap["failed"]:
            problems.append(
                f"router accounting does not close: submitted="
                f"{snap['submitted']} != completed={snap['completed']} "
                f"+ failed={snap['failed']}")
    finally:
        r.stop()

    # reroute budget: a tier with no healthy replica rejects typed,
    # within 1 + max_reroutes admission attempts
    r2 = BCPNNRouter.local(1, max_reroutes=2)
    r2.add_model("m", state, spec)
    r2.start()
    try:
        r2._engines["engine0"].kill("contract-probe")
        import time as _time
        deadline = _time.perf_counter() + 30.0
        while r2._engines["engine0"].alive():
            if _time.perf_counter() > deadline:
                problems.append("killed engine never died")
                return problems
            _time.sleep(0.002)
        try:
            r2.submit(rng.random(ni).astype(np.float32), model="m")
            problems.append("submit admitted a request on a tier with no "
                            "healthy replica")
        except NoHealthyReplica as e:
            if e.attempts > 1 + r2.max_reroutes:
                problems.append(f"reroute budget exceeded: {e.attempts} "
                                f"attempts > 1 + {r2.max_reroutes}")
        if r2.metrics.snapshot()["rejected"] != 1.0:
            problems.append("NoHealthyReplica rejection not counted")
    finally:
        r2.stop()
    return problems


# ------------------------------------------------- replica merge ----

def check_replica_merge() -> List[str]:
    """The reconciliation merge's bitwise contract on a REAL folded
    model state: merging K agreeing replicas is bit-identical to each
    replica (the disjoint-support reassembly is lossless for every leaf
    shape/dtype in the state tree), and a diverged replica set cannot
    merge clean."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from ..core.network import (
        init_network, make_network_spec, supervised_readout_step,
    )
    from ..serve.reconcile import (
        merge_replica_states, state_divergence, states_bitwise_equal,
    )

    spec = make_network_spec((2, 2), [(1, 4)], 2, backend="jnp")
    state0 = init_network(spec, jax.random.PRNGKey(1))
    fold = jax.jit(lambda st, x, y: supervised_readout_step(st, spec, x, y))
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.random((4, spec.input_geom.N)).astype(np.float32))
    y = jnp.asarray(rng.integers(0, 2, size=4).astype(np.int32))
    folded = fold(state0, x, y)

    problems: List[str] = []
    for k in (1, 2, 3):
        merged = merge_replica_states([folded] * k)
        if not states_bitwise_equal(merged, folded):
            div = "; ".join(state_divergence(merged, folded)[:3])
            problems.append(f"merge of {k} agreeing replicas is not "
                            f"bit-identical: {div}")
    mixed = merge_replica_states([folded, state0])
    if states_bitwise_equal(mixed, folded) and \
            states_bitwise_equal(mixed, state0):
        problems.append("merge failed to expose a diverged replica set — "
                        "reconcile() could report drifted replicas as "
                        "consistent")
    return problems


# -------------------------------------------------------------- driver ----

CONTRACTS: Dict[str, Callable[[], List[str]]] = {
    "donation-guard": check_donation_guard,
    "recompile-sentinel": check_recompile_sentinel,
    "dp-seams": check_dp_seams,
    "masked-seams": check_masked_seams,
    "pallas-plans": check_pallas_plans,
    "quarantine-rollback": check_quarantine_rollback,
    "router-exactly-once": check_router_exactly_once,
    "replica-merge": check_replica_merge,
}


def run_contracts(names: Optional[Sequence[str]] = None
                  ) -> Dict[str, List[str]]:
    """Run the named contract checks (all by default) -> {name: problems}."""
    picked = list(names) if names else sorted(CONTRACTS)
    unknown = [n for n in picked if n not in CONTRACTS]
    if unknown:
        raise ValueError(f"unknown contract checks {unknown}; known: "
                         f"{sorted(CONTRACTS)}")
    return {name: CONTRACTS[name]() for name in picked}
