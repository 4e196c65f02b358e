"""Bring-up smoke of the BCPNN train-and-serve path on a TPU.

    python chip_smoke.py              # one chip: Table-1 Model 1 train + serve
    python chip_smoke.py --chips 4    # four chips: Model 3 data-parallel fit

One chip (the default), every projection on ``backend="pallas"``:

* train -- ``Trainer.fit`` of Table-1 Model 1 (784x2 -> 32x128 -> 10)
  and its struct variant on the compact layout (nact 128), a few whole
  batches of the seeded MNIST surrogate, each compared with the same fit
  on the jnp reference;
* serve -- both trained models behind one ``BCPNNService.multi``, in
  fp32, bf16 and int8: a few dozen requests compared with ``infer`` on
  the jnp reference, then one feedback fold per model compared with the
  reference fold, and the engine's counters checked (nothing failed,
  crashed, bisected or dropped);
* the Pallas kernels must be compiled, not interpreted: ``tpu_custom_call``
  in the compiled train step and in every compiled serving program.

On one chip all f32 matmuls run at full precision
(``jax_default_matmul_precision="highest"``) on both sides of every
comparison: the TPU's default rounds f32 operands to bf16, and the
differences would then measure that rounding rather than the kernels.

Four chips (``--chips 4``): Table-1 Model 3 dense (8192 -> 32x128 -> 2)
trained column-sharded over a four-device ``elastic_mesh``, compared with
the single-device fit.  Both are jnp programs (the data-parallel steps run
the reference compute path) at the default matmul precision.

Findings go to standard output; the last line is one JSON object naming
the device.  The script exits non-zero, without that line, when JAX finds
no TPU or any check fails.  It is one process and starts no other.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))

# Bounds for the one-chip comparisons.  Both sides compute in f32 at full
# matmul precision; what differs is accumulation order (the kernels' K
# blocking, the butterfly softmax sums) and the TPU's exp/log.  Rates are
# softmaxes of 1568-term sums of log-odds, so that rounding moves a rate
# by up to ~1e-3 relative, and the log-domain weights w = log(p_ij /
# (p_i p_j)) of tiny probabilities by as much: weights are printed, and
# checked through what they compute -- the class probabilities.  A wrong
# kernel moves probabilities by O(0.1).
ATOL = {
    "traces": 1e-4,    # probabilities p_i, p_j, p_ij of the learned state
    "probs": 1e-3,     # class probabilities, served or inferred
}
N_TRAIN = 512          # four whole batches: the Pallas learn kernels run
BATCH = 128
BUCKETS = (8, 32)
N_REQUESTS = 48        # per serving dtype, alternating the two models
FEEDBACK_BATCH = 32    # one full fold per model


class Smoke:
    """Collects failures so one run reports every check."""

    def __init__(self):
        self.failures = []

    def log(self, msg: str) -> None:
        print(f"[smoke] {msg}", flush=True)

    def check(self, ok: bool, what: str) -> None:
        if not ok:
            self.failures.append(what)
            self.log(f"FAIL {what}")

    def close(self, what: str, got, want, atol: float) -> None:
        import numpy as np
        d = float(np.max(np.abs(np.asarray(got, np.float64)
                                - np.asarray(want, np.float64))))
        self.log(f"{what}: max |diff| {d:.3e} (atol {atol:g})")
        self.check(d <= atol, f"{what}: max |diff| {d:.3e} > {atol:g}")

    def phase(self, name: str, fn, *args):
        t0 = time.perf_counter()
        try:
            out = fn(*args)
        except Exception:
            traceback.print_exc()
            self.check(False, f"phase {name} raised")
            return None
        self.log(f"phase {name} done in {time.perf_counter() - t0:.1f}s "
                 f"(host clock, compiles included)")
        return out


def _device():
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def _kernels_in(smoke: Smoke, what: str, compiled) -> None:
    n = compiled.as_text().count("tpu_custom_call")
    smoke.log(f"{what}: {n} tpu_custom_call")
    smoke.check(n > 0, f"{what}: no Mosaic kernel in the compiled program")


def _compare_states(smoke: Smoke, what: str, got, want, cfg, x) -> None:
    """Two learned states: traces within ``ATOL["traces"]``, integer
    leaves (masks, tables, counters, keys) equal, and the class
    probabilities each gives on ``x`` (jnp reference forward) within
    ``ATOL["probs"]``."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import infer
    from repro.core.network import as_spec

    diffs = {"traces": 0.0, "weights": 0.0}
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree_util.tree_leaves(want)):
        name = jax.tree_util.keystr(path)
        a, b = np.asarray(a), np.asarray(b)
        if not np.issubdtype(a.dtype, np.floating) or "mask" in name:
            smoke.check(np.array_equal(a, b), f"{what} {name} differs")
            continue
        group = "traces" if ".traces." in name else "weights"
        diffs[group] = max(diffs[group], float(
            np.max(np.abs(a.astype(np.float64) - b))))
    smoke.close(f"{what} traces", diffs["traces"], 0.0, ATOL["traces"])
    smoke.log(f"{what} weights (log domain): max |diff| "
              f"{diffs['weights']:.3e}")
    spec = as_spec(cfg).with_backend("jnp")
    smoke.close(f"{what} class probs", infer(got, spec, jnp.asarray(x))[0],
                infer(want, spec, jnp.asarray(x))[0], ATOL["probs"])


# ------------------------------------------------------------ one chip ----

def train_phase(smoke: Smoke, data):
    import jax.numpy as jnp
    from repro.configs.bcpnn_models import MODEL1_MNIST, MODEL1_MNIST_STRUCT
    from repro.core import Trainer
    from repro.core.trainer import _train_projection_epoch

    x, y = data["x_train"], data["y_train"]
    models = {
        "model1": dataclasses.replace(MODEL1_MNIST, backend="pallas"),
        "model1-struct": dataclasses.replace(
            MODEL1_MNIST_STRUCT, backend="pallas", patchy_traces=True,
            compact=True),
    }
    trained = {}
    for name, cfg in models.items():
        tr = Trainer(cfg, seed=0)
        # the unsupervised epoch program Trainer.fit runs on whole batches
        xs = jnp.asarray(x.reshape(-1, BATCH, x.shape[1]))
        _kernels_in(smoke, f"{name} train step",
                    _train_projection_epoch.lower(tr.state, tr.spec, xs, 0)
                    .compile())
        t0 = time.perf_counter()
        tr.fit(x, y, epochs=1, batch=BATCH)
        smoke.log(f"{name} pallas fit: {len(x)} samples, "
                  f"{time.perf_counter() - t0:.1f}s incl. compile")
        ref = Trainer(dataclasses.replace(cfg, backend="jnp"), seed=0)
        ref.fit(x, y, epochs=1, batch=BATCH)
        _compare_states(smoke, f"{name} trained state vs jnp", tr.state,
                        ref.state, cfg, data["x_test"])
        trained[name] = (tr.state, cfg)
    return trained


def serve_phase(smoke: Smoke, trained, data, dtype: str) -> None:
    import jax.numpy as jnp
    import numpy as np
    from repro.core import infer, supervised_readout_step
    from repro.core.network import as_spec
    from repro.serve import BCPNNService, cycle_batch

    names = list(trained)
    svc = BCPNNService.multi(
        trained, buckets=BUCKETS, max_batch=BUCKETS[-1],
        online_learning=True, feedback_batch=FEEDBACK_BATCH,
        feedback_eager=False, infer_dtype=dtype)
    svc.start()  # compiles every (model, bucket) and the folds
    for name in names:
        slot = svc._slot(name)
        for b in BUCKETS:
            _kernels_in(smoke, f"{dtype} {name} infer bucket {b}",
                        slot.infer_program(b))
    xq = data["x_test"][:N_REQUESTS]
    sent = [(names[i % len(names)], i) for i in range(N_REQUESTS)]
    rids = [svc.submit(xq[i], model=m) for m, i in sent]
    results = [svc.result(r, timeout=300.0) for r in rids]
    for name in names:
        state, cfg = trained[name]
        ref_spec = as_spec(cfg).with_backend("jnp").with_infer_dtype(dtype)
        idx = [i for m, i in sent if m == name]
        got = np.stack([r.probs for r, (m, _) in zip(results, sent)
                        if m == name])
        pred = np.array([r.pred for r, (m, _) in zip(results, sent)
                         if m == name])
        want, want_pred = infer(state, ref_spec, jnp.asarray(xq[idx]))
        want, want_pred = np.asarray(want), np.asarray(want_pred)
        smoke.close(f"{dtype} {name} served probs vs jnp", got, want,
                    ATOL["probs"])
        top2 = np.sort(want, axis=1)[:, -2:]
        decided = (top2[:, 1] - top2[:, 0]) > 2 * ATOL["probs"]
        bad = int(np.sum((pred != want_pred) & decided))
        smoke.log(f"{dtype} {name} served preds: {len(pred)} requests, "
                  f"{bad} disagree with jnp")
        smoke.check(bad == 0, f"{dtype} {name}: {bad} preds disagree")
    fb = [(data["x_fb"][i], int(data["y_fb"][i]))
          for i in range(FEEDBACK_BATCH)]
    for name in names:
        for xi, yi in fb:
            svc.feedback(xi, yi, model=name)
    svc.stop()  # drains: exactly one full fold per model
    snap = svc.snapshot()
    counts = {k: snap[k] for k in ("submitted", "completed", "crashes",
                                   "failed", "bisects", "feedback_dropped",
                                   "learn_steps")}
    smoke.log(f"{dtype} serving counters: {counts}")
    smoke.check(snap["completed"] == snap["submitted"] == N_REQUESTS,
                f"{dtype}: completed != submitted")
    for k in ("crashes", "failed", "bisects", "feedback_dropped"):
        smoke.check(snap[k] == 0, f"{dtype}: {k} = {snap[k]:g}")
    smoke.check(snap["learn_steps"] >= 1, f"{dtype}: no feedback fold")
    fx, fy = cycle_batch(fb, FEEDBACK_BATCH)
    for name in names:
        state, cfg = trained[name]
        ref_spec = as_spec(cfg).with_backend("jnp")
        want = supervised_readout_step(state, ref_spec, jnp.asarray(fx),
                                       jnp.asarray(fy))
        _compare_states(smoke, f"{dtype} {name} folded state vs jnp",
                        svc.model_state(name), want, cfg, xq)


def one_chip(smoke: Smoke) -> None:
    import jax
    import numpy as np
    from repro.data.synthetic import encode_images, load_or_synthesize
    from repro.kernels import ops

    jax.config.update("jax_default_matmul_precision", "highest")
    interpreted = ops._interpret()
    smoke.log(f"kernels.ops._interpret() = {interpreted}")
    smoke.check(interpreted is False, "Pallas kernels would be interpreted")
    ds = load_or_synthesize("mnist")
    data = {
        "x_train": encode_images(ds.x_train[:N_TRAIN]),
        "y_train": ds.y_train[:N_TRAIN],
        "x_fb": encode_images(ds.x_train[N_TRAIN:N_TRAIN + FEEDBACK_BATCH]),
        "y_fb": ds.y_train[N_TRAIN:N_TRAIN + FEEDBACK_BATCH],
        "x_test": encode_images(ds.x_test[:N_REQUESTS]).astype(np.float32),
    }
    trained = smoke.phase("train", train_phase, smoke, data)
    if trained is None:
        return
    for dtype in ("fp32", "bf16", "int8"):
        smoke.phase(f"serve-{dtype}", serve_phase, smoke, trained, data,
                    dtype)


# --------------------------------------------------------- four chips ----

def four_chips(smoke: Smoke) -> None:
    import jax
    import numpy as np
    from repro.configs.bcpnn_models import MODEL3_BREAST
    from repro.core import Trainer
    from repro.data.synthetic import encode_images, load_or_synthesize
    from repro.distributed.fault import describe_failure_domains, elastic_mesh

    n_dev = len(jax.devices())
    smoke.check(n_dev >= 4, f"--chips 4 needs 4 devices, JAX has {n_dev}")
    if n_dev < 4:
        return
    cfg = dataclasses.replace(MODEL3_BREAST, backend="jnp")
    ds = load_or_synthesize("breast")
    x = encode_images(ds.x_train[:N_TRAIN])
    y = ds.y_train[:N_TRAIN]

    def fit(mesh):
        tr = Trainer(cfg, seed=0, mesh=mesh)
        t0 = time.perf_counter()
        tr.fit(x, y, epochs=1, batch=BATCH)
        return tr, time.perf_counter() - t0

    single, t1 = fit(None)
    smoke.log(f"model3 single-device fit: {len(x)} samples, {t1:.1f}s "
              f"incl. compile")
    mesh = elastic_mesh((4,), ("data",))
    smoke.log(f"mesh: {describe_failure_domains(mesh)}, devices "
              f"{[d.id for d in mesh.devices.flat]}")
    smoke.check(mesh.devices.size == 4, "mesh does not span 4 devices")
    dp, t4 = fit(mesh)
    smoke.log(f"model3 4-way data-parallel fit: {t4:.1f}s incl. compile")
    pij = dp.state.projs[0].traces.pij
    placement = [(s.device.id, tuple(s.data.shape))
                 for s in pij.addressable_shards]
    smoke.log(f"DP state pij {tuple(pij.shape)} shards (device, shape): "
              f"{placement}")
    smoke.check(len({d for d, _ in placement}) == 4,
                "DP state is not placed on 4 devices")
    leaves = zip(jax.tree_util.tree_leaves_with_path(single.state),
                 jax.tree_util.tree_leaves(dp.state))
    same, worst = True, (0.0, "")
    for (path, a), b in leaves:
        a, b = np.asarray(a), np.asarray(b)
        if not np.array_equal(a, b):
            same = False
            if np.issubdtype(a.dtype, np.floating):
                d = float(np.max(np.abs(a.astype(np.float64) - b)))
                worst = max(worst, (d, jax.tree_util.keystr(path)))
            else:
                smoke.check(False, f"integer leaf "
                            f"{jax.tree_util.keystr(path)} differs")
    smoke.log(f"DP state bit-identical to single-device: {same}; max "
              f"|diff| {worst[0]:.3e} {worst[1]}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: train + serve on one chip; 4: the "
                         "data-parallel phase only")
    args = ap.parse_args(argv)
    import jax

    device = _device()
    if device["platform"] != "tpu":
        print(f"[smoke] FAIL: no TPU (JAX platform {device['platform']!r})",
              file=sys.stderr)
        return 1
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.compile_cache import enable_compile_cache

    smoke = Smoke()
    smoke.log(f"device {device}; compile cache {enable_compile_cache()}; "
              f"jax {jax.__version__}")
    if args.chips == 4:
        smoke.phase("data-parallel", four_chips, smoke)
    else:
        one_chip(smoke)
    if smoke.failures:
        print(f"[smoke] {len(smoke.failures)} check(s) failed:",
              file=sys.stderr)
        for f in smoke.failures:
            print(f"  {f}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
