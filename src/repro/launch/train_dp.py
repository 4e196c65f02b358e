"""Fault-tolerant data-parallel training driver (DESIGN.md §12).

    PYTHONPATH=src python -m repro.launch.train_dp --smoke

Phases:
  1. synthetic task + single-device baseline fit (the bit-exactness
     reference; the default --train-n does NOT divide the batch, so the
     padded-tail masked path is exercised end to end);
  2. data-parallel fit on the full mesh: the SAME layerwise-greedy
     schedule driven through the shard_map scan-over-batches epoch
     programs — ``--smoke`` asserts the final state is bit-identical to
     the single-device fit, and reports images/s + scaling;
  3. kill-resume: a fresh DP fit checkpoints every ``--ckpt-every``
     batches and a fault hook raises ``WorkerLost`` mid-schedule; the
     driver then rebuilds the largest surviving mesh with
     ``elastic_mesh`` (one device is "lost"), restores the latest
     checkpoint, and resumes from its cursor — ``--smoke`` asserts the
     recovered state is STILL bit-identical to the uninterrupted run
     (column-sharded DP is exact for any shard count), and the recovery
     overhead is reported.

``--devices N`` is the data-axis width, taken from the devices JAX
finds (the mesh shrinks to fit when fewer are present).  With no
accelerator attached, ``main`` gives the host (CPU) platform N virtual
devices via ``--xla_force_host_platform_device_count``; the flag must act
before jax initializes, so this module imports jax inside ``run``.
"""
from __future__ import annotations

import argparse
import os
import tempfile
import time


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--smoke", action="store_true",
                   help="assert bit-exactness + recovery, tiny workload")
    p.add_argument("--devices", type=int, default=2,
                   help="data-axis width (virtual devices on the CPU)")
    p.add_argument("--side", type=int, default=12)
    p.add_argument("--depth", type=int, default=2)
    p.add_argument("--classes", type=int, default=5)
    p.add_argument("--train-n", type=int, default=328,
                   help="train samples (default leaves a padded tail)")
    p.add_argument("--test-n", type=int, default=256)
    p.add_argument("--epochs", type=int, default=2)
    p.add_argument("--batch", type=int, default=64)
    p.add_argument("--ckpt-every", type=int, default=2,
                   help="checkpoint cadence in batches for the kill phase")
    p.add_argument("--kill-at-chunk", type=int, default=3,
                   help="which chunk boundary raises the simulated loss")
    return p


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    # The flag sizes the host (CPU) platform only: an attached TPU keeps
    # its own devices and stays JAX's default.
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count="
            f"{args.devices}").strip()
    from .compile_cache import enable_compile_cache
    enable_compile_cache()
    return run(args)


def run(args: argparse.Namespace) -> int:
    """The three phases on the devices this process already has."""
    import jax
    import numpy as np

    from ..configs.bcpnn_models import deep_synth_spec
    from ..core import Trainer
    from ..data.synthetic import encode_images, make_synthetic
    from ..distributed.fault import (WorkerLost, describe_failure_domains,
                                     elastic_mesh)

    spec = deep_synth_spec(side=args.side, depth=args.depth,
                           n_classes=args.classes, backend="jnp")
    ds = make_synthetic(args.train_n, args.test_n, args.side, args.classes,
                        seed=0)
    xtr, xte = encode_images(ds.x_train), encode_images(ds.x_test)
    ytr, yte = ds.y_train, ds.y_test
    n_img = len(xtr) * args.epochs * spec.depth

    def fit_once(trainer):
        t0 = time.perf_counter()
        trainer.fit(xtr, ytr, epochs=args.epochs, batch=args.batch)
        return time.perf_counter() - t0

    # ---- phase 1: single-device reference ------------------------------
    tr1 = Trainer(spec, seed=0)
    t_single = fit_once(tr1)
    ref = tr1.state
    acc1 = tr1.evaluate(xte, yte, batch=args.batch)
    print(f"[train-dp] single-device: {t_single:.2f}s "
          f"({n_img / t_single:.0f} img/s), acc {acc1:.3f}")

    # ---- phase 2: data-parallel fit on the full mesh -------------------
    mesh = elastic_mesh((args.devices,), ("data",))
    n_dev = mesh.devices.size
    print(f"[train-dp] mesh: {describe_failure_domains(mesh)}")
    tr2 = Trainer(spec, seed=0, mesh=mesh)
    t_dp = fit_once(tr2)
    acc2 = tr2.evaluate(xte, yte, batch=args.batch)
    print(f"[train-dp] {n_dev}-way DP: {t_dp:.2f}s "
          f"({n_img / t_dp:.0f} img/s), acc {acc2:.3f}, "
          f"scaling {t_single / t_dp:.2f}x")
    same = all(
        np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree_util.tree_leaves(ref),
                        jax.tree_util.tree_leaves(tr2.state)))
    print(f"[train-dp] DP state bit-identical to single-device: {same}")
    if args.smoke:
        assert same, "DP fit diverged from the single-device fit"
        assert abs(acc1 - acc2) == 0.0

    # ---- phase 3: kill-resume via elastic_mesh -------------------------
    with tempfile.TemporaryDirectory() as ckpt_dir:
        chunks = {"n": 0}

        def fault_hook(cursor):
            chunks["n"] += 1
            if chunks["n"] == args.kill_at_chunk:
                raise WorkerLost(
                    f"simulated device loss at chunk {chunks['n']} "
                    f"(cursor {cursor})")

        tr3 = Trainer(spec, seed=0, mesh=mesh)
        t_kill0 = time.perf_counter()
        try:
            tr3.fit(xtr, ytr, epochs=args.epochs, batch=args.batch,
                    ckpt_dir=ckpt_dir, ckpt_every_batches=args.ckpt_every,
                    on_chunk=fault_hook)
            raise SystemExit("[train-dp] fault hook never fired — "
                             "lower --kill-at-chunk")
        except WorkerLost as e:
            t_killed = time.perf_counter() - t_kill0
            print(f"[train-dp] {e} after {t_killed:.2f}s")
        # Recovery ladder: largest mesh from the survivors, restore the
        # latest checkpoint, resume from its cursor.
        mesh_devices = list(mesh.devices.flat)
        survivors = mesh_devices[:-1] if n_dev > 1 else mesh_devices
        mesh_r = elastic_mesh((n_dev,), ("data",), devices=survivors)
        print(f"[train-dp] rebuilt mesh from {len(survivors)} survivors: "
              f"{describe_failure_domains(mesh_r)}")
        t_rec0 = time.perf_counter()
        tr_r = Trainer(spec, seed=0, mesh=mesh_r)
        tr_r.fit(xtr, ytr, epochs=args.epochs, batch=args.batch,
                 ckpt_dir=ckpt_dir, ckpt_every_batches=args.ckpt_every,
                 resume=True)
        t_resume = time.perf_counter() - t_rec0
        acc_r = tr_r.evaluate(xte, yte, batch=args.batch)
        overhead = t_killed + t_resume - t_dp
        same_r = all(
            np.array_equal(np.asarray(a), np.asarray(b))
            for a, b in zip(jax.tree_util.tree_leaves(tr2.state),
                            jax.tree_util.tree_leaves(tr_r.state)))
        print(f"[train-dp] kill-resume on {len(survivors)} device(s): "
              f"{t_killed + t_resume:.2f}s total "
              f"({overhead:+.2f}s vs uninterrupted), acc {acc_r:.3f}, "
              f"bit-identical {same_r}")
        if args.smoke:
            assert same_r, ("resumed fit diverged from the uninterrupted "
                            "run")
            assert float(acc_r) == float(acc2)
        if tr_r.timer is not None and tr_r.timer.events:
            print(f"[train-dp] straggler events: "
                  f"{len(tr_r.timer.events)} (last: "
                  f"{tr_r.timer.events[-1]})")

    print("[train-dp] smoke OK" if args.smoke else "[train-dp] done")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
