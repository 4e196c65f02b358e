"""Trainer-driven data-parallel training: scaling + kill-resume rows.

Measures the DESIGN.md §12 fit path end to end: images/s of
``Trainer.fit`` on 1/2/4-way meshes (scan-over-batches shard_map epoch
programs, padded-tail masked learning included — the default train_n
does not divide the batch), plus the elastic kill-resume row from the
2-way run (a ``WorkerLost`` is raised mid-schedule, the mesh is rebuilt
from the survivors, and the fit resumes from the latest checkpoint
cursor; ``resumed_bit_identical`` must stay 1).

Every row runs in this process on the devices JAX already has — one
process per chip, so no child ever competes for an attached TPU.  Rows
wider than the device count are skipped.  For host-CPU rows, give the
process virtual devices before JAX starts
(``XLA_FLAGS=--xla_force_host_platform_device_count=4``); those devices
share the machine's cores, so CPU "scaling" is a plumbing check, not a
speedup claim.
"""
import json
import os

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_rows(n_devices: int, *, train_n: int, epochs: int, batch: int,
              no_kill: bool) -> dict:
    import tempfile

    from repro.launch import train_dp

    with tempfile.TemporaryDirectory() as td:
        out_json = os.path.join(td, "out.json")
        argv = ["--devices", str(n_devices), "--train-n", str(train_n),
                "--epochs", str(epochs), "--batch", str(batch),
                "--warmup", "--no-single", "--json", out_json]
        if no_kill:
            argv.append("--no-kill")
        train_dp.run(train_dp.build_parser().parse_args(argv))
        with open(out_json) as f:
            return json.load(f)


def run(devices=(1, 2, 4), json_path="BENCH_train_dp.json", train_n=328,
        epochs=2, batch=64, kill_devices=2) -> dict:
    import jax

    present = jax.devices()
    out = {"train_n": train_n, "epochs": epochs, "batch": batch,
           "platform": present[0].platform,
           "device_kind": present[0].device_kind,
           "scaling": {}, "kill_resume": None}
    base_img_s = None
    for n in devices:
        if n > len(present):
            print(f"train_dp,skipped,{n}way ({len(present)} devices)")
            continue
        row = _run_rows(n, train_n=train_n, epochs=epochs, batch=batch,
                        no_kill=(n != kill_devices))
        img_s = row["dp_images_per_s"]
        entry = {"dp_s": row["dp_s"], "dp_images_per_s": img_s,
                 "dp_acc": row["dp_acc"]}
        print(f"train_dp,{img_s:.0f},images_per_s_{n}way")
        if base_img_s is None:
            base_img_s = img_s
        else:
            entry["scaling_vs_1way"] = img_s / base_img_s
            print(f"train_dp,{img_s / base_img_s:.2f},"
                  f"scaling_{n}way_vs_{devices[0]}way")
        out["scaling"][str(n)] = entry
        if n == kill_devices and "kill_resume_s" in row:
            out["kill_resume"] = {
                "devices": n,
                "kill_resume_s": row["kill_resume_s"],
                "recovery_overhead_s": row["recovery_overhead_s"],
                "resumed_bit_identical": row["resumed_bit_identical"],
                "resumed_acc": row["resumed_acc"],
            }
            print(f"train_dp,{row['kill_resume_s']:.2f},kill_resume_s")
            print(f"train_dp,{row['recovery_overhead_s']:.2f},"
                  f"recovery_overhead_s")
            print(f"train_dp,{int(row['resumed_bit_identical'])},"
                  f"resumed_bit_identical")
    if json_path:
        with open(os.path.join(_ROOT, json_path)
                  if not os.path.isabs(json_path) else json_path, "w") as f:
            json.dump(out, f, indent=2)
            f.write("\n")
    return out


if __name__ == "__main__":
    run()
