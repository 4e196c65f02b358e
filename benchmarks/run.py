"""Benchmark harness — one module per paper table/figure.

Prints ``name,value,unit`` CSV rows:
  * bench_bcpnn           — Table 2 latency/accuracy rows (CPU baseline)
  * bench_struct          — Table 2 'struct' rows (on-device rewire cost)
  * bench_stream_vs_seq   — §4.1 sequential vs stream-dataflow
  * bench_kernels         — dense vs padded vs patchy kernel schedules
                            (writes BENCH_kernels.json)
  * bench_roofline_bcpnn  — Fig. 6 roofline placement (TPU target)
  * bench_lm_rooflines    — assigned-arch dry-run roofline table
  * bench_train_dp        — Trainer DP fit images/s at 1/2/4-way meshes
                            of the devices present + elastic kill-resume
                            overhead (writes BENCH_train_dp.json)

``--assert-patchy-speedup`` is the CI smoke gate for the compact patchy
schedule: it reruns the kernels bench and fails if the measured
patchy-vs-padded step ratio regressed by more than 20% against the
committed ``BENCH_kernels.json``.  The RATIO is compared, not absolute
step_ms — CI hardware differs from whatever produced the committed
snapshot, but both schedules of one run share the machine and geometry,
so their ratio is the transportable signal.  The run must use the same
``--scale`` as the committed snapshot (the ratio is geometry-dependent;
the gate enforces this).

``--assert-quant-accuracy`` is the CI gate for the low-precision serving
path (DESIGN.md §8): it trains a small compact-patchy network on the
synthetic task and fails if bf16 or int8 inference loses more than
0.5pp eval accuracy vs the same state's fp32 path.  Accuracy, unlike
step time, IS machine-transportable, so this gate compares absolutes.
"""
import argparse
import json
import sys

REGRESSION_HEADROOM = 0.8  # fresh ratio must be >= 80% of committed
MAX_QUANT_ACC_DELTA_PP = 0.5  # low-precision eval may lose at most this
# Only gate geometries whose committed patchy-vs-padded margin is material:
# a ratio barely above parity (e.g. model1's 1.04x) leaves less slack than
# shared-runner timing noise, which is exactly the flaky assert the old CI
# step removed.  Near-parity geometries are reported but not enforced.
MIN_GATED_RATIO = 1.2


def assert_patchy_speedup(fresh: dict, baseline: dict) -> None:
    if fresh.get("scale") != baseline.get("scale"):
        raise SystemExit(
            f"--assert-patchy-speedup: this run used --scale "
            f"{fresh.get('scale')} but the committed baseline was recorded "
            f"at --scale {baseline.get('scale')}; the patchy/padded ratio "
            f"is geometry-dependent, so the gate only compares same-scale "
            f"runs — pass --scale {baseline.get('scale')}")
    checked = 0
    for name, row in fresh["geometries"].items():
        base_row = baseline.get("geometries", {}).get(name)
        if base_row is None or "patchy_speedup_vs_padded" not in base_row:
            continue
        committed = base_row["patchy_speedup_vs_padded"]
        got = row["patchy_speedup_vs_padded"]
        if committed < MIN_GATED_RATIO:
            print(f"assert_patchy_speedup,{got:.3f},{name}_ratio "
                  f"(informational: committed {committed:.3f} is below the "
                  f"{MIN_GATED_RATIO} gating margin)")
            continue
        want = committed * REGRESSION_HEADROOM
        print(f"assert_patchy_speedup,{got:.3f},{name}_ratio "
              f"(floor {want:.3f}, committed {committed:.3f})")
        if got < want:
            raise SystemExit(
                f"patchy speedup regression on {name}: patchy/padded step "
                f"ratio {got:.3f} fell below {want:.3f} (committed "
                f"{committed:.3f} with 20% headroom) — the scatter-free "
                f"compact schedule lost its edge; inspect "
                f"BENCH_kernels.json")
        checked += 1
    if checked == 0:
        raise SystemExit(
            "--assert-patchy-speedup: no comparable geometries between "
            "this run and the committed baseline")
    print(f"assert_patchy_speedup,OK,{checked}_geometries")


def assert_quant_accuracy(max_delta_pp: float = MAX_QUANT_ACC_DELTA_PP,
                          epochs: int = 6, seed: int = 0) -> dict:
    """Train small, eval the SAME fp32 state under each serving dtype
    (``infer`` reroutes low-precision specs through the packed path, so
    this measures exactly what the engine serves)."""
    from repro.configs.bcpnn_models import deep_synth_spec
    from repro.core import Trainer, evaluate_padded
    from repro.data.synthetic import encode_images, make_synthetic

    ds = make_synthetic(768, 256, 8, 4, seed=3, max_shift=1)
    xt, xe = encode_images(ds.x_train), encode_images(ds.x_test)
    spec = deep_synth_spec(side=8, depth=1, n_classes=4, hidden_hc=8,
                           hidden_mc=16, nact=[32], patchy_traces=True,
                           compact=True, struct_every=25, backend="pallas")
    tr = Trainer(spec, seed=seed)
    tr.fit(xt, ds.y_train, epochs=epochs, batch=64)
    acc32 = evaluate_padded(tr.state, spec, xe, ds.y_test, 64)
    print(f"assert_quant_accuracy,{acc32*100:.2f},fp32_acc_pct")
    out = {"fp32": acc32}
    for dt in ("bf16", "int8"):
        acc = evaluate_padded(tr.state, spec.with_infer_dtype(dt),
                              xe, ds.y_test, 64)
        delta = (acc32 - acc) * 100
        out[dt] = acc
        print(f"assert_quant_accuracy,{acc*100:.2f},{dt}_acc_pct "
              f"(delta {delta:+.2f}pp, max {max_delta_pp}pp)")
        if delta > max_delta_pp:
            raise SystemExit(
                f"low-precision accuracy regression: {dt} inference lost "
                f"{delta:.2f}pp vs fp32 ({acc32*100:.2f}% -> "
                f"{acc*100:.2f}%), more than the {max_delta_pp}pp budget "
                f"— inspect the quantization path (kernels/quant.py, "
                f"DESIGN.md §8)")
    print("assert_quant_accuracy,OK,2_dtypes")
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of benches")
    ap.add_argument("--quick", action="store_true",
                    help="skip the slow BCPNN latency benches")
    ap.add_argument("--scale", type=int, default=None,
                    help="geometry shrink factor for bench_kernels")
    ap.add_argument("--iters", type=int, default=None,
                    help="timing iterations for bench_kernels")
    ap.add_argument("--assert-patchy-speedup", action="store_true",
                    help="fail if the kernels bench's patchy/padded step "
                         "ratio regressed >20%% vs --baseline")
    ap.add_argument("--assert-quant-accuracy", action="store_true",
                    help="fail if bf16/int8 inference loses more than "
                         f"{MAX_QUANT_ACC_DELTA_PP}pp eval accuracy vs "
                         "fp32 on the synthetic task")
    ap.add_argument("--baseline", default="BENCH_kernels.json",
                    help="committed snapshot the speedup gate compares to")
    args = ap.parse_args()
    from . import (bench_bcpnn, bench_kernels, bench_lm_rooflines,
                   bench_roofline_bcpnn, bench_stream_vs_seq, bench_struct,
                   bench_train_dp)

    kernels_kw = {}
    if args.scale is not None:
        kernels_kw["scale"] = args.scale
    if args.iters is not None:
        kernels_kw["iters"] = args.iters

    def run_kernels():
        # Snapshot the committed baseline BEFORE the bench runs: the bench
        # rewrites BENCH_kernels.json (its default json_path), which is
        # also the default baseline.
        baseline = None
        if args.assert_patchy_speedup:
            try:
                with open(args.baseline) as f:
                    baseline = json.load(f)
            except FileNotFoundError:
                raise SystemExit(
                    f"--assert-patchy-speedup: baseline file "
                    f"{args.baseline!r} does not exist — run the kernels "
                    f"bench once to record it, or point --baseline at the "
                    f"committed snapshot")
            except json.JSONDecodeError as e:
                raise SystemExit(
                    f"--assert-patchy-speedup: baseline {args.baseline!r} "
                    f"is not valid JSON ({e}) — re-record it with the "
                    f"kernels bench")
            if "geometries" not in baseline or "scale" not in baseline:
                raise SystemExit(
                    f"--assert-patchy-speedup: baseline {args.baseline!r} "
                    f"carries no geometries/scale spec — it is not a "
                    f"kernels-bench snapshot; re-record it")
            # keep the committed snapshot pristine: the gate run records
            # its (machine/scale-specific) numbers next to it instead
            kernels_kw.setdefault("json_path", "BENCH_kernels.latest.json")
        out = bench_kernels.run(**kernels_kw)
        if baseline is not None:
            assert_patchy_speedup(out, baseline)
        return out

    benches = {
        "roofline_bcpnn": bench_roofline_bcpnn.run,
        "lm_rooflines": bench_lm_rooflines.run,
        "stream_vs_seq": bench_stream_vs_seq.run,
        "kernels": run_kernels,
        "bcpnn": bench_bcpnn.run,
        "struct": bench_struct.run,
        "train_dp": bench_train_dp.run,
        "quant_accuracy": assert_quant_accuracy,
    }
    selected = (args.only.split(",") if args.only
                else [k for k in benches
                      if not (args.quick and k in ("bcpnn", "struct",
                                                   "train_dp"))
                      and k != "quant_accuracy"])
    if args.assert_quant_accuracy and "quant_accuracy" not in selected:
        selected.append("quant_accuracy")
    if args.assert_patchy_speedup and "kernels" not in selected:
        print("--assert-patchy-speedup requires the kernels bench",
              file=sys.stderr)
        raise SystemExit(2)
    for name in selected:
        benches[name]()


if __name__ == "__main__":
    main()
