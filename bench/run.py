"""Run one benchmark cell on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One run is one process: it builds the cell named in ``BENCHMARK.json``
from the seed, warms up every shape the cell's traffic uses (set-up,
reported as ``setup_s``), measures for ``--seconds``, compares what the
timed path produced with the plain reference, and prints one JSON object
as the last line of standard output.  ``--trace 1`` profiles the window
and reports the cell's per-layer metrics instead of its end-to-end ones.
The run refuses to start, and prints no result, when JAX finds no TPU or
fewer chips than the cell asks for.  JAX's compilation cache is kept in
``.jax_cache/`` at the root of the checkout.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    # The cache lives in the checkout, at a fixed path: the path is part
    # of the cache's key.
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    sys.path.insert(0, ROOT)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from bench import harness

    try:
        ctx = harness.resolve(ROOT, args.workload)
        import jax

        harness.device_info(ctx.chips)
    except (harness.NoChip, KeyError, FileNotFoundError, RuntimeError) as e:
        print(f"[bench] not run: {e}", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    # Every program the cell runs is small enough to compile in under a
    # second; cache them all, so only a checkout's first run compiles.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    out = harness.execute(ROOT, args.workload, args.seed, args.seconds,
                          bool(args.trace), T_START)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
