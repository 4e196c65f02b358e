"""A benchmark tree at CPU size, written into a temporary directory:
``BENCHMARK.json`` and every part the harness finds by name."""
import json
import os
import time

from bench import harness, work

NET = {"input_mc": 2, "hidden_hc": 2, "hidden_mc": 16, "alpha": 0.002,
       "eps": 0.0001, "gain": 1.0, "support_noise": 3.0, "noise_steps": 30,
       "struct_every": 0, "backend": "jnp", "infer_dtype": "fp32",
       "dtype": "float32", "matmul_precision": "highest",
       "image_side": 8, "input_hc": 64, "nact_hi": 64,
       "reference": "bench/references/bcpnn.py"}
CONFIGS = {
    "tiny-breast": dict(NET, dataset="breast", n_classes=2, epochs=3,
                        n_train=40, n_test=16),
    "tiny-mnist": dict(NET, dataset="mnist", n_classes=10, epochs=1,
                       n_train=64, n_test=16),
}
SERVE = {"kind": "serve", "feedback_share": 0.1, "pool": 64,
         "feedback_pool": 64, "setup_fit_images": 64, "setup_fit_epochs": 1,
         "batch": 16, "warmup_s": 0.2, "sample": 40,
         "engine": {"max_batch": 8, "max_wait_ms": 2.0,
                    "online_learning": True, "feedback_batch": 8,
                    "feedback_eager": False}}
TRAFFIC = {
    "fit": {"kind": "train_fit", "batch": 16, "setup_fits": 3,
            "data_parallel": 1},
    "fit-dp2": {"kind": "train_fit", "batch": 16, "setup_fits": 3,
                "data_parallel": 2},
    "closed": dict(SERVE, in_flight=16),
}
CELLS = [("t-train", "tiny-breast", "fit", 1),
         ("t-train-dp2", "tiny-breast", "fit-dp2", 2),
         ("t-closed", "tiny-mnist", "closed", 1)]
# Limits at this size, between the program's readings here and the
# control's (the reference at ``high``) on three seeds: 99th-percentile
# trace gaps up to 8.9e-6 against 2.4e-5 and more, fold gaps up to
# 7.2e-6 against 3.2e-5 and more.
TRAIN_LIMITS = {"trace_gap_p99.fit1": 2e-5, "trace_gap_p99.fit3": 1.5e-5,
                "probs_gap.fit3": 1e-3}
SERVE_LIMITS = {"unanswered": 0.0, "pred_gap": 0.5, "fold_gap_p99": 1.5e-5}
METRIC = '''def read(r):
    if r.window_s <= 0:
        return None
    return r.e2e["setup_s"] / r.window_s
'''


def write(root: str) -> str:
    def put(rel, obj):
        path = os.path.join(root, rel)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as f:
            f.write(obj if isinstance(obj, str) else json.dumps(obj))

    for name, cfg in CONFIGS.items():
        put(f"bench/configs/{name}.json", dict(cfg, name=name))
    for name, tf in TRAFFIC.items():
        put(f"bench/traffic/{name}.json", tf)
    for cell, _, traffic, _ in CELLS:
        lim = TRAIN_LIMITS if traffic.startswith("fit") else SERVE_LIMITS
        put(f"bench/limits/{cell}.json",
            {"checks": {k: {"limit": v} for k, v in lim.items()}})
    put("bench/metrics/setup_per_window.py", METRIC)
    put("BENCHMARK.json", {
        "workloads": [{"name": c, "config": cfg, "traffic": t, "chips": n,
                       "why": "test"} for c, cfg, t, n in CELLS],
        "end_to_end": [
            {"name": "train_images_per_s", "unit": "images/s",
             "better": "higher", "bound": 0.05, "source": "host_clock",
             "workloads": ["t-train", "t-train-dp2"]},
            {"name": "serve_images_per_s", "unit": "images/s",
             "better": "higher", "bound": 0.05, "source": "host_clock",
             "workloads": ["t-closed"]},
            {"name": "setup_s", "unit": "s", "better": "lower",
             "bound": 0.25, "source": "host_clock"}],
        "per_layer": [
            {"name": "setup_per_window", "unit": "1", "better": "lower",
             "source": "host_clock", "layer": "harness",
             "moves": "train_images_per_s", "workloads": ["t-train"]}]})
    return root


def run(root: str, cell: str, seed: int = 2**31 + 11, seconds: float = 0.3,
        trace: bool = False, trace_dir=None) -> dict:
    """One run of ``cell`` on the CPU, the look for a chip skipped."""
    return harness.execute(root, cell, seed, seconds, trace,
                           time.perf_counter(), require_tpu=False,
                           trace_dir=trace_dir,
                           peak=work.peaks("TPU v5 lite"))


def lower_program_precision(monkeypatch) -> None:
    """The control in the program's place: while a cell's driver sets up,
    runs its window and releases the program, every float32 product
    anywhere is computed in three bfloat16 passes (``high``); the
    comparison with the reference, after that, runs at full precision."""
    import contextlib
    import functools

    import jax
    import jax.numpy as jnp
    from jax._src.lax import lax as lax_impl

    real = lax_impl.dot_general

    def three_passes(lhs, rhs, dimension_numbers, precision=None,
                     preferred_element_type=None, **kw):
        dot = functools.partial(real, dimension_numbers=dimension_numbers,
                                precision=jax.lax.Precision.HIGHEST,
                                preferred_element_type=preferred_element_type,
                                **kw)
        if lhs.dtype != jnp.float32 or rhs.dtype != jnp.float32:
            return dot(lhs, rhs)

        def bf16(a):
            return a.astype(jnp.bfloat16).astype(jnp.float32)

        lh, rh = bf16(lhs), bf16(rhs)
        return dot(lh, rh) + (dot(lh, bf16(rhs - rh))
                              + dot(bf16(lhs - lh), rh))

    @contextlib.contextmanager
    def lowered():
        lax_impl.dot_general = three_passes
        try:
            yield
        finally:
            lax_impl.dot_general = real

    real_load = harness.load_module

    def load(path, name):
        mod = real_load(path, name)
        if name.startswith("bench_driver_"):
            class Lowered(mod.Driver):
                def setup(self):
                    with lowered():
                        return super().setup()

                def window(self, seconds):
                    with lowered():
                        return super().window(seconds)

                def release(self):
                    with lowered():
                        return super().release()
            mod.Driver = Lowered
        return mod

    monkeypatch.setattr(harness, "load_module", load)
