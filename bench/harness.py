"""The benchmark harness: finds a cell's parts by name, times its set-up
and its window, reads the per-layer metrics and decides ``correct``.

Everything that belongs to one configuration, traffic mix or metric sits
in a file of its own, found by the name ``BENCHMARK.json`` gives it:

* ``bench/configs/<config>.json``   -- the network and how it is run;
* ``bench/traffic/<traffic>.json``  -- the mix; its ``kind`` names the
  generator in ``bench/drivers/<kind>.py``;
* ``bench/metrics/<metric>.py``     -- one reader per per-layer metric,
  ``read(reading) -> float | None``;
* ``bench/limits/<workload>.json``  -- the limit of each number that the
  cell's correctness comparison reads.

A driver class ``Driver(ctx)`` runs one cell: ``setup()``, then
``window(seconds)`` (the measured part), ``release()`` (frees the
program's state) and ``check()`` (the comparison with the plain
reference, after the window).
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import os
import shutil
import sys
import time
from typing import Dict, List, Optional

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoChip(RuntimeError):
    """JAX finds no accelerator, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Check:
    """One number the correctness comparison reads, beside its limit."""

    name: str
    value: float
    limit: Optional[float]
    compared: bool = True       # False: printed beside the others only

    @property
    def ok(self) -> bool:
        return self.limit is not None and self.value <= self.limit


@dataclasses.dataclass
class WindowResult:
    """What a driver's measured window produced."""

    metrics: Dict[str, float]           # end-to-end, by name
    attempted: int
    failed: int
    window_s: float
    work: Dict[str, object] = dataclasses.field(default_factory=dict)
    model_flops: float = 0.0
    counters: Dict[str, float] = dataclasses.field(default_factory=dict)
    log: List[str] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class Reading:
    """What a per-layer metric's reader may read."""

    e2e: Dict[str, float]
    window_s: float
    work: Dict[str, object]
    model_flops: float
    counters: Dict[str, float]
    trace: object                       # reduce.Summary, or None
    chips: int
    peak: dict


@dataclasses.dataclass
class Context:
    """A cell as the driver sees it."""

    root: str
    workload: dict
    config: dict
    traffic: dict
    seed: int
    chips: int
    trace: bool
    excluded_s: float = 0.0             # correctness-only time in set-up

    @contextlib.contextmanager
    def excluded(self):
        """Time spent inside this block is kept out of ``setup_s``."""
        t = time.perf_counter()
        try:
            yield
        finally:
            self.excluded_s += time.perf_counter() - t


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def part(root: str, kind: str, name: str, ext: str = ".json") -> str:
    """Path of a cell's part by name: ``bench/<kind>/<name><ext>``."""
    path = os.path.join(root, "bench", kind, name + ext)
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind} named {name!r} ({path})")
    return path


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def resolve(root: str, workload: str) -> Context:
    """The cell named ``workload`` in ``<root>/BENCHMARK.json``."""
    spec = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    return Context(root=root, workload=w,
                   config=load_json(part(root, "configs", w["config"])),
                   traffic=load_json(part(root, "traffic", w["traffic"])),
                   seed=0, chips=int(w["chips"]), trace=False)


def metrics_of(root: str, workload: str, trace: bool) -> List[dict]:
    """The metrics a run of ``workload`` reports: its end-to-end metrics
    (``trace`` off) or its per-layer metrics (``trace`` on)."""
    spec = load_json(os.path.join(root, "BENCHMARK.json"))
    e2e = [m for m in spec["end_to_end"]
           if workload in m.get("workloads", [workload])]
    if not trace:
        return e2e
    moved = {m["name"] for m in e2e}
    return [m for m in spec["per_layer"]
            if workload in m.get("workloads", [workload] if m["moves"]
                                 in moved else [])]


def limits(root: str, workload: str) -> Dict[str, float]:
    path = os.path.join(root, "bench", "limits", workload + ".json")
    if not os.path.isfile(path):
        return {}
    return {k: float(v["limit"])
            for k, v in load_json(path)["checks"].items()}


def device_info(chips: int) -> dict:
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX finds no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX has "
                     f"{len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak(chips: int) -> int:
    import jax

    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


class CollectionClock:
    """The interpreter's garbage collections while armed: how many of
    each generation, and the longest pause."""

    def __init__(self):
        import gc

        self.armed = False
        self.count = [0, 0, 0]
        self.longest = 0.0
        self.total = 0.0
        self._t = None
        gc.callbacks.append(self._on)

    def _on(self, phase: str, info: dict) -> None:
        if not self.armed:
            return
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            d = time.perf_counter() - self._t
            self._t = None
            self.count[info["generation"]] += 1
            self.total += d
            self.longest = max(self.longest, d)

    def close(self) -> None:
        import gc

        gc.callbacks.remove(self._on)


class CompileCounter:
    """Counts executables built or fetched from the cache while armed."""

    def __init__(self):
        import jax

        self.armed = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if self.armed and event == COMPILE_EVENT:
            self.count += 1


def span(name: str):
    """A host span in the profiler's trace (``bench.*``)."""
    import jax

    return jax.profiler.TraceAnnotation(name)


def _fmt(x: float) -> str:
    return repr(float(x))


def execute(root: str, workload: str, seed: int, seconds: float,
            trace: bool, t_start: float, require_tpu: bool = True,
            trace_dir: Optional[str] = None, peak: Optional[dict] = None,
            err=None) -> dict:
    """Run one cell and return its result line (a dict)."""
    import jax

    err = err or sys.stderr
    ctx = resolve(root, workload)
    ctx.seed, ctx.trace = seed, trace
    if require_tpu:
        device = device_info(ctx.chips)
    else:
        devs = jax.devices()
        device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
                  "count": len(devs)}
    from bench import work

    peak = peak or work.peaks(device["kind"])
    jax.config.update("jax_default_matmul_precision",
                      ctx.config["matmul_precision"])
    driver = load_module(os.path.join(BENCH, "drivers",
                                      ctx.traffic["kind"] + ".py"),
                         "bench_driver_" + ctx.traffic["kind"]).Driver(ctx)
    counter = CompileCounter()
    driver.setup()
    setup_s = time.perf_counter() - t_start - ctx.excluded_s
    summary = None
    if trace:
        trace_dir = trace_dir or os.path.join(root, ".bench_traces",
                                              workload)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    gcs = CollectionClock()
    counter.armed = gcs.armed = True
    res = driver.window(seconds)
    counter.armed = gcs.armed = False
    gcs.close()
    if trace:
        jax.profiler.stop_trace()
        summary = _summarize(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
    mem = memory_peak(ctx.chips)
    for line in res.log:
        print(line, file=err)
    print(f"[bench] compiles inside the window: {counter.count}", file=err)
    print(f"[bench] interpreter collections inside the window (generations "
          f"0/1/2): {gcs.count}, {_fmt(gcs.total)} s in all, longest "
          f"{_fmt(gcs.longest)} s", file=err)
    print(f"[bench] setup_s {_fmt(setup_s)} (correctness snapshots kept out:"
          f" {_fmt(ctx.excluded_s)} s)", file=err)
    driver.release()
    readings = driver.check()
    for c in readings:
        if not c.compared:
            print(f"[bench] reading {c.name} {_fmt(c.value)} (not compared)",
                  file=err)
    checks = [c for c in readings if c.compared]
    lim = limits(root, workload)
    for c in checks:
        c.limit = lim.get(c.name)
    correct = (bool(checks) and all(c.ok for c in checks)
               and res.attempted > 0)
    metrics = {}
    e2e = dict(res.metrics, setup_s=setup_s)
    if not trace:
        for m in metrics_of(root, workload, False):
            if m["name"] in e2e:
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}
    else:
        reading = Reading(e2e=e2e, window_s=res.window_s, work=res.work,
                          model_flops=res.model_flops,
                          counters=res.counters, trace=summary,
                          chips=ctx.chips, peak=peak)
        for m in metrics_of(root, workload, True):
            reader = load_module(part(root, "metrics", m["name"], ".py"),
                                 "bench_metric_" + m["name"].replace(".", "_"))
            value = reader.read(reading)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
    device["memory_peak_bytes"] = mem
    out = {"correct": correct, "attempted": res.attempted,
           "failed": res.failed, "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        out["breakdown"] = {
            "device_ops": [[n, s] for n, s in summary.device_ops],
            "idle_gaps": [[n, s] for n, s in summary.idle_gaps]}
    out["compiles_in_window"] = counter.count
    out["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                     for c in checks}
    for c in checks:
        print(f"[check] {c.name} {_fmt(c.value)} limit "
              f"{'none' if c.limit is None else _fmt(c.limit)} "
              f"{'ok' if c.ok else 'FAIL'}", file=err)
    return out


def _summarize(trace_dir: str):
    """Reduce the newest trace under ``trace_dir`` over the window span."""
    import glob

    from bench import reduce

    path = max(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                         recursive=True), key=os.path.getmtime)
    tr = reduce.load(path)
    win = [s for s in tr.spans if s[0] == "bench.window"]
    if not win:
        return None
    return reduce.summarize(tr, win[-1][1], win[-1][2])
