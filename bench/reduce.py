"""Reduction of a profiler trace (``.xplane.pb``) to the numbers the
benchmark reports: device busy and idle time, device time per kernel and
per module, time in collectives, and the ``breakdown`` of the result line.

The trace holds one plane per TPU (``/device:TPU:<n>``) whose
``XLA Ops`` line has one event per executed HLO operation, named by its
HLO text (``%bcpnn_fwd_pallas.3 = f32[...] custom-call(...)``), and an
``XLA Modules`` line with one event per program execution.  The host
plane (``/host:CPU``) holds the benchmark's own spans (``bench.*``,
written with ``jax.profiler.TraceAnnotation``) and the runtime's
``DoEnqueueProgram`` / ``CompleteCallbacks`` events, which carry the same
``run_id`` as the device's module events.  Device and host timestamps
start from different origins; the offset between them is taken from
those pairs: a program cannot start on the device before the host has
enqueued it, nor end after the host ran its completion callbacks.

An operation that contains others on the same line (a ``while`` around
its body) is a container: busy time is the union of the leaf operations
only, and container time is never counted as an operation's own.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Tuple

# Collectives by op name; JAX names an all-reduce after its ``psum``.
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
    r"|^psum")
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


def op_name(hlo_text: str) -> str:
    """``%fusion.12 = f32[..] fusion(...)`` -> ``fusion.12``."""
    head = hlo_text.split(" = ", 1)[0]
    return head[1:] if head.startswith("%") else head


def module_name(event_name: str) -> str:
    """``jit_step(1234)`` -> ``jit_step``."""
    return event_name.split("(", 1)[0]


def kernel_of(op: str) -> str:
    """The kernel a custom call runs, by the name of the function that
    built it: ``bcpnn_fwd_pallas.3`` -> ``bcpnn_fwd_pallas``."""
    return re.sub(r"\.\d+$", "", op)


@dataclasses.dataclass
class Op:
    device: int
    module: str
    name: str
    start: float   # seconds, host clock of the trace
    end: float


@dataclasses.dataclass
class Trace:
    """What the reduction keeps of one trace, in seconds on the host clock
    of the trace (device events shifted by their device's offset)."""

    ops: List[Op]                          # leaf operations, every device
    modules: List[Op]                      # program executions
    spans: List[Tuple[str, float, float]]  # benchmark host spans
    devices: List[int]


def _stats(event) -> dict:
    return {k: v for k, v in (event.stats or ())}


def _offsets(planes) -> Dict[int, float]:
    """Per device ordinal, seconds to add to device time to get host
    time."""
    enq: Dict[Tuple[int, int], float] = {}
    done: Dict[Tuple[int, int], float] = {}
    for plane in planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name not in ("DoEnqueueProgram", "CompleteCallbacks"):
                    continue
                st = _stats(e)
                if "run_id" not in st:
                    continue
                key = (int(st.get("device_ordinal", 0)), int(st["run_id"]))
                if e.name == "DoEnqueueProgram":
                    t = (e.start_ns + e.duration_ns) * 1e-9
                    enq[key] = min(enq.get(key, t), t)
                else:
                    done[key] = max(done.get(key, 0.0), e.start_ns * 1e-9)
    lo: Dict[int, float] = {}
    hi: Dict[int, float] = {}
    for plane in planes:
        m = DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        dev = int(m.group(1))
        for line in plane.lines:
            if line.name != "XLA Modules":
                continue
            for e in line.events:
                rid = _stats(e).get("run_id")
                if rid is None:
                    continue
                key = (dev, int(rid))
                s, f = e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9
                if key in enq:
                    lo[dev] = max(lo.get(dev, -1e30), enq[key] - s)
                if key in done:
                    hi[dev] = min(hi.get(dev, 1e30), done[key] - f)
    out = {}
    for dev in set(lo) | set(hi):
        a, b = lo.get(dev), hi.get(dev)
        if a is None:
            out[dev] = b
        elif b is None or a <= b:
            out[dev] = a
        else:
            out[dev] = 0.5 * (a + b)
    return out


def _leaves(events: List[Op]) -> List[Op]:
    """Drop every event that contains another one (same device)."""
    events = sorted(events, key=lambda o: (o.start, -o.end))
    keep = []
    for i, o in enumerate(events):
        nxt = events[i + 1] if i + 1 < len(events) else None
        if (nxt is not None and nxt.device == o.device
                and nxt.start < o.end and nxt.end <= o.end):
            continue
        keep.append(o)
    return keep


def load(path: str) -> Trace:
    import jax

    planes = list(jax.profiler.ProfileData.from_file(path).planes)
    offsets = _offsets(planes)
    ops, modules, spans, devices = [], [], [], []
    for plane in planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        s = e.start_ns * 1e-9
                        spans.append((e.name, s, s + e.duration_ns * 1e-9))
            continue
        m = DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        dev = int(m.group(1))
        devices.append(dev)
        off = offsets.get(dev, 0.0)
        mods, raw = [], []
        for line in plane.lines:
            if line.name not in ("XLA Modules", "XLA Ops"):
                continue
            for e in line.events:
                s = e.start_ns * 1e-9 + off
                f = s + e.duration_ns * 1e-9
                if line.name == "XLA Modules":
                    mods.append(Op(dev, module_name(e.name), "", s, f))
                else:
                    raw.append(Op(dev, "", op_name(e.name), s, f))
        mods.sort(key=lambda o: o.start)
        j = 0
        for o in sorted(raw, key=lambda o: o.start):
            while j + 1 < len(mods) and mods[j + 1].start <= o.start:
                j += 1
            if mods and mods[j].start <= o.start < mods[j].end:
                o.module = mods[j].module
        modules.extend(mods)
        ops.extend(_leaves(raw))
    spans.sort(key=lambda s: s[1])
    return Trace(ops=ops, modules=modules, spans=spans,
                 devices=sorted(devices))


def _union(intervals, lo: float, hi: float) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, f in sorted((max(s, lo), min(f, hi)) for s, f in intervals):
        if f <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], f))
        else:
            out.append((s, f))
    return out


def _length(intervals) -> float:
    return sum(f - s for s, f in intervals)


@dataclasses.dataclass
class Summary:
    """The trace's numbers over one window (host clock, seconds)."""

    window_s: float
    busy_s: float                      # mean over devices
    busy_by_device: Dict[int, float]
    kernel_s: Dict[str, float]         # custom calls by kernel, all devices
    module_s: Dict[str, float]         # program executions, all devices
    collective_s: Dict[int, float]     # union of collective ops per device
    device_ops: List[Tuple[str, float]]
    idle_gaps: List[Tuple[str, float]]
    n_devices: int


def summarize(tr: Trace, lo: float, hi: float, top: int = 10) -> Summary:
    """Reduce ``tr`` over the host-clock window [lo, hi]."""
    busy: Dict[int, float] = {}
    coll: Dict[int, float] = {}
    unions = {}
    for dev in tr.devices:
        mine = [(o.start, o.end) for o in tr.ops if o.device == dev]
        unions[dev] = _union(mine, lo, hi)
        busy[dev] = _length(unions[dev])
        coll[dev] = _length(_union(
            [(o.start, o.end) for o in tr.ops
             if o.device == dev and COLLECTIVE.search(o.name)], lo, hi))
    kernel: Dict[str, float] = {}
    per_op: Dict[str, float] = {}
    for o in tr.ops:
        d = min(o.end, hi) - max(o.start, lo)
        if d <= 0:
            continue
        kernel[kernel_of(o.name)] = kernel.get(kernel_of(o.name), 0.0) + d
        key = f"{o.module}/{o.name}" if o.module else o.name
        per_op[key] = per_op.get(key, 0.0) + d
    module: Dict[str, float] = {}
    for m in tr.modules:
        d = min(m.end, hi) - max(m.start, lo)
        if d > 0:
            module[m.module] = module.get(m.module, 0.0) + d
    gaps = []
    first = tr.devices[0] if tr.devices else None
    if first is not None:
        edges = [lo] + [t for iv in unions[first] for t in iv] + [hi]
        gaps = [(s, f) for s, f in zip(edges[::2], edges[1::2]) if f > s]
    # Name only the longest gaps: a serving window has thousands of gaps
    # and a span for every request.
    gaps.sort(key=lambda g: g[0] - g[1])
    gaps = [(span_at(tr.spans, s, f), f - s) for s, f in gaps[:top]]
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    n = max(1, len(tr.devices))
    return Summary(window_s=hi - lo, busy_s=sum(busy.values()) / n,
                   busy_by_device=busy, kernel_s=kernel, module_s=module,
                   collective_s=coll, device_ops=ops, idle_gaps=gaps,
                   n_devices=len(tr.devices))


def span_at(spans, s: float, f: float) -> str:
    """What the host was doing in the gap [s, f]: the benchmark call span
    (``bench.*``, leaving out the window's own ``bench.window``) that
    overlaps the gap the most, the innermost on a tie; ``outside bench
    calls`` where none does."""
    best: Optional[Tuple[float, float, str]] = None
    for name, a, b in spans:
        if a >= f:
            break
        if name == WINDOW_SPAN:
            continue
        ov = min(b, f) - max(a, s)
        if ov > 0:
            cand = (ov, -(b - a), name)
            if best is None or cand > best:
                best = cand
    return best[2] if best else "outside bench calls"
