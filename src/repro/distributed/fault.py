"""Fault-tolerance utilities: straggler detection and elastic meshes.

Straggler mitigation at 1000+ nodes is observability first: per-step wall
times are tracked online (median + MAD), outlier steps are attributed and
logged so the scheduler can drain/replace slow hosts.  Elastic restart is
mesh rebuilding from whatever devices remain + checkpoint resharding
(checkpoint/ckpt.py restores onto the new mesh's shardings).
"""
from __future__ import annotations

import dataclasses
import time
from typing import List, Optional

import jax
import numpy as np
from jax.sharding import Mesh


class WorkerLost(RuntimeError):
    """A data-parallel worker (device/host) dropped out of the mesh.

    Raised by fault-injection hooks (``Trainer.fit(on_chunk=...)``) and by
    real loss detectors; the recovery ladder is: rebuild the largest
    fitting mesh with ``elastic_mesh`` from the survivors, restore the
    latest checkpoint, and resume the fit from its stored cursor
    (DESIGN.md §12)."""


@dataclasses.dataclass
class StepTimer:
    """Online step-time tracker with robust outlier detection.

    ``_times`` is trimmed to the last ``window`` entries on every
    ``stop`` — the tracker is O(window) memory no matter how long the
    serving engine or fit runs (it used to append forever and only
    *slice* the window at read time, a leak on multi-day runs).
    ``median`` is therefore the median of the retained window, which is
    also exactly the statistic the outlier test uses.
    """

    window: int = 50
    threshold: float = 3.0  # MADs above median = straggler event
    _times: List[float] = dataclasses.field(default_factory=list)
    _t0: Optional[float] = None
    events: List[dict] = dataclasses.field(default_factory=list)

    def start(self):
        self._t0 = time.perf_counter()

    def stop(self, step: int, tag: Optional[str] = None) -> float:
        """Close the started window; ``tag`` attributes the step to an
        owner (the serving engine passes the model name, so an injected
        or genuine straggler batch names WHOSE microbatch stalled).

        A ``stop()`` with no open window (no prior ``start()``, or a
        double stop) is a caller bug — raise a clear error instead of
        the bare ``TypeError`` that ``None`` arithmetic used to produce.
        """
        if self._t0 is None:
            raise RuntimeError(
                f"StepTimer.stop(step={step}, tag={tag!r}) called without "
                f"a prior start() — every timed window must be opened "
                f"with start() before it is closed")
        dt = time.perf_counter() - self._t0
        self._t0 = None
        return self.record(dt, step, tag)

    def record(self, dt: float, step: int, tag: Optional[str] = None) -> float:
        """Add one step of ``dt`` seconds timed elsewhere (the serving
        engine's and the trainer's step spans read the clock once for
        both the span and this detector)."""
        hist = self._times  # already at most `window` entries
        if len(hist) >= 8:
            med = float(np.median(hist))
            mad = float(np.median(np.abs(np.asarray(hist) - med))) + 1e-9
            if dt > med + self.threshold * 1.4826 * mad:
                ev = {"step": step, "time": dt, "median": med}
                if tag is not None:
                    ev["tag"] = tag
                self.events.append(ev)
        self._times.append(dt)
        if len(self._times) > self.window:
            del self._times[: -self.window]
        return dt

    @property
    def median(self) -> float:
        """Median over the retained window (the last ``window`` steps)."""
        return float(np.median(self._times)) if self._times else 0.0


def order_devices_host_major(devices) -> list:
    """Stable host-major device order: group by ``process_index``, then by
    device id within a host.  A mesh built over this order keeps each
    host's devices contiguous along the leading (data) axis, so losing a
    host removes WHOLE data-axis rows instead of leaving surviving rows
    that straddle processes (which would put a dead device inside a live
    shard_map row)."""
    return sorted(devices, key=lambda d: (getattr(d, "process_index", 0),
                                          getattr(d, "id", 0)))


def fit_mesh_shape(preferred_shape, n_devices: int) -> list:
    """Shrink the data axis (axis 0) of ``preferred_shape`` until the mesh
    fits ``n_devices``; raises when even a single data row does not."""
    shape = list(preferred_shape)
    total = int(np.prod(shape))
    while total > n_devices and shape[0] > 1:
        shape[0] -= 1
        total = int(np.prod(shape))
    if total > n_devices:
        raise RuntimeError(
            f"cannot build mesh {tuple(preferred_shape)} from "
            f"{n_devices} devices")
    return shape


def elastic_mesh(preferred_shape, axis_names, devices=None) -> Mesh:
    """Build the largest mesh of `preferred_shape`'s aspect that fits the
    currently-available devices (drop data-parallel rows for lost hosts).

    Devices are ordered host-major (``order_devices_host_major``) before
    the prefix is taken, so the devices dropped by a shrink are whole
    trailing hosts — not an id-ordered prefix that can split a surviving
    host across data rows.
    """
    devices = order_devices_host_major(
        list(devices if devices is not None else jax.devices()))
    shape = fit_mesh_shape(preferred_shape, len(devices))
    total = int(np.prod(shape))
    use = np.empty(total, dtype=object)
    use[:] = devices[:total]
    return Mesh(use.reshape(shape), axis_names)


def describe_failure_domains(mesh: Mesh) -> dict:
    """Summarize how mesh axes map to failure domains (host/pod)."""
    hosts = {}
    for d in mesh.devices.flat:
        hosts.setdefault(getattr(d, "process_index", 0), []).append(d.id)
    return {"n_devices": mesh.devices.size, "n_hosts": len(hosts),
            "axis_names": list(mesh.axis_names),
            "axis_sizes": list(mesh.devices.shape)}
