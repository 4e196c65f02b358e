"""Host spans on the profiler's clock.

``span(name, **args)`` marks one stretch of host work in the serving
engine or the trainer.  It is on exactly while a JAX profiler session is
active (``jax.profiler.start_trace`` / ``jax.profiler.trace``):

* off, it costs one ``TraceAnnotation.is_enabled()`` check and returns
  one shared no-op object; nothing is recorded;
* on, it opens a ``jax.profiler.TraceAnnotation`` carrying its
  arguments, so the span lands on the trace's host plane beside the
  device's programs, and appends a ``Record`` to a bounded in-memory
  buffer (``recorded()``), nested under the span that was open on the
  same thread when it started.

``timed(name, **args)`` is the same span for a step whose length is
needed whether or not a session is active (the straggler detector's
steps): it always reads the clock and exposes ``dt``, and records only
while a session is active.

``cpu=True`` adds ``cpu_s``, the thread's CPU time over the span: a
host-work span whose wall time far exceeds its ``cpu_s`` was waiting
(for the interpreter lock, or for another thread).  Arguments known only
inside the span are added with ``set(...)``; work done only to compute
them is guarded by ``if sp.on``.

Spans are opened on the host only, never inside a jitted function.
"""
from __future__ import annotations

import itertools
import threading
import time
from typing import Dict, List, NamedTuple, Optional

from jax.profiler import TraceAnnotation

CAPACITY = 1 << 17          # records kept; later spans are counted as dropped


class Record(NamedTuple):
    """One closed span: host ``perf_counter`` seconds, the ``id`` of the
    span open on the same thread when it started (``-1`` at the top)."""

    name: str
    start: float
    end: float
    parent: int
    args: Dict[str, object]
    id: int

    @property
    def dt(self) -> float:
        return self.end - self.start


_enabled = TraceAnnotation.is_enabled
_buf: List[Record] = []
_dropped = 0
_lock = threading.Lock()
_ids = itertools.count()
_local = threading.local()


class _Off:
    """The shared stand-in returned while no profiler session is
    active."""

    __slots__ = ()
    on = False

    def __enter__(self) -> "_Off":
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def set(self, **args) -> None:
        pass


OFF = _Off()


class _Span:
    __slots__ = ("name", "args", "on", "cpu", "t0", "t1", "id", "parent",
                 "_ann", "_cpu0")

    def __init__(self, name: str, args: dict, cpu: bool, on: bool):
        self.name, self.args, self.cpu, self.on = name, args, cpu, on
        self.t0 = self.t1 = 0.0

    @property
    def dt(self) -> float:
        return self.t1 - self.t0

    def set(self, **args) -> None:
        if self.on:
            self.args.update(args)

    def __enter__(self) -> "_Span":
        if self.on:
            stack = _stack()
            self.parent = stack[-1] if stack else -1
            self.id = next(_ids)
            stack.append(self.id)
            self._ann = TraceAnnotation(self.name)
            self._ann.__enter__()
            if self.cpu:
                self._cpu0 = time.thread_time()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        self.t1 = time.perf_counter()
        if self.on:
            if self.cpu:
                self.args["cpu_s"] = time.thread_time() - self._cpu0
            if self.args:
                self._ann.set_metadata(**self.args)
            self._ann.__exit__(*exc)
            _stack().pop()
            # A span that outlives its session is dropped, as the
            # profiler drops its annotation.
            if _enabled():
                _keep(Record(self.name, self.t0, self.t1, self.parent,
                             self.args, self.id))
        return False


def _stack() -> list:
    s = getattr(_local, "stack", None)
    if s is None:
        s = _local.stack = []
    return s


def _keep(rec: Record) -> None:
    global _dropped
    with _lock:
        if len(_buf) < CAPACITY:
            _buf.append(rec)
        else:
            _dropped += 1


def span(name: str, cpu: bool = False, **args):
    """A host span; the shared no-op ``OFF`` while no session is
    active."""
    if not _enabled():
        return OFF
    return _Span(name, args, cpu, True)


def timed(name: str, cpu: bool = False, **args) -> _Span:
    """A span that always times itself (``dt`` after it closes) and is
    recorded only while a session is active."""
    return _Span(name, args, cpu, _enabled())


def recorded() -> List[Record]:
    """The spans closed while a session was active, oldest first."""
    return list(_buf)


def dropped() -> int:
    """Spans not kept because the buffer was full."""
    return _dropped


def clear() -> None:
    global _dropped
    with _lock:
        _buf.clear()
        _dropped = 0


def total(records: List[Record], *names: str,
          under: Optional[str] = None) -> float:
    """Seconds in spans named ``names``; with ``under``, only those
    nested, at any depth, in a span named ``under``."""
    by_id = {r.id: r for r in records} if under else {}

    def inside(r: Record) -> bool:
        p = by_id.get(r.parent)
        while p is not None:
            if p.name == under:
                return True
            p = by_id.get(p.parent)
        return False

    return sum(r.dt for r in records
               if r.name in names and (under is None or inside(r)))


def arg_total(records: List[Record], name: str, key: str) -> float:
    """Sum of argument ``key`` over the spans named ``name`` that carry
    it."""
    return float(sum(r.args[key] for r in records
                     if r.name == name and key in r.args))
