"""Trace blocks: what one join did on the host, counted while it runs.

``JoinPlan.execute`` opens one block (:func:`trace_block`) around each
run and reports its contents in ``JoinStats.extra``. Code under it adds
to the innermost open block of its own thread (or task):

* routed rows (:func:`note_routed`, ``extra["routed"]``): every row the
  device path hands elsewhere — interval rows wider than a kernel tile
  admits (to the host), lanes longer than one compaction launch (to
  jnp), and guard-band pairs re-checked at host f64 — so no cut-off is
  silent;
* counters (:func:`count`, ``extra["counters"]``): named integers computed
  from host-known shapes, never from device values, so counting adds no
  device sync — bucket programs and padded rows of the filter, bytes
  moved each way, syncs, and the programs compiled or loaded from the
  persistent compile cache while the block was open;
* spans (:func:`span`, ``extra["spans_s"]``): host seconds per span name.
  A span is also a ``jax.profiler.TraceAnnotation``, so in a profiler
  trace it sits on the same clock as the device ops it dispatched.

Blocks nest — an inner block's totals also add to the enclosing block's
when it closes — and each thread counts only its own work. Outside any
block every call here is a no-op. Nothing is switched on or off: a span
outside the profiler costs two clock reads and an inactive annotation.
"""
from __future__ import annotations

import contextlib
import contextvars
import threading
import time

import jax
from jax.profiler import TraceAnnotation

__all__ = ["ROUTED_KEYS", "Block", "trace_block", "count_routed",
           "note_routed", "count", "span"]

#: routed-row count names (see :func:`note_routed`)
ROUTED_KEYS = ("filter_wide_rows_host", "compact_long_lane_rows_jnp",
               "refine_escalated_rows_host")

#: the innermost open block of this thread (or task); None outside any
_BLOCK: contextvars.ContextVar = contextvars.ContextVar("trace_block",
                                                        default=None)


class Block:
    """The counts of one :func:`trace_block`."""

    __slots__ = ("routed", "counters", "spans_s")

    def __init__(self):
        self.routed = dict.fromkeys(ROUTED_KEYS, 0)
        self.counters: dict[str, int] = {}
        self.spans_s: dict[str, float] = {}

    def add(self, other: "Block") -> None:
        for key, n in other.routed.items():
            self.routed[key] += n
        for key, n in other.counters.items():
            self.counters[key] = self.counters.get(key, 0) + n
        for key, s in other.spans_s.items():
            self.spans_s[key] = self.spans_s.get(key, 0.0) + s


@contextlib.contextmanager
def trace_block():
    """Open a block; yields the :class:`Block` it fills. On exit its
    totals also add to the enclosing block, if any."""
    _listen_for_compiles()
    outer = _BLOCK.get()
    block = Block()
    token = _BLOCK.set(block)
    try:
        yield block
    finally:
        _BLOCK.reset(token)
        if outer is not None:
            outer.add(block)


@contextlib.contextmanager
def count_routed():
    """A :func:`trace_block` that yields only its ``{name: rows}`` routed
    counts."""
    with trace_block() as block:
        yield block.routed


def note_routed(key: str, n: int) -> None:
    """Add ``n`` rows to routed-row count ``key`` of the current block."""
    block = _BLOCK.get()
    if block is not None and n:
        block.routed[key] += int(n)


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to counter ``name`` of the current block."""
    block = _BLOCK.get()
    if block is not None and n:
        block.counters[name] = block.counters.get(name, 0) + int(n)


class _Span:
    __slots__ = ("name", "block", "annotation", "t0")

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        self.block = _BLOCK.get()
        if self.block is not None:
            self.annotation = TraceAnnotation(self.name)
            self.annotation.__enter__()
            self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        block = self.block
        if block is not None:
            dt = time.perf_counter() - self.t0
            self.annotation.__exit__(*exc)
            block.spans_s[self.name] = block.spans_s.get(self.name, 0.0) + dt
        return False


def span(name: str) -> _Span:
    """A context manager naming a host step: a profiler annotation
    ``name``, whose seconds add to ``spans_s[name]`` of the block that was
    current when it opened."""
    return _Span(name)


# -- compile events ----------------------------------------------------------

#: JAX reports a program loaded from the persistent cache as a backend
#: compile too, after a cache-hit event on the same thread
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"

_listen_lock = threading.Lock()
_listening = False
_cache_hit = threading.local()


def _on_event(event: str, **kw) -> None:
    if event == _CACHE_HIT_EVENT:
        _cache_hit.pending = True


def _on_duration(event: str, duration: float, **kw) -> None:
    if event != _COMPILE_EVENT:
        return
    if getattr(_cache_hit, "pending", False):
        _cache_hit.pending = False
        count("cache_loads")
    else:
        count("compiles")


def _listen_for_compiles() -> None:
    """Register the compile listeners, once per process. JAX calls them
    on the thread that compiles, so each block counts its own."""
    global _listening
    if _listening:
        return
    with _listen_lock:
        if not _listening:
            jax.monitoring.register_event_listener(_on_event)
            jax.monitoring.register_event_duration_secs_listener(
                _on_duration)
            _listening = True
