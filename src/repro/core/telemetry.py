"""Program spans, counters and the vocabulary of device scopes.

Host spans mark the layer boundaries of the program (inspector, drivers,
serving loop): :func:`span` enters a ``jax.profiler.TraceAnnotation`` named
``repro.<name>``, so under a profiler session the span lands on the host
timeline of the same trace as the device's operations, and keeps a
thread-local stack of the open span names and, per name, the host-clock
``(start, end)`` of its most recent spans.  Without a profiler session the
annotation is a no-op, and a span costs a few microseconds.

Counters live in one process-wide registry (:func:`count`,
:func:`counters`).  Two hooks feed it and the spans from outside the
program's own calls:

* every program JAX compiles or loads from its persistent cache counts as
  ``programs.<innermost open span>`` (``programs.outside`` with none open),
  so the program says which of its own steps compiled;
* every garbage collection runs inside a ``gc`` span, so a collection that
  holds the host shows on the trace by name.

Device code names its phases with ``jax.named_scope``; the names form one
vocabulary, :data:`SCOPES`.  A scope is metadata only (each operation's
``op_name``, which the profiler reports as its ``tf_op``): fusion does not
read it, so an operation fused across a scope boundary is charged to the
scope of the operation it was fused into.
"""
from __future__ import annotations

import collections
import contextlib
import gc
import threading
import time

import jax

#: The named scopes of the device path (``jax.named_scope``), outermost
#: first: the drivers' loop bodies, the two directions of an advance, and
#: the phases of the advance and its executors.
SCOPES = (
    "bfs.level",      # one BFS level (sparse/graph.py::_bfs_loop)
    "sssp.iter",      # one Bellman-Ford iteration (_sssp_loop)
    "delta.bucket",   # one delta-stepping bucket, light and heavy phases
    "pagerank.iter",  # one power iteration (_pagerank_loop)
    "push",           # the push branch of a direction-resolved advance
    "pull",           # the pull branch
    "mask",           # frontier gather AND edge-subset mask per edge
    "compact",        # gather-compaction of the active edges, and its branch
    # the compacted branch's rung k (core/execute.py::compact_rungs), r0
    # the top; 22 rungs cover every capacity below 2**31
    *(f"compact.r{k}" for k in range(22)),
    "masked",         # the full-window fallback of a compacting advance
    "windows",        # per-chunk value windows (pure or kernel)
    "scatter",        # segmented reductions by output id
    "fixup",          # cross-chunk partial tiles combined
    "gather",         # PageRank's per-edge gather of rank shares
    "update",         # PageRank's rank update and dangling sum
    "frontier",       # BFS depth update and the frontier's active edges
    "kernel",         # a Pallas kernel launch (core/execute.py::pallas_call)
)

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

#: Host-clock spans kept per span name.
SPAN_HISTORY = 256

_local = threading.local()
_lock = threading.Lock()
_counters: collections.Counter = collections.Counter()
_recent: dict = collections.defaultdict(
    lambda: collections.deque(maxlen=SPAN_HISTORY))


def _open() -> list:
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


@contextlib.contextmanager
def span(name: str):
    """Host span ``repro.<name>`` around the ``with`` block (or function,
    used as a decorator)."""
    stack = _open()
    stack.append(name)
    start = time.perf_counter()
    try:
        with jax.profiler.TraceAnnotation(f"repro.{name}"):
            yield
    finally:
        stack.pop()
        _recent[name].append((start, time.perf_counter()))


def recent_spans(name: str) -> list:
    """Host-clock ``(start, end)`` of the latest spans ``name``, oldest
    first (at most :data:`SPAN_HISTORY`)."""
    return list(_recent.get(name, ()))


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to the counter ``name``."""
    with _lock:
        _counters[name] += n


def counters() -> dict:
    """Every counter of the process: ``{name: total}``."""
    with _lock:
        return dict(_counters)


def _on_event(event: str, duration: float, **kwargs) -> None:
    if event == COMPILE_EVENT:
        stack = _open()
        count(f"programs.{stack[-1] if stack else 'outside'}")


def _on_gc(phase: str, info: dict) -> None:
    if phase == "start":
        _local.gc = span("gc")
        _local.gc.__enter__()
    elif getattr(_local, "gc", None) is not None:
        collection, _local.gc = _local.gc, None
        collection.__exit__(None, None, None)


jax.monitoring.register_event_duration_secs_listener(_on_event)
gc.callbacks.append(_on_gc)
