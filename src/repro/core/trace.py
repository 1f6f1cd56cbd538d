"""Spans, waits and event counts inside the engine.

Each engine owns one :class:`Tracer` and hands it to every layer that
holds an entity: the planner, the event loop and its queues, the
admission controller, the remote pool and its servers, and the offload
backends.  Three kinds of record, each always on:

- a **span** (``tracer.span(name, **ids)``) is work that starts and ends
  on one thread.  It adds 1 to the span's count and its duration to the
  span's seconds, and it opens the profiler annotation ``vdms.<name>``
  (a :class:`jax.profiler.TraceAnnotation`), so that a profiler trace
  shows it in the host planes on the same clock as the device planes.
  ``ids`` name the request: ``qid``, ``eid`` where the span covers one
  entity, ``n`` for the size of a group; spans of one request share its
  ``qid``, and spans nested on one thread nest in the trace.
- a **wait** (``tracer.wait(name, seconds)``) starts on one thread and
  ends on another: an item put on a queue and taken off it.
  :class:`TimedQueue` records its items' waits itself.
- a **count** (``tracer.count(name, k)``) is an event.

The names are fixed (:data:`SPANS`, :data:`WAITS`, :data:`COUNTS`), and
``stats()`` reports every one of them from the start, zeros included,
so that a reader can take the difference of two snapshots.  The busy
time of the native workers and of Thread_3 stays with their
:class:`~repro.core.event_loop.BusyMeter`\\ s, which open their own
``vdms.native`` and ``vdms.thread3`` annotations through
:func:`annotation`.

Cost with no profiler running: one lock acquisition and two additions
per record, and one check that no profiler is running (the annotation
is not built then).
"""
from __future__ import annotations

import collections
import queue
import threading
import time

import jax

PREFIX = "vdms."
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"

SPANS = ("submit", "expand", "device_collect", "device_stage",
         "device_settle", "device_fetch", "device_deliver",
         "remote_transport", "remote_exec",
         "model_host", "model_prefill", "model_decode")
WAITS = ("admission", "queue1", "queue2", "offload_inbox", "remote_inbox")
COUNTS = ("entities_planned", "entities_done", "compiles",
          "remote_compiled", "remote_eager",
          "model_calls", "model_prefill_tokens", "model_decode_tokens",
          "model_moe_rows", "model_moe_assignments")

_Annotation = jax.profiler.TraceAnnotation
_profiling = _Annotation.is_enabled


def annotation(name: str, **ids):
    """The profiler annotation ``vdms.<name>`` carrying ``ids`` (a
    context manager), or None while no profiler is running."""
    return _Annotation(PREFIX + name, **ids) if _profiling() else None


# ------------------------------------------------------------ compiles
_compile_lock = threading.Lock()
_compiles = 0          # guarded-by: _compile_lock
_listening = False     # guarded-by: _compile_lock


def _on_duration(event: str, duration: float, **_):
    global _compiles
    if event == BACKEND_COMPILE:
        with _compile_lock:
            _compiles += 1


def _listen_for_compiles() -> None:
    """Install the one process-wide compile listener (JAX keeps its
    listeners for the life of the process)."""
    global _listening
    with _compile_lock:
        if _listening:
            return
        _listening = True
    jax.monitoring.register_event_duration_secs_listener(_on_duration)


def process_compiles() -> int:
    with _compile_lock:
        return _compiles


# ---------------------------------------------------------------- spans
class _Span:
    """One open span; ``weight`` (default 1) is how many members the
    span stands for: a span of weight ``k`` counts ``k`` times, each
    with its full duration."""

    __slots__ = ("_tracer", "_name", "_ann", "_t0", "weight")

    def __init__(self, tracer: "Tracer", name: str, ids: dict):
        self._tracer = tracer
        self._name = name
        self._ann = annotation(name, **ids)
        self.weight = 1

    def __enter__(self) -> "_Span":
        if self._ann is not None:
            self._ann.__enter__()
        self._t0 = time.monotonic()
        return self

    def __exit__(self, *exc) -> bool:
        dt = time.monotonic() - self._t0
        if self._ann is not None:
            self._ann.__exit__(*exc)
        self._tracer._add_span(self._name, self.weight, dt * self.weight)
        return False


class Tracer:
    """Span, wait and count aggregates of one engine (module docstring).

    ``stats()["counts"]["compiles"]`` is the process-wide number of XLA
    compiles (``backend_compile`` events, which a load from the
    persistent compilation cache raises too), read from one listener
    installed once per process: every ``Tracer`` in the process reports
    the same total, its own engine's compiles and any other's."""

    def __init__(self):
        self._lock = threading.Lock()
        self._spans = {n: [0, 0.0] for n in SPANS}    # guarded-by: _lock
        self._waits = {n: [0, 0.0] for n in WAITS}    # guarded-by: _lock
        # compiles are read from the process-wide listener, not kept here
        counted = [n for n in COUNTS if n != "compiles"]
        self._counts = dict.fromkeys(counted, 0)  # guarded-by: _lock
        _listen_for_compiles()

    def span(self, name: str, **ids) -> _Span:
        """Context manager timing work that starts and ends on this
        thread, under the annotation ``vdms.<name>``."""
        return _Span(self, name, ids)

    def _add_span(self, name: str, k: int, seconds: float) -> None:
        with self._lock:
            agg = self._spans[name]
            agg[0] += k
            agg[1] += seconds

    def wait(self, name: str, seconds: float) -> None:
        """One wait of ``seconds`` that began on another thread."""
        with self._lock:
            agg = self._waits[name]
            agg[0] += 1
            agg[1] += seconds

    def count(self, name: str, k: int = 1) -> None:
        with self._lock:
            self._counts[name] += k

    def stats(self) -> dict:
        """``{"spans": {name: {"n", "s"}}, "waits": {name: {"n", "s"}},
        "counts": {name: k}}``, every name present."""
        with self._lock:
            spans = {n: {"n": a[0], "s": a[1]} for n, a in self._spans.items()}
            waits = {n: {"n": a[0], "s": a[1]} for n, a in self._waits.items()}
            counts = dict(self._counts)
        counts["compiles"] = process_compiles()
        return {"spans": spans, "waits": waits, "counts": counts}


class TimedQueue(queue.Queue):
    """A ``queue.Queue`` that records, as the wait ``name``, how long
    each item sat in it: stamped when put, recorded when taken (by
    ``get`` with or without a timeout, or ``get_nowait``).  Items are
    stored as they are given."""

    def __init__(self, tracer: Tracer, name: str, maxsize: int = 0):
        self.tracer = tracer
        self.wait_name = name
        self._stamps = collections.deque()  # guarded-by: mutex
        super().__init__(maxsize)

    def _put(self, item) -> None:
        super()._put(item)
        # analysis: ok(guarded-by) — Queue.put calls _put under self.mutex
        self._stamps.append(time.monotonic())

    def _get(self):
        # analysis: ok(guarded-by) — Queue.get calls _get under self.mutex
        t = self._stamps.popleft()
        self.tracer.wait(self.wait_name, time.monotonic() - t)
        return super()._get()
