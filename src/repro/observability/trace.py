"""Span tracer: nested timing spans, in the JAX profiler's trace and in a
Chrome-trace/Perfetto export of its own.

The engine's execution pipeline emits spans

    plan                           the DAG cut, fusion plan, plan cache
    materialize → pass → stream → partition → {device_step, combine}
                                   between partitions: prefetch_wait, or
                                   stage when staging is synchronous;
                                   after the stream: epilogue
    stage → stage_put              the prefetcher's ``fm-prefetch`` thread
    fetch                          a device result copied to the host

on the thread that performs each piece of work, so the prefetcher's
background staging thread gets its OWN track and the stage/compute overlap
the paper's §III-F design promises is directly visible in the timeline.
``pass`` spans, and the ``stream`` span of a streamed pass, carry a
per-process sequence id (``seq``); the ``stage`` spans of the stream's
prefetcher carry the same id, so the spans of one pass can be joined
across threads.

One tracer, two sinks:

  * ``fm.trace()`` turns on the in-memory recording (``enabled``), on the
    host's ``time.perf_counter`` clock, exported as Chrome-trace JSON;
  * while a JAX profiler session is active (``jax.profiler.trace``), every
    span is also a profiler TraceMe named ``fm.<span>``: it lands in the
    session's ``.xplane.pb`` on the profiler's clock, beside the device's
    operations, on the line of the thread that did the work.  The ``fm.``
    prefix tells the engine's events from the library's.

Design constraints (this module is on the per-partition hot path):

  * **near-zero overhead when both sinks are off** — ``span()`` returns a
    shared no-op context manager after one attribute check and one call of
    the profiler's ``TraceMe.is_enabled`` (about 0.05 µs): no allocation,
    no lock, no clock read;
  * **thread-safe when enabled** — events append under one lock; each
    event carries its thread id, and thread names are recorded as
    Chrome-trace metadata so Perfetto labels the tracks;
  * **no device synchronization** — a span never blocks on a device
    value, so tracing leaves the pipeline's asynchronous dispatch as it
    is.  ``device_step``, ``combine`` and ``epilogue`` therefore time the
    host's dispatch of that work; the device's own time is in the device
    trace of the same profiler session.

Use through the R-like surface:

    with fm.trace():                    # enable + collect
        fm.materialize(...)
    fm.trace_export("run.trace.json")   # chrome://tracing / ui.perfetto.dev

or ``fm.trace(export="run.trace.json")`` to export on scope exit.  Under
``jax.profiler.trace(log_dir)`` the same spans appear in the profile that
TensorBoard's profile plugin or Perfetto (``perfetto_trace.json.gz``)
open.
"""
from __future__ import annotations

import contextlib
import itertools
import json
import threading
import time
from typing import Optional

try:  # the profiler's TraceMe, and whether a profiler session is active
    from jax._src.lib import _profiler as _xprof
    _TraceMe = _xprof.TraceMe
    profiler_active = _xprof.TraceMe.is_enabled
except (ImportError, AttributeError):  # no profiler: its sink stays off
    _TraceMe = None

    def profiler_active() -> bool:
        return False

#: Per-process sequence ids of ``pass`` and streamed ``stream`` spans.
next_seq = itertools.count(1).__next__


def name_os_thread(name: str) -> None:
    """Give the calling thread's OS thread ``name`` (Linux keeps 15
    bytes), the name the profiler gives the thread's host line; a no-op
    where the name cannot be set."""
    import ctypes
    try:
        prctl = ctypes.CDLL(None).prctl
    except (OSError, AttributeError):  # no C library, or not Linux
        return
    prctl.argtypes = [ctypes.c_int, ctypes.c_char_p, ctypes.c_ulong,
                      ctypes.c_ulong, ctypes.c_ulong]
    prctl.restype = ctypes.c_int
    prctl(15, name.encode()[:15], 0, 0, 0)  # PR_SET_NAME


class _NullSpan:
    """Shared do-nothing context manager: the disabled-tracer fast path."""

    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


class _Span:
    """A span of the in-memory recording, also a profiler TraceMe while a
    profiler session is active."""

    __slots__ = ("_tracer", "_name", "_args", "_t0", "_me")

    def __init__(self, tracer: "SpanTracer", name: str, args: dict):
        self._tracer = tracer
        self._name = name
        self._args = args
        self._me = None

    def __enter__(self):
        if profiler_active():
            self._me = _TraceMe("fm." + self._name, **self._args)
            self._me.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        if self._me is not None:
            self._me.__exit__(None, None, None)
        self._tracer.record(self._name, self._t0, t1, self._args)
        return False


class SpanTracer:
    """Collect timing spans; export as Chrome-trace JSON.

    One process-wide instance (`TRACER`) is shared by the whole engine;
    ``enabled`` gates collection.  Events survive ``stop()`` so a trace can
    be exported after the traced block exits; ``reset()`` clears them.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._events: list[dict] = []
        self._thread_names: dict[int, str] = {}
        self._epoch = time.perf_counter()
        self.enabled = False

    # -- recording ----------------------------------------------------------
    def span(self, name: str, **args):
        """Context manager timing one span into whichever sinks are on.
        Near-free when neither is."""
        if self.enabled:
            return _Span(self, name, args)
        if profiler_active():
            return _TraceMe("fm." + name, **args)
        return _NULL_SPAN

    def record(self, name: str, t_start: float, t_end: float,
               args: Optional[dict] = None):
        """Record a completed span from raw ``perf_counter`` timestamps
        into the in-memory recording only: a profiler TraceMe cannot be
        emitted after the fact, so the engine's call sites use ``span``."""
        if not self.enabled:
            return
        tid = threading.get_ident()
        ev = {
            "name": name,
            "ts": (t_start - self._epoch) * 1e6,   # µs, Chrome-trace unit
            "dur": max((t_end - t_start) * 1e6, 0.0),
            "tid": tid,
        }
        if args:
            ev["args"] = args
        with self._lock:
            if tid not in self._thread_names:
                self._thread_names[tid] = threading.current_thread().name
            self._events.append(ev)

    # -- lifecycle ----------------------------------------------------------
    def start(self):
        self.enabled = True

    def stop(self):
        self.enabled = False

    def reset(self):
        with self._lock:
            self._events.clear()
            self._thread_names.clear()
        self._epoch = time.perf_counter()

    @contextlib.contextmanager
    def recording(self, export: Optional[str] = None, *, reset: bool = True):
        """Enable tracing over a with-block (`fm.trace()`).  ``reset=True``
        starts from an empty buffer; ``export=`` writes the Chrome-trace
        JSON on exit."""
        if reset:
            self.reset()
        self.start()
        try:
            yield self
        finally:
            self.stop()
            if export is not None:
                self.export(export)

    # -- inspection / export -------------------------------------------------
    def events(self) -> list[dict]:
        """Snapshot of collected span events (ts/dur in µs, per-thread)."""
        with self._lock:
            return [dict(ev) for ev in self._events]

    def chrome_trace(self) -> dict:
        """The trace as a Chrome-trace JSON object: complete ('X') events
        plus thread-name metadata, loadable by chrome://tracing and
        ui.perfetto.dev."""
        with self._lock:
            events = [dict(ev) for ev in self._events]
            names = dict(self._thread_names)
        trace_events = [
            {"ph": "M", "name": "process_name", "pid": 0, "tid": 0,
             "args": {"name": "repro.fm engine"}},
        ]
        for tid, tname in sorted(names.items()):
            trace_events.append(
                {"ph": "M", "name": "thread_name", "pid": 0, "tid": tid,
                 "args": {"name": tname}})
        for ev in events:
            out = {"ph": "X", "cat": "fm", "pid": 0,
                   "name": ev["name"], "tid": ev["tid"],
                   "ts": round(ev["ts"], 3), "dur": round(ev["dur"], 3)}
            if "args" in ev:
                out["args"] = ev["args"]
            trace_events.append(out)
        return {"traceEvents": trace_events, "displayTimeUnit": "ms"}

    def export(self, path) -> str:
        """Write the Chrome-trace JSON to ``path``; returns the path."""
        with open(path, "w", encoding="utf-8") as fh:
            json.dump(self.chrome_trace(), fh)
            fh.write("\n")
        return str(path)


#: The process-wide tracer every engine layer records into.
TRACER = SpanTracer()


def span(name: str, **args):
    """Module-level shorthand: ``trace.span('pass', idx=0)``."""
    return TRACER.span(name, **args)
