"""Observability: span tracing, scoped metrics and plan explain.

The instrumentation substrate the execution engine records into
(core/materialize.py, core/lowering.py, storage/prefetch.py) and the
benchmarks/serving layers read from:

* `trace`   — nested timing spans with Chrome-trace/Perfetto export
  (``fm.trace(...)`` / ``fm.trace_export(path)``), also in the JAX
  profiler's trace while a profiler session is active;
* `metrics` — thread-safe scoped counters/gauges/histograms behind the
  ``exec_stats()`` compatibility view, plus ``fm.collect_stats()`` for
  per-request isolation;
* `explain` — the fused-plan pretty-printer behind ``fm.explain(x)``.

`metrics` is stdlib-only and `trace` needs only jax's profiler hook, with
a fallback (core imports this package at module load); `explain` imports
core lazily inside its functions.
"""
from . import explain, metrics, trace                       # noqa: F401
from .explain import explain as explain_outputs, explain_plan  # noqa: F401
from .metrics import REGISTRY, Scope                        # noqa: F401
from .trace import TRACER, SpanTracer, span                 # noqa: F401
