"""Fault-tolerance runtime: preemption, elastic re-mesh, stragglers.

At 1000+ nodes the mean time between node failures is shorter than a long
training run, so the framework assumes failure is routine:

**Preemption / crash** — `PreemptionGuard` installs SIGTERM/SIGINT handlers
(cloud preemption notices) that request a final synchronous checkpoint at
the next step boundary; combined with checkpoint/checkpoint.py's atomic
saves, the job loses at most one step plus the async-save lag.

**Elastic re-mesh** — `replan_mesh(n_devices)` picks the largest valid
(data, model) factorization for the surviving device count; the checkpoint
restores with the *new* shardings (see Checkpointer.restore), so training
continues at reduced width instead of waiting for repair.  Batch size is
held constant by rescaling grad_accum (same global batch, more
microbatches per device).

**Stragglers** — a `StragglerMonitor` tracks per-step wall times; steps
slower than `threshold × median` are logged with the step payload so the
scheduler can blocklist the slow host. In synchronous SPMD the mitigation
is re-mesh without the slow host (same path as failure) — plus the
data-loader prefetch (data/pipeline.py) and async checkpointing already
remove the two most common self-inflicted stalls.
"""
from __future__ import annotations

import logging
import signal
import statistics
import time
from typing import Optional

log = logging.getLogger("repro.runtime")


class PreemptionGuard:
    """SIGTERM/SIGINT → request checkpoint-and-exit at next step boundary."""

    def __init__(self):
        self.requested = False
        self._orig = {}
        for sig in (signal.SIGTERM, signal.SIGINT):
            try:
                self._orig[sig] = signal.signal(sig, self._handler)
            except ValueError:           # non-main thread (tests)
                pass

    def _handler(self, signum, frame):
        log.warning("preemption signal %s: checkpoint at next boundary", signum)
        self.requested = True

    def restore_handlers(self):
        for sig, h in self._orig.items():
            signal.signal(sig, h)


def replan_mesh(n_devices: int, *, prefer_model: int = 16):
    """Largest (data, model) grid for the surviving device count.

    Keeps the model axis at `prefer_model` when divisible (parameter shards
    stay valid), otherwise falls back to the largest power-of-two divisor —
    the elastic-scaling policy after losing hosts."""
    import jax
    from jax.sharding import AxisType

    model = prefer_model
    while model > 1 and n_devices % model:
        model //= 2
    data = n_devices // model
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def rescale_grad_accum(cfg_accum: int, old_data: int, new_data: int) -> int:
    """Hold the global batch constant across a re-mesh: fewer data shards
    => proportionally more microbatches."""
    return max(1, int(round(cfg_accum * old_data / max(1, new_data))))


class StragglerMonitor:
    def __init__(self, threshold: float = 2.0, window: int = 50):
        self.threshold = threshold
        self.window = window
        self.times: list = []
        self.flagged: list = []

    def record(self, step: int, seconds: float) -> bool:
        """Returns True if this step was a straggler."""
        self.times.append(seconds)
        if len(self.times) > self.window:
            self.times.pop(0)
        if len(self.times) >= 10:
            med = statistics.median(self.times)
            if seconds > self.threshold * med:
                self.flagged.append((step, seconds, med))
                log.warning("straggler: step %d took %.2fs (median %.2fs)",
                            step, seconds, med)
                return True
        return False


class StepTimer:
    def __init__(self, monitor: Optional[StragglerMonitor] = None):
        self.monitor = monitor or StragglerMonitor()
        self._t0 = None
        self.step = 0

    def __enter__(self):
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter() - self._t0
        self.monitor.record(self.step, dt)
        self.step += 1
        return False
