"""Shared plan-cache / epilogue instrumentation helpers.

Reused by test_lowering.py, test_epilogue.py and test_parity_fuzz.py: the
engine exposes raw counters (repro.core.materialize.exec_stats), and these
helpers turn them into delta assertions so tests state intent
("this block must MISS once then HIT twice, with one epilogue launch per
materialize") instead of poking at the counter dict.
"""
from __future__ import annotations

import contextlib
import dataclasses

import numpy as np

from repro.core import materialize as mz
from repro.core.matrix import DenseStore, FMMatrix


@dataclasses.dataclass
class CacheActivity:
    """Counter deltas observed across a ``cache_activity()`` block."""

    hits: int = 0
    misses: int = 0
    materialize_calls: int = 0
    epilogue_launches: int = 0
    epilogue_host_inputs: int = 0
    partition_steps: int = 0


@contextlib.contextmanager
def cache_activity():
    """Record plan-cache and epilogue counter deltas over a with-block."""
    before = mz.exec_stats()
    act = CacheActivity()
    try:
        yield act
    finally:
        after = mz.exec_stats()
        act.hits = after["plan_cache_hits"] - before["plan_cache_hits"]
        act.misses = after["plan_cache_misses"] - before["plan_cache_misses"]
        act.materialize_calls = (after["materialize_calls"]
                                 - before["materialize_calls"])
        act.epilogue_launches = (after["epilogue_launches"]
                                 - before["epilogue_launches"])
        act.epilogue_host_inputs = (after["epilogue_host_inputs"]
                                    - before["epilogue_host_inputs"])
        act.partition_steps = (after["partition_steps"]
                               - before["partition_steps"])


def assert_activity(act: CacheActivity, **expected):
    """Assert exact counter deltas, e.g. ``assert_activity(act, misses=1,
    hits=2, epilogue_launches=3)``.  Unmentioned counters are unchecked."""
    for name, want in expected.items():
        got = getattr(act, name)
        assert got == want, (
            f"{name}: expected {want}, got {got} (full activity: {act})")


# ---------------------------------------------------------------------------
# Deferred long-output routing
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class PassExecWatch:
    """What `watch_pass_execs` saw: every `_PassExec` built, and after each
    partition's ``route_outputs`` the member's pending queue length beside
    the window it was given."""

    members: list = dataclasses.field(default_factory=list)
    pending: list = dataclasses.field(default_factory=list)


def watch_pass_execs(monkeypatch) -> PassExecWatch:
    """Swap the executor's `_PassExec` for a subclass that records into the
    returned `PassExecWatch`."""
    seen = PassExecWatch()

    class Watched(mz._PassExec):
        def __init__(self, *args, **kw):
            super().__init__(*args, **kw)
            seen.members.append(self)

        def route_outputs(self, start, stop, outputs, window):
            super().route_outputs(start, stop, outputs, window)
            seen.pending.append((len(self.pending), window))

    monkeypatch.setattr(mz, "_PassExec", Watched)
    return seen


# ---------------------------------------------------------------------------
# Staging fault injection (multi-pass interruption tests)
# ---------------------------------------------------------------------------

class StagingFault(RuntimeError):
    """The simulated partition-staging failure raised by FlakyStore."""


class FlakyStore(DenseStore):
    """A host-tier DenseStore whose ``block()`` raises `StagingFault` after
    ``fail_after`` successful partition reads — simulates a disk/staging
    error mid-stream.  ``heal()`` turns the fault off so a retry of the
    same plan (same cache entry) can succeed."""

    def __init__(self, data: np.ndarray, fail_after: int):
        super().__init__(np.asarray(data))
        self.fail_after = int(fail_after)
        self.reads = 0
        self.failed = False

    def block(self, start: int, stop: int):
        if self.fail_after >= 0 and self.reads >= self.fail_after:
            self.failed = True
            raise StagingFault(
                f"injected staging failure after {self.reads} reads")
        self.reads += 1
        return super().block(start, stop)

    def heal(self):
        self.fail_after = -1


def flaky_matrix(arr: np.ndarray, fail_after: int):
    """A host-tier FMMatrix whose partition staging fails after
    ``fail_after`` block reads.  Returns ``(matrix, store)`` — call
    ``store.heal()`` to let a retry succeed."""
    arr = np.asarray(arr)
    store = FlakyStore(arr, fail_after)
    return FMMatrix(arr.shape, arr.dtype, store=store, name="flaky"), store


def assert_no_partial_results(*nodes):
    """After an interrupted execution, NO node of the plan may have been
    registered (a partially-registered sink would poison later cuts that
    reuse it as a source)."""
    for n in nodes:
        assert getattr(n, "cached_store", None) is None, (
            f"{n!r} was registered by an interrupted execution")
