"""Double-buffered async partition prefetcher (paper §III-F I/O overlap).

A background thread walks the long dimension, reads each source's
I/O-level partition from its store (a disk read for ``MmapStore``, a RAM
slice for host ``DenseStore``), makes it contiguous and ``device_put``s
it, then parks the staged partition in a bounded queue.  The consumer
(``materialize._execute_stream``) pops partition *i* and computes while
the thread is already staging partition *i+1* — disk I/O, host→device DMA
and compute overlap, which is the mechanism that lets the paper's
out-of-core execution track in-memory performance.

``depth`` bounds how far ahead the thread runs (default 2 = classic
double buffering), which also bounds staged memory to
``depth × partition_bytes`` — the memory-chunk discipline.

Staged device blocks are exclusively owned by the pipeline, so the
consumer may donate them to the fused step (buffer recycling) without a
defensive copy.
"""
from __future__ import annotations

import queue
import threading
import time
import weakref
from typing import Iterator, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..observability import metrics
from ..observability.trace import TRACER, name_os_thread

_DONE = object()

#: Hard ceiling on a negotiated prefetch depth: beyond this the queue only
#: adds staged-memory pressure (depth × partition bytes) without hiding any
#: more latency.
MAX_NEGOTIATED_DEPTH = 8


def negotiate_depth(n_members: int, partition_nbytes: int,
                    base: Optional[int] = None,
                    budget_bytes: Optional[int] = None) -> int:
    """Group-aware prefetch depth for a co-scheduled stream (ISSUE 8).

    A solo stream double-buffers (``base``, default the configured
    ``prefetch_depth``); a group of k member plans consumes each staged
    partition k times, so compute per partition is ~k× longer and the
    stager can usefully run further ahead — one extra slot per extra
    member, capped at `MAX_NEGOTIATED_DEPTH` and (when ``budget_bytes``
    is given) at the number of partitions that fit the staging budget —
    the budget clamp may go below ``base``, but never below 1.
    """
    from . import registry
    if base is None:
        base = int(registry.get_conf("prefetch_depth"))
    depth = min(base + max(0, int(n_members) - 1), MAX_NEGOTIATED_DEPTH)
    if budget_bytes and partition_nbytes > 0:
        depth = min(depth, int(budget_bytes) // int(partition_nbytes))
    return max(1, depth)


def stage_block(mat, start: int, stop: int, *, donate: bool = True,
                to_device: bool = True, device=None, seq=None):
    """Read one I/O-level partition from ``mat`` and stage it for the fused
    step — the single definition of the staging rules, shared by the
    prefetch thread and the synchronous (prefetch-off) path:

    * slow-tier (numpy/memmap) blocks are made contiguous and
      ``device_put`` — the transfer itself is async, so the H2D copy
      overlaps downstream compute;
    * device-resident blocks are defensively copied when the consumer will
      donate them (donation must not consume the source buffer).

    Emits a ``stage`` span on whichever thread runs it (the prefetch
    worker's own track when pipelined), carrying the prefetcher's stream
    sequence id ``seq``, with each ``device_put`` in a nested ``stage_put``
    span.  Counters:

    * ``stage_seconds`` — the whole call, on any tier;
    * ``stage_bytes_read`` / ``stage_read_seconds`` — slow-tier blocks
      only (a device-resident block involves no tier read): the bytes, and
      the time from the slice until ``device_put`` has taken them.  For a
      contiguous host slice ``np.ascontiguousarray`` copies nothing, so
      the read itself (a memmap's page faults) and the host's layout of
      the block for the device both happen inside ``device_put``.  With
      ``to_device=False`` the time ends at the contiguous copy.

    ``device`` pins the staged block to one device of a mesh (the sharded
    partition loop stages each shard's rows onto that shard's device);
    ``None`` keeps the default uncommitted placement.
    """
    t0 = time.perf_counter()
    with TRACER.span("stage", start=int(start), stop=int(stop), seq=seq):
        blk = _stage(mat, start, stop, donate, to_device, device)
    metrics.inc("stage_seconds", time.perf_counter() - t0)
    return blk


def _device_put(x, device):
    """``jax.device_put`` inside a ``stage_put`` span."""
    with TRACER.span("stage_put"):
        return jax.device_put(x, device)


def _stage(mat, start: int, stop: int, donate: bool, to_device: bool,
           device):
    t0 = time.perf_counter()
    blk = mat.block(start, stop)
    if type(blk).__name__ == "SparseBlock":
        # Sparse (ELL) partition: a (cols, vals) pytree.  Same rules as the
        # dense branches, applied leaf-wise.
        from ..core.sparse import SparseBlock
        if isinstance(blk.vals, np.ndarray):
            cols = np.ascontiguousarray(blk.cols)
            vals = np.ascontiguousarray(blk.vals)
            if to_device:
                cols, vals = _device_put((cols, vals), device)
            metrics.inc("stage_bytes_read", cols.nbytes + vals.nbytes)
            metrics.inc("stage_read_seconds", time.perf_counter() - t0)
            return SparseBlock(cols, vals, blk.ncol)
        if device is not None:
            return SparseBlock(*_device_put((blk.cols, blk.vals), device),
                               blk.ncol)
        if donate:
            return SparseBlock(jnp.copy(blk.cols), jnp.copy(blk.vals),
                               blk.ncol)
        return blk
    if isinstance(blk, np.ndarray):
        blk = np.ascontiguousarray(blk)
        if to_device:
            blk = _device_put(blk, device)
        metrics.inc("stage_bytes_read", blk.nbytes)
        metrics.inc("stage_read_seconds", time.perf_counter() - t0)
    elif device is not None:
        # Cross-device copy: commits to the shard's device and leaves the
        # resident source buffer untouched, so donation stays safe.
        blk = _device_put(blk, device)
    elif donate:
        blk = jnp.copy(blk)
    return blk


def _source_name(mat) -> str:
    """Best human-readable identity of a staged source, for error context:
    the matrix's registry name, its backing file path, or its type."""
    name = getattr(mat, "name", "")
    if name:
        return str(name)
    store = getattr(mat, "store", None)
    path = getattr(store, "path", None) or getattr(mat, "path", None)
    if path:
        return str(path)
    return type(store or mat).__name__


class PrefetchError(RuntimeError):
    """A staging-thread failure, re-raised on the consumer side."""


#: Every constructed prefetcher, weakly held — leak-audit introspection
#: (ISSUE 9): after a stream ends (normally or via a fault) no entry may
#: have a live worker thread or staged partitions still queued.
_LIVE: "weakref.WeakSet" = weakref.WeakSet()


def live_prefetchers() -> list:
    """Prefetchers whose worker thread is still running — must be empty
    between streams; a non-empty result is a shutdown leak."""
    return [p for p in list(_LIVE) if p.alive]


def staged_leaks() -> list:
    """Closed-or-dead prefetchers still holding staged partitions in their
    queue (device memory pinned past shutdown) — must be empty."""
    leaks = []
    for p in list(_LIVE):
        if not p.alive and p.queued:
            leaks.append(p)
    return leaks


class PartitionPrefetcher:
    """Iterate ``(start, stop, {node_id: staged_block})`` over partitions.

    sources: ``[(node_id, matrix)]`` where each matrix exposes
    ``block(start, stop)`` (FMMatrix or a bare MatrixStore).
    """

    def __init__(self, sources: Sequence[Tuple[int, object]],
                 partition_rows: int, long_dim: int, *, depth: int = 2,
                 donate: bool = True, stage_to_device: bool = True,
                 reuse: Optional[dict] = None, row_start: int = 0,
                 device=None, seq=None):
        if depth < 1:
            raise ValueError("prefetch depth must be >= 1")
        self.sources = list(sources)
        self.partition_rows = int(partition_rows)
        self.long_dim = int(long_dim)
        # Half-open row range [row_start, long_dim): a sharded partition
        # loop drives one prefetcher per device shard, each over its own
        # range, staged onto that shard's ``device``.
        self.row_start = int(row_start)
        self.device = device
        # The sequence id of the stream this prefetcher stages for: its
        # ``stage`` spans carry it.
        self.seq = seq
        self.donate = donate
        self.stage_to_device = stage_to_device
        # {node_id: staged block} for the FINAL partition: when the previous
        # pass ran the identical partition schedule, its last resident
        # partition is still on device — serve it instead of re-reading
        # (counted as ``prefetch_reuse_hits``; core/materialize owns the
        # residency bookkeeping and schedule-equality check).
        self.reuse = dict(reuse) if reuse else None
        self._q: queue.Queue = queue.Queue(maxsize=depth)
        self._stop = threading.Event()
        self._closed = False
        # Metrics scopes open on the CONSTRUCTING thread: the worker adopts
        # them so background staging is attributed to the fm.collect_stats()
        # request that spawned this pipeline.
        self._scopes = metrics.current_scopes()
        self._thread = threading.Thread(
            target=self._worker, name="fm-prefetch", daemon=True)
        _LIVE.add(self)
        self._thread.start()

    # -- staging thread --------------------------------------------------------
    def _worker(self):
        name_os_thread(self._thread.name)
        with metrics.use_scopes(self._scopes):
            try:
                start = self.row_start
                while start < self.long_dim and not self._stop.is_set():
                    stop = min(start + self.partition_rows, self.long_dim)
                    final = stop >= self.long_dim
                    blocks = {}
                    for nid, mat in self.sources:
                        if final and self.reuse and nid in self.reuse:
                            # Partition-reuse: the identical final partition
                            # is already staged from the previous pass.
                            blocks[nid] = self.reuse[nid]
                            metrics.inc("prefetch_reuse_hits")
                            continue
                        try:
                            blocks[nid] = stage_block(
                                mat, start, stop, donate=self.donate,
                                to_device=self.stage_to_device,
                                device=self.device, seq=self.seq)
                        except Exception as exc:
                            raise PrefetchError(
                                f"prefetch thread failed staging rows "
                                f"[{start}, {stop}) of source "
                                f"{_source_name(mat)!r}: {exc!r}") from exc
                    if not self._put((start, stop, blocks)):
                        return
                    start = stop
                self._put(_DONE)
            except Exception as exc:  # noqa: BLE001 - forwarded to consumer
                self._put(exc)

    def _put(self, item) -> bool:
        """Bounded put that aborts promptly when close() is requested."""
        while not self._stop.is_set():
            try:
                self._q.put(item, timeout=0.05)
                return True
            except queue.Full:
                continue
        return False

    # -- consumer side ---------------------------------------------------------
    def __iter__(self) -> Iterator[tuple]:
        while True:
            t0 = time.perf_counter()
            with TRACER.span("prefetch_wait"):
                item = self._q.get()
            # Time the compute thread spent blocked on the staging queue:
            # the numerator of prefetch_wait_frac (pipeline-fill included).
            metrics.inc("prefetch_wait_seconds", time.perf_counter() - t0)
            if item is _DONE:
                self._closed = True
                return
            if isinstance(item, PrefetchError):
                # Already carries partition + source context from _worker.
                self._closed = True
                raise item
            if isinstance(item, Exception):
                self._closed = True
                raise PrefetchError(f"prefetch thread failed: {item!r}") from item
            yield item

    def close(self):
        """Stop the staging thread and drop queued partitions.  Idempotent;
        safe to call mid-stream (early consumer exit) or after exhaustion.

        Drain and join must INTERLEAVE: a worker parked in ``_put`` on a
        full queue re-checks ``_stop`` only on its 50 ms timeout, so a
        single drain *before* the join races it — the worker could enqueue
        one more staged partition after the drain and leave device blocks
        pinned in the dead pipeline's queue (the ISSUE 9 shutdown leak).
        """
        self._stop.set()
        deadline = time.monotonic() + 10.0
        while True:
            self._drain()
            self._thread.join(timeout=0.05)
            if not self._thread.is_alive() or time.monotonic() > deadline:
                break
        self._drain()
        self._closed = True

    def _drain(self):
        try:
            while True:
                self._q.get_nowait()
        except queue.Empty:
            pass

    @property
    def alive(self) -> bool:
        return self._thread.is_alive()

    @property
    def queued(self) -> int:
        """Staged partitions currently parked in the queue (leak audit)."""
        return self._q.qsize()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False
