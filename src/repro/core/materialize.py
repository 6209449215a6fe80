"""Materialization engine (paper §III-F).

Executes a fused `fusion.Plan` in one of three modes:

* ``whole``  — the entire long dimension in one fused XLA computation.  The
  default for in-memory (device-resident) matrices; XLA performs the
  CPU-cache/VMEM-level fusion that the paper implements by hand, and an
  optional device mesh shards the long dimension for data-parallel
  execution (partition-per-device ≙ the paper's partition-per-thread, with
  `psum`-style combines materializing the sinks).
* ``stream`` — explicit I/O-level partition loop on device: the 2-level-
  partitioning demonstrator and the building block of out-of-core.
* ``ooc``    — sources live on a slow tier: host RAM (numpy) or the real
  disk tier (`storage.MmapStore` over the on-disk matrix format).
  Partitions are staged by a double-buffered background prefetcher
  (`storage.PartitionPrefetcher`): the disk read + host→device copy of
  partition i+1 overlaps the compute of partition i (the paper's
  I/O/compute overlap).  The fused step consumes staged blocks with buffer
  donation (the paper's memory-chunk recycling), and long-dimension
  outputs copy to the host asynchronously and write through, as many
  partitions behind the step as the prefetcher runs ahead of it, to
  preallocated host buffers or — with ``save='disk'`` — into a
  preallocated on-disk matrix (spill).

Sinks accumulate partition partials and merge with the aggregation VUDF's
``combine`` — identical in all three modes, which is exactly why the paper's
out-of-core execution can match in-memory performance once arithmetic
intensity is high enough.
"""
from __future__ import annotations

import collections
import contextlib
import threading
import time
import warnings
from collections import OrderedDict
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

# Buffer donation is the memory-chunk-recycling analog (DESIGN.md §1); when a
# donated block has no same-shaped output XLA declines it — harmless, and on
# CPU (this container) donation is advisory anyway.
warnings.filterwarnings(
    "ignore", message="Some donated buffers were not usable")

from . import dtypes, lowering
from .dag import LeafNode, Node, as_node, wrap
from .fusion import Plan
from .matrix import DenseStore, FMMatrix
from ..observability import metrics
from ..observability.trace import TRACER, next_seq


# Compiled-plan cache: structurally identical DAG cuts (k-means iteration
# N+1, GMM E-steps, any steady-state loop) reuse one jitted executable —
# the compile-once/stream-many behavior a production engine needs.  Keyed
# by Plan.signature() plus the mesh's structural identity (axis names +
# shape; NOT id(mesh), which a garbage collector can reissue to a
# different mesh), with LRU eviction at PLAN_CACHE_LIMIT.
_PLANS: "OrderedDict" = OrderedDict()
PLAN_CACHE_LIMIT = 256

# Thread-safety (ISSUE 8 audit) — two locks with distinct jobs:
#
# _PLANS_LOCK guards the cache OrderedDict itself (get / LRU move_to_end /
# insert / evict).  Eviction racing a borrow is safe WITHOUT further
# locking because eviction only drops the cache's reference: a borrower
# holds a strong reference to the template Plan for the whole execution,
# and template nodes are never mutated by executions (results land on the
# requesting plan's own nodes via _store_results(onto=...)).
#
# _DAG_LOCK serializes the two operations that touch LIVE DAG node
# metadata (cached_store / save): plan construction (which classifies
# nodes by that state) and result registration.  Concurrent requests may
# share upstream nodes (fm.serve, threads over one traced graph), so a
# registration must never interleave with another thread's classification
# pass.  Both are cheap relative to execution; execution itself runs
# outside the lock.
_PLANS_LOCK = threading.Lock()
_DAG_LOCK = threading.RLock()

# Execution counters — the observable evidence the benchmarks and tests
# assert on (one fused pass, one epilogue launch, compile-once/stream-many).
# ``epilogue_host_inputs`` counts host (numpy/memmap) buffers that reached
# the epilogue callable: it must stay 0 — merged sinks land on device even
# when the sources are disk-backed.  ``passes`` counts streaming passes
# executed (a two-pass ``scale(X)`` plan adds 2 per materialize); the
# per-pass bytes of the MOST RECENT execution are surfaced as
# ``pass_bytes_in`` so multi-pass I/O is observable.
#
# The counters live in the observability metrics registry (root scope plus
# any ``fm.collect_stats()`` scopes open on the calling thread); this list
# names the compatibility subset ``exec_stats()`` exposes as ints.
#
# ``streams`` counts physical partition sweeps over the sources: for a solo
# materialize it equals ``passes``, but a batched execution (core/batch.py)
# drives ONE stream per co-scheduled group while counting every member's
# logical pass — k plans × 1 stream shows up as passes=k, streams=1.
# ``prefetch_reuse_hits`` counts staged partition blocks served from the
# previous pass's resident final partition instead of a re-read.
#
# ``shards`` counts per-device shard drives under a mesh (ISSUE 9): a
# sharded sweep adds one per non-empty shard range (= the mesh's data-axis
# size whenever the matrix has at least one partition per shard); a whole-
# mode mesh run adds the data-axis size its inputs actually sharded over.
# ``shard_merges`` counts cross-device sink merges through the associative
# ``combine`` path — exactly one per shard boundary (shards − 1 per pass
# with sinks); ``bytes_in`` stays the UNION of rows read (each row is
# staged by exactly one shard), with the per-shard split observable as the
# ``shard_bytes_in`` tuple, and the device ids each shard's partials
# accumulated on as ``shard_devices``.  ``kernel_calls.<kernel>`` counts the
# launches of each Pallas kernel unit (one per unit per partition step).
EXEC_COUNTERS = (
    "materialize_calls",
    "plan_cache_hits",
    "plan_cache_misses",
    "partition_steps",
    "passes",
    "streams",
    "shards",
    "shard_merges",
    "midstream_admits",
    "prefetch_reuse_hits",
    "epilogue_launches",
    "epilogue_host_inputs",
)


def exec_stats() -> dict:
    """Snapshot of the engine's execution counters (see EXEC_COUNTERS), plus
    ``pass_bytes_in``: the per-pass streamed bytes of the last execution.

    A compatibility view over the root metrics scope; the full instrument
    set (timings, bandwidth, queue occupancy, derived rates) is
    ``observability.metrics.stats()`` or a ``fm.collect_stats()`` scope."""
    st = {k: int(metrics.root_counter(k)) for k in EXEC_COUNTERS}
    st["pass_bytes_in"] = tuple(metrics.root_value("pass_bytes_in", ()))
    st["shard_bytes_in"] = tuple(metrics.root_value("shard_bytes_in", ()))
    st["shard_devices"] = tuple(metrics.root_value("shard_devices", ()))
    return st


def reset_exec_stats():
    metrics.REGISTRY.reset()


def clear_plan_cache():
    with _PLANS_LOCK:
        _PLANS.clear()


def _mesh_key(mesh):
    if mesh is None:
        return None
    return (tuple(mesh.axis_names), tuple(np.shape(mesh.devices)))


def _default_mesh(mesh):
    """Resolve the execution mesh: an explicit ``mesh=`` argument wins,
    else the configured default (``fm.set_conf(mesh=...)``), else None
    (unsharded)."""
    if mesh is not None:
        return mesh
    from ..storage import registry  # deferred: storage depends on core
    return registry.get_conf("mesh")


def materialize(*mats: FMMatrix, mode: str = "auto", fuse: bool = True,
                mesh=None, donate: bool = True, reuse_plans: bool = True,
                prefetch: Optional[bool] = None,
                backend: Optional[str] = None) -> list[FMMatrix]:
    """fm.materialize: force computation of virtual matrices.

    Returns one *physical* FMMatrix per argument (physical args pass
    through).  Multiple arguments materialize together in one fused pass
    over the data (paper: "FlashMatrix can materialize any virtual matrix in
    a DAG and can materialize multiple virtual matrices together").

    ``prefetch`` controls the async partition pipeline in streaming modes:
    None = the storage config default (on for slow-tier sources), False =
    synchronous staging (the ablation the storage benchmark measures).

    ``backend`` picks the lowering backend ('xla' | 'pallas' | 'auto');
    None = the engine default (fm.set_conf(backend=...), 'auto' initially:
    pallas on TPU, xla elsewhere).  See core/lowering.py.
    """
    virtuals = [m for m in mats if m.is_virtual]
    if not virtuals:
        return list(mats)

    metrics.inc("materialize_calls")
    backend = lowering.resolve_backend(backend)
    mesh = _default_mesh(mesh)

    if not fuse:
        with TRACER.span("materialize", backend=backend, fuse=False,
                         outputs=len(virtuals)):
            _materialize_eager([m.node for m in virtuals], mode=mode,
                               backend=backend)
        return [_result_of(m) for m in mats]

    plan, exec_plan = _build_plan(virtuals, backend, mesh, reuse_plans)

    # A cached plan's nodes belong to the FIRST caller's live DAG.  The
    # execution reads schedule/program state from the (possibly borrowed)
    # template but registers results onto THIS call's own nodes
    # (_store_results onto= — the same borrow discipline as fm.batch), so
    # the template is never mutated: its persisted results survive, a
    # retry after a failed execution sees clean state, and concurrent
    # materializes of structurally identical plans (fm.serve workers) can
    # share one cache entry safely.
    with TRACER.span("materialize", backend=backend,
                     passes=plan.n_passes, outputs=len(virtuals),
                     cached=exec_plan is not plan):
        _execute(exec_plan, onto=plan, mode=mode, mesh=mesh, donate=donate,
                 sources=[m for _, m in plan.sources],
                 bc_sources=[m for _, m in plan.broadcast_sources],
                 epi_sources=[m for _, m in plan.epilogue_sources],
                 smalls=plan.small_values(), prefetch=prefetch,
                 backend=backend)
    return [_result_of(m) for m in mats]


def _result_of(m: FMMatrix) -> FMMatrix:
    if not m.is_virtual:
        return m
    store = getattr(m.node, "cached_store", None)
    assert store is not None, f"{m.node} failed to materialize"
    return store


def _build_plan(virtuals, backend: str, mesh, reuse_plans: bool):
    """(``Plan(virtuals)``, its executable plan from `_acquire_exec_plan`),
    shared by ``materialize`` and the batch executor: built under
    ``_DAG_LOCK`` (plan construction classifies live DAG node state), in a
    ``plan`` span, timed into ``plan_seconds``."""
    t0 = time.perf_counter()
    with TRACER.span("plan", outputs=len(virtuals)), _DAG_LOCK:
        plan = Plan(virtuals)
        exec_plan = _acquire_exec_plan(plan, backend, mesh, reuse_plans)
    metrics.inc("plan_seconds", time.perf_counter() - t0)
    return plan, exec_plan


def _acquire_exec_plan(plan: Plan, backend: str, mesh, reuse_plans: bool):
    """Plan-cache lookup shared by ``materialize`` and the batch executor.

    Both partition levels OF EVERY PASS and the backend are part of the
    key: the I/O partition size reads IO_PARTITION_BYTES at plan build and
    the IR's block-row schedule reads VMEM_PARTITION_BYTES, so a
    fm.set_conf change — or a backend switch — must miss the cache rather
    than reuse an executable built for different tiling.  (plan.signature()
    itself embeds the pass structure: node roles carry pass numbers, so
    one-pass and two-pass cuts never collide.)

    Thread-safe: lookup, LRU touch and eviction happen under _PLANS_LOCK
    (see the lock's comment for why eviction racing a borrow is benign).
    """
    if not reuse_plans:
        return plan
    sig = (plan.signature(), plan.pass_key(), backend, _mesh_key(mesh))
    with _PLANS_LOCK:
        cached = _PLANS.get(sig)
        if cached is not None:
            metrics.inc("plan_cache_hits")
            _PLANS.move_to_end(sig)  # LRU touch
            return cached
        metrics.inc("plan_cache_misses")
        _PLANS[sig] = plan
        while len(_PLANS) > PLAN_CACHE_LIMIT:
            _PLANS.popitem(last=False)  # evict least-recently-used
        return plan


# ---------------------------------------------------------------------------
# Iteration inspector: cross-materialize partition residency
# ---------------------------------------------------------------------------

_INSPECT = threading.local()


def inspecting() -> bool:
    """True while an ``iteration_scope`` is open on this thread."""
    return getattr(_INSPECT, "depth", 0) > 0


@contextlib.contextmanager
def iteration_scope():
    """fm.inspect_iterations: declare an iterative driver's loop.

    Inside the scope the executor keeps the LAST staged partition of every
    streaming pass resident across materialize calls, so iteration i+1's
    first pass — whose partition schedule matches iteration i's last pass —
    reuses the already-staged final partition instead of re-reading it
    (``prefetch_reuse_hits``).  The iterative drivers (kmeans / glm IRLS /
    nmf / gmm) open this around their loops; on exit the resident blocks
    are dropped so no device memory outlives the loop.
    """
    _INSPECT.depth = getattr(_INSPECT, "depth", 0) + 1
    try:
        yield
    finally:
        _INSPECT.depth -= 1
        if _INSPECT.depth == 0:
            _INSPECT.residents = None


def _tls_residents():
    return getattr(_INSPECT, "residents", None) if inspecting() else None


def _set_tls_residents(residents):
    if inspecting():
        _INSPECT.residents = residents


class _Resident:
    """The final staged partition of a streaming pass, kept alive so a
    following pass with the SAME partition schedule (rows, long_dim — hence
    the same final row range) can consume it without re-staging.  Blocks
    are keyed by physical-matrix identity; ``mats`` holds strong references
    so an ``id()`` can't be reissued while the entry is live."""

    __slots__ = ("rows", "long_dim", "blocks", "mats")

    def __init__(self, rows: int, long_dim: int, blocks: dict, mats: list):
        self.rows = rows
        self.long_dim = long_dim
        self.blocks = blocks  # {id(mat): staged device block}
        self.mats = mats

    def matches(self, rows: int, long_dim: int) -> bool:
        return self.rows == rows and self.long_dim == long_dim


def _reuse_from(residents, group_pairs, rows: int, long_dim: int):
    """Reusable final-partition blocks for a pass streaming ``group_pairs``
    ([(group_key, mat)]) at ``rows``: {group_key: block} for every source
    whose block is resident under an identical partition schedule.
    Per-source, so a pass that re-streams X alongside a NEW matrix still
    reuses the X block."""
    out = {}
    for entry in residents or ():
        if not entry.matches(rows, long_dim):
            continue
        for key, mat in group_pairs:
            if key not in out and id(mat) in entry.blocks:
                out[key] = entry.blocks[id(mat)]
    return out or None


# ---------------------------------------------------------------------------
# Fused execution
# ---------------------------------------------------------------------------




class _PassExec:
    """Executor state of ONE member pass inside a stream group.

    The group runners (`_run_whole_group` / `_run_stream_group`) drive one
    partition sweep over the UNION of the members' staged sources; while a
    staged partition is resident every member's compiled ``step`` consumes
    it and folds its own sink partials through its own ``combine`` before
    the blocks are evicted — k plans × 1 stream becomes 1 stream × k steps
    (core/batch.py builds multi-member groups; a solo materialize is the
    one-member degenerate case).

    ``out_nodes`` pairs each long-dimension output's TEMPLATE node (the
    plan-cache entry's node, whose id keys the lowered step's outputs) with
    the node whose save flag / name / shape describe where the result goes
    — identical for a solo run, the requesting plan's own node for a batch
    member executing through a borrowed cached template.  ``scopes`` are
    the metrics scopes captured when the request joined the batch; the
    runners adopt them around this member's compute so per-request
    attribution reports the member's OWN share, not the group's.

    ``pending`` holds the partitions whose row-addressed long outputs (host
    buffer or disk store) are still on their way to the host: one
    ``(start, stop, [(nid, device_value), ...])`` per partition, oldest
    first (`route_outputs`, `drain`).
    """

    __slots__ = ("ps", "prog", "sources", "smalls", "epi_sources",
                 "bindings", "out_nodes", "scopes", "accs", "out_parts",
                 "host_bufs", "disk_stores", "finals", "epi_outs", "pending")

    def __init__(self, ps, prog, sources, smalls, epi_sources, bindings, *,
                 out_nodes=None, scopes=()):
        self.ps = ps
        self.prog = prog
        self.sources = sources
        self.smalls = smalls
        self.epi_sources = epi_sources
        self.bindings = bindings
        if out_nodes is None:
            outs = ps.row_local_roots + ps.saves
            out_nodes = list(zip(outs, outs))
        self.out_nodes = out_nodes
        self.scopes = tuple(scopes)
        self.accs = ps.init_accs()
        self.out_parts = {tmpl.id: [] for tmpl, _ in out_nodes}
        self.host_bufs: dict[int, np.ndarray] = {}
        self.disk_stores: dict[int, object] = {}
        self.finals = None
        self.epi_outs = None
        self.pending = collections.deque()

    def route_outputs(self, start: int, stop: int, outputs: dict,
                      window: int):
        """Route one partition step's long outputs.  Device-resident ones
        append to ``out_parts``.  A row-addressed one starts its copy to
        the host and waits in ``pending``; the partitions more than
        ``window`` steps old are then written to their targets.  The
        compute thread so never waits for the step it just dispatched."""
        queued = []
        for nid, val in outputs.items():
            if nid in self.disk_stores or nid in self.host_bufs:
                with TRACER.span("fetch_start"):
                    val.copy_to_host_async()
                queued.append((nid, val))
            else:
                self.out_parts[nid].append(val)
        if queued:
            metrics.inc("deferred_output_copies", len(queued))
            self.pending.append((start, stop, queued))
        self.drain(keep=window)

    def drain(self, keep: int = 0):
        """Write pending partitions' outputs to their targets, oldest
        first, until ``keep`` partitions are left pending."""
        while len(self.pending) > keep:
            self._write_rows(*self.pending.popleft())

    def _write_rows(self, start: int, stop: int, queued: list):
        t0 = time.perf_counter()
        for nid, val in queued:
            with TRACER.span("fetch"):
                rows = np.asarray(val)
            if nid in self.disk_stores:
                self.disk_stores[nid].write_rows(start, rows)
            else:
                self.host_bufs[nid][start:stop] = rows
        metrics.inc("output_drain_seconds", time.perf_counter() - t0)


def _output_window(prefetch: bool, depth: Optional[int]) -> int:
    """How many partitions' long outputs may wait for their host copies:
    as many as the stream's staged inputs lead the step, the configured
    prefetch depth where no prefetcher runs."""
    from .. import storage  # deferred: storage depends on core.matrix
    return depth if prefetch else storage.get_conf("prefetch_depth")


@contextlib.contextmanager
def _draining(members):
    """Around a partition loop: when it completes, write out every
    member's pending long outputs; whether or not it does, drop what is
    left, so a failed sweep, whose results never register, holds no
    device values."""
    try:
        yield
        for m in members:
            m.drain()
    finally:
        for m in members:
            m.pending.clear()


def _member_stack(member: _PassExec):
    """The metrics-scope stack to adopt around this member's compute: the
    executor thread's open scopes plus the scopes captured at request time
    (deduped).  None when nothing extra is captured — record normally."""
    if not member.scopes:
        return None
    cur = metrics.current_scopes()
    extra = [s for s in member.scopes if s not in set(cur)]
    return tuple(cur) + tuple(extra) if extra else None


def _in_stack(stack):
    return metrics.use_scopes(stack) if stack else contextlib.nullcontext()


def _group_staging(members):
    """Union staging plan of a group: one ``(key, mat)`` per distinct
    physical matrix across every member (key = the matrix's identity), and
    per member the canonical-node-id → key map that fans a staged block
    back out to its compiled step."""
    group_pairs: list[tuple[int, object]] = []
    seen: set[int] = set()
    maps: list[dict[int, int]] = []
    for m in members:
        mp = {}
        for nid, mat in m.ps.staged_sources(m.sources):
            if id(mat) not in seen:
                seen.add(id(mat))
                group_pairs.append((id(mat), mat))
            mp[nid] = id(mat)
        maps.append(mp)
    return group_pairs, maps


def _count_member_scopes(member, ambient, stream_scopes: list):
    """One member's request-scope share of a stream: its own plan's pass +
    bytes (what a solo run of that request would have read), recorded on
    every captured scope that is not already ambient on the executor."""
    own = None
    for sc in member.scopes:
        if sc in ambient:
            continue
        if own is None:
            own = member.ps.bytes_in(member.sources)
        sc.inc("passes", 1)
        sc.inc("bytes_streamed", own)
        if sc not in stream_scopes:
            stream_scopes.append(sc)


def _count_stream(members, union_bytes: int):
    """Stream accounting.  Root + the executor's ambient scopes record the
    PHYSICAL sweep — one stream, union bytes read once, one logical pass
    per member (so a batched group shows passes=k, streams=1).  Each
    member's request scopes additionally record the stream and their OWN
    plan's byte share: `fm.collect_stats()` around one request of a batch
    reports that plan's traffic, not the whole group's."""
    metrics.inc("streams")
    metrics.inc("bytes_streamed", union_bytes)
    metrics.inc("passes", len(members))
    ambient = set(metrics.REGISTRY.scopes())
    stream_scopes: list = []
    for m in members:
        _count_member_scopes(m, ambient, stream_scopes)
    for sc in stream_scopes:
        sc.inc("streams", 1)


def _count_admitted(member):
    """Accounting for a mid-stream-admitted member (ISSUE 8): its logical
    pass joins the CURRENT physical sweep — root passes +1 but streams
    unchanged, since no new partition sweep starts.  Root bytes for the
    catch-up prefix are added as those partitions actually stage
    (`_catch_up`); the member's own request scopes see what a solo run
    would have reported (one stream, its full plan bytes)."""
    metrics.inc("passes")
    metrics.inc("midstream_admits")
    ambient = set(metrics.REGISTRY.scopes())
    stream_scopes: list = []
    _count_member_scopes(member, ambient, stream_scopes)
    for sc in stream_scopes:
        sc.inc("streams", 1)


def _member_step(member, blocks, key_map, start, stop, *, donate_blocks,
                 idx):
    """Run one member's step + combine over the staged partition."""
    step = member.prog.step_donated if donate_blocks else member.prog.step
    mblocks = {nid: blocks[key] for nid, key in key_map.items()}
    metrics.inc("partition_steps")
    # Kernel dispatch evidence: one launch of each kernel unit per step.
    for unit in member.prog.kernel_units:
        metrics.inc("kernel_calls." + unit.kernel)
    t0 = time.perf_counter()
    with TRACER.span("device_step", rows=stop - start, member=idx):
        partials, outputs = step(mblocks, member.smalls, member.bindings,
                                 jnp.asarray(start, jnp.int32))
    metrics.inc("device_step_seconds", time.perf_counter() - t0)
    # The paper's partial-merge: each partition's sink partials fold into
    # the member's running accumulators with the aggregation VUDFs'
    # ``combine`` (donated: the old acc buffers recycle in place).
    t0 = time.perf_counter()
    with TRACER.span("combine", member=idx):
        member.accs = member.prog.combine(member.accs, partials)
    metrics.inc("combine_seconds", time.perf_counter() - t0)
    return outputs


def _replicate(tree, mesh):
    """Commit every jax leaf of ``tree`` replicated across ``mesh`` (empty
    PartitionSpec): merged sink values, epilogue inputs and bindings are
    held by EVERY device, so the epilogue runs replicated and the next
    pass's shard executors find their broadcast values wherever they run."""
    sh = NamedSharding(mesh, P())
    return jax.tree_util.tree_map(
        lambda x: jax.device_put(x, sh) if isinstance(x, jax.Array) else x,
        tree)


def _finish_members(members, stacks, mesh=None):
    """Finalize + epilogue for every member once the sweep completes.
    Under a mesh the merged accumulators are replicated first (the
    cross-device reduction already happened — `_run_sharded_stream`'s
    shard merges, or GSPMD's all-reduce in whole mode), so finalize and
    the epilogue execute replicated on every device."""
    for m, stack in zip(members, stacks):
        with _in_stack(stack):
            if mesh is not None:
                m.accs = _replicate(m.accs, mesh)
            m.finals = m.ps.finalize_accs(m.accs)
            m.epi_outs = _run_epilogue(m.ps, m.prog, m.finals,
                                       m.epi_sources, m.smalls, m.bindings,
                                       mesh=mesh)
        for nid, buf in m.host_bufs.items():
            m.out_parts[nid] = [buf]
        for st in m.disk_stores.values():
            st.flush()


def _run_whole_group(members, mesh=None):
    """Whole-mode sweep of a group: the union of the members' sources is
    staged once, then every member's step consumes it (offset 0, one
    partition).  Under a mesh, long-aligned inputs are committed sharded
    over the data axis (when the row count divides — `_long_spec`) so XLA
    runs the fused step SPMD with one logical shard per data slot."""
    group_pairs, maps = _group_staging(members)
    long_dim = members[0].ps.long_dim
    spec = n_shards = None
    if mesh is not None:
        spec, n_shards = _long_spec(mesh, long_dim)
        metrics.inc("shards", n_shards)
    blocks = {}
    for key, mat in group_pairs:
        if getattr(mat.store, "sparse", False):
            # Sparse source: stage the whole matrix as one ELL partition
            # (stage_block owns the leaf-wise device_put).  No sharded
            # commit — mesh parity for sparse runs through the sharded
            # stream path, which stages per-shard row ranges instead.
            from ..storage.prefetch import stage_block
            blocks[key] = stage_block(mat, 0, mat.shape[0], donate=False)
            continue
        data = mat.logical_data()
        arr = jnp.asarray(np.asarray(data)) if mat.on_host else data
        if mesh is not None and mat.shape[0] == long_dim:
            arr = jax.device_put(arr, NamedSharding(mesh, spec))
        blocks[key] = arr
    _count_stream(members, sum(mat.nbytes() for _, mat in group_pairs))
    stacks = [_member_stack(m) for m in members]
    with TRACER.span("stream", members=len(members), mode="whole"):
        with TRACER.span("partition", start=0, stop=long_dim):
            for i, (m, mp, stack) in enumerate(zip(members, maps, stacks)):
                with _in_stack(stack):
                    outputs = _member_step(m, blocks, mp, 0, long_dim,
                                           donate_blocks=False, idx=i)
                # Whole mode: every output is one full-height value; save
                # targets are applied later by _store_results.
                for nid, val in outputs.items():
                    m.out_parts[nid].append(val)
    _finish_members(members, stacks, mesh=mesh)
    return None


def _execute(plan: Plan, **kw):
    """`_execute_passes` plus the ISSUE 9 concurrency fix: a failure mid-
    plan (a staging error, an interrupted stream) clears the thread's
    resident-partition capture.  The residents in TLS belong to the
    PREVIOUS materialize's final partition; after a partial run they no
    longer correspond to any upcoming schedule, and leaving them pinned
    holds device memory for the rest of the iteration scope."""
    try:
        return _execute_passes(plan, **kw)
    except BaseException:
        _set_tls_residents(None)
        raise


def _execute_passes(plan: Plan, *, onto: Optional[Plan] = None,
                    mode: str = "auto",
                    mesh=None, donate: bool = True, sources=None, smalls=None,
                    prefetch: Optional[bool] = None,
                    backend: Optional[str] = None,
                    epi_sources=None, bc_sources=None):
    """Run every pass of ``plan`` in order, then register the results.

    ``onto`` is the equal-signature plan results belong to (the caller's
    own trace) when ``plan`` is a borrowed cached template; the template's
    schedules/programs drive execution, the out specs and registration
    target ``onto``'s nodes, and the template is never mutated.  Defaults
    to ``plan`` itself.

    A multi-pass plan (fusion.PassSchedule) carries each pass's finalized
    sinks + epilogue outputs forward as the next pass's ``bindings``
    (broadcast inputs of the compiled step) — the moment-pass → sweep-pass
    schedule executing under one plan-cache entry and one materialize
    call.  Results register only after EVERY pass succeeds, so an
    interrupted pass (a staging error mid-stream) leaves no
    partially-registered sinks behind.

    Streaming passes keep their FINAL staged partition resident whenever
    the next pass — of this plan, or of the next materialize inside an
    ``iteration_scope`` — runs an identical partition schedule over (some
    of) the same physical matrices: the re-drive then starts from the
    resident blocks instead of re-reading them (``prefetch_reuse_hits``).
    """
    own = onto if onto is not None else plan
    if sources is None:
        sources = [m for _, m in own.sources]
    if bc_sources is None:
        bc_sources = [m for _, m in own.broadcast_sources]
    if epi_sources is None:
        epi_sources = [m for _, m in own.epilogue_sources]
    if smalls is None:
        smalls = own.small_values()
    prog = plan.program(lowering.resolve_backend(backend))
    pass_progs = getattr(prog, "passes", None) or [prog]
    mode = _pick_mode_src(sources, mode)
    if mode not in ("whole", "stream", "ooc"):
        raise ValueError(f"unknown mode {mode!r}")

    carried: dict[int, object] = {}
    finals_all: dict[int, object] = {}
    parts_all: dict[int, list] = {}
    epi_all: dict[int, object] = {}
    disk_all: dict[int, object] = {}
    # Per-EXECUTION pass bytes, published atomically to the metrics scopes
    # once every pass has run — never a half-written module global an
    # interleaved materialize can clobber mid-plan.
    pass_bytes: list[int] = []
    residents = _tls_residents()
    src_i = bc_i = epi_i = 0
    for k, (ps, pprog) in enumerate(zip(plan.passes, pass_progs)):
        ns, nb, ne = (len(ps.sources), len(ps.broadcast_sources),
                      len(ps.epilogue_sources))
        ps_src = sources[src_i:src_i + ns]
        ps_bc = bc_sources[bc_i:bc_i + nb]
        ps_epi = epi_sources[epi_i:epi_i + ne]
        src_i, bc_i, epi_i = src_i + ns, bc_i + nb, epi_i + ne
        # Pass bindings: earlier passes' merged values, plus this pass's
        # whole-staged small physical sources.
        bindings = {nid: carried[nid] for nid in ps.binding_ids}
        for nid, mat in ps.broadcast_source_pairs(ps_bc):
            bindings[nid] = _stage_whole(mat)
        out_nodes = None
        if own is not plan:
            own_ps = own.passes[k]
            out_nodes = list(zip(ps.row_local_roots + ps.saves,
                                 own_ps.row_local_roots + own_ps.saves))
        member = _PassExec(ps, pprog, ps_src, smalls, ps_epi, bindings,
                           out_nodes=out_nodes)
        t_pass = time.perf_counter()
        seq = next_seq()
        with TRACER.span("pass", idx=ps.idx, mode=mode,
                         partition_rows=ps.partition_rows, seq=seq):
            if mode == "whole":
                _run_whole_group([member], mesh=mesh)
                residents = None
            else:
                # Keep the final staged partition resident when the next
                # streaming pass (this plan's, or — inside an
                # iteration_scope — the next materialize's first) could
                # consume it: same partition rows, shared physical matrix.
                capture = inspecting()
                nxt = plan.passes[k + 1] if k + 1 < len(plan.passes) else None
                if (not capture and nxt is not None
                        and nxt.partition_rows == ps.partition_rows):
                    cur_ids = {id(mat)
                               for _, mat in ps.staged_sources(ps_src)}
                    nxt_src = sources[src_i:src_i + len(nxt.sources)]
                    capture = any(
                        id(mat) in cur_ids
                        for _, mat in nxt.staged_sources(nxt_src))
                entry = _run_stream_group(
                    [member], to_host=(mode == "ooc"), donate=donate,
                    prefetch=prefetch, residents=residents, capture=capture,
                    mesh=mesh, seq=seq)
                residents = [entry] if entry is not None else None
                disk_all.update(member.disk_stores)
        metrics.inc("pass_seconds", time.perf_counter() - t_pass)
        pass_bytes.append(ps.bytes_in(ps_src))
        finals_all.update(member.finals)
        parts_all.update(member.out_parts)
        epi_all.update(member.epi_outs)
        carried.update(member.finals)
        carried.update(member.epi_outs)
    _set_tls_residents(residents)
    metrics.put("pass_bytes_in", tuple(pass_bytes))
    _store_results(plan, finals_all, parts_all, to_host=(mode == "ooc"),
                   disk_stores=disk_all, epilogue_outs=epi_all, onto=own)
    return plan


def _pick_mode_src(sources, mode: str) -> str:
    if mode != "auto":
        return mode
    if any(mat.on_host for mat in sources):
        return "ooc"
    return "whole"


def _stage_whole(mat) -> "jax.Array":
    """Stage a small matrix whole onto the device (broadcast/epilogue
    sources, pass bindings must never leak host buffers into jit)."""
    data = mat.logical_data()
    return jnp.asarray(np.asarray(data)) if mat.on_host else data


def _run_epilogue(ps, prog, sink_finals, epi_sources, smalls, bindings,
                  mesh=None):
    """Invoke the lowered epilogue exactly ONCE after a pass's merge.

    Inputs are the finalized sink values (device arrays out of the jitted
    ``combine``) plus any small physical matrices only the epilogue
    consumes, staged with ``jnp.asarray`` so a disk-backed plan never leaks
    ``np.memmap``/numpy buffers into the compiled callable — the
    ``epilogue_host_inputs`` counter records any violation.

    Under a mesh the epilogue runs REPLICATED: its committed inputs (the
    finalized sinks — already replicated by `_finish_members` — plus the
    epilogue sources and earlier-pass bindings, replicated here) all live
    on every mesh device, so one jit call executes the identical epilogue
    per device with no cross-device traffic.
    """
    if prog.epilogue is None:
        return {}
    epi_vals = {}
    for nid, mat in ps.epilogue_source_pairs(epi_sources):
        epi_vals[nid] = _stage_whole(mat)
    if mesh is not None:
        epi_vals = _replicate(epi_vals, mesh)
        bindings = _replicate(bindings, mesh)
    leaves = jax.tree_util.tree_leaves((sink_finals, epi_vals))
    metrics.inc("epilogue_host_inputs", sum(
        1 for leaf in leaves if isinstance(leaf, np.ndarray)))
    metrics.inc("epilogue_launches")
    t0 = time.perf_counter()
    with TRACER.span("epilogue", idx=ps.idx):
        outs = prog.epilogue(sink_finals, epi_vals, smalls, bindings)
    metrics.inc("epilogue_seconds", time.perf_counter() - t0)
    return outs


def _long_spec(mesh, long_dim: int):
    """(PartitionSpec, shard count) for a whole-mode long-aligned input:
    the row dimension shards across the data tier when it divides evenly
    (``distributed.sharding.resolve``'s divisibility check — the ``rows``
    rule), otherwise replicates with shard count 1.  Model-like axes
    always replicate — GenOps are row-parallel."""
    from ..distributed import sharding as shd
    spec = shd.resolve("rows|rep", (long_dim, 1), mesh)
    n_shards = shd.data_axis_size(mesh) if spec[0] is not None else 1
    return P(spec[0], None), n_shards


def _inline_partitions(src_pairs, rows: int, n: int, donate: bool,
                       reuse=None, row_start: int = 0, device=None):
    """Synchronous partition staging (prefetch-off ablation): same staging
    rules as the prefetch thread (storage.stage_block), but the disk read
    happens on the compute thread; only device_put dispatch overlaps.
    ``reuse`` maps source keys to the previous pass's resident FINAL
    partition blocks — served in place of the last re-read.  ``row_start``
    and ``device`` mirror the prefetcher's shard parameters: one shard's
    half-open range, staged onto that shard's device."""
    from ..storage.prefetch import stage_block
    start = row_start
    while start < n:
        stop = min(start + rows, n)
        blocks = {}
        for nid, mat in src_pairs:
            if stop >= n and reuse and nid in reuse:
                blocks[nid] = reuse[nid]
                metrics.inc("prefetch_reuse_hits")
            else:
                blocks[nid] = stage_block(mat, start, stop, donate=donate,
                                          device=device)
        yield start, stop, blocks
        start = stop


def _alloc_out_targets(member, to_host: bool):
    """Allocate a member's long-dimension output targets before its first
    partition step."""
    from .. import storage  # deferred: storage depends on core.matrix
    for tmpl, spec in member.out_nodes:
        target = spec.save or ("host" if to_host else "device")
        if target == "disk":
            # Write-through spill: the long-dimension output streams
            # into a preallocated on-disk matrix, partition by
            # partition — it never exists whole in RAM.  Works for any
            # pass: scale(X, save='disk') spills the PASS-2 sweep
            # output out-of-core end to end.
            member.disk_stores[tmpl.id] = storage.create_matrix(
                storage.spill_path(spec.name), (spec.nrow, spec.ncol),
                dtypes.np_equiv(spec.dtype))
        elif target == "host":
            member.host_bufs[tmpl.id] = np.empty(
                (spec.nrow, spec.ncol), dtypes.np_equiv(spec.dtype))


def _join_member(member, members, maps, stacks, joined, group_keys,
                 to_host: bool, start: int):
    """Splice a mid-stream-admitted member into a live sweep at a
    partition boundary (ISSUE 8).  The member consumes every partition
    from ``start`` on alongside the group, then `_catch_up` re-drives the
    prefix it missed.  Requirements checked here:

    * its staged sources must be a subset of the group's (it adds
      consumers to already-staged blocks, never new staging);
    * its long-dimension outputs must be row-addressed (host or disk
      targets) — device-resident outputs concatenate in partition order,
      which a late joiner would scramble.  Sink/epilogue-only plans (the
      typical serving analytics shape) always qualify.
    """
    mp = {}
    for nid, mat in member.ps.staged_sources(member.sources):
        if id(mat) not in group_keys:
            raise ValueError(
                "mid-stream admission requires the member's staged sources "
                "to be a subset of the live group's")
        mp[nid] = id(mat)
    if any((spec.save or ("host" if to_host else "device")) == "device"
           for _, spec in member.out_nodes):
        raise ValueError(
            "mid-stream admission cannot take device-resident "
            "long-dimension outputs (order-dependent concatenation)")
    _alloc_out_targets(member, to_host)
    members.append(member)
    maps.append(mp)
    stacks.append(_member_stack(member))
    joined[len(members) - 1] = start
    _count_admitted(member)


def _catch_up(members, maps, stacks, joined, group_pairs, rows: int,
              donate: bool):
    """Re-drive the partition prefix [0, join_start) that mid-stream
    admitted members missed.  Sink combines are order-independent and late
    long-dimension outputs are row-addressed (enforced by `_join_member`),
    so sweeping the prefix after the tail is exact."""
    from ..storage.prefetch import stage_block
    max_join = max(joined.values())
    late_keys = {key for idx in joined for key in maps[idx].values()}
    pairs = [(key, mat) for key, mat in group_pairs if key in late_keys]
    window = _output_window(False, None)
    start = 0
    with TRACER.span("catch_up", members=len(joined), upto=max_join), \
            _draining(members):
        while start < max_join:
            stop = min(start + rows, max_join)
            blocks = {key: stage_block(mat, start, stop, donate=donate)
                      for key, mat in pairs}
            metrics.inc("bytes_streamed",
                        sum(int(getattr(b, "nbytes", 0))
                            for b in blocks.values()))
            live = [i for i, j0 in joined.items() if j0 > start]
            with TRACER.span("partition", start=start, stop=stop):
                for pos, i in enumerate(live):
                    m, mp, stack = members[i], maps[i], stacks[i]
                    donate_blocks = donate and pos == len(live) - 1
                    with _in_stack(stack):
                        outputs = _member_step(
                            m, blocks, mp, start, stop,
                            donate_blocks=donate_blocks, idx=i)
                    m.route_outputs(start, stop, outputs, window)
            start = stop


def _run_stream_group(members, *, to_host: bool, donate: bool = True,
                      prefetch: Optional[bool] = None, residents=None,
                      capture: bool = False, admit=None,
                      depth: Optional[int] = None, mesh=None, seq=None):
    """Stream ONE co-scheduled group of member passes partition by
    partition: one prefetcher drive over the UNION of the members' staged
    sources, every member's step consuming each staged partition while it
    is resident (1 stream × k steps).  A solo materialize pass is the
    one-member case and behaves exactly like the classic per-plan stream.

    ``residents`` holds the previous pass's resident final partition(s);
    blocks whose partition schedule matches are fed to the prefetcher as
    ``reuse`` so the last partition is not re-staged.  With ``capture``
    the sweep's OWN final partition is returned as a `_Resident` (its
    blocks are excluded from donation) for the next pass to consume.

    ``admit`` is the mid-stream admission hook (fm.serve): called at every
    partition boundary with ``(start, stop)``, it may return new
    `_PassExec` members that join the live sweep from this partition on
    (`_join_member`); after the main sweep they catch up on the prefix
    they missed (`_catch_up`).  ``depth`` overrides the prefetch queue
    depth; None negotiates a group-aware depth
    (`storage.negotiate_depth`).

    ``mesh`` routes the sweep to the SHARDED runner — one prefetcher drive
    per device shard (`_run_sharded_stream`) — unless a live-admission
    gate is active: mid-stream admission splices a member into ONE
    sequential sweep at a partition boundary, and a sharded sweep has no
    single boundary order to splice into, so gated streams run unsharded
    (fm.serve instead serializes admission under a mesh — late requests
    wait for the next window; see Engine._run_group).

    ``seq`` is the sweep's sequence id, carried by its ``stream`` span and
    its prefetcher's ``stage`` spans (the caller's ``pass`` id; a new one
    when None).
    """
    from .. import storage  # deferred: storage depends on core.matrix

    seq = next_seq() if seq is None else seq
    if mesh is not None and admit is None:
        return _run_sharded_stream(members, mesh, to_host=to_host,
                                   donate=donate, prefetch=prefetch,
                                   depth=depth, seq=seq)

    n = members[0].ps.long_dim
    # Partition schedules in one group are power-of-two row counts over the
    # same long dimension: the min is a common partitioning for all members.
    rows = min(m.ps.partition_rows for m in members)
    group_pairs, maps = _group_staging(members)
    _count_stream(members, sum(mat.nbytes() for _, mat in group_pairs))

    for m in members:
        _alloc_out_targets(m, to_host)

    reuse_map = _reuse_from(residents, group_pairs, rows, n)
    group_keys = {key for key, _ in group_pairs}
    joined: dict[int, int] = {}  # member index -> partition start it joined at
    stacks = [_member_stack(m) for m in members]
    captured = None
    if prefetch is None:
        # Default on for slow-tier sources; a single-partition stream has
        # nothing to overlap, so skip the thread.
        prefetch = (storage.get_conf("prefetch") and n > rows
                    and any(mat.on_host for _, mat in group_pairs))
    # Nothing may come between pipeline construction and the try below:
    # the finally's close() is what guarantees an interrupted stream never
    # leaves the worker thread alive or staged partitions pinned.
    if prefetch:
        if depth is None:
            # Group-aware depth: k members consume each staged partition,
            # so the stager can usefully run further ahead (ISSUE 8).
            part_nbytes = rows * sum(
                mat.nbytes() // max(1, mat.shape[0])
                for _, mat in group_pairs)
            depth = storage.negotiate_depth(len(members), part_nbytes)
        parts = storage.PartitionPrefetcher(
            group_pairs, rows, n, donate=donate, depth=depth,
            reuse=reuse_map, seq=seq)
    else:
        parts = _inline_partitions(group_pairs, rows, n, donate,
                                   reuse=reuse_map)
    window = _output_window(prefetch, depth)
    try:
        with TRACER.span("stream", members=len(members), rows=rows,
                         reused=len(reuse_map or ()), seq=seq), \
                _draining(members):
            for start, stop, blocks in parts:
                if admit is not None:
                    for new_member in admit(start, stop):
                        _join_member(new_member, members, maps, stacks,
                                     joined, group_keys, to_host, start)
                is_final = stop >= n
                # The final partition's blocks survive the step when they
                # are being captured for the next pass, or when they CAME
                # from a resident entry that may be consulted again.
                pin_final = is_final and (capture or reuse_map is not None)
                with TRACER.span("partition", start=start, stop=stop):
                    for i, (m, mp, stack) in enumerate(
                            zip(members, maps, stacks)):
                        # Staged blocks are donated only by the LAST
                        # member's step — earlier members share them.
                        donate_blocks = (donate and i == len(members) - 1
                                         and not pin_final)
                        with _in_stack(stack):
                            outputs = _member_step(
                                m, blocks, mp, start, stop,
                                donate_blocks=donate_blocks, idx=i)
                        m.route_outputs(start, stop, outputs, window)
                    if capture and is_final:
                        captured = _Resident(
                            rows, n,
                            {key: blocks[key] for key, _ in group_pairs},
                            [mat for _, mat in group_pairs])
    finally:
        if hasattr(parts, "close"):
            parts.close()

    if joined:
        _catch_up(members, maps, stacks, joined, group_pairs, rows, donate)
    _finish_members(members, stacks)
    return captured


def _to_device(tree, dev):
    return jax.tree_util.tree_map(
        lambda x: jax.device_put(x, dev) if isinstance(x, jax.Array) else x,
        tree)


def _run_sharded_stream(members, mesh, *, to_host: bool, donate: bool = True,
                        prefetch: Optional[bool] = None,
                        depth: Optional[int] = None, seq=None):
    """Shard a group's partition sweep across the mesh's data axis
    (ISSUE 9 tentpole — the paper's partition-per-thread NUMA mapping,
    §III-D, as partition-range-per-device):

    * the long dimension splits into contiguous partition-aligned row
      ranges (`fusion.shard_ranges`), one per data shard;
    * each shard runs its OWN prefetcher drive + per-device executor over
      its range (the disk tier serves arbitrary ``block(start, stop)``),
      staging blocks onto its device — shard workers are plain threads, so
      N shards stream and compute concurrently;
    * sink partials merge across shards through the SAME associative
      ``combine`` the partition loop uses, pairwise (a tree all-reduce):
      exactly one merge per shard boundary (``shard_merges``);
    * the merged sinks replicate across the mesh and the epilogue runs
      replicated (`_finish_members(mesh=...)`).

    Row-addressed targets (ooc host buffers, ``save='disk'`` spill stores)
    are SHARED by the shard clones — ranges are disjoint, so concurrent
    row writes never overlap and a spill streams every shard's rows into
    one on-disk matrix.  Device-resident long outputs gather to the first
    shard's device in shard order, then re-commit sharded over the mesh
    when the row count divides (`LoweredProgram.shard_specs`, resolved
    through ``distributed.sharding.resolve``).

    One failed shard fails the whole sweep (every drive is joined, the
    first error re-raised AFTER all prefetchers shut down), so callers
    never register partial sinks.  Capture/residency reuse is disabled
    under a mesh: the resident-final-partition optimization assumes one
    sequential sweep.  ``bytes_in`` accounting stays the union — each row
    is staged by exactly one shard — with the per-shard byte split
    published as ``shard_bytes_in``.
    """
    import concurrent.futures as cf

    from .. import storage  # deferred: storage depends on core.matrix
    from ..distributed import sharding as shd
    from .fusion import shard_ranges

    n = members[0].ps.long_dim
    rows = min(m.ps.partition_rows for m in members)
    group_pairs, maps = _group_staging(members)
    _count_stream(members, sum(mat.nbytes() for _, mat in group_pairs))
    for m in members:
        _alloc_out_targets(m, to_host)

    devices = shd.shard_devices(mesh)
    ranges = shard_ranges(n, rows, len(devices))
    shards = [(si, lo, hi, dev)
              for si, ((lo, hi), dev) in enumerate(zip(ranges, devices))
              if hi > lo]
    metrics.inc("shards", len(shards))
    row_bytes = sum(mat.nbytes() // max(1, mat.shape[0])
                    for _, mat in group_pairs)
    metrics.put("shard_bytes_in",
                tuple(row_bytes * (hi - lo) for _, lo, hi, _d in shards))

    if prefetch is None:
        prefetch = (storage.get_conf("prefetch") and n > rows
                    and any(mat.on_host for _, mat in group_pairs))
    if prefetch and depth is None:
        depth = storage.negotiate_depth(len(members), rows * row_bytes)
    window = _output_window(prefetch, depth)

    # Per-shard executor clones: the SAME compiled per-pass program run as
    # per-device executors, one row range each.  Bindings (earlier passes'
    # merged values) and device-resident smalls REPLICATE — each clone
    # gets a copy committed to its shard's device, so the jitted step
    # never sees inputs committed to two different devices.
    clones_by_shard = []
    for _si, _lo, _hi, dev in shards:
        clones = []
        for m in members:
            bindings = _to_device(m.bindings, dev)
            smalls = _to_device(m.smalls, dev)
            sm = _PassExec(m.ps, m.prog, m.sources, smalls, m.epi_sources,
                           bindings, out_nodes=m.out_nodes, scopes=m.scopes)
            # The identity accumulators start on the shard's device, not
            # wherever the default device put them.
            sm.accs = _to_device(sm.accs, dev)
            sm.host_bufs = m.host_bufs
            sm.disk_stores = m.disk_stores
            clones.append(sm)
        clones_by_shard.append(clones)

    # Metrics scopes are thread-local: capture the calling thread's full
    # stack (ambient + each member's request scopes) here and re-enter it
    # on the shard worker threads, so per-request attribution and the
    # prefetcher's scope adoption keep working off the caller.
    ambient = metrics.current_scopes()
    amb_set = set(ambient)
    stacks = [tuple(ambient)
              + tuple(s for s in m.scopes if s not in amb_set)
              for m in members]

    def drive(shard_idx: int):
        si, lo, hi, dev = shards[shard_idx]
        clones = clones_by_shard[shard_idx]
        with metrics.use_scopes(ambient):
            if prefetch:
                parts = storage.PartitionPrefetcher(
                    group_pairs, rows, hi, row_start=lo, donate=donate,
                    depth=depth, device=dev, seq=seq)
            else:
                parts = _inline_partitions(group_pairs, rows, hi, donate,
                                           row_start=lo, device=dev)
            try:
                with TRACER.span("shard", idx=si, start=lo, stop=hi), \
                        _draining(clones):
                    for start, stop, blocks in parts:
                        with TRACER.span("partition", start=start,
                                         stop=stop, shard=si):
                            for i, (sm, mp) in enumerate(zip(clones, maps)):
                                donate_blocks = (donate
                                                 and i == len(clones) - 1)
                                with metrics.use_scopes(stacks[i]):
                                    outputs = _member_step(
                                        sm, blocks, mp, start, stop,
                                        donate_blocks=donate_blocks, idx=i)
                                sm.route_outputs(start, stop, outputs,
                                                 window)
            finally:
                if hasattr(parts, "close"):
                    parts.close()

    with TRACER.span("stream", members=len(members), rows=rows,
                     shards=len(shards), seq=seq):
        if len(shards) == 1:
            drive(0)
        else:
            with cf.ThreadPoolExecutor(
                    max_workers=len(shards),
                    thread_name_prefix="fm-shard") as pool:
                futures = [pool.submit(drive, i)
                           for i in range(len(shards))]
                errors = [f.exception() for f in futures]
            for exc in errors:
                if exc is not None:
                    raise exc

    # Where each shard's partials accumulated — one device per shard when
    # the sweep really spread over the mesh.
    metrics.put("shard_devices", tuple(
        tuple(sorted({d.id for sm in clones
                      for leaf in jax.tree_util.tree_leaves(sm.accs)
                      for d in leaf.devices()}))
        for clones in clones_by_shard))
    dev0 = shards[0][3]
    for mi, m in enumerate(members):
        if m.ps.sinks:
            entries = [(clones_by_shard[s][mi].accs, shards[s][3])
                       for s in range(len(shards))]
            while len(entries) > 1:
                nxt = []
                for j in range(0, len(entries) - 1, 2):
                    (a, dev_a), (b, _dev_b) = entries[j], entries[j + 1]
                    with TRACER.span("shard_combine", member=mi):
                        a = m.prog.combine(a, _to_device(b, dev_a))
                    metrics.inc("shard_merges")
                    nxt.append((a, dev_a))
                if len(entries) % 2:
                    nxt.append(entries[-1])
                entries = nxt
            m.accs = entries[0][0]
        for tmpl, _spec in m.out_nodes:
            nid = tmpl.id
            if nid in m.host_bufs or nid in m.disk_stores:
                continue  # row-addressed shared targets: already written
            for s in range(len(shards)):
                m.out_parts[nid].extend(
                    _to_device(p, dev0)
                    for p in clones_by_shard[s][mi].out_parts[nid])

    _finish_members(members, [_member_stack(m) for m in members], mesh=mesh)
    _apply_output_specs(members, mesh)
    return None


def _apply_output_specs(members, mesh):
    """Re-commit device-resident long-dimension outputs by their resolved
    specs: shard the rows over the mesh when they divide (the ``rows``
    rule), so a sharded materialize hands downstream consumers an already
    data-sharded result."""
    for m in members:
        specs = m.prog.shard_specs(mesh)
        for tmpl, _spec in m.out_nodes:
            nid = tmpl.id
            parts = m.out_parts.get(nid)
            if not parts or isinstance(parts[0], np.ndarray):
                continue
            spec = specs.get(nid)
            if spec is None or not len(spec) or spec[0] is None:
                continue
            data = parts[0] if len(parts) == 1 else jnp.concatenate(parts, 0)
            m.out_parts[nid] = [
                jax.device_put(data, NamedSharding(mesh, spec))]


def _store_results(plan: Plan, sink_finals, out_parts, *, to_host: bool,
                   disk_stores=None, epilogue_outs=None, onto: Plan = None):
    """Register the execution's values as each result node's cached store.

    ``onto`` is an equal-signature plan to register results ON: a request
    executing through a borrowed cached template (solo materialize, batch
    member, serve member alike) reads values keyed by the TEMPLATE's node
    ids but registers them on its own plan's nodes (positionally aligned —
    same signature, same deterministic node order), so the template's
    nodes are never mutated.  Defaults to ``plan`` itself.

    Runs under _DAG_LOCK: registration flips nodes to physical, and must
    not interleave with another thread's plan construction over a shared
    subgraph (ISSUE 8 audit)."""
    onto = onto if onto is not None else plan
    with _DAG_LOCK:
        _store_results_locked(plan, onto, sink_finals, out_parts,
                              to_host=to_host, disk_stores=disk_stores,
                              epilogue_outs=epilogue_outs)


def _store_results_locked(plan, onto, sink_finals, out_parts, *, to_host,
                          disk_stores, epilogue_outs):
    for node, dst in zip(plan.sinks, onto.sinks):
        arr = sink_finals[node.id]
        dst.cached_store = FMMatrix(
            dst.shape, dst.dtype, store=DenseStore(arr), name=dst.name)
    if epilogue_outs:
        # Epilogue results are small post-merge values: like sinks they stay
        # on device in every mode, unless an explicit save flag retargets
        # them (out_parts routes them through the ordinary target logic).
        out_parts = dict(out_parts)
        for node in plan.epilogue_roots:
            out_parts[node.id] = [epilogue_outs[node.id]]
    epi_ids = {n.id for n in plan.epilogue_roots}
    tmpl_outs = plan.row_local_roots + plan.saves + plan.epilogue_roots
    own_outs = onto.row_local_roots + onto.saves + onto.epilogue_roots
    for node, dst in zip(tmpl_outs, own_outs):
        if disk_stores and node.id in disk_stores:
            dst.cached_store = FMMatrix(
                dst.shape, dst.dtype, store=disk_stores[node.id],
                name=dst.name)
            dst.save = None
            continue
        parts = out_parts[node.id]
        if len(parts) == 1:
            data = parts[0]
        else:
            data = jnp.concatenate(parts, axis=0)
        target = dst.save or (
            "host" if to_host and node.id not in epi_ids else None)
        if target == "disk":
            # whole-mode save='disk': spill the materialized output in one go.
            from .. import storage
            store = storage.create_matrix(
                storage.spill_path(dst.name), dst.shape,
                dtypes.np_equiv(dst.dtype))
            store.write_rows(0, np.asarray(data))
            store.flush()
            dst.cached_store = FMMatrix(
                dst.shape, dst.dtype, store=store, name=dst.name)
            dst.save = None
            continue
        if target == "host" and not isinstance(data, np.ndarray):
            data = np.asarray(data)
        dst.cached_store = FMMatrix(
            dst.shape, dst.dtype, store=DenseStore(data), name=dst.name)
        dst.save = None


# ---------------------------------------------------------------------------
# Eager (unfused) execution — the ablation baseline
# ---------------------------------------------------------------------------

def _materialize_eager(nodes: Sequence[Node], *, mode: str = "auto",
                       backend: Optional[str] = None):
    """Materialize every DAG node separately, writing each intermediate out
    in full before the next operation reads it back.

    This is the behaviour the paper ascribes to frameworks without operation
    fusion ("MLlib materializes operations such as aggregation separately"),
    and the `fuse=False` arm of benchmarks/fusion_ablation.py.  Out-of-core,
    every intermediate roundtrips the host tier (mem-fuse off); in memory,
    every intermediate lands in HBM (cache-fuse off).
    """
    order = Plan._cut_toposort(list(nodes))
    temp: list[Node] = []
    ooc = any(isinstance(n, LeafNode) and n.mat.on_host for n in order)
    for n in order:
        with _DAG_LOCK:
            if Plan._is_source(n):
                continue
            sub = Plan([wrap(n)])
            if ooc and not n.is_sink:
                n.save = "host"  # roundtrip the slow tier, as an unfused engine must
        sub_mode = mode
        if mode == "auto":
            sub_mode = "ooc" if ooc else "whole"
        _execute(sub, mode=sub_mode, backend=backend)
        temp.append(n)
    return temp
