"""Pluggable lowering backends: plan IR → executable partition programs.

Third layer of the execution engine (after `fusion.Plan` cut/schedule and
`plan_ir` segment compilation).  A backend lowers the IR's segments into a
`LoweredProgram` whose contract the materializer consumes:

    partials, row_local_outputs = program.step(source_blocks, smalls, offset)
    accs = program.combine(accs, partials)       # the paper's partial merge

``step`` pushes ONE I/O-level partition through the whole fused cut and
returns each sink's *partial* for that partition; ``combine`` merges
partials into the running accumulators with the aggregation VUDFs'
``combine`` — exactly the paper's "each thread computes partial aggregation
results independently … in the end, FlashMatrix merges the partial
aggregation results" (§III-F), with partitions standing in for threads.

Backends:

* ``xla``    — every segment is traced node-by-node through the generic
  ``block_eval`` / ``block_update`` rules and XLA performs the cache-level
  fusion (the engine's previous behavior).
* ``pallas`` — eligible segments lower onto the hand-written kernels in
  `repro/kernels/` (the VMEM-tier analog of the paper's CPU-cache fusion):
  inner-product contractions → `gram`/`xty`, apply→agg.col chains sharing a
  source → one `fused_apply_agg` call, and the k-means Lloyd pattern
  (distances → which.min → groupby) → `kmeans_assign`.  Segments with no
  kernel match fall back to the generic trace, and on non-TPU backends the
  kernels run in interpret mode so the same lowering path is exercised in
  tests.

Backend selection: ``fm.set_conf(backend=...)`` ('auto' | 'xla' | 'pallas')
or the ``backend=`` argument of ``fm.materialize``; 'auto' picks pallas on
TPU and xla elsewhere.  The backend name and the IR's two-level partition
schedule are both part of the plan-cache key, so switching backends or
retuning either partition level retraces instead of reusing a stale
executable.

Registering a new kernel lowering = appending a matcher to
``PallasBackend.MATCHERS``: a callable ``(plan, ir, claimed) -> list[unit]``
that inspects unclaimed segments, marks the ones it consumes in ``claimed``
and returns execution units (objects with ``run(values, partials, smalls,
offset)``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import dtypes
from .dag import (AggFullNode, GroupByRowNode, InnerProdContractNode,
                  MapNode, Node, Small)
from .sparse import SparseBlock

# ---------------------------------------------------------------------------
# Backend registry + selection
# ---------------------------------------------------------------------------

BACKENDS: dict[str, "Backend"] = {}

#: Engine-wide default, settable via fm.set_conf(backend=...).
DEFAULT_BACKEND = "auto"


def register_backend(name: str, backend: "Backend"):
    BACKENDS[name] = backend
    return backend


def resolve_backend(name: str | None = None) -> str:
    """'auto' (or None) → pallas on TPU, xla elsewhere."""
    name = name or DEFAULT_BACKEND
    if name == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "xla"
    if name not in BACKENDS:
        raise ValueError(
            f"unknown backend {name!r}; have {sorted(BACKENDS)} + 'auto'")
    return name


def lower(plan, ir, backend: str) -> "LoweredProgram":
    return BACKENDS[backend].lower(plan, ir)


# ---------------------------------------------------------------------------
# The lowered program
# ---------------------------------------------------------------------------

class LoweredProgram:
    """An executable lowering of ONE PASS of a plan: per-partition ``step``,
    the sink-partial ``combine`` merge, and — when the pass has post-sink
    lazy math — an ``epilogue`` callable the executor invokes exactly ONCE
    after the merge: ``epilogue(merged_sinks, epilogue_sources, smalls,
    bindings) → outputs`` (the engine's fourth stage).

    ``bindings`` is the multi-pass contract (fusion.PassSchedule): merged
    values produced by EARLIER passes of the same plan — the pass-1 moment
    vector a pass-2 ``scale(X)`` sweep consumes — handed to ``step`` and
    ``epilogue`` as broadcast arguments keyed by node id.  They are normal
    runtime inputs of the jitted callables (never baked constants, never
    donated: every partition of the pass reads them)."""

    def __init__(self, plan, ir, backend: str, units):
        self.plan = plan
        self.ir = ir
        self.backend = backend
        self.units = units
        self._sinks_by_id = {n.id: n for n in plan.sinks}
        self.step = jax.jit(self._step)
        # Buffer donation = the paper's memory-chunk recycling: staged
        # partition blocks are dead after the step consumes them, and the
        # previous accumulators are dead after the merge.
        self.step_donated = jax.jit(self._step, donate_argnums=(0,))
        self.combine = jax.jit(self._combine, donate_argnums=(0,))
        self.epilogue = (jax.jit(self._epilogue)
                         if plan.epilogue_nodes else None)

    @property
    def kernel_units(self):
        """The units lowered onto hand-written kernels (empty under xla)."""
        return [u for u in self.units if getattr(u, "kernel", None)]

    def describe(self) -> str:
        lines = [f"LoweredProgram(backend={self.backend}, "
                 f"units={len(self.units)})"]
        lines += ["  " + u.describe() for u in self.units]
        if self.epilogue is not None:
            lines.append(f"  epilogue nodes={len(self.plan.epilogue_nodes)} "
                         f"outs={[n.name for n in self.plan.epilogue_roots]}")
        return "\n".join(lines)

    def shard_specs(self, mesh) -> dict:
        """PartitionSpec per result node of this pass under a data-sharded
        mesh (ISSUE 9), resolved through the shared divisibility-checked
        policy (``distributed.sharding.resolve``): long-dim outputs shard
        their row dimension over the data tier (``rows`` — falls back to
        replicate when the row count does not divide), merged sinks and
        epilogue values replicate (``rep`` — every device holds the full
        reduction, which is what lets the epilogue run replicated)."""
        from ..distributed import sharding as shd
        specs = {}
        for n in self.plan.row_local_roots + self.plan.saves:
            specs[n.id] = shd.resolve("rows|rep", (n.nrow, n.ncol), mesh)
        for n in list(self.plan.sinks) + list(self.plan.epilogue_roots):
            specs[n.id] = shd.resolve("rep|rep", (n.nrow, n.ncol), mesh)
        return specs

    def _step(self, source_blocks, smalls, bindings, offset):
        """One I/O-level partition through the fused cut of this pass.

        Returns (sink_partials, row_local_outputs) for this partition;
        partials start from each sink's identity so ``combine`` can merge
        them into accumulators of the same structure.

        ``source_blocks`` holds ONE staged block per physical matrix
        (keyed by the staging group's canonical node id); every aliasing
        source node sees the same traced value, so a matrix referenced
        through k leaves is read and transferred once per partition.
        ``bindings`` holds the pass's broadcast inputs keyed by node id:
        earlier-pass merged values plus whole-staged small physical sources
        (fusion.PassSchedule.broadcast_sources).
        """
        values = {nid: source_blocks[canon]
                  for nid, canon in self.plan.source_aliases.items()}
        values.update(bindings)
        partials = {n.id: n.identity() for n in self.plan.sinks}
        for unit in self.units:
            unit.run(values, partials, smalls, offset)
        outputs = {n.id: values[n.id]
                   for n in self.plan.row_local_roots + self.plan.saves}
        return partials, outputs

    def _combine(self, accs, partials):
        return {nid: self._sinks_by_id[nid].combine(accs[nid], partials[nid])
                for nid in accs}

    def _epilogue(self, sink_finals, epi_sources, smalls, bindings):
        """The pass's post-sink lazy math (paper §III-E: expressions like
        ``colSums(X) / n`` fuse into the same execution job), evaluated on
        the FINALIZED sink values — one on-device launch per pass, cached
        with the rest of the plan.

        ``sink_finals``: {sink node id: finalized value} out of the merge;
        ``epi_sources``: {leaf id: whole array} for small physical operands
        only the epilogue consumes (e.g. a ridge eye matrix);
        ``bindings``: earlier-pass merged values (multi-pass plans).  A
        sink-kind node appearing here (``sum(colMeans(X))``) contracts an
        already-merged small value, so it runs its identity→update→finalize
        quartet once with offset 0.

        Returns the pass's epilogue ROOTS (requested/saved results) plus
        its CARRIES — unrequested epilogue values a later pass consumes.
        """
        values = dict(bindings)
        values.update(epi_sources)
        values.update(sink_finals)
        zero = jnp.zeros((), jnp.int32)
        for n in self.plan.epilogue_nodes:
            blocks = [smalls[self.plan._small_pos[id(p)]]
                      if isinstance(p, Small) else values[p.id]
                      for p in n.parents]
            if n.is_sink:
                acc = n.block_update(n.identity(), blocks, zero)
                values[n.id] = n.finalize(acc)
            else:
                values[n.id] = n.block_eval(blocks, zero)
        outs = {n.id: values[n.id] for n in self.plan.epilogue_roots}
        for n in getattr(self.plan, "epilogue_carries", []):
            outs[n.id] = values[n.id]
        return outs


class MultiPassProgram:
    """The compiled executable of a multi-pass plan: one `LoweredProgram`
    per pass, run in order by the executor under ONE plan-cache entry.
    Pass k+1's ``bindings`` are fed from pass k's finalized sinks and
    epilogue outputs (core/materialize.py carries them forward)."""

    def __init__(self, passes):
        self.passes = list(passes)
        self.backend = self.passes[0].backend if self.passes else "?"

    @property
    def kernel_units(self):
        return [u for p in self.passes for u in p.kernel_units]

    @property
    def epilogue(self):
        """Truthy when any pass has post-merge math (observability only —
        the executor always goes through the per-pass programs)."""
        return next((p.epilogue for p in self.passes
                     if p.epilogue is not None), None)

    def describe(self) -> str:
        lines = [f"MultiPassProgram(passes={len(self.passes)})"]
        for k, p in enumerate(self.passes):
            lines.append(f" pass {k}:")
            lines.extend("  " + line for line in p.describe().splitlines())
        return "\n".join(lines)

    def shard_specs(self, mesh) -> dict:
        """Union of every pass's per-node output specs (node ids are unique
        across the plan) — the sharded executor runs the SAME per-pass
        programs as per-device executors, one row range each, and places
        results by these specs."""
        specs = {}
        for p in self.passes:
            specs.update(p.shard_specs(mesh))
        return specs


class GroupProgram:
    """The co-scheduled executable of ONE stream group (core/batch.py): k
    member passes driven over a single shared partition stream.

    The members keep their own compiled ``step``/``combine``/``epilogue``
    (plan-cache identity, donation rules and sink merge are per member);
    what the group composes is the SCHEDULE — while a staged partition is
    resident, every member's step consumes it before eviction, so k plans
    × 1 stream executes as 1 stream × k steps.  ``members`` holds
    ``(PassSchedule, LoweredProgram)`` pairs in execution order; the
    runner is ``materialize._run_stream_group``.
    """

    def __init__(self, members):
        self.members = list(members)

    @property
    def kernel_units(self):
        return [u for _, prog in self.members for u in prog.kernel_units]

    @property
    def partition_rows(self) -> int:
        """The group's common partitioning: the smallest member's rows (all
        are powers of two under one I/O budget, so every member's schedule
        divides it)."""
        return min(ps.partition_rows for ps, _ in self.members)

    def describe(self) -> str:
        lines = [f"GroupProgram(members={len(self.members)}, "
                 f"partition_rows={self.partition_rows})"]
        for i, (ps, prog) in enumerate(self.members):
            lines.append(f" member {i} (pass {ps.idx}, "
                         f"rows={ps.partition_rows}):")
            lines.extend("  " + line
                         for line in prog.describe().splitlines())
        return "\n".join(lines)


class Backend:
    name = "?"

    def lower(self, plan, ir) -> LoweredProgram:
        raise NotImplementedError


# ---------------------------------------------------------------------------
# Execution units
# ---------------------------------------------------------------------------

class GenericUnit:
    """Trace a segment node-by-node through the dag eval rules (the xla
    path, and the fallback for segments no kernel matcher claims)."""

    kernel = None

    def __init__(self, plan, segment):
        self.plan = plan
        self.segment = segment
        self.nodes = segment.nodes

    def describe(self) -> str:
        return (f"generic seg#{self.segment.sid} [{self.segment.kind}] "
                f"root={self.segment.root.name}")

    def run(self, values, partials, smalls, offset):
        # Sparse (ELL) partition blocks densify lazily into a LOCAL cache:
        # only node rules with no sparse path see the dense form, and the
        # shared ``values`` dict keeps the SparseBlock so a kernel unit
        # consuming the same staged leaf still gets nnz-proportional input.
        # matmul_small keeps its sparse gather path (dag._inner_prod_block
        # handles a SparseBlock left operand directly).
        dense: dict[int, object] = {}

        def block_of(n, pos, p):
            v = values[p.id]
            if isinstance(v, SparseBlock) and not (
                    n.kind == "matmul_small" and pos == 0):
                if p.id not in dense:
                    dense[p.id] = v.todense()
                v = dense[p.id]
            return v

        for n in self.nodes:
            blocks = [smalls[self.plan._small_pos[id(p)]]
                      if isinstance(p, Small) else block_of(n, i, p)
                      for i, p in enumerate(n.parents)]
            if n.is_sink:
                partials[n.id] = n.block_update(partials[n.id], blocks, offset)
            else:
                values[n.id] = n.block_eval(blocks, offset)


class _KernelUnit:
    """Base for units lowered onto a Pallas kernel.  ``interpret=None``
    defers to kernels.common.default_interpret(): Mosaic on TPU,
    interpreter elsewhere — the same call sites run in both worlds."""

    def __init__(self, kernel: str, block_rows: int):
        self.kernel = kernel
        self.block_rows = int(block_rows)

    #: Row-local segment nodes absorbed ahead of the kernel (e.g. the IRLS
    #: weights feeding XᵀWX), evaluated generically before the kernel call
    #: with the plan's small operands.
    prefix = ()
    plan = None

    @staticmethod
    def _merge(partials, node, part):
        partials[node.id] = node.combine(
            partials[node.id], part.astype(partials[node.id].dtype))

    def _eval_prefix(self, values, smalls, offset):
        for n in self.prefix:
            blocks = [smalls[self.plan._small_pos[id(p)]]
                      if isinstance(p, Small) else values[p.id]
                      for p in n.parents]
            values[n.id] = n.block_eval(blocks, offset)


class ContractionUnit(_KernelUnit):
    """InnerProdContractNode (mul, sum) → kernels.gram / kernels.xty."""

    def __init__(self, node: InnerProdContractNode, block_rows: int):
        left, right = node.parents
        # crossprod(X) wraps one physical matrix in two LeafNodes: detect
        # the shared backing so it lowers to gram (one read) rather than xty.
        same = left is right or (
            getattr(left, "mat", None) is not None
            and left.mat is getattr(right, "mat", None))
        super().__init__("gram" if same else "xty", block_rows)
        self.node = node
        self.left_id, self.right_id = left.id, right.id

    def describe(self) -> str:
        return f"pallas:{self.kernel} root={self.node.name}"

    def run(self, values, partials, smalls, offset):
        from ..kernels import gram as gram_mod
        x = values[self.left_id]
        if self.kernel == "gram":
            part = gram_mod.gram(x, block_rows=min(self.block_rows, x.shape[0]))
        else:
            part = gram_mod.xty(x, values[self.right_id],
                                block_rows=min(self.block_rows, x.shape[0]))
        self._merge(partials, self.node, part)


class WeightedGramUnit(_KernelUnit):
    """The IRLS weighted-Gram pattern — ``mapply.col(X, w, mul)`` feeding an
    (mul, sum) contraction of the same X — → one kernels.wgram call: the
    reweighted rows never exist outside the VMEM tile.  ``prefix`` holds an
    absorbed row-local chain computing w."""

    def __init__(self, node: InnerProdContractNode, x_id: int, w_id: int,
                 block_rows: int, *, plan=None, prefix=()):
        super().__init__("wgram", block_rows)
        self.node = node
        self.x_id = x_id
        self.w_id = w_id
        self.plan = plan
        self.prefix = tuple(prefix)

    def describe(self) -> str:
        return f"pallas:{self.kernel} root={self.node.name}"

    def run(self, values, partials, smalls, offset):
        from ..kernels import weighted_gram as wg
        self._eval_prefix(values, smalls, offset)
        x = values[self.x_id]
        w = values[self.w_id]
        part = wg.wgram(x, w, block_rows=min(self.block_rows, x.shape[0]))
        self._merge(partials, self.node, part)


class ApplyAggUnit(_KernelUnit):
    """N apply→agg.col chains over one source → one fused_apply_agg call
    (the paper's sink co-materialization: X is read once for all stats)."""

    def __init__(self, source_id: int, chains, sinks, block_rows: int):
        super().__init__("fused_apply_agg", block_rows)
        self.source_id = source_id
        self.chains = tuple(chains)
        self.sinks = list(sinks)

    def describe(self) -> str:
        return (f"pallas:{self.kernel} chains={len(self.chains)} "
                f"sinks={[s.name for s in self.sinks]}")

    def run(self, values, partials, smalls, offset):
        from ..kernels import fused_apply_agg as faa
        x = values[self.source_id]
        parts = faa.fused_apply_agg(
            x, self.chains, block_rows=min(self.block_rows, x.shape[0]))
        for node, part in zip(self.sinks, parts):
            self._merge(partials, node, part.reshape(node.identity().shape))


class KMeansUnit(_KernelUnit):
    """The Lloyd-step pattern → one kernels.kmeans_assign call per
    partition: distances, argmin, groupby sums/counts and the objective all
    from one VMEM-resident read of X."""

    def __init__(self, *, x_id: int, centers_pos: int, labels: Node,
                 sums: Node, counts: Node | None, wss: Node | None,
                 block_rows: int):
        super().__init__("kmeans_assign", block_rows)
        self.x_id = x_id
        self.centers_pos = centers_pos
        self.labels, self.sums, self.counts, self.wss = (
            labels, sums, counts, wss)

    def describe(self) -> str:
        outs = [self.labels.name, self.sums.name]
        outs += [n.name for n in (self.counts, self.wss) if n is not None]
        return f"pallas:{self.kernel} outs={outs}"

    def run(self, values, partials, smalls, offset):
        from ..kernels import kmeans_assign as ka
        x = values[self.x_id]
        centers = smalls[self.centers_pos].T  # matmul_small stores (p, k)
        lab, sums, cnts, wss = ka.kmeans_assign(
            x, centers, block_rows=min(self.block_rows, x.shape[0]))
        values[self.labels.id] = lab.reshape(-1, 1)
        self._merge(partials, self.sums, sums)
        if self.counts is not None:
            self._merge(partials, self.counts, cnts.reshape(-1, 1))
        if self.wss is not None:
            self._merge(partials, self.wss, wss.reshape(()))


class SpmmUnit(_KernelUnit):
    """A sparse-ELL contraction lowered onto the kernels.spmm family: the
    staged SparseBlock flows straight into the kernel (nnz-proportional HBM
    traffic — the paper's one-hot/Criteo tier), scatter-densified to a VMEM
    tile inside the kernel only.  ``prefix`` holds an absorbed row-local
    chain computing the dense right operand (the IRLS score's ``y − μ``) or
    the weights of XᵀWX, evaluated generically before the kernel call; it
    never touches the sparse leaf (the matcher declines otherwise)."""

    def __init__(self, kernel: str, node: InnerProdContractNode, *, plan,
                 seg, x_id: int, y_id: int | None = None,
                 w_id: int | None = None, absorb=()):
        super().__init__(kernel, seg.block_rows)
        self.plan = plan
        self.node = node
        self.x_id = x_id
        self.y_id = y_id
        self.w_id = w_id
        # ``absorb``: segment nodes the KERNEL computes itself (the wgram
        # reweighting mapply) — they must not be evaluated generically,
        # since they read the sparse leaf.
        self.prefix = tuple(n for n in seg.nodes
                            if n is not node and n not in absorb)

    def describe(self) -> str:
        return f"pallas:{self.kernel} root={self.node.name}"

    def run(self, values, partials, smalls, offset):
        from ..kernels import spmm
        self._eval_prefix(values, smalls, offset)
        x = values[self.x_id]
        if not isinstance(x, SparseBlock):
            # Densified between tracing and execution (a tier move): the
            # plan cache keys on the source's sparse signature, so this is
            # a defensive fallback, not a hot path.
            part = self._dense_part(x, values)
        elif self.kernel == "spmm_gram":
            part = spmm.spmm_gram(x.cols, x.vals, ncol=x.ncol)
        elif self.kernel == "spmm_xty":
            y = values[self.y_id]
            part = spmm.spmm_xty(x.cols, x.vals, y, ncol=x.ncol)
        else:
            w = values[self.w_id]
            part = spmm.spmm_wgram(x.cols, x.vals, w, ncol=x.ncol)
        self._merge(partials, self.node, part)

    def _dense_part(self, x, values):
        x = x.astype(jnp.float32)
        if self.kernel == "spmm_gram":
            y = x
        elif self.kernel == "spmm_xty":
            y = values[self.y_id].astype(jnp.float32)
        else:
            y = x
            x = x * values[self.w_id].astype(jnp.float32).reshape(-1, 1)
        return jnp.matmul(x.T, y, precision=jax.lax.Precision.HIGHEST)


# ---------------------------------------------------------------------------
# xla backend
# ---------------------------------------------------------------------------

class XlaBackend(Backend):
    """Generic traced lowering: XLA performs the cache-level fusion."""

    name = "xla"

    def lower(self, plan, ir) -> LoweredProgram:
        # The epilogue segment is not a partition unit: LoweredProgram
        # compiles it into the separate post-merge callable.
        units = [GenericUnit(plan, seg) for seg in ir.segments
                 if seg.kind != "epilogue"]
        return LoweredProgram(plan, ir, self.name, units)


# ---------------------------------------------------------------------------
# pallas backend
# ---------------------------------------------------------------------------

def _f32_acc(node) -> bool:
    return dtypes.canon(node.acc_dtype) == jnp.dtype(jnp.float32)


def _sparse_leaf(p) -> bool:
    """True when an operand is a leaf over a sparse-tier store — its staged
    partition block arrives as a SparseBlock, which the dense kernels must
    never consume."""
    mat = getattr(p, "mat", None)
    return (mat is not None
            and getattr(getattr(mat, "store", None), "sparse", False))


def _decline(reasons, seg, msg: str):
    """Record why a matcher passed on a segment it inspected (ISSUE 10):
    ``dispatch_report`` replays the matchers with a ``reasons`` dict and
    renders these next to the generic-trace fallback, so sparse-vs-dense
    dispatch decisions are auditable in ``fm.explain``.  ``lower()`` calls
    the matchers without the dict — declining stays free on the hot path."""
    if reasons is not None:
        reasons.setdefault(seg.sid, []).append(msg)


def _source_key(node: Node):
    """Identity of the data a node's partition block carries.  Distinct
    LeafNodes over one physical matrix (each GenOp call wraps its own leaf)
    must compare equal so their chains fuse into one kernel read."""
    mat = getattr(node, "mat", None)
    if mat is not None:
        return ("leaf", id(mat))
    return ("node", node.id)


def _same_source(a: Node, b: Node) -> bool:
    return a is b or _source_key(a) == _source_key(b)


def _is_pure_unary_chain(seg):
    """segment = [sapply*, sink]: returns the unary-name tuple source→sink,
    or None when the absorbed chain is not a linear unary pipeline."""
    names = []
    expect = seg.nodes[-1].parents[0]  # the sink's operand, walking upward
    for n in reversed(seg.nodes[:-1]):
        if n is not expect or n.kind != "sapply":
            return None
        names.append(n.fn_info["vudf"].name)
        expect = n.parents[0]
    if isinstance(expect, Small):
        return None
    return tuple(reversed(names))


def _reads_sparse(nodes) -> bool:
    return any(_sparse_leaf(p) for n in nodes for p in n.parents)


def _match_spmm(plan, ir, claimed, reasons=None):
    """Sparse-ELL contraction → kernels.spmm — runs BEFORE the dense
    contraction matchers so a SparseBlock operand is never fed to the dense
    gram/xty/wgram kernels.  Three shapes:

    * ``crossprod(Xs)``         — len-1 segment, both operands one sparse
      leaf → ``spmm_gram``;
    * ``crossprod(Xs * w, Xs)`` — the absorbed ``mapply_col`` reweighting
      of the contraction's own sparse source → ``spmm_wgram`` (the sparse
      IRLS XᵀWX hot spot);
    * ``crossprod(Xs, Y)``      — sparse left against a dense right.

    The segment may have absorbed a row-local prefix computing w or Y (the
    IRLS weights, the score's ``y − μ``), which the unit evaluates
    generically first.
    """
    units = {}
    for seg in ir.segments:
        if seg.sid in claimed or seg.kind != "contraction":
            continue
        node = seg.root
        if not isinstance(node, InnerProdContractNode):
            continue
        left, right = node.parents
        l_sp, r_sp = _sparse_leaf(left), _sparse_leaf(right)
        if not (l_sp or r_sp):
            continue
        if node.mul.name != "mul" or node.add.name != "sum":
            _decline(reasons, seg,
                     f"sparse operand under a ({node.mul.name},"
                     f"{node.add.name}) semiring: spmm covers (mul,sum) "
                     "only")
            continue
        if not _f32_acc(node):
            _decline(reasons, seg, "sparse operand with 64-bit "
                     "accumulation: spmm kernels accumulate in f32")
            continue
        if len(seg.nodes) == 1:
            if l_sp and r_sp and _same_source(left, right):
                claimed.add(seg.sid)
                units[seg.sid] = SpmmUnit("spmm_gram", node, plan=plan,
                                          seg=seg, x_id=left.id)
                continue
            if l_sp and r_sp:
                _decline(reasons, seg, "two distinct sparse operands: "
                         "spmm expects one sparse source")
                continue
            if l_sp and not isinstance(right, Small) \
                    and dtypes.is_floating(right.dtype):
                claimed.add(seg.sid)
                units[seg.sid] = SpmmUnit("spmm_xty", node, plan=plan,
                                          seg=seg, x_id=left.id,
                                          y_id=right.id)
                continue
            _decline(reasons, seg,
                     "sparse right operand: spmm computes sparseᵀ·dense "
                     "(put the sparse matrix on the left)" if r_sp else
                     "sparse left against a non-float right operand")
            continue
        # Multi-node segment: the wgram shape (its weights perhaps computed
        # by an absorbed dense prefix), or an absorbed dense prefix
        # computing the right operand of an xty.
        m = seg.nodes[-2]
        other = right if left is m else left if right is m else None
        if (isinstance(m, MapNode) and m.kind == "mapply_col"
                and m.fn_info["vudf"].name == "mul"
                and other is not None
                and not isinstance(other, Small)):
            xx, ww = m.parents
            if (_sparse_leaf(xx) and not _sparse_leaf(ww)
                    and not isinstance(ww, Small)
                    and _same_source(xx, other)
                    and dtypes.is_floating(ww.dtype)
                    and not _reads_sparse(seg.nodes[:-2])):
                claimed.add(seg.sid)
                units[seg.sid] = SpmmUnit("spmm_wgram", node, plan=plan,
                                          seg=seg, x_id=xx.id,
                                          w_id=ww.id, absorb=(m,))
                continue
        if l_sp and not isinstance(right, Small):
            if _reads_sparse(seg.nodes[:-1]):
                _decline(reasons, seg, "absorbed prefix reads the sparse "
                         "source: spmm feeds the leaf to the kernel "
                         "unseen")
                continue
            claimed.add(seg.sid)
            units[seg.sid] = SpmmUnit("spmm_xty", node, plan=plan, seg=seg,
                                      x_id=left.id, y_id=right.id)
            continue
        _decline(reasons, seg, "sparse contraction shape not covered by "
                 "spmm (gram / xty / weighted-gram)")
    return units


def _match_contractions(plan, ir, claimed, reasons=None):
    from ..kernels import common as kcommon  # noqa: F401  (import check)
    units = {}
    for seg in ir.segments:
        if seg.sid in claimed or seg.kind != "contraction":
            continue
        node = seg.root
        if len(seg.nodes) != 1 or not isinstance(node, InnerProdContractNode):
            continue
        if any(_sparse_leaf(p) for p in node.parents):
            _decline(reasons, seg, "sparse operand: dense gram/xty "
                     "kernels read dense tiles")
            continue
        if node.mul.name != "mul" or node.add.name != "sum":
            _decline(reasons, seg,
                     f"({node.mul.name},{node.add.name}) semiring: "
                     "gram/xty cover (mul,sum) only")
            continue
        if not _f32_acc(node):
            continue  # f64 accumulation: the generic trace keeps full precision
        if any(isinstance(p, Small) for p in node.parents):
            _decline(reasons, seg, "small broadcast operand: nothing to "
                     "stream through the contraction kernel")
            continue
        if not all(dtypes.is_floating(p.dtype) for p in node.parents):
            _decline(reasons, seg, "non-float operand: gram/xty are "
                     "MXU (floating) kernels")
            continue
        claimed.add(seg.sid)
        units[seg.sid] = ContractionUnit(node, seg.block_rows)
    return units


def _match_weighted_gram(plan, ir, claimed, reasons=None):
    """crossprod(X * w, X) — a contraction segment ending in one
    ``mapply_col(·, ·, mul)`` reweighting of the contraction's own source,
    after an optional absorbed row-local prefix computing w — →
    kernels.wgram.  XᵀWX is symmetric in which operand carries the
    weights, so both orientations match."""
    units = {}
    for seg in ir.segments:
        if seg.sid in claimed or seg.kind != "contraction":
            continue
        if len(seg.nodes) < 2:
            continue
        *prefix, m, node = seg.nodes
        if not isinstance(node, InnerProdContractNode) or \
                not isinstance(m, MapNode) or m.kind != "mapply_col":
            continue
        if _reads_sparse([node, m, *prefix]):
            _decline(reasons, seg, "sparse operand: the dense wgram "
                     "kernel reads dense tiles")
            continue
        if node.mul.name != "mul" or node.add.name != "sum":
            continue
        if m.fn_info["vudf"].name != "mul":
            _decline(reasons, seg, "absorbed mapply_col is not a mul "
                     "reweighting: not the XᵀWX shape")
            continue
        if not _f32_acc(node):
            continue
        left, right = node.parents
        other = right if left is m else left if right is m else None
        if other is None or isinstance(other, Small):
            continue
        xx, ww = m.parents
        if isinstance(xx, Small) or isinstance(ww, Small):
            continue
        if not _same_source(xx, other):
            _decline(reasons, seg, "reweighted matrix differs from the "
                     "contraction's other operand: not XᵀWX")
            continue  # weights against a different matrix: not XᵀWX
        if not all(dtypes.is_floating(p.dtype) for p in (xx, ww, other)):
            continue
        claimed.add(seg.sid)
        units[seg.sid] = WeightedGramUnit(node, xx.id, ww.id, seg.block_rows,
                                          plan=plan, prefix=prefix)
    return units


def _chain_acc_dtype(node) -> str | None:
    """Kernel accumulator dtype for an agg.col sink, or None if ineligible.

    Float accumulation runs in f32 (f64 keeps the generic trace's full
    precision); integer accumulation runs in i32 — EXACT for integer
    sums/counts, unlike the old f32-only kernel, which is what makes int
    apply→agg chains eligible (ROADMAP item)."""
    acc = dtypes.canon(node.acc_dtype)
    if acc == jnp.dtype(jnp.float32):
        return "float32"
    if acc.kind == "i":
        return "int32"
    return None


def _chain_source_ok(source) -> bool:
    """int64/f64 stay on the generic trace (no TPU-native 64-bit); bool
    sources have no meaningful sum/min/max algebra in the kernel."""
    dt = dtypes.canon(source.dtype)
    return dt.kind in ("i", "f") and dt.itemsize <= 4


def _match_apply_agg(plan, ir, claimed, reasons=None):
    _AGG_MAP = {"sum": "sum", "min": "min", "max": "max",
                "count": "count", "count_nonzero": "count_nonzero"}
    from ..kernels.fused_apply_agg import CHAIN_UNARIES
    # Group eligible chains by their shared source so N statistics become
    # one kernel call (one read of X).  Chains carry a per-chain accumulator
    # dtype, so float stats and exact integer counts share the call.
    by_source: dict[int, list] = {}
    for seg in ir.segments:
        if seg.sid in claimed or seg.kind != "sink_update":
            continue
        node = seg.root
        if node.kind != "agg_col" or node.agg.name not in _AGG_MAP:
            continue
        acc = _chain_acc_dtype(node)
        if acc is None:
            continue
        unaries = _is_pure_unary_chain(seg)
        if unaries is None or any(u not in CHAIN_UNARIES for u in unaries):
            continue
        source = seg.nodes[0].parents[0]
        if isinstance(source, Small) or not _chain_source_ok(source):
            continue
        if _sparse_leaf(source):
            _decline(reasons, seg, "sparse source: fused_apply_agg "
                     "streams dense tiles (implicit zeros participate "
                     "in the reduction via the generic trace)")
            continue
        by_source.setdefault(_source_key(source), []).append(
            (seg, source.id, (unaries, _AGG_MAP[node.agg.name], acc)))
    units = {}
    for entries in by_source.values():
        segs = [seg for seg, _, _ in entries]
        chains = tuple(chain for _, _, chain in entries)
        for seg in segs:
            claimed.add(seg.sid)
        units[segs[0].sid] = ApplyAggUnit(
            entries[0][1], chains, [seg.root for seg in segs],
            min(seg.block_rows for seg in segs))
    return units


def _single_node_seg(ir, node, kind=None):
    for seg in ir.segments:
        if seg.root is node and len(seg.nodes) == 1:
            if kind is None or seg.kind == kind:
                return seg
    return None


def _match_kmeans(plan, ir, claimed, reasons=None):
    """distances (squared_diff,sum) → which.min labels → groupby sums
    [+ counts, + wss] → kernels.kmeans_assign."""
    units = {}
    value_roots = {n.id for n in plan.row_local_roots + plan.saves}
    for seg in ir.segments:
        if seg.sid in claimed or seg.kind != "row_local":
            continue
        labels = seg.root
        if (len(seg.nodes) != 1 or not isinstance(labels, MapNode)
                or labels.kind != "agg_row"
                or labels.fn_info["vudf"].name != "which.min"):
            continue
        d = labels.parents[0]
        if (isinstance(d, Small) or not isinstance(d, MapNode)
                or d.kind != "matmul_small"
                or d.fn_info["mul"].name != "squared_diff"
                or d.fn_info["add"].name != "sum"
                or d.id in value_roots):
            continue
        x = d.parents[0]
        centers = d.parents[1]
        if (isinstance(x, Small) or not isinstance(centers, Small)
                or not dtypes.is_floating(x.dtype)
                or dtypes.canon(x.dtype) == jnp.dtype(jnp.float64)):
            continue
        if _sparse_leaf(x):
            _decline(reasons, seg, "sparse source: kmeans_assign reads "
                     "dense tiles")
            continue
        d_seg = _single_node_seg(ir, d)
        if d_seg is None or d_seg.sid in claimed:
            continue

        # Consumers of d: labels (+ optionally rowMins feeding the wss sink).
        d_consumers = ir.consumers.get(d.id, [])
        mind = None
        ok = True
        for c in d_consumers:
            if c is labels:
                continue
            if (isinstance(c, MapNode) and c.kind == "agg_row"
                    and c.fn_info["vudf"].name == "min" and mind is None
                    and c.id not in value_roots):
                mind = c
            else:
                ok = False
        if not ok:
            continue

        # Consumers of labels: the groupby sums sink (+ optionally counts).
        lab_consumers = ir.consumers.get(labels.id, [])
        sums = counts = None
        for c in lab_consumers:
            if (isinstance(c, GroupByRowNode) and c.agg.name == "sum"
                    and _same_source(c.parents[0], x)
                    and c.parents[1] is labels
                    and _f32_acc(c) and sums is None):
                sums = c
            elif (isinstance(c, GroupByRowNode) and c.agg.name == "count"
                  and c.parents[0] is labels and c.parents[1] is labels
                  and counts is None):
                counts = c
            else:
                ok = False
        if not ok or sums is None:
            continue
        sums_seg = _single_node_seg(ir, sums, "sink_update")
        counts_seg = (_single_node_seg(ir, counts, "sink_update")
                      if counts is not None else None)
        if sums_seg is None or (counts is not None and counts_seg is None):
            continue
        if counts is not None and sums.num_groups != counts.num_groups:
            continue

        # wss: AggFullNode(sum) exclusively over mind, absorbed in one seg.
        wss = wss_seg = None
        if mind is not None:
            mind_consumers = ir.consumers.get(mind.id, [])
            if (len(mind_consumers) == 1
                    and isinstance(mind_consumers[0], AggFullNode)
                    and mind_consumers[0].agg.name == "sum"
                    and _f32_acc(mind_consumers[0])):
                wss = mind_consumers[0]
                for s in ir.segments:
                    if s.root is wss and [n.id for n in s.nodes] == \
                            [mind.id, wss.id]:
                        wss_seg = s
            if wss is None or wss_seg is None or wss_seg.sid in claimed:
                continue  # mind exists but doesn't fold into the kernel

        group = [seg, d_seg, sums_seg] + \
            [s for s in (counts_seg, wss_seg) if s is not None]
        if any(s.sid in claimed for s in group):
            continue
        for s in group:
            claimed.add(s.sid)
        units[min(s.sid for s in group)] = KMeansUnit(
            x_id=x.id, centers_pos=plan._small_pos[id(centers)],
            labels=labels, sums=sums, counts=counts, wss=wss,
            block_rows=d_seg.block_rows)
    return units


def dispatch_report(plan, ir, backend: str) -> dict[int, str]:
    """Per-segment dispatch decision, for ``fm.explain``: replay the
    backend's matcher pipeline over a pass's IR (claiming but not lowering)
    and say which kernel claimed each segment — or why it falls back to the
    generic trace.  ``plan`` is the per-pass schedule the segments belong
    to (fusion.PassSchedule, or a one-pass Plan)."""
    backend = resolve_backend(backend)
    report: dict[int, str] = {}
    claimed: set[int] = set()
    reasons: dict[int, list] = {}
    if backend == "pallas":
        for matcher in PallasBackend.MATCHERS:
            before = set(claimed)
            placed = matcher(plan, ir, claimed, reasons=reasons)
            kernels = sorted({u.kernel for u in placed.values()})
            mname = matcher.__name__.lstrip("_")
            for sid, unit in placed.items():
                report[sid] = f"pallas:{unit.kernel} (claimed by {mname})"
            for sid in claimed - before:
                if sid not in placed:
                    # A member of a multi-segment kernel unit (the k-means
                    # group, sibling apply→agg chains folded into one call).
                    report[sid] = (f"fused into pallas:{'/'.join(kernels)} "
                                   f"(claimed by {mname})")
    for seg in ir.segments:
        if seg.sid in report:
            continue
        if seg.kind == "epilogue":
            report[seg.sid] = "post-merge epilogue (single launch per pass)"
        elif backend != "pallas":
            report[seg.sid] = "xla generic trace"
        elif dtypes.canon(seg.dtype).itemsize >= 8:
            report[seg.sid] = ("generic trace (64-bit dtype: kernels keep "
                               "full precision on the XLA path)")
        elif seg.sid in reasons:
            # ISSUE 10: say WHY every matcher that inspected the segment
            # passed on it — auditable sparse-vs-dense dispatch.
            why = "; ".join(dict.fromkeys(reasons[seg.sid]))
            report[seg.sid] = f"generic trace (declined: {why})"
        else:
            report[seg.sid] = "generic trace (no kernel pattern matched)"
    return report


class PallasBackend(Backend):
    """Lower eligible segments onto the Pallas kernels; generic fallback
    for the rest.  Matchers run in order and claim segments by sid."""

    name = "pallas"
    # _match_spmm runs before the dense contraction matchers: a sparse
    # segment either lowers onto the spmm kernels or records why not —
    # the dense kernels never see a SparseBlock operand.
    MATCHERS = [_match_kmeans, _match_spmm, _match_weighted_gram,
                _match_contractions, _match_apply_agg]

    def lower(self, plan, ir) -> LoweredProgram:
        claimed: set[int] = set()
        placed: dict[int, object] = {}
        for matcher in self.MATCHERS:
            placed.update(matcher(plan, ir, claimed))
        units = []
        for seg in ir.segments:
            if seg.kind == "epilogue":
                continue  # post-merge math: LoweredProgram.epilogue, once
            if seg.sid in placed:
                units.append(placed[seg.sid])
            elif seg.sid not in claimed:
                units.append(GenericUnit(plan, seg))
        return LoweredProgram(plan, ir, self.name, units)


register_backend("xla", XlaBackend())
register_backend("pallas", PallasBackend())
