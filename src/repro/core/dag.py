"""Expression DAG for lazy evaluation (paper §III-E).

A lazily evaluated GenOp outputs a *virtual matrix* capturing the
computation and references to its input matrices.  The DAG has two node
classes, exactly as the paper's Fig. 5 distinguishes:

* **row-local nodes** ("the first type ... generates matrices with the same
  long dimension size as the input matrices") — sapply/mapply/mapply.row/
  mapply.col/agg.row-on-tall/cbind/inner-product-with-a-small-matrix.
  These fuse: partition *i* of the output needs only partitions *i* of the
  parents, so an entire chain streams through the fast tier one partition at
  a time.
* **sink nodes** ("the second type ... generates matrices with different
  long dimension sizes") — agg/agg.col-on-tall/groupby.row/inner-product
  contracting the long dimension.  Sinks produce per-partition *partials*
  merged with the aggregation VUDF's ``combine`` (paper §III-F: "each thread
  computes partial aggregation results independently ... in the end,
  FlashMatrix merges the partial aggregation results").

Classification is by actual long-dimension algebra, not by operator name:
``fm.agg.row`` on a tall matrix keeps the long dimension (an n-vector), so
it is row-local and fusable; ``fm.agg.col`` on the same matrix contracts the
long dimension and is a sink.

All virtual matrices in one DAG share the same long dimension (paper
§III-E), which `fusion.Plan` validates.
"""
from __future__ import annotations

import dataclasses
import itertools
from typing import Any, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from . import dtypes, vudf as vudf_mod
from .matrix import FMMatrix, DenseStore
from .sparse import SparseBlock

_ids = itertools.count()

#: Ops that only ever run in the plan EPILOGUE (post-sink small-tier math):
#: they are classified post-sink even when their operands are physical, so
#: e.g. ``solve`` is never streamed through the partition loop.
EPILOGUE_ONLY_KINDS = frozenset({"solve"})

#: Matmul precision of every f32 contraction the eval rules trace.  The
#: TPU's default f32 matmul rounds operands to bf16 in one MXU pass, which
#: costs IRLS's X·β and XᵀWX about three significant digits; elsewhere the
#: setting is a no-op.
_F32 = jax.lax.Precision.HIGHEST


# ---------------------------------------------------------------------------
# Operands
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Small:
    """A broadcast operand: a scalar or a small physical array that is
    replicated to every partition (the paper's computation-node "immutable
    computation state, such as scalar variables and small matrices")."""

    value: Any  # python scalar or jnp array

    @property
    def dtype(self):
        if hasattr(self.value, "dtype"):
            return dtypes.canon(self.value.dtype)
        if isinstance(self.value, bool):
            return jnp.dtype(jnp.bool_)
        if isinstance(self.value, int):
            return jnp.dtype(jnp.int64)
        return jnp.dtype(jnp.float32)


Operand = Union["Node", Small]


# ---------------------------------------------------------------------------
# Nodes
# ---------------------------------------------------------------------------

class Node:
    """Base DAG node.  ``shape`` is the logical (nrow, ncol) of the output;
    row-local nodes always have nrow == the DAG's long dimension."""

    kind: str = "?"

    def __init__(self, shape, dtype, parents: Sequence[Operand], name=""):
        self.id = next(_ids)
        self.shape = (int(shape[0]), int(shape[1]))
        self.dtype = dtypes.canon(dtype)
        self.parents = list(parents)
        self.name = name or f"{self.kind}#{self.id}"
        # Materialization control (paper: fm.set.mate.level / write-through
        # cache).  None = stay virtual; 'device' | 'host' | 'disk' = persist
        # the materialized partitions during the next DAG execution ('disk'
        # streams them into an on-disk matrix — write-through spill).
        self.save: Optional[str] = None

    # Row-local nodes implement block_eval; sinks implement the
    # identity/update/combine/finalize quartet.
    @property
    def is_sink(self) -> bool:
        return False

    @property
    def nrow(self):
        return self.shape[0]

    @property
    def ncol(self):
        return self.shape[1]

    def parent_nodes(self):
        return [p for p in self.parents if isinstance(p, Node)]

    def flops_per_row(self) -> float:
        """FLOPs per long-dim element — feeds the complexity/roofline counters."""
        return 0.0

    def __repr__(self):
        return f"<{self.kind} {self.name} {self.shape} {self.dtype.name}>"


class LeafNode(Node):
    kind = "leaf"

    def __init__(self, mat: FMMatrix):
        super().__init__(mat.shape, mat.dtype, [], name=mat.name or None)
        self.mat = mat

    def block_eval(self, blocks, offset):
        raise AssertionError("leaves are sliced by the executor, not evaluated")


class MapNode(Node):
    """Row-local computation node.  ``op`` dispatches the eval rule."""

    def __init__(self, op: str, shape, dtype, parents, fn_info, name=""):
        self.kind = op
        super().__init__(shape, dtype, parents, name)
        self.fn_info = fn_info  # op-specific payload (VUDFs, axes, ...)

    def flops_per_row(self) -> float:
        info = self.fn_info
        op = self.kind
        if op in ("sapply", "mapply", "mapply_row", "mapply_col"):
            return info["vudf"].flops * self.ncol
        if op == "agg_row":
            return info["vudf"].flops * self.parents[0].shape[1]
        if op == "matmul_small":
            k = self.parents[0].shape[1]
            return 2.0 * k * self.ncol  # f1+f2 per (col, k)
        if op == "groupby_col":
            return self.parents[0].shape[1]
        return 0.0

    # -- evaluation ----------------------------------------------------------
    def block_eval(self, blocks, offset):
        """blocks: list of per-parent partition arrays (Small operands appear
        as their raw values).  offset: global row offset of this partition."""
        op = self.kind
        info = self.fn_info
        if op == "sapply":
            return info["vudf"].fn(blocks[0])
        if op == "mapply":
            return info["vudf"].fn(blocks[0], blocks[1])
        if op == "mapply_row":
            # CC_ij = f(AA_ij, B_j): vector indexed by column -> broadcast row.
            v = blocks[1]
            v = v.reshape(1, -1)
            return info["vudf"].fn(blocks[0], v)
        if op == "mapply_col":
            # CC_ij = f(AA_ij, B_i): vector indexed by row -> partitioned
            # alongside the matrix (a one-column long operand).
            v = blocks[1]
            v = v.reshape(-1, 1)
            return info["vudf"].fn(blocks[0], v)
        if op == "agg_row":
            agg = info["vudf"]
            part = agg.aggregate(blocks[0], 1, 0)
            out = agg.finalize(part)
            return out.reshape(-1, 1)
        if op == "cbind":
            cols = [b if b.ndim == 2 else b.reshape(-1, 1) for b in blocks]
            return jnp.concatenate(cols, axis=1)
        if op == "matmul_small":
            return _inner_prod_block(blocks[0], blocks[1],
                                     info["mul"], info["add"], self.dtype)
        if op == "groupby_col":
            # CC_{i,k} = agg over columns j with labels[j]==k; row-local.
            agg_name = info["vudf"].name
            labels = blocks[1].reshape(-1).astype(jnp.int32)
            k = info["num_groups"]
            onehot = jax.nn.one_hot(labels, k, dtype=blocks[0].dtype)
            if agg_name in ("sum", "count", "count_nonzero"):
                base = blocks[0]
                if agg_name == "count":
                    base = jnp.ones_like(base)
                elif agg_name == "count_nonzero":
                    base = (base != 0).astype(base.dtype)
                return jnp.matmul(base, onehot, precision=_F32)
            raise NotImplementedError(
                f"groupby_col with agg {agg_name!r}; supported: sum/count")
        if op == "solve":
            # Epilogue-only op (EPILOGUE_ONLY_KINDS): a·x = b on the merged
            # sink values.  One same-precision iterative-refinement step
            # recovers most of the accuracy the old eager float64 small-tier
            # path had; the system is p×p so the extra solve is free.
            a = blocks[0].astype(self.dtype)
            b = blocks[1]
            if b.ndim == 1:
                b = b.reshape(-1, 1)
            elif b.shape[0] != a.shape[0]:
                b = b.reshape(a.shape[0], -1)  # (1, n) vector sink → column
            b = b.astype(self.dtype)
            x = jnp.linalg.solve(a, b)
            r = b - jnp.matmul(a, x, precision=_F32)
            return x + jnp.linalg.solve(a, r)
        raise AssertionError(f"unknown map op {op}")


def _inner_prod_block(a_blk, b_small, mul: vudf_mod.BinaryVUDF,
                      add: vudf_mod.AggVUDF, out_dtype):
    """inner.prod(tall, small): t = f1(A_ik, B_kj); C_ij = f2-reduce_k t.

    Paper §III-C: for the (mul, sum) semiring on floating types use BLAS —
    our analog is the MXU via jnp.matmul.  General semirings evaluate f1 on a
    broadcast (rows, k, ncol_out) tile; k and ncol_out are small by
    definition of this GenOp so the tile stays cache/VMEM-resident.

    A sparse (ELL) left operand with the (mul, sum) semiring takes the
    gather path — out[i,j] = Σ_k vals[i,k]·B[cols[i,k], j] — so ``X @ beta``
    over a one-hot matrix does nnz-proportional work; other semirings
    densify the block first (implicit zeros participate in e.g. a min
    reduction, so the dense evaluation is the correct semantics).
    """
    if isinstance(a_blk, SparseBlock):
        if mul.name == "mul" and add.name == "sum":
            return a_blk.matmul_small(b_small, out_dtype)
        a_blk = a_blk.todense()
    if mul.name == "mul" and add.name == "sum" and dtypes.is_floating(out_dtype):
        return jnp.matmul(a_blk, b_small, precision=_F32).astype(out_dtype)
    t = mul.fn(a_blk[:, :, None], b_small[None, :, :])
    part = add.aggregate(t, 1, 0)
    return add.finalize(part).astype(out_dtype)


class SinkNode(Node):
    """Long-dimension-contracting node: evaluated as identity → per-partition
    update → pairwise combine → finalize."""

    @property
    def is_sink(self) -> bool:
        return True

    def identity(self):
        raise NotImplementedError

    def block_update(self, acc, blocks, offset):
        raise NotImplementedError

    def combine(self, a, b):
        raise NotImplementedError

    def finalize(self, acc):
        return acc


class AggFullNode(SinkNode):
    kind = "agg"

    def __init__(self, parent: Node, agg: vudf_mod.AggVUDF):
        out_dt = agg.out_dtype(parent.dtype)
        super().__init__((1, 1), out_dt, [parent], name=f"agg[{agg.name}]")
        self.agg = agg
        self.acc_dtype = _acc_dtype(agg, parent.dtype)

    def flops_per_row(self) -> float:
        return self.agg.flops * self.parents[0].shape[1]

    def identity(self):
        return self.agg.identity((), self.acc_dtype)

    def block_update(self, acc, blocks, offset):
        part = self.agg.aggregate(blocks[0], None, offset)
        return self.agg.combine(acc, part)

    def combine(self, a, b):
        return self.agg.combine(a, b)

    def finalize(self, acc):
        out = self.agg.finalize(acc)
        return jnp.asarray(out).reshape(1, 1)


class AggColNode(SinkNode):
    """Per-column aggregation over the long (row) dimension: C_j."""

    kind = "agg_col"

    def __init__(self, parent: Node, agg: vudf_mod.AggVUDF):
        out_dt = agg.out_dtype(parent.dtype)
        super().__init__((1, parent.ncol), out_dt, [parent],
                         name=f"agg.col[{agg.name}]")
        self.agg = agg
        self.acc_dtype = _acc_dtype(agg, parent.dtype)

    def flops_per_row(self) -> float:
        return self.agg.flops * self.parents[0].shape[1]

    def identity(self):
        return self.agg.identity((self.ncol,), self.acc_dtype)

    def block_update(self, acc, blocks, offset):
        part = self.agg.aggregate(blocks[0], 0, offset)
        return self.agg.combine(acc, part)

    def combine(self, a, b):
        return self.agg.combine(a, b)

    def finalize(self, acc):
        return self.agg.finalize(acc).reshape(1, -1)


class GroupByRowNode(SinkNode):
    """fm.groupby.row: CC_{k,j} = agg over rows i with labels[i]==k.

    The clustering/classification workhorse (paper §III-C) — and, in the LM
    stack, the combine path of MoE expert dispatch (DESIGN.md §1.4).
    """

    kind = "groupby_row"

    _AT_OPS = {"sum": "add", "count": "add", "count_nonzero": "add",
               "min": "min", "max": "max"}

    def __init__(self, parent: Node, labels: Node, agg: vudf_mod.AggVUDF,
                 num_groups: int):
        if agg.name not in self._AT_OPS:
            raise NotImplementedError(
                f"groupby.row supports {sorted(self._AT_OPS)} aggregation, "
                f"got {agg.name!r}")
        out_dt = agg.out_dtype(parent.dtype)
        super().__init__((num_groups, parent.ncol), out_dt, [parent, labels],
                         name=f"groupby.row[{agg.name}]")
        self.agg = agg
        self.num_groups = num_groups
        self.acc_dtype = _acc_dtype(agg, parent.dtype)

    def flops_per_row(self) -> float:
        return self.parents[0].shape[1]

    def identity(self):
        return self.agg.identity((self.num_groups, self.ncol), self.acc_dtype)

    def block_update(self, acc, blocks, offset):
        vals, labels = blocks[0], blocks[1].reshape(-1).astype(jnp.int32)
        if self.agg.name == "count":
            vals = jnp.ones_like(vals, self.acc_dtype)
        elif self.agg.name == "count_nonzero":
            vals = (vals != 0).astype(self.acc_dtype)
        else:
            vals = vals.astype(self.acc_dtype)
        ref = acc.at[labels]
        part = getattr(ref, self._AT_OPS[self.agg.name])(
            vals, mode="drop", unique_indices=False)
        return part

    def combine(self, a, b):
        return self.agg.combine(a, b)

    def finalize(self, acc):
        return self.agg.finalize(acc)


class InnerProdContractNode(SinkNode):
    """inner.prod contracting the long dimension: C = f2-reduce_i f1(tA_i, B_i).

    This is ``fm.inner.prod(wide, tall)`` with the wide matrix expressed as
    the lazy transpose of a long-aligned operand (the common R form
    ``t(X) %*% Y``, e.g. Gram matrices for correlation/SVD and t(R) %*% X in
    the GMM M-step).  Per partition: partial = f2-reduce over the partition's
    rows; partials combine with f2 — the exact paper decomposition, and the
    pattern the `kernels/gram.py` Pallas kernel implements on TPU.
    """

    kind = "inner_prod"

    def __init__(self, left: Node, right: Node, mul: vudf_mod.BinaryVUDF,
                 add: vudf_mod.AggVUDF):
        out_dt = add.out_dtype(mul.out_dtype(left.dtype, right.dtype))
        super().__init__((left.ncol, right.ncol), out_dt, [left, right],
                         name=f"inner[{mul.name},{add.name}]")
        self.mul, self.add = mul, add
        self.acc_dtype = _acc_dtype(add, mul.out_dtype(left.dtype, right.dtype))

    def flops_per_row(self) -> float:
        return 2.0 * self.shape[0] * self.shape[1]

    def identity(self):
        return self.add.identity(self.shape, self.acc_dtype)

    def block_update(self, acc, blocks, offset):
        a_blk, b_blk = blocks  # both (rows, p) row-aligned
        if (self.mul.name == "mul" and self.add.name == "sum"
                and dtypes.is_floating(self.acc_dtype)):
            part = jnp.matmul(a_blk.T.astype(self.acc_dtype),
                              b_blk.astype(self.acc_dtype), precision=_F32)
            return self.add.combine(acc, part)
        t = self.mul.fn(a_blk[:, :, None], b_blk[:, None, :])
        part = self.add.aggregate(t, 0, offset)
        return self.add.combine(acc, part)

    def combine(self, a, b):
        return self.add.combine(a, b)

    def finalize(self, acc):
        return self.add.finalize(acc).astype(self.dtype)


def _acc_dtype(agg: vudf_mod.AggVUDF, in_dtype):
    """Accumulator dtype: widen low-precision floats so long streaming
    reductions keep precision (bf16 inputs accumulate in f32) — the TPU
    analog of the paper accumulating in registers wider than the data."""
    out = agg.out_dtype(in_dtype)
    if out == jnp.dtype(jnp.bfloat16):
        return jnp.dtype(jnp.float32)
    return out


# ---------------------------------------------------------------------------
# Graph utilities
# ---------------------------------------------------------------------------

def as_node(mat_or_node) -> Node:
    if isinstance(mat_or_node, Node):
        return mat_or_node
    if isinstance(mat_or_node, FMMatrix):
        if mat_or_node.is_virtual:
            return mat_or_node.node
        return LeafNode(mat_or_node)
    raise TypeError(type(mat_or_node))


def wrap(node: Node, name: str = "") -> FMMatrix:
    """Wrap a node as a virtual FMMatrix handle."""
    return FMMatrix(node.shape, node.dtype, node=node, name=name or node.name)


def toposort(roots: Sequence[Node]) -> list[Node]:
    seen: dict[int, Node] = {}
    order: list[Node] = []

    def visit(n: Node):
        if n.id in seen:
            return
        seen[n.id] = n
        for p in n.parent_nodes():
            visit(p)
        order.append(n)

    for r in roots:
        visit(r)
    return order


def schedule_passes(order: Sequence[Node], is_source, long_dim: int):
    """Multi-pass schedule of a DAG cut (paper §III-E/F generalized).

    Classifies every executable node in ``order`` (topological) as

    * ``'loop'`` — streams through the partition loop: row-local nodes and
      long-dimension-contracting sinks; or
    * ``'epi'``  — post-sink *epilogue* math evaluated once after a pass's
      partial merge (``colSums(X)/n``, ``solve(XᵀWX, XᵀWz)``, and sinks
      whose operands are themselves merged values),

    and assigns each a **pass number**.  A loop node that consumes a merged
    value (a sink or epilogue result) cannot run in the pass that produces
    it — its operand only exists after that pass's merge — so it is
    scheduled one pass later, with the merged value bound as a broadcast
    small (the FlashR ``scale(X)`` shape: pass 1 streams the moment sinks +
    epilogue, pass 2 re-streams X with the moments bound).  Pass numbers
    chain transitively, so moment-of-a-moment programs schedule as three
    passes, and so on.

    Returns ``(roles, passno)`` dicts keyed by node id.  Raises for the one
    genuinely unschedulable shape: an epilogue-only op (``solve``) over a
    streaming *intermediate*, whose value would have to be materialized.
    """
    roles: dict[int, str] = {}
    passno: dict[int, int] = {}
    for n in order:
        if is_source(n):
            continue
        has_stream = False
        stream_pass = 0
        merged_pass = -1
        for p in n.parents:
            if isinstance(p, Small):
                continue
            if is_source(p):
                if p.shape[0] == long_dim and max(p.shape) > 1:
                    has_stream = True
                continue
            if roles[p.id] == "loop" and not p.is_sink:
                has_stream = True
                stream_pass = max(stream_pass, passno[p.id])
            else:  # merged value: a sink or an epilogue node
                merged_pass = max(merged_pass, passno[p.id])
        if n.kind in EPILOGUE_ONLY_KINDS:
            for p in n.parents:
                if (isinstance(p, Node) and not is_source(p)
                        and roles[p.id] == "loop" and not p.is_sink):
                    raise ValueError(
                        f"epilogue op {n.name} consumes the streaming "
                        f"intermediate {p.name}: {n.kind} may only touch "
                        f"aggregation results, small operands or other "
                        f"epilogue values inside one DAG — materialize "
                        f"{p.name} first (it needs its own pass)")
            roles[n.id] = "epi"
            passno[n.id] = max(merged_pass, 0)
        elif not has_stream and merged_pass >= 0:
            # Small post-merge math: runs in the owning pass's epilogue.
            roles[n.id] = "epi"
            passno[n.id] = merged_pass
        else:
            roles[n.id] = "loop"
            passno[n.id] = max(stream_pass, merged_pass + 1)
    return roles, passno


def post_sink_ids(order: Sequence[Node], is_source=None) -> set:
    """Ids of nodes DOWNSTREAM of a sink within ``order`` — the plan's
    *epilogue* set (paper §III-E post-aggregation math like
    ``colSums(X) / n``).  Such a node's operands only exist after the
    partition-loop partial merge, so it cannot run inside the loop; the
    engine evaluates the whole set once, after the merge
    (fusion.Plan → lowering.LoweredProgram.epilogue).

    ``is_source`` marks cut points (previously persisted nodes count as
    sources, not sinks); epilogue-only ops (``solve``) are always post-sink.
    """
    src = is_source or (lambda n: isinstance(n, LeafNode)
                        or getattr(n, "cached_store", None) is not None)
    post: set = set()
    for n in order:
        if src(n):
            continue
        if n.kind in EPILOGUE_ONLY_KINDS or any(
                isinstance(p, Node) and not src(p)
                and (p.is_sink or p.id in post)
                for p in n.parents):
            post.add(n.id)
    return post


def long_dim_of(roots: Sequence[Node]) -> int:
    """All matrices in a DAG share one streaming dimension (paper §III-E).

    The partition axis is uniformly ROWS (shape[0]) — wide matrices are
    simply short streams (the paper handles them as transposed-tall groups;
    our lazy transpose feeds `inner_prod` the tall orientation, so by the
    time a node is in a DAG its rows are the stream)."""
    # Cut-aware walk: a previously-persisted node (cached_store) is a
    # SOURCE of this cut — its upstream DAG belongs to other plans and must
    # not constrain this plan's streaming dimension.
    seen: set = set()
    order: list[Node] = []

    def visit(n: Node):
        if n.id in seen:
            return
        seen.add(n.id)
        if getattr(n, "cached_store", None) is None:
            for p in n.parent_nodes():
                visit(p)
        order.append(n)

    for r in roots:
        visit(r)
    post = post_sink_ids(order)
    consumers: dict = {}
    for n in order:
        for p in n.parent_nodes():
            consumers.setdefault(p.id, []).append(n)
    dims = set()
    for n in order:
        if n.id in post:
            continue  # epilogue math is small-tier: exempt from streaming
        if (isinstance(n, LeafNode)
                or getattr(n, "cached_store", None) is not None):
            cons = consumers.get(n.id, [])
            if cons and all(c.id in post for c in cons):
                continue  # epilogue-only operand (e.g. a ridge eye matrix)
            if max(n.shape) > 1:
                dims.add(n.shape[0])
        elif not n.is_sink:
            dims.add(n.shape[0])
        else:
            for p in n.parent_nodes():
                if not p.is_sink and p.id not in post:
                    dims.add(p.shape[0])
    dims.discard(1)
    if len(dims) > 1:
        raise ValueError(
            f"all matrices in one DAG must share the streaming (row) "
            f"dimension; got {sorted(dims)}")
    return dims.pop() if dims else 1
