"""R-base-like matrix API reimplemented on GenOps (paper Table III).

The paper's whole point: users write ordinary R matrix code and the engine
runs it parallel + out-of-core.  `FM` wraps an FMMatrix handle with R's
operator vocabulary; every method lowers to a GenOp, so an arbitrary chain
of these calls builds one lazy DAG that `fm.materialize` fuses.

    >>> X = fm.runif_matrix(1_000_000, 16)
    >>> Z = (X - colMeans(X)) / colSds(X)      # standardize (lazy GenOps)
    >>> G = crossprod(Z)                       # Gram sink
    >>> (G,) = fm.materialize(G)               # ONE call, two scheduled passes

(colMeans/colSds are pure lazy chains — a colSums sink plus post-sink
epilogue math evaluated once after the partition-loop merge; recycling
them across X is a lazy sweep too, so the whole standardize-then-Gram
program is ONE DAG that the multi-pass planner runs as moment pass →
sweep+Gram pass inside a single materialize.)

All functions accept and return `FM`.  `conv_FM2R` drops to numpy.
"""
from __future__ import annotations

import math
import warnings
from typing import Optional

import jax.numpy as jnp
import numpy as np

from . import genops, materialize as mat_mod, matrix as matrix_mod
from .dag import as_node
from .matrix import FMMatrix


class FM:
    """R-flavoured wrapper around an FMMatrix handle (virtual or physical)."""

    __slots__ = ("m",)

    def __init__(self, m: FMMatrix):
        self.m = m

    # -- shape ---------------------------------------------------------------
    @property
    def shape(self):
        return self.m.shape

    @property
    def nrow(self):
        return self.m.nrow

    @property
    def ncol(self):
        return self.m.ncol

    @property
    def dtype(self):
        return self.m.dtype

    @property
    def is_virtual(self):
        return self.m.is_virtual

    def __repr__(self):
        return f"FM({self.m!r})"

    # -- element-wise binary (auto row/col recycling like R sweep) -----------
    def _bin(self, other, op):
        if isinstance(other, FM):
            if other.shape == self.shape:
                return FM(genops.mapply(self.m, other.m, op))
            return self._recycle(other, op)
        return FM(genops.mapply(self.m, other, op))

    def _rbin(self, other, op):
        # scalar/array `other` on the left.
        return FM(genops.mapply(other, self.m, op))

    def _recycle(self, other: "FM", op):
        """R-style recycling of a vector across a matrix: a length-ncol
        vector applies per row (mapply.row); length-nrow per column
        (mapply.col).

        A VIRTUAL length-ncol vector (``X - colMeans(X)``) stays lazy: the
        sweep becomes a DAG edge and the multi-pass planner schedules
        moment pass → sweep pass automatically — one materialize, two
        streaming passes.  Physical vectors broadcast eagerly as before.

        Ambiguity rule: when the matrix is square (nrow == ncol), a
        length-n vector pairs with the ROW INDEX (mapply.col) — R stores
        matrices column-major, so recycling walks down each column.
        """
        n = max(other.shape)
        if min(other.shape) != 1:
            raise ValueError(
                f"recycling needs a vector (an n×1 or 1×n matrix); got "
                f"shape {other.shape} against {self.shape} — for "
                f"elementwise matrix∘matrix the shapes must match exactly")
        if n == self.ncol and n != self.nrow:
            vec = other.m if other.m.is_virtual else _vec_data(other.m)
            return FM(genops.mapply_row(self.m, vec, op))
        if n == self.nrow:
            # Includes the square-matrix case: R's column-major recycling
            # pairs vector element i with row i.
            return FM(genops.mapply_col(self.m, other.m, op))
        raise ValueError(
            f"cannot recycle a length-{n} vector across a "
            f"{self.nrow}×{self.ncol} matrix: R recycling needs length "
            f"{self.nrow} (pairs with each row index, mapply.col) or "
            f"{self.ncol} (pairs with each column index, mapply.row)")

    def __add__(self, o):
        return self._bin(o, "add")

    def __radd__(self, o):
        return self._rbin(o, "add")

    def __sub__(self, o):
        return self._bin(o, "sub")

    def __rsub__(self, o):
        return self._rbin(o, "sub")

    def __mul__(self, o):
        return self._bin(o, "mul")

    def __rmul__(self, o):
        return self._rbin(o, "mul")

    def __truediv__(self, o):
        return self._bin(o, "div")

    def __rtruediv__(self, o):
        return self._rbin(o, "div")

    def __pow__(self, o):
        if isinstance(o, (int, float)) and o == 2:
            return FM(genops.sapply(self.m, "sq"))
        return self._bin(o, "pow")

    def __neg__(self):
        return FM(genops.sapply(self.m, "neg"))

    def __eq__(self, o):  # noqa: A003 - R semantics, not identity
        return self._bin(o, "eq")

    def __ne__(self, o):
        return self._bin(o, "neq")

    def __lt__(self, o):
        return self._bin(o, "lt")

    def __le__(self, o):
        return self._bin(o, "le")

    def __gt__(self, o):
        return self._bin(o, "gt")

    def __ge__(self, o):
        return self._bin(o, "ge")

    def __hash__(self):
        return id(self)

    # -- matmul ---------------------------------------------------------------
    def __matmul__(self, o):
        """%*%: matrix multiplication with the (mul, sum) semiring — the
        paper dispatches floating-point cases to BLAS; ours go to the MXU."""
        rhs = o.m if isinstance(o, FM) else o
        return FM(genops.inner_prod(self.m, rhs, "mul", "sum"))

    # -- transforms -------------------------------------------------------------
    def t(self) -> "FM":
        return FM(self.m.transpose())

    @property
    def T(self) -> "FM":
        return self.t()


def _vec_data(m: FMMatrix):
    if m.is_virtual:
        (m,) = mat_mod.materialize(m)
    return jnp.asarray(np.asarray(m.logical_data())).reshape(-1)


# ---------------------------------------------------------------------------
# Free functions (R vocabulary)
# ---------------------------------------------------------------------------

def _fm(x) -> FMMatrix:
    return x.m if isinstance(x, FM) else x


def sapply(x, f) -> FM:
    return FM(genops.sapply(_fm(x), f))


def mapply(a, b, f) -> FM:
    return FM(genops.mapply(_fm(a), _fm(b) if isinstance(b, FM) else b, f))


def mapply_row(a, vec, f) -> FM:
    return FM(genops.mapply_row(_fm(a), _fm(vec) if isinstance(vec, FM) else vec, f))


def mapply_col(a, vec, f) -> FM:
    return FM(genops.mapply_col(_fm(a), _fm(vec) if isinstance(vec, FM) else vec, f))


def inner_prod(a, b, f1="mul", f2="sum") -> FM:
    return FM(genops.inner_prod(_fm(a), _fm(b) if isinstance(b, FM) else b, f1, f2))


def agg(x, f) -> FM:
    return FM(genops.agg(_fm(x), f))


def agg_row(x, f) -> FM:
    return FM(genops.agg_row(_fm(x), f))


def agg_col(x, f) -> FM:
    return FM(genops.agg_col(_fm(x), f))


def groupby_row(x, labels, f, num_groups: int) -> FM:
    return FM(genops.groupby_row(_fm(x), _fm(labels) if isinstance(labels, FM)
                                 else labels, f, num_groups))


def groupby_col(x, labels, f, num_groups: int) -> FM:
    return FM(genops.groupby_col(_fm(x), labels, f, num_groups))


def cbind(*xs) -> FM:
    return FM(genops.cbind(*[_fm(x) for x in xs]))


# element-wise sugar
def sqrt(x) -> FM:
    return sapply(x, "sqrt")


def exp(x) -> FM:
    return sapply(x, "exp")


def log(x) -> FM:
    return sapply(x, "log")


def log1p(x) -> FM:
    return sapply(x, "log1p")


def sigmoid(x) -> FM:
    """1 / (1 + exp(-x)) — the logistic link inverse (GLM/IRLS)."""
    return sapply(x, "sigmoid")


def abs_(x) -> FM:
    return sapply(x, "abs")


def pmin(a, b) -> FM:
    return mapply(a, b, "pmin")


def pmax(a, b) -> FM:
    return mapply(a, b, "pmax")


def ifelse0(x, mask) -> FM:
    return mapply(x, mask, "ifelse0")


def is_na(x) -> FM:
    return sapply(x, "isna")


# aggregates (R names)
def sum_(x) -> FM:
    return agg(x, "sum")


def rowSums(x) -> FM:
    return agg_row(x, "sum")


def colSums(x) -> FM:
    return agg_col(x, "sum")


def rowMins(x) -> FM:
    return agg_row(x, "min")


def colMins(x) -> FM:
    return agg_col(x, "min")


def rowMaxs(x) -> FM:
    return agg_row(x, "max")


def colMaxs(x) -> FM:
    return agg_col(x, "max")


def which_min_row(x) -> FM:
    """R's max.col(-X) / apply(X, 1, which.min), zero-based."""
    return agg_row(x, "which.min")


def which_max_row(x) -> FM:
    return agg_row(x, "which.max")


def any_(x) -> FM:
    return agg(x, "any")


def all_(x) -> FM:
    return agg(x, "all")


def colMeans(x) -> FM:
    """R colMeans — a pure lazy chain: the colSums sink divided by n in the
    plan EPILOGUE (post-sink lazy math, evaluated once after the
    partition-loop merge), so colMeans fuses into whatever pass
    materializes it.  Recycling across the matrix (``X - colMeans(X)``)
    stays lazy too: the planner schedules the sweep one pass after the
    moment pass, all inside one materialize."""
    return colSums(x) / float(_fm(x).nrow)


def rowMeans(x) -> FM:
    """R rowMeans — row-local and LAZY (keeps the long dimension), unlike
    the sink-backed colMeans."""
    return rowSums(x) / float(_fm(x).ncol)


def colSds(x) -> FM:
    """Column standard deviations (matrixStats::colSds), fully lazy: the
    colSums and colSums(x²) sinks co-materialize in ONE streaming pass and
    sqrt((Σx² − (Σx)²/n)/(n−1)) runs as an epilogue chain in the same
    plan — nothing computes until the result is materialized."""
    n = float(_fm(x).nrow)
    s, s2 = colSums(x), colSums(x ** 2)
    var = (s2 - s * s / n) / (n - 1.0)
    return sqrt(pmax(var, 0.0))


def mean_(x) -> FM:
    """R mean(): grand mean over all elements — a lazy epilogue scalar
    (1×1); use ``fm.as_scalar`` for a python float."""
    m = _fm(x)
    return agg(x, "sum") / float(m.nrow * m.ncol)


def sweep(x, margin: int, stat, fun: str = "sub") -> FM:
    """R sweep(): apply ``fun`` between X and a summary statistic vector.

    ``margin=2`` pairs ``stat`` with each column index (``mapply.row``);
    ``margin=1`` with each row index (``mapply.col``).  ``stat`` may be a
    LAZY vector (``sweep(X, 2, colMeans(X))``): the whole expression stays
    one DAG and the multi-pass planner schedules the moment pass and the
    sweep pass inside a single materialize."""
    if margin == 2:
        return mapply_row(x, stat, fun)
    if margin == 1:
        return mapply_col(x, stat, fun)
    raise ValueError(f"sweep margin must be 1 (rows) or 2 (columns), "
                     f"got {margin!r}")


def scale(x, center=True, scale=True, save: Optional[str] = None) -> FM:
    """R scale(): center/standardize columns — a PURE LAZY chain.

    Nothing computes here: the moment sinks (colSums, colSums(x²)), their
    epilogue math and the sweeps are one DAG, and ``fm.materialize``
    schedules it as moment pass → sweep pass automatically (TWO streaming
    passes over X, one plan-cache entry, ``exec_stats()['passes'] == 2``).
    The standardized matrix also fuses into a downstream Gram or IRLS pass
    — FlashR's ``scale(as.double(...))`` ingestion idiom.  ``save='disk'``
    write-through-spills the swept output into an on-disk matrix during
    pass 2, so ``scale(X, save='disk')`` streams out-of-core end to end.
    Constant columns follow R: division yields non-finite values rather
    than being silently clamped."""
    z = x if isinstance(x, FM) else FM(x)
    if center:
        z = mapply_row(z, colMeans(x), "sub")
    if scale:
        z = mapply_row(z, colSds(x), "div")
    if save is not None and z.m.is_virtual:
        persist(z, tier=save)
    return z


def crossprod(x, y: Optional[FM] = None) -> FM:
    """R crossprod: t(x) %*% y (y defaults to x) — the Gram sink."""
    y = x if y is None else y
    return FM(genops.inner_prod(_fm(x).transpose(), _fm(y), "mul", "sum"))


def diag(x) -> FM:
    """R diag(): the diagonal of a (small, materialized) matrix as a
    vector, or a diagonal matrix from a vector.  Small-tier math — the
    operand is materialized if virtual."""
    arr = conv_FM2R(x) if isinstance(x, FM) else np.asarray(x)
    if arr.ndim == 2 and min(arr.shape) == 1:
        arr = arr.reshape(-1)
    if arr.ndim <= 1:
        return conv_R2FM(np.diag(arr.reshape(-1)))
    return conv_R2FM(np.diag(arr).copy())


def solve(a, b=None) -> FM:
    """R solve(): a⁻¹ (b=None) or the solution of a x = b.

    With a VIRTUAL operand (the XᵀWX / XᵀWz sinks of an IRLS step) this is
    a LAZY GenOp evaluated in the plan epilogue: the Newton solve joins the
    same fused pass as the sinks it consumes, one launch after the merge.
    Like all on-device linear algebra it does NOT raise on singular
    systems — non-finite values propagate into the result (check with
    ``np.isfinite``; ``glm`` does).  Physical operands keep the eager
    small-tier path (numpy, float64, raises ``LinAlgError``)."""
    a_virtual = isinstance(a, FM) and a.is_virtual
    b_virtual = isinstance(b, FM) and b.is_virtual
    if a_virtual or b_virtual:
        return FM(genops.solve(_fm(a), _fm(b) if isinstance(b, FM) else b))
    A = np.asarray(conv_FM2R(a) if isinstance(a, FM) else a, np.float64)
    if b is None:
        return conv_R2FM(np.linalg.inv(A))
    B = np.asarray(conv_FM2R(b) if isinstance(b, FM) else b, np.float64)
    if B.ndim <= 1:
        B = B.reshape(-1, 1)   # R: a bare vector is a one-column RHS
    return conv_R2FM(np.linalg.solve(A, B))


def rowsum(x, groups, num_groups: int) -> FM:
    """R rowsum: sum rows by group label."""
    return groupby_row(x, groups, "sum", num_groups)


def table_(groups, num_groups: int) -> FM:
    """R table() over integer labels: per-group counts."""
    g = _fm(groups)
    return FM(genops.groupby_row(g, g, "count", num_groups))


# -- construction / conversion ------------------------------------------------
def runif_matrix(nrow, ncol, **kw) -> FM:
    return FM(matrix_mod.runif_matrix(nrow, ncol, **kw))


def rnorm_matrix(nrow, ncol, **kw) -> FM:
    return FM(matrix_mod.rnorm_matrix(nrow, ncol, **kw))


def rep_int(value, n, **kw) -> FM:
    return FM(matrix_mod.rep_int(value, n, **kw))


def seq_int(n, **kw) -> FM:
    return FM(matrix_mod.seq_int(n, **kw))


def conv_R2FM(arr, host: bool = False) -> FM:
    return FM(matrix_mod.conv_R2FM(arr, host=host))


def conv_FM2R(x) -> np.ndarray:
    return matrix_mod.conv_FM2R(_fm(x))


class Factor:
    """A factor vector (paper Table III ``fm.as.factor``): integer codes
    in ``[0, num_levels)`` plus the level count — what ``fm.one_hot``
    consumes to build the sparse design-matrix columns."""

    __slots__ = ("codes", "num_levels")

    def __init__(self, codes: np.ndarray, num_levels: int):
        self.codes = codes
        self.num_levels = int(num_levels)

    def __len__(self):
        return int(self.codes.shape[0])

    def __repr__(self):
        return f"Factor(n={len(self)}, num_levels={self.num_levels})"


def as_factor(x, num_levels: Optional[int] = None) -> Factor:
    """fm.as.factor: integer labels → a factor vector.

    ``x`` is an FM, FMMatrix or array of integer-valued labels (one
    column); ``num_levels`` defaults to ``max(code) + 1``.  Codes must be
    in ``[0, num_levels)`` — the hashed-categorical convention of the
    Criteo workload, where each of the 26 hash columns becomes a factor."""
    if isinstance(x, Factor):
        return x if num_levels is None else Factor(x.codes, num_levels)
    arr = np.asarray(conv_FM2R(x) if isinstance(x, (FM, FMMatrix)) else x)
    codes = arr.reshape(-1)
    if not np.issubdtype(codes.dtype, np.integer):
        rounded = np.rint(codes)
        if not np.array_equal(rounded, codes):
            raise ValueError(
                "as_factor needs integer-valued labels; got non-integer "
                "values (bin or hash continuous features first)")
        codes = rounded
    codes = codes.astype(np.int64)
    if codes.size and codes.min() < 0:
        raise ValueError("as_factor: negative label codes")
    if num_levels is None:
        num_levels = int(codes.max()) + 1 if codes.size else 1
    elif codes.size and codes.max() >= num_levels:
        raise ValueError(
            f"as_factor: label code {int(codes.max())} out of range for "
            f"num_levels={num_levels}")
    return Factor(codes, num_levels)


def one_hot(*factors, dtype=np.float32, host: bool = True) -> FM:
    """One-hot encode factor(s) into ONE sparse matrix (the ELL tier).

    Each argument is a ``Factor`` (from ``fm.as_factor``) or raw integer
    labels; k factors cbind with running column offsets, so every row has
    exactly k ones — the Criteo design matrix (26 factor columns → a CSR
    row of 26 ones among ~2^20 columns) without ever densifying.
    ``host=False`` places the slab on device.  Persist with
    ``fm.persist(X, tier='disk')`` to write the CSR ``.fmat``."""
    if not factors:
        raise ValueError("one_hot needs at least one factor")
    fs = [as_factor(f) for f in factors]
    n = len(fs[0])
    if any(len(f) != n for f in fs):
        raise ValueError(
            f"one_hot: factor lengths differ ({[len(f) for f in fs]})")
    ncol, offset = 0, []
    for f in fs:
        offset.append(ncol)
        ncol += f.num_levels
    cols = np.stack([f.codes + off for f, off in zip(fs, offset)],
                    axis=1).astype(np.int32)
    vals = np.ones(cols.shape, np.dtype(dtype))
    from ..storage.sparse import SparseEllStore  # lazy: avoid cycle
    if not host:
        cols, vals = jnp.asarray(cols), jnp.asarray(vals)
    store = SparseEllStore(cols, vals, ncol, nnz=n * len(fs))
    return FM(FMMatrix((n, ncol), vals.dtype, store=store))


def persist(x, tier: str = "device", *, name: Optional[str] = None) -> FM:
    """fm.persist: the ONE entry point for keeping a matrix on a tier.

    ``tier`` is 'device' (HBM analog), 'host' (RAM), or 'disk' (the SSD
    tier — FlashR's ``in.mem=FALSE``).  Dense and sparse matrices both
    route here; a sparse matrix persists in its sparse representation
    (ELL slab in RAM, CSR ``.fmat`` on disk) — it is never densified.

      * VIRTUAL ``x``: marks the lazy result so the NEXT materialization
        keeps it on ``tier`` — ``tier='disk'`` write-through-spills the
        streaming output (no extra pass), subsuming the old
        ``materialize(..., save='disk')`` / ``set_mate_level`` spellings.
      * PHYSICAL ``x``: moves the data now — ``tier='disk'`` writes it
        into the configured data directory under ``name`` (or the
        matrix's own name) and returns the reopened mmap-backed handle,
        subsuming the old ``conv_store`` spelling.

    Returns an FM either way (the same lazy handle for virtuals, the new
    tier's handle for physicals)."""
    if tier not in ("device", "host", "disk"):
        raise ValueError(
            f"unknown tier {tier!r}: expected 'device', 'host' or 'disk'")
    m = _fm(x)
    if m.is_virtual:
        genops.set_mate_level(m, tier)
        if name:
            m.name = name
        return x if isinstance(x, FM) else FM(m)
    return FM(matrix_mod.conv_store(m, tier, name=name or ""))


def conv_store(x, where: str, *, name: str = "") -> FM:
    """Deprecated spelling of ``fm.persist(x, tier=where, name=...)``."""
    warnings.warn(
        "fm.conv_store(x, where, name=...) is deprecated; use "
        "fm.persist(x, tier=..., name=...)", DeprecationWarning,
        stacklevel=2)
    return persist(x, tier=where, name=name or None)


# -- the disk tier / EM-matrix registry (repro/storage/) ----------------------
def set_conf(**kw) -> dict:
    """fm.set.conf: data_dir / prefetch / prefetch_depth /
    io_partition_bytes / vmem_partition_bytes / backend / direct_io /
    mesh (a jax Mesh from launch.mesh.make_host_mesh — installs sharded
    execution engine-wide; ``mesh=False`` clears it).  Unknown knobs
    raise with a did-you-mean hint (``storage.registry.KNOWN_KNOBS`` is
    the authoritative table); for a scoped override use ``fm.conf``."""
    from ..storage import registry
    return registry.set_conf(**kw)


def conf(**kw):
    """fm.conf: scoped configuration override (a context manager).

        with fm.conf(backend='pallas', prefetch=False):
            fm.materialize(G)          # runs under the override
        # prior values restored here, even on error

    Validates knob names exactly like ``fm.set_conf`` and snapshots the
    prior values on entry — replacing the manual save/restore dance in
    tests and benchmarks."""
    from ..storage import registry
    return registry.conf(**kw)


def get_dense_matrix(name: str) -> FM:
    """fm.get.dense.matrix: reopen a named on-disk matrix (mmap-backed)."""
    from ..storage import registry
    return FM(registry.get_dense_matrix(name))


def load_dense_matrix(src, name: str, **kw) -> FM:
    """fm.load.dense.matrix: ingest CSV/binary/npy/array → on-disk matrix."""
    from ..storage import registry
    return FM(registry.load_dense_matrix(src, name, **kw))


def load_factor_matrix(src, name: str, *, num_levels, **kw) -> FM:
    """fm.load.factor.matrix: stream a CSV of integer factor columns into
    a CSR on-disk matrix of one-hot rows (the Criteo design matrix) and
    reopen it on the sparse tier."""
    from ..storage import registry
    return FM(registry.load_factor_matrix(src, name, num_levels=num_levels,
                                          **kw))


def save_dense_matrix(x, name: Optional[str] = None, **kw) -> FM:
    """Write a physical matrix into the registry; returns the disk handle."""
    from ..storage import registry
    m = _fm(x)
    if getattr(m, "is_virtual", False):
        (m,) = mat_mod.materialize(m)
    return FM(registry.save_dense_matrix(m, name, **kw))


def conv_layout(x, layout: str) -> FM:
    return FM(matrix_mod.conv_layout(_fm(x), layout))


def set_mate_level(x, level: str) -> FM:
    """Deprecated spelling of ``fm.persist(x, tier=level)``."""
    warnings.warn(
        "fm.set_mate_level(x, level) is deprecated; use "
        "fm.persist(x, tier=...)", DeprecationWarning, stacklevel=2)
    return persist(x, tier=level)


def materialize(*xs, **kw) -> list[FM]:
    """fm.materialize: fused evaluation of every argument in one pass."""
    mats = mat_mod.materialize(*[_fm(x) for x in xs], **kw)
    return [FM(m) for m in mats]


def batch(*request_groups, **kw):
    """fm.batch: cross-materialize stream fusion (core/batch.py).

    Each argument is one request — a lazy matrix, or a tuple/list of lazy
    matrices that would otherwise be one ``fm.materialize(...)`` call.
    Every request keeps its own plan, but requests whose passes stream the
    same physical sources are co-scheduled onto ONE partition sweep: k
    plans × 1 stream (``fm.exec_stats()['streams']``).

        means, (sds, ctp) = fm.batch(fm.colMeans(X),
                                     (fm.colSds(X), fm.crossprod(X)))

    With no arguments, returns a collector to queue requests explicitly:

        with fm.batch() as b:
            h = b.add(fm.colMeans(X))
        h.value

    Keywords (``mode``, ``backend``, ``donate``, ``prefetch``,
    ``reuse_plans``, ``mesh``) follow ``fm.materialize``; ``mode='auto'``
    picks per group from the union of that group's sources."""
    from . import batch as batch_mod
    b = batch_mod.Batch(**kw)
    if not request_groups:
        return b
    handles = []
    for grp in request_groups:
        outs = grp if isinstance(grp, (tuple, list)) else (grp,)
        handles.append(b.add(*[_fm(x) for x in outs]))
    b.run()
    results = []
    for grp, h in zip(request_groups, handles):
        v = h.value
        results.append([FM(m) for m in v] if isinstance(v, list) else FM(v))
    return results


def serve(**kw):
    """fm.serve: start an async multi-tenant serving `Engine`
    (core/serve.py) — concurrent threads ``submit()`` lazy requests, a
    short admission window groups strangers' plans by shared sources, and
    each group streams its matrices ONCE for all members (k requests ×
    1 stream), with bandwidth admission control and mid-stream admission
    of late same-group plans.

        with fm.serve(window_ms=5) as eng:
            h1 = eng.submit(fm.colMeans(X))   # any thread
            h2 = eng.submit(fm.crossprod(X))  # same window, same stream
            mu, G = h1.result(), h2.result()

    Keywords are `Engine`'s (window_ms, max_window_requests,
    max_concurrent_streams, max_inflight_bytes, max_pending_requests,
    submit_timeout_s, midstream_admission, mode, backend, donate,
    prefetch, prefetch_depth, reuse_plans, mesh)."""
    from . import serve as serve_mod
    return serve_mod.Engine(**kw)


def __getattr__(name):
    # fm.Engine without importing the serving layer at fm import time.
    if name in ("Engine", "EngineSaturated"):
        from . import serve as serve_mod
        return getattr(serve_mod, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def inspect_iterations():
    """fm.inspect_iterations: declare an iterative driver's loop so the
    executor keeps each streaming pass's final staged partition resident
    across materialize/batch calls — iteration i+1's first pass over the
    same partition schedule starts from the resident blocks instead of
    re-reading them (``prefetch_reuse_hits``).  The iterative drivers
    (kmeans / glm IRLS / nmf / gmm) open this around their loops."""
    return mat_mod.iteration_scope()


def as_scalar(x) -> float:
    (r,) = materialize(x) if _fm(x).is_virtual else (x,)
    return float(matrix_mod.fetch(_fm(r).logical_data()).reshape(()))


def as_np(x) -> np.ndarray:
    return conv_FM2R(x)


# -- observability (repro/observability/) --------------------------------------

def trace(export: Optional[str] = None, *, reset: bool = True):
    """fm.trace: enable span tracing over a with-block.

        with fm.trace():
            fm.materialize(G)
        fm.trace_export("run.trace.json")   # chrome://tracing / Perfetto

    ``export=`` writes the Chrome-trace JSON on scope exit; ``reset=False``
    appends to the already-collected events instead of starting fresh.
    The prefetcher's staging thread records onto its own track, so
    stage/compute overlap is visible in the timeline."""
    from ..observability.trace import TRACER
    return TRACER.recording(export, reset=reset)


def trace_export(path) -> str:
    """fm.trace.export: write collected spans as Chrome-trace JSON."""
    from ..observability.trace import TRACER
    return TRACER.export(path)


def trace_events() -> list:
    """Collected span events (dicts with name/ts/dur/tid), for programmatic
    inspection without round-tripping the JSON export."""
    from ..observability.trace import TRACER
    return TRACER.events()


def collect_stats(name: str = ""):
    """fm.collect.stats: a metrics scope isolating THIS thread's engine
    activity (its materialize calls, plus the prefetch pipelines they
    spawn).  Yields the scope; read it with ``.stats()``:

        with fm.collect_stats() as sc:
            fm.materialize(G)
        sc.stats()["stream_bandwidth_bytes_s"]

    Scopes are per-thread, so concurrent requests each see only their own
    execution — the per-request accounting a serving layer needs."""
    from ..observability import metrics
    return metrics.collect(name)


def exec_stats() -> dict:
    """fm.exec.stats: the engine's execution counters (compatibility view
    over the metrics registry's root scope)."""
    return mat_mod.exec_stats()


def reset_exec_stats():
    mat_mod.reset_exec_stats()


def explain(*xs, backend: Optional[str] = None) -> str:
    """fm.explain: render the fused plan ``fm.materialize(*xs)`` would run
    — pass schedule, source tiers, both partition levels, per-segment
    backend dispatch — without executing anything."""
    from ..observability.explain import explain as _explain
    return _explain(*[_fm(x) for x in xs], backend=backend)


def explain_batch(*request_groups, backend: Optional[str] = None) -> str:
    """fm.explain_batch: render the co-schedule ``fm.batch(*requests)``
    would run — per round, the stream groups with their member plans,
    shared sources and the union bytes one drive reads — without executing
    anything.  Arguments mirror ``fm.batch``: each one is a lazy matrix or
    a tuple/list of them forming one request."""
    from ..observability.explain import explain_batch as _explain_batch
    groups = [grp if isinstance(grp, (tuple, list)) else (grp,)
              for grp in request_groups]
    return _explain_batch([[_fm(x) for x in g] for g in groups],
                          backend=backend)
