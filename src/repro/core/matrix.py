"""Dense-matrix handles and the two-level partitioning model.

Paper §III-B: dense matrices are the main data type; a matrix is *physical*
(in memory / on SSD) or *virtual* (a sequence of computation).  Tall-and-
skinny (TAS) matrices are the optimized case; wide matrices are viewed as
transposed TAS.  Two-level horizontal partitioning:

* **I/O-level partitions** — rows-per-partition is a power of two; each
  partition is contiguous in the slow tier and is the streaming/DMA unit
  (megabytes).  Our analog: the chunk granule of the out-of-core executor
  and the per-device shard granule under `shard_map`.
* **CPU-level partitions** — fits L1/L2 so a fused operation chain stays in
  cache.  Our analog: the Pallas BlockSpec VMEM tile (multiples of (8,128)).

``FMMatrix`` is an immutable handle.  Physical storage lives behind the
``MatrixStore`` protocol: ``DenseStore`` (jax array on device, or numpy array
in host RAM) or ``storage.MmapStore`` (the real SSD tier — an on-disk matrix
file served through ``np.memmap``, see repro/storage/).
Virtual matrices point at a DAG node (core/dag.py) and are materialized by
core/materialize.py.
"""
from __future__ import annotations

import abc
import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import dtypes
from ..observability import metrics
from ..observability.trace import TRACER

# ---------------------------------------------------------------------------
# Partition-size policy
# ---------------------------------------------------------------------------

# Default I/O-level partition budget: bytes of the *fused group's* working
# set per partition.  64 MiB mirrors the paper's memory-chunk size; the
# fusion planner divides this by the number of live matrices in the group.
IO_PARTITION_BYTES = 64 * 1024 * 1024

# CPU-level partition budget: should fit comfortably in L1/L2 (paper) or a
# VMEM tile (TPU).  Used by the Pallas kernels' BlockSpec defaults.
CPU_PARTITION_BYTES = 128 * 1024

# Processor-level (second tier) partition budget for the execution engine's
# per-segment schedule: the VMEM working-set analog of the paper's CPU-cache
# partition (§III-F).  Settable via ``fm.set_conf(vmem_partition_bytes=...)``;
# read at plan-IR build, so it is part of the plan-cache key (the schedule).
VMEM_PARTITION_BYTES = 4 * 1024 * 1024

# TPU lane/sublane alignment: row counts that are multiples of 8 and column
# tiles that are multiples of 128 vectorize cleanly (paper's "number of rows
# in an I/O-level partition is always 2^i ... data well aligned ... to help
# CPU vectorization").
ROW_ALIGN = 8


def _pow2_rows(ncol: int, dtype, n_live: int, budget_bytes: int) -> int:
    """Largest power of two rows such that ``n_live`` arrays of that many
    rows fit the byte budget (paper: partitions are always 2^i rows)."""
    row_bytes = max(1, ncol) * dtypes.nbytes(dtype) * max(1, n_live)
    rows = max(ROW_ALIGN, budget_bytes // max(1, row_bytes))
    return 1 << (int(rows).bit_length() - 1)


def io_partition_rows(ncol: int, dtype, n_live: int = 1,
                      budget_bytes: Optional[int] = None) -> int:
    """Rows per I/O-level partition: the largest power of two such that
    ``n_live`` matrices of that many rows fit the partition budget.

    ``budget_bytes=None`` reads the module-level ``IO_PARTITION_BYTES`` at
    call time, so ``fm.set_conf(io_partition_bytes=...)`` takes effect on
    every subsequently built plan."""
    if budget_bytes is None:
        budget_bytes = IO_PARTITION_BYTES
    return _pow2_rows(ncol, dtype, n_live, budget_bytes)


def proc_partition_rows(ncol: int, dtype, n_live: int = 1,
                        budget_bytes: Optional[int] = None) -> int:
    """Rows per processor-level (VMEM-tile) partition for a fused segment:
    the same 2^i rule as the I/O level, one tier down (paper §III-F's
    second partitioning level).

    ``budget_bytes=None`` reads ``VMEM_PARTITION_BYTES`` at call time so
    ``fm.set_conf(vmem_partition_bytes=...)`` reschedules later plans."""
    if budget_bytes is None:
        budget_bytes = VMEM_PARTITION_BYTES
    return _pow2_rows(ncol, dtype, n_live, budget_bytes)


def cpu_partition_rows(ncol: int, dtype,
                       budget_bytes: int = CPU_PARTITION_BYTES) -> int:
    """Rows per CPU-level (VMEM-tile) partition.

    Paper: "FlashMatrix determines the number of rows in a CPU-level
    partition based on the number of columns in a matrix."
    """
    ncol = max(1, ncol)
    rows = max(ROW_ALIGN, budget_bytes // (ncol * dtypes.nbytes(dtype)))
    return (rows // ROW_ALIGN) * ROW_ALIGN


# ---------------------------------------------------------------------------
# Storage
# ---------------------------------------------------------------------------

class MatrixStore(abc.ABC):
    """Store protocol: the physical backing of a materialized matrix.

    ``FMMatrix`` is backend-agnostic — any tier (device HBM, host RAM, SSD)
    plugs in by implementing this interface.  The logical shape of the
    matrix is always (nrow, ncol); ``layout`` records the physical majority
    (paper supports both and avoids copies on transpose by flipping the
    tag).  A 'col'-layout store holds the transposed buffer, shape
    (ncol, nrow).

    Implementations: ``DenseStore`` (device / host-RAM tiers, below) and
    ``repro.storage.MmapStore`` (the disk tier).
    """

    layout: str = "row"  # 'row' | 'col'

    @property
    @abc.abstractmethod
    def on_host(self) -> bool:
        """True when partitions must be staged host→device by the executor
        (the out-of-core tiers: host RAM and disk)."""

    @property
    def on_disk(self) -> bool:
        return False

    @abc.abstractmethod
    def logical(self):
        """Return data in logical (nrow, ncol) orientation (may transpose)."""

    @abc.abstractmethod
    def block(self, start: int, stop: int):
        """Logical rows [start, stop) — the I/O-level partition read.
        Must touch only that partition's bytes, never the whole buffer."""

    @abc.abstractmethod
    def nbytes(self) -> int:
        """Physical size of the backing buffer in bytes."""

    @abc.abstractmethod
    def transposed(self) -> "MatrixStore":
        """A store over the same buffer with the layout tag flipped
        (the zero-copy transpose)."""


@dataclasses.dataclass
class DenseStore(MatrixStore):
    """In-memory backing: ``data`` is a jax Array (device tier) or numpy
    ndarray (host-RAM tier — paged in chunk-by-chunk by the streaming
    executor).  For a 'col'-layout matrix ``data`` holds the transposed
    buffer, i.e. shape (ncol, nrow)."""

    data: Any
    layout: str = "row"  # 'row' | 'col'

    @property
    def on_host(self) -> bool:
        return isinstance(self.data, np.ndarray)

    def logical(self):
        return self.data.T if self.layout == "col" else self.data

    def block(self, start: int, stop: int):
        # Slice the stored buffer and transpose only the block — a col-layout
        # store must never transpose the entire buffer per partition read.
        if self.layout == "col":
            return self.data[:, start:stop].T
        return self.data[start:stop]

    def nbytes(self) -> int:
        return int(self.data.nbytes)

    def transposed(self) -> "DenseStore":
        return DenseStore(self.data, "col" if self.layout == "row" else "row")


class FMMatrix:
    """Immutable matrix handle (paper: all FlashMatrix matrices are immutable).

    Exactly one of ``store`` / ``node`` is set:
      * store: MatrixStore       — physical matrix (any tier)
      * node:  dag.Node          — virtual matrix (lazy computation)
    """

    __slots__ = ("shape", "dtype", "store", "node", "name", "_transposed_of")

    def __init__(self, shape, dtype, *, store: Optional[MatrixStore] = None,
                 node=None, name: str = ""):
        assert (store is None) != (node is None), "exactly one backing"
        self.shape = (int(shape[0]), int(shape[1]))
        self.dtype = dtypes.canon(dtype)
        self.store = store
        self.node = node
        self.name = name
        self._transposed_of: Optional[FMMatrix] = None

    # -- basic properties ---------------------------------------------------
    @property
    def nrow(self) -> int:
        return self.shape[0]

    @property
    def ncol(self) -> int:
        return self.shape[1]

    @property
    def is_virtual(self) -> bool:
        return self.node is not None

    @property
    def is_tall(self) -> bool:
        return self.nrow >= self.ncol

    @property
    def long_dim(self) -> int:
        """Size of the long dimension (paper: the dimension with larger size)."""
        return max(self.shape)

    @property
    def long_axis(self) -> int:
        return 0 if self.is_tall else 1

    @property
    def on_host(self) -> bool:
        return self.store is not None and self.store.on_host

    @property
    def on_disk(self) -> bool:
        return self.store is not None and self.store.on_disk

    @property
    def is_sparse(self) -> bool:
        """True for a physical matrix on the sparse (CSR/ELL) tier."""
        return self.store is not None and getattr(self.store, "sparse", False)

    def nbytes(self) -> int:
        """Bytes the streaming executor actually moves for this matrix.

        Physical matrices ask the store — on the sparse tier that is the
        nnz-proportional section size, not nrow·ncol·itemsize (dense
        stores report exactly the dense formula, so this is a pure
        delegation, not a behavior change)."""
        if self.store is not None:
            return int(self.store.nbytes())
        return self.nrow * self.ncol * dtypes.nbytes(self.dtype)

    # -- construction helpers -------------------------------------------------
    @staticmethod
    def from_array(arr, *, layout: str = "row", name: str = "") -> "FMMatrix":
        """Wrap a jax/numpy array (1-D arrays become one-column matrices,
        mirroring the paper's 'a vector is stored as a one-column dense
        matrix')."""
        if hasattr(arr, "ndim") and arr.ndim == 1:
            arr = arr.reshape(-1, 1)
        if isinstance(arr, np.ndarray):
            data = np.asarray(arr, dtype=dtypes.np_equiv(arr.dtype))
        else:
            data = jnp.asarray(arr)
        shape = data.shape
        if layout == "col":
            data = data.T  # store transposed buffer
        return FMMatrix(shape, arr.dtype, store=DenseStore(data, layout), name=name)

    def transpose(self) -> "FMMatrix":
        """Lazy transpose: no data movement, flip layout tag (paper §III-B1:
        'we avoid data copy for common matrix operations such as matrix
        transpose')."""
        if self.store is not None:
            out = FMMatrix((self.ncol, self.nrow), self.dtype,
                           store=self.store.transposed(),
                           name=f"t({self.name})" if self.name else "")
        else:
            # Virtual transpose handle: consumers (inner_prod) peel it off.
            out = FMMatrix((self.ncol, self.nrow), self.dtype, node=self.node,
                           name=f"t({self.name})" if self.name else "")
        out._transposed_of = self
        return out

    @property
    def transposed_of(self) -> Optional["FMMatrix"]:
        return self._transposed_of

    # -- data access ----------------------------------------------------------
    def logical_data(self):
        """Materialized data in logical row-major orientation.

        Only valid on physical matrices; virtual matrices must go through
        core.materialize first.
        """
        if self.store is None:
            raise ValueError(
                f"matrix {self.name or '<anon>'} is virtual; call "
                "fm.materialize() first")
        return self.store.logical()

    def block(self, start: int, stop: int):
        """Slice ROWS [start, stop) of a *physical* matrix in logical
        orientation — the I/O-level partition read (rows are the streaming
        axis throughout the engine; see dag.long_dim_of).  Delegates to the
        store so only the partition's bytes are touched."""
        if self.store is None:
            raise ValueError(
                f"matrix {self.name or '<anon>'} is virtual; call "
                "fm.materialize() first")
        return self.store.block(start, stop)

    def __repr__(self):
        kind = ("virtual" if self.is_virtual
                else "disk" if self.on_disk
                else "host" if self.on_host else "device")
        return (f"FMMatrix({self.nrow}x{self.ncol}, {self.dtype.name}, {kind}"
                + (f", name={self.name!r}" if self.name else "") + ")")


# ---------------------------------------------------------------------------
# Construction utilities (paper Table II)
# ---------------------------------------------------------------------------

def rep_int(value, n: int, dtype=jnp.float32) -> FMMatrix:
    """fm.rep.int: vector with a repeated value."""
    return FMMatrix.from_array(jnp.full((n,), value, dtypes.canon(dtype)))


def seq_int(n: int, dtype=jnp.int64) -> FMMatrix:
    """fm.seq.int: 0..n-1 sequence vector."""
    return FMMatrix.from_array(jnp.arange(n, dtype=dtypes.canon(dtype)))


def runif_matrix(nrow: int, ncol: int, *, key=None, dtype=jnp.float32,
                 minval=0.0, maxval=1.0, host: bool = False) -> FMMatrix:
    """fm.runif.matrix: uniform random matrix.  host=True places it on the
    out-of-core tier (numpy), the SSD stand-in."""
    key = key if key is not None else jax.random.PRNGKey(0)
    dt = dtypes.canon(dtype)
    x = jax.random.uniform(key, (nrow, ncol), dt, minval, maxval)
    if host:
        return FMMatrix.from_array(np.asarray(x))
    return FMMatrix.from_array(x)


def rnorm_matrix(nrow: int, ncol: int, *, key=None, dtype=jnp.float32,
                 mean=0.0, sd=1.0, host: bool = False) -> FMMatrix:
    """fm.rnorm.matrix: normal random matrix."""
    key = key if key is not None else jax.random.PRNGKey(0)
    dt = dtypes.canon(dtype)
    x = jax.random.normal(key, (nrow, ncol), dt) * sd + mean
    if host:
        return FMMatrix.from_array(np.asarray(x))
    return FMMatrix.from_array(x)


def conv_R2FM(arr, *, host: bool = False) -> FMMatrix:
    """fm.conv.R2FM: wrap an external (numpy) array."""
    if host:
        return FMMatrix.from_array(np.asarray(arr))
    return FMMatrix.from_array(jnp.asarray(arr))


def conv_FM2R(mat: FMMatrix) -> np.ndarray:
    """fm.conv.FM2R: to a host numpy array (materializes virtuals)."""
    if mat.is_virtual:
        from . import materialize as _mat
        mat = _mat.materialize(mat)[0]
    return fetch(mat.logical_data())


def fetch(data) -> np.ndarray:
    """``data`` as a host numpy array: the one place a result the caller
    asked for (``conv_FM2R``, ``as_scalar``) comes from the device.  A
    device array's transfer runs in a ``fetch`` span and is counted
    (``host_fetches``)."""
    if not isinstance(data, jax.Array):
        return np.asarray(data)
    with TRACER.span("fetch"):
        out = np.asarray(data)
    metrics.inc("host_fetches")
    return out


def conv_store(mat: FMMatrix, where: str, *, name: str = "") -> FMMatrix:
    """fm.conv.store: move a physical matrix between tiers
    ('device' = HBM analog, 'host' = RAM tier, 'disk' = the real SSD tier —
    FlashR's ``fm.conv.store(in.mem=FALSE)``).

    ``where='disk'`` writes the matrix into the configured data directory
    (``storage.registry.set_conf``) under ``name`` (or the matrix's own
    name) and returns a handle backed by ``MmapStore``."""
    if where == "disk":
        from ..storage import registry as _registry  # lazy: avoid cycle
        if getattr(mat.store, "sparse", False):
            return _registry.save_sparse_matrix(mat, name or mat.name or None)
        return _registry.save_dense_matrix(mat, name or mat.name or None)
    if getattr(mat.store, "sparse", False) and where in ("host", "device"):
        # Tier moves keep the sparse representation: only cols/vals migrate.
        from ..storage.sparse import SparseEllStore  # lazy: avoid cycle
        blk = mat.store.block(0, mat.nrow)
        conv = (np.asarray if where == "host"
                else (lambda a: jnp.asarray(np.asarray(a))))
        store = SparseEllStore(conv(blk.cols), conv(blk.vals), mat.ncol,
                               nnz=getattr(mat.store, "nnz", None))
        return FMMatrix(mat.shape, mat.dtype, store=store, name=mat.name)
    data = mat.logical_data()
    if where == "host":
        return FMMatrix.from_array(np.asarray(data), name=mat.name)
    if where == "device":
        return FMMatrix.from_array(jnp.asarray(np.asarray(data)), name=mat.name)
    raise ValueError(f"unknown store {where!r}")


def conv_layout(mat: FMMatrix, layout: str) -> FMMatrix:
    """fm.conv.layout: physically convert row/col majority."""
    data = mat.logical_data()
    if layout == mat.store.layout:
        return mat
    if isinstance(data, np.ndarray):
        buf = np.ascontiguousarray(data.T) if layout == "col" else np.ascontiguousarray(data)
    else:
        buf = data.T if layout == "col" else data
    return FMMatrix(mat.shape, mat.dtype, store=DenseStore(buf, layout), name=mat.name)


def rbind(*mats: FMMatrix) -> FMMatrix:
    """fm.rbind: stack physical matrices by rows."""
    datas = [m.logical_data() for m in mats]
    if any(isinstance(d, np.ndarray) for d in datas):
        return FMMatrix.from_array(np.concatenate([np.asarray(d) for d in datas], 0))
    return FMMatrix.from_array(jnp.concatenate(datas, 0))


def cbind_physical(*mats: FMMatrix) -> FMMatrix:
    """fm.cbind on physical matrices (virtual cbind lives in the DAG)."""
    datas = [m.logical_data() for m in mats]
    if any(isinstance(d, np.ndarray) for d in datas):
        return FMMatrix.from_array(np.concatenate([np.asarray(d) for d in datas], 1))
    return FMMatrix.from_array(jnp.concatenate(datas, 1))
