"""Shared helpers for the Pallas TPU kernels.

All kernels target TPU (pl.pallas_call + explicit BlockSpec VMEM tiling) and
validate on CPU via ``interpret=True`` — the kernel body executes in Python
so the BlockSpec/grid logic is what is under test.  ``default_interpret()``
returns True on non-TPU backends so tests and benchmarks run here while the
same call sites compile to Mosaic on real hardware.

Layout policy: the engine's matrices are tall and skinny, and XLA's TPU
layout stores an (n, p) array with p < 128 column-major ({0,1}) — the
(p, n) transpose is a free bitcast, while a row-major (bm, p) tile would pad
p to 128 lanes (4x the VMEM and, through the relayout copy XLA inserts in
front of the kernel, 4x the HBM at p = 32).  So every streaming kernel
reads the TRANSPOSED view: (p, bc) tiles whose long dimension runs along
the 128 lanes and whose p columns are sublanes.  ``bc`` — the kernel's
"block rows" — is a multiple of 128 lanes, or the whole extent when it fits
one block.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANE = 128
SUBLANE = 8


def default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def pick_block_rows(n_rows: int, n_cols: int, dtype,
                    vmem_budget: int = 4 * 1024 * 1024,
                    n_live: int = 2) -> int:
    """Rows per VMEM block: largest power of two whose working set
    (n_live copies of a cols×rows lane-major tile, cols padded to whole
    sublanes) fits the VMEM budget."""
    cols = round_up(max(1, n_cols), SUBLANE)
    bytes_per_row = cols * jnp.dtype(dtype).itemsize * n_live
    rows = max(LANE, vmem_budget // bytes_per_row)
    rows = 1 << (int(rows).bit_length() - 1)
    return int(min(rows, max(SUBLANE, n_rows)))


def lane_block(n: int, block_rows: int) -> int:
    """Rows per grid step of a lane-major operand: the whole extent when it
    fits one block, else ``block_rows`` rounded up to whole 128-lane vregs
    (Mosaic tiles a block's minor dimension in lanes)."""
    if n <= block_rows:
        return n
    return round_up(block_rows, LANE)


def lanes(x, multiple: int, value=0):
    """The (cols, rows) lane-major view of a tall (rows, cols) operand, its
    row count padded to a multiple of ``multiple`` with ``value``.  On TPU
    the transpose is a layout bitcast for cols < 128 (see module doc)."""
    xt = x.T
    n = xt.shape[1]
    target = round_up(n, multiple)
    if target == n:
        return xt
    return jnp.pad(xt, ((0, 0), (0, target - n)), constant_values=value)


def lane_contract(a, b):
    """a·bᵀ over the shared lane (row) axis: (m, bc) × (q, bc) → (m, q) in
    f32, at HIGHEST precision: the MXU's default f32 pass rounds operands to
    bf16, which costs an IRLS Gram about three significant digits."""
    return jax.lax.dot_general(
        a.astype(jnp.float32), b.astype(jnp.float32),
        (((1,), (1,)), ((), ())), precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)


def row_mask(n_true, block: int):
    """(1, block) validity mask of the current grid step's lanes: rows at or
    beyond the true length are padding."""
    i = jax.lax.broadcasted_iota(jnp.int32, (1, block), 1)
    return i + pl.program_id(0) * block < n_true
