"""Sparse (ELL) contraction kernels: SpMM for the one-hot/Criteo tier.

The IRLS sinks over a sparse design matrix are the same contractions
`gram.py` / `weighted_gram.py` compute — XᵀX, XᵀY, XᵀWX — but the operand
arrives as an ELL slab (core/sparse.SparseBlock: ``cols`` int32 and
``vals`` of shape (rows, kmax), kmax ≪ ncol).  The FlashR story is that
these workloads are I/O bound: what matters is that HBM (≙ SSD) traffic is
nnz-proportional, 2·kmax scalars per row instead of ncol.

Inside the kernel each VMEM-resident slab — lane-major, (kmax, bc), as in
common.py's layout policy — is expanded to a dense (p, bc) tile by
comparing a sublane iota against each slot's column ids (Mosaic has no
scatter):

    tileᵀ = Σ_j (iota_p == cols[j]) · vals[j]        (kmax VPU selects)

— and contracted on the MXU, exactly like the dense kernels.  The
expansion never exists in HBM; padding entries are (col=0, val=0), neutral
under the sum and the sum-product contraction (same zero-padding argument
as `gram.py`).  Grid, accumulator residency and writeback follow the
`weighted_gram.py` template: 1-D grid over row blocks, (p, p) f32
accumulator in VMEM scratch for the whole sweep.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import (default_interpret, lane_block, lane_contract, lanes,
                     pick_block_rows)


def _dense_tile(colst, valst, ncol: int):
    """Lane-major ELL slab (kmax, bc) → dense f32 (ncol, bc) tile, built in
    registers/VMEM by an iota compare per ELL slot."""
    ids = jax.lax.broadcasted_iota(jnp.int32, (ncol, colst.shape[1]), 0)
    vals = valst.astype(jnp.float32)
    tile = jnp.zeros(ids.shape, jnp.float32)
    for j in range(colst.shape[0]):
        tile += jnp.where(ids == colst[j:j + 1, :], vals[j:j + 1, :], 0.0)
    return tile


def _spmm_block_rows(n: int, kmax: int, p: int, dtype) -> int:
    # Live tiles per block: the slab (2 arrays, kmax wide) plus the
    # expanded (p, bc) tile — budget on the widest.
    return pick_block_rows(n, max(p, 2 * kmax), dtype, n_live=2)


def _slabs(cols, vals, bc):
    return lanes(cols, bc, value=0), lanes(vals, bc, value=0)


def _spmm_gram_kernel(cols_ref, vals_ref, g_ref, acc, *, ncol):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    xt = _dense_tile(cols_ref[...], vals_ref[...], ncol)
    acc[...] += lane_contract(xt, xt)

    @pl.when(i == pl.num_programs(0) - 1)
    def _writeback():
        g_ref[...] = acc[...]


@functools.partial(jax.jit, static_argnames=("ncol", "block_rows",
                                             "interpret"))
def spmm_gram(cols, vals, *, ncol: int, block_rows: int = 0,
              interpret: bool | None = None):
    """G = XᵀX for sparse ELL X (n rows, ncol logical columns)."""
    interpret = default_interpret() if interpret is None else interpret
    n, kmax = cols.shape
    if not block_rows:
        block_rows = _spmm_block_rows(n, kmax, ncol, vals.dtype)
    bc = lane_block(n, block_rows)
    ct, vt = _slabs(cols, vals, bc)
    kernel = functools.partial(_spmm_gram_kernel, ncol=ncol)
    return pl.pallas_call(
        kernel,
        grid=(ct.shape[1] // bc,),
        in_specs=[pl.BlockSpec((kmax, bc), lambda i: (0, i)),
                  pl.BlockSpec((kmax, bc), lambda i: (0, i))],
        out_specs=pl.BlockSpec((ncol, ncol), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((ncol, ncol), jnp.float32),
        scratch_shapes=[pltpu.VMEM((ncol, ncol), jnp.float32)],
        interpret=interpret,
    )(ct, vt)


def _spmm_xty_kernel(cols_ref, vals_ref, yt_ref, g_ref, acc, *, ncol):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    xt = _dense_tile(cols_ref[...], vals_ref[...], ncol)
    acc[...] += lane_contract(xt, yt_ref[...].astype(jnp.float32))

    @pl.when(i == pl.num_programs(0) - 1)
    def _writeback():
        g_ref[...] = acc[...]


@functools.partial(jax.jit, static_argnames=("ncol", "block_rows",
                                             "interpret"))
def spmm_xty(cols, vals, y, *, ncol: int, block_rows: int = 0,
             interpret: bool | None = None):
    """XᵀY for sparse ELL X and row-aligned dense Y (n, q); (ncol, q) f32."""
    interpret = default_interpret() if interpret is None else interpret
    n, kmax = cols.shape
    q = y.shape[1]
    if not block_rows:
        block_rows = _spmm_block_rows(n, kmax, max(ncol, q), vals.dtype)
    bc = lane_block(n, block_rows)
    ct, vt = _slabs(cols, vals, bc)
    yt = lanes(y, bc)
    kernel = functools.partial(_spmm_xty_kernel, ncol=ncol)
    return pl.pallas_call(
        kernel,
        grid=(ct.shape[1] // bc,),
        in_specs=[pl.BlockSpec((kmax, bc), lambda i: (0, i)),
                  pl.BlockSpec((kmax, bc), lambda i: (0, i)),
                  pl.BlockSpec((q, bc), lambda i: (0, i))],
        out_specs=pl.BlockSpec((ncol, q), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((ncol, q), jnp.float32),
        scratch_shapes=[pltpu.VMEM((ncol, q), jnp.float32)],
        interpret=interpret,
    )(ct, vt, yt)


def _spmm_wgram_kernel(cols_ref, vals_ref, w_ref, g_ref, acc, *, ncol):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    xt = _dense_tile(cols_ref[...], vals_ref[...], ncol)
    w = w_ref[...].astype(jnp.float32)  # (1, bc), broadcasts per row
    acc[...] += lane_contract(xt * w, xt)

    @pl.when(i == pl.num_programs(0) - 1)
    def _writeback():
        g_ref[...] = acc[...]


@functools.partial(jax.jit, static_argnames=("ncol", "block_rows",
                                             "interpret"))
def spmm_wgram(cols, vals, w, *, ncol: int, block_rows: int = 0,
               interpret: bool | None = None):
    """G = XᵀWX for sparse ELL X and per-row weights w (n,) or (n, 1) —
    the sparse IRLS hot spot.  Zero-padded w rows are neutral."""
    interpret = default_interpret() if interpret is None else interpret
    n, kmax = cols.shape
    if not block_rows:
        block_rows = _spmm_block_rows(n, kmax, ncol, vals.dtype)
    bc = lane_block(n, block_rows)
    ct, vt = _slabs(cols, vals, bc)
    wt = lanes(w.reshape(n, 1), bc)
    kernel = functools.partial(_spmm_wgram_kernel, ncol=ncol)
    return pl.pallas_call(
        kernel,
        grid=(ct.shape[1] // bc,),
        in_specs=[pl.BlockSpec((kmax, bc), lambda i: (0, i)),
                  pl.BlockSpec((kmax, bc), lambda i: (0, i)),
                  pl.BlockSpec((1, bc), lambda i: (0, i))],
        out_specs=pl.BlockSpec((ncol, ncol), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((ncol, ncol), jnp.float32),
        scratch_shapes=[pltpu.VMEM((ncol, ncol), jnp.float32)],
        interpret=interpret,
    )(ct, vt, wt)
