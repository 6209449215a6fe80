"""Tall-skinny Gram kernel: G = XᵀX with streaming row blocks.

The hot inner product of correlation and SVD (paper §IV-A): contract the
long dimension of a TAS matrix.  The paper hands this to BLAS; on TPU the
analog is feeding the MXU from VMEM-resident tiles while the (p, p)
accumulator never leaves VMEM for the whole sweep — one read of X, one
write of G.

Grid: 1-D over row blocks of the lane-major view Xᵀ (common.py's layout
policy); f32 accumulation regardless of input dtype (bf16 in → f32 acc,
the MXU-native mixed-precision mode).
Also provides ``xty`` (Xᵀ·Y for a second tall matrix) — the GMM M-step
moment sink (X⊙r)ᵀX shares this code path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import (default_interpret, lane_block, lane_contract, lanes,
                     pick_block_rows)


def _gram_kernel(xt_ref, g_ref, acc):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    xt = xt_ref[...]
    # Padding rows are zero — harmless for a sum-product contraction.
    acc[...] += lane_contract(xt, xt)

    @pl.when(i == pl.num_programs(0) - 1)
    def _writeback():
        g_ref[...] = acc[...]


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def gram(x, *, block_rows: int = 0, interpret: bool | None = None):
    """G = XᵀX for tall (n, p) X; returns (p, p) float32."""
    interpret = default_interpret() if interpret is None else interpret
    n, p = x.shape
    if not block_rows:
        block_rows = pick_block_rows(n, p, x.dtype, n_live=2)
    bc = lane_block(n, block_rows)
    xt = lanes(x, bc)  # zero pad: neutral for sum-product
    return pl.pallas_call(
        _gram_kernel,
        grid=(xt.shape[1] // bc,),
        in_specs=[pl.BlockSpec((p, bc), lambda i: (0, i))],
        out_specs=pl.BlockSpec((p, p), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((p, p), jnp.float32),
        scratch_shapes=[pltpu.VMEM((p, p), jnp.float32)],
        interpret=interpret,
    )(xt)


def _xty_kernel(xt_ref, yt_ref, g_ref, acc):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    acc[...] += lane_contract(xt_ref[...], yt_ref[...])

    @pl.when(i == pl.num_programs(0) - 1)
    def _writeback():
        g_ref[...] = acc[...]


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def xty(x, y, *, block_rows: int = 0, interpret: bool | None = None):
    """XᵀY for row-aligned tall X (n, p) and Y (n, q); returns (p, q) f32."""
    interpret = default_interpret() if interpret is None else interpret
    n, p = x.shape
    _, q = y.shape
    if not block_rows:
        block_rows = pick_block_rows(n, max(p, q), x.dtype, n_live=3)
    bc = lane_block(n, block_rows)
    xt = lanes(x, bc)
    yt = lanes(y, bc)
    return pl.pallas_call(
        _xty_kernel,
        grid=(xt.shape[1] // bc,),
        in_specs=[pl.BlockSpec((p, bc), lambda i: (0, i)),
                  pl.BlockSpec((q, bc), lambda i: (0, i))],
        out_specs=pl.BlockSpec((p, q), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((p, q), jnp.float32),
        scratch_shapes=[pltpu.VMEM((p, q), jnp.float32)],
        interpret=interpret,
    )(xt, yt)
