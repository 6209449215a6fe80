"""Weighted Gram kernel: G = XᵀWX for a diagonal weight vector w.

The IRLS hot spot (algorithms/glm.py): every GLM Newton step contracts the
long dimension of X against itself under per-row weights,

    G = Σ_i w_i · x_i x_iᵀ        (p × p, f32 accumulation)

which in R is ``crossprod(X * w, X)``.  The engine's pallas backend
(``core.lowering._match_weighted_gram``) recognizes the fused
``mapply.col(X, w, mul) → inner.prod(mul, sum)`` contraction segment and
lowers it onto this kernel, so the elementwise reweighting never exists in
HBM — X and w stream through VMEM once and only the (p, p) accumulator
persists across the grid sweep, exactly like `gram.py`.

Grid: 1-D over row blocks of the lane-major views Xᵀ (p, bc) and wᵀ
(1, bc); zero row padding is neutral (padded w rows are zero, so their
outer products vanish).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import (default_interpret, lane_block, lane_contract, lanes,
                     pick_block_rows)


def _wgram_kernel(xt_ref, w_ref, g_ref, acc):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    xt = xt_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)  # (1, bc), broadcasts per row
    acc[...] += lane_contract(xt * w, xt)

    @pl.when(i == pl.num_programs(0) - 1)
    def _writeback():
        g_ref[...] = acc[...]


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def wgram(x, w, *, block_rows: int = 0, interpret: bool | None = None):
    """G = XᵀWX for tall (n, p) X and per-row weights w (n,) or (n, 1).

    Returns (p, p) float32.  One HBM read of X and w; the reweighted rows
    exist only inside the VMEM tile.
    """
    interpret = default_interpret() if interpret is None else interpret
    n, p = x.shape
    if not block_rows:
        block_rows = pick_block_rows(n, p, x.dtype, n_live=3)
    bc = lane_block(n, block_rows)
    xt = lanes(x, bc)  # zero pad: neutral under zero weights
    wt = lanes(w.reshape(n, 1), bc)
    return pl.pallas_call(
        _wgram_kernel,
        name="wgram",
        grid=(xt.shape[1] // bc,),
        in_specs=[pl.BlockSpec((p, bc), lambda i: (0, i)),
                  pl.BlockSpec((1, bc), lambda i: (0, i))],
        out_specs=pl.BlockSpec((p, p), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((p, p), jnp.float32),
        scratch_shapes=[pltpu.VMEM((p, p), jnp.float32)],
        interpret=interpret,
    )(xt, wt)
