"""Fused Lloyd-step kernel: distances → argmin → cluster stats, one pass.

The paper's k-means iteration is its marquee fusion demo: inner.prod with
the (squared-diff, sum) semiring, which.min, groupby.row(sum) and the
objective all stream together (core/algorithms/kmeans.py builds the same
DAG).  Here the whole fused group is ONE Pallas kernel:

  per VMEM-resident lane-major row block X_bᵀ (p, bc), centers C (k, p):
    Dᵀ   = ‖X_b‖² - 2 C X_bᵀ + ‖C‖²        (MXU matmul + VPU epilogue)
    lab  = argmin_k Dᵀ                      (VPU, over sublanes)
    Hᵀ   = onehot(lab)                      (VPU)
    sums += Hᵀ X_b                          (MXU)   — groupby.row(sum)
    cnts += Σ H                             (VPU)   — table()
    wss  += Σ min_k D                       (VPU)   — objective
    labels_b written out                    (HBM, bc ints)

X is read once; everything else lives in VMEM scratch until the final
writeback.  k and p are small (paper: k ≤ 64, p ≤ 512) so C, sums (k, p)
and the D tile (k, bc) all fit comfortably.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import (default_interpret, lane_block, lane_contract, lanes,
                     pick_block_rows, row_mask)


def _kernel(xt_ref, c_ref, nrows_ref, lab_ref, sums_ref, cnts_ref, wss_ref,
            acc_sums, acc_cnts, acc_wss, *, block):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        acc_sums[...] = jnp.zeros_like(acc_sums)
        acc_cnts[...] = jnp.zeros_like(acc_cnts)
        acc_wss[...] = jnp.zeros_like(acc_wss)

    # Everything stays 2-D with the rows along lanes: (p, bc) data, (k, bc)
    # distances, (1, bc) labels — no sublane↔lane relayouts.
    xt = xt_ref[...].astype(jnp.float32)        # (p, bc)
    c = c_ref[...].astype(jnp.float32)          # (k, p)
    k = c.shape[0]

    # Squared Euclidean distances via the inner-product expansion so the MXU
    # does the heavy lifting (the paper's BLAS dispatch, TPU-style).
    x2 = (xt * xt).sum(axis=0, keepdims=True)                     # (1, bc)
    c2 = (c * c).sum(axis=1, keepdims=True)                       # (k, 1)
    cx = jax.lax.dot_general(c, xt, (((1,), (0,)), ((), ())),
                             precision=jax.lax.Precision.HIGHEST,
                             preferred_element_type=jnp.float32)  # (k, bc)
    d = x2 - 2.0 * cx + c2

    valid = row_mask(nrows_ref[0], block)                         # (1, bc)
    mind = d.min(axis=0, keepdims=True)                           # (1, bc)
    # argmin as "first cluster attaining the minimum" (jnp.argmin's tie
    # rule), from a min over the sublane axis.
    kid = jax.lax.broadcasted_iota(jnp.int32, d.shape, 0).astype(jnp.float32)
    lab = jnp.where(d == mind, kid, float(k)).min(axis=0, keepdims=True)
    lab_ref[...] = lab.astype(jnp.int32)
    onehot = jnp.where((kid == lab) & valid, 1.0, 0.0)            # (k, bc)

    acc_sums[...] += lane_contract(onehot, xt)                    # (k, p)
    acc_cnts[...] += onehot.sum(axis=1, keepdims=True)            # (k, 1)
    acc_wss[...] += jnp.where(valid, mind, 0.0).sum(axis=1, keepdims=True)

    @pl.when(i == pl.num_programs(0) - 1)
    def _writeback():
        sums_ref[...] = acc_sums[...]
        cnts_ref[...] = acc_cnts[...]
        wss_ref[...] = acc_wss[...]


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def kmeans_assign(x, centers, *, block_rows: int = 0,
                  interpret: bool | None = None):
    """One fused Lloyd step.

    Args:   x (n, p) float; centers (k, p) float.
    Returns (labels (n,) int32, sums (k, p) f32, counts (k,) f32, wss (1,) f32).
    """
    interpret = default_interpret() if interpret is None else interpret
    n, p = x.shape
    k = centers.shape[0]
    if not block_rows:
        block_rows = pick_block_rows(n, p + k, x.dtype, n_live=3)
    bc = lane_block(n, block_rows)
    xt = lanes(x, bc)
    nrows = jnp.full((1,), n, jnp.int32)

    kernel = functools.partial(_kernel, block=bc)
    lab, sums, cnts, wss = pl.pallas_call(
        kernel,
        name="kmeans_assign",
        grid=(xt.shape[1] // bc,),
        in_specs=[
            pl.BlockSpec((p, bc), lambda i: (0, i)),
            pl.BlockSpec((k, p), lambda i: (0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, bc), lambda i: (0, i)),
            pl.BlockSpec((k, p), lambda i: (0, 0)),
            pl.BlockSpec((k, 1), lambda i: (0, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, xt.shape[1]), jnp.int32),
            jax.ShapeDtypeStruct((k, p), jnp.float32),
            jax.ShapeDtypeStruct((k, 1), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((k, p), jnp.float32),
            pltpu.VMEM((k, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
        ],
        interpret=interpret,
    )(xt, centers, nrows)
    return lab[0, :n], sums, cnts.reshape(k), wss.reshape(1)
