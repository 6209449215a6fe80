"""Fused apply→aggregate streaming kernel — the GenOps cache-fuse hot-spot.

This is the paper's statistical-summary workload (§IV-A) as ONE Pallas
kernel, generalized: a tall matrix streams HBM→VMEM block-by-block and an
arbitrary set of *chains* — each a pipeline of unary VUDFs followed by a
column aggregation — updates from the same resident tile.  The elementwise
"apply" stages (x², |x|, √x, casts, …) never touch HBM — exactly the
paper's CPU-cache operation fusion, restated for the HBM→VMEM tier.

``fused_apply_agg(x, chains)`` takes a static chain spec

    chains = (((unary_name, ...), agg_name[, acc_dtype]), ...)

where each unary name resolves in the core VUDF registry (core/vudf.py),
agg_name ∈ {sum, min, max, count, count_nonzero}, and the optional
per-chain ``acc_dtype`` ('float32' | 'int32', default = the call-level
``acc_dtype`` parameter) selects the VMEM accumulator element type.  An
int32 accumulator makes integer sums/counts EXACT (a float32 accumulator
loses integer exactness past 2²⁴), which is what lets the engine's pallas
lowering claim integer apply→agg chains and chains containing lazy cast
nodes instead of falling back to the generic trace (ROADMAP item).  The
engine's pallas lowering (core/lowering.py) compiles eligible agg.col sink
segments sharing one source into a single call, so N statistics cost one
read of X.  ``fused_summary`` is the paper's six-statistic instance.

Grid: 1-D over row blocks (the processor-level partition axis) of the
lane-major view Xᵀ (common.py's layout policy): each statistic reduces
along lanes into a (p, 1) accumulator.
Accumulators live in VMEM scratch for the whole grid sweep (TPU grids
execute sequentially per core), initialized at step 0 and written back at
the last step — the same identity→update→combine contract as core/dag.py
sinks.

Rows are padded to the block multiple with neutral values handled by
masking inside the kernel (min/max need ±inf / int extrema, so padding
cannot be plain zeros).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import (default_interpret, lane_block, lanes,
                     pick_block_rows, row_mask)

#: Aggregations the chain kernel can accumulate in a VMEM scratch register.
CHAIN_AGGS = ("sum", "min", "max", "count", "count_nonzero")

#: Unary VUDFs safe to evaluate on a VMEM tile inside the kernel body.
#: The cast family keeps lazily-inserted dtype conversions (paper §III-D)
#: inside the kernel so mixed-dtype chains stay eligible.
CHAIN_UNARIES = ("identity", "abs", "sq", "sqrt", "exp", "log", "log1p",
                 "neg", "sigmoid", "floor", "ceil", "round", "sign",
                 "cast_float32", "cast_int32", "cast_bfloat16")

#: Accumulator dtypes a chain may request.
CHAIN_ACC_DTYPES = ("float32", "int32")

#: fused_summary's chain spec: (sum, sum-of-squares, min, max, L1, nnz).
SUMMARY_CHAINS = (((), "sum"), (("sq",), "sum"), ((), "min"), ((), "max"),
                  (("abs",), "sum"), ((), "count_nonzero"))


def _unary_fn(name):
    from ..core import vudf as vudf_mod  # deferred: keep kernels importable alone
    return vudf_mod.unary(name).fn


def _acc_extreme(dtype, *, biggest: bool):
    dt = jnp.dtype(dtype)
    if dt.kind == "f":
        return jnp.inf if biggest else -jnp.inf
    info = np.iinfo(dt.name)
    return info.max if biggest else info.min


def normalize_chains(chains, acc_dtype: str = "float32"):
    """Canonicalize a chain spec to ((unaries, agg, acc_dtype), ...);
    2-tuples take the call-level default accumulator dtype."""
    out = []
    for chain in chains:
        if len(chain) == 2:
            unaries, agg = chain
            acc = acc_dtype
        else:
            unaries, agg, acc = chain
        out.append((tuple(unaries), agg, acc))
    return tuple(out)


def _chain_kernel(xt_ref, nrows_ref, *refs, chains, block):
    n_out = len(chains)
    out_refs, accs = refs[:n_out], refs[n_out:]
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        for (_, agg, _), acc in zip(chains, accs):
            if agg == "min":
                acc[...] = jnp.full_like(
                    acc, _acc_extreme(acc.dtype, biggest=True))
            elif agg == "max":
                acc[...] = jnp.full_like(
                    acc, _acc_extreme(acc.dtype, biggest=False))
            else:
                acc[...] = jnp.zeros_like(acc)

    x = xt_ref[...]                        # (p, block): rows along lanes
    # Rows beyond the true length are padding: mask them out of every stat.
    valid = row_mask(nrows_ref[0], block)  # (1, block)

    for (unaries, agg, _), acc in zip(chains, accs):
        at = acc.dtype
        # Float accumulators evaluate the chain in f32 (the MXU/VPU-native
        # mode); int accumulators keep the source's integer algebra exact.
        v = x.astype(jnp.float32) if jnp.dtype(at).kind == "f" else x
        for u in unaries:
            v = _unary_fn(u)(v)
        if agg == "sum":
            acc[...] += jnp.where(valid, v, 0).astype(at).sum(
                axis=1, keepdims=True)
        elif agg == "count":
            acc[...] += jnp.broadcast_to(
                valid, v.shape).astype(at).sum(axis=1, keepdims=True)
        elif agg == "count_nonzero":
            acc[...] += (valid & (v != 0)).astype(at).sum(
                axis=1, keepdims=True)
        elif agg == "min":
            big = _acc_extreme(at, biggest=True)
            acc[...] = jnp.minimum(
                acc[...], jnp.where(valid, v.astype(at), big).min(
                    axis=1, keepdims=True))
        elif agg == "max":
            small = _acc_extreme(at, biggest=False)
            acc[...] = jnp.maximum(
                acc[...], jnp.where(valid, v.astype(at), small).max(
                    axis=1, keepdims=True))

    @pl.when(i == pl.num_programs(0) - 1)
    def _writeback():
        for o, acc in zip(out_refs, accs):
            o[...] = acc[...]


@functools.partial(jax.jit, static_argnames=("chains", "acc_dtype",
                                             "block_rows", "interpret"))
def fused_apply_agg(x, chains, *, acc_dtype: str = "float32",
                    block_rows: int = 0, interpret: bool | None = None):
    """Column statistics of a tall (n, p) matrix in one HBM pass.

    ``chains``: static tuple of ``((unary_name, ...), agg_name)`` or
    ``((unary_name, ...), agg_name, acc_dtype)`` entries; ``acc_dtype`` is
    the default accumulator element type for the 2-tuple form.
    Returns one (p,) array per chain, in that chain's accumulator dtype.
    """
    if acc_dtype not in CHAIN_ACC_DTYPES:
        raise ValueError(f"unsupported accumulator dtype {acc_dtype!r}; "
                         f"have {CHAIN_ACC_DTYPES}")
    chains = normalize_chains(chains, acc_dtype)
    for unaries, agg, acc in chains:
        if agg not in CHAIN_AGGS:
            raise ValueError(f"unsupported chain aggregation {agg!r}")
        if acc not in CHAIN_ACC_DTYPES:
            raise ValueError(f"unsupported accumulator dtype {acc!r}; "
                             f"have {CHAIN_ACC_DTYPES}")
        for u in unaries:
            if u not in CHAIN_UNARIES:
                raise ValueError(f"unsupported chain unary {u!r}")
    interpret = default_interpret() if interpret is None else interpret
    n, p = x.shape
    if not block_rows:
        block_rows = pick_block_rows(n, p, x.dtype, n_live=2)
    bc = lane_block(n, block_rows)
    xt = lanes(x, bc)
    nrows = jnp.full((1,), n, jnp.int32)

    kernel = functools.partial(_chain_kernel, chains=chains, block=bc)
    outs = pl.pallas_call(
        kernel,
        grid=(xt.shape[1] // bc,),
        in_specs=[
            pl.BlockSpec((p, bc), lambda i: (0, i)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=[pl.BlockSpec((p, 1), lambda i: (0, 0))] * len(chains),
        out_shape=[jax.ShapeDtypeStruct((p, 1), jnp.dtype(acc))
                   for _, _, acc in chains],
        scratch_shapes=[pltpu.VMEM((p, 1), jnp.dtype(acc))
                        for _, _, acc in chains],
        interpret=interpret,
    )(xt, nrows)
    return tuple(o.reshape(p) for o in outs)


def fused_summary(x, *, block_rows: int = 0, interpret: bool | None = None):
    """Column statistics of a tall (n, p) matrix in one HBM pass.

    Returns (sum, sumsq, min, max, l1, nnz) each of shape (p,), float32.
    """
    return fused_apply_agg(x, SUMMARY_CHAINS, block_rows=block_rows,
                           interpret=interpret)
