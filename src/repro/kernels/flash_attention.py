"""Blockwise-softmax (Flash) attention kernel for the LM stack.

The LM architectures' prefill hot-spot.  FlashMatrix's two-level insight
applies directly: the (S, S) score matrix is a *virtual matrix* that must
never be materialized in HBM; only VMEM-resident (bq, bk) tiles of it ever
exist, with the online-softmax running (m, l) statistics playing the role
of the streaming aggregation VUDF's accumulator (same identity → update →
combine contract as core/dag.py sinks — logsumexp is literally the
``logsumexp`` AggVUDF).

Grid: (batch·heads, q_blocks, kv_blocks), sequential on TPU per core; the
kv axis is innermost so the (m, l, acc) scratch carries across kv blocks
and writes the output tile once at the last kv step.

Causal masking uses absolute row/col ids; fully-masked tiles are skipped
(the index-map trick would need a dynamic grid — masking with a finite
NEG_INF keeps the kernel robust in interpret mode and on Mosaic).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import default_interpret, round_up

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
            *, scale, causal, bq, bk, seq_len):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)  # (bq, d)
    k = k_ref[0].astype(jnp.float32)  # (bk, d)
    v = v_ref[0].astype(jnp.float32)  # (bk, d)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    q_ids = iq * bq + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    k_ids = ik * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = k_ids < seq_len  # kv padding
    if causal:
        mask = mask & (q_ids >= k_ids)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])
    l_scr[...] = l_scr[...] * alpha + p.sum(axis=1)
    m_scr[...] = m_new
    acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(ik == pl.num_programs(2) - 1)
    def _writeback():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("causal", "bq", "bk", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, scale: float | None = None,
                    bq: int = 128, bk: int = 128,
                    interpret: bool | None = None):
    """Blockwise attention over (BH, S, D) tensors.

    GQA is handled by the caller (repeat/reshape of KV heads); this kernel
    sees matched head counts.  Returns (BH, S, D) in q.dtype.
    """
    interpret = default_interpret() if interpret is None else interpret
    bh, s_len, d = q.shape
    skv = k.shape[1]
    scale = (d ** -0.5) if scale is None else scale
    bq = min(bq, round_up(s_len, 8))
    bk = min(bk, round_up(skv, 8))

    def pad_seq(x, blk):
        target = round_up(x.shape[1], blk)
        if target == x.shape[1]:
            return x
        return jnp.pad(x, ((0, 0), (0, target - x.shape[1]), (0, 0)))

    qp, kp, vp = pad_seq(q, bq), pad_seq(k, bk), pad_seq(v, bk)
    grid = (bh, qp.shape[1] // bq, kp.shape[1] // bk)

    kernel = functools.partial(_kernel, scale=scale, causal=causal,
                               bq=bq, bk=bk, seq_len=skv)
    out = pl.pallas_call(
        kernel,
        name="flash_attention",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct(qp.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        interpret=interpret,
    )(qp, kp, vp)
    return out[:, :s_len]
