"""Float32 matrix products in bfloat16, the control's precision.

Each float32 operand is rounded to bfloat16 and the products of the
rounded values are accumulated in float32: one pass of the matrix unit,
as ``jax.lax.Precision.DEFAULT`` computes on a TPU.  Written out with
``reduce_precision`` (which the compiler keeps) and exact float32
products, it computes the same on every platform, so a CPU test reads
what the chip reads.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _bf16(a):
    return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)


def dot(a, b):
    """``a @ b`` of float32 matrices, their entries rounded to bfloat16."""
    return jnp.dot(_bf16(a), _bf16(b), precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32)
