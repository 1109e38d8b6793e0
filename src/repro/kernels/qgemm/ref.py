"""Pure-jnp oracle for the qgemm kernel (int8 W8A8 GEMM + dequant epilogue)."""
from __future__ import annotations

import jax.numpy as jnp

from ..epilogue import epilogue


def qgemm_ref(x_q, w_q, scale, bias, *, activation: str | None = None,
              out_scale: float | None = None):
    """x_q: (M, K) int8; w_q: (K, N) int8; scale: (N,) f32; bias: (N,) f32
    (real-domain) or int32 (``b_q``, added to the int32 accumulator)."""
    acc = jnp.dot(x_q.astype(jnp.int32), w_q.astype(jnp.int32))
    bias = jnp.asarray(bias)
    return epilogue(acc, scale[None, :], bias[None, :], activation=activation,
                    out_scale=out_scale,
                    int_bias=jnp.issubdtype(bias.dtype, jnp.integer),
                    out_dtype=jnp.float32)
