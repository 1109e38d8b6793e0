"""Pure-jnp oracle for the int8 depthwise conv kernel (3x3 or 5x5)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..epilogue import epilogue


def dwconv_valid_ref(x_pad, w, scale, bias, *, stride: int = 1,
               activation: str | None = None,
               out_scale: float | None = None):
    """x_pad: (C, H + k - 1, W + k - 1) int8 pre-padded; w: (C, k, k) int8;
    bias: (C,) f32 (real-domain) or int32 (``b_q``, added to the int32
    accumulator)."""
    lhs = x_pad[None].astype(jnp.int32)
    rhs = w[:, None].astype(jnp.int32)
    acc = jax.lax.conv_general_dilated(
        lhs, rhs, window_strides=(stride, stride), padding="VALID",
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
        feature_group_count=x_pad.shape[0],
        preferred_element_type=jnp.int32)[0]
    bias = jnp.asarray(bias)
    return epilogue(acc, scale[:, None, None], bias[:, None, None],
                    activation=activation, out_scale=out_scale,
                    int_bias=jnp.issubdtype(bias.dtype, jnp.integer),
                    out_dtype=jnp.float32)

