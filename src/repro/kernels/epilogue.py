"""The fused epilogue the int8 Pallas kernels (``qgemm``, ``dwconv``) share:
bias, dequantization, activation and requantization on an int32
accumulator tile, in VMEM before the tile is written back.

The arithmetic is the executors' (``core.quantize.requantize``) step for
step, so every path rounds alike; see ``core.quantize.epilogue_params`` for
the rule that keeps float steps to multiplies, and for how the hard-sigmoid's
``+ 3`` is written under it.
"""
from __future__ import annotations

import jax.numpy as jnp


def activate(y, activation: str | None):
    """The fused activation on a float32 tile."""
    if activation is None:
        return y
    if activation == "relu":
        return jnp.maximum(y, 0.0)
    if activation == "relu6":
        return jnp.clip(y, 0.0, 6.0)
    if activation == "hsigmoid":
        return jnp.clip(y * (1.0 / 6.0), -0.5, 0.5) + 0.5
    if activation == "hswish":
        return y * (jnp.clip(y * (1.0 / 6.0), -0.5, 0.5) + 0.5)
    raise ValueError(f"unknown activation {activation!r}")


def epilogue(acc, scale, bias, *, activation: str | None,
             out_scale: float | None, int_bias: bool, out_dtype):
    """Folded-BN + activation + requantization of an int32 accumulator
    (``scale``/``bias`` broadcast against it; arrays, or Pallas refs read
    where they are used).  An int32 ``bias`` (the quantized ``b_q``) is
    added in exact int32 before the one dequantizing multiply; a float32
    one after it."""
    if int_bias:
        y = (acc + bias[...]).astype(jnp.float32) * scale[...]
    else:
        y = acc.astype(jnp.float32) * scale[...] + bias[...]
    y = activate(y, activation)
    if out_scale is not None:
        return jnp.clip(jnp.round(y * (1.0 / out_scale)),
                        -127, 127).astype(jnp.int8)
    return y.astype(out_dtype)
