"""Pallas TPU kernel: int8 3x3 depthwise convolution with fused folded-BN +
ReLU6 + requantization (MobileNetV2's hot-spot op, §VI).

Depthwise conv has no reduction over channels, so it is VPU (not MXU) work:
each grid step loads a (block_c, rows, cols) tile of the pre-padded input
into VMEM and accumulates the 9 shifted element-wise products in int32 —
the whole channel tile's activations stay VMEM-resident through the
epilogue.  Channels are independent ("kernel-wise" in the paper's
splitting), so the channel grid dimension is also the natural split axis.

Layout rules the TPU compiler (Mosaic) imposes, and how the kernel meets
them:

* per-channel operands (tap weights, scale, bias) arrive as ``(..., C, 1, 1)``
  arrays blocked ``(block_c, 1, 1)``: a block's last two dims equal the
  array's, and ``(bc, 1, 1)`` broadcasts against the ``(bc, oh, ow)``
  accumulator without any in-kernel reshape;
* stride 2 never slices a loaded value with a step: the wrapper splits the
  padded input into ``stride**2`` row/column phases (a plain reshape +
  transpose in XLA), so tap ``(i, j)`` is the unit-stride window of phase
  ``(i % s, j % s)`` at offset ``(i // s, j // s)`` — a static ref load.

:func:`dwconv3x3_bands` takes a stack of spatial band windows
(bands, C, R, W+2): the **band index is a grid axis**, so every band of a
fused spatial block executes in a single ``pallas_call`` instead of one
dispatch per band (the split-executor hot path).  Rows beyond a band's valid
window are zero-filled by the caller and their outputs discarded, so
heterogeneous band heights ride one uniform grid.  :func:`dwconv3x3` is the
one-sample case (a stack of one band).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..backend import resolve_interpret


def _epilogue(acc, scale, bias, *, activation: str | None,
              out_scale: float | None, int_bias: bool, out_dtype):
    """Fused folded-BN + activation + requantization epilogue on a
    (bc, oh, ow) int32 accumulator (scale/bias are (bc, 1, 1))."""
    if int_bias:
        # b_q added in exact int32; float steps are multiplies only so the
        # result is bit-identical to the executors' jnp epilogue (no
        # FMA-contraction sensitivity — see core.quantize).
        y = (acc + bias).astype(jnp.float32) * scale
    else:
        y = acc.astype(jnp.float32) * scale + bias
    if activation == "relu":
        y = jnp.maximum(y, 0.0)
    elif activation == "relu6":
        y = jnp.clip(y, 0.0, 6.0)
    if out_scale is not None:
        return jnp.clip(jnp.round(y * (1.0 / out_scale)),
                        -127, 127).astype(jnp.int8)
    return y.astype(out_dtype)


def _dwconv_kernel(x_ref, w_ref, scale_ref, bias_ref, o_ref, *, stride: int,
                   activation: str | None, out_scale: float | None,
                   int_bias: bool):
    # x_ref: (1, s*s, bc, hh, ww) int8 phases; w_ref: (9, bc, 1, 1) int32;
    # scale_ref/bias_ref: (bc, 1, 1); o_ref: (1, bc, oh, ow)
    _, bc, oh, ow = o_ref.shape
    acc = jnp.zeros((bc, oh, ow), jnp.int32)
    for i in range(3):
        for j in range(3):
            phase = (i % stride) * stride + j % stride
            win = x_ref[0, phase, :, pl.ds(i // stride, oh),
                        pl.ds(j // stride, ow)]
            acc = acc + win.astype(jnp.int32) * w_ref[3 * i + j]
    o_ref[0] = _epilogue(acc, scale_ref[...], bias_ref[...],
                         activation=activation, out_scale=out_scale,
                         int_bias=int_bias, out_dtype=o_ref.dtype)


def _phase_split(x, stride: int, oh: int, ow: int):
    """(B, C, R, Wp) -> (B, s*s, C, oh + 2//s, ow + 2//s): phase ``p*s + q``
    holds rows ``p::s`` and columns ``q::s``, cropped or zero-padded to the
    common extent every tap window needs.  Stride 1 is one phase, as is."""
    if stride == 1:
        return x[:, None]
    b, c, _, _ = x.shape
    hh, ww = oh + 2 // stride, ow + 2 // stride
    x = x[:, :, :stride * hh, :stride * ww]
    x = jnp.pad(x, ((0, 0), (0, 0), (0, stride * hh - x.shape[2]),
                    (0, stride * ww - x.shape[3])))
    x = x.reshape(b, c, hh, stride, ww, stride).transpose(0, 3, 5, 1, 2, 4)
    return x.reshape(b, stride * stride, c, hh, ww)


@functools.partial(jax.jit, static_argnames=("stride", "activation",
                                             "out_scale", "block_c",
                                             "interpret"))
def dwconv3x3_bands(x_win, w, scale, bias, *, stride: int = 1,
                    activation: str | None = None,
                    out_scale: float | None = None,
                    block_c: int = 8, interpret: bool | None = None):
    """Batched-band 3x3 depthwise conv: ``x_win`` is (bands, C, R, W+2) int8
    — one pre-gathered row window per spatial band (halo/zero rows and the
    width pad already in place, shorter bands zero-filled to the common R).

    The band index is the leading **grid axis** (grid = (bands, C//block_c)),
    so a fused spatial block's depthwise stage is ONE kernel invocation for
    the whole cluster instead of one dispatch per band.  The per-channel
    weight/scale/bias tiles are selected by the channel ``program_id``,
    shared across bands (spatial mode replicates weights).

    ``w``: (C, 3, 3) int8; ``scale``: (C,) f32; ``bias``: (C,) f32
    (real-domain, f32 epilogue) or int32 (quantized ``b_q``, added in exact
    int32 — the bit-exact executor path).  Returns (bands, C, oh, ow) int8
    (requantized at ``out_scale``) or f32.  C must be a multiple of
    ``block_c`` (ops.py pads).  ``interpret=None`` auto-detects: compiled on
    TPU, interpret elsewhere.
    """
    interpret = resolve_interpret(interpret)
    b, c, rp, wp = x_win.shape
    assert c % block_c == 0
    oh = (rp - 3) // stride + 1
    ow = (wp - 3) // stride + 1
    phases = _phase_split(x_win, stride, oh, ow)
    _, n_ph, _, hh, ww = phases.shape
    taps = w.reshape(c, 9).T.astype(jnp.int32).reshape(9, c, 1, 1)
    bias = jnp.asarray(bias)
    int_bias = jnp.issubdtype(bias.dtype, jnp.integer)
    out_dtype = jnp.int8 if out_scale is not None else jnp.float32
    kernel = functools.partial(_dwconv_kernel, stride=stride,
                               activation=activation, out_scale=out_scale,
                               int_bias=int_bias)
    per_channel = pl.BlockSpec((block_c, 1, 1), lambda bi, ci: (ci, 0, 0))
    return pl.pallas_call(
        kernel,
        grid=(b, c // block_c),
        in_specs=[
            pl.BlockSpec((1, n_ph, block_c, hh, ww),
                         lambda bi, ci: (bi, 0, ci, 0, 0)),
            pl.BlockSpec((9, block_c, 1, 1), lambda bi, ci: (0, ci, 0, 0)),
            per_channel,
            per_channel,
        ],
        out_specs=pl.BlockSpec((1, block_c, oh, ow),
                               lambda bi, ci: (bi, ci, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, c, oh, ow), out_dtype),
        interpret=interpret,
    )(phases, taps, scale.reshape(c, 1, 1), bias.reshape(c, 1, 1))


def dwconv3x3(x_pad, w, scale, bias, *, stride: int = 1,
              activation: str | None = None, out_scale: float | None = None,
              block_c: int = 8, interpret: bool | None = None):
    """One sample: ``x_pad`` is (C, H+2, W+2) int8 (pre-padded by 1); same
    contract as :func:`dwconv3x3_bands` otherwise.  Returns (C, oh, ow)."""
    return dwconv3x3_bands(x_pad[None], w, scale, bias, stride=stride,
                           activation=activation, out_scale=out_scale,
                           block_c=block_c, interpret=interpret)[0]
