"""Pallas TPU kernel: int8 k x k depthwise convolution (k = 3 or 5) with
fused folded-BN + activation + requantization (MobileNetV2's hot-spot op,
§VI; MobileNetV3 adds the 5x5 layers).

Depthwise conv has no reduction over channels, so it is VPU (not MXU) work:
k*k shifted element-wise products accumulated in int32, then a
multiply-only f32 epilogue (``kernels.epilogue``).  The kernel works **channels-last**: the wrapper transposes the
(N, C, R, W) band stack to (N, R, W, C), so channels fill the 128-lane axis
and the map width the sublanes, and transposes the int8 output back.  The
callers (the executor is CHW) see the same shapes in and out.

One grid step covers a block of many stack entries (samples × bands) and
channels, sized from the shape by :func:`dwconv_blocks`; inside a step the
kernel loops over the entries and over chunks of output rows, so the code
Mosaic emits stays the size of one row chunk whatever the block holds.

Layout rules the TPU compiler (Mosaic) imposes, and how the kernel meets
them:

* per-channel operands (tap weights, scale, bias) arrive as ``(..., 1, C)``
  lane rows: a block's last two dims equal the array's or tile it, and
  ``(1, cb)`` broadcasts against the ``(rows, cols, cb)`` accumulator;
* stride 2 never slices a loaded value with a step: the wrapper splits the
  padded input into ``stride**2`` row/column phases (a reshape + transpose in
  XLA, folded into the channels-last transpose), so tap ``(i, j)`` is the
  unit-stride window of phase ``(i % s, j % s)`` at offset
  ``(i // s, j // s)``;
* the column offset of a tap is a sublane offset, which Mosaic takes only on
  32-bit values: a row chunk is widened to int32 once, then windowed.

:func:`dwconv3x3_bands` takes a stack of spatial band windows
(bands, C, R, W+2), :func:`dwconv5x5_bands` of (bands, C, R, W+4): one
kernel body, the tap count a static parameter, each size behind its own
jitted entry so its device events carry its name.  Under ``jax.vmap`` (the executor's ``run_batch``) a
``custom_vmap`` rule folds the batch into the stack, so B samples × bands
reach the kernel as one stack, not as a grid axis with a block of one
sample.  Rows beyond a band's valid window are zero-filled by the caller and
their outputs discarded, so heterogeneous band heights ride one uniform
stack.  :func:`dwconv3x3` and :func:`dwconv5x5` are the one-sample cases (a
stack of one band).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..backend import resolve_interpret
from ..batching import stack_folding
from ..epilogue import epilogue

# Estimated VMEM of one grid step (double-buffered blocks plus the row
# chunk's temporaries), kept well under v5e's 16 MiB scoped VMEM.
VMEM_BUDGET = 6 * 2**20
# int32 vregs (8 sublanes x 128 lanes) one widened input row chunk may span
CHUNK_VREGS = 16


def _cdiv(a: int, b: int) -> int:
    return -(-a // b)


def _tile_bytes(rows: int, cols: int, lanes: int, itemsize: int) -> int:
    """VMEM bytes of a (rows, cols, lanes) array: lanes pad to 128 and cols
    to the dtype's sublane tile (32 rows for int8, 8 for 32-bit)."""
    sub = 32 // itemsize
    return rows * _cdiv(cols, sub) * sub * _cdiv(lanes, 128) * 128 * itemsize


class Geometry(NamedTuple):
    oh: int       # output rows
    ow: int       # output cols
    phases: int   # stride**2 row/column phases of the input
    hh: int       # rows of one phase
    ww: int       # cols of one phase
    k: int = 3    # taps along each axis


def geometry(rows: int, cols: int, stride: int, k: int = 3) -> Geometry:
    oh = (rows - k) // stride + 1
    ow = (cols - k) // stride + 1
    if stride == 1:
        return Geometry(oh, ow, 1, rows, cols, k)
    return Geometry(oh, ow, stride * stride, oh + (k - 1) // stride,
                    ow + (k - 1) // stride, k)


class Blocks(NamedTuple):
    stack: int      # stack entries (samples x bands) per grid step
    channels: int   # channels per grid step: all of C, or a multiple of 128
    rows: int       # output rows per in-kernel chunk


def step_vmem_bytes(stack: int, channels: int, rows: int, g: Geometry) -> int:
    """Estimated VMEM of one grid step: input, output and per-channel blocks,
    each double-buffered, plus the int32 temporaries of one row chunk."""
    x = stack * g.phases * _tile_bytes(g.hh, g.ww, channels, 1)
    out = stack * max(_tile_bytes(g.oh, g.ow, channels, 1),
                      _tile_bytes(g.oh, g.ow, channels, 4))
    per_channel = (g.k * g.k + 2) * _tile_bytes(1, 1, channels, 4)
    slab = _tile_bytes(rows + g.k - 1, g.ww, channels, 4)
    # widened phases, their column shifts, the accumulator and epilogue
    temps = (g.k * g.phases * slab
             + 4 * _tile_bytes(rows, g.ow, channels, 4))
    return 2 * (x + out + per_channel) + temps


def _divisors(n: int) -> list[int]:
    return [d for d in range(1, n + 1) if n % d == 0]


def dwconv_blocks(n: int, rows: int, cols: int, c: int,
                  stride: int, k: int = 3) -> Blocks:
    """The blocks for a stack of ``n`` windows of (c, rows, cols): the
    widest channel block (all of C, else the multiple of 128 that pads C the
    least) whose step fits :data:`VMEM_BUDGET`, the largest divisor of the output rows whose
    widened input chunk spans at most :data:`CHUNK_VREGS` vregs, and the
    largest divisor of ``n`` whose step fits the budget.  A shape no block
    fits gets the smallest blocks, and Mosaic reports what does not fit."""
    g = geometry(rows, cols, stride, k)
    # below all of C, the blocks that pad C least come first, widest first
    narrow = sorted(range(128, 128 * _cdiv(c, 128), 128),
                    key=lambda b: (_cdiv(c, b) * b, -b))
    widths = [c, *narrow]
    cb = next((k for k in widths
               if step_vmem_bytes(1, k, 1, g) <= VMEM_BUDGET), min(c, 128))
    per_row = _cdiv(g.ww, 8) * _cdiv(cb, 128)
    rb = max(r for r in _divisors(g.oh)
             if r == 1 or (r + k - 1) * per_row <= CHUNK_VREGS)
    nb = max(d for d in _divisors(n)
             if d == 1 or step_vmem_bytes(d, cb, rb, g) <= VMEM_BUDGET)
    return Blocks(nb, cb, rb)


def _dwconv_kernel(x_ref, w_ref, scale_ref, bias_ref, o_ref, *, k: int,
                   stride: int, rows: int, activation: str | None,
                   out_scale: float | None, int_bias: bool):
    # x_ref: (nb, s*s, hh, ww, cb) int8 phases; w_ref: (k*k, 1, cb) int32;
    # scale_ref/bias_ref: (1, cb); o_ref: (nb, oh, ow, cb)
    nb, oh, ow, _ = o_ref.shape
    extra = (k - 1) // stride   # input rows a chunk needs beyond its own
    taps = [w_ref[t] for t in range(k * k)]
    scale, bias = scale_ref[...], bias_ref[...]

    def chunk(t, carry):
        n, r0 = t // (oh // rows), (t % (oh // rows)) * rows
        slabs = {}
        acc = None
        for i in range(k):
            for j in range(k):
                p, q, a, b = i % stride, j % stride, i // stride, j // stride
                if (p, q) not in slabs:
                    slabs[p, q] = x_ref[n, p * stride + q,
                                        pl.ds(r0, rows + extra)
                                        ].astype(jnp.int32)
                if (p, q, b) not in slabs:
                    slabs[p, q, b] = slabs[p, q][:, b:b + ow]
                term = slabs[p, q, b][a:a + rows] * taps[k * i + j]
                acc = term if acc is None else acc + term
        o_ref[n, pl.ds(r0, rows)] = epilogue(
            acc, scale, bias, activation=activation, out_scale=out_scale,
            int_bias=int_bias, out_dtype=o_ref.dtype)
        return carry

    jax.lax.fori_loop(0, nb * (oh // rows), chunk, 0)


def _channels_last_phases(x, stride: int, g: Geometry):
    """(N, C, R, Wp) -> (N, s*s, hh, ww, C): phase ``p*s + q`` holds rows
    ``p::s`` and columns ``q::s``, cropped or zero-padded to the common
    extent every tap window needs.  Stride 1 is one phase, as is."""
    if stride == 1:
        return x.transpose(0, 2, 3, 1)[:, None]
    n, c, _, _ = x.shape
    x = x[:, :, :stride * g.hh, :stride * g.ww]
    x = jnp.pad(x, ((0, 0), (0, 0), (0, stride * g.hh - x.shape[2]),
                    (0, stride * g.ww - x.shape[3])))
    x = x.reshape(n, c, g.hh, stride, g.ww, stride).transpose(0, 3, 5, 2, 4, 1)
    return x.reshape(n, stride * stride, g.hh, g.ww, c)


def _dwconv_stack(x_win, w, scale, bias, *, stride: int,
                  activation: str | None, out_scale: float | None,
                  interpret: bool):
    """The kernel on one (N, C, R, Wp) stack and (C, k, k) taps; returns
    (N, C, oh, ow)."""
    n, c, rp, wp = x_win.shape
    k = w.shape[-1]
    g = geometry(rp, wp, stride, k)
    blk = dwconv_blocks(n, rp, wp, c, stride, k)
    cp = _cdiv(c, blk.channels) * blk.channels
    phases = _channels_last_phases(x_win, stride, g)
    taps = w.reshape(c, k * k).T.astype(jnp.int32)[:, None]
    scale, bias = scale.reshape(1, c), jnp.asarray(bias).reshape(1, c)
    if cp != c:
        pad = ((0, 0),) * 4 + ((0, cp - c),)
        phases = jnp.pad(phases, pad)
        taps, scale, bias = (jnp.pad(a, pad[-a.ndim:])
                             for a in (taps, scale, bias))
    int_bias = jnp.issubdtype(bias.dtype, jnp.integer)
    out_dtype = jnp.int8 if out_scale is not None else jnp.float32
    kernel = functools.partial(_dwconv_kernel, k=k, stride=stride,
                               rows=blk.rows,
                               activation=activation, out_scale=out_scale,
                               int_bias=int_bias)
    nb, cb = blk.stack, blk.channels
    per_channel = pl.BlockSpec((1, cb), lambda si, ci: (0, ci))
    out = pl.pallas_call(
        kernel,
        grid=(n // nb, cp // cb),
        in_specs=[
            pl.BlockSpec((nb, g.phases, g.hh, g.ww, cb),
                         lambda si, ci: (si, 0, 0, 0, ci)),
            pl.BlockSpec((k * k, 1, cb), lambda si, ci: (0, 0, ci)),
            per_channel,
            per_channel,
        ],
        out_specs=pl.BlockSpec((nb, g.oh, g.ow, cb),
                               lambda si, ci: (si, 0, 0, ci)),
        out_shape=jax.ShapeDtypeStruct((n, g.oh, g.ow, cp), out_dtype),
        interpret=interpret,
    )(phases, taps, scale, bias)
    return out[..., :c].transpose(0, 3, 1, 2)


def _bands(x_win, w, scale, bias, *, stride, activation, out_scale,
           interpret):
    fn = functools.partial(_dwconv_stack, stride=stride,
                           activation=activation, out_scale=out_scale,
                           interpret=resolve_interpret(interpret))
    return stack_folding(fn)(x_win, w, scale, bias)


_STATIC = ("stride", "activation", "out_scale", "interpret")


@functools.partial(jax.jit, static_argnames=_STATIC)
def dwconv3x3_bands(x_win, w, scale, bias, *, stride: int = 1,
                    activation: str | None = None,
                    out_scale: float | None = None,
                    interpret: bool | None = None):
    """Batched-band 3x3 depthwise conv: ``x_win`` is (bands, C, R, W+2) int8
    — one pre-gathered row window per spatial band (halo/zero rows and the
    width pad already in place, shorter bands zero-filled to the common R).

    Every band of a fused spatial block (and, under ``jax.vmap``, every
    sample's bands) runs in one kernel invocation of a few grid steps; the
    per-channel weight/scale/bias are shared across the stack (spatial mode
    replicates weights).

    ``w``: (C, 3, 3) int8; ``scale``: (C,) f32; ``bias``: (C,) f32
    (real-domain, f32 epilogue) or int32 (quantized ``b_q``, added in exact
    int32 — the bit-exact executor path).  Returns (bands, C, oh, ow) int8
    (requantized at ``out_scale``) or f32.  ``interpret=None`` auto-detects:
    compiled on TPU, interpret elsewhere.
    """
    return _bands(x_win, w, scale, bias, stride=stride,
                  activation=activation, out_scale=out_scale,
                  interpret=interpret)


@functools.partial(jax.jit, static_argnames=_STATIC)
def dwconv5x5_bands(x_win, w, scale, bias, *, stride: int = 1,
                    activation: str | None = None,
                    out_scale: float | None = None,
                    interpret: bool | None = None):
    """:func:`dwconv3x3_bands` for 5x5 taps: ``x_win`` is (bands, C, R,
    W+4), ``w`` (C, 5, 5).  Same kernel body and contract."""
    return _bands(x_win, w, scale, bias, stride=stride,
                  activation=activation, out_scale=out_scale,
                  interpret=interpret)



def dwconv3x3(x_pad, w, scale, bias, *, stride: int = 1,
              activation: str | None = None, out_scale: float | None = None,
              interpret: bool | None = None):
    """One sample: ``x_pad`` is (C, H+2, W+2) int8 (pre-padded by 1); same
    contract as :func:`dwconv3x3_bands` otherwise.  Returns (C, oh, ow)."""
    return dwconv3x3_bands(x_pad[None], w, scale, bias, stride=stride,
                           activation=activation, out_scale=out_scale,
                           interpret=interpret)[0]


def dwconv5x5(x_pad, w, scale, bias, *, stride: int = 1,
              activation: str | None = None, out_scale: float | None = None,
              interpret: bool | None = None):
    """One sample: ``x_pad`` is (C, H+4, W+4) int8 (pre-padded by 2); same
    contract as :func:`dwconv5x5_bands` otherwise.  Returns (C, oh, ow)."""
    return dwconv5x5_bands(x_pad[None], w, scale, bias, stride=stride,
                           activation=activation, out_scale=out_scale,
                           interpret=interpret)[0]


# the entry points by tap count, for callers that hold a (C, k, k) weight
BANDS = {3: dwconv3x3_bands, 5: dwconv5x5_bands}
SAMPLE = {3: dwconv3x3, 5: dwconv5x5}
