"""jit'd wrapper for the depthwise conv kernel: pads channels to the block
multiple and the spatial dims by 1 (SAME padding for 3x3)."""
from __future__ import annotations

import jax.numpy as jnp

from .dwconv import dwconv3x3, dwconv3x3_bands
from .ref import dwconv3x3_ref


def dwconv(x_q, w, scale, bias, *, stride: int = 1, activation=None,
           out_scale=None, block_c: int = 8, interpret: bool | None = None):
    """x_q: (C, H, W) int8 (unpadded); SAME 3x3 depthwise conv.
    ``interpret=None`` auto-detects the backend (see kernels.backend)."""
    c = x_q.shape[0]
    pad_c = (-c) % block_c
    xp = jnp.pad(x_q, ((0, pad_c), (1, 1), (1, 1)))
    wp = jnp.pad(w, ((0, pad_c), (0, 0), (0, 0)))
    sp = jnp.pad(scale, (0, pad_c))
    bp = jnp.pad(bias, (0, pad_c))
    out = dwconv3x3(xp, wp, sp, bp, stride=stride, activation=activation,
                    out_scale=out_scale, block_c=block_c, interpret=interpret)
    return out[:c]


def dwconv_bands(x_win, w, scale, bias, *, stride: int = 1, activation=None,
                 out_scale=None, block_c: int = 8,
                 interpret: bool | None = None):
    """Batched-band 3x3 depthwise conv over pre-gathered band windows:
    ``x_win`` is (bands, C, R, W+2) with every band's halo/zero rows already
    materialized (shorter bands zero-filled to the common R).  Pads channels
    to the block multiple and runs :func:`dwconv3x3_bands` — the band index
    is a Pallas grid axis, so all bands execute in one kernel invocation."""
    c = x_win.shape[1]
    pad_c = (-c) % block_c
    xp = jnp.pad(x_win, ((0, 0), (0, pad_c), (0, 0), (0, 0)))
    wp = jnp.pad(w, ((0, pad_c), (0, 0), (0, 0)))
    sp = jnp.pad(scale, (0, pad_c))
    bp = jnp.pad(bias, (0, pad_c))
    out = dwconv3x3_bands(xp, wp, sp, bp, stride=stride,
                          activation=activation, out_scale=out_scale,
                          block_c=block_c, interpret=interpret)
    return out[:, :c]


def dwconv_ref(x_q, w, scale, bias, *, stride: int = 1, activation=None,
               out_scale=None):
    xp = jnp.pad(x_q, ((0, 0), (1, 1), (1, 1)))
    return dwconv3x3_ref(xp, w, scale, bias, stride=stride,
                         activation=activation, out_scale=out_scale)
