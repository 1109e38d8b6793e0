"""Wrappers for the depthwise conv kernel: pad the spatial dims by k // 2
(SAME padding for 3x3 or 5x5) or take pre-gathered band windows."""
from __future__ import annotations

import jax.numpy as jnp

from .dwconv import BANDS, SAMPLE
from .ref import dwconv_valid_ref


def _same_pad(x_q, k: int):
    p = k // 2
    return jnp.pad(x_q, ((0, 0), (p, p), (p, p)))


def dwconv(x_q, w, scale, bias, *, stride: int = 1, activation=None,
           out_scale=None, interpret: bool | None = None):
    """x_q: (C, H, W) int8 (unpadded); w: (C, k, k) with k 3 or 5; SAME
    depthwise conv.  ``interpret=None`` auto-detects the backend (see
    kernels.backend)."""
    k = w.shape[-1]
    return SAMPLE[k](_same_pad(x_q, k), w, scale, bias, stride=stride,
                     activation=activation, out_scale=out_scale,
                     interpret=interpret)


def dwconv_bands(x_win, w, scale, bias, *, stride: int = 1, activation=None,
                 out_scale=None, interpret: bool | None = None):
    """Batched-band depthwise conv over pre-gathered band windows:
    ``x_win`` is (bands, C, R, W + k - 1) with every band's halo/zero rows
    already materialized (shorter bands zero-filled to the common R).  All
    bands run in one kernel invocation (``dwconv3x3_bands`` or
    ``dwconv5x5_bands``, by the taps of ``w``)."""
    return BANDS[w.shape[-1]](x_win, w, scale, bias, stride=stride,
                              activation=activation, out_scale=out_scale,
                              interpret=interpret)


def dwconv_ref(x_q, w, scale, bias, *, stride: int = 1, activation=None,
               out_scale=None):
    return dwconv_valid_ref(_same_pad(x_q, w.shape[-1]), w, scale, bias,
                            stride=stride, activation=activation,
                            out_scale=out_scale)
