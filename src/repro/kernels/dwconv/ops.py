"""Wrappers for the depthwise conv kernel: pad the spatial dims by 1 (SAME
padding for 3x3) or take pre-gathered band windows."""
from __future__ import annotations

import jax.numpy as jnp

from .dwconv import dwconv3x3, dwconv3x3_bands
from .ref import dwconv3x3_ref


def dwconv(x_q, w, scale, bias, *, stride: int = 1, activation=None,
           out_scale=None, interpret: bool | None = None):
    """x_q: (C, H, W) int8 (unpadded); SAME 3x3 depthwise conv.
    ``interpret=None`` auto-detects the backend (see kernels.backend)."""
    xp = jnp.pad(x_q, ((0, 0), (1, 1), (1, 1)))
    return dwconv3x3(xp, w, scale, bias, stride=stride, activation=activation,
                     out_scale=out_scale, interpret=interpret)


def dwconv_bands(x_win, w, scale, bias, *, stride: int = 1, activation=None,
                 out_scale=None, interpret: bool | None = None):
    """Batched-band 3x3 depthwise conv over pre-gathered band windows:
    ``x_win`` is (bands, C, R, W+2) with every band's halo/zero rows already
    materialized (shorter bands zero-filled to the common R).  All bands run
    in one kernel invocation (:func:`dwconv3x3_bands`)."""
    return dwconv3x3_bands(x_win, w, scale, bias, stride=stride,
                           activation=activation, out_scale=out_scale,
                           interpret=interpret)


def dwconv_ref(x_q, w, scale, bias, *, stride: int = 1, activation=None,
               out_scale=None):
    xp = jnp.pad(x_q, ((0, 0), (1, 1), (1, 1)))
    return dwconv3x3_ref(xp, w, scale, bias, stride=stride,
                         activation=activation, out_scale=out_scale)
