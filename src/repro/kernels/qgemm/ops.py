"""Public wrappers around the qgemm Pallas kernel: the GEMM on arbitrary
shapes, plus the im2col path that lowers the paper's quantized conv +
folded-BN + ReLU6 onto the GEMM kernel."""
from __future__ import annotations

import jax.numpy as jnp

from .qgemm import qgemm
from .ref import qgemm_ref


def qgemm_padded(x_q, w_q, scale, bias, *, activation=None, out_scale=None,
                 interpret=None):
    """qgemm on arbitrary shapes, with the blocks the kernel's tiling rule
    (:func:`.qgemm.qgemm_blocks`) picks for the shape; under ``jax.vmap``
    the batch folds into M."""
    return qgemm(x_q, w_q, scale, bias, activation=activation,
                 out_scale=out_scale, interpret=interpret)


def im2col(x_q, kernel_hw, stride, padding):
    """x_q: (C, H, W) int8 -> (out_h*out_w, C*kh*kw) patches (CHW order,
    matching core/reinterpret's flat-index convention)."""
    c, h, w = x_q.shape
    kh, kw = kernel_hw
    sh, sw = stride
    ph, pw = padding
    xp = jnp.pad(x_q, ((0, 0), (ph, ph), (pw, pw)))
    oh = (h + 2 * ph - kh) // sh + 1
    ow = (w + 2 * pw - kw) // sw + 1
    idx_h = (jnp.arange(oh) * sh)[:, None] + jnp.arange(kh)[None, :]
    idx_w = (jnp.arange(ow) * sw)[:, None] + jnp.arange(kw)[None, :]
    patches = xp[:, idx_h[:, None, :, None], idx_w[None, :, None, :]]
    # (C, oh, ow, kh, kw) -> (oh*ow, C*kh*kw)
    patches = patches.transpose(1, 2, 0, 3, 4).reshape(oh * ow, c * kh * kw)
    return patches, (oh, ow)


def im2col_bands(x_q, kernel_hw, stride):
    """Batched-band im2col: (bands, C, R, W) pre-padded windows ->
    (bands*oh*ow, C*kh*kw) patches, band-major.  Folding the band axis into
    the GEMM M dimension makes the band index part of the qgemm grid — a
    fused spatial block's conv stage is ONE kernel call for every band, with
    the shared per-output-channel scale/bias epilogue indexed by the N-tile
    ``program_id`` exactly as in the single-sample path."""
    bsz, c, h, w = x_q.shape
    kh, kw = kernel_hw
    sh, sw = stride
    oh = (h - kh) // sh + 1
    ow = (w - kw) // sw + 1
    idx_h = (jnp.arange(oh) * sh)[:, None] + jnp.arange(kh)[None, :]
    idx_w = (jnp.arange(ow) * sw)[:, None] + jnp.arange(kw)[None, :]
    patches = x_q[:, :, idx_h[:, None, :, None], idx_w[None, :, None, :]]
    # (B, C, oh, ow, kh, kw) -> (B, oh, ow, C, kh, kw) -> (B*oh*ow, C*kh*kw)
    patches = patches.transpose(0, 2, 3, 1, 4, 5).reshape(
        bsz * oh * ow, c * kh * kw)
    return patches, (oh, ow)


def qconv2d(x_q, w_q, scale, bias, *, stride=(1, 1), padding=(0, 0),
            activation=None, out_scale=None, interpret=None):
    """Quantized conv via im2col + qgemm (paper's conv+BN+ReLU6 fused op).

    x_q: (C, H, W) int8; w_q: (Cout, Cin, kh, kw) int8;
    scale/bias: (Cout,) f32 (BN folded).  Returns (Cout, oh, ow).
    """
    cout, cin, kh, kw = w_q.shape
    patches, (oh, ow) = im2col(x_q, (kh, kw), stride, padding)
    w2 = w_q.reshape(cout, cin * kh * kw).T          # (C*kh*kw, Cout)
    y = qgemm_padded(patches, w2, scale, bias, activation=activation,
                     out_scale=out_scale, interpret=interpret)
    return y.T.reshape(cout, oh, ow)


def qconv2d_ref(x_q, w_q, scale, bias, *, stride=(1, 1), padding=(0, 0),
                activation=None, out_scale=None):
    """Oracle for qconv2d built on the qgemm oracle."""
    cout, cin, kh, kw = w_q.shape
    patches, (oh, ow) = im2col(x_q, (kh, kw), stride, padding)
    w2 = w_q.reshape(cout, cin * kh * kw).T
    y = qgemm_ref(patches, w2, scale, bias, activation=activation,
                  out_scale=out_scale)
    return y.T.reshape(cout, oh, ow)
