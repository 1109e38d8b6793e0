"""Pallas TPU kernel: int8 x int8 -> int32 GEMM with fused dequant epilogue.

This is the paper's §V.D stack (int8 W8A8 quantization + conv/BN/ReLU fusion)
as a single MXU kernel: the BN scale/bias are folded into the per-output-
channel dequant scale and bias, and the activation + requantization happen in
VMEM before the tile is written back — no intermediate HBM round-trips.

TPU adaptation (DESIGN.md §2): the MCU runtime fuses at the operator level;
on TPU the win is keeping the int32 accumulator tile resident in VMEM across
the K loop (grid-innermost), with (bm, bn) output tiles aligned to the
128x128 MXU.  Conv layers reach this kernel in im2col form (ops.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..backend import resolve_interpret
from ..epilogue import epilogue


def _qgemm_kernel(x_ref, w_ref, scale_ref, bias_ref, o_ref, acc_ref,
                  *, n_k: int, activation: str | None, out_scale: float | None,
                  int_bias: bool):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # int8 tiles -> int32 MXU accumulation
    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], w_ref[...],
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)

    @pl.when(pl.program_id(2) == n_k - 1)
    def _epilogue():
        o_ref[...] = epilogue(acc_ref[...], scale_ref, bias_ref,
                              activation=activation, out_scale=out_scale,
                              int_bias=int_bias, out_dtype=o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("activation", "out_scale",
                                             "block_m", "block_n", "block_k",
                                             "interpret"))
def qgemm(x_q, w_q, scale, bias, *, activation: str | None = None,
          out_scale: float | None = None, block_m: int = 128,
          block_n: int = 128, block_k: int = 128,
          interpret: bool | None = None):
    """x_q: (M, K) int8; w_q: (K, N) int8; scale: (N,) f32.

    ``bias``: (N,) float32 (BN-folded real-domain bias, added in the f32
    epilogue) **or** int32 (the quantized ``b_q`` at accumulator scale,
    added in exact int32 before dequant — the bit-exact path the split
    executors use).

    Returns (M, N): int8 (requantized at ``out_scale``) or f32.
    Shapes must be multiples of the block sizes (ops.py pads).
    ``interpret=None`` auto-detects the backend: the compiled kernel on TPU,
    interpret mode (kernel body as plain jax ops) everywhere else.
    """
    interpret = resolve_interpret(interpret)
    m, k = x_q.shape
    k2, n = w_q.shape
    assert k == k2 and m % block_m == 0 and n % block_n == 0 and k % block_k == 0
    n_k = k // block_k
    out_dtype = jnp.int8 if out_scale is not None else jnp.float32
    bias = jnp.asarray(bias)
    int_bias = jnp.issubdtype(bias.dtype, jnp.integer)
    kernel = functools.partial(_qgemm_kernel, n_k=n_k, activation=activation,
                               out_scale=out_scale, int_bias=int_bias)
    return pl.pallas_call(
        kernel,
        grid=(m // block_m, n // block_n, n_k),
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((block_k, block_n), lambda i, j, kk: (kk, j)),
            # per-channel rows: a rank-1 block must match XLA's tiling of
            # the whole vector, which Mosaic refuses once N > block_n
            pl.BlockSpec((1, block_n), lambda i, j, kk: (0, j)),
            pl.BlockSpec((1, block_n), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.int32)],
        interpret=interpret,
    )(x_q, w_q, scale.reshape(1, n), bias.reshape(1, n))
