"""Pallas TPU kernel: int8 x int8 -> int32 GEMM with fused dequant epilogue.

This is the paper's §V.D stack (int8 W8A8 quantization + conv/BN/ReLU fusion)
as a single MXU kernel: the BN scale/bias are folded into the per-output-
channel dequant scale and bias, and the activation + requantization happen in
VMEM before the tile is written back — no intermediate HBM round-trips.

TPU adaptation (DESIGN.md §2): the MCU runtime fuses at the operator level;
on TPU the win is few grid steps over large tiles.  :func:`qgemm_blocks`
sizes the blocks from the GEMM's shape: all of K and N in one block where
they fit (so there is no K loop and no accumulator revisited across steps),
and the M block as large as :data:`VMEM_BUDGET` allows.  Inside a step the
kernel walks the M block in chunks of rows, so the code Mosaic emits stays
the size of one chunk whatever the block holds.  Under ``jax.vmap`` (the
executor's ``run_batch``) the batch folds into M, so the blocks are sized
for the whole batch.  Conv layers reach this kernel in im2col form (ops.py).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..backend import resolve_interpret
from ..batching import stack_folding
from ..epilogue import epilogue

# Estimated VMEM of one grid step (double-buffered blocks plus one row
# chunk's temporaries), kept well under v5e's 16 MiB scoped VMEM, as the
# depthwise kernel keeps it.
VMEM_BUDGET = 6 * 2**20
# the widest N block: up to it N is one block
MAX_BLOCK_N = 1280
# int8 rows tile sublanes in 32s: M blocks and row chunks are multiples
SUBLANES = 32
# int32 elements of one row chunk's accumulator (64 vregs), but never fewer
# rows than one 128-row MXU pass
CHUNK_ELEMS = 64 * 8 * 128
MIN_CHUNK_ROWS = 128


def _cdiv(a: int, b: int) -> int:
    return -(-a // b)


def _round_up(a: int, b: int) -> int:
    return _cdiv(a, b) * b


def _tile_bytes(rows: int, lanes: int, itemsize: int) -> int:
    """VMEM bytes of a (rows, lanes) array: lanes pad to 128 and rows to
    the dtype's sublane tile (32 rows for int8, 8 for 32-bit)."""
    return (_round_up(rows, 32 // itemsize) * _round_up(lanes, 128)
            * itemsize)


class Blocks(NamedTuple):
    m: int
    n: int
    k: int


def chunk_rows(block_m: int, block_n: int) -> int:
    """Rows of the M block the kernel computes at once: about
    :data:`CHUNK_ELEMS` int32 accumulator elements, at least one MXU pass,
    a multiple of :data:`SUBLANES` (a block that is not one is one chunk)."""
    if block_m % SUBLANES:
        return block_m
    rows = max(MIN_CHUNK_ROWS, CHUNK_ELEMS // block_n // SUBLANES * SUBLANES)
    return min(block_m, rows)


def step_vmem_bytes(block_m: int, block_k: int, block_n: int,
                    n_k: int) -> int:
    """Estimated VMEM of one grid step: the x, w and output blocks (the
    output as float32, the wider of its dtypes) and the (1, N) scale and
    bias rows, each double-buffered; the int32 accumulator block where K
    takes more than one step; and one row chunk's int32 temporaries (the
    product and the epilogue's intermediates)."""
    blocks = (_tile_bytes(block_m, block_k, 1) + _tile_bytes(block_k, block_n, 1)
              + _tile_bytes(block_m, block_n, 4) + 2 * _tile_bytes(1, block_n, 4))
    acc = _tile_bytes(block_m, block_n, 4) if n_k > 1 else 0
    temps = 4 * _tile_bytes(chunk_rows(block_m, block_n), block_n, 4)
    return 2 * blocks + acc + temps


def qgemm_blocks(m: int, k: int, n: int) -> Blocks:
    """The blocks for an (m, k) x (k, n) GEMM whose K and N pad to 128.

    N is one block up to :data:`MAX_BLOCK_N`, else the widest multiple of
    128 that divides the padded N and is no wider.  K is one block if a
    step of one MXU pass of rows fits :data:`VMEM_BUDGET` with it, else the
    widest multiple of 128 dividing the padded K that does.  M takes the
    fewest blocks of a multiple of :data:`SUBLANES` rows whose step fits the
    budget, and of those block sizes the smallest, which pads M least.  A
    shape no block fits gets the smallest blocks, and Mosaic reports what
    does not fit."""
    kp, np_ = _round_up(k, 128), _round_up(n, 128)
    bn = max(b for b in range(128, min(np_, MAX_BLOCK_N) + 1, 128)
             if np_ % b == 0)
    m_lo = min(_round_up(m, SUBLANES), MIN_CHUNK_ROWS)
    bk = next((b for b in range(kp, 0, -128) if kp % b == 0
               and step_vmem_bytes(m_lo, b, bn, kp // b) <= VMEM_BUDGET), 128)

    def fits(bm):
        return step_vmem_bytes(bm, bk, bn, kp // bk) <= VMEM_BUDGET

    # the largest fitting M block, by bisection over multiples of SUBLANES
    lo, hi = 1, _cdiv(m, SUBLANES)
    while lo < hi:
        mid = (lo + hi + 1) // 2
        lo, hi = (mid, hi) if fits(mid * SUBLANES) else (lo, mid - 1)
    n_m = _cdiv(m, lo * SUBLANES)
    return Blocks(_round_up(_cdiv(m, n_m), SUBLANES), bn, bk)


def _qgemm_kernel(x_ref, w_ref, scale_ref, bias_ref, o_ref, *acc_ref,
                  n_k: int, rows: int, activation: str | None,
                  out_scale: float | None, int_bias: bool):
    k_step = pl.program_id(2)

    def chunk(r0, size):
        r = pl.ds(r0, size)
        # int8 tiles -> int32 MXU accumulation
        part = jax.lax.dot_general(
            x_ref[r, :], w_ref[...],
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)

        def finish(acc):
            o_ref[r, :] = epilogue(acc, scale_ref, bias_ref,
                                   activation=activation, out_scale=out_scale,
                                   int_bias=int_bias, out_dtype=o_ref.dtype)

        if n_k == 1:
            finish(part)
            return
        # K split over the grid's last axis: the chunk's int32 partial sums
        # stay in the accumulator block across the K steps
        acc = acc_ref[0]

        @pl.when(k_step == 0)
        def _init():
            acc[r, :] = jnp.zeros_like(part)

        acc[r, :] += part

        @pl.when(k_step == n_k - 1)
        def _epilogue():
            finish(acc[r, :])

    full, rest = divmod(o_ref.shape[0], rows)

    def body(t, carry):
        chunk(pl.multiple_of(t * rows, rows), rows)
        return carry

    if full == 1:
        chunk(0, rows)
    else:
        jax.lax.fori_loop(0, full, body, 0)
    if rest:
        chunk(full * rows, rest)


def _pad_to(x, m, axis):
    pad = (-x.shape[axis]) % m
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _qgemm_call(x_q, w_q, scale, bias, *, n: int, blocks: Blocks | None,
                activation: str | None, out_scale: float | None,
                interpret: bool):
    """The kernel on one (M, K) operand, K and N padded to ``blocks``
    (:func:`qgemm_blocks` of the shape where None), the first ``n`` output
    columns returned.  M is not padded: the last M block may overhang the
    rows, and its rows past M, read as whatever the buffer holds, are
    computed and never written (each output row depends on its own input
    row alone)."""
    m, k = x_q.shape
    bm, bn, bk = blocks or qgemm_blocks(m, k, n)
    xp = _pad_to(x_q, bk, 1)
    wp = _pad_to(_pad_to(w_q, bk, 0), bn, 1)
    sp, bp = _pad_to(scale, bn, 0), _pad_to(bias, bn, 0)
    kp, np_ = xp.shape[1], wp.shape[1]
    n_k = kp // bk
    out_dtype = jnp.int8 if out_scale is not None else jnp.float32
    int_bias = jnp.issubdtype(bias.dtype, jnp.integer)
    kernel = functools.partial(_qgemm_kernel, n_k=n_k, rows=chunk_rows(bm, bn),
                               activation=activation, out_scale=out_scale,
                               int_bias=int_bias)
    scratch = [pltpu.VMEM((bm, bn), jnp.int32)] if n_k > 1 else []
    out = pl.pallas_call(
        kernel,
        grid=(_cdiv(m, bm), np_ // bn, n_k),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            # per-channel rows: a rank-1 block must match XLA's tiling of
            # the whole vector, which Mosaic refuses once N > block_n
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, np_), out_dtype),
        scratch_shapes=scratch,
        interpret=interpret,
    )(xp, wp, sp.reshape(1, np_), bp.reshape(1, np_))
    return out[:, :n]


@functools.partial(jax.jit, static_argnames=("activation", "out_scale",
                                             "block_m", "block_n", "block_k",
                                             "interpret"))
def qgemm(x_q, w_q, scale, bias, *, activation: str | None = None,
          out_scale: float | None = None, block_m: int | None = None,
          block_n: int | None = None, block_k: int | None = None,
          interpret: bool | None = None):
    """x_q: (M, K) int8; w_q: (K, N) int8; scale: (N,) f32.

    ``bias``: (N,) float32 (BN-folded real-domain bias, added in the f32
    epilogue) **or** int32 (the quantized ``b_q`` at accumulator scale,
    added in exact int32 before dequant — the bit-exact path the split
    executors use).

    Returns (M, N): int8 (requantized at ``out_scale``) or f32.  Any shape:
    K and N are zero-padded to the blocks and N sliced back; the last M
    block may overhang M.  The blocks are :func:`qgemm_blocks` of the shape
    unless all three are given.  Under ``jax.vmap`` (the executor's
    ``run_batch``) a vmap rule folds the batch into M, so B (M, K)
    operands are one (B*M, K) GEMM
    whose blocks are sized for all B*M rows, not a grid axis of B samples.
    The fold sits inside this jitted function, so the kernel's events keep
    its name (``vmap_jit_qgemm``).  ``interpret=None`` auto-detects the
    backend: the compiled kernel on TPU, interpret mode (kernel body as
    plain jax ops) everywhere else.
    """
    blocks = (None if None in (block_m, block_n, block_k)
              else Blocks(block_m, block_n, block_k))
    fn = functools.partial(_qgemm_call, n=w_q.shape[1], blocks=blocks,
                           activation=activation, out_scale=out_scale,
                           interpret=resolve_interpret(interpret))
    # K and N pad to 128 lanes before the fold: folding the rows of an
    # array whose minor dim is narrower than 128 lanes made the TPU
    # compiler take 20x longer on the stem's stage
    lanes = (_pad_to(x_q, 128, 1), _pad_to(_pad_to(w_q, 128, 0), 128, 1),
             _pad_to(scale, 128, 0), _pad_to(jnp.asarray(bias), 128, 0))
    return stack_folding(fn)(*lanes)
