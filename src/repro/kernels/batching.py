"""The vmap rule the int8 Pallas kernels share.

``run_batch`` vmaps the whole per-sample program, and the batching rule of a
``pallas_call`` adds the batch to its grid: B grid steps of one sample's
blocks each.  A kernel whose leading input axis is already a stack (the
depthwise kernel's bands, the GEMM's rows) can take the batch into that
axis instead, and size its blocks for the whole batch.
"""
from __future__ import annotations

import jax


def stack_folding(fn):
    """``fn(x, *consts)`` with ``x``'s leading axis a stack, given a vmap
    rule that folds a batched ``x`` (B, N, ...) into one (B*N, ...) stack
    and calls ``fn`` once.  Batched ``consts`` (weights, scale, bias: never
    the executor's case) vmap ``fn`` as is."""
    folded = jax.custom_batching.custom_vmap(fn)

    @folded.def_vmap
    def _rule(axis_size, in_batched, x, *consts):
        if any(in_batched[1:]) or not in_batched[0]:
            axes = tuple(0 if b else None for b in in_batched)
            return jax.vmap(fn, in_axes=axes)(x, *consts), True
        y = folded(x.reshape(-1, *x.shape[2:]), *consts)
        return y.reshape(axis_size, -1, *y.shape[1:]), True

    return folded
