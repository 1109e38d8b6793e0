"""Backend detection for the Pallas kernels.

The kernels target TPU; everywhere else they must run in Pallas interpret
mode (the kernel body traced as plain jax ops) so CPU CI and laptops still
work.  Callers pass ``interpret=None`` ("auto") and it resolves here from the
platform alone; an explicit ``interpret=True`` is for tests on a TPU-less
host.
"""
from __future__ import annotations

import jax


def resolve_interpret(interpret: bool | None) -> bool:
    """Resolve an ``interpret`` kwarg: None means interpret everywhere but
    on a TPU."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)
