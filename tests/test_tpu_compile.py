"""Ahead-of-time compiles of the main-path kernels for a described TPU v5e.

Interpret-mode tests run the kernel bodies as plain jax ops, so they cannot
see what the TPU's kernel compiler (Mosaic) refuses: block shapes that do
not tile, in-kernel reshapes, strided slices of loaded values.  The TPU
compiler is installed even where no chip is attached, and compiles for a
chip that is only described.  These tests compile the int8 kernels at every
MobileNetV2@112 shape the served path uses, plus one whole int8
``run_batch`` program, and check that each lowers to a compiled kernel
(``tpu_custom_call``).  Nothing runs, so results are checked elsewhere.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library at a time.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import CompiledSplitExecutor, quantize_model, split_model
from repro.kernels.dwconv.dwconv import dwconv3x3, dwconv3x3_bands
from repro.kernels.qgemm.ops import qgemm_padded
from repro.models import mobilenet_v2_paper

# (channels, input rows = cols, stride) of every MobileNetV2@112 depthwise
# layer; test_depthwise_shapes_match_model keeps the list honest
MNV2_112_DWCONV = [(32, 56, 1), (96, 56, 2), (144, 28, 1), (144, 28, 2),
                   (192, 14, 1), (192, 14, 2), (384, 7, 1), (576, 7, 1),
                   (576, 7, 2), (960, 4, 1)]
# uneven worker ratings, so band heights differ within a stack
RATINGS = [4.0, 3.0, 3.0, 2.0, 2.0, 1.0, 1.0]


@pytest.fixture(scope="module")
def one_chip():
    """One device of a described v5e, with JAX's persistent compilation
    cache off: a compile for a described chip is written to the cache but
    cannot be read back without the chip."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def model():
    return mobilenet_v2_paper()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *specs):
    compiled = jax.jit(fn).lower(*specs).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _dw_specs(one_chip, x_shape):
    c = x_shape[-3]
    return (_spec(one_chip, x_shape, jnp.int8),
            _spec(one_chip, (c, 3, 3), jnp.int8),
            _spec(one_chip, (c,), jnp.float32),
            _spec(one_chip, (c,), jnp.int32))


def test_depthwise_shapes_match_model(model):
    got = sorted({(lyr.in_shape[0], lyr.in_shape[1], lyr.stride[0])
                  for lyr in model.layers if lyr.kind == "dwconv"})
    assert got == MNV2_112_DWCONV


@pytest.mark.parametrize("c,hw,stride", MNV2_112_DWCONV)
def test_dwconv3x3_compiles(one_chip, c, hw, stride):
    fn = functools.partial(dwconv3x3, stride=stride, activation="relu6",
                           out_scale=0.05, interpret=False)
    _compile(fn, *_dw_specs(one_chip, (c, hw + 2, hw + 2)))


@pytest.mark.parametrize("fused", [True, False], ids=["block", "layer"])
def test_dwconv3x3_bands_compiles_on_planned_stacks(one_chip, model, fused):
    """Every depthwise band stack the executor builds for a 7-worker
    spatial plan of MobileNetV2@112 (fused blocks, or one band per layer
    as the planner picks on the demo cluster)."""
    plan = split_model(model, RATINGS, mode="spatial", fused=fused)
    ex = CompiledSplitExecutor(plan)
    stacks = set()
    for idxs in plan.block_groups:
        if plan.splits[idxs[0]].mode != "spatial":
            continue            # the pool and classifier stay flat
        for st in ex._banded_block(tuple(idxs)).stages:
            layer = model.layers[st.index]
            if layer.kind == "dwconv":
                stacks.add((st.src_rows.shape[0], layer.in_shape[0],
                            st.src_rows.shape[1], layer.in_shape[2] + 2,
                            layer.stride[0]))
    assert {s[-1] for s in stacks} == {1, 2}
    for bands, c, rows, cols, stride in sorted(stacks):
        fn = functools.partial(dwconv3x3_bands, stride=stride,
                               activation="relu6", out_scale=0.05,
                               interpret=False)
        _compile(fn, *_dw_specs(one_chip, (bands, c, rows, cols)))


@pytest.mark.parametrize("batch,m,k,n", [
    (8, 784, 96, 24),        # 28x28 project conv
    (8, 3136, 24, 144),      # 56x56 expand conv: N pads to 256
    (2, 16, 960, 320),       # 4x4 project conv
    (2, 1, 1280, 1000),      # classifier
])
def test_qgemm_padded_vmapped_compiles(one_chip, batch, m, k, n):
    """``run_batch`` vmaps the plan, so the GEMM kernel compiles batched."""
    fn = jax.vmap(functools.partial(qgemm_padded, activation="relu6",
                                    out_scale=0.05, interpret=False),
                  in_axes=(0, None, None, None))
    _compile(fn, _spec(one_chip, (batch, m, k), jnp.int8),
             _spec(one_chip, (k, n), jnp.int8),
             _spec(one_chip, (n,), jnp.float32),
             _spec(one_chip, (n,), jnp.int32))


def test_int8_run_batch_compiles(one_chip, model):
    """One whole int8 program, batch bucket 2: a 7-worker fused spatial
    plan, with the Pallas kernels compiled (not interpreted)."""
    qm = quantize_model(model, [0.05] * (len(model.layers) + 1))
    plan = split_model(model, RATINGS, mode="spatial")
    ex = CompiledSplitExecutor(plan, qm, use_pallas=True, interpret=False)
    x = _spec(one_chip, (2, *model.input_shape), jnp.float32)
    text = ex.lower_batch(x, "int8").compile().as_text()
    assert "tpu_custom_call" in text
