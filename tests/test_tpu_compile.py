"""Ahead-of-time compiles of the main-path kernels for a described TPU v5e.

Interpret-mode tests run the kernel bodies as plain jax ops, so they cannot
see what the TPU's kernel compiler (Mosaic) refuses: block shapes that do
not tile, in-kernel reshapes, strided slices of loaded values.  The TPU
compiler is installed even where no chip is attached, and compiles for a
chip that is only described.  These tests compile the int8 kernels at every
MobileNetV2@112 shape the served path uses, every depthwise band stack of
the committed MobileNetV3-Large@224 plan (3x3 and 5x5), plus one whole int8
``run_batch`` program, and check that each lowers to a compiled kernel
(``tpu_custom_call``).  Nothing runs, so results are checked elsewhere.
Beside them, with no chip described, the tiling rules are checked on the
committed plans: the depthwise blocks, and the GEMM's grid steps a dispatch.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library at a time.
"""
import functools
import math
import os
import pathlib
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.api import Plan
from repro.core import CompiledSplitExecutor, quantize_model, split_model
from repro.kernels.dwconv.dwconv import (BANDS, VMEM_BUDGET, dwconv3x3,
                                         dwconv3x3_bands, dwconv_blocks,
                                         geometry, step_vmem_bytes)
from repro.kernels.qgemm.ops import qgemm_padded
from repro.models import mobilenet_v2_paper, mobilenet_v3_large

from qgemm_calls import qgemm_calls

# (channels, input rows = cols, stride) of every MobileNetV2@112 depthwise
# layer; test_depthwise_shapes_match_model keeps the list honest
MNV2_112_DWCONV = [(32, 56, 1), (96, 56, 2), (144, 28, 1), (144, 28, 2),
                   (192, 14, 1), (192, 14, 2), (384, 7, 1), (576, 7, 1),
                   (576, 7, 2), (960, 4, 1)]
# uneven worker ratings, so band heights differ within a stack
RATINGS = [4.0, 3.0, 3.0, 2.0, 2.0, 1.0, 1.0]
# the benchmark's committed plans, with the batch bucket each is served at
CONFIGS = pathlib.Path(__file__).resolve().parent.parent / "bench" / "configs"
PLANS = {"spatial": ("mnv2_112_int8_spatial.plan.json", 8),
         "neuron": ("mnv2_112_int8_neuron.plan.json", 32)}
# grid steps the tiling may take for one kernel call, and for all of a
# dispatch's calls, at each plan's bucket (it was 3,840 and 36,704 with
# 8-channel, one-sample, one-band blocks)
MAX_STEPS_PER_CALL = 32
MAX_STEPS_PER_DISPATCH = {"spatial": 100, "neuron": 500}
MNV3L_PLAN = ("mnv3l_224_int8_spatial.plan.json", 32)
# at 224x224 one band of the 112x112 maps fills a step's VMEM budget alone,
# so a call at bucket 32 takes up to 32 x 7 steps
MNV3L_MAX_STEPS = {"call": 256, "dispatch": 768}
# qgemm grid steps a dispatch with 128x128x128 blocks and the vmap batch as
# a grid axis, by committed plan and bucket; the tiling must take a tenth of
# them at buckets 8 and 32 and no more at bucket 1
QGEMM_STEPS_128 = {("mnv3l", 1): 1471, ("mnv3l", 32): 47072,
                   ("spatial", 1): 531, ("spatial", 8): 4248,
                   ("spatial", 32): 16992,
                   ("neuron", 1): 1136, ("neuron", 32): 36352}
# the pattern of the benchmark's qgemm_roofline metrics (bench/metrics)
QGEMM_EVENT = re.compile(r"^\s*%?[\w.]*jit_qgemm[\w.]* = ", re.M)


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


def planned_dwconv_calls(model, plan_file):
    """(stack, C, rows, cols, stride) of every ``dwconv3x3_bands`` call the
    executor makes for one sample of a committed plan: band stacks of the
    spatial blocks, one-window stacks of the flat per-shard layers."""
    plan = Plan.from_json(CONFIGS / plan_file, model).split
    ex = CompiledSplitExecutor(plan)
    calls = []
    for idxs in plan.block_groups:
        if plan.splits[idxs[0]].mode == "spatial":
            for st in ex._banded_block(tuple(idxs)).stages:
                layer = model.layers[st.index]
                if layer.kind == "dwconv":
                    calls.append((st.src_rows.shape[0], layer.in_shape[0],
                                  st.src_rows.shape[1],
                                  layer.in_shape[2] + 2, layer.stride[0]))
            continue
        for i in idxs:
            layer = model.layers[i]
            if layer.kind == "dwconv":
                calls.extend((1, g.c_hi - g.c_lo + 1, layer.in_shape[1] + 2,
                              layer.in_shape[2] + 2, layer.stride[0])
                             for g in ex._geometry[i] if g is not None)
    return calls


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


@pytest.mark.parametrize("plan", sorted(PLANS))
def test_dwconv3x3_bands_vmapped_compiles_on_committed_plans(one_chip, model,
                                                             plan):
    """``run_batch`` vmaps the kernel: at the plan's batch bucket the batch
    folds into the stack, on every stack of the committed plan."""
    plan_file, batch = PLANS[plan]
    for n, c, rows, cols, stride in sorted(set(
            planned_dwconv_calls(model, plan_file))):
        fn = jax.vmap(functools.partial(dwconv3x3_bands, stride=stride,
                                        activation="relu6", out_scale=0.05,
                                        interpret=False),
                      in_axes=(0, None, None, None))
        _compile(fn, *_dw_specs(one_chip, (batch, n, c, rows, cols)))


@pytest.mark.parametrize("plan", sorted(PLANS))
def test_dwconv_tiling_on_committed_plans(model, plan):
    """The blocks the tiling rule picks at every planned shape, at bucket 1
    and at the plan's bucket: few grid steps a call and a dispatch, whole
    blocks, and a VMEM estimate within the budget."""
    plan_file, bucket = PLANS[plan]
    calls = planned_dwconv_calls(model, plan_file)
    assert calls
    for batch in (1, bucket):
        total = 0
        for n, c, rows, cols, stride in calls:
            blk = dwconv_blocks(batch * n, rows, cols, c, stride)
            g = geometry(rows, cols, stride)
            assert (batch * n) % blk.stack == 0 and g.oh % blk.rows == 0
            assert blk.channels == c or blk.channels % 128 == 0
            assert step_vmem_bytes(blk.stack, blk.channels, blk.rows,
                                   g) <= VMEM_BUDGET
            steps = batch * n // blk.stack * -(-c // blk.channels)
            assert steps <= MAX_STEPS_PER_CALL
            total += steps
        assert total <= MAX_STEPS_PER_DISPATCH[plan]


@pytest.mark.parametrize("batch,m,k,n", [
    (8, 784, 96, 24),        # 28x28 project conv
    (8, 3136, 24, 144),      # 56x56 expand conv: N pads to 256
    (2, 16, 960, 320),       # 4x4 project conv
    (2, 1, 1280, 1000),      # classifier
    (32, 19712, 27, 16),     # MobileNetV3-Large's banded stem at bucket 32
    (32, 1, 1280, 1000),     # classifier at bucket 32: one 32-row GEMM
])
def test_qgemm_padded_vmapped_compiles(one_chip, batch, m, k, n):
    """``run_batch`` vmaps the plan, so the GEMM kernel compiles batched,
    the batch folded into M; its op keeps the name the benchmark's
    ``qgemm_roofline`` pattern reads."""
    fn = jax.vmap(functools.partial(qgemm_padded, activation="relu6",
                                    out_scale=0.05, interpret=False),
                  in_axes=(0, None, None, None))
    compiled = _compile(fn, _spec(one_chip, (batch, m, k), jnp.int8),
                        _spec(one_chip, (k, n), jnp.int8),
                        _spec(one_chip, (n,), jnp.float32),
                        _spec(one_chip, (n,), jnp.int32))
    assert QGEMM_EVENT.search(compiled.as_text())


@pytest.mark.parametrize("plan,batch", sorted(QGEMM_STEPS_128))
def test_qgemm_tiling_on_committed_plans(plan, batch):
    """The qgemm grid steps a dispatch of the committed plans: at most a
    tenth of the 128x128x128 blocks' at bucket 8 and 32, and no more at
    bucket 1 (steady's partial buckets)."""
    steps = sum(math.prod(c.grid) for c in qgemm_calls(plan, batch))
    limit = QGEMM_STEPS_128[plan, batch]
    assert steps <= (limit if batch == 1 else limit // 10)


def test_int8_run_batch_compiles(one_chip, model):
    """One whole int8 program, batch bucket 2: a 7-worker fused spatial
    plan, with the Pallas kernels compiled (not interpreted)."""
    qm = quantize_model(model, [0.05] * (len(model.layers) + 1))
    plan = split_model(model, RATINGS, mode="spatial")
    ex = CompiledSplitExecutor(plan, qm, use_pallas=True, interpret=False)
    x = _spec(one_chip, (2, *model.input_shape), jnp.float32)
    text = ex.lower_batch(x, "int8").compile().as_text()
    assert "tpu_custom_call" in text


def mnv3l_dwconv_stacks():
    """(stack, C, rows, cols, stride, k) of every depthwise band stack the
    executor builds for one sample of the committed MobileNetV3-Large@224
    plan (spatial, one band stack per layer)."""
    model = mobilenet_v3_large()
    plan = Plan.from_json(CONFIGS / MNV3L_PLAN[0], model).split
    ex = CompiledSplitExecutor(plan)
    stacks = []
    for idxs in plan.block_groups:
        if plan.splits[idxs[0]].mode != "spatial":
            continue
        for st in ex._banded_block(tuple(idxs)).stages:
            layer = model.layers[st.index]
            if layer.kind == "dwconv":
                k = layer.kernel[0]
                stacks.append((st.src_rows.shape[0], layer.in_shape[0],
                               st.src_rows.shape[1],
                               layer.in_shape[2] + k - 1, layer.stride[0], k))
    return stacks


def test_mnv3l_plan_bands_every_depthwise_layer():
    stacks = mnv3l_dwconv_stacks()
    assert len(stacks) == 15
    assert sum(k == 5 for *_, k in stacks) == 6
    assert {(s, k) for *_, s, k in stacks} == {(1, 3), (2, 3), (1, 5), (2, 5)}


def test_dwconv_bands_vmapped_compiles_on_mnv3l_plan(one_chip):
    """At the served bucket of 32 the batch folds into every 3x3 and 5x5
    band stack of the committed MobileNetV3-Large plan, and the tiling
    keeps the few-step, in-budget blocks of the MobileNetV2 plans."""
    batch = MNV3L_PLAN[1]
    stacks = mnv3l_dwconv_stacks()
    total = 0
    for n, c, rows, cols, stride, k in stacks:
        blk = dwconv_blocks(batch * n, rows, cols, c, stride, k)
        g = geometry(rows, cols, stride, k)
        assert step_vmem_bytes(blk.stack, blk.channels, blk.rows,
                               g) <= VMEM_BUDGET
        # a narrower channel block pads C no further than 128 lanes do
        assert -(-c // blk.channels) * blk.channels <= -(-c // 128) * 128
        steps = batch * n // blk.stack * -(-c // blk.channels)
        assert steps <= MNV3L_MAX_STEPS["call"]
        total += steps
    assert total <= MNV3L_MAX_STEPS["dispatch"]
    for n, c, rows, cols, stride, k in sorted(set(stacks)):
        fn = jax.vmap(functools.partial(BANDS[k], stride=stride,
                                        activation="hswish", out_scale=0.05,
                                        interpret=False),
                      in_axes=(0, None, None, None))
        _compile(fn, _spec(one_chip, (batch, n, c, rows, cols), jnp.int8),
                 _spec(one_chip, (c, k, k), jnp.int8),
                 _spec(one_chip, (c,), jnp.float32),
                 _spec(one_chip, (c,), jnp.int32))
