"""MobileNetV3-Large on the normal path: the channel gate in the IR, the
hard-swish / hard-sigmoid epilogues, the 5x5 depthwise kernel, the split and
cost of a squeeze-excite branch, and bit-exact int8 through Planner ->
Session in every plan mode (CPU, Pallas in interpret mode)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import Cluster, Objective, Planner
from repro.core import (CompiledSplitExecutor, SimConfig, SplitExecutor,
                        comm_volume, plan_memory, quantize_model,
                        reference_forward, simulate, split_model)
from repro.core.executor import _plan_fingerprint, se_paths
from repro.core.fusion import apply_activation, group_blocks
from repro.core.quantize import requantize
from repro.core.reinterpret import LayerSpec, layer_macs, trace_sequential
from repro.kernels.dwconv.dwconv import dwconv3x3_bands, dwconv5x5_bands
from repro.kernels.dwconv.ops import dwconv, dwconv_ref
from repro.kernels.dwconv.ref import dwconv_valid_ref
from repro.kernels.qgemm.qgemm import qgemm
from repro.models import mobilenet_v3_large, mobilenet_v3_large_smoke
from repro.runtime.shards import build_coordinator_plan, build_worker_setup

OBJECTIVE = dict(minimize="latency", ram_cap_bytes=512 * 1024)


@pytest.fixture(scope="module")
def smoke():
    return mobilenet_v3_large_smoke()


@pytest.fixture(scope="module")
def xs(smoke):
    rng = np.random.default_rng(7)
    return rng.standard_normal((3, *smoke.input_shape)).astype(np.float32)


# -- the model ----------------------------------------------------------------

def test_large_is_table_1():
    m = mobilenet_v3_large()
    assert m.input_shape == (3, 224, 224) and m.out_shape == (1000, 1, 1)
    gates = [lyr for lyr in m.layers if lyr.kind == "gate"]
    reduce = [lyr.out_shape[0] for lyr in m.layers
              if lyr.name.endswith("_se_reduce")]
    assert len(gates) == 8 and reduce == [24, 32, 32, 120, 168, 168, 240, 240]
    dw5 = [(lyr.in_shape[0], lyr.in_shape[1], lyr.stride[0])
           for lyr in m.layers if lyr.kind == "dwconv" and lyr.kernel == (5, 5)]
    assert dw5 == [(72, 56, 2), (120, 28, 1), (120, 28, 1), (672, 14, 2),
                   (960, 7, 1), (960, 7, 1)]
    # stem, expand and depthwise of blocks 7-15, head conv and head FC
    assert sum(lyr.activation == "hswish" for lyr in m.layers) == 21
    assert max(lyr.n_out for lyr in m.layers) == 64 * 112 * 112


def test_smoke_keeps_every_block_kind(smoke):
    dw = {lyr.name: lyr for lyr in smoke.layers if lyr.kind == "dwconv"}
    gated = {lyr.gate_of for lyr in smoke.layers if lyr.kind == "gate"}
    assert any(lyr.kernel == (5, 5) and lyr.stride == (2, 2)
               and lyr.save_as in gated for lyr in dw.values())
    # a gated block whose project adds the block input back
    assert any(lyr.residual_from and smoke.layers[i - 1].kind == "gate"
               for i, lyr in enumerate(smoke.layers))
    acts = {lyr.activation for lyr in smoke.layers}
    assert {"relu", "hswish", "hsigmoid"} <= acts


# -- the IR -------------------------------------------------------------------

def test_gate_shape_rules_and_macs():
    base = [dict(kind="conv", out_channels=4, kernel=(3, 3), save_as="x"),
            dict(kind="avgpool"), dict(kind="linear", features=4)]
    m = trace_sequential(base + [dict(kind="gate", gate_of="x")], (3, 6, 6))
    gate = m.layers[-1]
    assert (gate.in_shape, gate.out_shape) == ((4, 1, 1), (4, 6, 6))
    assert layer_macs(gate) == 4 * 6 * 6
    with pytest.raises(ValueError, match="names no earlier save_as"):
        trace_sequential(base + [dict(kind="gate", gate_of="y")], (3, 6, 6))
    bad = base[:-1] + [dict(kind="linear", features=5),
                       dict(kind="gate", gate_of="x")]
    with pytest.raises(ValueError, match="one factor per channel"):
        trace_sequential(bad, (3, 6, 6))
    with pytest.raises(ValueError, match="no MAC count"):
        layer_macs(LayerSpec("odd", "shuffle", (4, 6, 6), (4, 6, 6)))


def test_fingerprint_includes_gate_of():
    def model(key):
        ops = [dict(kind="conv", out_channels=4, kernel=(1, 1), save_as="a"),
               dict(kind="conv", out_channels=4, kernel=(1, 1), save_as="b"),
               dict(kind="avgpool"), dict(kind="linear", features=4),
               dict(kind="gate", gate_of=key)]
        return trace_sequential(ops, (3, 5, 5), np.random.default_rng(0))
    fps = {_plan_fingerprint(split_model(model(k), [1, 1]), None)
           for k in "ab"}
    assert len(fps) == 2


def test_se_branch_is_coordinator_side(smoke):
    """The depthwise layer a gate scales ends its own block, pool, FCs and
    gate never fuse, and in a spatial plan the gate is split like the pool:
    no worker holds a position of it, nothing of it is held in a worker's
    RAM, and the assembly of the stashed depthwise output is paid at the
    pool's boundary."""
    for b in group_blocks(smoke):
        kinds = [smoke.layers[i].kind for i in b.indices]
        assert len(b) == 1 or set(kinds) <= {"conv", "dwconv"}
        assert all(smoke.layers[i].save_as is None for i in b.indices[:-1])
    plan = split_model(smoke, [3, 2, 2, 1], mode="spatial", fused=True)
    mem = plan_memory(plan)
    for i, sp in enumerate(plan.splits):
        lyr = sp.layer
        if lyr.kind == "gate":
            assert plan.block_groups.count((i,)) == 1
            assert all(s.n_positions == 0 for s in sp.shards)
            assert not mem[i].per_worker_peak.any()
            # the project after it is banded again
            assert plan.splits[i + 1].mode == "spatial"
        if lyr.kind == "avgpool" and smoke.layers[i - 1].save_as:
            vol = comm_volume(plan.splits[i - 1], lyr, sp)
            assert vol.upload_bytes.sum() == smoke.layers[i - 1].n_out
    assert se_paths(smoke) == {i for i, lyr in enumerate(smoke.layers)
                               if "_se_" in lyr.name}


def test_pipelined_project_waits_for_the_gate(smoke):
    """Under the pipelined transport a gated block's project conv cannot
    start its download before the SE branch's last FC has uploaded: the
    coordinator-side pool and gate carry the barrier."""
    workers = list(Cluster.heterogeneous_demo(4).workers)
    plan = split_model(smoke, np.ones(4), mode="spatial")
    res = simulate(smoke, workers, np.ones(4), SimConfig(transport="pipelined"),
                   plan=plan)
    segs = [g[0] for g in plan.block_groups]
    ev = res.timeline.events
    for i, lyr in enumerate(smoke.layers):
        if lyr.kind != "gate":
            continue
        fc_up = max(e.end_s for e in ev
                    if e.kind == "upload" and e.segment == segs.index(i - 1))
        proj = [e for e in ev if e.kind == "download"
                and e.segment == segs.index(i + 1)]
        assert proj and min(e.start_s for e in proj) >= fc_up


def test_runtime_refuses_the_gate(smoke):
    plan = split_model(smoke, [1, 1], mode="neuron")
    with pytest.raises(ValueError, match="'gate'"):
        build_worker_setup(plan, None, "float", 0)
    with pytest.raises(ValueError, match="'gate'"):
        build_coordinator_plan(plan, None, "float")
    mnv2 = trace_sequential([dict(kind="conv", out_channels=4,
                                  activation="gelu")], (3, 4, 4))
    with pytest.raises(ValueError, match="'gelu'"):
        build_worker_setup(split_model(mnv2, [1]), None, "float", 0)


# -- activations: one rounding everywhere ----------------------------------------

def _tie_grid():
    """A dense sweep of int32 accumulators ``base[i] + offset[j]`` (base
    -64..63 in int8, one int32 offset a column) with per-column scales.
    With power-of-two scales the dequantized values hit the hard-sigmoid's
    breakpoints -3, 0 and 3 exactly, and with a power-of-two output scale
    many requantized values are exact halves: ties of the final round."""
    rng = np.random.default_rng(3)
    base = np.arange(-64, 64).astype(np.int8)
    scale = np.concatenate([2.0 ** -np.arange(2, 10).repeat(2),
                            rng.uniform(1e-3, 0.2, 112)]).astype(np.float32)
    offset = (3 / scale).astype(np.int32)
    offset[1::2] -= (6 / scale[1::2]).astype(np.int32)
    return base, offset, scale


@pytest.mark.parametrize("act", ["hswish", "hsigmoid"])
def test_hard_activations_round_alike(act):
    """Eager, jitted, both Pallas epilogues and plain numpy give the same
    int8 on the sweep, ties included."""
    base, offset, scale = _tie_grid()
    out_scale = 0.25
    acc = base[:, None].astype(np.int32) + offset[None, :]      # (128, 128)
    y = acc.astype(np.float32) * scale
    assert {-3.0, 0.0, 3.0} <= set(np.unique(y).tolist())
    t = apply_activation(y, act) * np.float32(1 / out_scale)
    assert t.dtype == np.float32 and (t % 1 == 0.5).sum() > 10
    want = np.clip(np.round(t), -127, 127).astype(np.int8)
    eager = np.asarray(requantize(jnp.asarray(acc), jnp.asarray(scale),
                                  out_scale, act))
    jitted = np.asarray(jax.jit(functools.partial(
        requantize, out_scale=out_scale, activation=act))(
            jnp.asarray(acc), jnp.asarray(scale)))
    np.testing.assert_array_equal(eager, want)
    np.testing.assert_array_equal(jitted, want)
    # qgemm: identity weights pass x through, the column's offset is b_q
    x = np.tile(base[:, None], (1, 128))
    got = qgemm(x, np.eye(128, dtype=np.int8), scale, offset,
                activation=act, out_scale=out_scale, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), want)
    # dwconv 3x3 and 5x5: a centre tap of 1 passes each channel's row of
    # the window through; channels are the columns of the sweep
    for k, fn in ((3, dwconv3x3_bands), (5, dwconv5x5_bands)):
        xw = np.zeros((1, 128, k, 128 + k - 1), np.int8)
        xw[0, :, k // 2, k // 2:k // 2 + 128] = base[None, :]
        w = np.zeros((128, k, k), np.int8)
        w[:, k // 2, k // 2] = 1
        got = fn(xw, w, scale, offset, activation=act, out_scale=out_scale,
                 interpret=True)
        np.testing.assert_array_equal(np.asarray(got)[0, :, 0, :], want.T)


# -- the 5x5 kernel -----------------------------------------------------------

@pytest.mark.parametrize("stride", [1, 2])
@pytest.mark.parametrize("act", ["relu", "hswish"])
def test_dwconv5x5_bands_matches_oracle(stride, act):
    rng = np.random.default_rng(stride)
    bands, c, out_rows, cols = 3, 40, 4, 13       # odd width
    rows = (out_rows - 1) * stride + 5            # band + halo of 2 each side
    x = rng.integers(-127, 128, (2, bands, c, rows, cols + 4)).astype(np.int8)
    w = rng.integers(-127, 128, (c, 5, 5)).astype(np.int8)
    s = rng.uniform(1e-5, 1e-4, c).astype(np.float32)
    b = rng.integers(-5000, 5000, c).astype(np.int32)
    fn = jax.vmap(functools.partial(dwconv5x5_bands, stride=stride,
                                    activation=act, out_scale=0.05,
                                    interpret=True),
                  in_axes=(0, None, None, None))
    got = np.asarray(fn(x, w, s, b))
    assert got.shape == (2, bands, c, out_rows, (cols - 1) // stride + 1)
    for i in range(2):
        for j in range(bands):
            exp = dwconv_valid_ref(x[i, j], w, s, b, stride=stride,
                                   activation=act, out_scale=0.05)
            np.testing.assert_array_equal(got[i, j], np.asarray(exp))
    # the SAME-padded one-sample path pads by 2
    xs = x[0, 0, :, 2:-2, 2:-2]
    np.testing.assert_array_equal(
        np.asarray(dwconv(xs, w, s, b, stride=stride, activation=act,
                          out_scale=0.05, interpret=True)),
        np.asarray(dwconv_ref(xs, w, s, b, stride=stride, activation=act,
                              out_scale=0.05)))


def test_dwconv5x5_events_carry_their_own_name():
    x = jnp.zeros((2, 8, 9, 12), jnp.int8)
    w = jnp.zeros((8, 5, 5), jnp.int8)
    text = jax.jit(functools.partial(dwconv5x5_bands, out_scale=0.05,
                                     interpret=True)).lower(
        x, w, jnp.ones(8), jnp.zeros(8, jnp.int32)).as_text()
    assert "dwconv5x5_bands" in text and "dwconv3x3_bands" not in text


# -- the normal path: Planner -> Session, int8 bit-exact ------------------------

@pytest.mark.parametrize("mode", ["spatial", "neuron", "kernel", "mixed"])
def test_int8_bit_exact_through_planner_and_session(smoke, xs, mode):
    plan = Planner(smoke, Cluster.heterogeneous_demo(8)).plan(
        Objective(modes=(mode,), **OBJECTIVE))
    assert plan.mode == mode
    if mode == "mixed":
        assert {"spatial"} < set(plan.split.group_modes)
    pallas = plan.compile("int8", use_pallas=True, interpret=True)
    jnp_path = plan.compile("int8", qmodel=pallas.qmodel, use_pallas=False)
    got = pallas.submit_many(xs)
    np.testing.assert_array_equal(got, jnp_path.submit_many(xs))
    eager = SplitExecutor(plan.split, pallas.qmodel).run(xs[0], "int8")
    np.testing.assert_array_equal(got[0], eager)
    assert np.abs(got.astype(np.int32)).max() > 20     # not a dead network


def test_float_matches_reference_forward(smoke, xs):
    plan = split_model(smoke, [3, 2, 1], mode="spatial")
    got = CompiledSplitExecutor(plan).run_batch(xs, "float")
    for x, g in zip(xs, got):
        ref = reference_forward(smoke, x)
        # the split sums bands and shards in another order: float32 noise
        assert np.max(np.abs(g - ref)) <= 1e-5 * np.max(np.abs(ref))


def test_quantized_gate_reads_its_stash_scale(smoke, xs):
    """A gate's int8 factor is s_stash * s_gate / s_out: the compiled and
    eager executors read the same stash scale."""
    scales = np.linspace(0.01, 0.05, len(smoke.layers) + 1)
    qm = quantize_model(smoke, list(scales))
    plan = split_model(smoke, [1, 1], mode="kernel")
    a = CompiledSplitExecutor(plan, qm, use_pallas=False).run(xs[1], "int8")
    b = SplitExecutor(plan, qm).run(xs[1], "int8")
    np.testing.assert_array_equal(a, b)
