"""The benchmark's work arithmetic, its peak table, and its committed plans."""
from __future__ import annotations

import json

import pytest

from benchtest_util import DATA, REPO
from benchlib import spec, work

from repro.api import Cluster, Plan
from repro.core.reinterpret import layer_macs
from repro.models import mobilenet_v2_paper, mobilenet_v2_smoke

BENCH = REPO / "bench"
CONFIGS = ("mnv2_112_int8_spatial", "mnv2_112_int8_neuron")


def _cfg(name):
    path = (BENCH / "configs" / f"{name}.json")
    if not path.exists():
        path = DATA / f"{name}.json"
    return json.loads(path.read_text())


def _layers(name):
    cfg = _cfg(name)
    return spec.load_module(BENCH / "models" / f"{cfg['arch']}.py").layers(cfg)


@pytest.mark.parametrize("name, program_model", [
    ("mnv2_smoke_int8_spatial", mobilenet_v2_smoke),
    ("mnv2_112_int8_spatial", mobilenet_v2_paper)])
def test_ops_per_sample_match_layer_macs(name, program_model):
    layers = _layers(name)
    model = program_model(0)
    assert [tuple(lyr["out_shape"]) for lyr in layers] == \
        [tuple(lyr.out_shape) for lyr in model.layers]
    expected = 2 * sum(layer_macs(lyr) for lyr in model.layers)
    assert work.ops_per_sample(layers) == expected
    if name == "mnv2_112_int8_spatial":
        assert expected == 164_408_576


def test_family_work_covers_every_weighted_layer():
    layers = _layers("mnv2_112_int8_spatial")
    dw_ops, dw_act, dw_w = work.family_work(layers, "dwconv")
    qg_ops, qg_act, qg_w = work.family_work(layers, "qgemm")
    pool = 2 * sum(work.layer_macs(lyr) for lyr in layers
                   if lyr["kind"] == "avgpool")
    assert dw_ops + qg_ops + pool == work.ops_per_sample(layers)
    assert sum(lyr["kind"] == "dwconv" for lyr in layers) == 17
    assert dw_ops == 2 * 5_295_744      # 3x3 depthwise MACs of one sample
    # the int8 weights: 3.47 M parameters, less the folded-away pool
    assert dw_w + qg_w == sum(work.weight_bytes(lyr) for lyr in layers)
    assert 3_400_000 < dw_w + qg_w < 3_600_000
    assert dw_act > 0 and qg_act > 0


def test_peaks_table():
    p = work.peaks("TPU v5 lite")
    assert p["int8_ops_per_s"] == 393e12
    assert p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        work.peaks("TPU v99")


def test_least_time_names_its_bound():
    p = work.peaks("TPU v5 lite")
    t, bound = work.least_time_s(393e12, 1.0, p)
    assert (t, bound) == (1.0, "compute")
    t, bound = work.least_time_s(1.0, 819e9, p)
    assert (t, bound) == (1.0, "memory")


@pytest.mark.parametrize("name", CONFIGS)
def test_counted_work_never_exceeds_what_the_plan_computes(name):
    """Shares stay at or under 100%: per layer, the plan's shards compute
    at least the useful outputs the benchmark counts (spatial bands also
    recompute halo rows; nothing is counted twice)."""
    cfg = _cfg(name)
    model = spec.program_model(BENCH, cfg, seed=0)
    plan = Plan.from_json(BENCH / cfg["plan_file"], model)
    layers = _layers(name)
    for lyr, sp in zip(layers, plan.split.splits):
        if lyr["kind"] == "avgpool":      # coordinator-side, not sharded
            continue
        n_out = lyr["out_shape"][0] * lyr["out_shape"][1] * lyr["out_shape"][2]
        if sp.mode == "spatial":
            rows = sum(s.row_hi - s.row_lo + 1 for s in sp.shards
                       if s.row_hi >= s.row_lo)
            assert rows >= lyr["out_shape"][1]
        else:
            assert sum(s.n_positions for s in sp.shards) >= n_out


def _roofline(layers, family, samples, batches, seconds):
    """What the roofline reader reads for one kernel event of ``seconds``."""
    import types
    reader = spec.load_module(BENCH / "readers" / "kernel_roofline.py")

    class Tr:
        def ops(self):
            return ["k"]

        def matching(self, events, pattern):
            return [types.SimpleNamespace(dur_ns=seconds * 1e9)]
    rec = types.SimpleNamespace(
        trace=Tr(), session={"requests": samples, "batches": batches},
        layers=layers, work=work.SHARED, peak=work.peaks("TPU v5 lite"))
    return reader.read(rec, {"family": family, "pattern": "x"})


@pytest.mark.parametrize("name", CONFIGS)
@pytest.mark.parametrize("family", ["dwconv", "qgemm"])
@pytest.mark.parametrize("batch", [8, 32])
def test_batched_program_at_its_least_time_reads_100(name, family, batch):
    """A program that reads each weight once per batch of 8 or 32 and each
    sample's activations once, at the chip's peaks, reads 100% and no more:
    the weights are not counted once per sample."""
    layers = _layers(name)
    peak = work.peaks("TPU v5 lite")
    ops, act, weights = work.family_work(layers, family)
    batches, samples = 10, 10 * batch
    t = max(samples * ops / peak["int8_ops_per_s"],
            (batches * weights + samples * act) / peak["hbm_bytes_per_s"])
    share = _roofline(layers, family, samples, batches, t)
    assert share == pytest.approx(100.0, rel=1e-12)
    # counting the weights once per sample would have read above 100%
    per_sample = max(samples * ops / peak["int8_ops_per_s"],
                     samples * (weights + act) / peak["hbm_bytes_per_s"])
    assert per_sample > t


def test_partial_batches_count_their_executions():
    """Padding slots add no work, but every execution reads the weights."""
    layers = _layers("mnv2_112_int8_spatial")
    peak = work.peaks("TPU v5 lite")
    full = work.family_least_time_s(layers, "qgemm", 80, 10, peak)[0]
    partial = work.family_least_time_s(layers, "qgemm", 80, 20, peak)[0]
    assert partial > full
    assert work.family_least_time_s(layers, "qgemm", 80, 10, peak)[1] == \
        "memory"


@pytest.mark.parametrize("name", CONFIGS)
@pytest.mark.parametrize("seed", [0, 2**31 + 17])
def test_committed_plan_loads_for_any_weight_seed(name, seed):
    cfg = _cfg(name)
    plan = Plan.from_json(BENCH / cfg["plan_file"], mobilenet_v2_paper(seed))
    assert plan.cluster.to_dict() == Cluster.heterogeneous_demo(8).to_dict()
    assert plan.objective.minimize == "latency"
    assert plan.objective.ram_cap_bytes == 512 * 1024
    want = cfg["expect_plan"]
    assert (plan.mode, plan.fusion, plan.n_workers) == (
        want["mode"], want["fusion"], want["n_workers"])
    if "modes" in cfg["plan_objective"]:
        assert plan.objective.modes == tuple(cfg["plan_objective"]["modes"])
    bench_model = spec.program_model(BENCH, cfg, seed=seed % 1000)
    assert Plan.from_json(BENCH / cfg["plan_file"], bench_model).mode == \
        plan.mode
