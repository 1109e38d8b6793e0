"""MobileNetV3-Large in the benchmark: its configuration and cells resolve
with their metrics, its work counts match an independent tally of the
paper's Table 1, its committed plan loads for any seed, and at the CPU test
size the served program reads 0 output steps from the configuration's own
reference while the int4 control fails the limit."""
from __future__ import annotations

import json
import re
import shutil
import time

import numpy as np
import pytest

from benchtest_util import DATA, REPO, make_root
from benchlib import cell, reference, spec

BENCH = REPO / "bench"
CONFIG = "mnv3l_224_int8_spatial"
SMOKE = "mnv3l_smoke_int8_spatial"
CELL = "mnv3l-smoke-offline"
OFFLINE = {"program_device_ms", "mfu", "dwconv_roofline", "qgemm_roofline",
           "device_idle"}
NEW_CELLS = {
    "mnv3l-spatial-offline": {f"{m}.mnv3l_offline"
                              for m in OFFLINE | {"dwconv5_roofline"}},
    "mnv2-spatial-offline": {f"{m}.spatial_offline" for m in OFFLINE},
}


def _bm():
    return spec.Benchmark(REPO)


def test_new_cells_resolve_with_their_metrics():
    bm = _bm()
    for name, want in NEW_CELLS.items():
        c = bm.cell(name)
        assert c.chips == 1 and c.traffic["name"] == "closed_128_b32"
        assert {m.name for m in c.end_to_end} == {"throughput_rps", "setup_s"}
        assert {m.name for m in c.per_layer} == want
    assert bm.cell("mnv2-spatial-offline").config == \
        bm.cell("mnv2-spatial-steady").config


def test_kernel_families_and_their_patterns():
    bm = _bm()
    cfg = bm.config(CONFIG)
    layers = bm.arch(cfg).layers(cfg)
    counts = bm.work(cfg)
    five = [lyr for lyr in layers if counts.families["dwconv5"](lyr)]
    three = [lyr for lyr in layers if counts.families["dwconv"](lyr)]
    assert len(five) == 6 and {lyr["k"] for lyr in five} == {5}
    assert len(three) == 9 and {lyr["k"] for lyr in three} == {3}
    pat = {name: json.loads((BENCH / "metrics" / f"{name}.json").read_text())
           ["params"]["pattern"]
           for name in ("dwconv5_roofline.mnv3l_offline",
                        "dwconv_roofline.mnv3l_offline")}
    five_op = "%jit_dwconv5x5_bands.3 = s8[32,72,28,28]{3,2,1,0} fusion()"
    three_op = "%jit_dwconv3x3_bands.1 = s8[7,64,9,58]{3,2,1,0} fusion()"
    assert re.search(pat["dwconv5_roofline.mnv3l_offline"], five_op)
    assert not re.search(pat["dwconv5_roofline.mnv3l_offline"], three_op)
    assert re.search(pat["dwconv_roofline.mnv3l_offline"], three_op)
    assert not re.search(pat["dwconv_roofline.mnv3l_offline"], five_op)


def _table_1_macs() -> int:
    """MobileNetV3-Large at 224x224 counted from Table 1 by hand: stem,
    then per bottleneck expand, depthwise, squeeze-excite (pool, both FCs,
    gate) and project, then head conv, pool, head FC and classifier."""
    rows = [(3, 16, 16, 0, 1), (3, 64, 24, 0, 2), (3, 72, 24, 0, 1),
            (5, 72, 40, 1, 2), (5, 120, 40, 1, 1), (5, 120, 40, 1, 1),
            (3, 240, 80, 0, 2), (3, 200, 80, 0, 1), (3, 184, 80, 0, 1),
            (3, 184, 80, 0, 1), (3, 480, 112, 1, 1), (3, 672, 112, 1, 1),
            (5, 672, 160, 1, 2), (5, 960, 160, 1, 1), (5, 960, 160, 1, 1)]
    se_width = {72: 24, 120: 32, 480: 120, 672: 168, 960: 240}
    hw, c = 112, 16
    total = hw * hw * 16 * 3 * 3 * 3
    for k, exp, out, se, s in rows:
        if exp != c:
            total += hw * hw * c * exp
        hw //= s
        total += hw * hw * exp * k * k
        if se:
            r = se_width[exp]
            total += hw * hw * exp + 2 * exp * r + hw * hw * exp
        total += hw * hw * exp * out
        c = out
    return total + 7 * 7 * 160 * 960 + 7 * 7 * 960 + 960 * 1280 + 1280 * 1000


def test_mac_total_matches_table_1():
    bm = _bm()
    cfg = bm.config(CONFIG)
    layers = bm.arch(cfg).layers(cfg)
    assert bm.work(cfg).ops_per_sample(layers) == 2 * _table_1_macs()
    assert _table_1_macs() == 217_831_616
    assert sum(bm.work(cfg).weight_bytes(lyr) for lyr in layers) == 5_451_272


@pytest.mark.parametrize("seed", [0, 2**31 + 17])
def test_committed_plan_loads_for_any_weight_seed(seed):
    from repro.api import Cluster, Plan
    cfg = _bm().config(CONFIG)
    assert cfg["reduced"] == [] and cfg["input_shape"] == [3, 224, 224]
    model = spec.program_model(BENCH, cfg, seed=seed)
    plan = Plan.from_json(BENCH / cfg["plan_file"], model)
    assert plan.cluster.to_dict() == Cluster.heterogeneous_demo(8).to_dict()
    assert plan.objective.minimize == "latency"
    assert plan.objective.ram_cap_bytes == 512 * 1024
    want = cfg["expect_plan"]
    assert (plan.mode, plan.fusion, plan.n_workers) == (
        want["mode"], want["fusion"], want["n_workers"])


@pytest.fixture(scope="module")
def smoke(tmp_path_factory):
    """A root with a CPU-sized MobileNetV3 cell, and one run of it."""
    root = make_root(tmp_path_factory.mktemp("mnv3l_root"),
                     cells=[{"name": CELL, "config": SMOKE,
                             "traffic": "smoke_closed", "chips": 1,
                             "why": "CPU test cell"}])
    for name in (f"{SMOKE}.json", f"{SMOKE}.plan.json"):
        shutil.copy(DATA / name, root / "bench" / "configs" / name)
    bm = spec.Benchmark(root)
    result = cell.run(bm, CELL, 2**31 + 29, 1.0, False, time.perf_counter(),
                      check_device=False, log=lambda msg: None)
    return bm, result


def test_smoke_cell_reads_no_gap(smoke):
    _, result = smoke
    assert result["correct"] and result["failed"] == 0
    assert result["checks"]["logit_gap_lsb"]["value"] == 0.0
    assert result["checks"]["unanswered"]["value"] == 0
    assert result["metrics"]["throughput_rps"]["value"] > 0


def test_int4_control_fails_the_limit(smoke):
    bm, _ = smoke
    cfg = bm.config(SMOKE)
    arch, plain = bm.arch(cfg), bm.reference(cfg)
    layers = arch.layers(cfg)
    limit = bm.config(CONFIG)["correct"]["logit_gap_lsb"]
    for seed in (5, 2**31 + 3):
        params = arch.make_params(cfg, seed)
        pool, calib = cell._pool(cfg, seed, 8)
        q8 = plain.quantize(layers, params,
                            plain.calibrate(layers, params, calib), 127)
        q4 = plain.quantize(layers, params,
                            plain.calibrate(layers, params, calib, 7), 7)
        gap = reference.logit_gap_lsb(
            plain.int_forward(layers, q4, pool), q4["out_scale"],
            plain.int_forward(layers, q8, pool), q8["out_scale"])
        assert gap > limit


def test_program_float_matches_float_reference(smoke):
    from repro.api import Plan
    bm, _ = smoke
    cfg = bm.config(SMOKE)
    arch, plain = bm.arch(cfg), bm.reference(cfg)
    params = arch.make_params(cfg, 11)
    model = spec.program_model(bm.bench, cfg, 11, params=params)
    sess = Plan.from_json(bm.bench / cfg["plan_file"], model).compile(
        "float")
    x = np.random.default_rng(4).standard_normal(
        (4, *cfg["input_shape"])).astype(np.float32)
    got = sess.submit_many(x).reshape(4, -1)
    want = plain.forward_float(arch.layers(cfg), params, x)
    # float32 both sides; the split sums bands in another order, and the
    # program writes hard-sigmoid as clip(x/6, -1/2, 1/2) + 1/2
    assert np.max(np.abs(got - want)) <= 1e-4 * np.max(np.abs(want))
