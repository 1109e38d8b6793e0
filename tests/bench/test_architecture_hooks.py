"""An architecture brings its own reference and work counts by new files
alone, the shared ones refuse what they do not know, and no reference
imports the program.

The new architecture is the smoke MobileNetV2 with its depthwise layers
given the op kind ``"depthwise"``, which neither ``benchlib.reference`` nor
``benchlib.work`` knows.  Its module names a reference of its own (the
shared one over the layers relabelled back, plus ``SHIFT`` output steps on
every logit) and counts of its own (itself), with a kernel family
``depthwise``.  It runs through ``cell.run`` on the CPU, once with
``SHIFT`` 0 and once with 20."""
from __future__ import annotations

import ast
import hashlib
import json
import shutil
import time
import types

import numpy as np
import pytest

from benchtest_util import DATA, REPO, SMOKE_CONFIG, make_root
from benchlib import cell, reference, spec, trace, work

ARCH = "mnv2_relabelled"
CONFIG = "mnv2_relabelled_smoke"
CELL = "relabelled-steady"
PROBE = "rec_probe"

ARCH_SRC = '''"""MobileNetV2 with its depthwise layers of kind "depthwise"."""
import pathlib

from benchlib import spec, work

_base = spec.load_module(pathlib.Path(__file__).with_name("mobilenet_v2.py"))
REFERENCE = "mnv2_relabelled_reference"
WORK = "mnv2_relabelled"
FAMILIES = {"depthwise": lambda lyr: lyr["kind"] == "depthwise"}
make_params = _base.make_params
program_ops = _base.program_ops


def layers(cfg):
    return [dict(lyr, kind="depthwise") if lyr["kind"] == "dwconv" else lyr
            for lyr in _base.layers(cfg)]


def layer_macs(lyr):
    if lyr["kind"] == "depthwise":
        c, h, w = lyr["out_shape"]
        return c * h * w * lyr["k"] * lyr["k"]
    return work.layer_macs(lyr)


def weight_bytes(lyr):
    if lyr["kind"] == "depthwise":
        return lyr["out_shape"][0] * lyr["k"] * lyr["k"]
    return work.weight_bytes(lyr)
'''

REFERENCE_SRC = '''"""The relabelled MobileNetV2's reference, SHIFT output steps off."""
from benchlib import reference as shared

SHIFT = 0


def _shared_kinds(layers):
    return [dict(lyr, kind="dwconv") if lyr["kind"] == "depthwise" else lyr
            for lyr in layers]


def calibrate(layers, params, calib, qmax=127):
    return shared.calibrate(_shared_kinds(layers), params, calib, qmax)


def quantize(layers, params, scales, qmax):
    return shared.quantize(_shared_kinds(layers), params, scales, qmax)


def int_forward(layers, q, x):
    return shared.int_forward(_shared_kinds(layers), q, x) + SHIFT
'''

PROBE_SRC = '''"""Keeps the record of every run it reads (a test's window into it)."""
SEEN = []


def read(rec, params):
    SEEN.append(rec)
    return float(len(SEEN))
'''


def _digests(root):
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in (root / "bench").rglob("*") if p.is_file()}


def _smoke_layers():
    cfg = json.loads((DATA / f"{SMOKE_CONFIG}.json").read_text())
    arch = spec.load_module(REPO / "bench" / "models" / f"{cfg['arch']}.py")
    return cfg, arch, arch.layers(cfg)


@pytest.fixture(scope="module")
def added(tmp_path_factory):
    """A root with the new architecture added by files alone, and the two
    runs of its cell (reference shift 0, then 20)."""
    root = make_root(tmp_path_factory.mktemp("arch_root"),
                     cells=[{"name": CELL, "config": CONFIG,
                             "traffic": "smoke_poisson", "chips": 1,
                             "why": "CPU test cell"}])
    before = _digests(root)
    bench = root / "bench"
    (bench / "models" / f"{ARCH}.py").write_text(ARCH_SRC)
    (bench / "models" / f"{ARCH}_reference.py").write_text(REFERENCE_SRC)
    cfg = json.loads((DATA / f"{SMOKE_CONFIG}.json").read_text())
    cfg.update(arch=ARCH, name=CONFIG,
               plan_file=f"configs/{CONFIG}.plan.json")
    (bench / "configs" / f"{CONFIG}.json").write_text(json.dumps(cfg))
    shutil.copy(DATA / f"{SMOKE_CONFIG}.plan.json",
                bench / "configs" / f"{CONFIG}.plan.json")
    (bench / "readers" / f"{PROBE}.py").write_text(PROBE_SRC)
    (bench / "metrics" / f"{PROBE}.json").write_text(json.dumps(
        {"reader": PROBE, "unit": "1"}))
    doc = json.loads((root / "BENCHMARK.json").read_text())
    doc["end_to_end"].append({"name": PROBE, "unit": "1", "better": "higher",
                              "bound": 0.01, "source": "host_clock",
                              "workloads": [CELL]})
    (root / "BENCHMARK.json").write_text(json.dumps(doc))

    bm = spec.Benchmark(root)
    own_ref = bm.reference(bm.config(CONFIG))
    runs = []
    try:
        for seed, shift in ((11, 0), (2**31 + 13, 20)):
            own_ref.SHIFT = shift
            runs.append(cell.run(bm, CELL, seed, 1.0, False,
                                 time.perf_counter(), check_device=False,
                                 log=lambda msg: None))
    finally:
        own_ref.SHIFT = 0
    probe = bm.reader(PROBE)
    return types.SimpleNamespace(
        root=root, bm=bm, cfg=bm.config(CONFIG), own_ref=own_ref, runs=runs,
        recs=list(probe.SEEN[-2:]), before=before, after=_digests(root))


def test_own_reference_decides_correct(added):
    assert added.own_ref is not reference
    assert added.bm.reference(added.cfg).__file__.endswith(
        f"{ARCH}_reference.py")
    same, shifted = added.runs
    assert same["correct"] is True
    assert same["checks"]["logit_gap_lsb"]["value"] == 0.0
    assert shifted["correct"] is False
    assert shifted["checks"]["logit_gap_lsb"]["value"] == pytest.approx(20.0)
    assert same["attempted"] and shifted["attempted"]
    # the shared reference alone could not have checked these layers
    layers = added.bm.arch(added.cfg).layers(added.cfg)
    with pytest.raises(ValueError, match="'depthwise'"):
        reference.quantize(layers, [None] * len(layers),
                           [1.0] * (len(layers) + 1), 127)


def _plain(added):
    """The same layers as the shared counts know them (kind dwconv)."""
    cfg = dict(added.cfg, arch="mobilenet_v2")
    return added.bm.arch(cfg).layers(cfg)


def test_own_family_is_what_kernel_roofline_reads(added):
    rec = added.recs[0]
    assert rec.work.families["depthwise"] is \
        added.bm.arch(added.cfg).FAMILIES["depthwise"]
    peak = work.peaks("TPU v5 lite")
    t_ns = 2e6
    ev = trace.Event("/device:TPU:0", trace.OPS_LINE,
                     "%fusion.3 = depthwise_kernel(...)", 0.0, t_ns)
    rec = types.SimpleNamespace(**dict(vars(rec), peak=peak,
                                       trace=trace.Trace([ev], 0.0, 1e9)))
    share = added.bm.reader("kernel_roofline").read(
        rec, {"family": "depthwise", "pattern": "depthwise_kernel"})
    least, _ = work.family_least_time_s(
        _plain(added), "dwconv", rec.session["requests"],
        rec.session["batches"], peak)
    assert rec.session["requests"] > 0
    assert share == pytest.approx(100.0 * least / (t_ns * 1e-9), rel=1e-12)
    with pytest.raises(ValueError, match="depthwise"):
        rec.work.family_work(rec.layers, "no_such_family")


def test_own_layer_macs_is_what_mfu_readers_read(added):
    rec = added.recs[0]
    own = added.bm.arch(added.cfg)
    assert rec.work.layer_macs is own.layer_macs
    assert rec.work.weight_bytes is own.weight_bytes
    with pytest.raises(ValueError, match="'depthwise'"):
        work.ops_per_sample(rec.layers)
    ops = work.ops_per_sample(_plain(added))
    peak = work.peaks("TPU v5 lite")
    rec = types.SimpleNamespace(**dict(vars(rec), peak=peak))
    w0, w1 = rec.window
    done = sum(r.status == "ok" and w0 <= r.done <= w1 for r in rec.requests)
    assert done > 0
    mfu = added.bm.reader("throughput_mfu").read(rec, {})
    assert mfu == pytest.approx(
        100.0 * done / rec.seconds * ops / peak["int8_ops_per_s"], rel=1e-12)
    t_ns = 5e6
    ev = trace.Event("/device:TPU:0", trace.MODULES_LINE, "jit_fn(7)", 0.0,
                     t_ns)
    rec.trace = trace.Trace([ev], 0.0, 1e9)
    step = added.bm.reader("step_mfu").read(rec, {"program": r"^jit_fn\("})
    assert step == pytest.approx(
        100.0 * rec.session["requests"] * ops
        / (t_ns * 1e-9 * peak["int8_ops_per_s"]), rel=1e-12)


def test_nothing_that_was_there_changed(added):
    assert {k: v for k, v in added.after.items()
            if k in added.before} == added.before
    new = set(added.after) - set(added.before)
    assert {p.as_posix() for p in new if "__pycache__" not in p.parts} == {
        f"bench/models/{ARCH}.py", f"bench/models/{ARCH}_reference.py",
        f"bench/configs/{CONFIG}.json", f"bench/configs/{CONFIG}.plan.json",
        f"bench/readers/{PROBE}.py", f"bench/metrics/{PROBE}.json"}


# --- the shared reference and counts refuse what they do not know ---------

def _with(layers, index, **change):
    return [dict(lyr, **change) if i == index else lyr
            for i, lyr in enumerate(layers)]


@pytest.fixture(scope="module")
def smoke():
    cfg, arch, layers = _smoke_layers()
    params = arch.make_params(cfg, 5)
    x = np.random.default_rng(5).standard_normal(
        (2, *cfg["input_shape"]), dtype=np.float32)
    q = reference.quantize(layers, params,
                           reference.calibrate(layers, params, x), 127)
    dw = next(i for i, lyr in enumerate(layers) if lyr["kind"] == "dwconv")
    conv = next(i for i, lyr in enumerate(layers) if lyr["kind"] == "conv")
    return types.SimpleNamespace(layers=layers, params=params, x=x, q=q,
                                 dw=dw, conv=conv)


@pytest.mark.parametrize("call, unknown", [
    ("calibrate", "se"), ("calibrate", "hswish"), ("int_forward", "se"),
    ("int_forward", "hswish"), ("quantize", "se"), ("quantize", "hswish")])
def test_shared_reference_refuses_the_unknown(smoke, call, unknown):
    layers = (_with(smoke.layers, smoke.dw, kind="se") if unknown == "se"
              else _with(smoke.layers, smoke.conv, act="hswish"))
    with pytest.raises(ValueError, match=f"'{unknown}'"):
        if call == "calibrate":
            reference.calibrate(layers, smoke.params, smoke.x)
        elif call == "int_forward":
            reference.int_forward(layers, smoke.q, smoke.x)
        else:
            reference.quantize(layers, smoke.params,
                               [1.0] * (len(layers) + 1), 127)


SE = {"kind": "se", "name": "b3_se", "in_shape": (72, 28, 28),
      "out_shape": (72, 28, 28), "k": 1, "stride": 1, "pad": 0,
      "act": None, "save_as": None, "residual_from": None}


@pytest.mark.parametrize("count", [
    lambda: work.layer_macs(SE), lambda: work.weight_bytes(SE),
    lambda: work.ops_per_sample([SE])],
    ids=["layer_macs", "weight_bytes", "ops_per_sample"])
def test_shared_work_refuses_an_unknown_kind(count):
    with pytest.raises(ValueError, match="'se'"):
        count()


def test_family_work_refuses_an_unknown_family():
    _, _, layers = _smoke_layers()
    with pytest.raises(ValueError, match=r"'dwconv5x5'.*\['dwconv', "
                                         r"'qgemm'\]"):
        work.family_work(layers, "dwconv5x5")
    own = types.SimpleNamespace(FAMILIES={"se_gate": lambda lyr: False,
                                          "dwconv": lambda lyr: False})
    counts = work.Counts(own)
    with pytest.raises(ValueError, match=r"\['dwconv', 'qgemm', 'se_gate'\]"):
        counts.family_work(layers, "dwconv5x5")
    # a shared family is found first, whatever the module brings
    assert counts.family_work(layers, "dwconv") == \
        work.family_work(layers, "dwconv")
    assert counts.family_work(layers, "se_gate") == (0, 0, 0)


# --- no reference imports the program ---------------------------------------

def _program_imports(source: str) -> list[str]:
    """Every import of ``repro`` in ``source``: statements, and calls of
    ``importlib.import_module`` or ``__import__`` with a literal name."""
    found = []
    for node in ast.walk(ast.parse(source)):
        if isinstance(node, ast.Import):
            names = [a.name for a in node.names]
        elif isinstance(node, ast.ImportFrom):
            names = [node.module or ""] if node.level == 0 else []
        elif isinstance(node, ast.Call):
            fn = node.func
            fname = getattr(fn, "attr", None) or getattr(fn, "id", None)
            names = ([a.value for a in node.args[:1]
                      if isinstance(a, ast.Constant)
                      and isinstance(a.value, str)]
                     if fname in ("import_module", "__import__") else [])
        else:
            continue
        found += [n for n in names if n == "repro" or n.startswith("repro.")]
    return found


GUARDED = sorted((REPO / "bench" / "models").glob("*.py")) + [
    REPO / "bench" / "benchlib" / "reference.py",
    REPO / "bench" / "benchlib" / "work.py"]


@pytest.mark.parametrize("path", GUARDED,
                         ids=[p.relative_to(REPO).as_posix() for p in GUARDED])
def test_reference_side_imports_nothing_of_the_program(path):
    assert _program_imports(path.read_text()) == []


@pytest.mark.parametrize("source", [
    "import repro", "import repro.core.quantize as q",
    "from repro.core import reinterpret", "from repro import api",
    "import importlib\nimportlib.import_module('repro.models')",
    "def f():\n    return __import__('repro')"])
def test_import_guard_sees_every_form(source):
    assert _program_imports(source)
    assert _program_imports(source.replace("repro", "reprox")) == []
