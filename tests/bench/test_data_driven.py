"""A cell, a traffic mix and a per-layer metric are added by new files and
entries alone: the harness lists and loads them without an edit to any file
that is already there."""
from __future__ import annotations

import hashlib
import json
import types

from benchtest_util import REPO, make_root
from benchlib import spec


def _digests(root):
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in (root / "bench").rglob("*") if p.is_file()}


def test_new_cell_traffic_and_metric_from_files_alone(tmp_path):
    root = make_root(tmp_path)
    before = _digests(root)
    bench = root / "bench"
    # a new traffic mix: data only
    (bench / "traffic" / "poisson_x2.json").write_text(json.dumps(
        {"arrivals": "poisson", "rate_rps": 80, "lead_s": 0.1,
         "max_batch": 4, "buckets": [1, 4],
         "slo": {"p99_target_s": 0.5, "queue_cap": 256}}))
    # a new per-layer metric: its data file and its reader
    (bench / "metrics" / "refused_share.overload.json").write_text(json.dumps(
        {"reader": "refused_share", "unit": "%", "moves": "latency_p50_ms"}))
    (bench / "readers" / "refused_share.py").write_text(
        "def read(rec, params):\n"
        "    w = rec.window_requests\n"
        "    return 100.0 * sum(r.status == 'refused' for r in w) / len(w)\n")
    doc = json.loads((root / "BENCHMARK.json").read_text())
    doc["workloads"].append({"name": "smoke-overload",
                             "config": "mnv2_smoke_int8_spatial",
                             "traffic": "poisson_x2", "chips": 1,
                             "why": "overload"})
    for m in doc["end_to_end"]:
        if m["name"] == "latency_p50_ms":
            m["workloads"].append("smoke-overload")
    doc["per_layer"].append({"name": "refused_share.overload", "unit": "%",
                             "better": "lower", "source": "host_clock",
                             "layer": "Server", "moves": "latency_p50_ms",
                             "workloads": ["smoke-overload"]})
    (root / "BENCHMARK.json").write_text(json.dumps(doc))

    bm = spec.Benchmark(root)
    assert "smoke-overload" in bm.cells
    c = bm.cell("smoke-overload")
    assert c.traffic["rate_rps"] == 80
    assert c.config["name"] == "mnv2_smoke_int8_spatial"
    assert {m.name for m in c.end_to_end} == {"latency_p50_ms", "setup_s"}
    assert [m.name for m in c.per_layer] == ["refused_share.overload"]
    metric = c.per_layer[0]
    reader = bm.reader(metric.reader)
    rec = types.SimpleNamespace(window_requests=[
        types.SimpleNamespace(status=s) for s in ("ok", "refused", "ok",
                                                  "ok")])
    assert reader.read(rec, metric.params) == 25.0
    # nothing that was there changed
    after = _digests(root)
    assert {k: v for k, v in after.items() if k in before} == before


def test_real_cells_resolve():
    bm = spec.Benchmark(REPO)
    for name in bm.cells:
        c = bm.cell(name)
        assert c.chips in (1, 4)
        assert any(m.name == "setup_s" for m in c.end_to_end)
        assert len(c.end_to_end) >= 2 and c.per_layer
        for m in c.per_layer:
            assert (bm.bench / "readers" / f"{m.reader}.py").exists()
        # the reference and the work counts resolve for its architecture
        layers = bm.arch(c.config).layers(c.config)
        ref = bm.reference(c.config)
        assert all(callable(getattr(ref, f))
                   for f in ("calibrate", "quantize", "int_forward"))
        assert bm.work(c.config).ops_per_sample(layers) > 0
