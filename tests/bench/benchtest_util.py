"""Shared set-up of the benchmark's tests (imported by name, so that it
cannot collide with another directory's ``conftest``): a checkout-shaped root in a
temporary directory whose ``BENCHMARK.json`` adds CPU-sized cells to the
real ones, with the real architecture modules, readers and metric files
linked in and the test configuration and traffic files written beside the
real ones."""
from __future__ import annotations

import json
import pathlib
import shutil
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parents[2]
DATA = pathlib.Path(__file__).resolve().parent / "data"
for p in (REPO / "bench", REPO / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

SMOKE_CONFIG = "mnv2_smoke_int8_spatial"
SMOKE_TRAFFIC = {
    "smoke_poisson": {"arrivals": "poisson", "rate_rps": 40, "lead_s": 0.2,
                      "max_batch": 4, "buckets": [1, 4],
                      "slo": {"p99_target_s": None, "queue_cap": None},
                      "input_pool": 8},
    "smoke_closed": {"arrivals": "closed", "outstanding": 8, "lead_s": 0.2,
                     "max_batch": 4, "buckets": [1, 4],
                     "slo": {"p99_target_s": None, "queue_cap": None},
                     "input_pool": 8},
}
SMOKE_CELLS = [
    {"name": "smoke-steady", "config": SMOKE_CONFIG,
     "traffic": "smoke_poisson", "chips": 1, "why": "CPU test cell"},
    {"name": "smoke-offline", "config": SMOKE_CONFIG,
     "traffic": "smoke_closed", "chips": 1, "why": "CPU test cell"},
]


def make_root(tmp: pathlib.Path, cells=SMOKE_CELLS,
              traffic=SMOKE_TRAFFIC) -> pathlib.Path:
    """A root holding ``BENCHMARK.json`` with ``cells`` added, the real
    ``bench/`` files, and the smoke configuration and traffic files."""
    bench = tmp / "bench"
    shutil.copytree(REPO / "bench", bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    for name in (f"{SMOKE_CONFIG}.json", f"{SMOKE_CONFIG}.plan.json"):
        shutil.copy(DATA / name, bench / "configs" / name)
    for name, t in traffic.items():
        (bench / "traffic" / f"{name}.json").write_text(json.dumps(t))
    doc = json.loads((REPO / "BENCHMARK.json").read_text())
    doc["workloads"] += cells
    for m in doc["end_to_end"] + doc["per_layer"]:
        steady = "steady" in m["name"] or m["name"].startswith("latency")
        offline = "offline" in m["name"] or m["name"] == "throughput_rps"
        if "workloads" in m:
            m["workloads"] += [c["name"] for c in cells
                               if (steady and c["name"].endswith("steady"))
                               or (offline and c["name"].endswith("offline"))]
    (tmp / "BENCHMARK.json").write_text(json.dumps(doc, indent=1))
    return tmp


@pytest.fixture(scope="module")
def smoke_root(tmp_path_factory):
    """A root as :func:`make_root` builds it (import the fixture by name:
    this directory has no ``conftest.py``, which would shadow the test
    suite's own ``conftest`` module)."""
    return make_root(tmp_path_factory.mktemp("bench_root"))
