"""Regenerate the committed plan JSONs of the benchmark's configurations.

Run from the repository root, on the CPU (the planner is host-only):

    JAX_PLATFORMS=cpu python bench/regen_plans.py

For every configuration file under ``bench/configs/`` that names a
``plan_file`` and a ``plan_objective``, it runs the planner for that
objective on the configuration's cluster and writes ``Plan.to_json`` beside
the configuration.  A plan's model fingerprint is structural (layer count,
input shape, MACs, weight bytes), so the written plan loads for every weight
seed.  The full search takes about 15 s for the two MobileNetV2@112 plans.
"""
from __future__ import annotations

import dataclasses
import json
import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent


def main() -> int:
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(BENCH))
    from repro.api import Cluster, Objective, Planner

    from benchlib import spec

    for path in sorted((BENCH / "configs").glob("*.json")):
        if path.name.endswith(".plan.json"):
            continue
        cfg = json.loads(path.read_text())
        if "plan_file" not in cfg:
            continue
        model = spec.program_model(BENCH, cfg, seed=0)
        cluster = getattr(Cluster, cfg["cluster"]["factory"])(
            *cfg["cluster"].get("args", []))
        obj = cfg["plan_objective"]
        objective = Objective(minimize=obj["minimize"],
                              ram_cap_bytes=obj["ram_cap_bytes"])
        if obj.get("modes"):
            objective = dataclasses.replace(objective,
                                            modes=tuple(obj["modes"]))
        plan = Planner(model, cluster).plan(objective)
        plan.to_json(BENCH / cfg["plan_file"])
        print(f"{path.name}: mode={plan.mode}/{plan.fusion} "
              f"workers={plan.n_workers} -> {cfg['plan_file']}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
