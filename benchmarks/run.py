"""Benchmark driver: one function per paper table/figure + kernel bench +
the executor engine bench (which also writes BENCH_executor.json).
Prints ``name,value,derived`` CSV.

Run:  PYTHONPATH=src python -m benchmarks.run [--smoke]
  or  PYTHONPATH=src python benchmarks/run.py [--smoke]

``--smoke`` (or REPRO_BENCH_QUICK=1) restricts the executor bench to the
smoke config — the CI invocation.  Exits non-zero if ANY sub-benchmark
raises: a failed suite prints an ``<title>,ERROR,...`` row, the remaining
suites still run, and the failure is reported at exit so CI cannot go green
on partial results.
"""
from __future__ import annotations

import argparse
import functools
import os
import sys
import time


def build_suites(quick: bool):
    if __package__ in (None, ""):
        # run as a plain script: the suites import each other as
        # ``benchmarks.*``, so the repository root must be importable
        sys.path.insert(0, os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
    from benchmarks import (elastic_bench, executor_bench, kernel_bench,
                            paper_benchmarks as pb, planner_bench,
                            roofline_report, runtime_bench, serving_bench)
    return [
        ("Table I (K1 calibration)", pb.table1_k1),
        ("Table II (allocation strategies)", pb.table2_allocation),
        ("Fig 8 (layer-wise peak RAM)", pb.fig8_layer_peak_ram),
        ("Fig 9 (latency scaling)", pb.fig9_latency_scaling),
        ("Figs 10-11 (layer-wise comm/comp)", pb.fig10_fig11_layerwise),
        ("Fig 12 (memory scalability)", pb.fig12_scalability),
        ("Partitioning modes (comm/peak tradeoff)", pb.mode_tradeoff),
        ("Kernels", kernel_bench.bench_kernels),
        ("Executor (eager vs compiled)",
         functools.partial(executor_bench.bench_executor, quick=quick)),
        ("Planner (plan-search)",
         functools.partial(planner_bench.bench_planner, quick=quick)),
        ("Runtime (distributed coordinator)",
         functools.partial(runtime_bench.bench_runtime, quick=quick)),
        ("Serving (multi-tenant continuous batching)",
         functools.partial(serving_bench.bench_serving, quick=quick)),
        ("Elastic (churn recovery)",
         functools.partial(elastic_bench.bench_elastic, quick=quick)),
        # last: renders the roofline/compile sections the executor bench
        # just persisted into roofline_report.md (uploaded by CI)
        ("Roofline (per-block report)", roofline_report.bench_roofline),
    ]


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="smoke configs only (CI; same as REPRO_BENCH_QUICK=1)")
    args = ap.parse_args(argv)
    quick = args.smoke or os.environ.get(
        "REPRO_BENCH_QUICK", "") not in ("", "0", "false", "False")
    print("name,value,derived")
    failed: list[str] = []
    for title, fn in build_suites(quick):
        t0 = time.time()
        try:
            rows = fn()
        except Exception as e:  # noqa: BLE001
            print(f"{title},ERROR,{type(e).__name__}: {e}")
            failed.append(title)
            continue
        for name, value, derived in rows:
            if isinstance(value, float):
                value = f"{value:.4f}"
            print(f"{name},{value},{derived}")
        print(f"# {title}: {time.time()-t0:.1f}s", file=sys.stderr)
    if failed:
        print(f"# FAILED suites: {', '.join(failed)}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    sys.exit(main())
