"""Find the highest Poisson rate a steady cell's server sustains (one-off,
on the chip, in one process; the cell's traffic file then fixes its rate at
about 0.8 of it).

    python3 bench/tools/knee.py --workload mnv2-spatial-steady --seed 1 \\
        --seconds 8 --rates 100,200,300,400

A rate is sustained when no request is refused, the backlog does not grow
across the window (the last third's median latency is within twice the
first third's, plus 5 ms), and the 95th percentile stays within the
server's default ``SLO().p99_target_s``.  One JSON line per rate, then the
knee.  Exits non-zero without an accelerator.
"""
from __future__ import annotations

import argparse
import json
import math
import pathlib
import statistics
import sys

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args()
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(BENCH))
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      str(ROOT / ".bench_cache" / "jax"))
    from benchlib import cell, spec
    from repro.serve import SLO

    target_ms = 1e3 * SLO().p99_target_s
    ctx = cell.setup(spec.Benchmark(ROOT), args.workload, args.seed)
    knee = None
    for rate in (float(r) for r in args.rates.split(",")):
        rec = cell.window(ctx, args.seconds,
                          traffic=dict(ctx.traffic, rate_rps=rate))
        w = sorted(rec.window_requests, key=lambda r: r.due)
        lat = sorted(1e3 * (r.done - r.due) if r.status == "ok" else math.inf
                     for r in w)
        p95 = lat[math.ceil(0.95 * len(lat)) - 1]
        third = max(1, len(w) // 3)

        def med(rs):
            return statistics.median(1e3 * (r.done - r.due) if r.status == "ok"
                                     else math.inf for r in rs)
        first, last = med(w[:third]), med(w[-third:])
        refused = sum(r.status != "ok" for r in w)
        ok = refused == 0 and last <= 2 * first + 5 and p95 <= target_ms
        print(json.dumps({"rate_rps": rate, "requests": len(w),
                          "refused": refused, "p50_ms": lat[len(lat) // 2],
                          "p95_ms": p95, "first_third_median_ms": first,
                          "last_third_median_ms": last,
                          "batch_size": (rec.session["requests"]
                                         / max(1, rec.session["batches"])),
                          "sustained": ok}), flush=True)
        if ok:
            knee = rate
    print(json.dumps({"knee_rps": knee}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
