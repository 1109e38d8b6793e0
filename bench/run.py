"""Run one cell of the chip benchmark once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine with the chips the cell asks
for.  The cell, its configuration, traffic mix and metrics are found by
name from ``BENCHMARK.json`` (see ``bench/benchlib/spec.py``).  With
``--trace 0`` the result reports the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of the
window.  The last line of standard output is the result, one JSON object;
the last lines of standard error are the numbers the correctness check
compared, each with its limit.  Without an accelerator, or with fewer chips
than the cell asks for, it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_DIR = ROOT / ".bench_cache" / "jax"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(BENCH))
    # the TPU runtime's logs stay in the checkout too
    if "TPU_LOG_DIR" not in os.environ:
        log_dir = ROOT / ".bench_cache" / "tpu_logs"
        log_dir.mkdir(parents=True, exist_ok=True)
        os.environ["TPU_LOG_DIR"] = str(log_dir)
    import jax
    # the persistent compile cache lives at one fixed path in the checkout,
    # unbounded: a size limit from the environment (JAX_COMPILATION_CACHE_
    # MAX_SIZE) would evict a neuron-plan program (~25 MB an entry) before
    # its seed runs again, and every repeat would compile anew
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    from benchlib import cell, spec

    print(f"bench: imports {time.perf_counter() - T_PROCESS:.3f} s",
          file=sys.stderr)
    bm = spec.Benchmark(ROOT)
    try:
        result = cell.run(bm, args.workload, args.seed, args.seconds,
                          bool(args.trace), T_PROCESS)
    except cell.NoDevice as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
