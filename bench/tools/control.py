"""Readings of the correctness control on the chip, at a cell's own size.

    python3 bench/tools/control.py --workload mnv2-spatial-steady \\
        --seeds 1,2,3

The control is the configuration's plain reference (as
``spec.Benchmark.reference`` resolves it) put in the program's place at the
nearest precision below the configuration's: int4 weights and activations
(scales max|.|/7) for the int8 configuration.  For every seed it makes the
cell's weights and input pool, and prints the number the cell's check
compares (``logit_gap_lsb``: the widest gap of the control's dequantized
logits from the int8 reference's, in the reference's output steps) over the
whole input pool.  Beside it, what a slot mix-up would read: every answer
given its neighbour's in the pool (``swap_logit_gap_lsb``), and the least
gap between the int8 reference answers of any two distinct inputs
(``swap_min_pair_lsb``, one swapped pair at worst).  Exits non-zero without
an accelerator.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

import numpy as np

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args()
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(BENCH))
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      str(ROOT / ".bench_cache" / "jax"))
    from benchlib import cell, reference, spec

    bm = spec.Benchmark(ROOT)
    c = bm.cell(args.workload)
    cell.device_info(jax, c.chips)
    arch = bm.arch(c.config)
    plain = bm.reference(c.config)
    layers = arch.layers(c.config)
    for seed in (int(s) for s in args.seeds.split(",")):
        params = arch.make_params(c.config, seed)
        pool, calib = cell._pool(c.config, seed,
                                 int(c.traffic.get("input_pool", 64)))
        q8 = plain.quantize(layers, params,
                            plain.calibrate(layers, params, calib), 127)
        q4 = plain.quantize(layers, params,
                            plain.calibrate(layers, params, calib, 7), 7)
        ref = plain.int_forward(layers, q8, pool)
        ctl = plain.int_forward(layers, q4, pool)
        refd = ref.reshape(len(ref), -1).astype(np.int64)
        pair = np.abs(refd[:, None, :] - refd[None, :, :]).max(axis=-1)
        pair[np.eye(len(refd), dtype=bool)] = np.iinfo(np.int64).max
        print(json.dumps({
            "seed": seed, "inputs": len(pool),
            "control_logit_gap_lsb": reference.logit_gap_lsb(
                ctl, q4["out_scale"], ref, q8["out_scale"]),
            "swap_logit_gap_lsb": reference.logit_gap_lsb(
                np.roll(ref, 1, axis=0), q8["out_scale"], ref,
                q8["out_scale"]),
            "swap_min_pair_lsb": float(pair.min()),
            "limit": c.config["correct"]["logit_gap_lsb"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
