"""The work the algorithm asks for, and the chip's peaks to hold it to.

Counts are the algorithm's own, from the layer shapes: useful outputs
only, no padding, no halo recompute, each sample's int8 input and output
moved once, and the int8 weights moved once per executed batch (a batched
program reads a weight once for all its samples).  So an honest program
never reads above 100% of a bound built from them, and a change that
removes padding or recompute raises the share.

The MAC count per layer is the one ``repro.core.reinterpret.layer_macs``
defines (a conv's output positions x kernel area x input channels, a
depthwise conv's without the channel sum, a linear layer's in x out, the
global pool's input size); ``tests/bench`` checks the two agree.
"""
from __future__ import annotations

import json
import pathlib

PEAKS_FILE = pathlib.Path(__file__).resolve().parent.parent / "peaks.json"

# which layers of the plan each kernel family is given
FAMILIES = {
    "dwconv": lambda lyr: (lyr["kind"] == "dwconv" and lyr["k"] == 3
                           and lyr["pad"] == 1),
    "qgemm": lambda lyr: lyr["kind"] in ("conv", "linear"),
}


def layer_macs(lyr: dict) -> int:
    c, h, w = lyr["out_shape"]
    if lyr["kind"] == "linear":
        return lyr["in_shape"][0] * c
    if lyr["kind"] == "avgpool":
        ci, hi, wi = lyr["in_shape"]
        return ci * hi * wi
    cin = 1 if lyr["kind"] == "dwconv" else lyr["in_shape"][0]
    return c * h * w * lyr["k"] * lyr["k"] * cin


def activation_bytes(lyr: dict) -> int:
    """int8 bytes of one sample's input and output of the layer."""
    ci, hi, wi = lyr["in_shape"]
    co, ho, wo = lyr["out_shape"]
    return ci * hi * wi + co * ho * wo


def weight_bytes(lyr: dict) -> int:
    """int8 bytes of the layer's weights."""
    ci, hi, wi = lyr["in_shape"]
    co, k = lyr["out_shape"][0], lyr["k"]
    return {"conv": co * ci * k * k, "dwconv": co * k * k,
            "linear": ci * hi * wi * co}.get(lyr["kind"], 0)


def ops_per_sample(layers: list[dict]) -> int:
    """Integer operations of one sample: 2 per MAC."""
    return 2 * sum(layer_macs(lyr) for lyr in layers)


def family_work(layers: list[dict], family: str) -> tuple[int, int, int]:
    """The work of the layers a kernel family is given: (ops per sample,
    activation bytes per sample, weight bytes per executed batch)."""
    sel = [lyr for lyr in layers if FAMILIES[family](lyr)]
    return (2 * sum(layer_macs(lyr) for lyr in sel),
            sum(activation_bytes(lyr) for lyr in sel),
            sum(weight_bytes(lyr) for lyr in sel))


def family_least_time_s(layers: list[dict], family: str, samples: int,
                        batches: int, peak: dict) -> tuple[float, str]:
    """Least time of a family's work for ``samples`` real samples served in
    ``batches`` executions of the batch program, and its bound."""
    ops, act, weights = family_work(layers, family)
    return least_time_s(samples * ops, samples * act + batches * weights,
                        peak)


def peaks(device_kind: str) -> dict:
    """The device's published peaks; a device not in the table is an
    error, never a default."""
    table = json.loads(PEAKS_FILE.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} "
                       f"(have {sorted(table)})")
    return table[device_kind]


def least_time_s(ops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """The least time the chip could take for the work, and which of its
    int8 compute or its memory bandwidth bounds it."""
    t_ops = ops / peak["int8_ops_per_s"]
    t_mem = nbytes / peak["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")
