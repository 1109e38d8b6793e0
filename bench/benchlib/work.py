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
global pool's input size); ``tests/bench`` checks the two agree.  These
shared counts know the op kinds conv, dwconv, linear and avgpool and refuse
any other; an architecture with kinds of its own brings its counts, and
:class:`Counts` puts them together with the arithmetic here.
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
    kind = lyr["kind"]
    if kind == "linear":
        return lyr["in_shape"][0] * c
    if kind == "avgpool":
        ci, hi, wi = lyr["in_shape"]
        return ci * hi * wi
    if kind in ("conv", "dwconv"):
        cin = 1 if kind == "dwconv" else lyr["in_shape"][0]
        return c * h * w * lyr["k"] * lyr["k"] * cin
    raise ValueError(f"layer {lyr.get('name')!r}: no shared MAC count for "
                     f"op kind {kind!r}; its architecture module names the "
                     "WORK module that counts it")


def activation_bytes(lyr: dict) -> int:
    """int8 bytes of one sample's input and output of the layer."""
    ci, hi, wi = lyr["in_shape"]
    co, ho, wo = lyr["out_shape"]
    return ci * hi * wi + co * ho * wo


def weight_bytes(lyr: dict) -> int:
    """int8 bytes of the layer's weights."""
    ci, hi, wi = lyr["in_shape"]
    co, k = lyr["out_shape"][0], lyr["k"]
    kind = lyr["kind"]
    if kind == "conv":
        return co * ci * k * k
    if kind == "dwconv":
        return co * k * k
    if kind == "linear":
        return ci * hi * wi * co
    if kind == "avgpool":
        return 0
    raise ValueError(f"layer {lyr.get('name')!r}: no shared weight count for "
                     f"op kind {kind!r}; its architecture module names the "
                     "WORK module that counts it")


class Counts:
    """The work counts of one architecture.

    ``own`` is the module its architecture names as ``WORK`` (see
    ``spec.Benchmark.work``), or None.  Its ``layer_macs``,
    ``activation_bytes`` and ``weight_bytes``, where it defines them, count
    every layer in place of the shared functions above; its ``FAMILIES``
    add kernel families, and a shared family of the same name is found
    first.  The sums over a layer list are the same for every
    architecture."""

    def __init__(self, own=None):
        self.layer_macs = getattr(own, "layer_macs", layer_macs)
        self.activation_bytes = getattr(own, "activation_bytes",
                                        activation_bytes)
        self.weight_bytes = getattr(own, "weight_bytes", weight_bytes)
        self.families = {**getattr(own, "FAMILIES", {}), **FAMILIES}

    def ops_per_sample(self, layers: list[dict]) -> int:
        """Integer operations of one sample: 2 per MAC."""
        return 2 * sum(self.layer_macs(lyr) for lyr in layers)

    def family_work(self, layers: list[dict],
                    family: str) -> tuple[int, int, int]:
        """The work of the layers a kernel family is given: (ops per
        sample, activation bytes per sample, weight bytes per executed
        batch)."""
        if family not in self.families:
            raise ValueError(f"no kernel family {family!r} (have "
                             f"{sorted(self.families)})")
        sel = [lyr for lyr in layers if self.families[family](lyr)]
        return (2 * sum(self.layer_macs(lyr) for lyr in sel),
                sum(self.activation_bytes(lyr) for lyr in sel),
                sum(self.weight_bytes(lyr) for lyr in sel))

    def family_least_time_s(self, layers: list[dict], family: str,
                            samples: int, batches: int,
                            peak: dict) -> tuple[float, str]:
        """Least time of a family's work for ``samples`` real samples
        served in ``batches`` executions of the batch program, and its
        bound."""
        ops, act, weights = self.family_work(layers, family)
        return least_time_s(samples * ops,
                            samples * act + batches * weights, peak)


# the counts of an architecture that brings none of its own
SHARED = Counts()
ops_per_sample = SHARED.ops_per_sample
family_work = SHARED.family_work
family_least_time_s = SHARED.family_least_time_s


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
