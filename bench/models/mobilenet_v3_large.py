"""MobileNetV3-Large (Howard et al., "Searching for MobileNetV3",
arXiv:1905.02244, Table 1) as the benchmark's own layer list, with its
weights made from the seed.

The layer list follows the table: a 3x3 stride-2 stem with hard-swish, 15
bottlenecks (expand 1x1 where the expanded width differs from the input ->
depthwise k x k -> squeeze-excite where the table marks it -> project 1x1,
residual where the stride is 1 and the widths match), a 1x1 head conv with
hard-swish, the global pool, a linear layer with hard-swish and the
classifier.  The squeeze-excite branch is written in layers: the depthwise
output is stashed (``save_as``), then ``avgpool`` -> ``linear`` to
``make_divisible(expanded / 4, 8)`` + ReLU -> ``linear`` back + hard-sigmoid
-> ``gate`` (the stashed activation times the per-channel factor;
``gate_of`` names the stash).  The reduce width follows torchvision's
``mobilenet_v3_large``.

Departures from the paper: random weights from the seed; BatchNorm taken as
folded into every conv (weight times a per-channel gain, as a BatchNorm
with gamma and variance in [0.5, 1.5] would leave it, and a small bias);
the classifier's dropout left out (inference).

The architecture's op kind ``gate`` and activations ``hswish`` and
``hsigmoid`` are not the shared reference's, so the module names its own
reference (``mobilenet_v3_large_reference``) and counts its work itself
(``WORK``): the gate, and the 5x5 depthwise family ``dwconv5`` beside the
shared 3x3 ``dwconv``.  It imports nothing of the program.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchlib import work

REFERENCE = "mobilenet_v3_large_reference"
WORK = "mobilenet_v3_large"
FAMILIES = {"dwconv5": lambda lyr: (lyr["kind"] == "dwconv" and lyr["k"] == 5
                                    and lyr["pad"] == 2)}


def _make_divisible(v: float, divisor: int = 8) -> int:
    new_v = max(divisor, int(v + divisor / 2) // divisor * divisor)
    if new_v < 0.9 * v:
        new_v += divisor
    return new_v


def layers(cfg: dict) -> list[dict]:
    """The layer list in execution order (CHW shapes); every entry has the
    shared keys and ``gate_of`` (None but on a gate)."""
    c, h, w = cfg["input_shape"]
    out: list[dict] = []

    def add(kind, name, cout, k=1, s=1, act=None, out_hw=(1, 1)):
        nonlocal c, h, w
        p = k // 2
        if kind in ("conv", "dwconv"):
            out_hw = (h + 2 * p - k) // s + 1, (w + 2 * p - k) // s + 1
        out.append(dict(kind=kind, name=name, in_shape=(c, h, w),
                        out_shape=(cout, *out_hw), k=k, stride=s, pad=p,
                        act=act, save_as=None, residual_from=None,
                        gate_of=None))
        c, (h, w) = cout, out_hw

    cin = cfg["stem_channels"]
    add("conv", "stem", cin, 3, 2, "hswish")
    for b, (k, exp, cout, se, act, s) in enumerate(cfg["bottlenecks"]):
        tag = f"b{b}"
        first = len(out)
        if exp != cin:
            add("conv", f"{tag}_expand", exp, 1, 1, act)
        add("dwconv", f"{tag}_dw", exp, k, s, act)
        if se:
            out[-1]["save_as"] = f"{tag}_se"
            hw = (h, w)
            r = _make_divisible(exp / 4, cfg["se_divisor"])
            add("avgpool", f"{tag}_se_pool", exp)
            add("linear", f"{tag}_se_reduce", r, act="relu")
            add("linear", f"{tag}_se_expand", exp, act="hsigmoid")
            add("gate", f"{tag}_se_gate", exp, out_hw=hw)
            out[-1]["gate_of"] = f"{tag}_se"
        add("conv", f"{tag}_project", cout)
        if s == 1 and cin == cout:
            # the block input is the output of the layer before the block
            out[first - 1]["save_as"] = f"{tag}_in"
            out[-1]["residual_from"] = f"{tag}_in"
        cin = cout
    add("conv", "head_conv", cfg["head_conv_channels"], act="hswish")
    add("avgpool", "gap", c)
    add("linear", "head_fc", cfg["head_fc_features"], act="hswish")
    add("linear", "classifier", cfg["num_classes"])
    return out


def weight_shape(layer: dict) -> tuple[int, ...] | None:
    cin, cout, k = layer["in_shape"][0], layer["out_shape"][0], layer["k"]
    if layer["kind"] == "conv":
        return (cout, cin, k, k)
    if layer["kind"] == "dwconv":
        return (cout, 1, k, k)
    if layer["kind"] == "linear":
        return (cin, cout)
    return None


@functools.partial(jax.jit, static_argnames=("shapes",))
def _make(seed_words, shapes):
    """Every weight and bias from two random draws sliced per layer (one
    small program for every seed, the seed being an argument).  Convs get
    He-normal weights times a folded BatchNorm's per-channel gain and a bias
    in [-0.05, 0.05); linear layers (no BatchNorm) He-normal weights and a
    bias in [-0.1, 0.1)."""
    key = jax.random.fold_in(jax.random.key(seed_words[0]), seed_words[1])
    k_w, k_c = jax.random.split(key)
    n_w = sum(int(np.prod(s)) for s in shapes)
    n_c = sum(s[1] if len(s) == 2 else s[0] for s in shapes)
    normal = jax.random.normal(k_w, (n_w,), jnp.float32)
    gamma, var, bias = jax.random.uniform(k_c, (3, n_c), jnp.float32, 0.5, 1.5)
    params, ow, oc = [], 0, 0
    for shape in shapes:
        size = int(np.prod(shape))
        cout = shape[1] if len(shape) == 2 else shape[0]
        fan_in = shape[0] if len(shape) == 2 else size // cout
        w = normal[ow:ow + size].reshape(shape) * np.float32(
            np.sqrt(2.0 / fan_in))
        if len(shape) == 2:
            b = (bias[oc:oc + cout] - 1.0) * 0.2
        else:
            gain = gamma[oc:oc + cout] / jnp.sqrt(var[oc:oc + cout])
            w = w * gain.reshape((cout,) + (1,) * (len(shape) - 1))
            b = (bias[oc:oc + cout] - 1.0) * 0.1
        params.append((w, b))
        ow, oc = ow + size, oc + cout
    return params


def seed_words(seed: int) -> np.ndarray:
    """A seed of any size as two uint32 words (low, high)."""
    seed = int(seed)
    return np.array([seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF], np.uint32)


def make_params(cfg: dict, seed: int) -> list[tuple[np.ndarray, np.ndarray] | None]:
    """Float32 weights and biases of every layer (None for the pools and
    gates), made on the device in one jitted call and fetched once."""
    lays = layers(cfg)
    shapes = tuple(s for s in map(weight_shape, lays) if s is not None)
    made = iter(jax.device_get(_make(seed_words(seed), shapes)))
    return [next(made) if weight_shape(lyr) is not None else None
            for lyr in lays]


def program_ops(cfg: dict, params) -> list[dict]:
    """The op list the program's ``trace_sequential`` builds its model
    from, carrying the benchmark's weights."""
    ops = []
    for lyr, p in zip(layers(cfg), params):
        op = dict(kind=lyr["kind"], name=lyr["name"])
        if lyr["kind"] in ("conv", "dwconv"):
            op.update(kernel=(lyr["k"],) * 2, stride=(lyr["stride"],) * 2,
                      padding=(lyr["pad"],) * 2, activation=lyr["act"])
            if lyr["kind"] == "conv":
                op["out_channels"] = lyr["out_shape"][0]
        elif lyr["kind"] == "linear":
            op.update(features=lyr["out_shape"][0], activation=lyr["act"])
        if p is not None:
            op.update(weight=np.asarray(p[0], np.float32),
                      bias=np.asarray(p[1], np.float32))
        for key in ("save_as", "residual_from", "gate_of"):
            if lyr[key] is not None:
                op[key] = lyr[key]
        ops.append(op)
    return ops


# -- work counts (WORK names this module) ----------------------------------

def layer_macs(lyr: dict) -> int:
    """A gate multiplies every element of the activation it scales once;
    every other kind is the shared count."""
    if lyr["kind"] == "gate":
        c, h, w = lyr["out_shape"]
        return c * h * w
    return work.layer_macs(lyr)


def weight_bytes(lyr: dict) -> int:
    return 0 if lyr["kind"] == "gate" else work.weight_bytes(lyr)


def activation_bytes(lyr: dict) -> int:
    """A gate reads its (C, 1, 1) factor and the stashed (C, H, W)
    activation and writes a (C, H, W) one."""
    if lyr["kind"] == "gate":
        c, h, w = lyr["out_shape"]
        return c + 2 * c * h * w
    return work.activation_bytes(lyr)
