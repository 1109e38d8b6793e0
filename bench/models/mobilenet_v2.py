"""MobileNetV2 (Sandler et al., arXiv:1801.04381) as the benchmark's own
layer list, with its weights made from the seed.

The benchmark owns the architecture and the weights, and hands the program
only an op list (``program_ops``) for ``repro.core.reinterpret.
trace_sequential``; the plain reference (``benchlib.reference``) runs the
same layer list with the same weights and imports nothing of the program.

Conv + BatchNorm are taken as already folded (the paper's offline step):
every conv carries a weight and a bias.  The folded weight is He-normal
times a per-channel gain, as a BatchNorm with gamma in [0.5, 1.5] and
variance in [0.5, 1.5] would leave it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def _make_divisible(v: float, divisor: int = 8) -> int:
    new_v = max(divisor, int(v + divisor / 2) // divisor * divisor)
    if new_v < 0.9 * v:
        new_v += divisor
    return new_v


def layers(cfg: dict) -> list[dict]:
    """The layer list: kind, shapes, stride, padding, activation and the
    residual stash/add keys, in execution order (CHW shapes)."""
    c, h, w = cfg["input_shape"]
    wm = cfg["width_mult"]
    out: list[dict] = []

    def conv(kind, name, cout, k, s, act, **kw):
        nonlocal c, h, w
        p = k // 2
        oh, ow = (h + 2 * p - k) // s + 1, (w + 2 * p - k) // s + 1
        out.append(dict(kind=kind, name=name, in_shape=(c, h, w),
                        out_shape=(cout, oh, ow), k=k, stride=s, pad=p,
                        act=act, save_as=None, residual_from=None, **kw))
        c, h, w = cout, oh, ow

    in_ch = _make_divisible(cfg["stem_channels"] * wm)
    conv("conv", "stem", in_ch, 3, 2, "relu6")
    block = 0
    for t, ch, n, s in cfg["inverted_residual"]:
        cout = _make_divisible(ch * wm)
        for i in range(n):
            stride = s if i == 0 else 1
            use_res = stride == 1 and in_ch == cout
            tag = f"b{block}"
            first = len(out)
            if t != 1:
                conv("conv", f"{tag}_expand", in_ch * t, 1, 1, "relu6")
            conv("dwconv", f"{tag}_dw", in_ch * t, 3, stride, "relu6")
            conv("conv", f"{tag}_project", cout, 1, 1, None)
            if use_res:
                # the block input is the output of the layer before the block
                out[first - 1]["save_as"] = f"{tag}_in"
                out[-1]["residual_from"] = f"{tag}_in"
            in_ch = cout
            block += 1
    last = _make_divisible(cfg["last_channels"] * max(1.0, wm))
    conv("conv", "head_conv", last, 1, 1, "relu6")
    out.append(dict(kind="avgpool", name="gap", in_shape=(c, h, w),
                    out_shape=(c, 1, 1), k=1, stride=1, pad=0, act=None,
                    save_as=None, residual_from=None))
    out.append(dict(kind="linear", name="classifier", in_shape=(c, 1, 1),
                    out_shape=(cfg["num_classes"], 1, 1), k=1, stride=1,
                    pad=0, act=None, save_as=None, residual_from=None))
    return out


def weight_shape(layer: dict) -> tuple[int, ...] | None:
    cin, cout = layer["in_shape"][0], layer["out_shape"][0]
    k = layer["k"]
    if layer["kind"] == "conv":
        return (cout, cin, k, k)
    if layer["kind"] == "dwconv":
        return (cout, 1, k, k)
    if layer["kind"] == "linear":
        return (cin, cout)
    return None


@functools.partial(jax.jit, static_argnames=("shapes",))
def _make(seed_words, shapes):
    """Every weight and bias from two random draws (one normal, one
    uniform) sliced per layer: a small program that traces fast and serves
    every seed, since the seed is an argument."""
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
        if len(shape) == 2:             # classifier: no BatchNorm to fold
            b = jnp.zeros((cout,), jnp.float32)
        else:
            gain = gamma[oc:oc + cout] / jnp.sqrt(var[oc:oc + cout])
            w = w * gain.reshape((cout,) + (1,) * (len(shape) - 1))
            b = (bias[oc:oc + cout] - 1.0) * 0.1       # uniform in [-0.05, 0.05)
        params.append((w, b))
        ow, oc = ow + size, oc + cout
    return params


def seed_words(seed: int) -> np.ndarray:
    """A seed of any size as two uint32 words (low, high), so seeds above
    2**32 do not collide with their low 32 bits."""
    seed = int(seed)
    return np.array([seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF], np.uint32)


def make_params(cfg: dict, seed: int) -> list[tuple[np.ndarray, np.ndarray] | None]:
    """Float32 weights and biases of every layer (None for the pool), made
    on the device in one jitted call from the seed and fetched once."""
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
        for key in ("save_as", "residual_from"):
            if lyr[key] is not None:
                op[key] = lyr[key]
        ops.append(op)
    return ops
