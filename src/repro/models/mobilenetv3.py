"""MobileNetV3-Large (Howard et al., "Searching for MobileNetV3",
arXiv:1905.02244, Table 1) as a reinterpreted layer list, with conv+BN+
activation pre-fused (BN folded into conv weights/bias, as for MobileNetV2).

Each bottleneck block is expand 1x1 -> depthwise k x k -> [squeeze-excite]
-> project 1x1, with a residual add where the stride is 1 and the widths
match.  The squeeze-excite branch is written in the IR's own ops: the
depthwise output is stashed (``save_as``), then global pool -> linear to
``make_divisible(expanded / 4, 8)`` + ReLU -> linear back + hard-sigmoid ->
``gate`` (the stashed activation times the per-channel factor).  Blocks 1-6
use ReLU, 7-15 and the stem and head use hard-swish.

Departures from the paper: weights are random (the splitting machinery is
weight-agnostic), BatchNorm is folded, and the classifier's dropout is left
out (inference).
"""
from __future__ import annotations

import numpy as np

from ..core.reinterpret import ReinterpretedModel, trace_sequential
from .mobilenetv2 import _fused_conv_weight, _make_divisible

# (kernel, expanded, out, squeeze-excite, activation, stride) — Table 1
_LARGE = dict(
    stem=16,
    blocks=[(3, 16, 16, False, "relu", 1),
            (3, 64, 24, False, "relu", 2),
            (3, 72, 24, False, "relu", 1),
            (5, 72, 40, True, "relu", 2),
            (5, 120, 40, True, "relu", 1),
            (5, 120, 40, True, "relu", 1),
            (3, 240, 80, False, "hswish", 2),
            (3, 200, 80, False, "hswish", 1),
            (3, 184, 80, False, "hswish", 1),
            (3, 184, 80, False, "hswish", 1),
            (3, 480, 112, True, "hswish", 1),
            (3, 672, 112, True, "hswish", 1),
            (5, 672, 160, True, "hswish", 2),
            (5, 960, 160, True, "hswish", 1),
            (5, 960, 160, True, "hswish", 1)],
    head_conv=960,
    head_fc=1280)

# a 5x5 stride-2 block with a gate, a gated block with a residual, both
# activations, at CPU-test size
_SMOKE = dict(
    stem=8,
    blocks=[(3, 8, 8, False, "relu", 1),
            (5, 24, 16, True, "relu", 2),
            (5, 32, 16, True, "hswish", 1),
            (3, 48, 24, False, "hswish", 2)],
    head_conv=48,
    head_fc=64)


def _linear(rng, fin, fout, name, activation):
    w = rng.standard_normal((fin, fout)).astype(np.float32) * np.sqrt(2.0 / fin)
    b = rng.uniform(-0.1, 0.1, fout).astype(np.float32)
    return dict(kind="linear", name=name, features=fout, weight=w, bias=b,
                activation=activation)


def _conv(rng, kind, name, cin, cout, k, stride, activation):
    w, b = _fused_conv_weight(rng, cout, 1 if kind == "dwconv" else cin, k)
    op = dict(kind=kind, name=name, kernel=(k, k), stride=(stride, stride),
              padding=(k // 2, k // 2), weight=w, bias=b,
              activation=activation)
    if kind == "conv":
        op["out_channels"] = cout
    return op


def mobilenet_v3_large(input_hw: tuple[int, int] = (224, 224),
                       num_classes: int = 1000, seed: int = 0,
                       cfg: dict | None = None) -> ReinterpretedModel:
    rng = np.random.default_rng(seed)
    cfg = cfg or _LARGE
    in_ch = cfg["stem"]
    ops: list[dict] = [_conv(rng, "conv", "stem", 3, in_ch, 3, 2, "hswish")]
    for block, (k, exp, cout, se, act, s) in enumerate(cfg["blocks"]):
        tag = f"b{block}"
        first = len(ops)
        if exp != in_ch:
            ops.append(_conv(rng, "conv", f"{tag}_expand", in_ch, exp, 1, 1,
                             act))
        ops.append(_conv(rng, "dwconv", f"{tag}_dw", exp, exp, k, s, act))
        if se:
            ops[-1]["save_as"] = f"{tag}_se"
            r = _make_divisible(exp / 4)
            ops += [dict(kind="avgpool", name=f"{tag}_se_pool"),
                    _linear(rng, exp, r, f"{tag}_se_reduce", "relu"),
                    _linear(rng, r, exp, f"{tag}_se_expand", "hsigmoid"),
                    dict(kind="gate", name=f"{tag}_se_gate",
                         gate_of=f"{tag}_se")]
        ops.append(_conv(rng, "conv", f"{tag}_project", exp, cout, 1, 1, None))
        if s == 1 and in_ch == cout:
            # the block input is the output of the op before the block
            ops[first - 1]["save_as"] = f"{tag}_in"
            ops[-1]["residual_from"] = f"{tag}_in"
        in_ch = cout
    ops.append(_conv(rng, "conv", "head_conv", in_ch, cfg["head_conv"], 1, 1,
                     "hswish"))
    ops.append(dict(kind="avgpool", name="gap"))
    ops.append(_linear(rng, cfg["head_conv"], cfg["head_fc"], "head_fc",
                       "hswish"))
    ops.append(_linear(rng, cfg["head_fc"], num_classes, "classifier", None))
    return trace_sequential(ops, (3, *input_hw), rng=rng)


def mobilenet_v3_large_smoke(seed: int = 0) -> ReinterpretedModel:
    """Reduced config (same block kinds) for CPU tests."""
    return mobilenet_v3_large(input_hw=(32, 32), num_classes=10, seed=seed,
                              cfg=_SMOKE)
