"""Post-training int8 quantization (paper §V.D).

Weights: symmetric per-output-channel int8.  Activations: symmetric
per-tensor int8, calibrated from a float forward pass over calibration
inputs (max-abs).  Accumulation in int32, requantization to the next layer's
activation scale — matching an integer-arithmetic-only MCU runtime
(Jacob et al., CVPR'18, the paper's [2]).
"""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np

from .fusion import apply_activation
from .reinterpret import ReinterpretedModel


@dataclasses.dataclass
class QuantizedLayer:
    w_q: np.ndarray | None          # int8, same layout as LayerSpec.weight
    w_scale: np.ndarray | None      # per-output-channel float scale
    b_q: np.ndarray | None          # int32 bias at scale (s_in * s_w)
    in_scale: float                 # activation scale feeding this layer
    out_scale: float                # activation scale of this layer's output


@dataclasses.dataclass
class QuantizedModel:
    model: ReinterpretedModel
    layers: list[QuantizedLayer]
    input_scale: float


def quantize_tensor_per_channel(w: np.ndarray, channel_axis: int) -> tuple[np.ndarray, np.ndarray]:
    mx = np.max(np.abs(w), axis=tuple(i for i in range(w.ndim) if i != channel_axis))
    scale = np.maximum(mx, 1e-12) / 127.0
    shape = [1] * w.ndim
    shape[channel_axis] = -1
    q = np.clip(np.round(w / scale.reshape(shape)), -127, 127).astype(np.int8)
    return q, scale.astype(np.float64)


def quantize_activation(x: np.ndarray, scale: float) -> np.ndarray:
    return np.clip(np.round(x / scale), -127, 127).astype(np.int8)


def dequantize(q: np.ndarray, scale) -> np.ndarray:
    return q.astype(np.float32) * np.asarray(scale, dtype=np.float32)


def calibrate_scales(model: ReinterpretedModel, calib_inputs: list[np.ndarray],
                     forward_fn) -> list[float]:
    """Max-abs activation scale per layer boundary.  ``forward_fn(model, x)``
    must return the list of post-activation outputs per layer (float path)."""
    n_layers = len(model.layers)
    maxes = np.zeros(n_layers + 1)
    for x in calib_inputs:
        maxes[0] = max(maxes[0], float(np.max(np.abs(x))))
        acts = forward_fn(model, x)
        for i, a in enumerate(acts):
            maxes[i + 1] = max(maxes[i + 1], float(np.max(np.abs(a))))
    return list(np.maximum(maxes, 1e-12) / 127.0)


def quantize_model(model: ReinterpretedModel, act_scales: list[float]) -> QuantizedModel:
    """act_scales: length n_layers+1 (input scale followed by per-layer output
    scales) from :func:`calibrate_scales`."""
    assert len(act_scales) == len(model.layers) + 1
    qlayers: list[QuantizedLayer] = []
    for i, layer in enumerate(model.layers):
        s_in, s_out = act_scales[i], act_scales[i + 1]
        if layer.weight is None:
            qlayers.append(QuantizedLayer(None, None, None, s_in, s_out))
            continue
        ch_axis = 0 if layer.kind in ("conv", "dwconv") else 1
        w_q, w_s = quantize_tensor_per_channel(layer.weight, ch_axis)
        bias = layer.bias if layer.bias is not None else np.zeros(
            layer.weight.shape[ch_axis], np.float32)
        b_q = np.round(bias / (s_in * w_s)).astype(np.int64)
        qlayers.append(QuantizedLayer(w_q, w_s, b_q, s_in, s_out))
    return QuantizedModel(model, qlayers, act_scales[0])


def epilogue_params(ql: QuantizedLayer) -> tuple[np.ndarray, np.ndarray]:
    """The int8 layer's fused-epilogue constants: the float32 per-channel
    dequant multiplier ``scale = s_in * w_scale`` and the int32 bias ``b_q``
    (already at accumulator scale).

    The epilogue contract — shared bit-for-bit by the eager executor, the
    compiled jnp path and the Pallas kernels — is

        y_real = f32(acc_i32 + b_q) * scale            # one f32 multiply
        q_out  = clip(round(y_real * (1 / out_scale)))  # one f32 multiply

    The bias is added in exact int32 arithmetic and every float step is a
    *multiply*: float adds are deliberately avoided because XLA contracts
    ``a*b + c`` into an FMA inside large fused graphs (jit) but not in
    op-by-op dispatch, which flips requantization rounding at ties.  With
    multiplies only, eager and jitted execution round identically.

    Two more rules keep that true for the activations.  An add is allowed
    only where its operand is not a product: the hard sigmoid's ``+ 3`` is
    written ``clip(y * (1/6), -1/2, 1/2) + 1/2`` (``fusion.apply_activation``),
    so the add follows a clip.  And no two multiplies by constants may
    follow each other: XLA folds ``(y * c1) * c2`` into ``y * (c1 * c2)``
    under jit but not op by op, so the 1/6 sits inside the clip, where a
    non-constant (the clip) separates it from ``1 / out_scale``.

    The channel gate (``gate``, coordinator-side) keeps to the same form as
    the pool and the residual add: the exact int32 product of the stashed
    activation and the gate, one f32 multiply by ``s_x * s_g / s_out``, round
    and clip (``executor._gate_int8``).
    """
    m = (ql.in_scale * ql.w_scale).astype(np.float32)
    return m, ql.b_q.astype(np.int32)


def requantize(acc_i32, scale, out_scale: float, activation: str | None):
    """Biased int32 accumulator -> int8 output at ``out_scale`` (jnp,
    on-device).  ``scale`` is the float32 multiplier array from
    :func:`epilogue_params`, broadcastable against ``acc_i32`` (per leading
    channel for (C, H, W) accumulators, per position for flat accumulators).
    See :func:`epilogue_params` for the exactness contract.
    """
    y = acc_i32.astype(jnp.float32) * scale
    y = apply_activation(y, activation)
    return jnp.clip(jnp.round(y * (1.0 / float(out_scale))),
                    -127, 127).astype(jnp.int8)


def quantize_activation_jnp(x, scale: float):
    """jnp counterpart of :func:`quantize_activation` (float32
    multiply-by-reciprocal — see :func:`epilogue_params` for why) — used
    on-device by both executors so the eager and compiled int8 paths round
    identically."""
    x = jnp.asarray(x, jnp.float32)
    return jnp.clip(jnp.round(x * (1.0 / float(scale))),
                    -127, 127).astype(jnp.int8)
