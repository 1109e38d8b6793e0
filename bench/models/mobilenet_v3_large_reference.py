"""The plain reference of MobileNetV3-Large: its layer list
(``mobilenet_v3_large.layers``) in straightforward ``jax.numpy``.  It imports
nothing of the program and takes nothing the program made.

* :func:`calibrate` — the float32 forward at ``highest`` matmul precision,
  with the activations as the paper writes them (hard-swish
  ``x * relu6(x + 3) / 6``, hard-sigmoid ``relu6(x + 3) / 6``); the
  per-layer max |activation| gives the activation scales.
* :func:`quantize` and :func:`int_forward` — the W8A8 semantics the
  configuration states (``quantization`` in its file), at any bit width
  (``qmax=127`` the reference, ``qmax=7`` the int4 control).  Conv,
  depthwise, linear, pool and residual steps are the shared reference's
  (``benchlib.reference``).  In the integer epilogue the hard-sigmoid is
  ``clip(y * (1/6), -1/2, 1/2) + 1/2`` (the configuration's
  ``quantization.activations``), and a gate is the exact int32 product of
  the stashed activation and the gate, times ``s_x * s_g / s_out`` in
  float32, rounded and clipped.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchlib import reference as shared

KINDS = ("conv", "dwconv", "linear", "avgpool", "gate")
ACTIVATIONS = (None, "relu", "hswish", "hsigmoid")


def _check(layers):
    for lyr in layers:
        if lyr["kind"] not in KINDS:
            raise ValueError(f"{lyr['name']}: no op kind {lyr['kind']!r} in "
                             f"this reference (it knows {KINDS})")
        if lyr["act"] not in ACTIVATIONS:
            raise ValueError(f"{lyr['name']}: no activation {lyr['act']!r} "
                             f"in this reference (it knows {ACTIVATIONS})")


def _static(layers: list[dict]) -> tuple:
    """The hashable structure of a layer list (a jit static argument)."""
    _check(layers)
    return tuple((lyr["kind"], tuple(lyr["out_shape"]), lyr["k"],
                  lyr["stride"], lyr["pad"], lyr["act"], lyr["save_as"],
                  lyr["residual_from"], lyr["gate_of"]) for lyr in layers)


def _act_float(y, act):
    """The activations as published (float32 forward)."""
    if act == "relu":
        return jnp.maximum(y, 0.0)
    if act == "hsigmoid":
        return jnp.clip(y + 3.0, 0.0, 6.0) / 6.0
    if act == "hswish":
        return y * jnp.clip(y + 3.0, 0.0, 6.0) / 6.0
    return y


def _act_int(y, act):
    """The activations in the integer epilogue's float32 steps."""
    if act == "relu":
        return jnp.maximum(y, 0.0)
    if act == "hsigmoid":
        return jnp.clip(y * (1.0 / 6.0), -0.5, 0.5) + 0.5
    if act == "hswish":
        return y * (jnp.clip(y * (1.0 / 6.0), -0.5, 0.5) + 0.5)
    return y


@functools.partial(jax.jit, static_argnames=("struct",))
def _float_forward(params, x, struct):
    """The float32 logits (B, classes), and max |a| of the input and of
    every layer's output over the batch."""
    cur = x
    stash = {}
    maxes = [jnp.max(jnp.abs(x))]
    for (kind, out_shape, k, s, p, act, save_as, res, gate_of), prm in zip(
            struct, params):
        if kind == "avgpool":
            cur = jnp.mean(cur, axis=(2, 3), keepdims=True)
        elif kind == "gate":
            cur = stash[gate_of] * cur
        elif kind == "linear":
            w, b = prm
            cur = jnp.dot(cur.reshape(cur.shape[0], -1), w,
                          precision=shared.HIGHEST) + b
            cur = _act_float(cur, act).reshape(cur.shape[0], *out_shape)
        else:
            w, b = prm
            groups = cur.shape[1] if kind == "dwconv" else 1
            cur = shared._conv(cur, w, s, p, groups, shared.HIGHEST)
            cur = _act_float(cur + b[:, None, None], act)
        if res is not None:
            cur = cur + stash[res]
        if save_as is not None:
            stash[save_as] = cur
        maxes.append(jnp.max(jnp.abs(cur)))
    return cur.reshape(cur.shape[0], -1), jnp.stack(maxes)


def forward_float(layers, params, x) -> np.ndarray:
    """The float32 logits (B, classes) at ``highest`` precision."""
    logits, _ = _float_forward(params, jnp.asarray(x, jnp.float32),
                               struct=_static(layers))
    return np.asarray(logits)


def calibrate(layers, params, calib, qmax: int = 127) -> list[float]:
    """Per-tensor activation scales: max |a| over the calibration inputs,
    / qmax, floored at 1e-12 (float64, input scale first)."""
    _, maxes = _float_forward(params, jnp.asarray(calib, jnp.float32),
                              struct=_static(layers))
    maxes = np.asarray(maxes, np.float32).astype(np.float64)
    return list(np.maximum(maxes, 1e-12) / float(qmax))


def _shared_view(layers):
    """The layers as the shared ``quantize`` reads them: its weight, bias,
    pool and residual constants do not depend on the activation, and a
    gate (no weights) stands in as a pool over its (C, 1, 1) input."""
    return [dict(lyr, act=None, kind="avgpool") if lyr["kind"] == "gate"
            else dict(lyr, act=None) for lyr in layers]


def quantize(layers, params, scales, qmax: int = 127) -> dict:
    """The shared reference's integer weights and epilogue constants, plus
    each gate's one float32 multiplier ``s_x * s_g / s_out`` (float64, then
    float32), where ``s_x`` is the stashed activation's scale and ``s_g``
    the gate's input scale."""
    _check(layers)
    q = shared.quantize(_shared_view(layers), params, scales, qmax)
    out_scale_of = {lyr["save_as"]: float(scales[i + 1])
                    for i, lyr in enumerate(layers) if lyr["save_as"]}
    for i, lyr in enumerate(layers):
        if lyr["kind"] == "gate":
            ql = dict(q["layers"][i])
            del ql["pool"]
            s_x, s_g, s_out = (out_scale_of[lyr["gate_of"]],
                               float(scales[i]), float(scales[i + 1]))
            ql["gate"] = np.float32(s_x * s_g / s_out)
            q["layers"][i] = ql
    return q


@functools.partial(jax.jit, static_argnames=("struct", "qmax"))
def _int_forward(q, x, struct, qmax):
    cur = shared._requant(x, q["inv_in"], qmax)
    stash = {}
    for (kind, out_shape, k, s, p, act, save_as, res, gate_of), ql in zip(
            struct, q["layers"]):
        if kind == "avgpool":
            tot = jnp.sum(cur, axis=(2, 3), keepdims=True)
            cur = shared._requant(tot.astype(jnp.float32), ql["pool"], qmax)
        elif kind == "gate":
            prod = stash[gate_of] * cur
            cur = shared._requant(prod.astype(jnp.float32), ql["gate"], qmax)
        else:
            x8 = cur.astype(jnp.int8)
            if kind == "linear":
                acc = jnp.dot(x8.reshape(x8.shape[0], -1), ql["w"],
                              preferred_element_type=jnp.int32)
                mult, bias = ql["m"], ql["b"]
            else:
                if kind == "dwconv":
                    acc = shared._dw_int(cur, ql["w"], s, p)
                else:
                    acc = shared._conv(x8, ql["w"], s, p, out=jnp.int32)
                mult, bias = ql["m"][:, None, None], ql["b"][:, None, None]
            y = (acc + bias).astype(jnp.float32) * mult
            cur = shared._requant(_act_int(y, act), ql["inv_out"], qmax)
            cur = cur.reshape(cur.shape[0], *out_shape)
        if res is not None:
            r = jnp.round(stash[res].astype(jnp.float32) * ql["res"])
            cur = jnp.clip(cur + r.astype(jnp.int32), -qmax, qmax)
        if save_as is not None:
            stash[save_as] = cur
    return cur.reshape(cur.shape[0], -1)


def int_forward(layers, q, x, chunk: int = 16) -> np.ndarray:
    """Integer logits (B, classes) as int32, in chunks of ``chunk`` rows so
    a large sample fits beside whatever else the device holds."""
    arrays = jax.device_put({"inv_in": q["inv_in"], "layers": q["layers"]})
    struct = _static(layers)
    x = np.asarray(x, np.float32)
    outs = []
    for i in range(0, len(x), chunk):
        xb = x[i:i + chunk]
        n = len(xb)
        if n < chunk and len(x) > chunk:
            xb = np.concatenate([xb, np.zeros((chunk - n, *xb.shape[1:]),
                                              np.float32)])
        outs.append(np.asarray(_int_forward(arrays, jnp.asarray(xb),
                                            struct=struct,
                                            qmax=q["qmax"]))[:n])
    return np.concatenate(outs)
