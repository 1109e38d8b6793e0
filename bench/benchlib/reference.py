"""The plain reference: the configuration's CNN in straightforward
``jax.numpy``, over the architecture module's layer list.  It imports
nothing of the program and takes nothing the program made.

* :func:`calibrate` — a float32 forward pass at ``highest`` matmul
  precision; the per-layer max |activation| gives the activation scales.
* :func:`int_forward` — the W8A8 semantics the configuration states
  (``quantization`` in its file), at any bit width: ``qmax=127`` is the
  reference, ``qmax=7`` (int4) is the control, the nearest lower precision.

Weights and scales are arguments of the jitted functions, never constants,
so one compiled program serves every seed.  Quantizing the weights and
deriving the epilogue constants happens on the host in numpy, in the same
float32 / float64 steps the configuration states.

It knows the op kinds conv, dwconv, linear and avgpool and the activations
None, relu and relu6, and raises ``ValueError`` on any other: an
architecture built from more brings its own reference (see
``spec.Benchmark.reference``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
KINDS = ("conv", "dwconv", "linear", "avgpool")
ACTIVATIONS = (None, "relu", "relu6")


def _unknown(what: str, value, known: tuple) -> ValueError:
    return ValueError(f"the shared reference has no {what} {value!r} (it "
                      f"knows {known}); the architecture module names the "
                      "REFERENCE module that computes it")


def _static(layers: list[dict]) -> tuple:
    """The hashable structure of a layer list (a jit static argument)."""
    return tuple((lyr["kind"], tuple(lyr["in_shape"]), tuple(lyr["out_shape"]),
                  lyr["k"], lyr["stride"], lyr["pad"], lyr["act"],
                  lyr["save_as"], lyr["residual_from"]) for lyr in layers)


def _act(y, act):
    if act == "relu6":
        return jnp.clip(y, 0.0, 6.0)
    if act == "relu":
        return jnp.maximum(y, 0.0)
    if act is None:
        return y
    raise _unknown("activation", act, ACTIVATIONS)


def _conv(x, w, stride, pad, groups=1, precision=None, out=None):
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
        feature_group_count=groups, precision=precision,
        preferred_element_type=out)


@functools.partial(jax.jit, static_argnames=("struct",))
def _activation_maxes(params, x, struct):
    """max |a| of the input and of every layer's output over the batch."""
    cur = x
    stash = {}
    maxes = [jnp.max(jnp.abs(x))]
    for (kind, _, out_shape, k, s, p, act, save_as, res), prm in zip(
            struct, params):
        if kind == "avgpool":
            cur = jnp.mean(cur, axis=(2, 3), keepdims=True)
        elif kind == "linear":
            w, b = prm
            cur = jnp.dot(cur.reshape(cur.shape[0], -1), w,
                          precision=HIGHEST) + b
            cur = _act(cur, act).reshape(cur.shape[0], *out_shape)
        elif kind in ("conv", "dwconv"):
            w, b = prm
            groups = cur.shape[1] if kind == "dwconv" else 1
            cur = _conv(cur, w, s, p, groups, HIGHEST) + b[:, None, None]
            cur = _act(cur, act)
        else:
            raise _unknown("op kind", kind, KINDS)
        if res is not None:
            cur = cur + stash[res]
        if save_as is not None:
            stash[save_as] = cur
        maxes.append(jnp.max(jnp.abs(cur)))
    return jnp.stack(maxes)


def calibrate(layers, params, calib, qmax: int = 127) -> list[float]:
    """Per-tensor activation scales: max |a| over the calibration inputs,
    / qmax, floored at 1e-12 (float64, input scale first).  ``params[i]`` is
    (w, b), or None for the pool."""
    maxes = _activation_maxes(params, jnp.asarray(calib, jnp.float32),
                              struct=_static(layers))
    maxes = np.asarray(maxes, np.float32).astype(np.float64)
    return list(np.maximum(maxes, 1e-12) / float(qmax))


def quantize(layers, params, scales, qmax: int = 127) -> dict:
    """Integer weights and epilogue constants, on the host.

    Weights: per output channel, ``s_w = max(max|w|, 1e-12) / qmax`` in
    float32, ``w_q = clip(round(w / s_w))``.  Bias: ``round(b / (s_in *
    s_w))`` in float64.  Multiplier ``s_in * s_w`` and ``1 / s_out`` in
    float32.  Residual and pooling rescales are one float32 multiply."""
    layer_q = []
    scale_of = {}
    for i, (lyr, prm) in enumerate(zip(layers, params)):
        if lyr["kind"] not in KINDS:
            raise _unknown("op kind", lyr["kind"], KINDS)
        if lyr["act"] not in ACTIVATIONS:
            raise _unknown("activation", lyr["act"], ACTIVATIONS)
        s_in, s_out = float(scales[i]), float(scales[i + 1])
        if lyr["save_as"] is not None:
            scale_of[lyr["save_as"]] = s_out
        q = {"inv_out": np.float32(1.0 / s_out)}
        if prm is not None:
            w, b = (np.asarray(a, np.float32) for a in prm)
            axis = 1 if lyr["kind"] == "linear" else 0
            other = tuple(a for a in range(w.ndim) if a != axis)
            s_w = (np.maximum(np.max(np.abs(w), axis=other), np.float32(1e-12))
                   / np.float32(qmax))
            shape = [1] * w.ndim
            shape[axis] = -1
            q["w"] = np.clip(np.round(w / s_w.reshape(shape)),
                             -qmax, qmax).astype(np.int8)
            s_w = s_w.astype(np.float64)
            q["b"] = np.round(b.astype(np.float64) / (s_in * s_w)).astype(
                np.int64).astype(np.int32)
            q["m"] = (s_in * s_w).astype(np.float32)
        if lyr["kind"] == "avgpool":
            _, h, w_ = lyr["in_shape"]
            q["pool"] = np.float32(s_in / (h * w_ * s_out))
        if lyr["residual_from"] is not None:
            q["res"] = np.float32(scale_of[lyr["residual_from"]] / s_out)
        layer_q.append(q)
    return {"inv_in": np.float32(1.0 / float(scales[0])), "layers": layer_q,
            "out_scale": float(scales[-1]), "qmax": int(qmax)}


def _requant(y, inv_out, qmax):
    return jnp.clip(jnp.round(y * inv_out), -qmax, qmax).astype(jnp.int32)


def _dw_int(x, w, stride, pad):
    """Depthwise conv as kh*kw shifted int32 products (exact)."""
    b, c, h, w_ = x.shape
    k = w.shape[-1]
    xp = jnp.pad(x, ((0, 0), (0, 0), (pad, pad), (pad, pad)))
    oh = (h + 2 * pad - k) // stride + 1
    ow = (w_ + 2 * pad - k) // stride + 1
    acc = jnp.zeros((b, c, oh, ow), jnp.int32)
    wi = w.astype(jnp.int32)
    for i in range(k):
        for j in range(k):
            win = jax.lax.slice(
                xp, (0, 0, i, j),
                (b, c, i + (oh - 1) * stride + 1, j + (ow - 1) * stride + 1),
                (1, 1, stride, stride))
            acc = acc + win * wi[:, 0, i, j][None, :, None, None]
    return acc


@functools.partial(jax.jit, static_argnames=("struct", "qmax"))
def _int_forward(q, x, struct, qmax):
    cur = _requant(x, q["inv_in"], qmax)
    stash = {}
    for (kind, _, out_shape, k, s, p, act, save_as, res), ql in zip(
            struct, q["layers"]):
        if kind == "avgpool":
            tot = jnp.sum(cur, axis=(2, 3), keepdims=True)
            cur = _requant(tot.astype(jnp.float32), ql["pool"], qmax)
        elif kind in ("conv", "dwconv", "linear"):
            x8 = cur.astype(jnp.int8)
            if kind == "linear":
                acc = jnp.dot(x8.reshape(x8.shape[0], -1), ql["w"],
                              preferred_element_type=jnp.int32)
                mult = ql["m"]
                bias = ql["b"]
            else:
                if kind == "dwconv":
                    acc = _dw_int(cur, ql["w"], s, p)
                else:
                    acc = _conv(x8, ql["w"], s, p, out=jnp.int32)
                mult = ql["m"][:, None, None]
                bias = ql["b"][:, None, None]
            y = (acc + bias).astype(jnp.float32) * mult
            cur = _requant(_act(y, act), ql["inv_out"], qmax)
            cur = cur.reshape(cur.shape[0], *out_shape)
        else:
            raise _unknown("op kind", kind, KINDS)
        if res is not None:
            r = jnp.round(stash[res].astype(jnp.float32) * ql["res"])
            cur = jnp.clip(cur + r.astype(jnp.int32), -qmax, qmax)
        if save_as is not None:
            stash[save_as] = cur
    return cur.reshape(cur.shape[0], -1)


def int_forward(layers, q, x, chunk: int = 64) -> np.ndarray:
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


def logit_gap_lsb(cand_q, cand_scale, ref_q, ref_scale) -> float:
    """Widest gap between a candidate's dequantized logits and the
    reference's, in units of the reference's output step (its LSB)."""
    cand = np.asarray(cand_q, np.float64).reshape(len(cand_q), -1) * cand_scale
    ref = np.asarray(ref_q, np.float64).reshape(len(ref_q), -1) * ref_scale
    return float(np.max(np.abs(cand - ref)) / ref_scale) if len(ref) else 0.0
