"""MobileNetV2's plain reference: its int8 forward pass in straightforward
JAX, from the configuration's rows alone.  It imports nothing of the
system under test.

Sandler et al., "MobileNetV2: Inverted Residuals and Linear Bottlenecks"
(arXiv:1801.04381): Table 2 gives the network, Table 1 and Fig. 4(d) the
bottleneck block.  A row is ``[name, kind, k_h, k_w, c_in, c_out, stride,
in_h, in_w, act, join]``; the wiring is read from the two trailing
columns, never from the names:

* conv / pointwise / depthwise / fc: int8 x int8 products summed exactly
  in int32 (SAME padding, VALID for fc, ``feature_group_count = C`` for a
  depthwise row), then per-output-channel dequantization ``acc *
  (w_scale * act_scale) + bias`` in float32, the row's ``act`` on the
  float (``"relu6"``: clipped to [0, 6.0]; ``"relu"``: max with 0;
  ``"none"``: linear), and requantization ``clip(round(y / act_scale),
  -127, 127)`` to int8;
* ``join`` ``{"skip_from": <row>, "act": <a>}``: the int32 sum of the
  row's int8 output and the int8 input of row ``<row>``, clipped to
  [-127, 127], then ``<a>`` (``"none"`` in MobileNetV2: no activation
  after the add);
* global average pool: the float32 mean over the map, requantized;
* the last row returns its float32 pre-quantization output: the logits.

Departures from the paper: weights and activations are int8 (one
per-tensor activation scale, per-output-channel weight scales) where the
paper trains in float; BatchNorm is folded into random per-channel scales
and biases (the shared draw, ``benchlib/model.py``); there is no dropout
at inference; the skip add is done in int8 at the shared ``act_scale``.

``weight_bits`` < 8 gives the control: every weight tensor rounded to that
many bits, the scale widened to match.
"""
from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from benchlib import work

_ACTS = {"relu": lambda y: jnp.maximum(y, 0.0),
         "relu6": lambda y: jnp.clip(y, 0.0, 6.0),
         "none": lambda y: y}


def _narrow(w, scale, bits):
    if bits >= 8:
        return w, scale
    step = 2 ** (8 - bits)
    hi = 2 ** (bits - 1) - 1
    q = jnp.clip(jnp.round(w.astype(jnp.float32) / step), -hi - 1, hi)
    return q.astype(jnp.int8), scale * step


def _quant(y, act_scale):
    return jnp.clip(jnp.round(y / act_scale), -127, 127).astype(jnp.int8)


def _layer(p, row, x, act_scale, bits):
    """One row: its int8 output and its float32 pre-quantization one."""
    _, kind, _, _, c_in, _, stride, _, _, act, _ = row
    if kind == "gap":
        y = jnp.mean(x.astype(jnp.float32), axis=(1, 2), keepdims=True)
        return _quant(y, act_scale), y
    w, scale = _narrow(p["w"], p["w_scale"], bits)
    acc = jax.lax.conv_general_dilated(
        x, w, window_strides=(stride, stride),
        padding="VALID" if kind == "fc" else "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=c_in if kind == "dwconv" else 1,
        preferred_element_type=jnp.int32)
    y = _ACTS[act](acc.astype(jnp.float32) * (scale * act_scale) + p["bias"])
    return _quant(y, act_scale), y


def forward(params, layers: Sequence[Sequence], x, *, act_scale: float,
            weight_bits: int = 8):
    """Logits [B, classes] float32 for int8 images ``x`` [B, H, W, C]."""
    sources = {row[10]["skip_from"] for row in layers if row[10]}
    inputs = {}
    for row in layers:
        if row[0] in sources:
            inputs[row[0]] = x
        x, y = _layer(params.get(row[0]), row, x, act_scale, weight_bits)
        join = row[10]
        if join:
            s = x.astype(jnp.int32) + inputs[join["skip_from"]].astype(
                jnp.int32)
            x = _ACTS[join["act"]](jnp.clip(s, -127, 127)).astype(jnp.int8)
    return y.reshape(y.shape[0], -1)


def logits_in_blocks(params, layers, images: np.ndarray, *, act_scale: float,
                     block: int, weight_bits: int = 8) -> np.ndarray:
    """``forward`` over ``images`` in fixed blocks of ``block`` images
    (the last one zero-padded), so one compiled shape serves any count."""
    fn = jax.jit(lambda p, x: forward(p, layers, x, act_scale=act_scale,
                                      weight_bits=weight_bits))
    out = []
    for i in range(0, len(images), block):
        chunk = images[i:i + block]
        pad = block - len(chunk)
        if pad:
            chunk = np.concatenate(
                [chunk, np.zeros((pad,) + chunk.shape[1:], chunk.dtype)])
        with jax.default_matmul_precision("highest"):
            y = np.asarray(fn(params, jnp.asarray(chunk)))
        out.append(y[:block - pad])
    return np.concatenate(out)


class DepthwiseLayer(work.Layer):
    """A depthwise row's work counts, for the ``dwconv`` kernel family
    (``"families"`` in the configuration); every other row counts for
    the family of the engine that computes it."""
    family = "dwconv"


def layers_of(table) -> tuple:
    return tuple((DepthwiseLayer if row[1] == "dwconv" else work.Layer)(
        *row[:9]) for row in table)
