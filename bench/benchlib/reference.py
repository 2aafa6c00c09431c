"""The plain reference: an int8 CNN forward pass in straightforward JAX.

Written from the layer table of a configuration file alone; it imports
nothing of the system under test.  The arithmetic is the int8 layer
contract the configurations state:

* conv / pointwise / fc: int8 x int8 products summed exactly in int32
  (SAME padding, VALID for fc), then per-output-channel dequantization
  ``acc * (w_scale * act_scale) + bias`` in float32, relu where the layer
  has one, and requantization ``clip(round(y / act_scale), -127, 127)``
  to int8 for the next layer;
* maxpool: the maximum over a SAME-padded window (padding never wins);
* global average pool: the float32 mean over the map, requantized;
* a residual block ``s{i}b{j}``: its convs in order (the last without
  relu), the optional ``ds`` conv on the identity path (no relu), an
  int32 add clipped to [-127, 127] and a relu;
* the last layer returns its float32 pre-quantization output: the
  logits.

``weight_bits`` < 8 gives the control: the same network with every weight
tensor rounded to that many bits (the scale widened to match), the
nearest lower precision a later change could be tempted to serve.
"""
from __future__ import annotations

import re
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

_BLOCK = re.compile(r"^(s\d+b\d+)(c\d+|ds)$")


def _requant(acc, w_scale, bias, act_scale, relu):
    y = acc.astype(jnp.float32) * (w_scale * act_scale) + bias
    if relu:
        y = jnp.maximum(y, 0.0)
    return jnp.clip(jnp.round(y / act_scale), -127, 127).astype(jnp.int8), y


def _narrow(w, scale, bits):
    """Round an int8 weight tensor to ``bits`` bits: returns the narrowed
    integers (still int8 storage) and the widened per-channel scale."""
    if bits >= 8:
        return w, scale
    step = 2 ** (8 - bits)
    hi = 2 ** (bits - 1) - 1
    q = jnp.clip(jnp.round(w.astype(jnp.float32) / step), -hi - 1, hi)
    return q.astype(jnp.int8), scale * step


def _conv(p, row, x, act_scale, relu, bits):
    name, kind, k_h, k_w, c_in, c_out, stride, in_h, in_w = row
    w, scale = _narrow(p["w"], p["w_scale"], bits)
    acc = jax.lax.conv_general_dilated(
        x, w, window_strides=(stride, stride),
        padding="VALID" if kind == "fc" else "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=c_in if kind == "dwconv" else 1,
        preferred_element_type=jnp.int32)
    return _requant(acc, scale, p["bias"], act_scale, relu)


def _maxpool(row, x):
    k, stride = row[2], row[6]
    y = jax.lax.reduce_window(x.astype(jnp.int32), jnp.int32(-2 ** 31),
                              jax.lax.max, (1, k, k, 1),
                              (1, stride, stride, 1), "SAME")
    return y.astype(jnp.int8)


def _gap(x, act_scale):
    m = jnp.mean(x.astype(jnp.float32), axis=(1, 2), keepdims=True)
    return jnp.clip(jnp.round(m / act_scale), -127, 127).astype(jnp.int8)


def units(layers: Sequence[Sequence]) -> List[Tuple[str, list]]:
    """The layer table grouped into ``("layer", [row])`` and
    ``("block", [rows])`` units, a block being the consecutive rows that
    share one ``s{i}b{j}`` prefix."""
    out: List[Tuple[str, list]] = []
    for row in layers:
        m = _BLOCK.match(row[0])
        if m and out and out[-1][0] == "block" \
                and _BLOCK.match(out[-1][1][0][0]).group(1) == m.group(1):
            out[-1][1].append(row)
        elif m:
            out.append(("block", [row]))
        else:
            out.append(("layer", [row]))
    return out


def forward(params: Dict, layers: Sequence[Sequence], x, *,
            act_scale: float, weight_bits: int = 8):
    """Logits [B, classes] float32 for int8 images ``x`` [B, H, W, C]."""
    table = units(layers)
    last = layers[-1][0]
    for kind, rows in table:
        if kind == "block":
            convs = [r for r in rows if not r[0].endswith("ds")]
            ds = [r for r in rows if r[0].endswith("ds")]
            h = x
            for i, r in enumerate(convs):
                h, _ = _conv(params[r[0]], r, h, act_scale,
                             i < len(convs) - 1, weight_bits)
            ident = x
            if ds:
                ident, _ = _conv(params[ds[0][0]], ds[0], x, act_scale,
                                 False, weight_bits)
            y = jnp.clip(h.astype(jnp.int32) + ident.astype(jnp.int32),
                         -127, 127)
            x = jnp.maximum(y, 0).astype(jnp.int8)
            continue
        row = rows[0]
        if row[1] == "maxpool":
            x = _maxpool(row, x)
        elif row[1] == "gap":
            x = _gap(x, act_scale)
        else:
            is_last = row[0] == last
            x, y = _conv(params[row[0]], row, x, act_scale, not is_last,
                         weight_bits)
            if is_last:
                return y.reshape(y.shape[0], -1)
    raise ValueError("the layer table does not end in an fc layer")


def logits_in_blocks(params, layers, images: np.ndarray, *, act_scale: float,
                     block: int, weight_bits: int = 8) -> np.ndarray:
    """``forward`` over ``images`` in fixed blocks of ``block`` images
    (the last one zero-padded), so one compiled shape serves any count
    and the reference never holds more than a block's activations."""
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
