"""The single requantization epilogue every int8 layer engine shares, and
the int8 join of a residual block.

HPIPE's layer contract (models/cnn.py): int32 conv/matmul accumulator ->
per-output-channel dequant + bias -> activation -> requantize to int8
for the next engine.  Bit-identity between the functional reference, the
Pallas conv engines, and the fc matmul path depends on all of them running
THIS function (inside their own jit) — round-to-nearest ties flip if the
float ops are duplicated and drift apart.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def requant_epilogue(y, w_scale, bias, act_scale: float = 0.05,
                     act: str = "relu"):
    """y: int32 accumulator [..., C_out].  ``act`` is ``"relu"``,
    ``"relu6"`` (the float clipped to [0, 6.0] before it is rounded) or
    ``"none"``.  Returns (int8 requantized, float32 pre-quant
    activations)."""
    y = y.astype(jnp.float32) * (w_scale * act_scale) + bias
    if act == "relu":
        y = jax.nn.relu(y)
    elif act == "relu6":
        y = jnp.clip(y, 0.0, 6.0)
    elif act != "none":
        raise ValueError(f"unknown activation {act!r}")
    y_q = jnp.clip(jnp.round(y / act_scale), -127, 127).astype(jnp.int8)
    return y_q, y


def residual_join(h, identity, join: str):
    """A residual block's join: the int32 add of the main path ``h`` and
    the ``identity``, clipped to int8, then ReLU (``join="relu"``,
    ResNet) or nothing (``"none"``, MobileNetV2's linear inverted
    residuals)."""
    y = jnp.clip(h.astype(jnp.int32) + identity.astype(jnp.int32),
                 -127, 127).astype(jnp.int8)
    if join == "relu":
        return jnp.where(y > 0, y, 0)                       # relu on int8
    if join != "none":
        raise ValueError(f"unknown join {join!r}")
    return y
