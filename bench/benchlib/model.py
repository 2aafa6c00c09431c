"""Weights and images from the seed, made by the benchmark itself.

Weights are made on the device in one jitted call, in the types they are
served in: int8 kernels, float32 per-output-channel scales and biases.
Scales are drawn per channel around ``gain / (73.3 * sqrt(fan_in))``, 73.3
being the RMS of a uniform int8 weight, so activations keep roughly their
size from layer to layer instead of all saturating at +-127 or all
rounding to 0; random per-channel scales and biases also make the
comparison with the reference sensitive to a channel out of place.
"""
from __future__ import annotations

from typing import Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from benchlib.work import POOL_KINDS

WEIGHT_RMS = 73.3
GAIN = 1.5


def seed_key(seed: int):
    """A JAX key for any non-negative seed, 64-bit ones included."""
    return jax.random.PRNGKey(seed % (2 ** 63))


def weight_shapes(layers: Sequence[Sequence]) -> Dict[str, tuple]:
    out = {}
    for name, kind, k_h, k_w, c_in, c_out, *_ in layers:
        if kind in POOL_KINDS:
            continue
        out[name] = ((k_h, k_w, 1, c_in) if kind == "dwconv"
                     else (k_h, k_w, c_in, c_out))
    return out


def make_params(key, layers: Sequence[Sequence], act_scale: float):
    """``{layer: {"w": int8 HWIO, "w_scale": f32[C], "bias": f32[C]}}``
    for every weighted layer, in one jitted device call.  All kernels are
    slices of one flat draw, and all scales and biases of two more: one
    random-number program per kind, whatever the depth, compiles fast."""
    shapes = weight_shapes(layers)
    sizes = [int(np.prod(s)) for s in shapes.values()]
    chans = [s[3] for s in shapes.values()]

    def build(key):
        kw, ks, kb = jax.random.split(key, 3)
        flat_w = jax.random.randint(kw, (sum(sizes),), -127, 128, jnp.int8)
        flat_u = jax.random.uniform(ks, (sum(chans),), jnp.float32, 0.5, 1.5)
        flat_b = jax.random.normal(kb, (sum(chans),), jnp.float32)
        params, w_at, c_at = {}, 0, 0
        for (name, shape), n, c in zip(shapes.items(), sizes, chans):
            fan_in = shape[0] * shape[1] * shape[2]
            params[name] = {
                "w": flat_w[w_at:w_at + n].reshape(shape),
                "w_scale": flat_u[c_at:c_at + c]
                * (GAIN / (WEIGHT_RMS * fan_in ** 0.5)),
                "bias": flat_b[c_at:c_at + c] * (4.0 * act_scale),
            }
            w_at, c_at = w_at + n, c_at + c
        return params

    return jax.jit(build)(key)


def image_pool(seed: int, count: int, shape: Sequence[int]) -> np.ndarray:
    """``count`` distinct int8 images [count, H, W, C] on the host."""
    rng = np.random.default_rng([seed, 1])
    return rng.integers(-127, 128, (count,) + tuple(shape), dtype=np.int8)
