"""A configuration's own weights: the shared ones plus a ``mark`` that its
``logits_in_blocks`` insists on, so a test sees both functions called on
the same parameters."""
import jax.numpy as jnp

from benchlib import model, reference

MARK = 12345.0


def make_params(key, layers, act_scale):
    params = model.make_params(key, layers, act_scale)
    params["mark"] = jnp.float32(MARK)
    return params


def logits_in_blocks(params, layers, images, *, act_scale, block,
                     weight_bits=8):
    if float(params.get("mark", 0.0)) != MARK:
        raise ValueError("the parameters are not this module's")
    rest = {k: v for k, v in params.items() if k != "mark"}
    return reference.logits_in_blocks(rest, layers, images,
                                      act_scale=act_scale, block=block,
                                      weight_bits=weight_bits)
