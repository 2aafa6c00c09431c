"""A configuration's own reference that disagrees with the program: the
shared reference with 0.5 added to the first logit of every image."""
from benchlib import reference


def logits_in_blocks(params, layers, images, *, act_scale, block,
                     weight_bits=8):
    y = reference.logits_in_blocks(params, layers, images,
                                   act_scale=act_scale, block=block,
                                   weight_bits=weight_bits)
    y[:, 0] += 0.5
    return y
