"""The algorithm's minimal work, from a configuration's layer table.

Counted for the published layer, never for an implementation: int8
multiply-accumulates (2 operations each), and int8 input, weights and
output each moved once.  Padding rows, int32 intermediates and weights
re-read per output row are the implementation's, and are not counted, so
a share of a roofline computed from these numbers cannot pass 100%.

A row of the table begins ``[name, kind, k_h, k_w, c_in, c_out, stride,
in_h, in_w]``; outputs are SAME-sized (``ceil(in / stride)``), an fc
layer's is 1x1.  Columns after the ninth (an activation, a block, a join)
are for the program and the configuration's own reference, and are not
read here.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Dict, Iterable, Sequence

POOL_KINDS = ("maxpool", "gap")


@dataclass(frozen=True)
class Layer:
    name: str
    kind: str
    k_h: int
    k_w: int
    c_in: int
    c_out: int
    stride: int
    in_h: int
    in_w: int

    @property
    def out_hw(self):
        if self.kind == "fc":
            return 1, 1
        if self.kind == "gap":
            return 1, 1
        return -(-self.in_h // self.stride), -(-self.in_w // self.stride)

    @property
    def macs(self) -> int:
        """Multiply-accumulates for one image."""
        if self.kind in POOL_KINDS:
            return 0
        oh, ow = self.out_hw
        per_out = self.k_h * self.k_w * (1 if self.kind == "dwconv"
                                         else self.c_in)
        return per_out * self.c_out * oh * ow

    @property
    def weight_bytes(self) -> int:
        if self.kind in POOL_KINDS:
            return 0
        if self.kind == "dwconv":
            return self.k_h * self.k_w * self.c_in
        return self.k_h * self.k_w * self.c_in * self.c_out

    @property
    def act_bytes(self) -> int:
        """int8 input read once plus int8 output written once, per image."""
        oh, ow = self.out_hw
        return self.in_h * self.in_w * self.c_in + oh * ow * self.c_out


def layers_of(table: Iterable[Sequence]) -> tuple:
    return tuple(Layer(*row[:9]) for row in table)


def macs_per_image(layers: Sequence[Layer]) -> int:
    return sum(l.macs for l in layers)


def kernel_family(layer: Layer, engine: str, fc_engines) -> str:
    """The kernel family that computes ``layer`` when the program binds
    it to ``engine``: the layer's own ``family`` where it has one (a
    configuration's ``layers_of`` may give it), else ``fc`` for the
    streamed-matmul engines, ``pool`` for pooling nodes, ``conv`` for every
    other weighted layer (conv engines, residual-block and stem engines,
    and an fc layer run as a conv)."""
    family = getattr(layer, "family", None)
    if family:
        return family
    if layer.kind in POOL_KINDS:
        return "pool"
    return "fc" if engine in fc_engines else "conv"


def least_seconds(layers: Sequence[Layer], images: int, dispatches: int,
                  peak_ops: float, peak_bytes: float,
                  family: Callable[[Layer], str]) -> Dict[str, float]:
    """Per kernel family: the least time the chip could take for
    ``images`` images served in ``dispatches`` dispatches, summed over the
    family's layers, each bound by the larger of its operations over
    ``peak_ops`` and its bytes (activations per image, weights once per
    dispatch) over ``peak_bytes``."""
    out: Dict[str, float] = {}
    for l in layers:
        ops = 2 * l.macs * images
        moved = l.act_bytes * images + l.weight_bytes * dispatches
        t = max(ops / peak_ops, moved / peak_bytes)
        fam = family(l)
        out[fam] = out.get(fam, 0.0) + t
    return out
