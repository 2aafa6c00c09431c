"""The TPU's tiling, and where Pallas kernels run.

Mosaic DMAs a slice of an HBM array only in whole (SUBLANES, LANES) tiles
of its last two dims, so the kernel wrappers pad widths and channels to
:func:`round_up` multiples of these.

On a TPU the Mosaic path compiles the kernels; on the CPU (tests, dev
machines) they execute in interpret mode.  :func:`resolve_interpret`
centralises that decision so callers can pass ``interpret=None`` ("do the
right thing for this backend") while tests keep forcing ``interpret=True``
explicitly.  Nothing here hides a missing device: a backend that fails to
initialise raises.
"""
from __future__ import annotations

from typing import Optional

import jax

SUBLANES = 8
LANES = 128


def round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """Resolve an ``interpret`` request against the running backend:
    ``True``/``False`` are honoured verbatim; ``None`` means "interpret
    unless the default backend is a TPU"."""
    if interpret is not None:
        return bool(interpret)
    return not on_tpu()
