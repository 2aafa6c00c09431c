"""The comparison that decides ``correct``.

Once the window has closed, a sample of the answered requests, drawn from
the seed and always holding the largest one, is run through the plain
reference (``reference.py``) on the same images.  What is compared:

* ``unanswered``: requests sent in the window that never got an answer
  or failed (limit 0);
* ``logit_gap``: over the sampled images, the largest
  ``max_j |served_j - reference_j|`` as a share of that image's largest
  reference logit.  The int8 contract makes the served logits equal to the
  reference's bit for bit; the limit in the workload file says how far
  from that a run may read.
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import numpy as np


def sample(answered: Sequence, count: int, seed: int) -> List:
    """Up to ``count`` of ``answered``, drawn from the seed, the one with
    the most images always among them."""
    if not answered:
        return []
    rng = np.random.default_rng([seed, 3])
    largest = max(range(len(answered)), key=lambda i: answered[i].n)
    rest = [i for i in range(len(answered)) if i != largest]
    k = min(count - 1, len(rest))
    picked = [largest] + list(rng.choice(rest, size=k, replace=False)
                              if k > 0 else [])
    return [answered[i] for i in sorted(picked)]


def logit_gap(served: np.ndarray, want: np.ndarray) -> float:
    """Largest per-image ``max |served - want| / max |want|``."""
    if served.shape != want.shape:
        return float("inf")
    scale = np.maximum(np.max(np.abs(want), axis=1), 1e-30)
    gap = np.max(np.abs(served.astype(np.float64) - want), axis=1) / scale
    return float(np.max(gap)) if len(gap) else 0.0


def compare(served: np.ndarray, want: np.ndarray, unanswered: int,
            limits: Dict[str, float]) -> Tuple[bool, Dict]:
    """``(correct, checks)``; each check is ``{"value", "limit"}``."""
    checks = {
        "unanswered": {"value": unanswered,
                       "limit": limits.get("unanswered", 0)},
        "logit_gap": {"value": logit_gap(served, want),
                      "limit": limits["logit_gap"]},
    }
    ok = all(c["value"] <= c["limit"] for c in checks.values())
    return ok, checks
