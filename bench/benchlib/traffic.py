"""The one load generator; every traffic mix is a data file it reads.

A mix (``bench/traffic/<name>.json``) is one of

* ``{"loop": "closed", "clients": K, "images": {"min": a, "max": b}}``:
  K clients, each sending its next request when its last one returns;
* ``{"loop": "open", "rate_per_s": R, "images": {...}}``: requests due
  on a Poisson schedule of mean rate R.

Every seed gets the same work in another order: the request sizes are
drawn from whole cycles of ``a..b``, shuffled, and open-loop gaps are
the evenly spaced quantiles of the exponential distribution, shuffled,
scaled so that all of them fall inside the window.  The seed moves the order and
which pool images each request carries, not how much there is to do.

A request is timed from when it was due: for a closed-loop client that is
the moment its previous request returned, for an open loop its scheduled
time, however late the generator got it out.
"""
from __future__ import annotations

import threading
import time
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional

import numpy as np

#: how long the generator waits for any one answer before it counts the
#: request as never answered
ANSWER_TIMEOUT_S = 60.0


@dataclass
class Sent:
    t_due: float
    t_sent: float
    n: int
    offset: int
    request: Any = None
    error: Optional[str] = None

    @property
    def t_done(self) -> Optional[float]:
        r = self.request
        return None if r is None or not r.done else r.t_done


def _sizes(rng, spec: Dict[str, int], count: int) -> np.ndarray:
    lo, hi = int(spec["min"]), int(spec["max"])
    cycle = np.arange(lo, hi + 1)
    reps = -(-count // len(cycle))
    sizes = np.tile(cycle, reps)
    rng.shuffle(sizes)
    return sizes[:count]


def _offsets(rng, sizes: np.ndarray, pool_size: int) -> np.ndarray:
    return rng.integers(0, pool_size - sizes + 1)


def open_loop_dues(mix: Dict, seconds: float, rng) -> np.ndarray:
    """Due times, seconds from the window's start, of an open-loop mix."""
    rate = float(mix["rate_per_s"])
    n = max(1, int(rate * seconds))
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q)
    rng.shuffle(gaps)
    # the last request is due half a mean gap before the window closes
    return np.cumsum(gaps) * ((seconds - 0.5 / rate) / gaps.sum())


def run(mix: Dict, submit: Callable, pool: np.ndarray, seed: int,
        t0: float, seconds: float) -> List[Sent]:
    """Drive ``submit(images) -> request`` with ``mix`` from ``t0`` for
    ``seconds``; returns every request sent, each answered or failed."""
    rng = np.random.default_rng([seed, 2])
    if mix["loop"] == "closed":
        return _closed(mix, submit, pool, rng, t0, seconds)
    if mix["loop"] == "open":
        return _open(mix, submit, pool, rng, t0, seconds)
    raise ValueError(f"unknown loop {mix['loop']!r}")


def _wait(s: Sent) -> None:
    try:
        s.request.result(timeout=ANSWER_TIMEOUT_S)
    except Exception as exc:          # an answer that never comes is a fault
        s.error = f"{type(exc).__name__}: {exc}"


def _closed(mix, submit, pool, rng, t0, seconds) -> List[Sent]:
    t_end = t0 + seconds
    clients = int(mix["clients"])
    per_client = 4096
    plans = []
    for _ in range(clients):
        sizes = _sizes(rng, mix["images"], per_client)
        plans.append((sizes, _offsets(rng, sizes, len(pool))))
    sent: List[List[Sent]] = [[] for _ in range(clients)]

    def client(i: int) -> None:
        sizes, offs = plans[i]
        k = 0
        while True:
            now = time.perf_counter()
            if now >= t_end:
                return
            n, off = int(sizes[k % per_client]), int(offs[k % per_client])
            k += 1
            s = Sent(t_due=now, t_sent=now, n=n, offset=off)
            try:
                s.request = submit(pool[off:off + n])
            except Exception as exc:
                s.error = f"{type(exc).__name__}: {exc}"
                sent[i].append(s)
                return
            s.t_sent = time.perf_counter()
            sent[i].append(s)
            _wait(s)
            if s.error:
                return

    threads = [threading.Thread(target=client, args=(i,), daemon=True,
                                name=f"bench-client-{i}")
               for i in range(clients)]
    while time.perf_counter() < t0:
        time.sleep(min(0.001, max(0.0, t0 - time.perf_counter())))
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return [s for per in sent for s in per]


def _open(mix, submit, pool, rng, t0, seconds) -> List[Sent]:
    dues = t0 + open_loop_dues(mix, seconds, rng)
    sizes = _sizes(rng, mix["images"], len(dues))
    offs = _offsets(rng, sizes, len(pool))
    sent: List[Sent] = []
    for due, n, off in zip(dues, sizes, offs):
        wait = due - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        s = Sent(t_due=float(due), t_sent=0.0, n=int(n), offset=int(off))
        try:
            s.request = submit(pool[int(off):int(off) + int(n)])
        except Exception as exc:
            s.error = f"{type(exc).__name__}: {exc}"
        s.t_sent = time.perf_counter()
        sent.append(s)
    for s in sent:
        if s.request is not None:
            _wait(s)
    return sent
