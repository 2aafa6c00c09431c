"""The load generator: the same seed gives the same requests, every seed
the same work, and open-loop due times follow the mix's rate."""
from __future__ import annotations

import sys
import threading
import time
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from benchlib import traffic  # noqa: E402

POOL = np.arange(64, dtype=np.int8).reshape(64, 1, 1, 1)


class _Req:
    """A request answered after ``delay`` seconds on a timer thread."""

    def __init__(self, images, delay):
        self.images = np.array(images)
        self.t_done = None
        self._ev = threading.Event()
        threading.Timer(delay, self._answer).start()

    def _answer(self):
        self.t_done = time.perf_counter()
        self._ev.set()

    @property
    def done(self):
        return self._ev.is_set()

    def result(self, timeout=None):
        if not self._ev.wait(timeout):
            raise TimeoutError
        return self.images


def _drive(mix, seed, seconds=0.3, delay=0.002):
    return traffic.run(mix, lambda x: _Req(x, delay), POOL, seed,
                       time.perf_counter(), seconds)


OPEN = {"loop": "open", "rate_per_s": 400, "images": {"min": 1, "max": 4}}


@pytest.mark.parametrize("seed", [0, 2 ** 33 + 17])
def test_open_loop_dues_follow_the_rate_and_repeat_per_seed(seed):
    a = traffic.open_loop_dues(OPEN, 10.0, np.random.default_rng([seed, 2]))
    b = traffic.open_loop_dues(OPEN, 10.0, np.random.default_rng([seed, 2]))
    assert np.array_equal(a, b)
    assert len(a) == 4000
    assert np.all(np.diff(a) > 0) and 0 < a[0] and a[-1] < 10.0
    gaps = np.diff(np.concatenate([[0.0], a]))
    assert gaps.mean() == pytest.approx(1 / 400, rel=0.01)
    assert np.std(gaps) / gaps.mean() == pytest.approx(1.0, abs=0.05)


def test_open_loop_gaps_are_the_same_set_for_every_seed():
    g = [np.sort(np.diff(np.concatenate(
        [[0.0], traffic.open_loop_dues(OPEN, 5.0,
                                       np.random.default_rng([s, 2]))])))
        for s in (1, 2)]
    assert np.allclose(g[0], g[1])


def test_open_loop_requests_are_timed_from_their_due_time():
    sent = _drive(OPEN, 5)
    assert len(sent) == int(400 * 0.3)
    assert all(s.t_sent >= s.t_due for s in sent)
    assert all(s.t_done is not None and s.error is None for s in sent)
    assert all(1 <= s.n <= 4 for s in sent)
    again = _drive(OPEN, 5)
    assert [(s.n, s.offset) for s in sent] == [(s.n, s.offset)
                                               for s in again]


def test_closed_loop_clients_wait_for_their_answers():
    mix = {"loop": "closed", "clients": 3, "images": {"min": 1, "max": 16}}
    sent = _drive(mix, 9, seconds=0.2, delay=0.01)
    assert sent and all(s.t_done is not None for s in sent)
    # at most one request per client in flight: each is due when the
    # client's previous one was answered
    per_window = 0.2 / 0.01 * 3
    assert len(sent) <= per_window + 3
    assert all(np.array_equal(s.request.result(),
                              POOL[s.offset:s.offset + s.n]) for s in sent)


def test_request_sizes_are_whole_shuffled_cycles():
    rng = np.random.default_rng(4)
    sizes = traffic._sizes(rng, {"min": 1, "max": 16}, 64)
    assert sorted(sizes) == sorted(list(range(1, 17)) * 4)
