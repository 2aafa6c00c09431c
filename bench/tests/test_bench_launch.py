"""The readers of the serving runtime's launch stamps and dispatcher spans,
on ``test_bench_metrics``'s hand-made run record; the trace reduction's
labels under nested dispatch spans; and the clock check's pairing of
program executions with their launch spans."""
from __future__ import annotations

import importlib.util
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from benchlib import trace  # noqa: E402
from benchlib.traffic import Sent  # noqa: E402
from test_bench_metrics import TRACE, _run, read  # noqa: E402

_s = importlib.util.spec_from_file_location("bench_clock_check",
                                            BENCH / "clock_check.py")
clock_check = importlib.util.module_from_spec(_s)
_s.loader.exec_module(clock_check)


class _Stamped:
    done = True

    def __init__(self, t_submit, t_launch, t_done):
        self.t_submit, self.t_launch, self.t_done = t_submit, t_launch, t_done


def _stamped(trace=None):
    """``_run()`` with requests the engine stamped: request i waits
    i + 1 ms for its launch, and one answered after the window waits
    far longer."""
    run = _run(trace)
    for i, s in enumerate(run.sent):
        s.request = _Stamped(s.t_sent, s.t_sent + 1e-3 * (i + 1),
                             s.request.t_done)
    run.sent.append(Sent(t_due=0.95, t_sent=0.95, n=4, offset=0,
                         request=_Stamped(0.95, 1.2, 1.5)))
    return run


def test_launch_and_result_waits_read_the_window_s_requests():
    r = _stamped()
    # launch waits 1..10 ms; result waits (19 ms less the launch wait)
    # 18..9 ms; the request answered after the window counts in neither
    assert read("launch_wait_ms", r) == pytest.approx(5.0)
    assert read("result_wait_ms", r) == pytest.approx(13.0)


@pytest.mark.parametrize("name", ["launch_wait_ms", "result_wait_ms"])
def test_a_wait_with_no_stamped_answer_in_the_window_is_none(name):
    # a program that stamps no launch
    assert read(name, _run()) is None
    # nothing answered
    r = _stamped()
    for s in r.sent:
        s.request = None
    assert read(name, r) is None


IDLE = [["waiting for requests", 0.2], ["credit_wait", 0.1],
        ["launch", 0.05], ["pack", 0.04], ["h2d", 0.03], ["dispatch", 0.02],
        ["fill", 0.01]]


def test_host_idle_share_sums_the_dispatcher_s_host_work():
    r = _run(dict(TRACE, idle_gaps=IDLE))
    assert read("host_idle_share.online", r) == pytest.approx(15.0)


def test_host_idle_share_with_nothing_to_read_is_none():
    assert read("host_idle_share.online", _run()) is None
    # a dispatcher whose dispatch span is not split into its steps
    unsplit = [["dispatch", 0.2], ["pack", 0.1],
               ["waiting for requests", 0.1]]
    assert read("host_idle_share.online",
                _run(dict(TRACE, idle_gaps=unsplit))) is None
    assert read("host_idle_share.online",
                _run(dict(TRACE, idle_gaps=IDLE, window_s=0.0))) is None


def test_idle_gaps_take_the_innermost_of_nested_dispatch_spans():
    spans = [("pack", 0, 10), ("dispatch", 10, 100), ("fill", 12, 20),
             ("credit_wait", 20, 30), ("h2d", 30, 40), ("launch", 40, 60)]
    gaps = [(5, 7), (14, 16), (22, 24), (32, 34), (45, 55), (70, 80),
            (200, 210)]
    assert trace.label_gaps(gaps, spans) == {
        "pack": 2, "fill": 2, "credit_wait": 2, "h2d": 2, "launch": 10,
        "dispatch": 10, "waiting for requests": 10}


def test_clock_check_pairs_executions_with_their_launches():
    modules = [("jit_forward", 300, 50),            # warm-up, before marker
               ("jit_bench_marker", 1000, 5),
               ("jit_forward", 2000, 500), ("jit_forward", 2600, 500),
               ("jit_forward", 4000, 100)]
    spans = [("launch", 200, 290), ("fill", 1700, 1750),
             ("launch", 1800, 1900), ("launch", 2100, 2200),
             ("launch", 3800, 3950), ("launch", 5000, 5100)]
    pairs = clock_check.launch_offsets(modules, spans, "jit_bench_marker")
    assert pairs == [
        {"after_start": 200, "after_end": 100, "idle": True},
        {"after_start": 500, "after_end": 400, "idle": False},
        {"after_start": 200, "after_end": 50, "idle": True}]
    rep = clock_check.clock_report(pairs)
    assert rep["executions"] == 3 and rep["idle_executions"] == 2
    assert rep["idle_start_minus_launch_start_us"]["max"] == \
        pytest.approx(0.2)
    assert rep["start_after_launch_start"] == 1.0
    assert rep["start_minus_launch_end_us"]["min"] == pytest.approx(0.05)
    assert rep["start_minus_launch_end_us"]["max"] == pytest.approx(0.4)
    assert rep["idle_start_minus_launch_end_us"]["max"] == pytest.approx(0.1)
    # a host clock running ahead of the device's shows as early starts
    early = [(n, s + 250, e + 250) for n, s, e in spans if n == "launch"]
    rep = clock_check.clock_report(
        clock_check.launch_offsets(modules, early, "jit_bench_marker"))
    assert rep["start_after_launch_start"] == pytest.approx(1 / 3)
