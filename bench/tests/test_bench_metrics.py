"""The metric readers on a hand-made run record: each reads what its
docstring says, and one with nothing to read returns None."""
from __future__ import annotations

import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from benchlib import spec, work  # noqa: E402
from benchlib.record import Run  # noqa: E402
from benchlib.traffic import Sent  # noqa: E402

LAYERS = work.layers_of(json.loads(
    (BENCH / "configs" / "vgg16.json").read_text())["layers"])
ENGINES = {l.name: ("stream_matmul" if l.name in ("fc1", "fc2")
                    else "conv2d_int8") for l in LAYERS}
PEAKS = spec.peaks("TPU v5 lite")


class _Answered:
    done = True

    def __init__(self, t):
        self.t_done = t


def _run(trace=None):
    sent = [Sent(t_due=0.1 * i, t_sent=0.1 * i + 0.001, n=4, offset=0,
                 request=_Answered(0.1 * i + 0.02)) for i in range(10)]
    return Run(seconds=1.0, t0=0.0, t_end=1.0, chips=1, setup_s=12.5,
               warmup_s=2.0, sent=sent,
               before={"padded_rows": 10, "dispatched_rows": 100},
               after={"padded_rows": 30, "dispatched_rows": 300},
               peaks=PEAKS, layers=LAYERS, engines=ENGINES,
               fc_engines=["stream_matmul"], trace=trace)


TRACE = {"window_s": 1.0, "busy_s": 0.9, "dispatches": 5,
         "family_s": {"conv": 0.5, "fc": 0.01}, "collective_s": 0.2,
         "collective_only_s": 0.05}


def read(name, run):
    return spec.metric_reader(name)(run)


def test_host_clock_metrics():
    r = _run()
    assert read("images_per_s", r) == pytest.approx(40.0)
    assert read("latency_p50_ms", r) == pytest.approx(20.0)
    assert read("setup_s", r) == 12.5 and read("warmup_s", r) == 2.0
    assert read("gen_late_ms", r) == pytest.approx(1.0)
    assert read("pad_fraction", r) == pytest.approx(10.0)


def test_trace_metrics():
    r = _run(TRACE)
    assert read("step_ms.sat", r) == pytest.approx(180.0)
    assert read("idle_share.sat", r) == pytest.approx(10.0)
    assert read("collective_share", r) == pytest.approx(5.0)
    macs = work.macs_per_image(LAYERS)
    assert read("mfu", r) == pytest.approx(100 * 2 * macs * 40 / 393e12)
    least = r.least_seconds(5)
    fc_bytes = sum(l.weight_bytes for l in LAYERS if l.name in ("fc1", "fc2"))
    assert least["fc"] == pytest.approx(
        (fc_bytes * 5 + 40 * (4096 + 4096 + 4096 + 1000)) / 819e9)
    assert read("fc_roofline", r) == pytest.approx(100 * least["fc"] / 0.01)
    assert read("conv_roofline", r) == pytest.approx(
        100 * least["conv"] / 0.5)


@pytest.mark.parametrize("name", ["step_ms.sat", "idle_share.sat", "mfu",
                                  "conv_roofline", "fc_roofline",
                                  "collective_share"])
def test_a_reader_with_nothing_to_read_returns_none(name):
    assert read(name, _run()) is None
    empty = dict(TRACE, dispatches=0, family_s={}, collective_s=0.0,
                 window_s=0.0)
    if name != "mfu":
        assert read(name, _run(empty)) is None
