"""The trace reduction, on hand-made intervals and on a short trace
recorded on a TPU v5e (``data/resnet50_sat_trace.json.gz``: four
ResNet-50 program executions and the gaps between them)."""
from __future__ import annotations

import gzip
import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from benchlib import spec, trace  # noqa: E402

DATA = Path(__file__).resolve().parent / "data" / "resnet50_sat_trace.json.gz"
FAMS = spec.families()


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(DATA, "rt") as f:
        return json.load(f)


def test_base_name_strips_suffixes_and_operands():
    assert trace.base_name(
        "%conv2d_int8.14 = s32[16,224,224,64] custom-call(s8[...] %p)"
    ) == "conv2d_int8"
    assert trace.base_name("%pad.73.clone = s8[1] pad(...)") == "pad"
    assert trace.base_name("%collective-permute-start.2 = (...)") \
        == "collective-permute-start"
    assert trace.base_name("while.6") == "while"


def test_union_and_intersect():
    u = trace.union([(5, 7), (0, 2), (1, 3), (6, 9), (12, 13)])
    assert u == [(0, 3), (5, 9), (12, 13)]
    assert trace.length(u) == 8
    assert trace.intersect(u, [(2, 6), (8, 20)]) == 1 + 1 + 1 + 1


def test_reduce_on_hand_made_devices():
    tr = {"devices": {
        "/device:TPU:0": {
            "ops": [["conv2d_int8", 0, 40], ["while", 0, 60],
                    ["collective-permute-done", 60, 20],
                    ["fusion", 70, 5], ["stream_matmul", 100, 10]],
            "modules": [["jit_round", 0, 80], ["jit_bench_marker", 150, 1],
                        ["jit_round", 100, 10]]},
        "/device:TPU:1": {
            "ops": [["collective-permute-done", 0, 50],
                    ["conv2d_int8", 100, 100]],
            "modules": [["jit_round", 0, 200]]},
    }}
    r = trace.reduce(tr, FAMS, (0, 200))
    # chip 0 busy [0,80) + [100,110) = 90; chip 1 [0,50) + [100,200) = 150
    assert r["busy_s"] == pytest.approx((90 + 150) / 2 / 1e9)
    assert r["window_s"] == pytest.approx(200 / 1e9)
    assert r["family_s"]["conv"] == pytest.approx((40 + 100) / 1e9)
    assert r["family_s"]["fc"] == pytest.approx(10 / 1e9)
    assert r["dispatches"] == 2                  # the marker is not counted
    # collectives: chip 0 [60,80) with a fusion in [70,75) -> 15 alone;
    # chip 1 [0,50) alone -> 50
    assert r["collective_s"] == pytest.approx((20 + 50) / 2 / 1e9)
    assert r["collective_only_s"] == pytest.approx((15 + 50) / 2 / 1e9)
    assert dict(r["device_ops"])["conv2d_int8"] == pytest.approx(140 / 1e9)
    assert "while" not in dict(r["device_ops"])
    idle = sum(v for _, v in r["idle_gaps"])
    assert idle == pytest.approx(0.2e-6 - r["busy_s"])


def test_idle_gaps_are_labelled_by_the_open_host_span():
    gaps = [(10, 20), (30, 50), (60, 70), (100, 110)]
    spans = [("pack", 0, 16), ("dispatch", 16, 45), ("credit_wait", 55, 80)]
    assert trace.label_gaps(gaps, spans) == {
        "pack": 10, "dispatch": 20, "credit_wait": 10,
        "waiting for requests": 10}


def test_marker_offset_reads_the_first_marker():
    tr = {"devices": {"/device:TPU:0": {"ops": [], "modules": [
        ["jit_forward", 2_000, 50], ["jit_bench_marker", 1_000, 5]]}}}
    assert trace.marker_offset_ns(tr, "jit_bench_marker", 1e-6) == 0
    with pytest.raises(ValueError):
        trace.marker_offset_ns(tr, "jit_other", 0.0)


def test_reduce_on_the_recorded_chip_trace(recorded):
    dev = recorded["devices"]["/device:TPU:0"]
    mods = sorted(dev["modules"], key=lambda m: m[1])
    lo, hi = mods[1][1], mods[-1][1]          # the whole executions
    r = trace.reduce(recorded, FAMS, (lo, hi))
    assert r["chips"] == 1
    assert r["dispatches"] == len(mods) - 2 == 4
    assert 0 < r["busy_s"] <= r["window_s"]
    # every program execution keeps the device busy nearly throughout
    assert r["busy_s"] >= 0.8 * sum(m[2] for m in mods[1:-1]) / 1e9
    conv = sum(min(s + d, hi) - max(s, lo) for n, s, d in dev["ops"]
               if n == "conv2d_int8" and s < hi and s + d > lo)
    assert r["family_s"]["conv"] == pytest.approx(conv / 1e9)
    assert r["family_s"]["pool"] > 0 and r["family_s"]["fc"] > 0
    assert r["device_ops"][0][0] == "conv2d_int8"
    assert r["collective_s"] == 0 and r["collective_only_s"] == 0
    idle = sum(v for _, v in r["idle_gaps"])
    assert idle == pytest.approx(r["window_s"] - r["busy_s"])
    assert [n for n, _ in r["idle_gaps"]] == ["waiting for requests"]
