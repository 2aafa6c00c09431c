"""``bench/run.py`` end to end on the CPU at mini size: it refuses a host
without a TPU, a sound run comes out correct, and a run whose served
answers are altered where they are produced comes out not correct.

The chip check is skipped by calling ``run_cell`` directly with a mini
ResNet-50 (both weight tiers in play) on the Pallas interpreter."""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import subprocess
import sys
import time
from pathlib import Path

import jax
import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

from benchlib import spec  # noqa: E402

_s = importlib.util.spec_from_file_location("bench_run", BENCH / "run.py")
bench_run = importlib.util.module_from_spec(_s)
_s.loader.exec_module(bench_run)


def _mini_cell():
    from repro.configs.cnn import mini_resnet50
    cfg = mini_resnet50()
    rows = [[l.name, l.kind, l.k_h, l.k_w, l.c_in, l.c_out, l.stride,
             l.in_h, l.in_w] for l in cfg.layers]
    conf = {"network": cfg.name, "layers": rows, "num_classes": 10,
            "image": [32, 32, 3], "act_scale": 0.05,
            "target": {"preset": "tpu-interpret",
                       "overrides": {"bram_m20ks": 6}}}
    limits = json.loads((BENCH / "workloads" / "resnet50-sat.json")
                        .read_text())["check"]["limits"]
    wl = {"config": "mini", "traffic": "mini", "chips": 1,
          "serve": {"entry": "serve", "microbatch": 4, "credits": 2},
          "pool_images": 32,
          "check": {"requests": 6, "block": 4, "limits": limits}}
    mix = {"loop": "closed", "clients": 2, "images": {"min": 1, "max": 4}}
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    e2e = [m for m in bench["end_to_end"] if m["name"] in
           ("images_per_s", "setup_s")]
    return spec.Cell("mini", wl, conf, mix, e2e, [])


def _run(cell, seed=2 ** 31 + 5):
    return bench_run.run_cell(cell, seed, 1.0, False, jax.devices()[:1],
                              time.perf_counter())


def test_refuses_a_host_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(BENCH / "run.py"),
                        "--workload", "resnet50-sat", "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       capture_output=True, text=True, env=env, cwd=ROOT,
                       timeout=300)
    assert p.returncode != 0
    assert p.stdout == ""
    assert "no TPU" in p.stderr


def test_a_sound_run_is_correct():
    r = _run(_mini_cell())
    assert r["correct"] is True
    assert r["attempted"] > 0 and r["failed"] == 0
    assert set(r["metrics"]) == {"images_per_s", "setup_s"}
    assert r["metrics"]["images_per_s"]["value"] > 0
    assert r["device"]["platform"] == "cpu" and r["device"]["count"] == 1
    assert list(r)[-1] == "checks"
    assert r["checks"]["logit_gap"]["value"] == 0.0


def test_an_answer_altered_where_it_is_produced_is_not_correct(monkeypatch):
    from repro.compiler import pipeline
    orig = pipeline.trace_fused
    bump = jax.jit(lambda y: y.at[:, 0].add(0.5))

    def altered(*a, **k):
        ft = orig(*a, **k)
        return dataclasses.replace(ft, fn=lambda p, x: bump(ft.fn(p, x)))

    monkeypatch.setattr(pipeline, "trace_fused", altered)
    r = _run(_mini_cell(), seed=77)
    assert r["correct"] is False
    assert r["checks"]["logit_gap"]["value"] > \
        r["checks"]["logit_gap"]["limit"]


def test_a_compilation_inside_the_window_is_counted():
    c = bench_run.CompileCounter().start()
    try:
        jax.jit(lambda x: x * 3 + 1)(jax.numpy.arange(7)).block_until_ready()
    finally:
        c.stop()
    assert c.events
    n = len(c.events)
    jax.jit(lambda x: x * 5)(jax.numpy.arange(3)).block_until_ready()
    assert len(c.events) == n                   # stopped: no longer counts


_STAGED = r'''
import json, sys, time
sys.path.insert(0, {bench!r}); sys.path.insert(0, {src!r})
import importlib.util
import jax
from benchlib import spec
from repro.configs.cnn import mini_resnet50
s = importlib.util.spec_from_file_location("bench_run", {run!r})
run = importlib.util.module_from_spec(s); s.loader.exec_module(run)
cfg = mini_resnet50(stages=4)
rows = [[l.name, l.kind, l.k_h, l.k_w, l.c_in, l.c_out, l.stride, l.in_h,
         l.in_w] for l in cfg.layers]
conf = {{"network": cfg.name, "layers": rows, "num_classes": 10,
        "image": [32, 32, 3], "act_scale": 0.05,
        "target": {{"preset": "tpu-interpret",
                   "overrides": {{"bram_m20ks": 6}}}}}}
wl = json.load(open({workload!r}))
wl["serve"]["round_microbatches"] = 2
wl.update(pool_images=32, check=dict(wl["check"], requests=4, block=4))
mix = {{"loop": "closed", "clients": 2, "images": {{"min": 1, "max": 4}}}}
cell = spec.Cell("mini-staged", wl, conf, mix, [], [])
if sys.argv[1] == "no-exchange":
    jax.lax.ppermute = lambda x, axis_name, perm: x
r = run.run_cell(cell, 2 ** 31 + 9, 1.0, False, jax.devices()[:4],
                 time.perf_counter())
print(json.dumps({{"correct": r["correct"], "checks": r["checks"]}}))
'''


@pytest.mark.parametrize("fault", ["none", "no-exchange"])
def test_the_staged_cell_is_not_correct_without_its_exchange(fault):
    """The four-stage path on forced host devices: sound, it is correct;
    with the stage-to-stage exchange left out, it is not."""
    code = _STAGED.format(bench=str(BENCH), src=str(ROOT / "src"),
                          run=str(BENCH / "run.py"),
                          workload=str(BENCH / "workloads"
                                       / "resnet50-x4-staged.json"))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", code, fault],
                       capture_output=True, text=True, env=env, cwd=ROOT,
                       timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    r = json.loads(p.stdout.strip().splitlines()[-1])
    assert r["correct"] is (fault == "none"), r
