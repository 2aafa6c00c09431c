"""``chip_smoke.py`` rehearsed on the CPU at mini size.

The script's own entry point needs a TPU and must refuse anything else.
Its phases are plain functions over (config, target), so here they run
on the Pallas interpreter over ``mini_resnet50`` with both weight tiers
in play, the four-device path runs on forced host devices in a
subprocess, and the mismatch diagnostics are driven by a deliberately
wrong engine."""
from __future__ import annotations

import importlib.util
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

from repro import compiler
from repro.compiler.engines import (get_engine, register_engine,
                                    unregister_engine)
from repro.configs.cnn import mini_resnet50

ROOT = Path(__file__).resolve().parents[1]
_spec = importlib.util.spec_from_file_location("chip_smoke",
                                               ROOT / "chip_smoke.py")
chip_smoke = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(chip_smoke)

#: small enough BRAM that Algorithm 1 streams s1b0c1 and s1b0ds
TARGET = compiler.TPU_INTERPRET.replace(bram_m20ks=6)


def test_main_refuses_a_host_without_tpu(capsys):
    assert chip_smoke.main([]) == 2
    out = capsys.readouterr()
    assert out.out == ""                       # no result line
    assert "no TPU" in out.err


def test_smoke_one_chip_mini_both_tiers():
    lines = []
    chip_smoke.smoke_one_chip(mini_resnet50(), TARGET, 0, interpret=True,
                              log=lines.append)
    text = "\n".join(lines)
    assert "HBM-streamed layers: 2 ['s1b0c1', 's1b0ds']" in text
    assert "jnp_ref" not in text
    assert "6 requests / 26 images" in text
    assert "6 requests bit-identical to cnn_forward" in text


def test_compile_checked_rejects_a_jnp_ref_binding():
    gap = unregister_engine("global_avgpool_int8")   # GAP falls to jnp_ref
    try:
        with pytest.raises(chip_smoke.SmokeFailure, match="gap"):
            chip_smoke.compile_checked(mini_resnet50(), TARGET,
                                       log=lambda _: None)
    finally:
        register_engine("global_avgpool_int8", priority=10)(gap)
    assert get_engine("global_avgpool_int8") is gap


def test_compare_logits_names_the_first_differing_layer():
    lines = []
    ok = [np.zeros((2, 3), np.float32)]
    chip_smoke.compare_logits(ok, [o.copy() for o in ok],
                              diagnose=None, log=lines.append)
    assert lines == []
    with pytest.raises(chip_smoke.SmokeFailure, match="1 request"):
        chip_smoke.compare_logits(
            ok + ok, ok + [np.full((2, 3), 0.5, np.float32)],
            diagnose=lambda i: ("fc", 1, 0.5), log=lines.append)
    assert "requests [1]" in lines[0] and "0.5" in lines[0]
    assert "('fc', 1, 0.5)" in lines[0]


def test_first_differing_layer_finds_a_wrong_engine():
    cfg = mini_resnet50()
    cp = compiler.compile(cfg, TARGET)
    params, images = chip_smoke.make_inputs(cfg, 0, sizes=(1,))
    assert chip_smoke.first_differing_layer(
        cp, params, images[0], interpret=True) is None

    pool = get_engine("maxpool_int8")

    class OffByOne:
        can_stream = False

        def supports(self, spec):
            return pool.supports(spec)

        def vmem_bytes(self, spec, sched):
            return pool.vmem_bytes(spec, sched)

        def stats(self, sched, batch):
            return pool.stats(sched, batch)

        def run(self, ctx, sched, p, x, act):
            y, y_f, st = pool.run(ctx, sched, p, x, act)
            y = jnp.minimum(y.astype(jnp.int32) + 1, 127)
            return y.astype(jnp.int8), y_f, st

    register_engine("maxpool_int8", priority=10)(OffByOne())
    try:
        name, dq, df = chip_smoke.first_differing_layer(
            cp, params, images[0], interpret=True)
    finally:
        unregister_engine("maxpool_int8")
    assert (name, dq, df) == ("maxpool", 1, 0.0)


FOUR_DEVICE_SCRIPT = textwrap.dedent("""
    import os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    sys.path.insert(0, os.getcwd())
    import jax
    import chip_smoke
    from repro import compiler
    from repro.configs.cnn import mini_resnet50
    assert len(jax.devices()) == 4
    chip_smoke.smoke_four_chips(
        mini_resnet50(), compiler.TPU_INTERPRET.replace(bram_m20ks=6), 0,
        jax.devices(), interpret=True)
""")


def test_smoke_four_chips_on_forced_host_devices():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", FOUR_DEVICE_SCRIPT],
                       capture_output=True, text=True, env=env, cwd=ROOT,
                       timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "partition(4)" in r.stdout
    assert "6 requests bit-identical to single-device run()" in r.stdout
