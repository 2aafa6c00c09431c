#!/usr/bin/env python3
"""The chip benchmark: one cell, one run, one JSON line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the repository root on a machine that holds the chips the cell
asks for.  One process, no children.  In order:

1. load the cell's files by name (``benchlib/spec.py``);
2. make the weights on the device (the configuration's ``make_params``,
   by default ``benchlib/model.py``'s) and the image pool on the host,
   both from ``--seed``;
3. ``compiler.compile()`` the configuration and start the serving engine
   the workload names (``serve`` or ``serve_sharded``);
4. warm every shape the window will dispatch, and only those;
5. drive the engine with the cell's traffic mix for ``--seconds``
   (``benchlib/traffic.py``); under ``--trace 1`` the profiler records the
   window's first ``TRACE_SECONDS`` and the per-layer metrics read them;
6. compare a sample of the answers with the configuration's plain
   reference (``spec.reference_of``: ``benchlib/reference.py`` unless the
   configuration has its own) by ``benchlib/check.py``;
7. print the metrics the cell reports, each read by its own
   ``bench/metrics/<name>.py``, as the last line of standard output.

``setup_s`` runs from process start to the window's opening.  Without a
TPU, or with fewer chips than the cell asks for, the run exits non-zero
and prints no result line.  A compilation inside the window is an error
of the run.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

from benchlib import spec  # noqa: E402

TRACE_DIR = BENCH / "traces"
#: how much of a --trace 1 run's window is traced and read
TRACE_SECONDS = 4.0


class CompileCounter:
    """Counts JAX's compile events (tracing, lowering, backend compiles,
    persistent-cache lookups) between ``start`` and ``stop``."""

    def __init__(self):
        self.events: list = []

    def on_event(self, event: str, *args, **kwargs) -> None:
        if "compil" in event:
            self.events.append(event)

    def start(self) -> "CompileCounter":
        import jax.monitoring as mon
        mon.register_event_listener(self.on_event)
        mon.register_event_duration_secs_listener(self.on_event)
        return self

    def stop(self) -> None:
        import jax.monitoring as mon
        mon.unregister_event_listener(self.on_event)
        mon.unregister_event_duration_listener(self.on_event)


class GcPauses:
    """The garbage collector's pauses between ``start`` and ``stop``."""

    def __init__(self):
        self.pauses: list = []
        self._t = 0.0

    def _cb(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t = time.perf_counter()
        else:
            self.pauses.append((info["generation"],
                                time.perf_counter() - self._t))

    def start(self) -> "GcPauses":
        gc.callbacks.append(self._cb)
        return self

    def stop(self) -> None:
        gc.callbacks.remove(self._cb)

    def summary(self) -> str:
        full = [d for g, d in self.pauses if g == 2]
        return (f"{len(self.pauses)} collections, {len(full)} full; "
                f"longest {1e3 * max((d for _, d in self.pauses), default=0):.1f}"
                f" ms, total {1e3 * sum(d for _, d in self.pauses):.1f} ms")


def sleep_until(t: float) -> None:
    while time.perf_counter() < t:
        time.sleep(min(0.05, max(0.0, t - time.perf_counter())))


def bench_marker(x):
    """The marker program that puts host time on the trace's clock."""
    return x + 1


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def program_config(conf: dict):
    """The configuration file's layer table as the program's CNNConfig,
    from the first nine columns of each row (``benchlib/spec.py``)."""
    from repro.configs.cnn import CNNConfig, ConvLayerSpec
    return CNNConfig(conf["network"],
                     tuple(ConvLayerSpec(*row[:9]) for row in conf["layers"]),
                     num_classes=conf["num_classes"])


def program_target(conf: dict):
    from repro.compiler.target import get_target
    t = conf["target"]
    return get_target(t["preset"]).replace(**t.get("overrides", {}))


def counters(eng) -> dict:
    """The engine's counters the window is read against."""
    rep = eng.report()
    return {"padded_rows": rep.padded_rows,
            "dispatched_rows": rep.dispatched_rows,
            "trace_cache_misses": rep.trace_cache.get("misses", 0)}


def start_engine(cell, cp, params, devices, tracer):
    """The workload's serving entry point, started, and the batch sizes
    its window will dispatch."""
    s = dict(cell.workload["serve"])
    entry = s.pop("entry")
    if entry == "serve":
        eng = cp.serve(params, tracer=tracer, **s)
        shapes = list(eng.microbatch_ladder)
    elif entry == "serve_sharded":
        import numpy as np
        from jax.sharding import Mesh
        stages = s.pop("stages")
        mesh = Mesh(np.asarray(devices[:stages]).reshape(1, stages),
                    ("data", "model"))
        eng = cp.serve_sharded(params, mesh=mesh, tracer=tracer, **s)
        shapes = [eng.microbatch]
    else:
        raise ValueError(f"unknown serving entry {entry!r}")
    eng.start()
    return eng, shapes


def warm(eng, pool, shapes) -> None:
    """Send one request of each dispatch size twice, alone, so each
    program the window uses has run before it opens."""
    for n in shapes:
        for _ in range(2):
            eng.submit(pool[:n]).result(timeout=600)


def run_cell(cell, seed: int, seconds: float, trace: bool, devices,
             t_start: float) -> dict:
    """One run of ``cell``: the result line's object."""
    import jax
    import numpy as np

    from benchlib import check, model, traffic
    from benchlib.record import Run
    from benchlib.spans import HostSpans
    from repro import compiler

    conf, wl = cell.config, cell.workload
    layers = conf["layers"]
    act_scale = conf["act_scale"]
    dev = devices[0]
    peaks = spec.peaks(dev.device_kind) if dev.platform == "tpu" else {}
    ref = spec.reference_of(conf)
    fams = spec.families(conf)

    params = ref.make_params(model.seed_key(seed), layers, act_scale)
    pool = model.image_pool(seed, wl["pool_images"], conf["image"])
    jax.block_until_ready(params)

    cp = compiler.compile(program_config(conf), program_target(conf))
    engine_of = cp.engine_table()
    engines = sorted(set(engine_of.values()))
    if "jnp_ref" in engines:
        raise RuntimeError("a layer is bound to the jnp reference engine")
    log(f"{cell.name}: {len(cp.assignments)} nodes on {engines}, "
        f"streamed {list(cp.streamed_names)}; reference {ref.path.name}")

    spans = HostSpans() if trace else None
    t_warm = time.perf_counter()
    eng, shapes = start_engine(cell, cp, params, devices, spans)
    warm(eng, pool, shapes)
    warmup_s = time.perf_counter() - t_warm
    marker = jax.jit(bench_marker)
    marker(np.int32(0)).block_until_ready()

    # set-up's objects (JAX, programs, weights, the pool) are moved out of
    # the collector's reach, so a full collection in the window walks
    # only what the window allocates
    gc.collect()
    gc.freeze()
    pauses = GcPauses().start()
    before = counters(eng)
    if trace:
        import shutil
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 0
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
        t_mark = time.perf_counter()
        marker(np.int32(0)).block_until_ready()
    compiles = CompileCounter().start()
    t0 = time.perf_counter() + 0.01
    setup_s = t0 - t_start
    t_end = t0 + seconds
    # a traced run's per-layer metrics are read over the window's first
    # TRACE_SECONDS, so that its trace stays small enough to read
    t_rec = min(t_end, t0 + TRACE_SECONDS) if trace else t_end
    sent: list = []
    gen = threading.Thread(
        target=lambda: sent.extend(traffic.run(cell.mix, eng.submit, pool,
                                               seed, t0, seconds)),
        name="bench-traffic", daemon=True)
    gen.start()
    sleep_until(t_rec)
    after = counters(eng)
    if trace:
        jax.profiler.stop_trace()
    sleep_until(t_end)
    gen.join()
    compiles.stop()
    pauses.stop()
    gc.unfreeze()
    memory = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in devices)
    eng.stop()
    del eng, cp
    in_window = len(compiles.events)
    log(f"compilations in the window: {in_window} "
        f"{sorted(set(compiles.events))}")
    if in_window or after["trace_cache_misses"] != before["trace_cache_misses"]:
        raise RuntimeError(f"{in_window} compilation(s) inside the window")

    log(f"collector pauses in the window: {pauses.summary()}")
    rec = Run(seconds=t_rec - t0, t0=t0, t_end=t_rec, chips=len(devices),
              setup_s=setup_s, warmup_s=warmup_s, sent=sent, before=before,
              after=after, peaks=peaks, layers=ref.layers_of(layers),
              engines=engine_of, fc_engines=fams["fc_engines"])
    if trace:
        from benchlib import trace as tr
        t = time.perf_counter()
        loaded = tr.load(TRACE_DIR)
        off = tr.marker_offset_ns(loaded, fams["marker_module"], t_mark)
        rec.trace = tr.reduce(
            loaded, fams, (int(t0 * 1e9) + off, int(t_rec * 1e9) + off),
            spans.on_trace_clock(off, "-dispatch"))
        log(f"trace of {t_rec - t0:.1f} s read in "
            f"{time.perf_counter() - t:.1f} s")

    # correctness: a sample of the answers against the plain reference
    answered = [s for s in sent if s.t_done is not None and not s.error]
    unanswered = len(sent) - len(answered)
    picked = check.sample(answered, wl["check"]["requests"], seed)
    if picked:
        served = np.concatenate([s.request.result() for s in picked])
        images = np.concatenate([pool[s.offset:s.offset + s.n]
                                 for s in picked])
        want = ref.logits_in_blocks(
            params, layers, images, act_scale=act_scale,
            block=wl["check"]["block"])
    else:
        served = want = np.zeros((0, 1), np.float32)
        unanswered = max(unanswered, 1)
    correct, checks = check.compare(served, want, unanswered,
                                    wl["check"]["limits"])

    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = spec.metric_reader(m["name"])(rec)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": int(memory)}
    result = {"correct": bool(correct), "attempted": len(sent),
              "failed": unanswered, "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = rec.trace["busy_s"]
        device["window_s"] = rec.trace["window_s"]
        result["breakdown"] = {"device_ops": rec.trace["device_ops"],
                               "idle_gaps": rec.trace["idle_gaps"]}
    log(f"checked {len(picked)} requests, {len(served)} images")
    for name, c in checks.items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = spec.load_cell(args.workload)
    # the TPU runtime's own logs would go to a fixed directory under /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        log(f"no TPU: JAX found {devices[0].platform}; nothing was run")
        return 3
    if len(devices) < cell.chips:
        log(f"{cell.name} needs {cell.chips} chips, JAX sees "
            f"{len(devices)}")
        return 3
    log(f"device: {devices[0].device_kind} x{len(devices)}, "
        f"jax {jax.__version__}")
    from repro.launch.compile_cache import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")
    # every program the run makes is kept, however quick its compile, so
    # that a cell's later runs compile nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      devices[:cell.chips], T_START)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
