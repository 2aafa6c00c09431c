"""Serving benchmark: continuous-streaming throughput vs sequential run().

Drives the :class:`~repro.runtime.cnn_serving.CnnServingEngine` over the
executable mini ResNet-18 (the 21-engine pipeline_throughput config) with
two workloads:

  * **closed loop** (saturation): a burst of mixed-size requests (1..4
    images each) submitted at once, ``credits`` microbatches in flight —
    the §V-A always-full pipeline.  Reported against the *sequential
    baseline*: the same requests run one at a time through warm
    ``CompiledPipeline.run()`` calls (one fused dispatch per request,
    blocking each).  The two sides are timed INTERLEAVED — each repeat
    runs sequential then serving back to back, and
    ``serving_speedup_x`` is the median of the per-pair ratios (the
    pipeline benchmark's scheme: host load spikes land on both sides of
    the ratio).  The acceptance bar is >= 1.5x with 4 in-flight
    credits; packing + double-buffering typically lands ~2x on the
    2-core CI shape (batching amortizes dispatch overhead AND the
    in-flight microbatches overlap on separate cores).
  * **open loop** (Poisson arrivals at ~60% of the measured closed-loop
    throughput): latency percentiles and queue depth under a live
    arrival process instead of a pre-filled queue.

Wall-clock numbers are interpret-mode Pallas on CPU — relative
comparison only, not an FPGA throughput claim; the deterministic
``hbm_words_per_image`` row joins the existing bench_diff Eq. 2 gate.

  PYTHONPATH=src python benchmarks/serving_throughput.py \
      [--requests N] [--repeats R] [--smoke] [--json BENCH_serving.json]

``--json`` writes the artifact CI uploads and diffs (bench_diff.py gates
``serving_images_per_s`` / ``serving_speedup_x`` at >5% regression, and
the measured ``admission_wait_fraction`` / ``dispatch_gap_fraction``
stall attribution under the wide wall-clock floor).  ``--trace`` writes
a Chrome Trace Event JSON of the final closed-loop serving repeat — load
it at https://ui.perfetto.dev or ``chrome://tracing`` to see admission
waits, packing, dispatches, in-flight microbatches and deliveries on
their own tracks.
"""
from __future__ import annotations

import argparse
import json
import statistics
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import compiler
from repro.configs.cnn import mini_resnet18
from repro.launch.compile_cache import enable_compile_cache
from repro.models.cnn import cnn_input_shape, init_cnn_params
from repro.obs import Tracer

MICROBATCH = 16
CREDITS = 4
REQ_SIZES = (1, 2, 1, 4)              # mixed request sizes, cycled


def make_requests(cfg, n_requests: int) -> List[np.ndarray]:
    rng = np.random.default_rng(0)
    shape = cnn_input_shape(cfg, 1)[1:]
    return [rng.integers(-127, 128, size=(REQ_SIZES[i % len(REQ_SIZES)],)
                         + shape, dtype=np.int16).astype(np.int8)
            for i in range(n_requests)]


def closed_loop_vs_sequential(cp, params, requests, repeats: int,
                              tracer: Optional[Tracer] = None) -> Dict:
    """Interleaved pairs: each repeat times the sequential baseline (one
    blocking warm ``run()`` per request, at the request's own batch
    size) then the saturated serving engine over the SAME requests; the
    speedup is the median of the per-pair ratios.  ``tracer`` (optional)
    records the LAST serving repeat only, so the traced repeat's spans
    line up with the reported throughput numbers."""
    ex = cp.executor()
    for n in sorted({len(r) for r in requests}):    # warm every shape
        jax.block_until_ready(ex.run(params, jnp.asarray(
            requests[0][:1].repeat(n, axis=0)))[0])
    with cp.serve(params, microbatch=MICROBATCH, credits=CREDITS) as eng:
        eng.serve(requests[:2])                     # warm the packed shape
    images = sum(len(r) for r in requests)
    seq, srv, ratios, report = [], [], [], None
    for rep_i in range(repeats):
        t0 = time.perf_counter()
        for r in requests:
            jax.block_until_ready(ex.run(params, jnp.asarray(r))[0])
        seq.append(images / (time.perf_counter() - t0))
        kw = {"tracer": tracer} if (
            tracer is not None and rep_i == repeats - 1) else {}
        with cp.serve(params, microbatch=MICROBATCH,
                      credits=CREDITS, **kw) as eng:
            t0 = time.perf_counter()
            _, report = eng.serve(requests)
            srv.append(images / (time.perf_counter() - t0))
        ratios.append(srv[-1] / seq[-1])
    return {"images_per_s": statistics.median(srv),
            "sequential_images_per_s": statistics.median(seq),
            "speedup": statistics.median(ratios), "report": report}


def open_loop(cp, params, requests, rate_images_per_s: float) -> Dict:
    """Poisson arrivals at ``rate_images_per_s`` offered load."""
    rng = np.random.default_rng(1)
    with cp.serve(params, microbatch=MICROBATCH, credits=CREDITS) as eng:
        for r in requests:
            time.sleep(float(rng.exponential(len(r) / rate_images_per_s)))
            eng.submit(r)
        eng.drain()
        report = eng.report()
    return {"report": report}


def bench(n_requests: int = 32, repeats: int = 3,
          tracer: Optional[Tracer] = None) -> List[Dict]:
    cfg = mini_resnet18(hw=8, width=16, stages=4)
    cp = compiler.compile(cfg, compiler.TPU_INTERPRET)
    params = init_cnn_params(jax.random.PRNGKey(0), cfg)
    requests = make_requests(cfg, n_requests)
    images = sum(len(r) for r in requests)

    closed = closed_loop_vs_sequential(cp, params, requests, repeats,
                                       tracer)
    rep = closed["report"]
    measured = rep.bandwidth_efficiency.get("measured", {})
    # the flat keys are the bench_diff gate surface; everything else
    # rides in the serialized report (no hand-rolled duplicate dicts)
    rows = [{
        "name": "serving/closed_loop",
        "net": cfg.name,
        "requests": n_requests,
        "images": images,
        "microbatch": MICROBATCH,
        "credits": CREDITS,
        "timing_repeats": repeats,
        "serving_images_per_s": round(closed["images_per_s"], 2),
        "sequential_images_per_s": round(
            closed["sequential_images_per_s"], 2),
        "serving_speedup_x": round(closed["speedup"], 2),
        "admission_wait_fraction": round(
            measured.get("admission_wait_fraction", 0.0), 4),
        "dispatch_gap_fraction": round(
            measured.get("dispatch_gap_fraction", 0.0), 4),
        "hbm_words_per_image": rep.hbm_words_per_image,
        "report": rep.to_dict(),
    }]

    target_rate = 0.6 * closed["images_per_s"]
    orep = open_loop(cp, params, requests, target_rate)["report"]
    rows.append({
        "name": "serving/open_loop",
        "net": cfg.name,
        "requests": n_requests,
        "images": images,
        "offered_images_per_s": round(target_rate, 2),
        "achieved_images_per_s": round(orep.images_per_s, 2),
        "hbm_words_per_image": orep.hbm_words_per_image,
        "report": orep.to_dict(),
    })
    return rows


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized run (fewer requests/repeats)")
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="write the BENCH_serving.json artifact here")
    ap.add_argument("--trace", metavar="PATH", default=None,
                    help="write a Chrome Trace Event JSON of the final "
                         "closed-loop serving repeat (Perfetto loadable)")
    args = ap.parse_args()
    enable_compile_cache()
    n_requests, repeats = args.requests, args.repeats
    if args.smoke:
        n_requests = min(n_requests, 16)

    tracer = Tracer(process_name="serving_throughput") \
        if args.trace else None
    rows = bench(n_requests, repeats, tracer)
    for row in rows:
        print("  ".join(f"{k}={v}" for k, v in row.items()
                        if k != "report"))
    if args.trace:
        tracer.dump(args.trace)
        print(f"wrote {args.trace} "
              f"({len(tracer.events())} events, {tracer.dropped} dropped)")
    if args.json:
        artifact = {"benchmark": "serving_throughput", "rows": rows}
        with open(args.json, "w") as f:
            json.dump(artifact, f, indent=2, sort_keys=True)
        print(f"wrote {args.json}")


if __name__ == "__main__":
    main()
