"""Pipeline benchmark: images/s + stall cycles vs fifo_sim, as JSON.

Runs the executable mini ResNet-18 through the compiled pipeline twice —
all weights pinned vs the Algorithm 1 hybrid plan — and reports, per plan:

  * warm-cache wall-clock images/s of the actual JAX execution, as the
    MEDIAN of ``--repeats`` runs after compilation (interpret-mode
    Pallas on CPU: a functional emulation, so wall-clock is for
    *relative* comparison only, not an FPGA throughput claim), for BOTH
    executor backends — the fused single-dispatch jit program and the
    eager per-layer walk — plus their speedup ratio.  The mini net is
    sized so host dispatch overhead is visible against compute: that
    overhead is exactly what the fused path removes;
  * the §VI analytic throughput model over the same plan;
  * streamed weight traffic (Eq. 2 words) from the traced dispatch
    counters — cross-checked (hard fail, ``ExecutionReport.verify``)
    against the plan analytics over 100% of the topology: every node
    (pool/GAP included) dispatched, per-node and per-fused-block words
    exact, plus the whole-graph ``topology_words_per_image`` total the
    regression gate tracks;
  * tail-engine stall cycles predicted by the §V-A credit-mode fifo_sim
    over the plan's per-row word demands, against the sim's delivered
    word counts.

It also records the *modelled* throughput + Eq. 2 HBM words/image for the
paper's full-size nets (compile-only — nothing executes at 224x224 on
CPU), so the perf trajectory of the planner is tracked per commit; CI
diffs these modelled numbers against the previous run's artifact and
fails on >5% regression (benchmarks/bench_diff.py).

  PYTHONPATH=src python benchmarks/pipeline_throughput.py [batch] \
      [--repeats N] [--json BENCH_pipeline.json]

``--json`` writes the machine-readable artifact CI uploads per run.
"""
from __future__ import annotations

import argparse
import json
import statistics
import time
from typing import Dict, List

import jax
import jax.numpy as jnp

from repro import compiler
from repro.configs.cnn import CNN_CONFIGS, mini_resnet18
from repro.core import fifo_sim
from repro.launch.compile_cache import enable_compile_cache
from repro.models.cnn import cnn_input_shape, init_cnn_params

PAPER_NETS = ("resnet18", "resnet50", "vgg16")
BACKENDS = ("eager", "fused")


def _paired_times_s(cp, params, x, repeats: int):
    """Warm-cache timing of both backends, INTERLEAVED: each repeat runs
    eager then fused back to back, and the reported speedup is the
    median of the per-pair ratios — so host load spikes land on both
    sides of the ratio instead of whichever backend was being timed.
    The first (untimed) run per backend absorbs trace/compile cost.
    Returns (times dict, last fused ExecutionReport) — the report is
    deterministic per shape, so reusing it saves an extra execution."""
    exs = {be: cp.executor(backend=be) for be in BACKENDS}
    for ex in exs.values():
        jax.block_until_ready(ex.run(params, x)[0])    # warm-up / compile
    times: Dict[str, List[float]] = {be: [] for be in BACKENDS}
    ratios = []
    report = None
    for _ in range(repeats):
        for be in BACKENDS:
            t0 = time.perf_counter()
            logits, rep = exs[be].run(params, x)
            jax.block_until_ready(logits)          # time execution, not
            times[be].append(time.perf_counter() - t0)   # async dispatch
            if be == "fused":
                report = rep
        ratios.append(times["eager"][-1] / times["fused"][-1])
    out = {be: statistics.median(ts) for be, ts in times.items()}
    out["speedup"] = statistics.median(ratios)
    return out, report


def bench(batch: int = 2, repeats: int = 7) -> List[Dict]:
    """Execute the mini net under pinned vs hybrid compiled pipelines,
    on both executor backends.

    The net is ResNet-18's full four-stage topology at executable scale
    (21 engines, tiny maps): per-engine compute is small against the
    ~20 host dispatches + jit-cache lookups a ``backend="eager"`` run
    pays per image — which is exactly the overhead the fused
    single-dispatch program removes, and what the speedup column
    measures."""
    cfg = mini_resnet18(hw=8, width=16, stages=4)
    params = init_cnn_params(jax.random.PRNGKey(0), cfg)
    x = jax.random.randint(jax.random.PRNGKey(1),
                           cnn_input_shape(cfg, batch), -127, 128, jnp.int8)

    hybrid = compiler.compile(cfg, compiler.TPU_INTERPRET)
    plans = {"pinned": hybrid.with_offload([]), "hybrid": hybrid}

    rows = []
    for label, cp in plans.items():
        t, report = _paired_times_s(cp, params, x, repeats)
        row = {
            "name": f"pipeline/{label}",
            "net": cfg.name,
            "topology_nodes": len(cp.schedules),
            "pool_nodes": sum(1 for s in cp.schedules if s.spec.is_pool),
            "streamed_layers": len(cp.streamed_names),
            "engines": sorted(set(cp.engine_table().values())),
            "fused_blocks": len(cp.block_assignments),
            "timing_repeats": repeats,
            "wallclock_images_per_s": round(batch / t["fused"], 2),
            "eager_images_per_s": round(batch / t["eager"], 2),
            "fused_speedup_x": round(t["speedup"], 2),
            "model_images_per_s": round(cp.throughput()["images_per_s"], 1),
            "hbm_words_streamed": report.total_hbm_words,
            "hbm_words_per_image": report.total_hbm_words // batch,
            # Eq. 2 words over the WHOLE topology (pool nodes included —
            # 0 words each by construction, so this equals the streamed
            # total; the gate catches any node ever starting to charge)
            "topology_words_per_image": sum(
                cp.hbm_words_per_image().values()),
        }
        # whole-net Eq. 2 cross-check, hard fail: every topology node
        # dispatched, executed words == plan analytics per node AND per
        # fused res_block_int8 unit (Eq2MismatchError on drift)
        report.verify()
        row["block_hbm_words_per_image"] = sum(
            r["hbm_words_per_image"] for r in report.block_rows())
        if cp.streamed_names:
            sim_cfg, scale = cp.plan.sim_config(outputs_needed=8)
            sim = fifo_sim.simulate(sim_cfg, "credit")
            row.update({
                "sim_stall_cycles": sim.stall_cycles,
                "sim_cycles": sim.cycles,
                "sim_words_delivered": sum(sim.per_layer_weight_words)
                * scale,
                "sim_completed": sim.completed,
            })
        rows.append(row)
    return rows


def modelled_rows() -> List[Dict]:
    """Compile-only §VI model numbers for the paper's full-size nets."""
    rows = []
    for name in PAPER_NETS:
        cp = compiler.compile(CNN_CONFIGS[name], compiler.NX2100)
        # execution-free whole-net Eq. 2 cross-check (hard fail): the
        # shape-static stats the bound engines will report must equal
        # the plan analytics for 100% of the topology
        cp.eq2_report().verify()
        t = cp.throughput()
        words = sum(cp.hbm_words_per_image().values())
        rows.append({
            "name": f"model/{name}",
            "net": name,
            "topology_nodes": len(cp.schedules),
            "pool_nodes": sum(1 for s in cp.schedules if s.spec.is_pool),
            "streamed_layers": len(cp.streamed_names),
            "fused_blocks": len(cp.block_assignments),
            "model_images_per_s": round(t["images_per_s"], 1),
            "bottleneck": t["bottleneck"],
            "hbm_words_per_image": words,
            "topology_words_per_image": words,
        })
    return rows


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("batch", nargs="?", type=int, default=2)
    ap.add_argument("--repeats", type=int, default=5,
                    help="warm runs per timing (median reported)")
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="write the BENCH_pipeline.json artifact here")
    args = ap.parse_args()
    enable_compile_cache()

    rows = bench(args.batch, args.repeats) + modelled_rows()
    for row in rows:
        print("  ".join(f"{k}={v}" for k, v in row.items()))
    if args.json:
        artifact = {"benchmark": "pipeline_throughput",
                    "batch": args.batch, "rows": rows}
        with open(args.json, "w") as f:
            json.dump(artifact, f, indent=2, sort_keys=True)
        print(f"wrote {args.json}")


if __name__ == "__main__":
    main()
