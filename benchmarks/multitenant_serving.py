"""Multi-tenant multi-network serving benchmark: one front door, three
compiled pipelines, weighted-fair delivery.

Drives :class:`~repro.runtime.frontend.MultiTenantFrontEnd` over the
executable mini ResNet-18 + ResNet-50 + MobileNet pipelines with three
workloads:

  * **bit identity** (hard gate, not a timing): mixed three-network
    closed- AND open-loop traffic through the front door must be
    BIT-IDENTICAL per request to each network's sequential
    ``CompiledPipeline.run()`` — the scheduler reorders service, never
    an output bit.  The MobileNet engine runs with the adaptive
    microbatch ladder so shape growth/shrink is exercised under load.
    Any mismatch exits non-zero;
  * **weighted fairness** (1:4): two tenants on one network under
    sustained backlog (front-end-wide ``max_outstanding=1`` serializes
    service, so the backlog pools at the DRR tier).  A mid-run snapshot
    measures the delivered split — the drained end state always
    converges to the submitted ratio and proves nothing.  The run
    hard-fails unless the ratio lands within 20% of the weights;
  * **deadline attribution**: one tenant with an unmeetable 0 ms
    deadline (miss rate pinned at 1.0) and one with an effectively
    infinite deadline (pinned at 0.0) — deliberately extreme so the
    per-tenant ``deadline_miss_rate`` rows are STABLE for the diff
    gate, plus the promotion counter showing the overdue tenant really
    jumped the line.

Wall-clock numbers are interpret-mode Pallas on CPU — relative
comparison only.  ``bench_diff.py`` gates ``tenant_images_per_s``
(down) and ``deadline_miss_rate`` (up) under METRIC_THRESHOLD_FLOOR
(both wall-clock-derived; the extreme deadlines keep the miss rates
exactly 0.0 / 1.0 so that gate only fires on a real behavior change).

  PYTHONPATH=src python benchmarks/multitenant_serving.py \
      [--requests N] [--smoke] [--json BENCH_multitenant.json]
"""
from __future__ import annotations

import argparse
import json
import time
from typing import Dict, List, Tuple

import jax
import numpy as np

from repro import compiler
from repro.configs.cnn import mini_mobilenet, mini_resnet18, mini_resnet50
from repro.launch.compile_cache import enable_compile_cache
from repro.models.cnn import cnn_input_shape, init_cnn_params
from repro.runtime.frontend import MultiTenantFrontEnd

NET_FACTORIES = {
    "mini_resnet18": lambda: mini_resnet18(hw=8, width=16, stages=4),
    "mini_resnet50": lambda: mini_resnet50(hw=8, width=16, stages=4),
    "mini_mobilenet": lambda: mini_mobilenet(hw=8, width=16, blocks=4),
}
REQ_SIZES = (1, 2, 1, 4)


def build_nets() -> Dict[str, Tuple]:
    out = {}
    for i, (name, factory) in enumerate(NET_FACTORIES.items()):
        cfg = factory()
        cp = compiler.compile(cfg, compiler.TPU_INTERPRET)
        out[name] = (cfg, cp, init_cnn_params(jax.random.PRNGKey(i), cfg))
    return out


def make_requests(cfg, n_requests: int, seed: int) -> List[np.ndarray]:
    rng = np.random.default_rng(seed)
    shape = cnn_input_shape(cfg, 1)[1:]
    return [rng.integers(-127, 128, size=(REQ_SIZES[i % len(REQ_SIZES)],)
                         + shape, dtype=np.int16).astype(np.int8)
            for i in range(n_requests)]


def reference_rows(cp, params, batches) -> List[np.ndarray]:
    big = np.concatenate(batches, axis=0)
    ref = np.asarray(cp.run(params, big)[0])
    out, off = [], 0
    for b in batches:
        out.append(ref[off:off + len(b)])
        off += len(b)
    return out


def bit_identity(nets, n_requests: int) -> Dict:
    """Mixed closed+open traffic through the front door vs per-network
    sequential run() — hard-fails on the first differing bit."""
    engines = {}
    for name, (cfg, cp, params) in nets.items():
        kw = {"adaptive": True} if name == "mini_mobilenet" else {}
        engines[name] = cp.serve(params, microbatch=4, credits=2,
                                 queue_depth=4, **kw)
    fe = MultiTenantFrontEnd(engines, max_outstanding=6)
    per_net = {name: make_requests(cfg, n_requests, seed=10 + i)
               for i, (name, (cfg, _, _)) in enumerate(nets.items())}
    for i, name in enumerate(nets):
        fe.register_tenant(f"t_{name}", network=name, weight=float(i + 1))
    half = n_requests // 2
    t0 = time.perf_counter()
    with fe:
        closed, _ = fe.serve([(f"t_{n}", b) for n in per_net
                              for b in per_net[n][:half]])
        open_reqs = [(n, i, fe.submit(f"t_{n}", per_net[n][i]))
                     for i in range(half, n_requests) for n in per_net]
        fe.drain()
        rep = fe.report()
    wall = time.perf_counter() - t0
    want = {n: reference_rows(nets[n][1], nets[n][2], per_net[n])
            for n in per_net}
    mismatches = 0
    idx = 0
    for n in per_net:
        for i in range(half):
            if not np.array_equal(closed[idx], want[n][i]):
                mismatches += 1
            idx += 1
    for n, i, req in open_reqs:
        if not np.array_equal(req.result(), want[n][i]):
            mismatches += 1
    if mismatches:
        raise SystemExit(
            f"BIT-IDENTITY FAILED: {mismatches} request(s) differ from "
            f"the sequential run() reference")
    shapes = {}
    for name, eng in engines.items():
        shapes[name] = eng.report().microbatch_shapes
    return {
        "name": "multitenant/bit_identity",
        "networks": len(nets),
        "requests": rep.requests,
        "images": rep.images,
        "bit_identical": 1,
        "frontend_images_per_s": round(rep.images / wall, 2),
        "adaptive_shapes_mobilenet": shapes["mini_mobilenet"],
        "report": rep.to_dict(),
    }


def weighted_fairness(nets, n_each: int) -> List[Dict]:
    """1:4 weights under sustained backlog; mid-run delivered split must
    track the weights within 20% (hard gate)."""
    cfg, cp, params = nets["mini_resnet18"]
    fe = MultiTenantFrontEnd(
        {"mini_resnet18": cp.serve(params, microbatch=1, credits=1,
                                   queue_depth=1)},
        max_outstanding=1)
    fe.register_tenant("light", network="mini_resnet18", weight=1.0)
    fe.register_tenant("heavy", network="mini_resnet18", weight=4.0)
    batches = make_requests(cfg, n_each, seed=20)
    batches = [b[:1] for b in batches]            # unit cost per request
    with fe:
        for b in batches:
            fe.submit("light", b)
            fe.submit("heavy", b)
        # mid-run snapshot, no earlier than 22 deliveries: right after a
        # light pick the DRR split reads 4k/(k+1), which only clears the
        # 20% band once k >= 4 — snapshotting sooner would flake on
        # quantization, not on fairness
        snapshot_at = min(2 * n_each - 4, max(22, n_each))
        while True:
            snap = fe.report()
            done = {r["tenant"]: r["images"] for r in snap.tenant_rows}
            if sum(done.values()) >= snapshot_at:
                break
            time.sleep(0.005)
        fe.drain()
        final = fe.report()
    ratio = done["heavy"] / max(1, done["light"])
    if not (4.0 * 0.8 <= ratio <= 4.0 * 1.2):
        raise SystemExit(
            f"WEIGHTED FAIRNESS FAILED: delivered ratio {ratio:.2f} "
            f"outside 20% of the 4.0 weight ratio ({done})")
    rows = []
    wall = final.wall_s
    for r in final.tenant_rows:
        rows.append({
            "name": f"multitenant/fairness/{r['tenant']}",
            "weight": r["weight"],
            "requests": r["requests"],
            "tenant_images_per_s": round(r["images_per_s"], 2),
            "deadline_miss_rate": r["deadline_miss_rate"],
            "p50_ms": round(r["p50_ms"], 3),
            "p99_ms": round(r["p99_ms"], 3),
        })
    rows.append({
        "name": "multitenant/fairness_summary",
        "weight_ratio": 4.0,
        "delivered_ratio_mid_run": round(ratio, 3),
        "jain_fairness_mid_run": round(snap.fairness, 4),
        "wall_s": round(wall, 4),
        "report": final.to_dict(),
    })
    return rows


def deadline_attribution(nets, n_each: int) -> List[Dict]:
    """Extreme deadlines → stable miss rates (1.0 / 0.0) for the diff
    gate, plus promotion evidence."""
    cfg, cp, params = nets["mini_mobilenet"]
    fe = MultiTenantFrontEnd(
        {"mini_mobilenet": cp.serve(params, microbatch=1, credits=1,
                                    queue_depth=1)},
        max_outstanding=1)
    fe.register_tenant("bulk", network="mini_mobilenet", weight=8.0,
                       deadline_ms=1e9)           # never missable
    fe.register_tenant("rt", network="mini_mobilenet", weight=1.0,
                       deadline_ms=0.0)           # never meetable
    batches = make_requests(cfg, n_each, seed=30)
    batches = [b[:1] for b in batches]
    with fe:
        for b in batches:
            fe.submit("bulk", b)
            fe.submit("rt", b)
        fe.drain()
        rep = fe.report()
    rows = []
    for r in rep.tenant_rows:
        rows.append({
            "name": f"multitenant/deadline/{r['tenant']}",
            "weight": r["weight"],
            "deadline_ms": r["deadline_ms"],
            "requests": r["requests"],
            "tenant_images_per_s": round(r["images_per_s"], 2),
            "deadline_miss_rate": r["deadline_miss_rate"],
            "deadline_misses": r["deadline_misses"],
        })
    want = {"rt": 1.0, "bulk": 0.0}
    for row in rows:
        tenant = row["name"].rsplit("/", 1)[1]
        if row["deadline_miss_rate"] != want[tenant]:
            raise SystemExit(
                f"DEADLINE ATTRIBUTION FAILED: {tenant} miss rate "
                f"{row['deadline_miss_rate']} != {want[tenant]}")
    if rep.promotions <= 0:
        raise SystemExit("DEADLINE ATTRIBUTION FAILED: the overdue "
                         "tenant was never promoted")
    rows.append({
        "name": "multitenant/deadline_summary",
        "promotions": rep.promotions,
        "report": rep.to_dict(),
    })
    return rows


def bench(n_requests: int = 12, n_fair: int = 24) -> List[Dict]:
    nets = build_nets()
    rows = [bit_identity(nets, n_requests)]
    rows.extend(weighted_fairness(nets, n_fair))
    rows.extend(deadline_attribution(nets, max(6, n_fair // 3)))
    return rows


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--requests", type=int, default=12,
                    help="bit-identity requests per network")
    ap.add_argument("--fair-requests", type=int, default=24,
                    help="per-tenant requests in the fairness run")
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized run (fewer requests)")
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="write the BENCH_multitenant.json artifact here")
    args = ap.parse_args()
    enable_compile_cache()
    n_requests, n_fair = args.requests, args.fair_requests
    if args.smoke:
        n_requests = min(n_requests, 8)
        n_fair = min(n_fair, 20)

    rows = bench(n_requests, n_fair)
    for row in rows:
        print("  ".join(f"{k}={v}" for k, v in row.items()
                        if k != "report"))
    if args.json:
        artifact = {"benchmark": "multitenant_serving", "rows": rows}
        with open(args.json, "w") as f:
            json.dump(artifact, f, indent=2, sort_keys=True)
        print(f"wrote {args.json}")


if __name__ == "__main__":
    main()
