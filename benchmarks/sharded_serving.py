"""Sharded-serving benchmark: the mesh-pipelined engine vs one stage.

Partitions each executable mini net 4 ways
(``CompiledPipeline.partition``), runs the
:class:`~repro.runtime.sharded_serving.ShardedCnnServingEngine` on a
FORCED 4-device host-platform CPU mesh, and reports both sides of the
acceptance bar:

  * **modelled throughput** (gated): the per-layer cycle model under the
    M/(M + S - 1) pipeline fill law — ``sharded_images_per_s``,
    ``scaling_efficiency`` and ``sharded_speedup_x`` from
    ``StagePartition.modelled_throughput(M=32)``.  These are
    deterministic compiler outputs (like ``model_images_per_s`` in the
    pipeline benchmark): the speedup bar (>= 2.5x over the 1-stage
    model on the mini resnets) is asserted here and the first two join
    the bench_diff gate.  Forced host devices TIME-SLICE one CPU, so
    wall clock cannot show parallel speedup in CI — the cycle model is
    the paper-facing claim, the wall numbers below are reported
    ungated;
  * **measured serving** (ungated): wall-clock images/s of the sharded
    engine on the forced mesh, plus the §V-A credit high-water mark;
  * **bit identity** (asserted): every request's sharded logits equal
    sequential ``run()`` exactly.

  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  PYTHONPATH=src python benchmarks/sharded_serving.py \
      [--requests N] [--smoke] [--json BENCH_sharded.json]

(The script forces the device count itself when XLA_FLAGS doesn't
already; ``--json`` writes the artifact CI uploads and diffs —
bench_diff.py gates ``sharded_images_per_s`` / ``scaling_efficiency``
at >5% regression, with ``topology_nodes`` resetting the baseline on
deliberate graph changes.)
"""
from __future__ import annotations

import os

_FORCE = "--xla_force_host_platform_device_count=8"
if "xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " " + _FORCE).strip()

import argparse                                        # noqa: E402
import json                                            # noqa: E402
import sys                                             # noqa: E402
import time                                            # noqa: E402
from typing import Dict, List                          # noqa: E402

import jax                                             # noqa: E402
import numpy as np                                     # noqa: E402

from repro import compiler                             # noqa: E402
from repro.configs.cnn import (mini_mobilenet,         # noqa: E402
                               mini_resnet18, mini_resnet50)
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import compat_make_mesh         # noqa: E402
from repro.models.cnn import (cnn_input_shape,         # noqa: E402
                              init_cnn_params)

N_STAGES = 4
MICROBATCH = 2
ROUND_MB = 32                # modelled round: amortizes the S-1 bubble
SPEEDUP_BAR = 2.5            # acceptance: modelled sharded vs 1-stage
REQ_SIZES = (1, 3, 2, 5)     # mixed request sizes, cycled

#: name -> (config, speedup bar asserted?).  The resnets carry the
#: acceptance bar; the depthwise net rides for dwconv coverage (its
#: dw/pw alternation balances less evenly at this depth).
NETS = {
    "mini_resnet18": (lambda: mini_resnet18(hw=8, width=16, stages=4),
                      True),
    "mini_resnet50": (lambda: mini_resnet50(hw=8, width=16, stages=4),
                      True),
    "mini_mobilenet": (lambda: mini_mobilenet(hw=8, width=32, blocks=6),
                       False),
}


def make_requests(cfg, n_requests: int) -> List[np.ndarray]:
    rng = np.random.default_rng(0)
    shape = cnn_input_shape(cfg, 1)[1:]
    return [rng.integers(-127, 128,
                         size=(REQ_SIZES[i % len(REQ_SIZES)],) + shape,
                         dtype=np.int16).astype(np.int8)
            for i in range(n_requests)]


def bench_net(name: str, cfg_fn, assert_bar: bool,
              n_requests: int) -> Dict:
    cfg = cfg_fn()
    cp = compiler.compile(cfg, compiler.TPU_INTERPRET)
    params = init_cnn_params(jax.random.PRNGKey(0), cfg)
    part = cp.partition(N_STAGES)
    model = part.modelled_throughput(ROUND_MB)

    mesh = compat_make_mesh((N_STAGES,), ("model",))
    requests = make_requests(cfg, n_requests)
    images = sum(len(r) for r in requests)
    with cp.serve_sharded(params, mesh=mesh, microbatch=MICROBATCH,
                          round_microbatches=8) as eng:
        eng.serve(requests[:2])                        # warm
        t0 = time.perf_counter()
        outs, rep = eng.serve(requests)
        wall = time.perf_counter() - t0

    bit_identical = all(
        np.array_equal(o, np.asarray(cp.run(params, r)[0]))
        for o, r in zip(outs, requests))
    if not bit_identical:
        raise SystemExit(f"{name}: sharded outputs != sequential run()")
    if assert_bar and model["sharded_speedup_x"] < SPEEDUP_BAR:
        raise SystemExit(
            f"{name}: modelled sharded speedup "
            f"{model['sharded_speedup_x']:.2f}x under the "
            f"{SPEEDUP_BAR}x acceptance bar")

    return {
        "name": f"sharded/{name}",
        "net": cfg.name,
        "topology_nodes": len(cp.schedules),
        "n_stages": N_STAGES,
        "round_microbatches": ROUND_MB,
        "microbatch": MICROBATCH,
        "balance": round(part.balance, 3),
        "max_stage_cycles": part.max_stage_cycles,
        "total_cycles": part.total_cycles,
        # gated modelled metrics (deterministic cycle model + fill law)
        "sharded_images_per_s": round(model["sharded_images_per_s"], 2),
        "scaling_efficiency": round(model["scaling_efficiency"], 4),
        "one_stage_images_per_s": round(
            model["one_stage_images_per_s"], 2),
        "sharded_speedup_x": round(model["sharded_speedup_x"], 3),
        "hbm_words_per_image": rep.hbm_words_per_image,
        # measured on the forced (time-sliced) mesh — ungated CI noise
        "wall_images_per_s": round(images / wall, 2) if wall > 0 else 0.0,
        "requests": len(requests),
        "images": images,
        "bit_identical": bit_identical,
        # everything else (rounds, credit high-water mark, latency
        # percentiles, metrics, stall attribution) rides in the
        # serialized report — no hand-rolled duplicate dict
        "report": rep.to_dict(),
    }


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized run (fewer requests)")
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="write the BENCH_sharded.json artifact here")
    args = ap.parse_args()
    enable_compile_cache()
    n_requests = min(args.requests, 6) if args.smoke else args.requests

    if jax.device_count() < N_STAGES:
        print(f"need {N_STAGES} devices, have {jax.device_count()} "
              f"(XLA_FLAGS was already set without the forced host "
              f"device count?)", file=sys.stderr)
        raise SystemExit(2)

    rows = [bench_net(name, fn, bar, n_requests)
            for name, (fn, bar) in NETS.items()]
    for row in rows:
        print("  ".join(f"{k}={v}" for k, v in row.items()
                        if k != "report"))
    if args.json:
        artifact = {"benchmark": "sharded_serving", "rows": rows}
        with open(args.json, "w") as f:
            json.dump(artifact, f, indent=2, sort_keys=True)
        print(f"wrote {args.json}")


if __name__ == "__main__":
    main()
