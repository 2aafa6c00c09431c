#!/usr/bin/env python3
"""Does the marker's clock alignment hold?  One traced run of a cell, with
each program execution on the first chip set against the dispatcher's
``launch`` span that started it.

    python3 bench/clock_check.py --workload <cell> --seed <n> --seconds 4

The run is ``bench/run.py``'s ``--trace 1`` run, in this process; this
script keeps the aligned host spans and the loaded trace that the run's
reduction reads.  After the marker's alignment each execution should start
on the device no earlier than its ``launch`` span starts.  With
``--seconds`` at most ``run.TRACE_SECONDS`` the whole window is traced, and
the result line's end-to-end metrics, read here over that window too, are
those of a traced run: beside a ``--trace 0`` run of the same length they
give the cost of tracing.  Prints one JSON line: the run's result and
``clock``, the execution count, the share of executions that start at or
after their launch span's start, and quantiles in microseconds of device
start minus launch start and minus launch end, over all executions and
over those that found the device idle.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import statistics
import sys
from typing import Dict, List, Sequence, Tuple

from benchlib import spec
from benchlib import trace as tr


def launch_offsets(modules: Sequence[Tuple[str, int, int]],
                   spans: Sequence[Tuple[str, int, int]],
                   marker: str) -> List[Dict[str, int]]:
    """Pair, in order, the program executions that follow the first
    ``marker`` execution with the ``launch`` spans that start after it,
    everything on the trace's clock in ns.  Per pair: device start less
    launch start and less launch end, and whether the device was idle
    when the launch span ended."""
    mark = min(s for n, s, _ in modules if n == marker)
    execs = sorted((s, s + d) for n, s, d in modules
                   if n != marker and s > mark)
    launches = sorted((s, e) for n, s, e in spans
                      if n == "launch" and s > mark)
    out, prev_end = [], mark
    for (d0, d1), (l0, l1) in zip(execs, launches):
        out.append({"after_start": d0 - l0, "after_end": d0 - l1,
                    "idle": prev_end <= l1})
        prev_end = max(prev_end, d1)
    return out


def quantiles_us(values_ns: Sequence[int]) -> Dict[str, float]:
    v = sorted(values_ns)
    if not v:
        return {}
    q = statistics.quantiles(v, n=20) if len(v) > 1 else [v[0]] * 19
    return {"min": v[0] / 1e3, "p5": q[0] / 1e3, "p25": q[4] / 1e3,
            "p50": q[9] / 1e3, "p75": q[14] / 1e3, "p95": q[18] / 1e3,
            "max": v[-1] / 1e3}


def clock_report(pairs: List[Dict[str, int]]) -> Dict:
    idle = [p for p in pairs if p["idle"]]
    ok = sum(1 for p in pairs if p["after_start"] >= 0)
    out = {"executions": len(pairs), "idle_executions": len(idle),
           "start_after_launch_start": ok / len(pairs) if pairs else None}
    for key in ("start", "end"):
        out[f"start_minus_launch_{key}_us"] = quantiles_us(
            [p[f"after_{key}"] for p in pairs])
        out[f"idle_start_minus_launch_{key}_us"] = quantiles_us(
            [p[f"after_{key}"] for p in idle])
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    import run as bench_run
    cell = spec.load_cell(args.workload)
    cell = dataclasses.replace(cell,
                               per_layer=cell.per_layer + cell.end_to_end)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        bench_run.log(f"{cell.name} needs {cell.chips} TPU chip(s), JAX "
                      f"sees {len(devices)} {devices[0].platform}")
        return 3
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    kept: Dict = {}
    reduce = tr.reduce

    def keep(loaded, fams, window, host_spans=()):
        kept.update(loaded=loaded, fams=fams, spans=list(host_spans))
        return reduce(loaded, fams, window, host_spans)

    tr.reduce = keep
    result = bench_run.run_cell(cell, args.seed, args.seconds, True,
                                devices[:cell.chips], bench_run.T_START)
    first = sorted(kept["loaded"]["devices"])[0]
    result["clock"] = clock_report(launch_offsets(
        kept["loaded"]["devices"][first]["modules"], kept["spans"],
        kept["fams"]["marker_module"]))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
