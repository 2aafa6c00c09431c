"""From a profiler trace to the numbers the per-layer metrics read.

``load`` reads the ``.xplane.pb`` a run wrote with ``jax.profiler`` and
keeps, for each TPU device plane, its operations (line ``XLA Ops``) and
program executions (line ``XLA Modules``) as ``(base_name, start_ns,
duration_ns)``.  ``reduce`` turns that, a window and the host spans into:

* ``busy_s``: the union of the device's operation intervals inside the
  window, averaged over the chips; ``window_s`` the window's length;
* ``family_s``: per kernel family (``families.json``), the summed
  device time of its kernels' events inside the window, all chips;
* ``dispatches``: programs started on the first chip inside the window;
* ``collective_s`` / ``collective_only_s``: time in which a collective
  runs, and in which one runs while no other operation does, averaged
  over the chips;
* ``device_ops``: the ten operations that took most device time;
* ``idle_gaps``: device idle time inside the window by what the serving
  engine's dispatcher was doing then (its host span open at the gap's
  midpoint, ``waiting for requests`` where none was), averaged over the
  chips, the ten largest.

Host spans are taken on ``time.perf_counter`` and put on the trace's
clock by a marker program run on the idle device just before the window
opens: its start on the device, less the host time it was launched at,
is the offset between the two clocks (good to the launch latency, some
tens of microseconds).
"""
from __future__ import annotations

import bisect
import glob
import re
import sys
from typing import Dict, Iterable, List, Sequence, Tuple

_SUFFIX = re.compile(r"(\.(\d+|clone))+$")
_DEVICE = re.compile(r"^/device:TPU:\d+$")

Interval = Tuple[int, int]


def base_name(hlo: str) -> str:
    """``%conv2d_int8.14 = s32[...] custom-call(...)`` -> ``conv2d_int8``."""
    name = hlo.split(" = ", 1)[0].lstrip("%")
    return sys.intern(_SUFFIX.sub("", name))


def load(trace_dir) -> Dict:
    """``{"devices": {plane: {"ops": [...], "modules": [...]}}}`` of the
    newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(paths[-1])
    devices = {}
    for plane in pd.planes:
        if not _DEVICE.match(plane.name):
            continue
        rec = {"ops": [], "modules": []}
        for line in plane.lines:
            key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(line.name)
            if key is None:
                continue
            names: Dict[str, str] = {}
            out = rec[key]
            for e in line.events:
                n = e.name
                b = names.get(n)
                if b is None:
                    b = names[n] = (base_name(n) if key == "ops"
                                    else sys.intern(n.split("(", 1)[0]))
                out.append((b, int(e.start_ns), int(e.duration_ns)))
        devices[plane.name] = rec
    if not devices:
        raise ValueError(f"no TPU device plane in {paths[-1]}")
    return {"devices": devices}


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, merged ``[start, end)`` intervals."""
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(intervals: Sequence[Interval]) -> int:
    return sum(e - s for s, e in intervals)


def intersect(a: Sequence[Interval], b: Sequence[Interval]) -> int:
    """Length of the overlap of two merged interval lists."""
    i = j = total = 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < e:
            total += e - s
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def _clip(events, lo: int, hi: int):
    for name, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if a < b:
            yield name, a, b


def _matches(name: str, prefixes: Sequence[str]) -> bool:
    return any(name.startswith(p) for p in prefixes)


def marker_offset_ns(tr: Dict, marker: str, host_s: float) -> int:
    """Trace time minus host time, from the first ``marker`` program on
    the first chip, launched at ``host_s`` on the host's clock."""
    first = sorted(tr["devices"])[0]
    starts = [s for name, s, _ in tr["devices"][first]["modules"]
              if name == marker]
    if not starts:
        raise ValueError(f"no {marker} program in the trace")
    return min(starts) - int(round(host_s * 1e9))


def label_gaps(gaps: Sequence[Interval], spans: Sequence[Tuple[str, int, int]]
               ) -> Dict[str, int]:
    """Idle nanoseconds per label: the host span open at each gap's
    midpoint (the innermost, i.e. latest-starting), else ``waiting for
    requests``."""
    spans = sorted(spans, key=lambda x: x[1])
    starts = [s for _, s, _ in spans]
    out: Dict[str, int] = {}
    for a, b in gaps:
        mid = (a + b) // 2
        label = "waiting for requests"
        k = bisect.bisect_right(starts, mid)
        for name, s, e in reversed(spans[max(0, k - 64):k]):
            if s <= mid < e:
                label = name
                break
        out[label] = out.get(label, 0) + (b - a)
    return out


def reduce(tr: Dict, fams: Dict, window_ns: Interval,
           host_spans: Sequence[Tuple[str, int, int]] = ()) -> Dict:
    lo, hi = window_ns
    planes = sorted(tr["devices"])
    chips = len(planes)
    kernels = fams["kernels"]
    containers = fams["containers"]
    collectives = fams["collectives"]
    marker = fams["marker_module"]
    busy = coll = coll_only = 0
    family_ns: Dict[str, int] = {}
    op_ns: Dict[str, int] = {}
    idle: Dict[str, int] = {}
    for plane in planes:
        ops = list(_clip(tr["devices"][plane]["ops"], lo, hi))
        busy_iv = union((a, b) for _, a, b in ops)
        busy += length(busy_iv)
        c_iv = union((a, b) for n, a, b in ops if _matches(n, collectives))
        other = union((a, b) for n, a, b in ops
                      if not _matches(n, collectives)
                      and not _matches(n, containers))
        coll += length(c_iv)
        coll_only += length(c_iv) - intersect(c_iv, other)
        for n, a, b in ops:
            if _matches(n, containers):
                continue
            op_ns[n] = op_ns.get(n, 0) + (b - a)
            for fam, prefixes in kernels.items():
                if _matches(n, prefixes):
                    family_ns[fam] = family_ns.get(fam, 0) + (b - a)
        gaps, t = [], lo
        for a, b in busy_iv:
            if a > t:
                gaps.append((t, a))
            t = max(t, b)
        if t < hi:
            gaps.append((t, hi))
        for k, v in label_gaps(gaps, host_spans).items():
            idle[k] = idle.get(k, 0) + v
    first = tr["devices"][planes[0]]["modules"]
    dispatches = sum(1 for n, s, _ in first if lo <= s < hi and n != marker)
    top_ops = sorted(op_ns.items(), key=lambda kv: -kv[1])[:10]
    top_idle = sorted(idle.items(), key=lambda kv: -kv[1])[:10]
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy / chips / 1e9,
        "chips": chips,
        "family_s": {k: v / 1e9 for k, v in family_ns.items()},
        "dispatches": dispatches,
        "collective_s": coll / chips / 1e9,
        "collective_only_s": coll_only / chips / 1e9,
        "device_ops": [[n, v / 1e9] for n, v in top_ops],
        "idle_gaps": [[n, v / chips / 1e9] for n, v in top_idle],
    }
