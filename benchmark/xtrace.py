"""Reduction of a profiler trace (`.xplane.pb`, written by
`POST /debug/profile` on the live server) to what the per-layer
metrics read: per device the union of the intervals in which an
operation ran, the traced window, per XLA module its count and median
duration, the operations that took most time, and the longest idle
gaps.

    python benchmark/xtrace.py <profile dir> [--dump]

Prints one JSON object on its last line. `--dump` lists every plane
and line with its event count and commonest names instead, for
reading a trace by hand. Runs in a child of its own with
JAX_PLATFORMS=cpu: reading a trace needs `jax.profiler.ProfileData`,
not a device.

How a v5e trace is laid out (seen by hand, PERF.md Findings): one
plane per chip named `/device:TPU:<n>`; its line `XLA Modules` holds
one event per executed program, named `<jit name>(<fingerprint>)`
(`jit__decode_paged(...)`, `jit__prefill(...)` with one fingerprint a
bucket); its line `XLA Ops` holds one event per HLO operation, named
by its whole HLO line, the layer scan's `while` and its children both;
`Async XLA Ops` repeats copies and is not read. Idle gaps are gaps
between module-or-op intervals on a device and are named
`unattributed`: the host's spans are not on this clock yet (A2).
"""

from __future__ import annotations

import glob
import json
import os
import re
import statistics
import sys
from typing import Dict, List, Tuple

DEVICE_PLANE = "/device:TPU:"
MODULE_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"


CONTAINERS = ("while", "conditional", "call")


def short_name(hlo: str) -> str:
    """`%fusion.2 = f32[16,151936]{...} fusion(...)` ->
    `%fusion.2 fusion f32[16,151936]`: the trace names an operation by
    its whole HLO line."""
    name, sep, rest = hlo.partition(" = ")
    if not sep:
        return hlo[:80]
    m = re.match(r"(\(?[a-z0-9]+\[[0-9,]*\])", rest)
    shape = m.group(1) if m else ""
    k = re.search(r"[)}\]] ([a-z][a-z\-]*)\(", rest)
    return f"{name} {k.group(1) if k else ''} {shape}"[:80]


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merge overlapping [start, end) intervals."""
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def reduce_events(devices: Dict[str, Dict[str, List[Tuple[str, float, float]]]],
                  window: Tuple[float, float]) -> Dict:
    """`devices`: plane name -> {"modules": [(name, start_s, dur_s)],
    "ops": [...]}; `window`: (start_s, end_s) of the traced stretch."""
    window_s = max(window[1] - window[0], 0.0)
    busy_by_device, gaps = [], []
    modules: Dict[str, List[float]] = {}
    ops: Dict[str, float] = {}
    for plane in sorted(devices):
        ev = devices[plane]
        source = ev["ops"] or ev["modules"]
        merged = union([(s, s + d) for _, s, d in source])
        busy_by_device.append(sum(e - s for s, e in merged))
        starts = sorted((s, n) for n, s, _ in ev["modules"])
        for (_, e0), (s1, _) in zip(merged, merged[1:]):
            nxt = next((n for s, n in starts if s >= s1 - 1e-9), None)
            label = "unattributed" + (f":before:{nxt.split('(')[0]}"
                                      if nxt else "")
            gaps.append((label, s1 - e0))
        if plane == sorted(devices)[0]:
            for n, _, d in ev["modules"]:
                modules.setdefault(n, []).append(d)
            for n, _, d in ev["ops"]:
                n = short_name(n)
                if n.split(" ")[1:2] and n.split(" ")[1] in CONTAINERS:
                    continue        # its time is its children's
                ops[n] = ops.get(n, 0.0) + d
    gaps.sort(key=lambda g: -g[1])
    return {
        "window_s": window_s,
        "busy_by_device": busy_by_device,
        "busy_s": (sum(busy_by_device) / len(busy_by_device)
                   if busy_by_device else 0.0),
        "modules": {n: {"count": len(d), "median_s": statistics.median(d),
                        "total_s": sum(d)} for n, d in modules.items()},
        "device_ops": sorted(([n, s] for n, s in ops.items()),
                             key=lambda x: -x[1])[:10],
        "idle_gaps": [[label, d] for label, d in gaps[:10]],
    }


def find_xplane(profile_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        profile_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise SystemExit(f"no .xplane.pb under {profile_dir}")
    return files[-1]


def read_planes(path: str):
    from jax.profiler import ProfileData
    return ProfileData.from_file(path).planes


def main(argv=None) -> int:
    argv = list(argv or sys.argv[1:])
    dump = "--dump" in argv
    path = find_xplane([a for a in argv if not a.startswith("--")][0])
    devices: Dict[str, Dict[str, list]] = {}
    lo, hi = float("inf"), 0.0
    listing = []
    for plane in read_planes(path):
        is_dev = plane.name.startswith(DEVICE_PLANE)
        for line in plane.lines:
            names: Dict[str, List[float]] = {}
            n = 0
            keep = None
            if is_dev and line.name in (MODULE_LINE, OPS_LINE):
                keep = devices.setdefault(
                    plane.name, {"modules": [], "ops": []})[
                        "modules" if line.name == MODULE_LINE else "ops"]
            for ev in line.events:
                n += 1
                s, d = ev.start_ns * 1e-9, ev.duration_ns * 1e-9
                if keep is not None:
                    # the traced stretch is what the DEVICE planes span:
                    # host planes run 0.3-0.8 s longer (start/stop_trace)
                    lo, hi = min(lo, s), max(hi, s + d)
                    keep.append((ev.name, s, d))
                if dump:
                    t = names.setdefault(ev.name, [0, 0.0])
                    t[0] += 1
                    t[1] += d
            if dump:
                top = sorted(names.items(), key=lambda kv: -kv[1][1])[:12]
                listing.append({"plane": plane.name, "line": line.name,
                                "events": n, "top": [
                                    [k[:100], v[0], round(v[1], 6)]
                                    for k, v in top]})
    if dump:
        print(json.dumps({"file": path, "device_extent_s": hi - lo,
                          "lines": listing}, indent=1))
        return 0
    if not devices:
        raise SystemExit(f"no {DEVICE_PLANE}* plane in {path}")
    print(json.dumps(reduce_events(devices, (lo, hi))))
    return 0


if __name__ == "__main__":
    sys.exit(main())
