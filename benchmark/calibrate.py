#!/usr/bin/env python3
"""Readings that a limit, a rate or a bound is set from: many windows
behind ONE server start, then one check child over all of them.

    python benchmark/calibrate.py --workload <cell> --seeds 11,12,13 \\
        --seconds 15 [--rates 3,4,5,6] [--control] \\
        [--serve-extra=--quantization=int8] [--tag name]

Not part of a run of the benchmark. It is how `PERF.md`'s tables were
read: the knee sweep (`--rates`: one window a rate, with the share of
requests that met the TTFT and mean-gap limits and the TTFT of the
window's second half against its first), the gap statistics of sound
runs over a dozen seeds, the int8 reference control at the same
positions (`--control`), and the program's own lower-precision paths
(`--serve-extra`). The summary goes to
`chiprun_out/calibrate-<tag>.json`.
"""

from __future__ import annotations

import argparse
import json
import os
import random
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import run as bench  # noqa: E402
from stats import gaps, percentile  # noqa: E402

TTFT_LIMIT_S = 1.0        # the two limits that define the knee
MEAN_GAP_LIMIT_S = 0.1


def window_summary(ctx) -> dict:
    ok = [a for a in ctx["answers"] if not a.failed]
    ttft = [a.arrivals[0] - a.due for a in ok]
    half = ctx["t0"] + ctx["seconds"] / 2
    first = [a.arrivals[0] - a.due for a in ok if a.due < half]
    second = [a.arrivals[0] - a.due for a in ok if a.due >= half]
    all_gaps, met = [], 0
    for a in ok:
        g = gaps(a.arrivals)
        all_gaps += gaps(a.arrivals, until=ctx["t1"])
        mean_gap = sum(g) / len(g) if g else 0.0
        met += (a.arrivals[0] - a.due <= TTFT_LIMIT_S
                and mean_gap <= MEAN_GAP_LIMIT_S)
    tokens = sum(1 for a in ctx["answers"] for t in a.arrivals
                 if ctx["t0"] <= t <= ctx["t1"])
    return {
        "attempted": len(ctx["answers"]), "failed": len(ctx["failed"]),
        "met_both_share": met / max(len(ctx["answers"]), 1),
        "ttft_p50_ms": 1e3 * (percentile(ttft, 50) or 0),
        "ttft_p95_ms": 1e3 * (percentile(ttft, 95) or 0),
        "ttft_p50_first_half_ms": 1e3 * (percentile(first, 50) or 0),
        "ttft_p50_second_half_ms": 1e3 * (percentile(second, 50) or 0),
        "itl_p50_ms": 1e3 * (percentile(all_gaps, 50) or 0),
        "itl_p95_ms": 1e3 * (percentile(all_gaps, 95) or 0),
        "out_tokens_per_s": tokens / ctx["seconds"],
        "drain_s": max([a.arrivals[-1] for a in ok] + [ctx["t1"]])
        - ctx["t1"],
        "memory_peak_bytes": ctx["metrics_after"].get(
            "ome_engine_hbm_peak_bytes"),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--rates", default="")
    ap.add_argument("--zip", action="store_true",
                    help="pair seeds with rates instead of crossing them")
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--serve-extra", action="append", default=[])
    ap.add_argument("--tag", default="run")
    ap.add_argument("--bench-root", default=bench.ROOT,
                    help="a tree with its own BENCHMARK.json and data "
                         "files: a cell that is not in the benchmark yet")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    rates = [float(r) for r in args.rates.split(",")] if args.rates else [None]
    extra = [x for e in args.serve_extra for x in e.split("=")]
    served = bench.Served(args.workload, seeds[0], False, serve_extra=extra,
                          bench_root=args.bench_root)
    windows, groups = [], []
    try:
        pairs = list(zip(rates, seeds)) if args.zip else \
            [(r, s) for r in rates for s in seeds]
        for rate, seed in pairs:
            over = {"rate_rps": rate} if rate is not None else None
            ctx = served.window(seed, args.seconds, over)
            row = dict(window_summary(ctx), seed=seed, rate_rps=rate)
            bench.say(phase="calibrate", **row)
            windows.append(row)
            groups.append(bench.pick_samples(ctx["answers"],
                                             random.Random(seed)))
    finally:
        served.stop()
    out = bench.check(served, groups, args.control)
    for row, g in zip(windows, out.pop("groups")):
        row["check"] = g
        bench.say(phase="calibrate-check", seed=row["seed"],
                  rate_rps=row["rate_rps"], **g)
    summary = {"workload": args.workload, "serve_extra": extra,
               "seconds": args.seconds, "setup_s": served.setup_s,
               "device": served.device, "check": out, "windows": windows}
    path = os.path.join(bench.ROOT, "chiprun_out",
                        f"calibrate-{args.tag}.json")
    with open(path, "w") as f:
        json.dump(summary, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
