#!/usr/bin/env python3
"""One run of one benchmark cell with the server's start-up surfaces
kept: where `setup_s` went, by the program's own clock.

    python scripts/startup_split.py --workload <cell> --seed <n> \
        [--seconds 51] [--trace 1] [--cold]

Runs `benchmark/run.py` as the driver does (its last line of output is
the benchmark's own result line) and, once warm-up is over and before
the window, reads what only the live server can say: the `startup`
block of `/health`, the engine's and the router's start-up gauges,
compile seconds by stage and by program and the cache events of
`/debug/programs` (without the instruction paths), and the
`program_compiled` flight events. It prints a summary and writes all
of it to `chiprun_out/startup/<cell>.<seed>.json`. `--cold` empties
the compile cache directory first. Like the benchmark, this parent
never imports JAX; through the chip tool it measures, here it only
rehearses (`--rehearse-cpu`: the toy cell of tests/benchmark/fixture).
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

import procs  # noqa: E402
import run as bench  # noqa: E402

STARTUP = ("ome_engine_startup", "ome_engine_compile",
           "ome_router_startup")


def surfaces(served) -> dict:
    """What the server and the router say of their own start, now."""
    _, health = procs.http(served.engine_url + "/health")
    programs = served.programs()
    _, events = procs.http(served.engine_url + "/debug/events?n=4096")
    metrics = dict(procs.scrape(served.engine_url),
                   **procs.scrape(served.url))
    return {
        "setup_s": served.setup_s,
        "startup": health.get("startup"),
        "metrics": {k: v for k, v in metrics.items()
                    if k.startswith(STARTUP)},
        "compile": programs.get("compile"),
        "programs": [{k: p.get(k) for k in (
            "program", "source", "dispatches", "compile_s", "cache")}
            for p in programs["programs"]],
        "program_compiled": [
            {k: e.get(k) for k in ("t_mono", "program", "stage",
                                   "seconds", "cache")}
            for e in (events or {}).get("events", [])
            if e.get("event") == "program_compiled"],
    }


def summary(doc: dict) -> None:
    def r(x):
        return None if x is None else round(x, 3)
    startup = doc["startup"] or {}
    bench.say(phase="startup.split", setup_s=r(doc["setup_s"]),
              phases={p["name"]: r(p["end_s"] - p["start_s"])
                      for p in startup.get("phases", [])},
              ready_s=r(startup.get("ready_s")),
              first_request_s=r(startup.get("first_request_s")),
              router={k.split("ome_router_startup_")[1]: r(v)
                      for k, v in doc["metrics"].items()
                      if k.startswith("ome_router_startup")})
    comp = doc["compile"] or {}
    bench.say(phase="startup.compile",
              seconds={stage: {w: r(s) for w, s in by.items()}
                       for stage, by in comp.get("seconds", {}).items()},
              events=comp.get("events"), listeners=comp.get("listeners"),
              other={k: r(v) for k, v in (comp.get("other") or {}).get(
                  "compile_s", {}).items()})
    for p in doc["programs"]:
        bench.say(phase="startup.program", program=p["program"],
                  cache=p["cache"], dispatches=p["dispatches"],
                  compile_s=p["compile_s"] and {
                      k: r(v) for k, v in p["compile_s"].items()})
    twice = {}
    for e in doc["program_compiled"]:
        if e["stage"] in ("backend_compile", "cache_load"):
            twice.setdefault(e["program"], []).append(
                [e["stage"], r(e["seconds"])])
    bench.say(phase="startup.compiles_by_program",
              note="each program's backend_compile / cache_load events: "
              "one large pair a program, the small ones are helpers "
              "compiled on its thread before the next capture",
              events={k: v for k, v in twice.items() if k != "other"})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    ap.add_argument("--cold", action="store_true",
                    help="empty the compile cache directory first")
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args(argv)
    if args.cold:
        shutil.rmtree(bench.cache_dir(), ignore_errors=True)
        os.makedirs(bench.cache_dir(), exist_ok=True)
    out_dir = os.path.join(ROOT, "chiprun_out", "startup")
    os.makedirs(out_dir, exist_ok=True)
    window = bench.Served.window

    def window_after_dump(served, seed, seconds, overrides=None):
        doc = surfaces(served)
        doc.update(workload=args.workload, seed=seed, cold=args.cold)
        path = os.path.join(out_dir, f"{args.workload}.{seed}.json")
        with open(path, "w") as f:
            json.dump(doc, f, indent=1)
        summary(doc)
        return window(served, seed, seconds, overrides)

    bench.Served.window = window_after_dump
    kw = {}
    if args.rehearse_cpu:
        kw = dict(require_tpu=False, env_extra={"JAX_PLATFORMS": "cpu"},
                  bench_root=os.path.join(ROOT, "tests", "benchmark",
                                          "fixture"))
    return bench.main(["--workload", args.workload, "--seed",
                       str(args.seed), "--seconds", str(args.seconds),
                       "--trace", str(args.trace)], **kw)


if __name__ == "__main__":
    sys.exit(main())
