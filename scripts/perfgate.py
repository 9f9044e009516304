#!/usr/bin/env python
"""Bench regression gate: diff fresh bench.py output against a
history of BENCH_r*.json results (--history GLOB, or one --baseline
file) and fail on real regressions. The repo keeps no history of its
own any more (ROADMAP C6: the driver's paired runs and
PERF_LEDGER.jsonl are the gate); tests pass a fixture.

This script imports no JAX and must stay so: it runs bench.py as a
child process, and a parent that has touched JAX holds the chip the
child needs.

The BENCH files record best-of-N numbers per round, so run-to-run
noise is already partly squeezed out — but not gone. The gate is
therefore noise-aware by construction:

  * every metric has a relative tolerance band sized to how noisy it
    is (dispatch_ms jitters ~10% on a quiet box; best-of-3 decode
    throughput holds within ~3%);
  * fewer best-of samples widen the bands (a best-of-1 round proves
    little);
  * improvements never fail, and metrics missing from either side are
    skipped (rounds grew the schema over time) — the gate compares
    the intersection and says so.

A waiver file (JSON: [{"metric": ..., "reason": ...}]) turns a known,
accepted regression into a warning — the reason is printed every run
so waivers cannot rot silently.

--cost-table emits the fitted per-program cost table (step ms per
program variant from the newest round's breakdowns) — the calibration
artifact the fleet capacity simulator consumes (ROADMAP item 6).

Usage:
  python scripts/perfgate.py --history 'runs/BENCH_r*.json' --run
  python scripts/perfgate.py --history GLOB --bench-json out.json
  python scripts/perfgate.py --history GLOB --check-only
  make benchgate BENCH_HISTORY='runs/BENCH_r*.json'

Exit codes: 0 pass, 1 regression, 2 usage/configuration error.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# metric -> (higher_is_better, relative tolerance band at best_of>=3).
# Unlisted numeric metrics are reported but never gate (unknown noise
# profile ==> no false alarms from schema growth).
POLICY = {
    "value": (True, 0.05),
    "int8_tokens_per_sec": (True, 0.05),
    "int4_tokens_per_sec": (True, 0.05),
    "paged_decode_tokens_per_sec_batch64": (True, 0.05),
    "decode_effective_gbps": (True, 0.05),
    "hbm_copy_gbps": (True, 0.08),
    "prefill_mfu": (True, 0.05),
    "prefill_ms_batch32x128": (False, 0.08),
    "dispatch_ms": (False, 0.15),
}
# nested families gate too: per-mode decode step ms and per-K
# multistep throughput (keys like decode_ms_breakdown.int8.step)
NESTED_POLICY = (
    (re.compile(r"^decode_ms_breakdown\.\w+\.step$"), (False, 0.08)),
    (re.compile(r"^multistep\.\d+\.tokens_per_sec$"), (True, 0.06)),
    (re.compile(r"^multistep\.\d+\.step_ms$"), (False, 0.08)),
    # paged decode sweep (batch x pool dtype): throughput gates like
    # the other decode families; bytes/slot is a deterministic byte
    # model, so ANY growth is a pool-layout regression (band 0)
    (re.compile(r"^paged_sweep\.\w+\.\d+\.tokens_per_sec$"),
     (True, 0.06)),
    (re.compile(r"^paged_sweep\.\w+\.\d+\.hbm_per_slot_bytes$"),
     (False, 0.0)),
    # StepPlan composition matrix (bench.py composition,
    # docs/step-plan.md): per-cell throughput and accept rate gate
    # like the other decode families; degraded_steps is a composition
    # contract — ANY step where the planner dropped a feature in a
    # cell that ran clean before is a regression (band 0)
    (re.compile(r"^composition\.cells\.\w+\.tokens_per_sec$"),
     (True, 0.08)),
    (re.compile(r"^composition\.cells\.\w+\.accept_rate$"),
     (True, 0.10)),
    (re.compile(r"^composition\.cells\.\w+\.degraded_steps$"),
     (False, 0.0)),
    (re.compile(r"^composition\.composed_vs_best_single$"),
     (True, 0.08)),
    # structured-output sweep (bench.py structured,
    # docs/structured-outputs.md): per-cell throughput gates like the
    # composition cells; the headline masked-vs-unmasked ratio is the
    # device-resident-mask-table contract (ROADMAP item 4's >=0.9);
    # mask_apply_ms is host walk time per timed batch — noisy, so a
    # wide band, but a blowup means the grammar cache stopped hitting
    (re.compile(r"^structured\.cells\.\w+\.tokens_per_sec$"),
     (True, 0.08)),
    (re.compile(r"^structured\.cells\.\w+\.degraded_steps$"),
     (False, 0.0)),
    (re.compile(r"^structured\.structured_vs_unmasked$"),
     (True, 0.08)),
    (re.compile(r"^structured\.mask_build_ms$"), (False, 0.5)),
)


def flatten(parsed: dict, prefix: str = "") -> dict:
    """{dotted.key: float} over every numeric leaf."""
    out = {}
    for k, v in (parsed or {}).items():
        key = f"{prefix}{k}"
        if isinstance(v, dict):
            out.update(flatten(v, f"{key}."))
        elif isinstance(v, bool):
            continue
        elif isinstance(v, (int, float)):
            out[key] = float(v)
    return out


def policy_for(metric: str):
    if metric in POLICY:
        return POLICY[metric]
    for pat, pol in NESTED_POLICY:
        if pat.match(metric):
            return pol
    return None


def load_bench(path: str) -> dict:
    with open(path) as f:
        doc = json.load(f)
    # BENCH_r* files wrap the parsed metrics in run metadata
    if "parsed" in doc and isinstance(doc["parsed"], dict):
        return doc["parsed"]
    return doc


def newest_history(history_glob: str):
    """(path, parsed) of the highest-numbered BENCH round."""
    paths = sorted(glob.glob(history_glob))
    if not paths:
        return None, None
    return paths[-1], load_bench(paths[-1])


def load_waivers(path):
    if not path or not os.path.exists(path):
        return {}
    with open(path) as f:
        entries = json.load(f)
    if not isinstance(entries, list):
        raise ValueError("waiver file must be a JSON list of "
                         '{"metric", "reason"} objects')
    return {e["metric"]: e.get("reason", "(no reason given)")
            for e in entries}


def compare(base: dict, fresh: dict, waivers: dict):
    """Returns (regressions, waived, improvements, skipped) lists of
    report lines; `regressions` non-empty ==> gate fails."""
    fb, ff = flatten(base), flatten(fresh)
    # best-of awareness: the band covers the NOISIER side
    widen = 1.0
    if min(fb.get("best_of", 3), ff.get("best_of", 3)) < 3:
        widen = 1.5
    regressions, waived, improvements, skipped = [], [], [], []
    for metric in sorted(set(fb) & set(ff)):
        pol = policy_for(metric)
        if pol is None:
            continue
        higher_better, band = pol
        b, f = fb[metric], ff[metric]
        if b <= 0:
            skipped.append(f"{metric}: baseline {b} unusable")
            continue
        delta = (f - b) / b
        line = (f"{metric}: {b:g} -> {f:g} "
                f"({delta:+.1%}, band {band * widen:.0%})")
        bad = (-delta if higher_better else delta) > band * widen
        if bad:
            if metric in waivers:
                waived.append(f"{line} [WAIVED: {waivers[metric]}]")
            else:
                regressions.append(line)
        elif (delta if higher_better else -delta) > band * widen:
            improvements.append(line)
    only_base = set(fb) - set(ff)
    if only_base:
        skipped.append("not in fresh run: "
                       + ", ".join(sorted(only_base)))
    return regressions, waived, improvements, skipped


def cost_table(parsed: dict, source: str) -> dict:
    """Fitted per-program cost table from one bench round — device
    step costs the fleet capacity simulator replays (ROADMAP item 6).
    Every field is optional: rounds grew the schema over time.
    ``schema_version`` is the exception — the simulator's
    CostModel.load refuses tables from another major, so bump it in
    lockstep with ome_tpu/sim/costmodel.py SCHEMA_VERSION whenever
    the shape changes incompatibly."""
    table = {"schema_version": 1, "source": source, "programs": {}}
    br = parsed.get("decode_ms_breakdown") or {}
    for mode, phases in br.items():
        if isinstance(phases, dict) and "step" in phases:
            table["programs"][f"decode_{mode}"] = {
                "step_ms": phases["step"],
                "phases_ms": {k: v for k, v in phases.items()
                              if k != "step"}}
    ms = parsed.get("multistep") or {}
    for k, row in ms.items():
        if isinstance(row, dict) and "step_ms" in row:
            table["programs"][f"decode_multi_k{k}"] = {
                "step_ms": row["step_ms"],
                "tokens_per_sec": row.get("tokens_per_sec")}
    if "prefill_ms_batch32x128" in parsed:
        table["programs"]["prefill_b32x128"] = {
            "step_ms": parsed["prefill_ms_batch32x128"],
            "mfu": parsed.get("prefill_mfu")}
    if "paged_decode_tokens_per_sec_batch64" in parsed:
        table["programs"]["decode_paged_b64"] = {
            "tokens_per_sec":
                parsed["paged_decode_tokens_per_sec_batch64"]}
    for mode, pts in (parsed.get("paged_sweep") or {}).items():
        if not isinstance(pts, dict):
            continue  # scalar keys like capacity_ratio_*
        for b, row in pts.items():
            if isinstance(row, dict) and "tokens_per_sec" in row:
                table["programs"][f"decode_paged_{mode}_b{b}"] = {
                    "tokens_per_sec": row["tokens_per_sec"],
                    "hbm_per_slot_bytes":
                        row.get("hbm_per_slot_bytes")}
    comp = (parsed.get("composition") or {}).get("cells") or {}
    for name, row in comp.items():
        if isinstance(row, dict) and "tokens_per_sec" in row:
            # composed step-plan cells (spec x chunk x pipeline,
            # docs/step-plan.md) — lets the simulator price serving
            # configs that enable several mechanisms at once
            table["programs"][f"composed_{name}"] = {
                "tokens_per_sec": row["tokens_per_sec"],
                "accept_rate": row.get("accept_rate")}
    struct = (parsed.get("structured") or {}).get("cells") or {}
    for name, row in struct.items():
        if isinstance(row, dict) and "tokens_per_sec" in row:
            # grammar-masked decode cells (masked share x chunk K,
            # docs/structured-outputs.md) — lets the simulator price
            # structured-output (JSON mode / tool call) traffic mixes
            table["programs"][f"structured_{name}"] = {
                "tokens_per_sec": row["tokens_per_sec"],
                "mask_apply_ms": row.get("mask_apply_ms")}
    if "dispatch_ms" in parsed:
        table["dispatch_ms"] = parsed["dispatch_ms"]
    if "warmup_ms" in parsed:
        # cold-start compile/warmup cost; the simulator adds it to
        # replica spawn delay so autoscale prices cold starts
        table["warmup_ms"] = parsed["warmup_ms"]
    for k in ("value", "decode_effective_gbps", "achievable_gbps",
              "best_of"):
        if k in parsed:
            table[k] = parsed[k]
    return table


def run_bench(out_path: str) -> dict:
    """Run bench.py fresh; its JSON report lands on the last stdout
    line (stderr carries the progress log)."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), "--json"],
        capture_output=True, text=True, cwd=REPO)
    if proc.returncode != 0:
        raise RuntimeError(
            f"bench.py failed rc={proc.returncode}:\n"
            f"{proc.stderr[-2000:]}")
    line = proc.stdout.strip().splitlines()[-1]
    parsed = json.loads(line)
    if out_path:
        with open(out_path, "w") as f:
            json.dump(parsed, f, indent=1)
    return parsed


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--bench-json", default=None,
                    help="fresh bench result to gate (JSON file; "
                         "BENCH_r* wrapper or bare parsed dict). "
                         "Without --run, required unless --check-only")
    ap.add_argument("--run", action="store_true",
                    help="run bench.py now and gate its output")
    ap.add_argument("--run-out", default=None,
                    help="with --run: also save the fresh result here")
    ap.add_argument("--baseline", default=None,
                    help="explicit baseline JSON (default: newest "
                         "match of --history)")
    ap.add_argument("--history", default=None, metavar="GLOB",
                    help="history glob of BENCH_r*.json results, used "
                         "when --baseline is unset")
    ap.add_argument("--waivers",
                    default=os.path.join(REPO, "bench-waivers.json"),
                    help="waiver file (JSON list of {metric, reason}); "
                         "missing file = no waivers")
    ap.add_argument("--check-only", action="store_true",
                    help="validate history/waivers/policy and exit 0 "
                         "— the tier-1 smoke mode, no bench run")
    ap.add_argument("--cost-table", default=None, metavar="OUT",
                    help="also write the fitted per-program cost "
                         "table (calibration artifact) to OUT")
    ap.add_argument("--json", action="store_true",
                    help="machine-readable report on stdout")
    args = ap.parse_args(argv)

    try:
        if args.baseline:
            base_path, base = args.baseline, load_bench(args.baseline)
        elif args.history:
            base_path, base = newest_history(args.history)
        else:
            print("perfgate: need --history or --baseline",
                  file=sys.stderr)
            return 2
        if base is None:
            print(f"perfgate: no baseline matches {args.history}",
                  file=sys.stderr)
            return 2
        waivers = load_waivers(args.waivers)
    except (OSError, ValueError, KeyError, json.JSONDecodeError) as e:
        print(f"perfgate: bad input: {e}", file=sys.stderr)
        return 2

    if args.cost_table:
        with open(args.cost_table, "w") as f:
            json.dump(cost_table(base, os.path.basename(base_path)),
                      f, indent=1)
            f.write("\n")
        print(f"perfgate: cost table -> {args.cost_table}",
              file=sys.stderr)

    if args.check_only:
        gated = [m for m in flatten(base) if policy_for(m)]
        report = {"mode": "check-only", "baseline": base_path,
                  "gated_metrics": sorted(gated),
                  "waivers": waivers}
        print(json.dumps(report, indent=1) if args.json else
              f"perfgate: check-only OK — baseline {base_path}, "
              f"{len(gated)} gated metrics, {len(waivers)} waivers")
        return 0

    try:
        if args.run:
            fresh = run_bench(args.run_out)
        elif args.bench_json:
            fresh = load_bench(args.bench_json)
        else:
            print("perfgate: need --bench-json, --run, or "
                  "--check-only", file=sys.stderr)
            return 2
    except (OSError, ValueError, RuntimeError,
            json.JSONDecodeError) as e:
        print(f"perfgate: {e}", file=sys.stderr)
        return 2

    regressions, waived, improvements, skipped = compare(
        base, fresh, waivers)
    if args.json:
        print(json.dumps({
            "baseline": base_path, "regressions": regressions,
            "waived": waived, "improvements": improvements,
            "skipped": skipped,
            "pass": not regressions}, indent=1))
    else:
        print(f"perfgate: baseline {base_path}")
        for title, lines in (("REGRESSION", regressions),
                             ("waived", waived),
                             ("improved", improvements),
                             ("skipped", skipped)):
            for line in lines:
                print(f"  [{title}] {line}")
        print("perfgate: FAIL" if regressions else "perfgate: pass")
    return 1 if regressions else 0


if __name__ == "__main__":
    sys.exit(main())
