"""The finer split of the decode step, by the names a program writes
INSIDE the phases that `phases.py` knows: device time of the decode
family under each of them.

    python benchmark/subphases.py <profile dir> [<op names>.json]

`phases.py` books an operation on the deepest PHASE of its `op_name`
path and knows a closed list of them; the names below sit deeper
(`.../mlp/moe_experts/...`, `.../gdn_mixer/attn/kv_write/gdn_state/
...`), so its metrics read a program that writes them exactly as
before, and this file reads what lies under them. It is the
benchmark's own copy of the program's `SUBPHASES`
(`ome_tpu/telemetry/scopes.py`, never imported here) and reuses
`phases.py` for everything else: reading the capture, the map from
instruction to path, the family of a module. An operation counts for
EVERY name below that is on its path (they nest: `gdn_state` lies
inside `gdn_mixer`), so a group of names is summed over operations,
not over names.

A program that writes none of these names (every program before PR
27) reduces to no time under any of them, and every reader of this
file's result then returns nothing.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
from typing import Dict, List, Optional, Tuple

import phases
from xtrace import CONTAINERS

SUBPHASES = ("gdn_mixer", "gdn_state", "moe_router", "moe_experts",
             "moe_shared")
MOE = ("moe_router", "moe_experts", "moe_shared")
LINEAR_ATTN = ("gdn_mixer", "gdn_state")


def subs_of(path: str) -> Tuple[str, ...]:
    parts = path.split("/")
    return tuple(s for s in SUBPHASES if s in parts)


def reduce_plane(modules: List[Tuple[str, float, float]],
                 ops: List[Tuple[str, float, float, str]],
                 names: Optional[Dict[str, Dict[str, str]]] = None) -> Dict:
    """One device plane, arguments as `phases.reduce_plane` takes
    them. Seconds of the decode family under each name, under each
    set of names that occurs together, and in all."""
    names = names or {}
    modules = sorted(modules, key=lambda m: m[1])
    starts = [m[1] for m in modules]
    timed, votes = [], {}
    for hlo, s, d, path in ops:
        if phases.opcode_of(hlo) in CONTAINERS:
            continue
        mi = phases._enclosing(starts, modules, s)
        if not path and mi is not None:
            path = names.get(modules[mi][0].split("(")[0], {}).get(
                phases.instruction_of(hlo), "")
        family, _ = phases.scope_of(path)
        if family and mi is not None:
            v = votes.setdefault(modules[mi][0], {})
            v[family] = v.get(family, 0.0) + d
        timed.append((d, family, subs_of(path), mi))
    module_family = {n: max(v, key=v.get) for n, v in votes.items()}
    whole, by_name, by_set = 0.0, {}, {}
    for d, family, subs, mi in timed:
        if family is None and mi is not None:
            family = module_family.get(modules[mi][0])
        if family != "decode":
            continue
        whole += d
        for s in subs:
            by_name[s] = by_name.get(s, 0.0) + d
        if subs:
            key = "+".join(subs)
            by_set[key] = by_set.get(key, 0.0) + d
    steps = sum(1 for n, _, _ in modules
                if module_family.get(n) == "decode")
    return {"decode_s": whole, "decode_steps": steps,
            "sub_s": by_name, "sets_s": by_set}


def reduce(devices: Dict[str, Dict[str, list]],
           names: Optional[Dict[str, Dict[str, str]]] = None) -> Dict:
    total: Dict = {}
    for _, ev in sorted(devices.items()):
        phases._add(total, reduce_plane(ev["modules"], ev["ops"], names))
    return total


def under(total: Optional[Dict], group: Tuple[str, ...]) -> float:
    """Seconds of the decode family on operations that have any name
    of `group` on their path."""
    return sum(v for key, v in (total or {}).get("sets_s", {}).items()
               if set(key.split("+")) & set(group))


def main(argv=None) -> int:
    argv = list(argv or sys.argv[1:])
    import xtrace
    path = xtrace.find_xplane(argv[0])
    names = {}
    if len(argv) > 1:
        with open(argv[1]) as f:
            names = json.load(f)
    devices, _ = phases.read_capture(path)
    print(json.dumps(reduce(devices, names)))
    return 0


# -- what the readers call ---------------------------------------------


def load(ctx: Dict) -> Optional[Dict]:
    """This traced run's capture reduced by `reduce`; None where the
    run has no capture, it cannot be read, or the program wrote none
    of the names. Runs the child once and keeps its answer in
    `ctx["subphases"]`."""
    if "subphases" in ctx:
        return ctx["subphases"]
    ctx["subphases"] = None
    profile_dir = (ctx.get("profile") or {}).get("dir")
    if not profile_dir or not os.path.isdir(profile_dir):
        return None
    names_file = os.path.join(profile_dir, "op_names.sub.json")
    try:
        with open(names_file, "w") as f:
            json.dump(phases.program_names(ctx.get("programs_after")), f)
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), profile_dir,
             names_file], env=dict(os.environ, JAX_PLATFORMS="cpu"),
            capture_output=True, text=True, timeout=600.0)
        lines = [ln for ln in proc.stdout.splitlines()
                 if ln.startswith("{")]
        if proc.returncode != 0 or not lines:
            raise ValueError(f"rc={proc.returncode}: {proc.stderr[-400:]}")
        total = json.loads(lines[-1])
    except (OSError, ValueError, subprocess.SubprocessError) as e:
        print(json.dumps({"phase": "decode_subphases",
                          "error": str(e)[-500:]}), flush=True)
        return None
    steps, whole = total.get("decode_steps", 0), total.get("decode_s", 0)
    if not total.get("sub_s") or not steps or whole <= 0:
        return None
    ctx["subphases"] = total
    print(json.dumps({
        "phase": "decode_subphases", "steps": steps,
        "step_ms": 1e3 * whole / steps,
        "sub_ms": {k: 1e3 * v / steps for k, v in sorted(
            total["sub_s"].items(), key=lambda kv: -kv[1])},
        "moe_ms": 1e3 * under(total, MOE) / steps,
        "linear_attn_ms": 1e3 * under(total, LINEAR_ATTN) / steps}),
        flush=True)
    return total


def decode_share(ctx: Dict, group: Tuple[str, ...]) -> Optional[float]:
    """Share of the decode family's device time under `group`, %."""
    total = load(ctx)
    if not total:
        return None
    return 100.0 * under(total, group) / total["decode_s"]


def step_seconds(ctx: Dict, group: Tuple[str, ...]) -> Optional[float]:
    """Device seconds a decode step spends under `group`."""
    total = load(ctx)
    spent = under(total, group)
    if not total or spent <= 0:
        return None
    return spent / total["decode_steps"]


def moved(ctx: Dict, name: str) -> float:
    """A counter's growth over the window."""
    return (ctx["metrics_after"].get(name, 0.0)
            - ctx["metrics_before"].get(name, 0.0))


def experts_hit_a_layer(ctx: Dict) -> Optional[float]:
    """Held experts hit a layer-step over the window, from the
    program's counters; None where the program has none."""
    steps = moved(ctx, "ome_engine_moe_layer_steps_total")
    if steps <= 0:
        return None
    return moved(ctx, "ome_engine_moe_experts_hit_total") / steps


def live_slots(ctx: Dict) -> Optional[float]:
    """Mean occupied decode slots through the window."""
    vals = [s["ome_engine_batch_occupancy_ratio"]
            for s in ctx["gauge_samples"]
            if "ome_engine_batch_occupancy_ratio" in s]
    args = [str(a) for a in ctx["config"]["benchmark"]["serve_args"]]
    if not vals or "--max-slots" not in args:
        return None
    slots = int(args[args.index("--max-slots") + 1])
    return slots * sum(vals) / len(vals)


if __name__ == "__main__":
    sys.exit(main())
