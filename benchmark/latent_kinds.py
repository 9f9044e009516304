"""Device time of a latent-attention (MLA) model's attention, by the
names its program writes: the scope `attn_latent` AROUND a layer's
cache write and attention (inside `layers`, outside `kv_write` /
`attn`, as `attn_window` / `attn_global` are), and the two kernels
`latent_decode` and `latent_prefill` by their instructions' names: of
the decode family, of the prefill family, and of each whole prefill
the capture holds, with the prompt's true length from the admission
thread's `admit.prefill` span that covers it.

    python benchmark/latent_kinds.py <profile dir> [<op names>.json]

The sibling of `attn_kinds.py`, which knows none of these names and
stays as it is. It is the benchmark's own copy of one of the program's
`SUBPHASES` and of its `SUBKERNELS` (`ome_tpu/telemetry/scopes.py`,
never imported here) and reuses `phases.py` for reading the capture,
the map from instruction to path and the family of a module, and
`attn_kinds.py` for the host's spans and the live lengths.

A program that writes none of the names (every program before PR 46,
and every other family) reduces to no time under any of them, and
every reader of this file's result then returns nothing.
"""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys
from typing import Dict, List, Optional, Tuple

import attn_kinds
import phases
from xtrace import CONTAINERS

SCOPE = "attn_latent"
KERNELS = ("latent_decode", "latent_prefill")
FAMILIES = ("decode", "prefill")


def kernel_of(hlo: str) -> Optional[str]:
    base = re.sub(r"\.\d+$", "", phases.instruction_of(hlo))
    return base if base in KERNELS else None


def reduce_plane(modules: List[Tuple[str, float, float]],
                 ops: List[Tuple[str, float, float, str]],
                 names: Optional[Dict[str, Dict[str, str]]] = None,
                 admits: Optional[List[Tuple[float, float, int]]] = None
                 ) -> Dict:
    """One device plane, arguments as `attn_kinds.reduce_plane` takes
    them. Seconds of each family in all, under the scope and under
    each kernel, the family's module count, and for every prefill
    module the capture holds WHOLE its seconds in all, under the scope
    and under the prefill kernel, with the prompt tokens of the
    `admit.prefill` span that covers most of it."""
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
        timed.append((s, d, family, SCOPE in path.split("/"),
                      kernel_of(hlo), mi))
    module_family = {n: max(v, key=v.get) for n, v in votes.items()}
    whole = {f: 0.0 for f in FAMILIES}
    scope = {f: 0.0 for f in FAMILIES}
    kernels = {f: {} for f in FAMILIES}
    per_module: Dict[int, List[float]] = {}
    for s, d, family, scoped, kernel, mi in timed:
        if family is None and mi is not None:
            family = module_family.get(modules[mi][0])
        if family not in FAMILIES:
            continue
        whole[family] += d
        scope[family] += d if scoped else 0.0
        if kernel:
            kernels[family][kernel] = kernels[family].get(kernel, 0.0) + d
        if family == "prefill" and mi is not None:
            acc = per_module.setdefault(mi, [0.0, 0.0, 0.0])
            acc[0] += d
            acc[1] += d if scoped else 0.0
            acc[2] += d if kernel else 0.0
    lo = min((s for s, *_ in timed), default=0.0)
    hi = max((s + d for s, d, *_ in timed), default=0.0)

    def prompt_of(start, dur):
        cover = [(min(a + d, start + dur) - max(a, start), n)
                 for a, d, n in admits or []]
        best = max(cover, default=(0.0, None))
        return best[1] if best[0] > 0.5 * dur else None

    prefills = [{"dur_s": modules[mi][2], "busy_s": acc[0],
                 "attn_s": acc[1], "kernel_s": acc[2],
                 "prompt_tokens": prompt_of(*modules[mi][1:])}
                for mi, acc in sorted(per_module.items())
                if modules[mi][1] >= lo - 1e-6
                and modules[mi][1] + modules[mi][2] <= hi + 1e-6]
    steps = {f: sum(1 for n, _, _ in modules
                    if module_family.get(n) == f) for f in FAMILIES}
    return {"family_s": whole, "scope_s": scope, "kernel_s": kernels,
            "modules": steps, "prefills": prefills}


def reduce(devices: Dict[str, Dict[str, list]],
           names: Optional[Dict[str, Dict[str, str]]] = None,
           admits: Optional[List[Tuple[float, float, int]]] = None) -> Dict:
    total: Dict = {"prefills": []}
    for _, ev in sorted(devices.items()):
        plane = reduce_plane(ev["modules"], ev["ops"], names, admits)
        total["prefills"] += plane.pop("prefills")
        phases._add(total, plane)
    return total


def main(argv=None) -> int:
    argv = list(argv or sys.argv[1:])
    import xtrace
    path = xtrace.find_xplane(argv[0])
    names = {}
    if len(argv) > 1:
        with open(argv[1]) as f:
            names = json.load(f)
    devices, _ = phases.read_capture(path)
    print(json.dumps(reduce(devices, names, attn_kinds.read_admits(path))))
    return 0


# -- what the readers call ---------------------------------------------


def load(ctx: Dict) -> Optional[Dict]:
    """This traced run's capture reduced by `reduce`; None where the
    run has no capture, it cannot be read, or the program wrote none
    of the names. Runs the child once and keeps its answer in
    `ctx["latent_kinds"]`."""
    if "latent_kinds" in ctx:
        return ctx["latent_kinds"]
    ctx["latent_kinds"] = None
    profile_dir = (ctx.get("profile") or {}).get("dir")
    if not profile_dir or not os.path.isdir(profile_dir):
        return None
    names_file = os.path.join(profile_dir, "op_names.latent.json")
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
        print(json.dumps({"phase": "latent_kinds", "error": str(e)[-500:]}),
              flush=True)
        return None
    if not any(total.get("scope_s", {}).get(f) for f in FAMILIES):
        return None
    ctx["latent_kinds"] = total
    line = {"phase": "latent_kinds"}
    for f in FAMILIES:
        n, whole = total["modules"].get(f, 0), total["family_s"].get(f, 0)
        if n and whole > 0:
            line[f] = {"modules": n, "ms_each": 1e3 * whole / n,
                       SCOPE: 1e3 * total["scope_s"].get(f, 0.0) / n,
                       **{k: 1e3 * v / n for k, v in
                          total["kernel_s"].get(f, {}).items()}}
    line["whole_prefills"] = [
        {"prompt_tokens": p.get("prompt_tokens"),
         **{k[:-2] + "_ms": round(1e3 * p[k], 2)
            for k in ("dur_s", "busy_s", "attn_s", "kernel_s")}}
        for p in total["prefills"]]
    print(json.dumps(line), flush=True)
    return total


def share(ctx: Dict, family: str) -> Optional[float]:
    """Share of `family`'s device time under the scope, %."""
    total = load(ctx)
    whole = (total or {}).get("family_s", {}).get(family, 0.0)
    spent = (total or {}).get("scope_s", {}).get(family, 0.0)
    if whole <= 0 or spent <= 0:
        return None
    return 100.0 * spent / whole


def decode_kernel_seconds(ctx: Dict) -> Optional[float]:
    """Device seconds a decode step spends in `latent_decode`."""
    total = load(ctx)
    steps = (total or {}).get("modules", {}).get("decode", 0)
    spent = (total or {}).get("kernel_s", {}).get("decode", {}).get(
        "latent_decode", 0.0)
    if not steps or spent <= 0:
        return None
    return spent / steps


def whole_prefills(ctx: Dict) -> List[Tuple[int, float]]:
    """(prompt tokens, device seconds under the scope) of every
    prefill the capture holds whole and an `admit.prefill` span
    names."""
    total = load(ctx)
    return [(int(p["prompt_tokens"]), p["attn_s"])
            for p in (total or {}).get("prefills") or []
            if p.get("prompt_tokens") and p["attn_s"] > 0]


live_lengths = attn_kinds.live_lengths


if __name__ == "__main__":
    sys.exit(main())
