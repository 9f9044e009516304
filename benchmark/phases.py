"""Reduction of a profiler capture by the program's OWN names: device
time by program family, by phase inside the decode step and by kernel,
and every idle gap of the device labelled with the scheduler span that
covers it.

    python benchmark/phases.py <profile dir> [<op names>.json] [--dump]

Prints one JSON object on its last line; `--dump` lists, for every
plane and line, its event count and a few events with their stats,
for reading a capture by hand. Like `xtrace.py` it runs in a child of
its own with JAX_PLATFORMS=cpu (`read_capture` alone needs
`jax.profiler.ProfileData`); `load(ctx)` starts that child once for a
traced run, keeps the result in `ctx` for the readers under
`layer_metrics/`, and prints the `decode_phases` line.

The vocabulary below is the benchmark's own copy of the program's
(`ome_tpu/telemetry/scopes.py`, never imported here). A program that
writes none of these names, as every program before PR 24, reduces to
the family `other` and to no phase, and every reader of this file's
result then returns nothing.

  * a FAMILY is the root `jax.named_scope` of a jitted program body,
    a PHASE a scope inside it. Both reach the capture as the `op_name`
    path of an HLO operation (`jit(_decode_paged)/decode/layers/while/
    body/closed_call/mlp/dot_general`). A v5e capture does not carry
    it (seen on the first trace, PERF.md section 3: the event is named
    by its HLO line without metadata, its stats hold only device
    times), so the path is joined by instruction name from the map
    `instruction -> op_name` that the program's ledger reads out of
    the compiled text and serves with each `/debug/programs` entry
    (`op_names`; a compiler-made instruction there already stands
    under its nearest producer's path). An operation counts for the
    DEEPEST phase on its path (self time); containers (`while`,
    `conditional`, `call`) are skipped as in xtrace.py, their
    children carry the time. An
    operation with no path takes the family of the module event that
    encloses it in time; a module's family is that of its scoped
    operations, `other` if it has none.
  * a KERNEL is the `name=` of a `pallas_call`: the name of its
    custom-call instruction (`%paged_attention.3`).
  * `sched.<phase>` spans are `jax.profiler.TraceAnnotation` events of
    the scheduler thread on the host plane of the same capture, on the
    same clock. An idle gap takes the label of the span that covers
    most of it (the shortest such span where several nest), `none`
    where no span does.

This supersedes the "(A2)" note in xtrace.py's docstring: the host's
spans are on the trace's clock now, and this file reads them.
`breakdown.idle_gaps` of the result line is still xtrace.py's and
keeps saying `unattributed` (PERF.md section 7).
"""

from __future__ import annotations

import bisect
import json
import os
import re
import subprocess
import sys
from typing import Dict, List, Optional, Tuple

import xtrace
from xtrace import CONTAINERS, DEVICE_PLANE, MODULE_LINE, OPS_LINE

FAMILIES = ("decode", "prefill", "verify", "insert")
PHASES = ("embed", "layers", "qkv", "kv_write", "attn", "o_proj", "mlp",
          "lm_head", "sample")
KERNELS = ("paged_attention", "flash_decode", "flash_prefill",
           "int4_matmul")
SCHED_PREFIX = "sched."
HOST_PLANE = "/host:CPU"
OTHER, UNSCOPED, NONE = "other", "unscoped", "none"

# an operation over this share of the decode step that sits on no
# phase is listed by name in the `decode_phases` line
UNSCOPED_LISTED = 0.01

_OP_NAME = re.compile(r'op_name="([^"]*)"')


def op_path(hlo: str) -> str:
    """The `op_name` path an `XLA Ops` event carries itself, where
    the HLO line it is named by holds `metadata={op_name=...}` (a v5e
    capture of JAX 0.9 holds none: the path then comes from the
    ledger's map, `reduce_plane`)."""
    m = _OP_NAME.search(hlo)
    return m.group(1) if m else ""


def scope_of(path: str) -> Tuple[Optional[str], Optional[str]]:
    """(family, deepest phase) of an op_name path; None where the
    path names none. The family is looked for only before the first
    phase: `jit(_decode_paged)/decode/sample/...`."""
    family = phase = None
    for part in path.split("/"):
        if part in PHASES:
            phase = part
        elif phase is None and family is None and part in FAMILIES:
            family = part
    return family, phase


def instruction_of(hlo: str) -> str:
    """`%paged_attention.3 = ... custom-call(...)` -> `paged_attention.3`."""
    return hlo.partition(" = ")[0].strip().lstrip("%")


def kernel_of(hlo: str) -> Optional[str]:
    """`%paged_attention.3 = ...` -> `paged_attention`."""
    base = re.sub(r"\.\d+$", "", instruction_of(hlo))
    return base if base in KERNELS else None


def opcode_of(hlo: str) -> str:
    parts = xtrace.short_name(hlo).split(" ")
    return parts[1] if len(parts) > 1 else ""


def _enclosing(starts: List[float], modules: List[Tuple[str, float, float]],
               t: float) -> Optional[int]:
    i = bisect.bisect_right(starts, t + 1e-9) - 1
    if i >= 0 and t < modules[i][1] + modules[i][2] + 1e-9:
        return i
    return None


def reduce_plane(modules: List[Tuple[str, float, float]],
                 ops: List[Tuple[str, float, float, str]],
                 spans: List[Tuple[str, float, float]],
                 names: Optional[Dict[str, Dict[str, str]]] = None) -> Dict:
    """One device plane. `modules`: (name, start_s, dur_s); `ops`:
    (hlo line, start_s, dur_s, op_name path found in the event, or
    ""); `spans`: the host's `sched.*` spans, (name, start_s, dur_s);
    `names`: jit name of a module (`jit__decode_paged`) ->
    {instruction -> op_name path}, for the operations whose event
    carries no path."""
    names = names or {}
    modules = sorted(modules, key=lambda m: m[1])
    starts = [m[1] for m in modules]
    # pass 1: every self-timed operation with what its path says
    timed = []
    votes: Dict[str, Dict[str, float]] = {}
    for hlo, s, d, path in ops:
        if opcode_of(hlo) in CONTAINERS:
            continue
        mi = _enclosing(starts, modules, s)
        if not path and mi is not None:
            path = names.get(modules[mi][0].split("(")[0], {}).get(
                instruction_of(hlo), "")
        family, phase = scope_of(path)
        if family and mi is not None:
            v = votes.setdefault(modules[mi][0], {})
            v[family] = v.get(family, 0.0) + d
        timed.append((hlo, d, family, phase, mi))
    module_family = {name: max(v, key=v.get) for name, v in votes.items()}
    # pass 2: totals
    families: Dict[str, float] = {}
    phases: Dict[str, float] = {}
    kernels: Dict[str, Dict[str, float]] = {}
    unscoped: Dict[str, float] = {}
    for hlo, d, family, phase, mi in timed:
        if family is None:
            family = module_family.get(modules[mi][0], OTHER) \
                if mi is not None else OTHER
        families[family] = families.get(family, 0.0) + d
        k = kernel_of(hlo)
        if k:
            kf = kernels.setdefault(family, {})
            kf[k] = kf.get(k, 0.0) + d
        if family == "decode":
            phases[phase or UNSCOPED] = phases.get(phase or UNSCOPED,
                                                   0.0) + d
            if phase is None:
                n = xtrace.short_name(hlo)
                unscoped[n] = unscoped.get(n, 0.0) + d
    steps = sum(1 for name, _, _ in modules
                if module_family.get(name) == "decode")
    # idle gaps inside the traced stretch, by the span that covers most
    busy = xtrace.union([(s, s + d) for _, s, d, _ in ops]
                        or [(s, s + d) for _, s, d in modules])
    sched = sorted((s, s + d, n[len(SCHED_PREFIX):]) for n, s, d in spans
                   if n.startswith(SCHED_PREFIX))
    sched_starts = [a for a, _, _ in sched]
    longest = max((b - a for a, b, _ in sched), default=0.0)
    idle: Dict[str, float] = {}
    for (_, e0), (s1, _) in zip(busy, busy[1:]):
        best, best_key = NONE, (0.0, 0.0)
        # a span that reaches into the gap started after e0 - longest
        lo = bisect.bisect_left(sched_starts, e0 - longest)
        hi = bisect.bisect_left(sched_starts, s1)
        for a, b, n in sched[lo:hi]:
            cover = min(b, s1) - max(a, e0)
            if cover > 0 and (cover, a - b) > best_key:
                best, best_key = n, (cover, a - b)
        idle[best] = idle.get(best, 0.0) + (s1 - e0)
    return {"busy_s": sum(e - s for s, e in busy),
            "families_s": families, "decode_phases_s": phases,
            "kernels_s": kernels, "decode_unscoped_ops_s": unscoped,
            "decode_steps": steps, "idle_s": idle,
            "module_family": module_family}


def _add(into: Dict, other: Dict) -> None:
    for k, v in other.items():
        if isinstance(v, dict):
            _add(into.setdefault(k, {}), v)
        elif isinstance(v, (int, float)):
            into[k] = into.get(k, 0) + v


def reduce(devices: Dict[str, Dict[str, list]],
           spans: List[Tuple[str, float, float]],
           names: Optional[Dict[str, Dict[str, str]]] = None) -> Dict:
    """`devices`: plane name -> {"modules": [...], "ops": [...]};
    `spans` and `names` as `reduce_plane` takes them. Gives every
    plane's reduction and their sum (`total`): under tensor
    parallelism the chips run the same programs, so a share over the
    sum is the chips' mean share and a per-step value is total time
    over total steps."""
    planes = {p: reduce_plane(ev["modules"], ev["ops"], spans, names)
              for p, ev in sorted(devices.items())}
    total: Dict = {}
    for r in planes.values():
        _add(total, {k: v for k, v in r.items() if k != "module_family"})
    return {"planes": planes, "total": total,
            "sched_spans": sum(1 for n, _, _ in spans
                               if n.startswith(SCHED_PREFIX))}


def decode_line(total: Dict) -> Optional[Dict]:
    """The `decode_phases` line: every scope with its ms a decode
    step and its share of the decode family, and by name every
    operation over 1 % of the step that sits on no phase."""
    steps = total.get("decode_steps", 0)
    whole = total.get("families_s", {}).get("decode", 0.0)
    if not steps or whole <= 0:
        return None
    per = 1e3 / steps
    return {
        "steps": steps, "step_ms": whole * per,
        "scopes_ms": {k: v * per for k, v in sorted(
            total["decode_phases_s"].items(), key=lambda kv: -kv[1])},
        "scopes_share": {k: 100.0 * v / whole
                         for k, v in total["decode_phases_s"].items()},
        "kernels_ms": {k: v * per for k, v in
                       total.get("kernels_s", {}).get("decode", {}).items()},
        "unscoped_ops_ms": sorted(
            ([n, v * per] for n, v in
             total.get("decode_unscoped_ops_s", {}).items()
             if v > UNSCOPED_LISTED * whole), key=lambda x: -x[1]),
        "families_s": total["families_s"], "idle_s": total.get("idle_s", {}),
    }


# -- reading a capture ------------------------------------------------


def _stats(ev) -> Dict[str, object]:
    try:
        return {str(k): v for k, v in ev.stats}
    except Exception:       # an event with no readable stats has none
        return {}


def read_capture(path: str, dump: bool = False):
    """(devices, spans) for `reduce`, or the listing of `--dump`."""
    from jax.profiler import ProfileData
    devices: Dict[str, Dict[str, list]] = {}
    spans: List[Tuple[str, float, float]] = []
    listing = []
    for plane in ProfileData.from_file(path).planes:
        is_dev = plane.name.startswith(DEVICE_PLANE)
        is_host = plane.name.startswith(HOST_PLANE)
        for line in plane.lines:
            shown, n = [], 0
            kind = {MODULE_LINE: "modules", OPS_LINE: "ops"}.get(
                line.name) if is_dev else None
            if kind:
                keep = devices.setdefault(
                    plane.name, {"modules": [], "ops": []})[kind]
            for ev in line.events:
                n += 1
                s, d = ev.start_ns * 1e-9, ev.duration_ns * 1e-9
                if kind == "modules":
                    keep.append((ev.name, s, d))
                elif kind == "ops":
                    keep.append((ev.name, s, d, op_path(ev.name)))
                elif is_host and ev.name.startswith(SCHED_PREFIX):
                    spans.append((ev.name, s, d))
                if dump and (len(shown) < 8 or (
                        ev.name.startswith((SCHED_PREFIX, "admit."))
                        and len(shown) < 40)):
                    shown.append({"name": ev.name[:400], "start_s": s,
                                  "dur_s": d, "stats": {
                                      k: str(v)[:300]
                                      for k, v in _stats(ev).items()}})
            if dump:
                listing.append({"plane": plane.name, "line": line.name,
                                "events": n, "first": shown})
    return listing if dump else (devices, spans)


def main(argv=None) -> int:
    argv = list(argv or sys.argv[1:])
    dump = "--dump" in argv
    plain = [a for a in argv if not a.startswith("--")]
    path = xtrace.find_xplane(plain[0])
    names = {}
    if len(plain) > 1:
        with open(plain[1]) as f:
            names = json.load(f)
    if dump:
        print(json.dumps({"file": path, "bytes": os.path.getsize(path),
                          "lines": read_capture(path, dump=True)},
                         indent=1))
        return 0
    devices, spans = read_capture(path)
    if not devices:
        raise SystemExit(f"no {DEVICE_PLANE}* plane in {path}")
    out = reduce(devices, spans, names)
    out["capture_bytes"] = os.path.getsize(path)
    print(json.dumps(out))
    return 0


# -- what the readers call ---------------------------------------------


def program_names(programs: Dict) -> Dict[str, Dict[str, str]]:
    """The `/debug/programs` body -> jit name of a module ->
    {instruction -> op_name path}. The trace names a module
    `jit_<function>(<fingerprint>)` and the ledger an entry
    `<function without its underscore>[<static arguments>]`: entries
    of one function (a prefill's buckets) share a jit name, their
    instruction names collide, and so their paths serve to tell the
    family, which is the same in all of them; a phase is read only
    where one program of that name ran (the decode program)."""
    out: Dict[str, Dict[str, str]] = {}
    for entry in (programs or {}).get("programs", []):
        if entry.get("op_names"):
            out.setdefault("jit__" + entry["name"], {}).update(
                entry["op_names"])
    return out


def load(ctx: Dict) -> Optional[Dict]:
    """The capture of this traced run, reduced (`reduce`'s `total`
    plus `capture_bytes`); None when the run has no capture or it
    cannot be read. Runs the child once and keeps its answer in
    `ctx["phases"]`."""
    if "phases" in ctx:
        return ctx["phases"]
    ctx["phases"] = None
    profile_dir = (ctx.get("profile") or {}).get("dir")
    if not profile_dir or not os.path.isdir(profile_dir):
        return None
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    names_file = os.path.join(profile_dir, "op_names.json")
    try:
        with open(names_file, "w") as f:
            json.dump(program_names(ctx.get("programs_after")), f)
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), profile_dir,
             names_file],
            env=env, capture_output=True, text=True, timeout=600.0)
        lines = [ln for ln in proc.stdout.splitlines()
                 if ln.startswith("{")]
        if proc.returncode != 0 or not lines:
            raise ValueError(f"rc={proc.returncode}: {proc.stderr[-400:]}")
        out = json.loads(lines[-1])
    except (OSError, ValueError, subprocess.SubprocessError) as e:
        print(json.dumps({"phase": "decode_phases", "error": str(e)[-500:]}),
              flush=True)
        return None
    total = dict(out["total"], capture_bytes=out["capture_bytes"],
                 sched_spans=out["sched_spans"])
    ctx["phases"] = total
    print(json.dumps(dict(decode_line(total) or {}, phase="decode_phases",
                          capture_bytes=out["capture_bytes"],
                          sched_spans=out["sched_spans"])), flush=True)
    return total


def decode_share(ctx: Dict, *scopes: str) -> Optional[float]:
    """Share of the decode family's device time on `scopes`, %."""
    total = load(ctx)
    whole = (total or {}).get("families_s", {}).get("decode", 0.0)
    if not whole:
        return None
    return 100.0 * sum(total["decode_phases_s"].get(s, 0.0)
                       for s in scopes) / whole


if __name__ == "__main__":
    sys.exit(main())
