#!/usr/bin/env python
"""The `copy` operations of compiled program texts, by result shape.

`scripts/aot_programs.py --text-dir DIR` writes each program's
compiled text for the described chip; this reads them back and lists
every `copy` / `copy-start` / `copy-done` whose result holds at least
`--min-mb` megabytes: a re-laid weight, a cache or a slab copied, which
is what a layout change can add or take away. With two directories it
prints, per program, the copies only one side has (how PR 41 showed
that storing `wq` / `wk` / `wv` / `w_ogate` out-major took their
relayout copies out of every program and added no other).

    python scripts/program_copies.py DIR            # what DIR's programs copy
    python scripts/program_copies.py DIR_A DIR_B    # what differs

Facts about programs, never timings.
"""

from __future__ import annotations

import argparse
import collections
import os
import re
import sys

_COPY = re.compile(
    r"= (\w+)\[([\d,]*)\](\{[^ ]*\})? (copy|copy-start|copy-done)\(")
_BYTES = {"bf16": 2, "f32": 4, "s32": 4, "u32": 4, "s8": 1, "u8": 1,
          "f16": 2, "pred": 1, "s4": 1, "u4": 1, "f8e4m3fn": 1}


def copies(text: str, min_bytes: int) -> collections.Counter:
    """{(opcode, dtype[shape]{minor-to-major}): count} of a compiled
    text's copies at or over `min_bytes` (a `copy-start`'s result is a
    tuple and is not counted: its `copy-done` is)."""
    out = collections.Counter()
    for line in text.splitlines():
        m = _COPY.search(line)
        if not m or m.group(4) == "copy-start":
            continue
        dtype, dims, layout, op = m.groups()
        n = 1
        for d in filter(None, dims.split(",")):
            n *= int(d)
        if n * _BYTES.get(dtype, 4) < min_bytes:
            continue
        order = re.match(r"\{([\d,]*)", layout or "{")
        out[f"{op} {dtype}[{dims}]{{{order.group(1)}}}"] += 1
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("dirs", nargs="+", help="one or two --text-dir outputs")
    ap.add_argument("--min-mb", type=float, default=1.0)
    args = ap.parse_args()
    if len(args.dirs) > 2:
        ap.error("one directory, or two to compare")
    floor = int(args.min_mb * 1e6)
    sides = []
    for d in args.dirs:
        sides.append({f[:-4]: copies(open(os.path.join(d, f)).read(), floor)
                      for f in sorted(os.listdir(d)) if f.endswith(".txt")})
    for name in sorted(set().union(*sides)):
        if len(sides) == 1:
            print(name, dict(sides[0][name]) or "no copy")
            continue
        a, b = (s.get(name, collections.Counter()) for s in sides)
        print(name, "only A:", dict(a - b) or "-", "| only B:",
              dict(b - a) or "-")
    return 0


if __name__ == "__main__":
    sys.exit(main())
