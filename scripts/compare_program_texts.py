#!/usr/bin/env python
"""Are two trees' compiled programs the same programs?

`scripts/aot_programs.py --text-dir DIR` writes each program's compiled
text for the described chip. Run it on two trees (the parent in a
`git archive` copy, the change here) and hand both directories to this
script: for every program it says whether the texts are equal to the
byte, and whether they are equal once what names SOURCE PLACES is left
out. Two things in a compiled text name source places and nothing the
chip runs:

  * the tables at its head (`FileNames`, `FunctionNames`,
    `FileLocations`, `StackFrames`: paths and line numbers of every
    traced Python frame) and each instruction's `stack_frame_id` into
    them;
  * the debug locations inside a Mosaic kernel's serialized body
    (`custom_call_config.body`, MLIR bytecode in base64): the kernel
    is parsed and printed again without them.

A tree at another path, or an edit that moves lines of a traced file,
changes those and no instruction. `program_equal` is the statement a
fence rests on ("this PR leaves the paged path's programs alone"):

    python scripts/aot_programs.py --buckets 64,128,256,512,1024,2048 \\
        --text-dir /root/scratch/aot/change        # and in the parent
    python scripts/compare_program_texts.py /root/scratch/aot/parent \\
        /root/scratch/aot/change

Exit code 1 if a program differs or exists on one side only. No chip
time; a text that is equal is not a timing.
"""

import base64
import hashlib
import os
import re
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

TABLES = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")
BODY = re.compile(r'("custom_call_config":\{"body":")([^"]+)(")')


def kernel_text(body: str) -> str:
    """A Mosaic kernel's MLIR with no debug locations."""
    from jax._src.interpreters import mlir
    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir
    ctx = mlir.make_ir_context()
    tpu.register_dialect(ctx)
    ctx.allow_unregistered_dialects = True
    with ctx:
        module = ir.Module.parse(base64.b64decode(body))
        return module.operation.get_asm(enable_debug_info=False)


def program(text: str) -> str:
    """The compiled text without what names source places."""
    out, skip = [], False
    for line in text.split("\n"):
        if line in TABLES:
            skip = True
        elif skip:
            skip = line != ""
        else:
            out.append(re.sub(r" stack_frame_id=\d+", "", line))
    kept = "\n".join(out)
    return BODY.sub(
        lambda m: m.group(1) + "sha256:" + hashlib.sha256(
            kernel_text(m.group(2)).encode()).hexdigest() + m.group(3),
        kept)


def main() -> int:
    a, b = sys.argv[1:3]
    names = sorted(set(os.listdir(a)) | set(os.listdir(b)))
    same = True
    for name in names:
        paths = [os.path.join(d, name) for d in (a, b)]
        if not all(os.path.exists(p) for p in paths):
            print(f"{name}: on one side only")
            same = False
            continue
        ta, tb = (open(p).read() for p in paths)
        pa, pb = program(ta), program(tb)
        same = same and pa == pb
        print(f"{name}: bytes {len(ta)} / {len(tb)}, "
              f"byte_equal={ta == tb}, program_equal={pa == pb}, "
              f"kernels={len(BODY.findall(ta))}, sha256 "
              f"{hashlib.sha256(pa.encode()).hexdigest()[:16]} / "
              f"{hashlib.sha256(pb.encode()).hexdigest()[:16]}")
    return 0 if same else 1


if __name__ == "__main__":
    sys.exit(main())
