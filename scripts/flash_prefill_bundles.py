#!/usr/bin/env python
"""What `flash_prefill` (or, with `--kernel decode`, `flash_decode`)
compiles to, without the chip: the kernel's final instruction bundles
for a described v5e, counted.

The installed libtpu compiles for a chip that is described, not
attached (on-chip-measurement guide, section 2), and with
`--xla_jf_dump_to=DIR --xla_jf_dump_llo_text=true` in
`LIBTPU_INIT_ARGS` it writes every pass of its low-level compiler
beside the kernel: `*flash_prefill*final_bundles.txt` is the VLIW
schedule the chip runs, `*final_hlo-static-per-bundle-utilization.txt`
how many of each slot a bundle fills (capacities on a v5e: MXU 4,
XLU 3, VALU 4, EUP 1, vector load 3, vector store 1, scalar 2). A
grid step's body is straight-line code, so its bundle count is its
time to a factor: 12.5 k bundles ran in 14.5 us and 4.9 k in 3.6 us
(my chip runs, PR 33). It costs ten seconds and no chip time, and it
is how PR 33 found that the kernel's time was single-sublane loads,
repacking and 3300 spills a step on the one store slot, not the
mask's arithmetic.

    python scripts/flash_prefill_bundles.py                # 16 384 bucket's tile
    python scripts/flash_prefill_bundles.py --body whole   # one body alone
    python scripts/flash_prefill_bundles.py --heads 16 --kv-heads 2 --dim 256
    python scripts/flash_prefill_bundles.py --kernel decode --heads 28
    python scripts/flash_prefill_bundles.py --kernel decode --heads 28 \
        --tree /root/scratch/parent --apart       # the kernel before PR 38
    python scripts/flash_prefill_bundles.py --kernel latent_decode --heads 128
    python scripts/flash_prefill_bundles.py --kernel latent_prefill --heads 32 \
        --body whole                  # a latent (MLA) model's two kernels

`--kernel decode` compiles the decode kernel over a stacked slab of
`--rows` rows for 8 slots (`--apart`: the slab as `[L, B, S, K, D]`,
which the kernel took before PR 38; since then `[L, B, S, K * D]`),
`--tree DIR` takes `ome_tpu` from another checkout. Besides the bundle
and slot counts the vector loads are counted by how many sublanes
each moves (`sublane_mask`): a block whose second-minor dimension is
K = 4 loads half-empty tiles, and one whose minor dimensions are
`[1, D]` a sublane at a time, and no source line shows either.

`--body whole|edge|none` compiles the kernel with every block sorted
as that kind (none: init, finish and the pipeline's own code; a
body's size is the difference). The dump ends in a crash of the
compiler's report writer (a template file it does not ship): the
bundles are written before it, so the child's exit code is ignored.
A compile that passes is not a chip run: a bundle count is not a time.
"""

import argparse
import collections
import glob
import os
import re
import shutil
import subprocess
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _compile(args):
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    sys.path.insert(0, args.tree or ROOT)
    from ome_tpu.ops import flash
    if args.body != "both" and args.kernel.endswith("prefill"):
        kind = flash._prefill_block_kind

        def only(*a):
            start, some, _ = kind(*a)
            return (start, args.body != "none" and some,
                    args.body == "whole")

        flash._prefill_block_kind = only
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def struct(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    S, D = args.rows, args.dim
    ints = struct((1,), jnp.int32)
    if args.kernel == "latent_decode":
        # a latent model's absorbed queries over its stacked slab of
        # padded rows `[c 512 | k_pe 64 | 64]` (--heads 128)
        slots, layers = 8, 2
        ints = struct((slots,), jnp.int32)

        def step(q_lat, q_pe, rows, lo, hi, layer):
            out = flash.latent_decode(q_lat, q_pe, rows, lo, hi,
                                      scale=192 ** -0.5, layer=layer)
            assert out is not None, "the kernel declined the shape"
            return out

        jax.jit(step).lower(
            struct((slots, args.heads, 512), jnp.bfloat16),
            struct((slots, args.heads, 64), jnp.bfloat16),
            struct((layers, slots, S, 640), jnp.bfloat16), ints, ints,
            struct((), jnp.int32)).compile()
        return
    if args.kernel == "latent_prefill":
        # a group of --heads materialised heads: keys 128 + the shared
        # 64, values 128
        def g(q_nope, q_pe, k_nope, k_pe, v, base, kv_hi):
            out = flash.latent_prefill(q_nope, q_pe, k_nope, k_pe, v, base,
                                       kv_hi, scale=192 ** -0.5)
            assert out is not None, "the kernel declined the shape"
            return out

        wide = struct((1, args.heads, S, 128), jnp.bfloat16)
        jax.jit(g).lower(wide, struct((1, args.heads, S, 64), jnp.bfloat16),
                         wide, struct((1, S, 64), jnp.bfloat16), wide, ints,
                         ints).compile()
        return
    if args.kernel == "decode":
        slots, layers = 8, 2
        rows = (args.kv_heads, D) if args.apart else (args.kv_heads * D,)
        kv = struct((layers, slots, S) + rows, jnp.bfloat16)

        def step(q, k, v, lo, hi, layer):
            out = flash._flash_decode(q, k, v, lo, hi, D ** -0.5, None,
                                      False, layer=layer)
            assert out is not None, "the kernel declined the shape"
            return out

        ints = struct((slots,), jnp.int32)
        jax.jit(step).lower(struct((slots, 1, args.heads, D), jnp.bfloat16),
                            kv, kv, ints, ints,
                            struct((), jnp.int32)).compile()
        return

    def f(q, k, v, base, kv_hi):
        return flash._flash_prefill(q, k, v, base, kv_hi, D ** -0.5, None,
                                    args.window, False)

    kv = struct((1, S, args.kv_heads, D), jnp.bfloat16)
    jax.jit(f).lower(struct((1, S, args.heads, D), jnp.bfloat16), kv, kv,
                     struct((1,), jnp.int32),
                     struct((1,), jnp.int32)).compile()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--kernel", default="prefill",
                    choices=("prefill", "decode", "latent_decode",
                             "latent_prefill"))
    ap.add_argument("--tree", default=None,
                    help="take ome_tpu from this checkout, not this one")
    ap.add_argument("--apart", action="store_true",
                    help="decode: hand the slab as [L, B, S, K, D]")
    ap.add_argument("--rows", type=int, default=2048,
                    help="Sq = S; the tile does not depend on it")
    ap.add_argument("--heads", type=int, default=32)
    ap.add_argument("--kv-heads", type=int, default=4)
    ap.add_argument("--dim", type=int, default=128)
    ap.add_argument("--window", type=int, default=None)
    ap.add_argument("--body", default="both",
                    choices=("both", "whole", "edge", "none"))
    ap.add_argument("--keep", default=None,
                    help="directory to keep the final bundles in")
    args = ap.parse_args()
    if os.environ.get("_FLASH_BUNDLES_CHILD"):
        return _compile(args)
    out = tempfile.mkdtemp(prefix="flash_bundles_")
    env = dict(os.environ, _FLASH_BUNDLES_CHILD="1", JAX_PLATFORMS="cpu",
               LIBTPU_INIT_ARGS=f"--xla_jf_dump_to={out} "
                                "--xla_jf_dump_llo_text=true")
    child = subprocess.run([sys.executable, __file__] + sys.argv[1:],
                           env=env, capture_output=True, text=True)
    try:
        name = args.kernel if args.kernel.startswith("latent") \
            else "flash_" + args.kernel
        found = [f for f in glob.glob(
            f"{out}/*{name}*final_bundles.txt")
            if "schedule-analysis" not in f]
        if not found:
            sys.exit(child.stderr[-3000:] or "no bundles were written")
        bundles = [line for line in open(found[0])
                   if re.match(r"\s*(0x[0-9a-f]+|\d+)\s", line)]
        ops, sublanes = collections.Counter(), collections.Counter()
        for line in bundles:
            for ins in line.partition("{")[2].split(";;"):
                m = re.search(r"=\s*([a-z_.0-9]+)", ins)
                if m:
                    ops[re.sub(r"\.(xlu|mxu)\d", "", m.group(1))] += 1
                    mask = re.search(r"sm:\$0x([0-9a-f]+)", ins)
                    if m.group(1).startswith("vld"):
                        # no mask: all 8 sublanes of the tile
                        sublanes[bin(int(mask.group(1), 16)).count("1")
                                 if mask else 8] += 1
        util = glob.glob(f"{out}/*{name}*final_hlo-static-per-"
                         "bundle-utilization.txt")[0]
        text = open(util).read().split("\n")
        names = text[1].replace(" ", "").split(",")
        caps = text[2].split()
        rows = [[int(x) for x in line.split()] for line in text[4:]
                if line.strip()]
        print(f"body={args.body}: {len(bundles)} bundles")
        for i, (name, cap) in enumerate(zip(names, caps)):
            print(f"  {name:13s} {sum(r[i] for r in rows):6d} slot uses "
                  f"({cap} a bundle)")
        print("  vector loads by sublanes moved: " + ", ".join(
            f"{n} sublanes x {c}" for n, c in sorted(sublanes.items())))
        print("  " + ", ".join(f"{k} {v}" for k, v in ops.most_common(24)))
        if args.keep:
            os.makedirs(args.keep, exist_ok=True)
            shutil.copy(found[0], args.keep)
    finally:
        shutil.rmtree(out, ignore_errors=True)


if __name__ == "__main__":
    main()
