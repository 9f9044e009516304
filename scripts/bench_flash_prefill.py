#!/usr/bin/env python
"""Time `flash_prefill` alone on the chip at the cells' shapes.

One process times one tree (`--tree DIR`, a checkout of this repo;
default: the one this file is in), so a parent commit unpacked under
`_chipcopy/` and the working tree are compared in one chip call:

    chiprun -- sh -c 'python scripts/bench_flash_prefill.py --tree _chipcopy/parent;
                      python scripts/bench_flash_prefill.py'

A line of JSON a (shape, variant): the median and the least of
`--reps` timed calls (host clock around `block_until_ready`; a call
is 4-70 ms, the dispatch some 0.1 ms of it), the grid steps by kind
where the tree can count them, and a digest of the output's bytes so
that two trees' results compare to the bit. Variants: `kernel` (the
kernel as the tree has it) and, where the tree has the hooks,
`all_edge` (every block that holds work through the masked body: the
trimmed grid alone) and `full_grid` (the sorting alone, over every
key block).
From the three at one shape the two parts book apart; from the kinds'
counts over the shapes a step of each kind follows by least squares
(`--fit`). Off the chip it refuses to time anything.
"""

import argparse
import hashlib
import json
import os
import statistics
import sys
import time

SHAPES = [
    # name, Sq = S, heads, KV heads, head_dim, window
    ("trinity.global.16384", 16384, 32, 4, 128, None),
    ("trinity.window.16384", 16384, 32, 4, 128, 2048),
    ("trinity.global.8192", 8192, 32, 4, 128, None),
    ("trinity.window.8192", 8192, 32, 4, 128, 2048),
    ("qwen3-next.4096", 4096, 16, 2, 256, None),
    ("qwen3-4b.2048", 2048, 32, 8, 128, None),
]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tree", default=os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    ap.add_argument("--reps", type=int, default=15)
    ap.add_argument("--out", default="chiprun_out/bench_flash_prefill.jsonl")
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="the control flow at toy sizes in interpret "
                         "mode; its times mean nothing")
    ap.add_argument("--fit", action="store_true",
                    help="also print a step of each kind by least squares")
    args = ap.parse_args()
    tree = os.path.abspath(args.tree)
    sys.path.insert(0, tree)
    import jax
    import jax.numpy as jnp
    import numpy as np
    from ome_tpu.ops import flash
    assert os.path.abspath(flash.__file__).startswith(tree), flash.__file__
    dev = jax.devices()[0]
    shapes = SHAPES
    if args.rehearse_cpu:
        shapes = [(n, S // 8, H // 4, max(K // 4, 1), D, window and 512)
                  for n, S, H, K, D, window in SHAPES[1::2]]
    elif dev.platform != "tpu":
        sys.exit(f"no chip here ({dev.platform}): nothing timed")

    hooks = hasattr(flash, "_prefill_block_kind")
    variants = ["kernel"] + (["all_edge", "full_grid"] if hooks else [])
    lines = []
    for name, S, H, K, D, window in shapes:
        ks = jax.random.split(jax.random.PRNGKey(33), 3)
        q = jax.random.normal(ks[0], (1, S, H, D), jnp.bfloat16)
        k = jax.random.normal(ks[1], (1, S, K, D), jnp.bfloat16)
        v = jax.random.normal(ks[2], (1, S, K, D), jnp.bfloat16)
        positions = jnp.arange(S, dtype=jnp.int32)[None, :]
        for variant in variants:
            saved = {}
            if variant == "all_edge":
                kind = saved["_prefill_block_kind"] = \
                    flash._prefill_block_kind

                def all_edge(*a, kind=kind):
                    start, some, whole = kind(*a)
                    return start, some, whole & False

                flash._prefill_block_kind = all_edge
            elif variant == "full_grid":
                saved["_prefill_key_steps"] = flash._prefill_key_steps
                flash._prefill_key_steps = \
                    lambda S, bq, bs, window: S // bs
            # the kernel's own jit would hand back the last variant's trace
            clear = getattr(getattr(flash, "_prefill_call", None),
                            "clear_cache", lambda: None)
            clear()
            try:
                fn = jax.jit(lambda q, k, v: flash.flash_attention(
                    q, k, v, positions=positions, sliding_window=window,
                    interpret=args.rehearse_cpu))
                out = jax.block_until_ready(fn(q, k, v))
                jax.block_until_ready(fn(q, k, v))
                times = []
                for _ in range(args.reps):
                    t0 = time.perf_counter()
                    jax.block_until_ready(fn(q, k, v))
                    times.append(time.perf_counter() - t0)
                kinds = flash.prefill_block_kinds(
                    S, S, K, H // K, D, 0, S, window) if hooks else None
            finally:
                for attr, value in saved.items():
                    setattr(flash, attr, value)
                clear()
            line = dict(
                tree=os.path.relpath(tree), shape=name, variant=variant,
                median_ms=1e3 * statistics.median(times),
                min_ms=1e3 * min(times), reps=args.reps,
                digest=hashlib.sha1(np.asarray(
                    out.astype(jnp.float32)).tobytes()).hexdigest()[:16],
                device=dev.device_kind)
            if kinds:
                line["kinds"] = kinds
            lines.append(line)
            print(json.dumps(line), flush=True)
    if args.fit and hooks:
        # least squares over every (shape, variant) of head_dim 128:
        # time = whole * a + edge * b + none * c
        rows = [l for l in lines if "qwen3-next" not in l["shape"]]
        A = np.array([[l["kinds"][k] for k in ("whole", "edge", "none")]
                      for l in rows], float)
        y = np.array([l["min_ms"] * 1e3 for l in rows])
        coef, *_ = np.linalg.lstsq(A, y, rcond=None)
        fit = dict(tree=os.path.relpath(tree), fit_us_a_step=dict(
            zip(("whole", "edge", "none"), (float(c) for c in coef))),
            residual_ms=[float(r) / 1e3 for r in (A @ coef - y)])
        lines.append(fit)
        print(json.dumps(fit), flush=True)
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "a") as f:
        for line in lines:
            f.write(json.dumps(line) + "\n")


if __name__ == "__main__":
    main()
