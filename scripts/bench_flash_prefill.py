#!/usr/bin/env python
"""Time `flash_prefill` and `latent_prefill` alone on the chip at the
cells' shapes.

One process times one tree (`--tree DIR`, a checkout of this repo;
default: the one this file is in), so a parent commit unpacked under
`_chipcopy/` and the working tree are compared in one chip call:

    chiprun -- sh -c 'python scripts/bench_flash_prefill.py --tree _chipcopy/parent;
                      python scripts/bench_flash_prefill.py'

A line of JSON a (shape, true length, variant): the median and the
least of `--reps` timed calls (host clock around `block_until_ready`;
a call is 4-70 ms, the dispatch some 0.1 ms of it), the grid steps by
kind where the tree can count them, and a digest of the REAL rows'
bytes so that two trees' results compare to the bit. `--true-len T
...` times each shape as a prompt of T tokens right-padded to the
shape's bucket (`kv_len = T`, what llama.forward hands the kernel;
default: the whole bucket): a kernel's time against T at one bucket.
Variants (`--variants`): `kernel` (the kernel as the tree has it)
and, for `flash_prefill` where the tree has the hooks, `all_edge`
(every block that holds work through the masked body: the trimmed
grid alone) and `full_grid` (the sorting alone, over every key block).
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
LATENT_SHAPES = [
    # name, Sq = S, heads a call (mla._prefill_head_group: a layer of
    # 128 heads is 4 calls of 32 at this bucket, 2 of 64 at 8192),
    # nope, rope, value width
    ("openpangu.latent.16384", 16384, 32, 128, 64, 128),
    ("openpangu.latent.8192", 8192, 64, 128, 64, 128),
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
    ap.add_argument("--true-len", type=int, nargs="*", default=[],
                    help="prompt lengths to time, each at the shapes "
                         "whose bucket it pads to; none: every shape at "
                         "its whole bucket")
    ap.add_argument("--only", nargs="*", default=[],
                    help="time the shapes whose name holds one of these")
    ap.add_argument("--variants", nargs="*",
                    default=["kernel", "all_edge", "full_grid"])
    args = ap.parse_args()
    tree = os.path.abspath(args.tree)
    sys.path.insert(0, tree)
    import jax
    import jax.numpy as jnp
    import numpy as np
    from ome_tpu.ops import flash
    assert os.path.abspath(flash.__file__).startswith(tree), flash.__file__
    dev = jax.devices()[0]
    shapes, latent = SHAPES, LATENT_SHAPES
    if args.rehearse_cpu:
        shapes = [(n, S // 8, H // 4, max(K // 4, 1), D, window and 512)
                  for n, S, H, K, D, window in SHAPES[1::2]]
        latent = [(n, S // 8, H // 8, nope, rope, dv)
                  for n, S, H, nope, rope, dv in LATENT_SHAPES[:1]]
    elif dev.platform != "tpu":
        sys.exit(f"no chip here ({dev.platform}): nothing timed")
    if not hasattr(flash, "latent_prefill"):
        latent = []
    wanted = [(x in latent, *x) for x in shapes + latent
              if not args.only or any(o in x[0] for o in args.only)]

    hooks = hasattr(flash, "_prefill_block_kind")
    lines = []
    for is_latent, name, S, H, *rest in wanted:
        ks = jax.random.split(jax.random.PRNGKey(33), 5)

        def draw(key, *shape):
            return jax.random.normal(key, shape, jnp.bfloat16)

        if is_latent:
            nope, rope, dv = rest
            operands = (draw(ks[0], 1, H, S, nope), draw(ks[1], 1, H, S, rope),
                        draw(ks[2], 1, H, S, nope), draw(ks[3], 1, S, rope),
                        draw(ks[4], 1, H, S, dv))
            window, variants = None, ["kernel"]
        else:
            K, D, window = rest
            operands = (draw(ks[0], 1, S, H, D), draw(ks[1], 1, S, K, D),
                        draw(ks[2], 1, S, K, D))
            variants = ["kernel"] + (["all_edge", "full_grid"]
                                     if hooks else [])
        variants = [x for x in variants if x in args.variants]
        positions = jnp.arange(S, dtype=jnp.int32)[None, :]
        # a prompt of T tokens takes the bucket S with S / 2 < T <= S
        lengths = [T for T in args.true_len if S // 2 < T <= S]
        for variant in variants:
            for line in _time(flash, variant, operands, positions,
                              lengths or [S], window, is_latent, hooks,
                              args):
                line = dict(tree=os.path.relpath(tree), shape=name,
                            variant=variant, **line,
                            device=dev.device_kind)
                lines.append(line)
                print(json.dumps(line), flush=True)
    if args.fit and hooks:
        # least squares over every (shape, variant) of flash_prefill at
        # head_dim 128: time = whole * a + edge * b + none * c
        rows = [l for l in lines if "kinds" in l
                and "qwen3-next" not in l["shape"]
                and "latent" not in l["shape"]]
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


def _time(flash, variant, operands, positions, lengths, window, is_latent,
          hooks, args):
    """One (shape, variant) at each true length of `lengths`, compiled
    once (the length is an operand): times, kinds, digest. `flash` is
    the timed tree's module."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    S = positions.shape[1]
    saved = {}
    if variant == "all_edge":
        kind = saved["_prefill_block_kind"] = flash._prefill_block_kind

        def all_edge(*a, kind=kind):
            start, some, whole = kind(*a)
            return start, some, whole & False

        flash._prefill_block_kind = all_edge
    elif variant == "full_grid":
        saved["_prefill_key_steps"] = flash._prefill_key_steps
        flash._prefill_key_steps = lambda S, bq, bs, window: S // bs
    # the kernel's own jit would hand back the last variant's trace
    clear = getattr(getattr(flash, "_prefill_call", None),
                    "clear_cache", lambda: None)
    clear()
    try:
        if is_latent:
            fn = jax.jit(lambda kv_len, *xs: flash.latent_prefill(
                *xs, positions[:, 0], kv_len, scale=0.07,
                interpret=args.rehearse_cpu))
            rows_axis = 2           # [1, H, S, dv]
        else:
            fn = jax.jit(lambda kv_len, q, k, v: flash.flash_attention(
                q, k, v, positions=positions, kv_len=kv_len,
                sliding_window=window, interpret=args.rehearse_cpu))
            rows_axis = 1           # [1, S, H, D]
        for T in lengths:
            kv_len = jnp.asarray([T], jnp.int32)
            out = jax.block_until_ready(fn(kv_len, *operands))
            jax.block_until_ready(fn(kv_len, *operands))
            times = []
            for _ in range(args.reps):
                t0 = time.perf_counter()
                jax.block_until_ready(fn(kv_len, *operands))
                times.append(time.perf_counter() - t0)
            kinds = None
            if is_latent and hasattr(flash, "latent_prefill_block_kinds"):
                kinds = flash.latent_prefill_block_kinds(
                    S, S, operands[0].shape[1], 0, T)
            elif hooks and not is_latent:
                q, k = operands[:2]
                K = k.shape[2]
                kinds = flash.prefill_block_kinds(
                    S, S, K, q.shape[2] // K, q.shape[3], 0, T, window)
            real = jnp.take(out, jnp.arange(T), axis=rows_axis)
            line = dict(
                true_len=T, median_ms=1e3 * statistics.median(times),
                min_ms=1e3 * min(times), reps=args.reps,
                digest=hashlib.sha1(np.asarray(
                    real.astype(jnp.float32)).tobytes()).hexdigest()[:16])
            if kinds:
                line["kinds"] = kinds
            yield line
    finally:
        for attr, value in saved.items():
            setattr(flash, attr, value)
        clear()


if __name__ == "__main__":
    main()
