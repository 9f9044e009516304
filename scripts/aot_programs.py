#!/usr/bin/env python
"""Compile the engine's whole programs for a described v5e, no chip.

The chip's compiler is installed here (libtpu) and compiles for a TPU
that is described, not attached (on-chip-measurement guide, section
2). This hands the engine's own jitted programs shapes at Qwen3-4B's
full size on a described v5e:2x2 and prints, per program, what the
compiler says: compile seconds, argument / output / temporary bytes on
each device, Mosaic custom calls, collectives. It refuses what does
not fit the chip's memory and what cannot be partitioned, which is
what a chip run would otherwise find on chip time. Nothing runs: these
are facts about programs, never timings.

    python scripts/aot_programs.py                      # one chip, paged
    python scripts/aot_programs.py --kv-blocks 240      # refused: HBM
    python scripts/aot_programs.py --quantization int4 --kv-dtype int8
    python scripts/aot_programs.py --tp 4               # dense, sharded
    python scripts/aot_programs.py --config benchmark/configs/<name>.json
                    # that configuration on the slab path, at the
                    # slots, length and buckets its `serve_args` give

One process at a time can hold libtpu (/tmp/libtpu_lockfile).
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import re
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import (Mesh, NamedSharding,  # noqa: E402
                          PartitionSpec as P, SingleDeviceSharding)

from chip_smoke import QWEN3_4B  # noqa: E402
from ome_tpu import device  # noqa: E402
from ome_tpu.engine.core import DecodeState, InferenceEngine  # noqa: E402
from ome_tpu.models import llama  # noqa: E402
from ome_tpu.models.config import ModelConfig  # noqa: E402
from ome_tpu.perf.ledger import ProgramLedger  # noqa: E402

COLLECTIVES = ("all-reduce", "all-gather", "all-to-all",
               "collective-permute", "reduce-scatter")


TEXT_DIR = None     # --text-dir: where each program's compiled text goes
ONLY = None         # --only: the prefix of the programs to compile


def report(name: str, lowered) -> None:
    if ONLY and not name.startswith(tuple(ONLY.split(","))):
        return
    t0 = time.time()
    compiled = lowered.compile()
    ma, text = compiled.memory_analysis(), compiled.as_text()
    if TEXT_DIR:
        os.makedirs(TEXT_DIR, exist_ok=True)
        with open(os.path.join(TEXT_DIR, name + ".txt"), "w") as f:
            f.write(text)
    print(json.dumps({
        "program": name, "compile_s": round(time.time() - t0, 1),
        "arg_gb": round(ma.argument_size_in_bytes / 1e9, 3),
        "out_gb": round(ma.output_size_in_bytes / 1e9, 3),
        "temp_gb": round(ma.temp_size_in_bytes / 1e9, 3),
        "mosaic_calls": text.count("tpu_custom_call"),
        "collectives": {c: len(re.findall(rf"\b{c}(-start)?\(", text))
                        for c in COLLECTIVES}}), flush=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--layers", type=int, default=36)
    ap.add_argument("--slots", type=int, default=16)
    ap.add_argument("--max-seq", type=int, default=2048)
    ap.add_argument("--kv-blocks", type=int, default=198)
    ap.add_argument("--kv-dtype", default="bf16")
    ap.add_argument("--quantization", default="none")
    ap.add_argument("--tp", type=int, default=1, choices=(1, 4))
    ap.add_argument("--buckets", default="64,2048")
    ap.add_argument("--config", default=None,
                    help="a benchmark configuration's file: its model "
                         "on the dense slab (no pool), sized by its "
                         "serve_args unless --slots / --max-seq say "
                         "otherwise")
    ap.add_argument("--text-dir", default=None,
                    help="write each program's compiled text here, to "
                         "look for what its temporaries are")
    ap.add_argument("--only", default=None,
                    help="compile only the programs whose name starts "
                         "with this (or with one of these, comma-"
                         "separated: `prefill,decode`)")
    args = ap.parse_args()
    global TEXT_DIR, ONLY
    TEXT_DIR, ONLY = args.text_dir, args.only

    # code that asks the device still sees the CPU here; steering it
    # is this script's job, not an option of the program
    device.on_tpu = lambda: True
    from jax.experimental import topologies
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    cfg = ModelConfig.from_hf_config(
        dict(QWEN3_4B, num_hidden_layers=args.layers))
    if args.config:
        cfg = config_of_file(args)

    def init():
        p = llama.init_params(jax.random.PRNGKey(0), cfg)
        if args.quantization != "none":
            from ome_tpu.models.quant import quantize_params
            p = quantize_params(p, mode=args.quantization)
        return p

    shapes = jax.eval_shape(init)
    scope = contextlib.nullcontext()
    if args.tp == 1:
        one = SingleDeviceSharding(topo.devices[0])
        rep = kv_sh = one
        shardings = jax.tree.map(lambda _: one, shapes)
    else:
        from ome_tpu.ops.attention import heads_sharded_over
        from ome_tpu.parallel.sharding import param_shardings
        mesh = Mesh(np.array(topo.devices).reshape(1, 1, 4),
                    ("dp", "pp", "tp"))
        rep = NamedSharding(mesh, P())
        # the slab's merged rows [L, B, S, K * D] (llama.KVCache): a
        # chip's KV heads are contiguous lanes of the last axis
        kv_sh = NamedSharding(mesh, P(None, None, None, "tp"))
        shardings = param_shardings(shapes, mesh)
        scope = heads_sharded_over(mesh)

    def S(shape, dtype, sharding=rep):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    params = jax.tree.map(lambda l, s: S(l.shape, l.dtype, s),
                          shapes, shardings)
    paged = args.tp == 1 and not args.config
    eng = InferenceEngine(
        params, cfg, max_slots=args.slots, max_seq=args.max_seq,
        kv_block=128 if paged else 0,
        kv_blocks=args.kv_blocks if paged else None,
        kv_dtype=args.kv_dtype if paged else None,
        ledger=ProgramLedger("off"))
    L, B, Kh, Dh = cfg.num_layers, args.slots, cfg.num_kv_heads, \
        cfg.head_dim
    i32, f32 = jnp.int32, jnp.float32
    if paged:
        quantized = args.kv_dtype == "int8"
        pool = S((L, args.kv_blocks, 128, Kh, Dh),
                 jnp.int8 if quantized else cfg.dtype)
        scale = S((L, args.kv_blocks, Kh, 128), f32) \
            if quantized else None
        state = DecodeState(k=pool, v=pool, lengths=S((B,), i32),
                            tokens=S((B,), i32),
                            adapters=S((B,), i32),
                            k_scale=scale, v_scale=scale)
    elif args.config:
        # whatever the model's slots own (rows, a ring, recurrent
        # state, expert counters), as the engine itself builds it
        state = jax.tree.map(lambda a: S(a.shape, a.dtype),
                             jax.eval_shape(eng.new_state))
    else:
        slab = S((L, B, args.max_seq, Kh * Dh), cfg.dtype, kv_sh)
        state = DecodeState(k=slab, v=slab, lengths=S((B,), i32),
                            tokens=S((B,), i32),
                            adapters=S((B,), i32))
    key = S((2,), jnp.uint32)

    def sampling(n):
        return S((n,), f32), S((n,), i32), S((n,), f32)

    scalar = S((), i32)
    with scope:
        for b in (int(x) for x in args.buckets.split(",")):
            report(f"prefill[bucket={b}]", eng._prefill_fn.lower(
                params, S((1, b), i32), S((1,), i32), *sampling(1),
                key, S((1,), i32), bucket=b))
            kv = S((L, 1, b, Kh, Dh) if paged else (L, 1, b, Kh * Dh),
                   cfg.dtype, kv_sh)
            if args.config:
                # insert takes what this model's prefill hands back
                _, *handed = jax.eval_shape(
                    lambda *a: eng._prefill_fn(*a, bucket=b),
                    params, S((1, b), i32), S((1,), i32), *sampling(1),
                    key, S((1,), i32))
                handed = jax.tree.map(lambda a: S(a.shape, a.dtype),
                                      handed)
                report(f"insert[bucket={b}]", eng._insert_fn.lower(
                    state, *handed[:2], scalar, scalar, scalar, scalar,
                    *handed[2:], bucket=b))
            elif paged:
                report(f"insert_paged[bucket={b}]",
                       eng._insert_paged_fn.lower(
                           state, kv, kv, S((-(-b // 128),), i32),
                           scalar, scalar, scalar, scalar, bucket=b))
            else:
                report(f"insert[bucket={b}]", eng._insert_fn.lower(
                    state, kv, kv, scalar, scalar, scalar, scalar,
                    bucket=b))
        if paged:
            table = S((B, eng.max_blocks), i32)
            report("decode_paged", eng.programs["decode_paged"].lower(
                params, state, table, *sampling(B), key))
            report("decode_multi_paged[n=4]",
                   eng.programs["decode_multi_paged"].lower(
                       params, state, table, *sampling(B), key,
                       S((B,), i32), S((B, 4), i32), n=4))
        else:
            report("decode", eng.programs["decode"].lower(
                params, state, *sampling(B), key))
            if args.config:
                report("decode_multi[n=4]",
                       eng.programs["decode_multi"].lower(
                           params, state, *sampling(B), key,
                           S((B,), i32), S((B, 4), i32), n=4))
    return 0


def config_of_file(args) -> ModelConfig:
    """The model of a benchmark configuration's file as `serve` would
    build it on one chip, and `args` sized by its `serve_args` where
    the command line left the defaults."""
    with open(args.config) as f:
        file = json.load(f)
    hf = {k: v for k, v in file.items()
          if k not in ("source", "reduced", "assumed", "benchmark")}
    cfg = ModelConfig.from_hf_config(hf)
    if cfg.is_moe:
        cfg = cfg.replace(moe_impl="ragged")
    serve = [str(a) for a in file["benchmark"]["serve_args"]]
    for flag, name, default in (("--max-slots", "slots", 16),
                                ("--max-seq", "max_seq", 2048)):
        if flag in serve and getattr(args, name) == default:
            setattr(args, name, int(serve[serve.index(flag) + 1]))
    if args.buckets == "64,2048":
        args.buckets = ",".join(
            str(b) for b in file["benchmark"]["prefill_buckets"][-2:])
    return cfg


if __name__ == "__main__":
    sys.exit(main())
