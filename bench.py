#!/usr/bin/env python
"""Driver benchmark: sustained decode throughput of the flagship model.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"} plus
context keys (int8/int4 throughput, a measured per-step decode time
breakdown, prefill MFU, and measured-vs-spec rooflines).

The reference (bcfre/ome) publishes no hardware numbers (BASELINE.md) —
its headline metric is BenchmarkJob *output tokens/sec* against a served
InferenceService (SURVEY.md §6). This bench measures the same quantity
at the layer we own end-to-end on one chip: batched autoregressive
decode tokens/sec of the flagship Llama-class model with a KV cache.

Round-4 structure (measured ablations, scripts/perf_lab.py):
  * decode runs UNROLLED over layers with per-layer cache planes and
    lax.scan over MULTISTEP tokens per dispatch — vs round 3's
    scan-over-layers/one-step-per-dispatch shape this avoids the
    full-cache stacked-ys rewrite and amortizes the host dispatch
    over MULTISTEP tokens.
  * the per-step breakdown is MEASURED, not modeled: host dispatch
    (empty jit), weights+sampling floor (attention ablated), and the
    attention/KV remainder — persisted in the parsed JSON so the gap
    between quantized modes is attributable (round-3 verdict #1).
  * round-5 (verdict #1): the floor is `floor_k` — decode_k itself
    with ONLY the KV-cache read ablated (same unrolled layers, same
    8-step scan, same per-layer cache planes and writes, same chained
    dispatch loop) — so weights + attn_kv + dispatch ≈ step by
    construction and the achievable anchor (weights bytes / floor
    time) sits ABOVE the decode-effective bandwidth, where a credible
    ceiling must be. Round 4's floor used a different dispatch shape
    (stacked-layer scan, 1 step/dispatch) whose ~8 ms of host arg
    marshaling landed in weights_ms, pushing the "floor" above the
    full step and clamping attn_kv to 0.
  * vs_baseline stays spec-anchored for round-over-round
    comparability, vs_achievable reports against the measured ceiling.
  * prefill reports tokens/sec AND MFU against the chip's bf16 peak
    (verdict #3).
"""

from __future__ import annotations

import functools
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


def sync(x):
    """Force completion: JAX dispatches asynchronously, so every timed
    region ends in block_until_ready."""
    return jax.block_until_ready(x)

# The chip's published peaks come from ome_tpu/perf/ledger.py — the
# engine's online roofline and this offline bench must never disagree
# about what the hardware can do, and a device that is not in the
# table is an error there, never "a v5e".
from ome_tpu.perf.ledger import device_spec

import os

BATCH = 32
PREFILL = 128
DECODE_STEPS = 128
# decode steps per dispatch: a larger value amortizes the host
# dispatch further at the cost of a bigger unrolled program (env knob
# for perf experiments)
MULTISTEP = int(os.environ.get("OME_BENCH_MULTISTEP", "8"))
CACHE_LEN = PREFILL + DECODE_STEPS
TRIALS = 3


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def dispatch_ms() -> float:
    """Per-call host-dispatch (enqueue) cost: N CHAINED empty calls,
    ONE sync, so the sync's own latency is divided across them."""
    f = jax.jit(lambda t: t + 1)
    t = jnp.zeros((32, 1), jnp.int32)
    sync(f(t))
    n = 64
    best = float("inf")
    for _ in range(3):
        x = t
        t0 = time.perf_counter()
        for _ in range(n):
            x = f(x)
        sync(x)
        best = min(best, time.perf_counter() - t0)
    return best / n * 1000


def composition_main() -> None:
    """`bench.py composition`: the StepPlan composition matrix.

    Sweeps spec-tokens x steps-per-dispatch x pipeline-depth through
    the REAL Scheduler (docs/step-plan.md) on a repetitive workload —
    tiled 4-token prompt patterns, so greedy streams settle into the
    short cycles the n-gram drafter feeds on. Each cell reports
    sustained tokens/sec, the verify accept rate, and the planner's
    degradation counts (any nonzero count means the cell silently
    lost a composition feature — the thing this sweep exists to
    catch). The composed cells (spec>0 x K>1 x depth 1) must beat the
    best single-mechanism cell; perfgate gates every cell under the
    ^composition. bands and --cost-table exports them to the fleet
    simulator."""
    from ome_tpu.engine.core import InferenceEngine
    from ome_tpu.engine.scheduler import Request, Scheduler
    from ome_tpu.models import llama

    cfg = flagship_config()
    SLOTS = int(os.environ.get("OME_BENCH_COMP_SLOTS", "8"))
    NEW = int(os.environ.get("OME_BENCH_COMP_TOKENS", "48"))
    SPECS = tuple(int(x) for x in os.environ.get(
        "OME_BENCH_COMP_SPECS", "0,4").split(","))
    KS = tuple(int(x) for x in os.environ.get(
        "OME_BENCH_COMP_KS", "1,4,8").split(","))
    DEPTHS = tuple(int(x) for x in os.environ.get(
        "OME_BENCH_COMP_DEPTHS", "0,1").split(","))

    log(f"bench: [composition] devices={jax.devices()}")
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    # ONE engine across all cells: each Scheduler brings its own
    # metrics registry and slot bookkeeping, so reusing the engine
    # amortizes the compile cache across the matrix
    eng = InferenceEngine(params, cfg, max_slots=SLOTS,
                          max_seq=CACHE_LEN, prefill_buckets=[16])

    def run_cell(spec, k_, depth):
        sched = Scheduler(eng, overlap=True, pipeline_depth=depth,
                          spec_tokens=spec, steps_per_dispatch=k_)
        sched.start()

        def batch(seed):
            rng = np.random.default_rng(seed)
            reqs = []
            for _ in range(SLOTS):
                pat = rng.integers(0, cfg.vocab_size, size=4)
                ids = [int(x) for x in np.tile(pat, 4)]
                reqs.append(sched.submit(Request(
                    prompt_ids=ids, max_new_tokens=NEW,
                    stop_ids=[])))
            for r in reqs:
                r.done.wait(timeout=600)
            assert all(r.done.is_set() for r in reqs), \
                f"cell spec{spec}_k{k_}_d{depth} stalled"

        batch(3)  # compile + reach the repetitive steady state
        p0 = sched.stats["spec_proposed_tokens_total"]
        a0 = sched.stats["spec_accepted_tokens_total"]
        t0 = time.perf_counter()
        batch(3)  # same prompts: the drafter's n-gram table is hot
        dt = time.perf_counter() - t0
        proposed = sched.stats["spec_proposed_tokens_total"] - p0
        accepted = sched.stats["spec_accepted_tokens_total"] - a0
        degr = dict(sched.degradations)
        sched.stop()
        return {
            "tokens_per_sec": round(SLOTS * NEW / dt, 1),
            "accept_rate": round(accepted / max(proposed, 1), 3),
            "spec": spec, "k": k_, "depth": depth,
            "degraded_steps": sum(degr.values()),
        }, degr

    cells = {}
    for spec in SPECS:
        for k_ in KS:
            for depth in DEPTHS:
                name = f"spec{spec}_k{k_}_d{depth}"
                cell, degr = run_cell(spec, k_, depth)
                cells[name] = cell
                extra = "".join(
                    f" {c}={n}" for c, n in degr.items() if n)
                log(f"bench: [composition] {name}: "
                    f"{cell['tokens_per_sec']:.1f} tok/s, accept "
                    f"{100 * cell['accept_rate']:.0f}%{extra}")
    # a "single-mechanism" cell enables at most one of the three
    # features; the composed cells must beat the best of them
    single = {n: c["tokens_per_sec"] for n, c in cells.items()
              if (c["spec"] > 0) + (c["k"] > 1) + (c["depth"] > 0) <= 1}
    composed = {n: c["tokens_per_sec"] for n, c in cells.items()
                if c["spec"] > 0 and c["k"] > 1 and c["depth"] > 0}
    best_single = max(single.values()) if single else 0.0
    best_composed = max(composed.values()) if composed else 0.0
    if single and composed:
        log(f"bench: [composition] best single-mechanism "
            f"{best_single:.1f} tok/s -> best composed "
            f"{best_composed:.1f} tok/s "
            f"({100 * best_composed / best_single - 100:+.0f}%)")
    print(json.dumps({"composition": {
        "cells": cells,
        "best_single_tokens_per_sec": round(best_single, 1),
        "best_composed_tokens_per_sec": round(best_composed, 1),
        "composed_vs_best_single": round(
            best_composed / max(best_single, 1e-9), 3),
    }}))


def structured_main() -> None:
    """`bench.py structured`: grammar-masked decode vs unmasked.

    Sweeps masked-slot share (0/50/100%) x steps-per-dispatch through
    the REAL Scheduler. Masked slots carry a JsonAutomaton TokenMasker
    (byte tokenizer, shared template so the grammar mask cache engages
    across requests); unmasked slots decode the same repetitive
    workload the composition sweep uses. The headline ratio is the
    100%-masked cell's tokens/sec over the 0%-masked cell's at the
    same K — the device-resident mask table (docs/structured-outputs.md)
    exists to keep that near 1.0, with the host-side `mask_apply`
    phase collapsing to cache lookups. perfgate bands every cell and
    the ratio under ^structured., and --cost-table exports the cells."""
    from ome_tpu.engine import ByteTokenizer
    from ome_tpu.engine.core import InferenceEngine
    from ome_tpu.engine.scheduler import Request, Scheduler
    from ome_tpu.engine.structured import JsonAutomaton, TokenMasker
    from ome_tpu.models import llama

    cfg = flagship_config()
    SLOTS = int(os.environ.get("OME_BENCH_STRUCT_SLOTS", "8"))
    NEW = int(os.environ.get("OME_BENCH_STRUCT_TOKENS", "48"))
    SHARES = tuple(int(x) for x in os.environ.get(
        "OME_BENCH_STRUCT_SHARES", "0,50,100").split(","))
    KS = tuple(int(x) for x in os.environ.get(
        "OME_BENCH_STRUCT_KS", "1,4").split(","))

    log(f"bench: [structured] devices={jax.devices()}")
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    eng = InferenceEngine(params, cfg, max_slots=SLOTS,
                          max_seq=CACHE_LEN, prefill_buckets=[16])
    tok = ByteTokenizer()
    # the template automaton is pre-advanced into a JSON string: a
    # bare JsonAutomaton completes after one short greedy value
    # (`true`, `-3`) and eos-stops, leaving the cell prefill-bound;
    # inside a string every step is a real free grammar position —
    # long steady-state masked decode, the thing this sweep measures
    template_auto = JsonAutomaton()
    assert template_auto.advance(ord('"'))
    template = TokenMasker(tok, automaton=template_auto)

    def run_cell(share, k_):
        sched = Scheduler(eng, overlap=True, pipeline_depth=1,
                          steps_per_dispatch=k_)
        sched.start()
        n_masked = SLOTS * share // 100

        def batch(seed):
            rng = np.random.default_rng(seed)
            reqs = []
            for i in range(SLOTS):
                if i < n_masked:
                    reqs.append(sched.submit(Request(
                        prompt_ids=tok.encode(f"item {i}: "),
                        max_new_tokens=NEW,
                        masker=template.copy())))
                else:
                    pat = rng.integers(0, cfg.vocab_size, size=4)
                    ids = [int(x) for x in np.tile(pat, 4)]
                    reqs.append(sched.submit(Request(
                        prompt_ids=ids, max_new_tokens=NEW,
                        stop_ids=[])))
            for r in reqs:
                r.done.wait(timeout=600)
            assert all(r.done.is_set() for r in reqs), \
                f"cell share{share}_k{k_} stalled"
            return sum(len(r.output_ids) for r in reqs)

        batch(3)  # compile + warm the grammar mask cache
        best = 0.0
        mask_ms = 0.0
        for _ in range(TRIALS):  # host-noise dominated on CPU
            m0 = sched._ph["mask_apply"].sum
            t0 = time.perf_counter()
            produced = batch(3)
            dt = time.perf_counter() - t0
            if produced / dt > best:
                best = produced / dt
                mask_ms = (sched._ph["mask_apply"].sum - m0) * 1000
        degr = dict(sched.degradations)
        sched.stop()
        return {
            "tokens_per_sec": round(best, 1),
            "mask_apply_ms": round(mask_ms, 2),
            "share": share, "k": k_,
            "degraded_steps": sum(degr.values()),
        }

    cells = {}
    for share in SHARES:
        for k_ in KS:
            name = f"share{share}_k{k_}"
            cells[name] = run_cell(share, k_)
            log(f"bench: [structured] {name}: "
                f"{cells[name]['tokens_per_sec']:.1f} tok/s, "
                f"mask_apply {cells[name]['mask_apply_ms']:.2f} ms")
    # headline: fully-masked decode speed relative to unmasked at the
    # same K — the acceptance bar for device-resident masking is 0.9
    ratios = [cells[f"share100_k{k_}"]["tokens_per_sec"]
              / max(cells[f"share0_k{k_}"]["tokens_per_sec"], 1e-9)
              for k_ in KS
              if f"share100_k{k_}" in cells and f"share0_k{k_}" in cells]
    ratio = min(ratios) if ratios else 0.0
    mask_build = sum(c["mask_apply_ms"] for c in cells.values()
                     if c["share"] == 100)
    log(f"bench: [structured] structured_vs_unmasked "
        f"{ratio:.3f}, mask_build {mask_build:.2f} ms")
    print(json.dumps({"structured": {
        "cells": cells,
        "structured_vs_unmasked": round(ratio, 3),
        "mask_build_ms": round(mask_build, 2),
    }}))


def flagship_config():
    """~1.9B-parameter dense Llama-class config: big enough that
    decode is genuinely HBM-bound, small enough to fit one v5e chip
    (16G HBM) in bf16 with headroom for the KV cache.
    OME_BENCH_COMP_CONFIG=tiny swaps in the test config for smoke
    runs of the composition sweep off-TPU."""
    from ome_tpu.models import config as cfgs
    if os.environ.get("OME_BENCH_COMP_CONFIG") == "tiny":
        return cfgs.tiny_test().replace(max_seq_len=CACHE_LEN)
    return cfgs.ModelConfig(
        vocab_size=32768, hidden_size=2048, num_layers=24, num_heads=16,
        num_kv_heads=8, head_dim=128, intermediate_size=8192,
        rope_theta=500000.0, max_seq_len=CACHE_LEN)


def main() -> None:
    from ome_tpu.models import llama
    from ome_tpu.models.llama import (_layer, _proj, _rope_frequencies,
                                      apply_rope, attention, dense_mlp,
                                      rms_norm)
    from ome_tpu.models.quant import QTensor, quantize_params, \
        quantized_bytes

    cfg = flagship_config()

    log(f"bench: devices={jax.devices()}")
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    n_params = llama.param_count(params)
    log(f"bench: params={n_params/1e9:.2f}B")
    disp_ms = None  # measured after the first mode's compile+warmup
    # cold-start cost: first mode's prefill + decode compile+warm
    # wall time — the cost table's warmup_ms, which the fleet
    # simulator adds to replica spawn delay (sim/costmodel.py)
    warm_ms = None

    @jax.jit
    def prefill(params, tokens, cache):
        logits, cache = llama.forward(params, cfg, tokens, cache=cache)
        return jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32), cache

    prompt = jax.random.randint(
        jax.random.PRNGKey(1), (BATCH, PREFILL), 0, cfg.vocab_size,
        dtype=jnp.int32)

    def split_layers(p):
        per = [jax.tree.map(lambda a: a[l], p["layers"])
               for l in range(cfg.num_layers)]
        top = {k: v for k, v in p.items() if k != "layers"}
        return per, top

    def head_logits(top, x):
        x = rms_norm(x, top["final_norm"], cfg.rms_norm_eps)
        head = top.get("lm_head")
        head = head.dequant(cfg.dtype) if isinstance(head, QTensor) \
            else head
        return jnp.einsum("bsd,dv->bsv", x, head,
                          preferred_element_type=jnp.float32)

    def embed(top, tok):
        emb = top["embed"]
        return emb.take(tok, cfg.dtype) if isinstance(emb, QTensor) \
            else jnp.take(emb, tok, axis=0).astype(cfg.dtype)

    def one_step(per, top, tok, ks, vs, index):
        """Unrolled decode step over per-layer cache planes."""
        B = tok.shape[0]
        x = embed(top, tok)
        freqs = _rope_frequencies(cfg)
        positions = jnp.broadcast_to(index[None, None], (B, 1))
        kv_len = jnp.broadcast_to(index + 1, (B,))
        nks, nvs = [], []
        for l in range(cfg.num_layers):
            x, nc = _layer(x, per[l], cfg, freqs, positions, kv_len,
                           (ks[l], vs[l]), index)
            nks.append(nc[0])
            nvs.append(nc[1])
        tok = jnp.argmax(head_logits(top, x), axis=-1).astype(jnp.int32)
        return tok, nks, nvs, index + 1

    @jax.jit
    def decode_k(per, top, tok, ks, vs, index):
        def body(carry, _):
            tok, ks, vs, index = carry
            return one_step(per, top, tok, *(ks, vs), index), None

        (tok, ks, vs, index), _ = lax.scan(
            body, (tok, ks, vs, index), None, length=MULTISTEP)
        return tok, ks, vs, index

    def one_step_floor(per, top, tok, ks, vs, index):
        """`one_step` with ONLY the KV-cache attention READ ablated.

        Same per-layer weight projections, same RoPE, same cache-plane
        writes, same sampling head, same carry structure — so `floor_k`
        below compiles to the IDENTICAL dispatch shape as `decode_k`
        (same ~300 buffers in/out, same 8-step scan, same jit-boundary
        cache copy), and `step - floor` isolates exactly the KV-cache
        stream + attention compute. Attention here runs over just the
        freshly written single token (the `cache_kv=None` shape of
        llama._mha), so q/k/v stay live and nothing is DCE'd.

        Round-4 verdict #1: the old floor scanned the STACKED layer
        tree with one step per dispatch, a different dispatch shape
        whose ~8 ms/call of host arg-marshaling landed in `weights_ms`
        and pushed the floor ABOVE the full step."""
        B = tok.shape[0]
        x = embed(top, tok)
        freqs = _rope_frequencies(cfg)
        positions = jnp.broadcast_to(index[None, None], (B, 1))
        nks, nvs = [], []
        for l in range(cfg.num_layers):
            lp = per[l]
            h = rms_norm(x, lp["attn_norm"], cfg.rms_norm_eps)
            q = _proj(h, lp["wq"], cfg.dtype,
                      out_dims=(cfg.num_heads, cfg.head_dim),
                      out_major=True)
            k = _proj(h, lp["wk"], cfg.dtype,
                      out_dims=(cfg.num_kv_heads, cfg.head_dim),
                      out_major=True)
            v = _proj(h, lp["wv"], cfg.dtype,
                      out_dims=(cfg.num_kv_heads, cfg.head_dim),
                      out_major=True)
            q = apply_rope(q, positions, freqs)
            k = apply_rope(k, positions, freqs)
            # the slab's rows lie merged, [B, S, K * Dh] (llama.KVCache)
            nks.append(lax.dynamic_update_slice(
                ks[l], k.astype(ks[l].dtype).reshape(B, 1, -1),
                (0, index, 0)))
            nvs.append(lax.dynamic_update_slice(
                vs[l], v.astype(vs[l].dtype).reshape(B, 1, -1),
                (0, index, 0)))
            # single-key softmax: no cache read; XLA backend — the
            # flash-decode kernel's grid assumes a real cache length
            attn = attention(q, k, v, backend="xla")
            a = _proj(attn, lp["wo"], cfg.dtype, flatten=2)
            x = x + a
            h = rms_norm(x, lp["mlp_norm"], cfg.rms_norm_eps)
            x = x + dense_mlp(h, lp, cfg)
        tok = jnp.argmax(head_logits(top, x), axis=-1).astype(jnp.int32)
        return tok, nks, nvs, index + 1

    @jax.jit
    def floor_k(per, top, tok, ks, vs, index):
        def body(carry, _):
            tok, ks, vs, index = carry
            return one_step_floor(per, top, tok, ks, vs, index), None

        (tok, ks, vs, index), _ = lax.scan(
            body, (tok, ks, vs, index), None, length=MULTISTEP)
        return tok, ks, vs, index

    def mode_bytes(p) -> int:
        return quantized_bytes(p)

    def run_mode(p, label: str):
        """-> (tok/s, step_ms, weights_ms, attn_ms)."""
        nonlocal disp_ms, warm_ms
        per, top = split_layers(p)
        t0 = time.perf_counter()
        tok, cache = prefill(p, prompt, llama.KVCache.create(
            cfg, BATCH, CACHE_LEN, merged=True))
        ks = [cache.k[l] for l in range(cfg.num_layers)]
        vs = [cache.v[l] for l in range(cfg.num_layers)]
        index = cache.index
        st = decode_k(per, top, tok, ks, vs, index)  # compile
        sync(st[0])
        log(f"bench: [{label}] prefill(batch={BATCH}, len={PREFILL}) "
            f"+ compile {time.perf_counter()-t0:.1f}s")
        if warm_ms is None:
            warm_ms = (time.perf_counter() - t0) * 1000
        if disp_ms is None:
            disp_ms = dispatch_ms()
            log(f"bench: dispatch floor {disp_ms:.2f} ms")

        n_disp = (DECODE_STEPS - 1) // MULTISTEP
        steps = n_disp * MULTISTEP
        best = float("inf")
        for _ in range(TRIALS):
            tok, cache = prefill(p, prompt, llama.KVCache.create(
                cfg, BATCH, CACHE_LEN, merged=True))
            ks = [cache.k[l] for l in range(cfg.num_layers)]
            vs = [cache.v[l] for l in range(cfg.num_layers)]
            st = (tok, ks, vs, cache.index)
            st = decode_k(per, top, *st)  # warm, not timed
            sync(st[0])
            t0 = time.perf_counter()
            for _ in range(n_disp - 1):
                st = decode_k(per, top, *st)
            sync(st[0])
            best = min(best, time.perf_counter() - t0)
        step_ms = best / ((n_disp - 1) * MULTISTEP) * 1000
        tps = BATCH / (step_ms / 1000)

        # weights+sampling floor: floor_k is decode_k with only the
        # KV-cache read ablated, measured over the SAME chained
        # dispatch loop — floor and full step share an identical
        # dispatch shape, so step - floor isolates attention/KV
        fbest = float("inf")
        for _ in range(TRIALS):
            tok2, cache2 = prefill(p, prompt, llama.KVCache.create(
                cfg, BATCH, CACHE_LEN, merged=True))
            ks2 = [cache2.k[l] for l in range(cfg.num_layers)]
            vs2 = [cache2.v[l] for l in range(cfg.num_layers)]
            st2 = (tok2, ks2, vs2, cache2.index)
            st2 = floor_k(per, top, *st2)  # warm/compile, not timed
            sync(st2[0])
            t0 = time.perf_counter()
            for _ in range(n_disp - 1):
                st2 = floor_k(per, top, *st2)
            sync(st2[0])
            fbest = min(fbest, time.perf_counter() - t0)
        floor_ms = fbest / ((n_disp - 1) * MULTISTEP) * 1000
        weights_ms = max(floor_ms - disp_ms / MULTISTEP, 0.0)
        attn_ms = max(step_ms - floor_ms, 0.0)
        log(f"bench: [{label}] decode {steps} x batch {BATCH}: best-of-"
            f"{TRIALS} {step_ms:.2f} ms/step -> {tps:.1f} tok/s "
            f"(weights {weights_ms:.2f} + attn/kv {attn_ms:.2f} + "
            f"dispatch {disp_ms/MULTISTEP:.2f})")
        return tps, step_ms, weights_ms, attn_ms

    # -- bf16 headline --------------------------------------------------
    bf16_tps, bf16_step, bf16_w, bf16_attn = run_mode(params, "bf16")

    # -- decode-loop step gap: sync fetch vs pipelined offload ----------
    # The serving scheduler's host bubble (the quantity its
    # ome_engine_step_gap_seconds histogram tracks): time from one
    # decode dispatch RETURNING to the next one STARTING. "sync"
    # fetches each dispatch's tokens before dispatching again (the
    # --pipeline-depth 0 loop); "pipelined" starts an async host copy
    # and reads tokens one dispatch LATE (depth 1), so the fetch
    # overlaps device execution instead of serializing with it.
    def step_gap_ms(pipelined: bool) -> float:
        per, top = split_layers(params)
        tok, cache = prefill(params, prompt, llama.KVCache.create(
            cfg, BATCH, CACHE_LEN, merged=True))
        ks = [cache.k[l] for l in range(cfg.num_layers)]
        vs = [cache.v[l] for l in range(cfg.num_layers)]
        st = (tok, ks, vs, cache.index)
        st = decode_k(per, top, *st)  # warm, not timed
        sync(st[0])
        n_disp = (DECODE_STEPS - 1) // MULTISTEP
        gaps, disp_end, pending = [], None, None
        for _ in range(n_disp - 1):
            t0 = time.perf_counter()
            if disp_end is not None:
                gaps.append(t0 - disp_end)
            st = decode_k(per, top, *st)
            disp_end = time.perf_counter()
            toks = st[0]
            if pipelined:
                copy = getattr(toks, "copy_to_host_async", None)
                if copy is not None:
                    copy()
                if pending is not None:
                    np.asarray(jax.device_get(pending))
                pending = toks
            else:
                np.asarray(jax.device_get(toks))
        if pending is not None:
            np.asarray(jax.device_get(pending))
        return sum(gaps) / max(len(gaps), 1) * 1000

    gap_sync = step_gap_ms(False)
    gap_pipe = step_gap_ms(True)
    log(f"bench: [bf16] decode {bf16_tps:.1f} tok/s | mean step gap "
        f"{gap_sync:.2f} ms/dispatch sync-fetch -> {gap_pipe:.2f} ms "
        f"pipelined (async token offload, one-dispatch lag)")

    # -- steady-state prefill (TTFT proxy) + MFU ------------------------
    cache2 = llama.KVCache.create(cfg, BATCH, CACHE_LEN, merged=True)
    prompt2 = jax.random.randint(jax.random.PRNGKey(2), (BATCH, PREFILL),
                                 0, cfg.vocab_size, dtype=jnp.int32)
    sync(prefill(params, prompt2, cache2)[0])
    pbest = float("inf")
    for _ in range(TRIALS):
        # 4 chained prefill dispatches, ONE sync: the sync's latency
        # is divided out of the per-call number
        t0 = time.perf_counter()
        for _ in range(4):
            t, _ = prefill(params, prompt2, cache2)
        sync(t)
        pbest = min(pbest, (time.perf_counter() - t0) / 4)
    T = BATCH * PREFILL
    pf_flops = 2 * n_params * T + 2 * cfg.num_layers * BATCH * (
        PREFILL ** 2) * cfg.num_heads * cfg.head_dim
    peak = device_spec()["peak_tflops"] * 1e12
    mfu = pf_flops / pbest / peak
    log(f"bench: steady prefill {pbest*1000:.0f} ms "
        f"({T/pbest:.0f} prefill tok/s, MFU {100*mfu:.1f}%)")
    del cache2, prompt2

    # -- quantized serving paths (engine --quantization int8/int4) -----
    q8 = quantize_params(params, mode="int8")
    q8_bytes = mode_bytes(q8)
    int8_tps, int8_step, int8_w, int8_attn = run_mode(q8, "int8")
    del q8
    q4 = quantize_params(params, mode="int4")
    q4_bytes = mode_bytes(q4)
    int4_tps, int4_step, int4_w, int4_attn = run_mode(q4, "int4")
    del q4
    log(f"bench: int8 {int8_tps:.1f} tok/s "
        f"({100*int8_tps/bf16_tps-100:+.0f}% vs bf16, "
        f"{q8_bytes/1e9:.2f} GB weights) | int4 {int4_tps:.1f} tok/s "
        f"({100*int4_tps/bf16_tps-100:+.0f}%, {q4_bytes/1e9:.2f} GB)")

    # -- paged-KV decode sweep: batch x pool dtype ----------------------
    # Measures the paged KERNEL PATH (ops/paged.py block-table
    # attention + pool scatter) in this bench's unrolled+multistep
    # harness — the shape that amortizes the host dispatch — across
    # batch {64, 128, 256} and pool dtype {bf16, int8}. The serving
    # engine's compiled program (llama.forward_paged: scan over
    # layers with the pool in its carry, token-exactness in
    # tests/test_paged_kv.py and tests/test_kv_int8.py) shares the
    # kernels and the whole-pool layout but not the unroll;
    # these numbers bound what that program reaches as its dispatch
    # amortization improves. Pool sized to dense-equivalent rows per
    # point, so the int8 column shows the --kv-dtype int8 trade the
    # engine offers: ~half the HBM per slot (per-token row is
    # L*K*(Dk+Dv) int8 bytes + 2*4 f32 scale bytes/head vs
    # L*K*(Dk+Dv)*2 bf16 — a 1.94x ratio at Dh=128) buys roughly
    # double the resident batch at fixed pool bytes, and the sweep
    # shows what that larger batch yields in tok/s.
    def bench_paged(p, PB: int, quantized: bool):
        """-> (tok/s, HBM bytes per decode slot at CACHE_LEN)."""
        from ome_tpu.ops.paged import paged_attention
        bs = 128
        bps = CACHE_LEN // bs               # blocks per slot
        nblk = PB * bps + 1
        per, top = split_layers(p)
        rows = jnp.arange(PB)
        # slot i owns blocks [1 + bps*i, ...] — block 0 is trash
        table = jnp.asarray(
            np.arange(PB * bps).reshape(PB, bps) + 1, jnp.int32)

        def one_step_paged(per, top, tok, ks, vs, kss, vss, index):
            x = embed(top, tok)
            freqs = _rope_frequencies(cfg)
            positions = index[:, None]
            kv_len = index + 1
            blk = table[rows, index // bs]
            off = index % bs
            for l in range(cfg.num_layers):
                lp = per[l]
                h = rms_norm(x, lp["attn_norm"], cfg.rms_norm_eps)
                q = _proj(h, lp["wq"], cfg.dtype,
                          out_dims=(cfg.num_heads, cfg.head_dim),
                          out_major=True)
                k = _proj(h, lp["wk"], cfg.dtype,
                          out_dims=(cfg.num_kv_heads, cfg.head_dim),
                          out_major=True)
                v = _proj(h, lp["wv"], cfg.dtype,
                          out_dims=(cfg.num_kv_heads, cfg.head_dim),
                          out_major=True)
                q = apply_rope(q, positions, freqs)
                k = apply_rope(k, positions, freqs)
                if quantized:
                    # per-(row, head) amax/127 symmetric — the same
                    # discipline as llama.forward_paged's append
                    def qrow(x2):
                        xf = x2[:, 0].astype(jnp.float32)
                        amax = jnp.max(jnp.abs(xf), axis=-1)
                        sc = jnp.maximum(amax, 1e-8) / 127.0
                        qv = jnp.clip(jnp.round(xf / sc[..., None]),
                                      -127, 127).astype(jnp.int8)
                        return qv, sc
                    kq, ksc = qrow(k)
                    vq, vsc = qrow(v)
                    ks = ks.at[l, blk, off].set(kq)
                    vs = vs.at[l, blk, off].set(vq)
                    kss = kss.at[l, blk, :, off].set(ksc)
                    vss = vss.at[l, blk, :, off].set(vsc)
                else:
                    ks = ks.at[l, blk, off].set(k[:, 0])
                    vs = vs.at[l, blk, off].set(v[:, 0])
                attn = paged_attention(q, ks, vs, table, kv_len, l,
                                       k_scale=kss, v_scale=vss)
                x = x + _proj(attn, lp["wo"], cfg.dtype, flatten=2)
                h = rms_norm(x, lp["mlp_norm"], cfg.rms_norm_eps)
                x = x + dense_mlp(h, lp, cfg)
            tok = jnp.argmax(head_logits(top, x),
                             axis=-1).astype(jnp.int32)
            return tok, ks, vs, kss, vss, index + 1

        @jax.jit
        def paged_k(per, top, tok, ks, vs, kss, vss, index):
            def body(carry, _):
                return one_step_paged(per, top, *carry), None

            carry, _ = lax.scan(body, (tok, ks, vs, kss, vss, index),
                                None, length=MULTISTEP)
            return carry

        K, Dh = cfg.num_kv_heads, cfg.head_dim
        pool_dt = jnp.int8 if quantized else cfg.dtype
        # whole pools [L, N, bs, K, D], written and read in place
        # through (layer, block): ops/paged.py's layout
        L = cfg.num_layers
        ks = jnp.zeros((L, nblk, bs, K, Dh), pool_dt)
        vs = jnp.zeros((L, nblk, bs, K, Dh), pool_dt)
        kss = jnp.zeros((L, nblk, K, bs), jnp.float32) \
            if quantized else None
        vss = jnp.zeros((L, nblk, K, bs), jnp.float32) \
            if quantized else None
        tok0 = jnp.zeros((PB, 1), jnp.int32)
        index0 = jnp.full((PB,), PREFILL, jnp.int32)
        n_disp = (DECODE_STEPS - 1) // MULTISTEP
        best = float("inf")
        for _ in range(2):
            st = (tok0, ks, vs, kss, vss, index0)
            st = paged_k(per, top, *st)  # compile/warm
            sync(st[0])
            t0 = time.perf_counter()
            for _ in range(n_disp - 1):
                st = paged_k(per, top, *st)
            sync(st[0])
            best = min(best, time.perf_counter() - t0)
        step_ms = best / ((n_disp - 1) * MULTISTEP) * 1000
        itemsize = jnp.dtype(pool_dt).itemsize
        row_bytes = cfg.num_layers * K * 2 * Dh * itemsize
        if quantized:
            row_bytes += cfg.num_layers * K * 2 * 4  # f32 scales
        return PB / (step_ms / 1000), row_bytes * bps * bs

    paged_sweep = {}
    paged_tps = None
    for qlabel, qz in (("bf16", False), ("int8", True)):
        paged_sweep[qlabel] = {}
        for PB in (64, 128, 256):
            tps, slot_bytes = bench_paged(params, PB, qz)
            paged_sweep[qlabel][str(PB)] = {
                "tokens_per_sec": round(tps, 1),
                "hbm_per_slot_bytes": int(slot_bytes),
            }
            log(f"bench: [paged {qlabel}] decode batch {PB}: "
                f"{tps:.1f} tok/s, {slot_bytes/1e6:.1f} MB/slot "
                f"(block-table pool attention)")
            if qlabel == "bf16" and PB == 64:
                paged_tps = tps
    if paged_tps is None:
        raise RuntimeError("paged bf16 batch-64 point failed — the "
                           "headline paged metric has no value")
    try:
        cap_ratio = (paged_sweep["bf16"]["64"]["hbm_per_slot_bytes"]
                     / paged_sweep["int8"]["64"]["hbm_per_slot_bytes"])
        paged_sweep["capacity_ratio_bf16_over_int8"] = round(
            cap_ratio, 3)
        log(f"bench: [paged] int8 pool fits {cap_ratio:.2f}x the "
            f"slots of bf16 at fixed HBM bytes")
    except (KeyError, ZeroDivisionError):
        pass

    # -- self-drafting speculative decode (engine verify path) ----------
    # Measures the SERVING engine's n-gram draft + batched-verify loop
    # (engine/spec.py + InferenceEngine.verify — the --spec-tokens
    # path) against the same engine's plain decode loop, on a
    # high-n-gram-hit workload: after a greedy warmup the random-weight
    # streams settle into short cycles (as repetitive serving traffic
    # does), so the prompt-lookup drafter proposes the continuation
    # and the verify forward accepts most of it — one weight read
    # yields several tokens per slot.
    def bench_spec(p):
        from ome_tpu.engine import spec as spec_drafter
        from ome_tpu.engine.core import InferenceEngine

        K_SPEC = int(os.environ.get("OME_BENCH_SPEC_K", "4"))
        SLOTS = BATCH
        WARM, MEAS = 40, 24  # rows: 17 + 40 + 24 + 5 + 24*5 <= 256
        eng = InferenceEngine(p, cfg, max_slots=SLOTS,
                              max_seq=CACHE_LEN, prefill_buckets=[16])
        state = eng.new_state()
        rng = np.random.default_rng(7)
        streams = []
        for s in range(SLOTS):
            pat = rng.integers(0, cfg.vocab_size, size=4)
            ids = [int(x) for x in np.tile(pat, 4)]  # 16-token prompt
            tok, kv, true_len, bucket = eng.prefill(ids)
            state = eng.insert(state, kv, s, true_len, tok, bucket)
            streams.append(ids + [tok])
        B = SLOTS
        t = np.zeros((B,), np.float32)
        tk = np.zeros((B,), np.int32)
        tp = np.ones((B,), np.float32)
        for _ in range(WARM):  # reach the repetitive steady state
            state, toks = eng.decode(state, t, tk, tp)
            for s, v in enumerate(np.asarray(toks)):
                streams[s].append(int(v))
        # plain decode tok/s, sync fetch per step (depth-0 loop shape)
        t0 = time.perf_counter()
        for _ in range(MEAS):
            state, toks = eng.decode(state, t, tk, tp)
            for s, v in enumerate(np.asarray(toks)):
                streams[s].append(int(v))
        plain_tps = SLOTS * MEAS / (time.perf_counter() - t0)

        def spec_step():
            drafts = np.zeros((B, K_SPEC), np.int32)
            dlen = np.zeros((B,), np.int32)
            for s in range(B):
                d = spec_drafter.propose(streams[s], K_SPEC)
                drafts[s, :d.size] = d
                dlen[s] = d.size
            nonlocal state
            state, out, acc = eng.verify(state, drafts, dlen, t, tk, tp)
            host_out, host_acc = np.asarray(out), np.asarray(acc)
            emitted = 0
            for s in range(B):
                n = int(host_acc[s]) + 1
                streams[s].extend(int(x) for x in host_out[s, :n])
                emitted += n
            return int(dlen.sum()), int(host_acc.sum()), emitted

        spec_step()  # compile the verify program, not timed
        proposed = accepted = emitted = 0
        t0 = time.perf_counter()
        for _ in range(MEAS):
            pr, ac, em = spec_step()
            proposed += pr
            accepted += ac
            emitted += em
        spec_tps = emitted / (time.perf_counter() - t0)
        return plain_tps, spec_tps, accepted / max(proposed, 1), K_SPEC

    spec_plain_tps, spec_tps, spec_rate, spec_k = bench_spec(params)
    log(f"bench: [spec] k={spec_k} batch {BATCH}: plain "
        f"{spec_plain_tps:.1f} tok/s -> spec {spec_tps:.1f} tok/s "
        f"({100*spec_tps/spec_plain_tps-100:+.0f}%, accept rate "
        f"{100*spec_rate:.0f}%)")

    # -- engine multi-token device decode (--steps-per-dispatch K) ------
    # The SERVING engine's fused decode loop (InferenceEngine
    # .decode_multi: lax.fori_loop over {forward, sample, KV append}
    # with on-device stop/budget masking — docs/multi-step-decode.md).
    # The raw decode_k harness above already proves the shape wins;
    # this sweep measures the REAL engine program — jit-boundary state
    # donation, per-iteration PRNG fold, stop-table compare — at
    # K in {1, 4, 8}. Per-token dispatch share falls as disp_ms / K
    # while step_ms approaches the device-bound floor; the scheduler
    # exposes the same knob as --steps-per-dispatch.
    def bench_multistep(p):
        from ome_tpu.engine.core import InferenceEngine

        SLOTS = BATCH
        eng = InferenceEngine(p, cfg, max_slots=SLOTS,
                              max_seq=CACHE_LEN, prefill_buckets=[16])
        state = eng.new_state()
        rng = np.random.default_rng(13)
        for s in range(SLOTS):
            ids = [int(x) for x in
                   rng.integers(0, cfg.vocab_size, size=16)]
            tok, kv, true_len, bucket = eng.prefill(ids)
            state = eng.insert(state, kv, s, true_len, tok, bucket)
        t = np.zeros((SLOTS,), np.float32)         # greedy
        tk = np.zeros((SLOTS,), np.int32)
        tp = np.ones((SLOTS,), np.float32)
        stops = np.full((SLOTS, 1), -1, np.int32)  # never fires
        per_k = {}
        for k_ in (1, 4, 8):
            budget = np.full((SLOTS,), k_, np.int32)
            n_disp = 48 // k_      # same 48 timed tokens per K
            # compile + warm dispatch, not timed (state donation flows
            # through, as in the scheduler's lag queue)
            state, toks, _adv = eng.decode_multi(
                state, t, tk, tp, steps=k_, budget=budget,
                stop_ids=stops)
            sync(toks)
            t0 = time.perf_counter()
            for _ in range(n_disp):
                state, toks, _adv = eng.decode_multi(
                    state, t, tk, tp, steps=k_, budget=budget,
                    stop_ids=stops)
            sync(toks)
            step_ms = (time.perf_counter() - t0) / (n_disp * k_) * 1000
            per_k[k_] = step_ms
            log(f"bench: [multistep] K={k_}: {step_ms:.2f} ms/token -> "
                f"{SLOTS/(step_ms/1000):.1f} tok/s (dispatch share "
                f"{disp_ms/k_:.3f} ms/token)")
        return per_k

    multistep_ms = bench_multistep(params)

    # -- scheduler step-phase attribution -------------------------------
    # Drives the SERVING scheduler (pipelined decode, depth 1) over the
    # real engine and reads back its ome_engine_step_phase_seconds
    # histograms — the same per-phase attribution an operator scrapes
    # from /metrics, here reduced to a mean-ms-per-step table. The
    # phases partition decode_step + step_gap: dispatch (the compiled
    # decode call), mask_apply (grammar masks; zero in this unmasked
    # workload), device_wait (blocking at the lag-queue token read),
    # host_sample (emit/finish bookkeeping after the read).
    def bench_step_phases(p):
        from ome_tpu.engine.core import InferenceEngine
        from ome_tpu.engine.scheduler import Request, Scheduler

        SLOTS = 8
        eng = InferenceEngine(p, cfg, max_slots=SLOTS,
                              max_seq=CACHE_LEN, prefill_buckets=[16])
        sched = Scheduler(eng, overlap=True, pipeline_depth=1)
        sched.start()
        rng = np.random.default_rng(11)
        reqs = []
        for _ in range(SLOTS):
            ids = [int(x) for x in
                   rng.integers(0, cfg.vocab_size, size=16)]
            reqs.append(sched.submit(
                Request(prompt_ids=ids, max_new_tokens=48)))
        for r in reqs:
            r.done.wait(timeout=300)
        phases = {}
        for name in ("dispatch", "mask_apply", "device_wait",
                     "host_sample"):
            child = sched._h_step_phase.labels(phase=name)
            phases[name] = (child.sum, child.count)
        step_sum = sched._h_decode_step.sum + sched._h_step_gap.sum
        steps = max(sched._h_decode_step.count, 1)
        sched.stop()
        return phases, step_sum, steps

    phase_raw, phase_step_sum, phase_steps = bench_step_phases(params)
    phase_total = sum(s for s, _ in phase_raw.values())
    step_phase_ms = {}
    log(f"bench: [phases] per-step attribution over {phase_steps} "
        f"scheduler steps (ome_engine_step_phase_seconds):")
    log(f"bench:   {'phase':<12} {'mean ms':>9} {'share':>7}")
    for name, (s, _count) in phase_raw.items():
        mean_ms = s / phase_steps * 1000
        step_phase_ms[name] = round(mean_ms, 3)
        share = s / phase_total if phase_total else 0.0
        log(f"bench:   {name:<12} {mean_ms:9.3f} {100*share:6.1f}%")
    phase_cov = phase_total / max(phase_step_sum, 1e-9)
    log(f"bench:   phase sum covers {100*phase_cov:.0f}% of "
        f"decode_step + step_gap")

    # -- rooflines ------------------------------------------------------
    # Per decode step the chip must read all weights once (amortized
    # across the batch) + each sequence's KV cache.
    bw_spec = device_spec()["hbm_gbps"]
    bf16_bytes = n_params * 2
    # the achievable anchor IS the weights floor: a weights-shaped
    # stream through the real matmul graph, not a synthetic probe
    bw_ach = bf16_bytes / (max(bf16_w, 1e-3) / 1000) / 1e9
    kv_bytes = (cfg.num_layers * CACHE_LEN * cfg.num_kv_heads * cfg.head_dim
                * 2 * 2)  # k+v, bf16, per sequence, full capacity
    # TRUE bytes moved: the flash-decode kernel DMA-clamps K/V reads to
    # the valid rows (ops/flash.py BlockSpec index clamp), so the
    # effective-bandwidth number uses the AVERAGE valid KV length over
    # the timed window — not cache capacity (round-4 verdict: the
    # anchor must sit at or above what decode itself sustains)
    t_lo = PREFILL + MULTISTEP          # first timed step (post-warm)
    t_hi = PREFILL + MULTISTEP * ((DECODE_STEPS - 1) // MULTISTEP)
    avg_kv = (t_lo + t_hi) / 2
    kv_bytes_true = kv_bytes * avg_kv / CACHE_LEN
    step_bytes = bf16_bytes + BATCH * kv_bytes  # capacity (vs_baseline)
    eff_gbps = (bf16_bytes + BATCH * kv_bytes_true) \
        * bf16_tps / BATCH / 1e9
    roof_spec = bw_spec * 1e9 / step_bytes * BATCH
    roof_ach = bw_ach * 1e9 / step_bytes * BATCH
    vs = bf16_tps / roof_spec
    vs_ach = bf16_tps / roof_ach

    log(f"bench: decode effective {eff_gbps:.0f} GB/s | achievable "
        f"(weights-stream anchor) {bw_ach:.0f} GB/s | spec {bw_spec:.0f}")
    log(f"bench: roofline vs spec {100*vs:.1f}% | vs achievable "
        f"{100*vs_ach:.1f}%")
    multistep_json = {}
    for k_, sm in multistep_ms.items():
        tps_k = BATCH / (sm / 1000)
        multistep_json[str(k_)] = {
            "step_ms": round(sm, 2),
            "tokens_per_sec": round(tps_k, 1),
            "dispatch_share_ms": round(disp_ms / k_, 3),
            "roofline_vs_spec": round(tps_k / roof_spec, 3),
        }
    print(json.dumps({
        "metric": "decode_tokens_per_sec_1.9B_bf16_batch32",
        "value": round(bf16_tps, 1),
        "unit": "tokens/s",
        "vs_baseline": round(vs, 3),
        "vs_achievable": round(vs_ach, 3),
        "best_of": TRIALS,
        "int8_tokens_per_sec": round(int8_tps, 1),
        "int4_tokens_per_sec": round(int4_tps, 1),
        "paged_decode_tokens_per_sec_batch64": round(paged_tps, 1),
        "paged_sweep": paged_sweep,
        "spec_decode_tokens_per_sec": round(spec_tps, 1),
        "spec_accept_rate": round(spec_rate, 3),
        "spec_plain_tokens_per_sec": round(spec_plain_tps, 1),
        "spec_k": spec_k,
        "multistep": multistep_json,
        "int4_vs_int8": {
            "int4_tokens_per_sec": round(int4_tps, 1),
            "int8_tokens_per_sec": round(int8_tps, 1),
            "int4_ahead": bool(int4_tps > int8_tps),
            "note": ("int4 must beat int8 (0.5 vs 1 byte/weight of "
                     "HBM traffic); parity of the two step floors "
                     "means the fused kernel declined — see the "
                     "kernel_declines of /debug/programs"),
        },
        "prefill_ms_batch32x128": round(pbest * 1000, 1),
        "prefill_mfu": round(mfu, 3),
        "dispatch_ms": round(disp_ms, 2),
        "warmup_ms": round(warm_ms or 0.0, 1),
        "step_phase_ms": step_phase_ms,
        "step_phase_coverage": round(phase_cov, 3),
        "decode_step_gap_ms": {"sync": round(gap_sync, 2),
                               "pipelined": round(gap_pipe, 2)},
        "achievable_gbps": round(bw_ach, 1),
        "decode_effective_gbps": round(eff_gbps, 1),
        "decode_ms_breakdown": {
            m: {"step": round(s, 2), "weights_sampling": round(w, 2),
                "attn_kv": round(a, 2),
                "dispatch": round(disp_ms / MULTISTEP, 2)}
            for m, (s, w, a) in {
                "bf16": (bf16_step, bf16_w, bf16_attn),
                "int8": (int8_step, int8_w, int8_attn),
                "int4": (int4_step, int4_w, int4_attn)}.items()},
    }))


if __name__ == "__main__":
    from ome_tpu import device
    device.enable_compile_cache()
    if not device.on_tpu() \
            and os.environ.get("OME_BENCH_COMP_CONFIG") != "tiny":
        # a CPU timing is never written under a device metric's name;
        # only the tiny composition/structured smoke runs off a chip
        sys.exit(f"bench.py: no TPU (found {device.identity()}); a "
                 f"measurement path that finds no chip fails")
    if len(sys.argv) > 1 and sys.argv[1] == "composition":
        composition_main()
    elif len(sys.argv) > 1 and sys.argv[1] == "structured":
        structured_main()
    else:
        main()
