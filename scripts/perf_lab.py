#!/usr/bin/env python
"""Decode-step ablation lab (round 4).

Measures ms/decode-step for structural variants of the flagship decode
loop on the real chip, to attribute the per-step time budget:

  base       current bench.py structure (lax.scan layers, cache as
             stacked scan output -> full-cache write every step)
  dispatch   empty jitted call round-trip (host dispatch floor)
  noattn     all weight matmuls, NO cache read/write/attention
             (weight-streaming floor)
  nocache    forward but the new cache is not an output (XLA can DCE
             the stacked-ys write; attention still reads the cache)
  inplace    unrolled layers, per-layer cache arrays donated ->
             true in-place dynamic-update-slice, no full-cache write
  multistep  inplace + lax.scan over K tokens inside one dispatch

Run: python scripts/perf_lab.py base inplace ... [--quant int8|int4]
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from ome_tpu.models import config as cfgs
from ome_tpu.models import llama
from ome_tpu.models.quant import quantize_params

BATCH, PREFILL, STEPS = 32, 128, 127
CACHE_LEN = 256


def sync(x):
    jax.block_until_ready(x)
    return np.asarray(jax.device_get(x))


def make_cfg():
    return cfgs.ModelConfig(
        vocab_size=32768, hidden_size=2048, num_layers=24, num_heads=16,
        num_kv_heads=8, head_dim=128, intermediate_size=8192,
        rope_theta=500000.0, max_seq_len=CACHE_LEN)


def time_loop(step_fn, state, steps=STEPS, trials=3, fresh=False):
    """state -> state; returns best ms/step. `fresh=True` deep-copies
    the initial state per trial — required for donate variants, whose
    warmup call deletes the original buffers."""
    def start():
        return jax.tree.map(jnp.copy, state) if fresh else state

    st = step_fn(start())   # compile + warm
    sync(jax.tree.leaves(st)[0])
    best = float("inf")
    for _ in range(trials):
        st = start()
        t0 = time.perf_counter()
        for _ in range(steps):
            st = step_fn(st)
        sync(jax.tree.leaves(st)[0])
        best = min(best, time.perf_counter() - t0)
    return best / steps * 1000


def report(name, ms):
    tps = BATCH / (ms / 1000)
    print(f"lab: {name:16s} {ms:7.2f} ms/step   {tps:8.1f} tok/s",
          flush=True)


def prep(cfg, quant):
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    if quant:
        params = quantize_params(params, mode=quant)
    prompt = jax.random.randint(jax.random.PRNGKey(1), (BATCH, PREFILL),
                                0, cfg.vocab_size, dtype=jnp.int32)

    @jax.jit
    def prefill(params, tokens, cache):
        logits, cache = llama.forward(params, cfg, tokens, cache=cache)
        return (jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32),
                cache)

    tok, cache = prefill(params, prompt,
                         llama.KVCache.create(cfg, BATCH, CACHE_LEN))
    sync(tok)
    return params, tok, cache


# -- variants ---------------------------------------------------------------


def run_base(cfg, quant):
    params, tok, cache = prep(cfg, quant)

    @jax.jit
    def decode(params, tok, cache):
        logits, cache = llama.forward(params, cfg, tok, cache=cache)
        return jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32), cache

    def step(st):
        tok, cache = st
        return decode(params, tok, cache)

    report(f"base/{quant or 'bf16'}", time_loop(step, (tok, cache)))


def run_dispatch(cfg, quant):
    tok = jnp.zeros((BATCH, 1), jnp.int32)
    f = jax.jit(lambda t: t + 1)
    report("dispatch", time_loop(lambda t: f(t), tok))


def run_noattn(cfg, quant):
    params, tok, cache = prep(cfg, quant)
    from ome_tpu.models.llama import (_proj, _w, dense_mlp, rms_norm)

    @jax.jit
    def decode(params, tok):
        emb = params["embed"]
        from ome_tpu.models.quant import QTensor
        x = emb.take(tok, cfg.dtype) if isinstance(emb, QTensor) \
            else jnp.take(emb, tok, axis=0).astype(cfg.dtype)

        def body(x, lp):
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
            # attention skipped: feed q straight to the output proj so
            # every weight still streams but no KV traffic happens
            a = _proj(q + 0 * (k.sum() + v.sum()), lp["wo"], cfg.dtype,
                      flatten=2)
            x = x + a
            h = rms_norm(x, lp["mlp_norm"], cfg.rms_norm_eps)
            return x + dense_mlp(h, lp, cfg), None

        from jax import lax
        x, _ = lax.scan(body, x, params["layers"])
        x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
        head = params.get("lm_head")
        from ome_tpu.models.quant import QTensor as QT
        head = head.dequant(cfg.dtype) if isinstance(head, QT) else head
        logits = jnp.einsum("bsd,dv->bsv", x, head,
                            preferred_element_type=jnp.float32)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)

    report(f"noattn/{quant or 'bf16'}",
           time_loop(lambda t: decode(params, t), tok))


def run_nocache(cfg, quant):
    params, tok, cache = prep(cfg, quant)

    @jax.jit
    def decode(params, tok, cache):
        logits, _ = llama.forward(params, cfg, tok, cache=cache)
        return jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)

    # cache never advances: every step attends at the same index; the
    # timing is what matters, not the tokens
    report(f"nocache/{quant or 'bf16'}",
           time_loop(lambda t: decode(params, t, cache), tok))


def _split_layers(params, n_layers):
    per = [jax.tree.map(lambda a: a[l], params["layers"])
           for l in range(n_layers)]
    top = {k: v for k, v in params.items() if k != "layers"}
    return per, top


def _unrolled_step(cfg, per_layers, top, tok, ks, vs, index):
    from ome_tpu.models.llama import (_layer, _rope_frequencies, rms_norm)
    from ome_tpu.models.quant import QTensor
    B = tok.shape[0]
    emb = top["embed"]
    x = emb.take(tok, cfg.dtype) if isinstance(emb, QTensor) \
        else jnp.take(emb, tok, axis=0).astype(cfg.dtype)
    freqs = _rope_frequencies(cfg)
    positions = jnp.broadcast_to(index[None, None], (B, 1))
    kv_len = jnp.broadcast_to(index + 1, (B,))
    new_ks, new_vs = [], []
    for l in range(cfg.num_layers):
        x, nc = _layer(x, per_layers[l], cfg, freqs, positions, kv_len,
                       (ks[l], vs[l]), index)
        new_ks.append(nc[0])
        new_vs.append(nc[1])
    x = rms_norm(x, top["final_norm"], cfg.rms_norm_eps)
    head = top.get("lm_head")
    head = head.dequant(cfg.dtype) if isinstance(head, QTensor) else head
    logits = jnp.einsum("bsd,dv->bsv", x, head,
                        preferred_element_type=jnp.float32)
    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return tok, new_ks, new_vs, index + 1


def run_inplace(cfg, quant, donate=True):
    params, tok, cache = prep(cfg, quant)
    per, top = _split_layers(params, cfg.num_layers)
    ks = [cache.k[l] for l in range(cfg.num_layers)]
    vs = [cache.v[l] for l in range(cfg.num_layers)]
    index = cache.index

    # per/top ride as jit ARGUMENTS — closing over them would bake
    # 3.3GB of weights into the HLO as constants
    def fn(per, top, tok, ks, vs, index):
        return _unrolled_step(cfg, per, top, tok, ks, vs, index)

    decode = jax.jit(fn, donate_argnums=(3, 4) if donate else ())

    def step(st):
        tok, ks, vs, index = st
        tok, ks, vs, index = decode(per, top, tok, ks, vs, index)
        return tok, ks, vs, index

    tag = "inplace" if donate else "unrolled-nodon"
    report(f"{tag}/{quant or 'bf16'}",
           time_loop(step, (tok, ks, vs, index), fresh=donate))


def run_multistep(cfg, quant, k_steps=8, donate=False):
    from jax import lax
    params, tok, cache = prep(cfg, quant)
    per, top = _split_layers(params, cfg.num_layers)
    ks = [cache.k[l] for l in range(cfg.num_layers)]
    vs = [cache.v[l] for l in range(cfg.num_layers)]
    index = cache.index

    def one(per, top, carry, _):
        tok, ks, vs, index = carry
        tok, ks, vs, index = _unrolled_step(cfg, per, top, tok, ks, vs,
                                            index)
        return (tok, ks, vs, index), tok

    import functools

    @functools.partial(jax.jit,
                       donate_argnums=(3, 4) if donate else ())
    def decode_k(per, top, tok, ks, vs, index):
        (tok, ks, vs, index), toks = lax.scan(
            functools.partial(one, per, top), (tok, ks, vs, index),
            None, length=k_steps)
        return tok, ks, vs, index

    def step(st):
        tok, ks, vs, index = st
        tok, ks, vs, index = decode_k(per, top, tok, ks, vs, index)
        return tok, ks, vs, index

    ms = time_loop(step, (tok, ks, vs, index), steps=STEPS // k_steps,
                   fresh=donate)
    report(f"multistep{k_steps}/{quant or 'bf16'}", ms / k_steps)


def _unrolled_stacked_step(cfg, per, top, tok, k, v, index):
    """Unrolled layers over STACKED [L, ...] cache arrays (two donated
    buffers instead of 2L): per-layer dynamic slices in, dynamic
    update slices out."""
    from jax import lax

    from ome_tpu.models.llama import (_layer, _rope_frequencies,
                                      rms_norm)
    from ome_tpu.models.quant import QTensor
    B = tok.shape[0]
    emb = top["embed"]
    x = emb.take(tok, cfg.dtype) if isinstance(emb, QTensor) \
        else jnp.take(emb, tok, axis=0).astype(cfg.dtype)
    freqs = _rope_frequencies(cfg)
    positions = jnp.broadcast_to(index[None, None], (B, 1))
    kv_len = jnp.broadcast_to(index + 1, (B,))
    for l in range(cfg.num_layers):
        x, nc = _layer(x, per[l], cfg, freqs, positions, kv_len,
                       (k[l], v[l]), index)
        k = lax.dynamic_update_index_in_dim(k, nc[0], l, axis=0)
        v = lax.dynamic_update_index_in_dim(v, nc[1], l, axis=0)
    x = rms_norm(x, top["final_norm"], cfg.rms_norm_eps)
    head = top.get("lm_head")
    head = head.dequant(cfg.dtype) if isinstance(head, QTensor) else head
    logits = jnp.einsum("bsd,dv->bsv", x, head,
                        preferred_element_type=jnp.float32)
    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return tok, k, v, index + 1


def run_stacked(cfg, quant, donate=True):
    params, tok, cache = prep(cfg, quant)
    per, top = _split_layers(params, cfg.num_layers)
    k, v, index = cache.k, cache.v, cache.index

    def fn(per, top, tok, k, v, index):
        return _unrolled_stacked_step(cfg, per, top, tok, k, v, index)

    decode = jax.jit(fn, donate_argnums=(3, 4) if donate else ())

    def step(st):
        tok, k, v, index = st
        return decode(per, top, tok, k, v, index)

    tag = "stacked" if donate else "stacked-nodon"
    report(f"{tag}/{quant or 'bf16'}",
           time_loop(step, (tok, k, v, index), fresh=donate))


def _unrolled_q8kv_step(cfg, per, top, tok, kq, vq, ksc, vsc, index):
    """Unrolled decode step over an INT8 KV cache (per-layer plane
    lists + per-token-head scales), attention via the quantized flash
    decode kernel."""
    from ome_tpu.models.llama import (_proj, _rope_frequencies,
                                      apply_rope, dense_mlp, rms_norm)
    from ome_tpu.models.quant import QTensor
    from ome_tpu.ops.flash import (flash_decode_quantized,
                                   quantize_kv_block)
    B = tok.shape[0]
    emb = top["embed"]
    x = emb.take(tok, cfg.dtype) if isinstance(emb, QTensor) \
        else jnp.take(emb, tok, axis=0).astype(cfg.dtype)
    freqs = _rope_frequencies(cfg)
    positions = jnp.broadcast_to(index[None, None], (B, 1))
    kv_len = jnp.broadcast_to(index + 1, (B,))
    from jax import lax
    nkq, nvq, nks, nvs = [], [], [], []
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
        kq8, ks8 = quantize_kv_block(k)   # [B,1,K,D], [B,K,1]
        vq8, vs8 = quantize_kv_block(v)
        upd = jax.vmap(lambda c, u, i: lax.dynamic_update_slice(
            c, u, (i, 0, 0)))
        upd_s = jax.vmap(lambda c, u, i: lax.dynamic_update_slice(
            c, u, (0, i)))                # scale planes are [K, S]
        idx = index * jnp.ones((B,), jnp.int32)
        ck = upd(kq[l], kq8, idx)
        cv = upd(vq[l], vq8, idx)
        cks = upd_s(ksc[l], ks8, idx)
        cvs = upd_s(vsc[l], vs8, idx)
        attn = flash_decode_quantized(q, ck, cv, cks, cvs,
                                      positions=positions,
                                      kv_len=kv_len,
                                      scale=cfg.query_scale)
        a = _proj(attn, lp["wo"], cfg.dtype, flatten=2)
        x = x + a
        h = rms_norm(x, lp["mlp_norm"], cfg.rms_norm_eps)
        x = x + dense_mlp(h, lp, cfg)
        nkq.append(ck)
        nvq.append(cv)
        nks.append(cks)
        nvs.append(cvs)
    x = rms_norm(x, top["final_norm"], cfg.rms_norm_eps)
    head = top.get("lm_head")
    head = head.dequant(cfg.dtype) if isinstance(head, QTensor) else head
    logits = jnp.einsum("bsd,dv->bsv", x, head,
                        preferred_element_type=jnp.float32)
    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return tok, nkq, nvq, nks, nvs, index + 1


def run_multistep_q8kv(cfg, quant, k_steps=8):
    from jax import lax
    params, tok, cache = prep(cfg, quant)
    per, top = _split_layers(params, cfg.num_layers)
    from ome_tpu.ops.flash import quantize_kv_block
    kq, vq, ksc, vsc = [], [], [], []
    for l in range(cfg.num_layers):
        q8, s8 = quantize_kv_block(cache.k[l])
        kq.append(q8)
        ksc.append(s8)
        q8, s8 = quantize_kv_block(cache.v[l])
        vq.append(q8)
        vsc.append(s8)
    index = cache.index

    def one(per, top, carry, _):
        tok, kq, vq, ksc, vsc, index = carry
        out = _unrolled_q8kv_step(cfg, per, top, tok, kq, vq, ksc, vsc,
                                  index)
        return out, out[0]

    import functools

    @jax.jit
    def decode_k(per, top, tok, kq, vq, ksc, vsc, index):
        carry, _ = lax.scan(functools.partial(one, per, top),
                            (tok, kq, vq, ksc, vsc, index), None,
                            length=k_steps)
        return carry

    def step(st):
        return decode_k(per, top, *st)

    ms = time_loop(step, (tok, kq, vq, ksc, vsc, index),
                   steps=STEPS // k_steps)
    report(f"q8kv-multistep{k_steps}/{quant or 'bf16'}", ms / k_steps)


def run_attnbench(cfg, quant):
    """Isolate decode attention: 24 chained flash-decode calls (one
    per layer) per step, bf16 cache vs int8 cache."""
    from ome_tpu.ops.flash import (flash_attention,
                                   flash_decode_quantized,
                                   quantize_kv_block)
    B, S, K, H, D = BATCH, CACHE_LEN, cfg.num_kv_heads, cfg.num_heads, \
        cfg.head_dim
    L = cfg.num_layers
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (B, 1, H, D), jnp.bfloat16)
    k = jax.random.normal(key, (B, S, K, D), jnp.bfloat16)
    v = jax.random.normal(key, (B, S, K, D), jnp.bfloat16)
    lengths = jnp.full((B,), S, jnp.int32)
    positions = (lengths - 1)[:, None]
    kq, ks = quantize_kv_block(k)
    vq, vs = quantize_kv_block(v)

    @jax.jit
    def plain(q, k, v):
        out = q
        for _ in range(L):
            out = flash_attention(out.reshape(B, 1, H, D), k, v,
                                  positions=positions, kv_len=lengths)
        return out

    @jax.jit
    def quant(q, kq, vq, ks, vs):
        out = q
        for _ in range(L):
            out = flash_decode_quantized(out.reshape(B, 1, H, D), kq,
                                         vq, ks, vs,
                                         positions=positions,
                                         kv_len=lengths)
        return out

    report("attn-bf16", time_loop(lambda t: plain(t, k, v), q,
                                  steps=32))
    report("attn-int8kv", time_loop(lambda t: quant(t, kq, vq, ks, vs),
                                    q, steps=32))


def run_prefill_bench(cfg, quant):
    """Prefill throughput + MFU: Pallas flash vs XLA attention (the
    trace reads OME_ATTN_BACKEND, so each backend gets a fresh jit)."""
    import os
    params, _, _ = prep(cfg, quant)
    prompt = jax.random.randint(jax.random.PRNGKey(2),
                                (BATCH, PREFILL), 0, cfg.vocab_size,
                                dtype=jnp.int32)
    n_params = llama.param_count(params)
    T = BATCH * PREFILL
    # matmul flops + causal attention flops
    flops = 2 * n_params * T + 2 * cfg.num_layers * BATCH * (
        PREFILL ** 2) * cfg.num_heads * cfg.head_dim
    for backend in ("pallas", "xla"):
        os.environ["OME_ATTN_BACKEND"] = backend

        def fwd(params, tokens):
            cache = llama.KVCache.create(cfg, BATCH, CACHE_LEN)
            logits, c = llama.forward(params, cfg, tokens, cache=cache)
            return jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)

        f = jax.jit(fwd)
        sync(f(params, prompt))  # compile
        best = float("inf")
        for _ in range(5):
            t0 = time.perf_counter()
            sync(f(params, prompt))  # ONE synced prefill per timing
            best = min(best, time.perf_counter() - t0)
        ms = best * 1000
        tps = T / (ms / 1000)
        mfu = flops / (ms / 1000) / 197e12
        print(f"lab: prefill/{backend:7s} {ms:7.2f} ms   "
              f"{tps:8.0f} tok/s   MFU {100*mfu:.1f}%", flush=True)
    os.environ.pop("OME_ATTN_BACKEND", None)


VARIANTS = {
    "base": run_base,
    "dispatch": run_dispatch,
    "noattn": run_noattn,
    "nocache": run_nocache,
    "inplace": run_inplace,
    "nodonate": lambda cfg, q: run_inplace(cfg, q, donate=False),
    "stacked": run_stacked,
    "stacked-nodon": lambda cfg, q: run_stacked(cfg, q, donate=False),
    "multistep": run_multistep,
    "multistep4": lambda cfg, q: run_multistep(cfg, q, k_steps=4),
    "multistep16": lambda cfg, q: run_multistep(cfg, q, k_steps=16),
    "multistep-don": lambda cfg, q: run_multistep(cfg, q, donate=True),
    "q8kv": run_multistep_q8kv,
    "attnbench": run_attnbench,
    "prefill": run_prefill_bench,
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("variants", nargs="+", choices=sorted(VARIANTS))
    ap.add_argument("--quant", choices=["int8", "int4"], default=None)
    args = ap.parse_args()
    cfg = make_cfg()
    print(f"lab: devices={jax.devices()} quant={args.quant}", flush=True)
    for v in args.variants:
        t0 = time.perf_counter()
        VARIANTS[v](cfg, args.quant)
        print(f"lab: [{v}] total {time.perf_counter()-t0:.0f}s",
              flush=True)


if __name__ == "__main__":
    main()
