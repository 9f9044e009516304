"""Paged (block) KV-cache attention for the serving engine's decode.

TPU-first analog of vLLM/SGLang PagedAttention (the engines the
reference deploys — SURVEY.md L0 — get this from CUDA kernels;
cite: reference runtime args in /root/reference/config/runtimes/srt/*).
Design:

  * KV lives in a POOL of fixed-size blocks `[L, N, bs, K, D]` (layer
    major) shared by all decode slots; each slot owns a chain of
    blocks listed in a per-slot BLOCK TABLE `[B, max_blocks]` (int32
    pool indices, the same chain in every layer). HBM is sized by
    TOTAL tokens in flight, not `slots x max_seq` — the round-4
    verdict's biggest structural gap vs the dense
    `[L, B, Smax, K, D]` allocation (engine/core.py round-4).
  * The pool stays where it is. Every entry here takes the WHOLE pool
    and a layer index: nothing slices a layer's `[N, bs, K, D]` out
    first, because a slice of the pool is a copy of 1/L of it, 2 x L
    times a step (llama.forward_paged carries the pool through its
    layer scan for the same reason).
  * All shapes are STATIC (pool size, table width), so one compiled
    decode program serves any mix of sequence lengths — the same
    property the dense engine has, without the worst-case allocation.
  * The Pallas kernel is the dense flash-decode kernel (ops/flash.py)
    with one change: the K/V BlockSpec index map reads the layer index
    and the block table (scalar prefetch) instead of a linear block
    index — sequence-space block `j` of layer `l` fetches pool block
    `(l, table[b, j])`. Past-the-end grid steps clamp to the last
    valid SEQUENCE block, whose repeated POOL index makes Pallas skip
    the DMA exactly as in the dense kernel.
  * The XLA path (CPU mesh / uncovered shapes) gathers each slot's
    blocks of layer `l` into a contiguous view (one gather indexed by
    `(l, table)`) and runs masked attention — the numerics-reference
    for the kernel and the byte-exactness tests.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import device
from . import note_decline
from .flash import M_INIT, _decode_block_range, _decode_kernel


def _gather_dequant(pool: jax.Array, scale_pool: Optional[jax.Array],
                    table: jax.Array, layer: jax.Array) -> jax.Array:
    """Gather each slot's block chain of layer `layer` into a
    contiguous f32 view: pool [L, N, bs, K, D] -> [B, M, bs, K, D] ->
    [B, M*bs, K, D], one gather indexed by (layer, table) that reads
    the chains' blocks and nothing else of the pool. int8 pools carry
    per-(row, head) scales [L, N, K, bs] (S-minor, the flash.py
    quantize_kv_block layout) gathered by the same index and
    multiplied back in — the XLA numerics reference for the quantized
    Pallas kernel."""
    B, M = table.shape
    bs = pool.shape[2]
    g = pool[layer, table].reshape(B, M * bs, pool.shape[3], -1)
    if scale_pool is None:
        return g.astype(jnp.float32)
    sg = scale_pool[layer, table]                 # [B, M, K, bs]
    sg = jnp.swapaxes(sg, 2, 3).reshape(B, M * bs, -1)  # [B, S, K]
    return g.astype(jnp.float32) * sg[..., None]


def paged_attention_xla(q: jax.Array, k_pool: jax.Array,
                        v_pool: jax.Array, table: jax.Array,
                        kv_len: jax.Array, layer: jax.Array,
                        scale: Optional[float] = None,
                        logit_softcap: Optional[float] = None,
                        k_scale: Optional[jax.Array] = None,
                        v_scale: Optional[jax.Array] = None,
                        ) -> jax.Array:
    """Reference paged decode attention (XLA gather + masked softmax).

    q: [B, 1, H, D]; pools: [L, N, bs, K, D]; table: [B, M] int32;
    kv_len: [B] valid rows per slot; layer: int32 scalar, the pool's
    layer to attend over. int8 pools pass their scale planes
    ([L, N, K, bs] f32) for dequantization. Returns [B, 1, H, D].
    """
    B, _, H, D = q.shape
    _, _, bs, K, _ = k_pool.shape
    M = table.shape[1]
    scale = scale if scale is not None else D ** -0.5
    # gather each slot's chain: [B, M, bs, K, D] -> [B, M*bs, K, D]
    kg = _gather_dequant(k_pool, k_scale, table, layer)
    vg = _gather_dequant(v_pool, v_scale, table, layer)
    G = H // K
    qh = q.reshape(B, K, G, D)
    logits = jnp.einsum("bkgd,bskd->bkgs", qh.astype(jnp.float32),
                        kg.astype(jnp.float32)) * scale
    if logit_softcap:
        logits = jnp.tanh(logits / logit_softcap) * logit_softcap
    col = jnp.arange(M * bs, dtype=jnp.int32)
    valid = col[None, :] < kv_len[:, None].astype(jnp.int32)  # [B, S]
    logits = jnp.where(valid[:, None, None, :], logits, M_INIT)
    p = jax.nn.softmax(logits, axis=-1)
    p = jnp.where(valid[:, None, None, :], p, 0.0)
    out = jnp.einsum("bkgs,bskd->bkgd", p, vg.astype(jnp.float32))
    return out.reshape(B, 1, H, D).astype(q.dtype)


def paged_attention_multi(q: jax.Array, k_pool: jax.Array,
                          v_pool: jax.Array, table: jax.Array,
                          q_positions: jax.Array, layer: jax.Array,
                          scale: Optional[float] = None,
                          logit_softcap: Optional[float] = None,
                          k_scale: Optional[jax.Array] = None,
                          v_scale: Optional[jax.Array] = None,
                          ) -> jax.Array:
    """Multi-query causal paged attention (speculative verify).

    Like paged_attention_xla but with Sq >= 1 queries per slot, each
    at its own sequence position: query s of slot b attends pool rows
    at sequence positions <= q_positions[b, s] (its own freshly
    written K/V row included — matching the dense decode convention
    kv_len = index + 1). XLA gather path only: the verify forward
    amortizes one weight pass over Sq tokens, so the gather cost is
    shared the same way; a Pallas multi-query kernel can slot in
    behind the same contract later.

    q: [B, Sq, H, D]; pools: [L, N, bs, K, D]; table: [B, M] int32;
    q_positions: [B, Sq] int32; layer: int32 scalar.
    Returns [B, Sq, H, D].
    """
    B, Sq, H, D = q.shape
    _, _, bs, K, _ = k_pool.shape
    M = table.shape[1]
    scale = scale if scale is not None else D ** -0.5
    kg = _gather_dequant(k_pool, k_scale, table, layer)
    vg = _gather_dequant(v_pool, v_scale, table, layer)
    G = H // K
    qh = q.reshape(B, Sq, K, G, D)
    logits = jnp.einsum("bqkgd,bskd->bkgqs", qh.astype(jnp.float32),
                        kg.astype(jnp.float32)) * scale
    if logit_softcap:
        logits = jnp.tanh(logits / logit_softcap) * logit_softcap
    col = jnp.arange(M * bs, dtype=jnp.int32)
    # per-query causal+length mask: rows past a slot's chain sit in
    # trash-block gathers at sequence positions > q_positions, so one
    # comparison covers both
    valid = col[None, None, :] <= q_positions[:, :, None]  # [B, Sq, S]
    logits = jnp.where(valid[:, None, None, :, :], logits, M_INIT)
    p = jax.nn.softmax(logits, axis=-1)
    p = jnp.where(valid[:, None, None, :, :], p, 0.0)
    out = jnp.einsum("bkgqs,bskd->bqkgd", p, vg.astype(jnp.float32))
    return out.reshape(B, Sq, H, D).astype(q.dtype)


def _paged_kernel(lim_ref, tbl_ref, lay_ref, q_ref, k_ref, v_ref,
                  *refs, bs: int, scale: float,
                  softcap: Optional[float], quantized: bool = False):
    # identical math to the dense decode kernel: `start` stays in
    # SEQUENCE space (col masking against [lo, hi)); only the DMA
    # source — chosen by the BlockSpec index maps from lay_ref and
    # tbl_ref — is pool-indexed, which the body never sees. Quantized
    # pools add two scale refs the dense kernel already knows how to
    # fold in.
    del tbl_ref, lay_ref
    _decode_kernel(lim_ref, q_ref, k_ref, v_ref, *refs, bs=bs,
                   scale=scale, softcap=softcap, quantized=quantized)


def paged_flash_decode(q: jax.Array, k_pool: jax.Array,
                       v_pool: jax.Array, table: jax.Array,
                       kv_len: jax.Array, layer: jax.Array,
                       scale: Optional[float] = None,
                       logit_softcap: Optional[float] = None,
                       k_scale: Optional[jax.Array] = None,
                       v_scale: Optional[jax.Array] = None,
                       interpret: bool = False
                       ) -> Optional[jax.Array]:
    """Pallas paged decode attention; None when shapes are uncovered
    (caller falls back to paged_attention_xla).

    The kernel is handed the whole pool [L, N, bs, K, D] and reads
    layer `layer` (int32 scalar) of it in place: the index rides as
    scalar prefetch beside the table and the K/V index maps put it in
    front of the table's block, so nothing of the pool is sliced or
    copied first.
    Pool block size doubles as the kernel block: bs must be a multiple
    of 128 lanes-worth of rows for efficient DMA — the engine default
    (128) satisfies this. int8 pools (k_scale/v_scale [L, N, K, bs]
    f32) stream 1 byte/element plus a tiny scale plane; the kernel
    converts raw int8 to the compute dtype for the MXU dots and
    multiplies the scales into the small [K*G, bs] logits/probs tiles
    (ops/flash.py quantized decode discipline).
    """
    B, Sq, H, D = q.shape
    _, _, bs, K, _ = k_pool.shape
    M = table.shape[1]
    if Sq != 1 or H % K != 0 or H < 8 or D % 128 != 0 \
            or bs % 128 != 0:
        return None
    quantized = k_scale is not None
    G = H // K
    scale = scale if scale is not None else D ** -0.5
    hi = kv_len.astype(jnp.int32)
    lo = jnp.zeros_like(hi)
    limits = jnp.stack([lo, hi], axis=1)          # [B, 2]
    qh = q.reshape(B, K, G, D)

    def kv_index(b, s, lim, tbl, lay):
        first, last = _decode_block_range(lim[b, 0], lim[b, 1], bs)
        j = jnp.minimum(first + s, last)          # sequence block
        return (lay[0], tbl[b, j], 0, 0, 0)       # layer, pool block

    def sc_index(b, s, lim, tbl, lay):
        return kv_index(b, s, lim, tbl, lay)[:4]

    def q_index(b, s, lim, tbl, lay):
        return (b, 0, 0, 0)

    # the leading None squeezes the layer dim: the body sees
    # (1, bs, K, D) blocks, as the dense kernel's body does
    in_specs = [
        pl.BlockSpec((1, K, G, D), q_index),
        pl.BlockSpec((None, 1, bs, K, D), kv_index),
        pl.BlockSpec((None, 1, bs, K, D), kv_index),
    ]
    args = [limits, table.astype(jnp.int32),
            jnp.asarray(layer, jnp.int32).reshape(1), qh, k_pool,
            v_pool]
    if quantized:
        in_specs += [pl.BlockSpec((None, 1, K, bs), sc_index),
                     pl.BlockSpec((None, 1, K, bs), sc_index)]
        args += [k_scale, v_scale]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,                    # limits, table, layer
        grid=(B, M),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, K, G, D), q_index),
        scratch_shapes=[
            pltpu.VMEM((H, 128), jnp.float32),
            pltpu.VMEM((H, 128), jnp.float32),
            pltpu.VMEM((H, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_kernel, bs=bs, scale=scale,
                          softcap=logit_softcap, quantized=quantized),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, K, G, D), q.dtype),
        interpret=interpret,
        name="paged_attention",
    )(*args)
    return out.reshape(B, 1, H, D)


def paged_attention(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                    table: jax.Array, kv_len: jax.Array,
                    layer: jax.Array,
                    scale: Optional[float] = None,
                    logit_softcap: Optional[float] = None,
                    k_scale: Optional[jax.Array] = None,
                    v_scale: Optional[jax.Array] = None,
                    backend: Optional[str] = None) -> jax.Array:
    """Dispatching entry: Pallas on TPU, XLA elsewhere (same contract
    as ops/attention.attention) over layer `layer` of the whole pool
    [L, N, bs, K, D]. int8 pools pass k_scale/v_scale.
    Interpret mode runs only when asked for by name
    ("pallas_interpret")."""
    import os
    if backend is None:
        backend = os.environ.get("OME_ATTN_BACKEND")
    if backend is None:
        backend = "pallas" if device.on_tpu() else "xla"
    if backend in ("pallas", "pallas_interpret"):
        out = paged_flash_decode(
            q, k_pool, v_pool, table, kv_len, layer, scale,
            logit_softcap, k_scale=k_scale, v_scale=v_scale,
            interpret=(backend == "pallas_interpret"))
        if out is not None:
            return out
        note_decline("paged_flash_decode",
                     f"q{tuple(q.shape)} pool{tuple(k_pool.shape)} "
                     f"outside the kernel's coverage")
    return paged_attention_xla(q, k_pool, v_pool, table, kv_len,
                               layer, scale, logit_softcap,
                               k_scale=k_scale, v_scale=v_scale)
