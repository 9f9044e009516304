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
  * The Pallas kernel walks a slot's chain and nothing else. Grid
    `(B,)`: one grid step a slot. The pools stay in HBM
    (`memory_space=pl.ANY`); lengths, table and layer index ride as
    scalar prefetch. Inside a step a loop with a run-time trip count
    walks blocks `j = 0 .. ceil(len / bs) - 1`: the kernel itself
    copies pool block `(l, table[b, j])` into one of two VMEM
    buffers, block `j + 1` in flight while block `j` is in the dots
    (ops/flash.py's online-softmax decode body, its state in the
    loop's carry), and the last block of slot `b` starts block 0 of
    slot `b + 1`. A table cell that holds no block costs nothing.
  * What ends a walk: the length, held to the table's width, and the
    TRASH BLOCK. `engine/core.py: free_slot` writes `TRASH_BLOCK`
    into every entry of a freed slot's row while the slot's device
    length counts on, so a row that starts there is an empty chain:
    one grid step, no block read, a row of zeros out (finite: its
    logits still reach the sampler). That block is never allocated
    and never read: the one thing kernel and engine agree on beyond
    shapes, named once, below.
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
from .flash import M_INIT

# The pool's block no chain owns: every table entry past a chain's end
# and every entry of a freed slot's row points here (engine/core.py
# writes it, this file's kernel stops at it). The one thing kernel and
# engine agree on beyond shapes.
TRASH_BLOCK = 0


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


def _chain_blocks(len_ref, tbl_ref, b, bs: int):
    """Blocks the kernel walks for slot `b`: ceil(len / bs), held to
    the table's width, and none for a slot whose first entry is the
    trash block (a freed slot: its device length counts on, its row
    does not)."""
    n = jnp.minimum(lax.div(len_ref[b] + (bs - 1), bs),
                    tbl_ref.shape[1])
    return jnp.where(tbl_ref[b, 0] == TRASH_BLOCK, 0, n)


def _chain_kernel(len_ref, tbl_ref, lay_ref, q_ref, *refs, bs: int,
                  scale: float, softcap: Optional[float],
                  quantized: bool):
    """One grid step a slot: walk the slot's chain, block `j` in the
    dots while block `j + 1` is on its way from HBM. The online
    softmax is ops/flash.py's decode body (float32 m, l, acc; the
    scales of an int8 pool multiplied into the small logits / probs
    tiles), carried through the loop instead of scratch."""
    n_in = 4 if quantized else 2
    pools, o_ref = refs[:n_in], refs[n_in]
    bufs, sem, nxt_ref = (refs[n_in + 1:2 * n_in + 1],
                          refs[2 * n_in + 1], refs[2 * n_in + 2])
    b, B = pl.program_id(0), pl.num_programs(0)
    lay = lay_ref[0]
    hi = len_ref[b]
    n = _chain_blocks(len_ref, tbl_ref, b, bs)
    K, G, D = q_ref.shape[1:]

    def copies(slot_b, j, buf):
        blk = tbl_ref[slot_b, j]
        return [pltpu.make_async_copy(pool.at[lay, blk], vm.at[buf],
                                      sem.at[i, buf])
                for i, (pool, vm) in enumerate(zip(pools, bufs))]

    def start(slot_b, j, buf):
        for c in copies(slot_b, j, buf):
            c.start()

    # the last block of slot b is in the dots while block 0 of slot
    # b + 1 arrives in the other buffer (without this hand-over every
    # live slot waits 0.9 us for its first block). Two scalars go from
    # one grid step to the next: the buffer the next slot's block 0
    # lands in, and whether this slot started its copy
    @pl.when(b == 0)
    def _():
        nxt_ref[0] = 0
        nxt_ref[1] = 0
    base, handed = nxt_ref[0], nxt_ref[1] == 1
    nxt = jnp.minimum(b + 1, B - 1)
    hand_on = (n > 0) & (b + 1 < B) & (
        _chain_blocks(len_ref, tbl_ref, nxt, bs) > 0)
    nxt_ref[0] = lax.rem(base + n, 2)
    nxt_ref[1] = hand_on.astype(jnp.int32)

    @pl.when((n > 0) & jnp.logical_not(handed))
    def _():
        start(b, 0, base)

    def block(j, carry):
        m_prev, l_prev, acc = carry
        buf = lax.rem(base + j, 2)
        more = j + 1 < n

        @pl.when(more | hand_on)
        def _():
            start(jnp.where(more, b, nxt), jnp.where(more, j + 1, 0),
                  1 - buf)

        for c in copies(b, j, buf):
            c.wait()
        q = q_ref[0]                                        # [K, G, D]
        k = bufs[0][buf]                                    # [bs, K, D]
        v = bufs[1][buf]
        if quantized:
            k, v = k.astype(q.dtype), v.astype(q.dtype)     # raw int8
        # per-KV-head 2D dots (Mosaic's matmul wants batch dims
        # aligned; K is small and static, so unroll)
        logits = jnp.concatenate(
            [lax.dot_general(q[kh], k[:, kh, :],
                             (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
             for kh in range(K)], axis=0)                   # [K*G, bs]
        if quantized:
            logits = (logits.reshape(K, G, bs)
                      * bufs[2][buf][:, None, :]).reshape(K * G, bs)
        logits = logits * scale
        if softcap:
            logits = jnp.tanh(logits / softcap) * softcap
        col = j * bs + lax.broadcasted_iota(jnp.int32, (K * G, bs), 1)
        valid = col < hi
        logits = jnp.where(valid, logits, M_INIT)
        m_new = jnp.maximum(m_prev,
                            jnp.max(logits, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(valid, jnp.exp(logits - m_new), 0.0)
        l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        if quantized:
            p = (p.reshape(K, G, bs)
                 * bufs[3][buf][:, None, :]).reshape(K * G, bs)
        pb = p.astype(v.dtype)
        pv = jnp.concatenate(
            [lax.dot_general(pb[kh * G:(kh + 1) * G], v[:, kh, :],
                             (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
             for kh in range(K)], axis=0)                   # [K*G, D]
        return m_new, l_new, acc * alpha + pv

    H = K * G
    _, l, acc = lax.fori_loop(
        0, n, block, (jnp.full((H, 1), M_INIT, jnp.float32),
                      jnp.zeros((H, 1), jnp.float32),
                      jnp.zeros((H, D), jnp.float32)))
    # an empty chain leaves acc at zero: a finite row for the sampler
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)).reshape(
        K, G, D).astype(o_ref.dtype)


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

    The kernel is handed the whole pool [L, N, bs, K, D] where it
    lies in HBM and copies block `(layer, table[b, j])` of it into
    VMEM itself, for the blocks slot `b`'s chain holds and no others;
    lengths, table and the layer index (int32 scalar) ride as scalar
    prefetch. A slot whose row starts at the trash block reads
    nothing and returns a row of zeros.
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
    if Sq != 1 or H % K != 0 or H < 8 or D % 128 != 0 \
            or bs % 128 != 0:
        return None
    G = H // K
    scale = scale if scale is not None else D ** -0.5
    pools = [k_pool, v_pool]
    if k_scale is not None:
        pools += [k_scale, v_scale]

    def q_index(b, lens, tbl, lay):
        return (b, 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,                    # lengths, table, layer
        grid=(B,),
        in_specs=[pl.BlockSpec((1, K, G, D), q_index)]
        + [pl.BlockSpec(memory_space=pl.ANY)] * len(pools),
        out_specs=pl.BlockSpec((1, K, G, D), q_index),
        # two buffers a stream, one DMA semaphore a buffer; the two
        # scalars one grid step leaves the next
        scratch_shapes=[pltpu.VMEM((2,) + p.shape[2:], p.dtype)
                        for p in pools]
        + [pltpu.SemaphoreType.DMA((len(pools), 2)),
           pltpu.SMEM((2,), jnp.int32)],
    )
    out = pl.pallas_call(
        functools.partial(_chain_kernel, bs=bs, scale=scale,
                          softcap=logit_softcap,
                          quantized=k_scale is not None),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, K, G, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_attention",
    )(kv_len.astype(jnp.int32), table.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), q.reshape(B, K, G, D),
      *pools)
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
