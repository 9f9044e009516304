"""Pallas TPU flash-attention kernels (prefill + decode).

TPU-first replacement for the attention math the reference delegates to
SGLang/vLLM CUDA kernels (SURVEY.md L0): here attention is an in-repo
Pallas kernel pair designed around the TPU memory system:

  * **decode** (`Sq == 1`): grid (B, kv_blocks); the per-sequence
    [lo, hi) valid-row window rides scalar prefetch so the K/V
    BlockSpec index maps *clamp* past-the-end block indices — Pallas
    skips the DMA when the block index repeats, so a sequence at
    length 300 in a 2048-slot cache streams ~300 rows of KV through
    VMEM, not 2048 (decode is HBM-bandwidth-bound; this is the win).
  * **prefill**: grid (B, K, q_blocks, kv_blocks) with the same
    clamping on the causal frontier, so upper-triangle KV blocks are
    neither fetched nor computed. GQA is handled by folding the G
    query heads of each KV head into the row dimension of one MXU
    matmul — no K/V duplication in VMEM.

Both kernels keep fp32 online-softmax state (m, l, acc) in VMEM
scratch across the innermost grid dimension and never materialize a
mask: causality, per-sequence KV length, and sliding windows are iota
comparisons against scalar limits. Supports GQA (H % K == 0), logit
softcap (Gemma-2), and chunked prefill (nonzero per-batch position
base writing into a pre-filled cache).

Returns None for shapes the kernels don't cover (tiny heads, ragged
sizes) — callers fall back to the XLA path (ops/attention.py), which
is also the CPU-mesh path; `interpret=True` runs the same kernels on
CPU for the numerics tests.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

M_INIT = -1.0e30  # finite lowest running max: exp(x - M_INIT) underflows to 0


def _pick_block(n: int, candidates) -> Optional[int]:
    for c in candidates:
        if n % c == 0:
            return c
    return None


# -- decode kernel ---------------------------------------------------------


def _decode_block_range(lo, hi, bs):
    """[first, last] block indices holding rows of [lo, hi) — the SAME
    mapping the BlockSpec index maps use, so the kernel can recover the
    absolute start of the block it was actually given."""
    first = jnp.maximum(lax.div(lo, bs), 0)
    last = jnp.maximum(lax.div(hi - 1, bs), first)
    return first, last


def _decode_kernel(lim_ref, q_ref, k_ref, v_ref, *refs, bs: int,
                   scale: float, softcap: Optional[float],
                   quantized: bool = False):
    if quantized:
        # int8 KV cache: per-(row, head) f32 scales ([K, bs] blocks —
        # S minor keeps the plane lane-aligned) ride as two extra
        # inputs. K/V convert to bf16 UNSCALED for the MXU dots; the
        # scales multiply the small [K*G, bs] logits/probs tiles
        # instead of the [bs, K, D] value blocks (128x fewer
        # multiplies), so HBM streams 1 byte/element + a tiny plane
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = refs
    else:
        ks_ref = vs_ref = None
        o_ref, m_ref, l_ref, acc_ref = refs
    s = pl.program_id(1)
    ns = pl.num_programs(1)

    @pl.when(s == 0)
    def _():
        m_ref[:] = jnp.full_like(m_ref, M_INIT)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    lo = lim_ref[pl.program_id(0), 0]
    hi = lim_ref[pl.program_id(0), 1]
    first, last = _decode_block_range(lo, hi, bs)
    start = jnp.minimum(first + s, last) * bs  # matches kv_index below

    # `first + s <= last` keeps the clamped (repeated, DMA-skipped)
    # grid steps beyond the range from double-counting the last block
    @pl.when((first + s <= last) & (start < hi) & (start + bs > lo))
    def _():
        q = q_ref[0]            # [K, G, D]
        k = k_ref[0]            # [bs, K, D]
        if quantized:
            k = k.astype(q.dtype)   # raw int8 values; scale on logits
        K, G, D = q.shape
        # per-KV-head 2D dots (Mosaic's matmul wants batch dims aligned;
        # K is small and static, so unroll): [G,D] x [bs,D]^T -> [G,bs]
        logits = jnp.concatenate(
            [lax.dot_general(q[kh], k[:, kh, :], (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
             for kh in range(K)], axis=0)                   # [K*G, bs]
        if quantized:
            sk = ks_ref[0]                                  # [K, bs]
            logits = (logits.reshape(K, G, bs)
                      * sk[:, None, :]).reshape(K * G, bs)
        logits = logits * scale
        if softcap:
            logits = jnp.tanh(logits / softcap) * softcap
        col = start + lax.broadcasted_iota(jnp.int32, (K * G, bs), 1)
        valid = (col >= lo) & (col < hi)
        logits = jnp.where(valid, logits, M_INIT)

        m_prev = m_ref[:, :1]                                   # [KG, 1]
        m_new = jnp.maximum(m_prev, jnp.max(logits, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(logits - m_new)
        p = jnp.where(valid, p, 0.0)
        l_new = alpha * l_ref[:, :1] + jnp.sum(p, axis=1, keepdims=True)
        v_blk = v_ref[0]                                    # [bs, K, D]
        if quantized:
            v_blk = v_blk.astype(q.dtype)  # raw; fold scales into p
            sv = vs_ref[0]                                  # [K, bs]
            p = (p.reshape(K, G, bs) * sv[:, None, :]).reshape(
                K * G, bs)
        pb = p.astype(v_blk.dtype)
        pv = jnp.concatenate(
            [lax.dot_general(pb[kh * G:(kh + 1) * G], v_blk[:, kh, :],
                             (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
             for kh in range(K)], axis=0)                   # [K*G, D]
        acc_ref[:] = acc_ref[:] * alpha + pv
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(s == ns - 1)
    def _():
        K, G, D = o_ref.shape[1:]
        l = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0] = (acc_ref[:] / l).reshape(K, G, D).astype(o_ref.dtype)


def _flash_decode(q, k, v, lo, hi, scale, softcap, interpret,
                  k_scale=None, v_scale=None):
    B, _, H, D = q.shape
    S, K = k.shape[1], k.shape[2]
    G = H // K
    bs = _pick_block(S, (512, 256, 128))
    if bs is None or H < 8 or D % 128 != 0:
        return None
    ns = S // bs
    quantized = k_scale is not None
    limits = jnp.stack(
        [lo.astype(jnp.int32), hi.astype(jnp.int32)], axis=1)  # [B, 2]
    qh = q.reshape(B, K, G, D)

    # walk blocks starting at the sliding-window's first valid block and
    # clamp at the last block holding a valid row: repeated indices make
    # Pallas skip the DMA for both the pre-window head (long-context
    # sliding window) and the cache tail (short sequences).
    def kv_index(b, s, lim):
        first, last = _decode_block_range(lim[b, 0], lim[b, 1], bs)
        return (b, jnp.minimum(first + s, last), 0, 0)

    def sc_index(b, s, lim):
        first, last = _decode_block_range(lim[b, 0], lim[b, 1], bs)
        return (b, 0, jnp.minimum(first + s, last))

    in_specs = [
        pl.BlockSpec((1, K, G, D), lambda b, s, lim: (b, 0, 0, 0)),
        pl.BlockSpec((1, bs, K, D), kv_index),
        pl.BlockSpec((1, bs, K, D), kv_index),
    ]
    args = [limits, qh, k, v]
    if quantized:
        # scales are [B, K, S] — S minor so each [K, bs] block is
        # lane-aligned (K=8 minor would DMA 8-lane vectors)
        in_specs += [pl.BlockSpec((1, K, bs), sc_index),
                     pl.BlockSpec((1, K, bs), sc_index)]
        args += [k_scale, v_scale]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, ns),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, K, G, D), lambda b, s, lim: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((H, 128), jnp.float32),
            pltpu.VMEM((H, 128), jnp.float32),
            pltpu.VMEM((H, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_decode_kernel, bs=bs, scale=scale,
                          softcap=softcap, quantized=quantized),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, K, G, D), q.dtype),
        interpret=interpret,
        name="flash_decode",
    )(*args)
    return out.reshape(B, 1, H, D)


def quantize_kv_block(x: jax.Array):
    """Per-(row, head) symmetric int8 for a KV slab [B, S, K, D] ->
    (int8 values [B, S, K, D], f32 scales [B, K, S]). One scale per
    token-head tracks each token's dynamic range (activation stats
    vary token to token far more than channel to channel); scales are
    stored S-minor so the decode kernel's [K, bs] scale blocks are
    lane-aligned."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)  # [B,S,K]
    s = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / s[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, jnp.swapaxes(s, -1, -2)


def flash_decode_quantized(q: jax.Array, kq: jax.Array, vq: jax.Array,
                           k_scale: jax.Array, v_scale: jax.Array,
                           positions: jax.Array,
                           kv_len: Optional[jax.Array] = None,
                           sliding_window: Optional[int] = None,
                           scale: Optional[float] = None,
                           logit_softcap: Optional[float] = None,
                           interpret: bool = False):
    """Decode attention over an int8 KV cache (quantize_kv_block
    layout). q: [B, 1, H, D] bf16; kq/vq: [B, S, K, D] int8; scales
    [B, K, S] f32. Returns [B, 1, H, D] or None if shapes uncovered.

    Experimental building block, NOT wired into the engine: the KV
    read is the second-largest term in the decode step's HBM budget
    after the weights and int8 halves it; whether the in-kernel
    int8->bf16 convert costs more than the halved read saves is not
    measured on the chip. It ships numerics-tested (tests/test_ops.py)
    but unreachable from serving (the paged pool's --kv-dtype int8 is
    the served int8 KV path).
    """
    B, Sq, H, D = q.shape
    assert Sq == 1
    scale = scale if scale is not None else D ** -0.5
    pos = positions[:, 0]
    if kv_len is None:
        kv_hi = jnp.full((B,), kq.shape[1], jnp.int32)
    else:
        kv_hi = jnp.broadcast_to(kv_len, (B,)).astype(jnp.int32)
    hi = jnp.minimum(pos + 1, kv_hi)
    lo = jnp.maximum(pos - sliding_window + 1, 0) if sliding_window \
        else jnp.zeros_like(pos)
    return _flash_decode(q, kq, vq, lo, hi, scale, logit_softcap,
                         interpret, k_scale=k_scale, v_scale=v_scale)


# -- prefill kernel --------------------------------------------------------


def _prefill_block_range(base, kv_hi, qi, bq, bs, window):
    """[first, last] KV block indices a q block can attend — the same
    mapping the prefill BlockSpec index maps use."""
    causal_last = lax.div(base + (qi + 1) * bq - 1, bs)
    len_last = jnp.maximum(lax.div(kv_hi - 1, bs), 0)
    last = jnp.minimum(causal_last, len_last)
    if window is None:
        first = jnp.zeros_like(last)
    else:
        first = jnp.maximum(lax.div(base + qi * bq - window + 1, bs), 0)
    return jnp.minimum(first, last), jnp.maximum(last, 0)


def _prefill_kernel(lim_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                    acc_ref, *, bq: int, bs: int, g: int, scale: float,
                    softcap: Optional[float], window: Optional[int]):
    b, qi, ki = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _():
        m_ref[:] = jnp.full_like(m_ref, M_INIT)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    base = lim_ref[b, 0]             # absolute position of q row 0
    kv_hi = lim_ref[b, 1]            # valid KV rows
    first, last = _prefill_block_range(base, kv_hi, qi, bq, bs, window)
    start = jnp.minimum(first + ki, last) * bs  # matches kv_index below
    q_lo = base + qi * bq            # absolute position of first q row
    q_hi = q_lo + bq - 1
    # block participates iff some (row, col) pair passes causal+len+window;
    # `first + ki <= last` keeps clamped (repeated, DMA-skipped) steps
    # from double-counting the boundary block
    process = (first + ki <= last) & (start <= q_hi) & (start < kv_hi)
    if window is not None:
        process = process & (start + bs > q_lo - window + 1)

    @pl.when(process)
    def _():
        q = q_ref[0, :, 0]           # [bq, G, D]
        D = q.shape[-1]
        rows = bq * g
        qf = q.reshape(rows, D)
        kb = k_ref[0, :, 0, 0]       # [bs, D]
        logits = lax.dot_general(
            qf, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # [rows, bs]
        if softcap:
            logits = jnp.tanh(logits / softcap) * softcap
        col = start + lax.broadcasted_iota(jnp.int32, (rows, bs), 1)
        qpos = q_lo + lax.broadcasted_iota(jnp.int32, (rows, bs), 0) // g
        valid = (col <= qpos) & (col < kv_hi)
        if window is not None:
            valid = valid & (col > qpos - window)
        logits = jnp.where(valid, logits, M_INIT)

        m_prev = m_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(logits, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(logits - m_new)
        p = jnp.where(valid, p, 0.0)
        l_new = alpha * l_ref[:, :1] + jnp.sum(p, axis=1, keepdims=True)
        pv = lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0, :, 0, 0],
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)            # [rows, D]
        acc_ref[:] = acc_ref[:] * alpha + pv
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ki == nk - 1)
    def _():
        bq_, _, g_, D = o_ref.shape[1:]
        l = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0, :, 0] = (acc_ref[:] / l).reshape(bq_, g_, D) \
            .astype(o_ref.dtype)


def _flash_prefill(q, k, v, base, kv_hi, scale, softcap, window, interpret):
    B, Sq, H, D = q.shape
    S, K = k.shape[1], k.shape[2]
    G = H // K
    # the query block holds whole GQA groups: bq * G rows of D. Keep
    # it at what 8 heads a KV head of 128 dims take (the most the
    # kernel has been compiled at): a wider head or group halves bq
    # instead of outgrowing VMEM (head_dim 256 with 8 heads a KV head
    # needs 24 MB of the 16 at bq 256, chip compiler, PR 27)
    bq = _pick_block(Sq, tuple(c for c in (256, 128, 64, 32, 16)
                               if c * G * D <= 256 * 8 * 128))
    bs = _pick_block(S, (512, 256, 128, 64, 32, 16))
    if bq is None or bs is None or bq * G < 8 or D % 128 != 0:
        return None
    limits = jnp.stack(
        [base.astype(jnp.int32), kv_hi.astype(jnp.int32)], axis=1)
    q5 = q.reshape(B, Sq, K, G, D)
    k5 = k.reshape(B, S, K, 1, D)
    v5 = v.reshape(B, S, K, 1, D)

    def kv_index(b, kh, qi, ki, lim):
        # clamp to [first, last]: the upper causal triangle, the cache
        # tail, and (with a sliding window) the pre-window head are all
        # mapped to repeated indices -> Pallas skips their DMA
        first, last = _prefill_block_range(lim[b, 0], lim[b, 1], qi, bq,
                                           bs, window)
        return (b, jnp.minimum(first + ki, last), kh, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, K, Sq // bq, S // bs),
        in_specs=[
            pl.BlockSpec((1, bq, 1, G, D),
                         lambda b, kh, qi, ki, lim: (b, qi, kh, 0, 0)),
            pl.BlockSpec((1, bs, 1, 1, D), kv_index),
            pl.BlockSpec((1, bs, 1, 1, D), kv_index),
        ],
        out_specs=pl.BlockSpec(
            (1, bq, 1, G, D), lambda b, kh, qi, ki, lim: (b, qi, kh, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((bq * G, 128), jnp.float32),
            pltpu.VMEM((bq * G, 128), jnp.float32),
            pltpu.VMEM((bq * G, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_prefill_kernel, bq=bq, bs=bs, g=G, scale=scale,
                          softcap=softcap, window=window),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Sq, K, G, D), q.dtype),
        interpret=interpret,
        name="flash_prefill",
    )(limits, q5, k5, v5)
    return out.reshape(B, Sq, H, D)


# -- public entry ----------------------------------------------------------


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    positions: Optional[jax.Array] = None,
                    kv_len: Optional[jax.Array] = None,
                    sliding_window: Optional[int] = None,
                    scale: Optional[float] = None,
                    logit_softcap: Optional[float] = None,
                    interpret: bool = False) -> Optional[jax.Array]:
    """Flash attention or None when the kernels don't cover the shapes.

    q: [B, Sq, H, D]; k, v: [B, Skv, K, D], H % K == 0.
    positions: [B, Sq] absolute query positions, assumed contiguous per
    row (base + arange — what the model forward produces); None means
    non-causal full attention (not covered here -> None).
    kv_len: [B] valid KV rows (None = all Skv rows valid).
    """
    if positions is None:
        return None  # non-causal: XLA path
    B, Sq, H, D = q.shape
    K = k.shape[2]
    if H % K != 0:
        return None
    scale = scale if scale is not None else D ** -0.5
    base = positions[:, 0]
    if kv_len is None:
        kv_hi = jnp.full((B,), k.shape[1], jnp.int32)
    else:
        kv_hi = jnp.broadcast_to(kv_len, (B,)).astype(jnp.int32)
    if Sq == 1:
        pos = positions[:, 0]
        hi = jnp.minimum(pos + 1, kv_hi)
        lo = jnp.maximum(pos - sliding_window + 1, 0) if sliding_window \
            else jnp.zeros_like(pos)
        return _flash_decode(q, k, v, lo, hi, scale, logit_softcap,
                             interpret)
    return _flash_prefill(q, k, v, base, kv_hi, scale, logit_softcap,
                          sliding_window, interpret)
