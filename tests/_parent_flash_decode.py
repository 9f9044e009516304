"""`flash_decode` as PR 36 left it, kept for the tests alone: the kernel
whose key / value block is `[1, bs, K, D]` of a slab `[B, S, K, D]`
(or the stacked `[L, B, S, K, D]`). PR 38 re-laid the blocks as dense
`[bs, K * D]` tiles and promised the same dots in the same order;
`tests/test_flash_decode_merged.py` holds the new kernel to this one
bit for bit. Nothing of the program imports it.
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ome_tpu.ops import flash


def _parent_decode_kernel(lim_ref, q_ref, k_ref, v_ref, *refs, bs: int,
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
        m_ref[:] = jnp.full_like(m_ref, flash.M_INIT)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    lo = lim_ref[pl.program_id(0), 0]
    hi = lim_ref[pl.program_id(0), 1]
    first, last = flash._decode_block_range(lo, hi, bs)
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
        logits = jnp.where(valid, logits, flash.M_INIT)

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


def parent_flash_decode(q, k, v, lo, hi, scale, softcap, interpret,
                  k_scale=None, v_scale=None, layer=None):
    """`layer` (an int32 scalar, traced or not): k and v are the
    STACKED slabs [L, B, S, K, D] of a layer scan that carries them,
    and the kernel reads layer `layer` of them where they lie: the
    index rides scalar prefetch into the block index maps, so no
    layer is sliced out first (a slice of a carried slab is a copy of
    it; ops/paged.py does the same for the pool)."""
    B, _, H, D = q.shape
    S, K = k.shape[-3], k.shape[-2]
    G = H // K
    bs = flash._pick_block(S, (512, 256, 128))
    if bs is None or H < 8 or D % 128 != 0:
        return None
    ns = S // bs
    quantized = k_scale is not None
    stacked = layer is not None
    assert not (stacked and quantized)
    limits = [lo.astype(jnp.int32), hi.astype(jnp.int32)]
    if stacked:
        # third column: the layer, the same for every row
        limits.append(jnp.broadcast_to(jnp.asarray(layer, jnp.int32),
                                       (B,)))
    limits = jnp.stack(limits, axis=1)                   # [B, 2 or 3]
    qh = q.reshape(B, K, G, D)

    # walk blocks starting at the sliding-window's first valid block and
    # clamp at the last block holding a valid row: repeated indices make
    # Pallas skip the DMA for both the pre-window head (long-context
    # sliding window) and the cache tail (short sequences).
    def kv_index(b, s, lim):
        first, last = flash._decode_block_range(lim[b, 0], lim[b, 1], bs)
        at = (b, jnp.minimum(first + s, last), 0, 0)
        return (lim[b, 2],) + at if stacked else at

    # the layer's dimension is squeezed out of the block: the kernel
    # sees [1, bs, K, D] either way
    kv_block = ((None,) if stacked else ()) + (1, bs, K, D)

    def sc_index(b, s, lim):
        first, last = flash._decode_block_range(lim[b, 0], lim[b, 1], bs)
        return (b, 0, jnp.minimum(first + s, last))

    in_specs = [
        pl.BlockSpec((1, K, G, D), lambda b, s, lim: (b, 0, 0, 0)),
        pl.BlockSpec(kv_block, kv_index),
        pl.BlockSpec(kv_block, kv_index),
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
        functools.partial(_parent_decode_kernel, bs=bs, scale=scale,
                          softcap=softcap, quantized=quantized),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, K, G, D), q.dtype),
        interpret=interpret,
        name="flash_decode",
    )(*args)
    return out.reshape(B, 1, H, D)
