"""Ring attention: context parallelism for long sequences.

The sequence dim is sharded over a mesh axis; each device keeps its
local Q shard resident and the K/V shards ROTATE around the ring
(lax.ppermute -> ICI neighbor exchange on TPU), with flash-style
online-softmax accumulation so no device ever materializes full
[S, S] attention — memory per device is O(S/n * S/n) per step and
total K/V traffic is one full rotation regardless of sequence length.
This is the jax-native equivalent of RingAttention/Context-Parallel
in the GPU stacks (the reference operator has none — SURVEY.md §2.9
lists SP/CP as ABSENT; its engines cap context per device instead).

Causality rides absolute positions: block (i attends j) masks by
comparing the static local position grid against the rotating block's
offset — no materialized [S, S] mask anywhere.

Layout contract: q/k/v enter sharded [B, S, H, D] with S split over
`axis` (shard_map handles the split); the output returns with the
same S sharding. Use for long-context training and chunked prefill;
decode keeps the KV-head-sharded engine path.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P


M_INIT = -1.0e30


def _block_attend(q, k, v, q_pos, kv_pos, scale, softcap):
    """One (local-Q x rotated-KV) block: masked logits + softmax stats.

    q: [B, Sq, K, G, D]; k/v: [B, Sk, K, D]. Returns (m, l, acc) with
    m/l [B, K, G, Sq, 1] f32, acc [B, K, G, Sq, D] f32.
    """
    logits = jnp.einsum("bskgd,btkd->bkgst", q, k,
                        preferred_element_type=jnp.float32) * scale
    if softcap:
        logits = jnp.tanh(logits / softcap) * softcap
    valid = (kv_pos[None, :] <= q_pos[:, None])[None, None, None]
    logits = jnp.where(valid, logits, M_INIT)
    m = jnp.max(logits, axis=-1, keepdims=True)
    p = jnp.exp(logits - m)
    p = jnp.where(valid, p, 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    # p stays f32 with f32 accumulation: one bf16 rounding per ring
    # step would compound over long sequences
    acc = jnp.einsum("bkgst,btkd->bkgsd", p, v,
                     preferred_element_type=jnp.float32)
    return m, l, acc


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                   mesh: Mesh, axis: str = "tp",
                   scale: Optional[float] = None,
                   logit_softcap: Optional[float] = None) -> jax.Array:
    """Causal GQA attention with the sequence sharded over `axis`.

    q: [B, S, H, D]; k, v: [B, S, K, D]; S % mesh.shape[axis] == 0.
    Equivalent to full causal attention over the gathered sequence.
    """
    B, S, H, D = q.shape
    K = k.shape[2]
    G = H // K
    n = mesh.shape[axis]
    assert S % n == 0, f"seq {S} must divide over {axis}={n}"
    scale_ = scale if scale is not None else D ** -0.5

    def local(q, k, v):
        # q: [B, S/n, H, D] local shard
        idx = lax.axis_index(axis)
        sl = q.shape[1]
        q5 = q.reshape(B, sl, K, G, D)
        q_pos = idx * sl + lax.broadcasted_iota(jnp.int32, (sl, 1), 0)[:, 0]

        m = jnp.full((B, K, G, sl, 1), M_INIT, jnp.float32)
        l = jnp.zeros((B, K, G, sl, 1), jnp.float32)
        acc = jnp.zeros((B, K, G, sl, D), jnp.float32)

        def merge(m, l, acc, kv_idx, k, v):
            kv_pos = kv_idx * sl + lax.broadcasted_iota(
                jnp.int32, (sl, 1), 0)[:, 0]
            bm, bl, bacc = _block_attend(q5, k, v, q_pos, kv_pos,
                                         scale_, logit_softcap)
            m_new = jnp.maximum(m, bm)
            alpha = jnp.exp(m - m_new)
            beta = jnp.exp(bm - m_new)
            return (m_new, alpha * l + beta * bl,
                    alpha * acc + beta * bacc)

        def step(carry, _):
            m, l, acc, k, v, kv_idx = carry
            m, l, acc = merge(m, l, acc, kv_idx, k, v)
            # rotate K/V (and their block index) to the next device
            perm = [(i, (i + 1) % n) for i in range(n)]
            k = lax.ppermute(k, axis, perm)
            v = lax.ppermute(v, axis, perm)
            kv_idx = lax.ppermute(kv_idx, axis, perm)
            return (m, l, acc, k, v, kv_idx), None

        # n-1 rotated steps; the last block merges WITHOUT rotating (a
        # final ppermute would ship every K/V shard once for nothing)
        if n > 1:
            (m, l, acc, k, v, kv_idx), _ = lax.scan(
                step, (m, l, acc, k, v, idx), None, length=n - 1)
        else:
            kv_idx = idx
        m, l, acc = merge(m, l, acc, kv_idx, k, v)
        out = acc / jnp.maximum(l, 1e-30)
        # [B, K, G, sl, D] -> [B, sl, H, D]
        return out.transpose(0, 3, 1, 2, 4).reshape(B, sl, H, D) \
            .astype(q.dtype)

    spec_q = P(None, axis, None, None)
    return jax.shard_map(local, mesh=mesh,
                         in_specs=(spec_q, spec_q, spec_q),
                         out_specs=spec_q, check_vma=False)(q, k, v)
