"""Multi-head Latent Attention (DeepSeek-V2/V3, Kimi-K2,
openPangu-Ultra-MoE).

The reference ships first-class DeepSeek support throughout
(/root/reference/pkg/hfutil/modelconfig/deepseek_v3.go, the srt PD
runtime YAMLs) but delegates the math to SGLang; here it is
implemented TPU-first (docs/latent-attention.md):

  * the KV cache stores per-token LATENTS: one row `[c | k_pe]`, the
    normed `kv_a_proj` output (kv_lora_rank) beside the shared rotary
    key (qk_rope_head_dim), instead of per-head K/V. At 128 heads
    that is 576 values a token against 128 x (192 + 128) = 41k: a
    seventieth of the decode step's cache bytes. A row wider than a
    tile of 128 lanes is padded to whole tiles, 576 -> 640
    (`ModelConfig.kv_cache_k_dim` says why). The v plane is
    zero-width.
  * decode uses the ABSORBED-weight path: q_nope is projected through
    w_uk into latent space once per step, and the kernel
    `latent_decode` (ops/flash.py, through ops/attention.py's
    dispatch) reads each block of a slot's rows ONCE, as key (all 576
    lanes) and as value (the first 512), for all heads; w_uv lifts
    the result back per head: no materialized K/V at decode. A layer
    scan that carries the stacked slab hands it over as a
    `llama.SlabLayer`: the rows are written in place and the kernel
    reads the slab by layer index.
  * prefill materializes per-head keys and values from the latents
    with two einsums, head-major, and runs `latent_prefill`: causal
    blocked attention whose query / key width (nope + rope) is not
    its value width, the rotary key one operand all heads share.
  * off the chip, under a head-sharded trace and where a kernel
    declines a shape, both take the einsum path of ops/attention.py
    (`xla_latent_decode`, `xla_latent_prefill`), which the kernels
    are tested against.

RoPE on the rope dims uses the interleaved-pair convention of the HF
reference (modeling_deepseek_v2.apply_rotary_emb /
v3.apply_rotary_pos_emb_interleave); attention scores are permutation-
invariant to the pair layout, so logits match both variants.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from .config import ModelConfig

Params = dict


def yarn_frequencies(cfg: ModelConfig, d: int):
    """Rope inverse frequencies + cos/sin attention factor.

    Plain RoPE unless cfg.rope_scaling is YaRN, in which case the
    published YaRN recipe applies (frequency interpolation below the
    beta_slow boundary, extrapolation above beta_fast, a linear ramp
    between — and the mscale attention factor on cos/sin), matching
    transformers' _compute_yarn_parameters as DeepSeek configures it
    (dim = qk_rope_head_dim).
    """
    import math
    half = d // 2
    pos_freqs = cfg.rope_theta ** (jnp.arange(half, dtype=jnp.float32)
                                   * 2 / d)
    inv_freq = 1.0 / pos_freqs
    rs = cfg.rope_scaling or {}
    if rs.get("rope_type", rs.get("type")) != "yarn":
        return inv_freq, 1.0
    factor = rs.get("factor", 1.0)
    beta_fast = rs.get("beta_fast") or 32
    beta_slow = rs.get("beta_slow") or 1
    orig = (rs.get("original_max_position_embeddings")
            or cfg.max_seq_len)

    def correction_dim(n_rot):
        return (d * math.log(orig / (n_rot * 2 * math.pi))
                / (2 * math.log(cfg.rope_theta)))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), d - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - low)
                    / (high - low), 0, 1)
    extrapolation_factor = 1.0 - ramp
    inv_freq = (inv_freq / factor * ramp
                + inv_freq * extrapolation_factor)

    def get_mscale(scale, m=1.0):
        return 0.1 * m * math.log(scale) + 1.0 if scale > 1 else 1.0

    att = rs.get("attention_factor")
    if att is None:
        mscale, mscale_all = rs.get("mscale"), rs.get("mscale_all_dim")
        if mscale and mscale_all:
            att = get_mscale(factor, mscale) / get_mscale(factor,
                                                          mscale_all)
        else:
            att = get_mscale(factor)
    return inv_freq, float(att)


def rope_interleaved(x: jax.Array, positions: jax.Array,
                     cfg: ModelConfig) -> jax.Array:
    """Rotate interleaved pairs: (x[2j], x[2j+1]) by pos * inv_freq_j,
    with YaRN frequency remapping + mscale when configured.

    x: [B, S, N, D] (N may be 1 for the shared MQA rope key)."""
    d = x.shape[-1]
    freqs, att = yarn_frequencies(cfg, d)
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B,S,d/2]
    cos = jnp.cos(angles)[:, :, None, :] * att
    sin = jnp.sin(angles)[:, :, None, :] * att
    xf = x.astype(jnp.float32)
    x0 = xf[..., 0::2]
    x1 = xf[..., 1::2]
    out0 = x0 * cos - x1 * sin
    out1 = x0 * sin + x1 * cos
    # scores are invariant to pair ordering as long as q and k agree,
    # so emit [evens | odds] (a cheap concat, no re-interleave)
    return jnp.concatenate([out0, out1], axis=-1).astype(x.dtype)


def mla_attention(h: jax.Array, lp: Params, cfg: ModelConfig,
                  positions: jax.Array,
                  kv_len: Optional[jax.Array],
                  cache_kv, cache_index: Optional[jax.Array]):
    """One MLA attention block (pre-normed input h [B, S, D]).

    Returns (attn_out [B, S, D], new_cache_kv or None). `cache_kv` is
    one layer's (k, v): the k plane holds latents [B, Smax, 1,
    kv_lora_rank + rope] (or merged rows [B, Smax, kv_lora_rank +
    rope], llama.KVCache) and the v plane is zero-width
    (cfg.kv_cache_v_dim == 0); or a `llama.SlabLayer`, the WHOLE
    stacked slabs of a layer scan that carries them and this layer's
    index, whose updated slabs are handed back.

    Scopes (telemetry/scopes.py): the projections under `qkv` (the
    absorbed query and a prompt's materialised keys and values too),
    the cache write and the attention itself under `attn_latent`
    (`kv_write`, `attn`), `w_uv`'s lift and the output projection
    under `o_proj`.
    """
    B, S, _ = h.shape
    nope, r, rope = (cfg.qk_nope_head_dim, cfg.kv_lora_rank,
                     cfg.qk_rope_head_dim)
    pad = cfg.kv_cache_k_dim - r - rope

    from ..ops import attention as ops
    from .llama import SlabLayer, _rows_as, _w, _write_rows, rms_norm

    slab = cache_kv if isinstance(cache_kv, SlabLayer) else None
    Hn = cfg.num_heads

    def queries(heads):
        """q_nope [B, G, S, nope] and rotated q_pe [B, G, S, rope] of
        the heads `heads(leaf, axis)` cuts out of a weight."""
        if cfg.q_lora_rank:
            # out-major too, [H, qk, q_rank]: a decode step's dot reads
            # a weight with its contraction dim minor, and a leaf laid
            # [q_rank, H, qk] was re-laid by a copy every step (chip
            # compiler, PR 46: 75 MB a layer at 128 heads)
            q = jnp.einsum("bsr,hkr->bshk", ql,
                           heads(_w(lp, "wq_b", cfg.dtype), 0))
        else:
            # out-major [H, qk, D], as every model's wq lies (llama._proj)
            q = jnp.einsum("bsd,hkd->bshk", h,
                           heads(_w(lp, "wq", cfg.dtype), 0))
        # (the weights are multiplied whole and the product cut: a
        # slice of a weight's minor dimension is a copy of it a step)
        q_nope = jnp.swapaxes(q[..., :nope], 1, 2)
        q_pe = q[..., nope:]
        q_pe = jnp.swapaxes(rope_interleaved(q_pe, positions, cfg), 1, 2)
        return q_nope, q_pe.astype(q_nope.dtype)

    with jax.named_scope("qkv"):
        if cfg.q_lora_rank:
            ql = jnp.einsum("bsd,dr->bsr", h, _w(lp, "wq_a", cfg.dtype))
            ql = rms_norm(ql, lp["q_a_norm"], cfg.rms_norm_eps)
        # -- the latent row [c | k_pe] -----------------------------------
        ckv = jnp.einsum("bsd,dr->bsr", h, _w(lp, "wkv_a", cfg.dtype))
        c = rms_norm(ckv[..., :r], lp["kv_a_norm"], cfg.rms_norm_eps)
        k_pe = rope_interleaved(ckv[:, :, None, r:], positions, cfg)[:, :, 0]
        # [B, S, r + rope (+ the cache row's zero padding)]
        latent = jnp.pad(jnp.concatenate([c, k_pe], axis=-1),
                         ((0, 0), (0, 0), (0, pad)))

    layer, new_cache = None, None
    if cache_kv is None:
        rows = latent                                # plain causal
    else:
        with jax.named_scope("attn_latent"), jax.named_scope("kv_write"):
            fresh = latent[:, :, None, :]            # the one latent "head"
            if slab is not None:
                rows = _write_rows(slab.k, fresh, slab.layer, cache_index)
                new_cache, layer = (rows, slab.v), slab.layer
            else:
                ck, cv = cache_kv
                fresh = _rows_as(fresh, ck.dtype, ck.shape[2:])
                rest = (0,) * (ck.ndim - 2)
                if cache_index.ndim == 1:
                    rows = jax.vmap(
                        lambda cc, u, i: lax.dynamic_update_slice(
                            cc, u, (i,) + rest))(ck, fresh, cache_index)
                else:
                    rows = lax.dynamic_update_slice(
                        ck, fresh, (0, cache_index) + rest)
                new_cache = (rows, cv)
    scale = cfg.mla_scale

    if S == 1 and cache_kv is not None:
        # -- absorbed decode: never leave latent space -----------------
        with jax.named_scope("qkv"):
            q_nope, q_pe = queries(lambda w, axis: w)
            q_lat = jnp.einsum("bhn,hnr->bhr", q_nope[:, :, 0],
                               _w(lp, "w_uk", cfg.dtype))
        with jax.named_scope("attn_latent"), jax.named_scope("attn"):
            out_lat = ops.latent_decode(
                q_lat, q_pe[:, :, 0], rows, positions, kv_len, rank=r,
                scale=scale, layer=layer)            # [B, H, r]
        with jax.named_scope("o_proj"):
            attn_out = jnp.einsum("bhr,hrv->bhv", out_lat,
                                  _w(lp, "w_uv", cfg.dtype))[None, :, :, None]
    else:
        # -- prefill: materialize per-head K/V from the latents, a
        # group of G heads at a time (heads are independent): a long
        # prompt's queries, keys and values for all heads at once are
        # gigabytes (0.54 GB each of four at 16 384 x 128 heads)
        with jax.named_scope("qkv"):
            if layer is not None:                    # a prompt: one copy
                rows = lax.dynamic_index_in_dim(rows, layer, 0, False)
            full = rows.reshape(rows.shape[:2] + (-1,))  # [B, T, r + rope +]
            c_all, kpe_all = full[..., :r], full[..., r:r + rope]
        G = _prefill_head_group(B * full.shape[1], Hn, nope)

        def group(g0):
            def heads(w, axis):
                return w if G == Hn else \
                    lax.dynamic_slice_in_dim(w, g0, G, axis)
            with jax.named_scope("qkv"):
                q_nope, q_pe = queries(heads)
                k_nope = jnp.einsum("btr,hnr->bhtn", c_all,
                                    heads(_w(lp, "w_uk", cfg.dtype), 0))
                v = jnp.einsum("btr,hrv->bhtv", c_all,
                               heads(_w(lp, "w_uv", cfg.dtype), 0))
            with jax.named_scope("attn_latent"), jax.named_scope("attn"):
                return ops.latent_prefill(
                    q_nope, q_pe, k_nope, kpe_all, v, positions,
                    kv_len if cache_kv is not None else None,
                    scale=scale)                     # [B, G, S, v]

        attn_out = group(0)[None] if G == Hn else \
            lax.map(group, jnp.arange(0, Hn, G, dtype=jnp.int32))

    with jax.named_scope("o_proj"):
        # [n, B, G, S, v]: the n head groups in order
        wo = _w(lp, "wo", cfg.dtype)
        out = jnp.einsum(
            "nbgsv,ngvd->bsd", attn_out,
            wo.reshape((attn_out.shape[0], -1) + wo.shape[1:]))
    return out, new_cache


# heads of a prompt materialised at a time: the most (a power-of-two
# share of them) whose keys stay under this many bytes
_PREFILL_GROUP_BYTES = 1 << 27


def _prefill_head_group(rows: int, heads: int, nope: int) -> int:
    G = heads
    while G % 2 == 0 and rows * G * nope * 2 > _PREFILL_GROUP_BYTES:
        G //= 2
    return G
