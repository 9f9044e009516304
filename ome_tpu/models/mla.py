"""Multi-head Latent Attention (DeepSeek-V2/V3, Kimi-K2).

The reference ships first-class DeepSeek support throughout
(/root/reference/pkg/hfutil/modelconfig/deepseek_v3.go, the srt PD
runtime YAMLs) but delegates the math to SGLang; here it is
implemented TPU-first:

  * the KV cache stores per-token LATENTS — `kv_a_proj` output
    (kv_lora_rank) + the shared rope key (qk_rope_head_dim) — instead
    of per-head K/V. For DeepSeek-V3 that is 576 values/token vs
    128 heads x 2 x 192 = 49k for naive MHA caching: an ~85x cut in
    the decode step's KV bytes, which is exactly what the
    bandwidth-bound TPU decode roofline wants (bench.py).
  * decode uses the ABSORBED-weight path: q_nope is projected through
    w_uk into latent space once per step, scores and the attention-
    weighted sum run entirely against the latent cache, and w_uv
    lifts the result back per head — no materialized K/V at decode.
  * prefill materializes per-head K/V from the latents with two
    einsums (compute-bound anyway) and reuses plain masked SDPA.

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


def _masked_softmax(scores: jax.Array, q_pos: jax.Array,
                    k_pos: jax.Array,
                    kv_len: Optional[jax.Array]) -> jax.Array:
    """scores [B, H, S, T]; causal + kv-length masking, fp32 softmax."""
    mask = k_pos[None, None, None, :] <= q_pos[:, None, :, None]
    if kv_len is not None:
        mask &= k_pos[None, None, None, :] < kv_len[:, None, None, None]
    scores = jnp.where(mask, scores, -1e30)
    return jax.nn.softmax(scores.astype(jnp.float32), axis=-1)


def mla_attention(h: jax.Array, lp: Params, cfg: ModelConfig,
                  positions: jax.Array,
                  kv_len: Optional[jax.Array],
                  cache_kv: Optional[Tuple[jax.Array, jax.Array]],
                  cache_index: Optional[jax.Array]):
    """One MLA attention block (pre-normed input h [B, S, D]).

    Returns (attn_out [B, S, D], new_cache_kv or None). The cache's k
    plane holds latents [B, Smax, 1, kv_lora_rank + rope] (or merged
    rows [B, Smax, kv_lora_rank + rope], llama.KVCache); the v plane
    is zero-width (cfg.kv_cache_v_dim == 0).
    """
    B, S, _ = h.shape
    Hn = cfg.num_heads
    nope, rope = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    r = cfg.kv_lora_rank

    from .llama import _rows_as, _w, rms_norm  # shared with llama

    # -- queries -------------------------------------------------------
    if cfg.q_lora_rank:
        ql = jnp.einsum("bsd,dr->bsr", h, _w(lp, "wq_a", cfg.dtype))
        ql = rms_norm(ql, lp["q_a_norm"], cfg.rms_norm_eps)
        q = jnp.einsum("bsr,rhk->bshk", ql, _w(lp, "wq_b", cfg.dtype))
    else:
        # out-major [H, qk, D], as every model's wq lies (llama._proj)
        q = jnp.einsum("bsd,hkd->bshk", h, _w(lp, "wq", cfg.dtype))
    q_nope, q_pe = q[..., :nope], q[..., nope:]
    q_pe = rope_interleaved(q_pe, positions, cfg)

    # -- latent K/V ----------------------------------------------------
    ckv = jnp.einsum("bsd,dr->bsr", h, _w(lp, "wkv_a", cfg.dtype))
    c, k_pe = ckv[..., :r], ckv[..., r:]
    c = rms_norm(c, lp["kv_a_norm"], cfg.rms_norm_eps)
    k_pe = rope_interleaved(k_pe[:, :, None, :], positions,
                            cfg)[:, :, 0]
    latent = jnp.concatenate([c, k_pe], axis=-1)[:, :, None, :]

    if cache_kv is not None:
        ck_cache, cv_cache = cache_kv
        # the one latent "head" a row: [B, Smax, 1, r + rope], or a
        # slab engine's merged rows [B, Smax, r + rope], the same bytes
        rows = _rows_as(latent, ck_cache.dtype, ck_cache.shape[2:])
        rest = (0,) * (ck_cache.ndim - 2)
        if cache_index.ndim == 1:
            upd = jax.vmap(
                lambda cc, u, i: lax.dynamic_update_slice(
                    cc, u, (i,) + rest))
            ck_cache = upd(ck_cache, rows, cache_index)
        else:
            ck_cache = lax.dynamic_update_slice(
                ck_cache, rows, (0, cache_index) + rest)
        new_cache = (ck_cache, cv_cache)
        full = ck_cache.reshape(ck_cache.shape[:2] + (-1,))  # [B, T, r+rope]
        k_pos = jnp.arange(full.shape[1], dtype=jnp.int32)
    else:
        new_cache = None
        full = latent[:, :, 0]                       # [B, S, r+rope]
        k_pos = None
    c_all, kpe_all = full[..., :r], full[..., r:]
    scale = cfg.mla_scale

    if S == 1 and cache_kv is not None:
        # -- absorbed decode: never leave latent space -----------------
        w_uk = _w(lp, "w_uk", cfg.dtype)             # [H, nope, r]
        w_uv = _w(lp, "w_uv", cfg.dtype)             # [H, r, v_dim]
        q_lat = jnp.einsum("bshn,hnr->bshr", q_nope, w_uk)
        scores = (jnp.einsum("bshr,btr->bhst", q_lat, c_all)
                  + jnp.einsum("bshp,btp->bhst", q_pe, kpe_all)) * scale
        attn = _masked_softmax(scores, positions, k_pos, kv_len)
        out_lat = jnp.einsum("bhst,btr->bshr",
                             attn.astype(c_all.dtype), c_all)
        attn_out = jnp.einsum("bshr,hrv->bshv", out_lat, w_uv)
    else:
        # -- prefill: materialize per-head K/V from the latents --------
        k_nope = jnp.einsum("btr,hnr->bthn", c_all,
                            _w(lp, "w_uk", cfg.dtype))
        v = jnp.einsum("btr,hrv->bthv", c_all,
                       _w(lp, "w_uv", cfg.dtype))
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(
                kpe_all[:, :, None, :],
                (*k_nope.shape[:3], rope)).astype(k_nope.dtype)],
            axis=-1)
        qf = jnp.concatenate([q_nope, q_pe.astype(q_nope.dtype)],
                             axis=-1)
        scores = jnp.einsum("bshk,bthk->bhst", qf, k) * scale
        if k_pos is None:
            k_pos_eff = positions[0]                 # plain causal
        else:
            k_pos_eff = k_pos
        attn = _masked_softmax(scores, positions, k_pos_eff, kv_len)
        attn_out = jnp.einsum("bhst,bthv->bshv",
                              attn.astype(v.dtype), v)

    out = jnp.einsum("bshv,hvd->bsd", attn_out, _w(lp, "wo", cfg.dtype))
    return out, new_cache
