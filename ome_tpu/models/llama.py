"""Flagship Llama-family decoder in pure-functional JAX.

TPU-first design choices (vs. the torch modules the reference's engines
wrap): parameters are a pytree of stacked per-layer arrays scanned with
`lax.scan` (one compiled layer body, natural fit for pipeline stages),
bf16 weights with fp32 softmax/norm accumulation, static shapes
everywhere, and attention dispatched through ome_tpu.ops so the Pallas
flash kernel is used on TPU with an XLA fallback on the CPU test mesh.

Covers dense Llama/Mistral/Qwen2 (qkv bias)/Qwen3 (qk-norm) models,
the Mixtral-style top-k MoE variant (dense or ragged dispatch), and
the gemma2 block shape (GeGLU, post-block (1+w) norms, alternating
sliding-window/global attention via a layer-pair scan, softcaps).
"""

from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..ops.attention import attention
from ..telemetry.scopes import scoped
from . import gdn
from .config import ModelConfig
from .quant import QTensor

Params = Dict[str, Any]


def _w(p: Params, name: str, dtype=None) -> jax.Array:
    """Weight accessor: dequantizes int8 QTensor leaves at use (XLA
    fuses the convert+scale into the consuming matmul's operand read,
    so quantized serving streams int8 bytes from HBM). dtype is the
    compute dtype (cfg.dtype); defaults to bfloat16."""
    w = p[name]
    if isinstance(w, QTensor):
        return w.dequant(dtype or jnp.bfloat16)
    return w


# the stacked leaves that lie out-major, [L, heads, Dh, D] (`_proj`;
# a latent model's second query projection [L, heads, qk, q_rank] too)
OUT_MAJOR = ("wq", "wk", "wv", "w_ogate", "wq_b")


def to_out_major(w):
    """[.., D, heads, Dh] -> [.., heads, Dh, D]: a projection out of
    the hidden size from the in-major order (in which the random
    weights are drawn) to the order it is stored in."""
    return jnp.moveaxis(w, -3, -1)


def _proj(x: jax.Array, w, dtype, out_dims=None, flatten: int = 1,
          out_major: bool = False):
    """Contract x's trailing `flatten` dims with weight `w`.

    `w` lies one of two ways. In-major, [K.., N..]: the contraction
    dims first, the output channels behind them (wo, the MLP's
    matrices, the DeltaNet leaves). `out_major`, [N.., K]: a row an
    output channel, the contraction its LAST dim, which is how the
    stacked attention projections wq / wk / wv / w_ogate are stored,
    [L, H, Dh, D] (`_init_layer_block`). The dot is the same dot with
    the operands' contraction dims named the other way; the reason is
    the decode step: its dot has a handful of rows, the compiler reads
    the weight with the contraction dim minor in HBM, and a leaf stored
    [L, D, H, Dh] was re-laid out by a `copy` before every use (a
    layer's slice inside the layer scan, or the whole stack ahead of
    it: 1.6-2.5 ms of every step, ROADMAP A4). wo [L, H, Dh, D]
    contracts its leading dims and never had one.

    int4 QTensor leaves route through the fused Pallas kernel
    (ops/int4_matmul.py) so the nibble unpack happens in VMEM and HBM
    streams packed bytes; everything else (bf16, int8, unsupported
    shapes, non-TPU) takes the dequant + einsum path, which XLA fuses
    for int8. An in-major int4 leaf packs its FIRST contraction dim
    and every dim up to it is a contraction dim (w_gate / w_up; not
    expert-stacked or per-head-factored leaves); an out-major one
    packs its last dim (quant._LAYER_CONTRACT).
    """
    import math
    lead = x.shape[:-flatten]
    K = math.prod(x.shape[len(lead):])
    x2 = x.reshape(*lead, K)
    y = None
    if isinstance(w, QTensor) and w.bits == 4:
        from ..ops.int4_matmul import int4_matmul
        y = int4_matmul(x2, w, dtype or jnp.bfloat16)
    if y is None:
        wd = w.dequant(dtype or jnp.bfloat16) \
            if isinstance(w, QTensor) else w
        if out_major:
            y = jnp.einsum("...k,nk->...n", x2, wd.reshape(-1, K))
        else:
            y = jnp.einsum("...k,kn->...n", x2, wd.reshape(K, -1))
    if out_dims:
        y = y.reshape(*y.shape[:-1], *out_dims)
    return y


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class KVCache:
    """Fixed-capacity per-layer KV cache.

    k, v: [L, B, S_max, K, Dh], or MERGED rows [L, B, S_max, K * Dh]:
    a row's K heads side by side in the lanes, which is how a slab
    engine's decode state lies, because `flash_decode` reads dense
    [rows, K * Dh] tiles of it (ops/flash.py). Which of the two is
    decided once, by who creates the cache (`create(merged=)`), from
    what will read it: merged for the slab and the prefills that fill
    it; heads apart for a paged engine's prefill, whose rows go into
    the pool [L, N, block, K, Dh]. Every reader and writer below
    tells the two apart by the array's rank.
    index: next-write position — scalar int32 (shared by the whole
    batch: training-style chunked prefill) or [B] int32 (per-slot
    write positions: the serving engine's continuous-batching decode,
    where every slot is at a different sequence length).
    """

    k: jax.Array
    v: jax.Array
    index: jax.Array
    # hybrid models only (cfg.is_hybrid): what a sequence carries
    # through the Gated DeltaNet layers, which is not KV rows:
    # {"S": [Ll, B, Hv, dk, dv] float32, "conv": [Ll, B, W-1, C]};
    # k and v then hold the full-attention layers only
    rec: Any = None
    # [3] uint32, expert-layer counters a decode program adds to:
    # layer-steps run, held experts hit, routed pairs that landed on
    # a held expert (engine/core.py reads them at scrape)
    stats: Any = None
    # periodic window / global models only (cfg.window_layers): the
    # window layers' RING, [Lw, B, W, K, Dh] (merged as k and v are:
    # [Lw, B, W, K * Dh]) with W = sliding_window (or the cache's
    # length where that is shorter): position p lives in row p % W, so
    # a slot holds its last W rows and no others
    # (docs/window-cache.md); k and v then hold the global layers only
    wk: Any = None
    wv: Any = None

    @classmethod
    def create(cls, cfg: ModelConfig, batch: int, max_seq: Optional[int] = None,
               dtype=None, merged: bool = False) -> "KVCache":
        S = max_seq or cfg.max_seq_len
        dtype = dtype or cfg.dtype
        L = cfg.kv_cache_layers
        ks, vs = kv_rows_shapes(cfg, (L, batch, S), merged)
        ring = (None, None)
        if cfg.window_layers:
            ring = kv_rows_shapes(
                cfg, (cfg.window_layers, batch, ring_rows(cfg, S)), merged)
        return cls(k=jnp.zeros(ks, dtype), v=jnp.zeros(vs, dtype),
                   index=jnp.zeros((), jnp.int32),
                   rec=recurrent_state(cfg, batch, dtype),
                   wk=ring[0] and jnp.zeros(ring[0], dtype),
                   wv=ring[1] and jnp.zeros(ring[1], dtype))


def kv_rows_shapes(cfg: ModelConfig, lead: Tuple[int, ...], merged: bool):
    """(k shape, v shape) of KV rows behind the dimensions `lead`:
    heads apart, lead + [K, D], or merged, lead + [K * D] (`KVCache`).
    MLA caches one latent "head" of kv_lora_rank+rope dims and a
    zero-width v plane (models/mla.py); dense models cache K/V."""
    K = cfg.kv_cache_heads
    return tuple(lead + ((K * d,) if merged else (K, d))
                 for d in (cfg.kv_cache_k_dim, cfg.kv_cache_v_dim))


def ring_rows(cfg: ModelConfig, max_seq: int) -> int:
    """Rows of a window layer's ring in a cache of `max_seq`
    positions: the window, or every position where there are fewer
    (a ring that never wraps)."""
    return min(cfg.sliding_window, max_seq)


def recurrent_state(cfg: ModelConfig, batch: int, dtype=None):
    """Zeroed per-sequence state of a hybrid model's DeltaNet layers
    (None for every other model)."""
    if not cfg.is_hybrid:
        return None
    Ll = cfg.linear_layers
    return {
        "S": jnp.zeros((Ll, batch, cfg.linear_num_value_heads,
                        cfg.linear_key_head_dim,
                        cfg.linear_value_head_dim), jnp.float32),
        "conv": jnp.zeros((Ll, batch, cfg.linear_conv_kernel - 1,
                           cfg.linear_conv_dim), dtype or cfg.dtype),
    }


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class PagedKVCache:
    """Block-pool KV cache for the serving engine's paged decode.

    k, v: [L, N, block, K, Dh] POOLS of N fixed-size blocks shared by
    every decode slot; `table`: [B, M] int32 block table mapping each
    slot's sequence block j to a pool block id (0 is the reserved
    trash block — unallocated entries point there and kv_len masking
    makes it unreachable for reads); `index`: [B] per-slot lengths.
    HBM is sized by total tokens in flight (N * block) instead of
    B * S_max — the vLLM/SGLang PagedAttention idea with TPU-static
    shapes (ops/paged.py).
    """

    k: jax.Array
    v: jax.Array
    index: jax.Array
    table: jax.Array
    # int8 pools only: per-(row, head) f32 dequant scales, stored
    # S-minor ([L, N, K, block]) so each block's [K, block] scale
    # plane is lane-aligned for the Pallas kernel (ops/flash.py
    # quantize_kv_block layout); None for bf16 pools
    k_scale: jax.Array = None
    v_scale: jax.Array = None

    @classmethod
    def create(cls, cfg: ModelConfig, batch: int, n_blocks: int,
               block: int, max_blocks: int,
               dtype=None) -> "PagedKVCache":
        dtype = dtype or cfg.dtype
        K, Dk, Dv = (cfg.kv_cache_heads, cfg.kv_cache_k_dim,
                     cfg.kv_cache_v_dim)
        L = cfg.num_layers
        quantized = jnp.dtype(dtype) == jnp.int8

        def scale():
            # distinct buffers per plane: donation refuses aliases
            return (jnp.zeros((L, n_blocks, K, block), jnp.float32)
                    if quantized else None)
        return cls(k=jnp.zeros((L, n_blocks, block, K, Dk), dtype),
                   v=jnp.zeros((L, n_blocks, block, K, Dv), dtype),
                   index=jnp.zeros((batch,), jnp.int32),
                   table=jnp.zeros((batch, max_blocks), jnp.int32),
                   k_scale=scale(), v_scale=scale())


# -- init ------------------------------------------------------------------


def _init_layer_block(rng: jax.Array, cfg: ModelConfig, L: int,
                      moe: bool, linear: bool = False) -> Params:
    """One stacked block of L structurally-identical layers.
    `linear`: a hybrid model's Gated DeltaNet layers (the mixer's
    leaves in place of the attention projections).

    The attention projections out of the hidden size, wq / wk / wv and
    the output gate w_ogate, lie OUT-MAJOR, [L, heads, Dh, D]: a row an
    output channel and the hidden size last, as wo [L, H, Dh, D] lies
    and as a Hugging Face `q_proj.weight` [heads * Dh, D] lies
    reshaped. That is the order in which the decode step's dot reads
    them (`_proj`), so no program re-lays a layer's slice or the stack
    before using it. Every other matrix is in-major, [L, K.., N..]."""
    D, H, K, Dh, F = (cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads,
                      cfg.head_dim, cfg.intermediate_size)
    # an out-major leaf is DRAWN [L, D, heads, Dh], the shape and the
    # key order its values have always had (the benchmark's references
    # draw the same), and re-laid after the draw, in the program that
    # drew it: `to_out_major`
    keys = iter(jax.random.split(rng, 24))
    depth = cfg.num_layers

    def norm(shape, key, std=cfg.init_std):
        return (jax.random.normal(key, shape, jnp.float32) * std).astype(cfg.dtype)

    def norm_scale(*shape):
        # unit-offset (gemma) norms store scale-1: zeros == identity
        fill = jnp.zeros if cfg.unit_offset_norm else jnp.ones
        return fill(shape, cfg.dtype)

    layers: Params = {
        "attn_norm": norm_scale(L, D),
        "mlp_norm": norm_scale(L, D),
    }
    if cfg.mla:
        qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
        r = cfg.kv_lora_rank
        if cfg.q_lora_rank:
            layers["wq_a"] = norm((L, D, cfg.q_lora_rank), next(keys))
            layers["q_a_norm"] = norm_scale(L, cfg.q_lora_rank)
            # out-major as wq is, [L, H, qk, q_rank] (`OUT_MAJOR`)
            layers["wq_b"] = to_out_major(
                norm((L, cfg.q_lora_rank, H, qk), next(keys)))
        else:
            layers["wq"] = to_out_major(norm((L, D, H, qk), next(keys)))
        layers["wkv_a"] = norm((L, D, r + cfg.qk_rope_head_dim),
                               next(keys))
        layers["kv_a_norm"] = norm_scale(L, r)
        layers["w_uk"] = norm((L, H, cfg.qk_nope_head_dim, r), next(keys))
        layers["w_uv"] = norm((L, H, r, cfg.v_head_dim), next(keys))
        layers["wo"] = norm((L, H, cfg.v_head_dim, D), next(keys),
                            std=cfg.init_std / (2 * depth) ** 0.5)
    else:
        layers.update({
            "wq": to_out_major(norm((L, D, H, Dh), next(keys))),
            "wk": to_out_major(norm((L, D, K, Dh), next(keys))),
            "wv": to_out_major(norm((L, D, K, Dh), next(keys))),
            "wo": norm((L, H, Dh, D), next(keys),
                       std=cfg.init_std / (2 * depth) ** 0.5),
        })
    if cfg.qk_norm:
        layers["q_norm"] = norm_scale(L, Dh)
        layers["k_norm"] = norm_scale(L, Dh)
    if cfg.attn_bias:
        layers["bq"] = jnp.zeros((L, H, Dh), cfg.dtype)
        layers["bk"] = jnp.zeros((L, K, Dh), cfg.dtype)
        layers["bv"] = jnp.zeros((L, K, Dh), cfg.dtype)
    if cfg.post_block_norms:
        layers["attn_post_norm"] = norm_scale(L, D)
        layers["mlp_post_norm"] = norm_scale(L, D)
    if moe:
        E, Fm = cfg.num_experts, cfg.moe_intermediate_size or F
        layers.update({
            "router": norm((L, D, cfg.router_width), next(keys)),
            "we_gate": norm((L, E, D, Fm), next(keys)),
            "we_up": norm((L, E, D, Fm), next(keys)),
            "we_down": norm((L, E, Fm, D), next(keys),
                            std=cfg.init_std / (2 * depth) ** 0.5),
        })
        if cfg.router_bias:
            # selection is among ALL experts, held here or not
            layers["router_bias"] = jnp.zeros((L, cfg.router_width),
                                              jnp.float32)
        if cfg.num_shared_experts > 0:
            Fs = Fm * cfg.num_shared_experts
            layers.update({
                "ws_gate": norm((L, D, Fs), next(keys)),
                "ws_up": norm((L, D, Fs), next(keys)),
                "ws_down": norm((L, Fs, D), next(keys),
                                std=cfg.init_std / (2 * depth) ** 0.5),
            })
    else:
        layers.update({
            "w_gate": norm((L, D, F), next(keys)),
            "w_up": norm((L, D, F), next(keys)),
            "w_down": norm((L, F, D), next(keys),
                           std=cfg.init_std / (2 * depth) ** 0.5),
        })
    # later additions draw after everything above, so the leaves of
    # the models that came first keep their values
    if moe and cfg.shared_expert_gate:
        layers["w_sg"] = norm((L, D, 1), next(keys))
    if linear:
        Hk, Hv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
        dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
        C, W = cfg.linear_conv_dim, cfg.linear_conv_kernel
        for name in ("wq", "wk", "wv", "wo", "q_norm", "k_norm"):
            layers.pop(name, None)
        layers.update({
            "w_qkv": norm((L, D, C), next(keys)),
            "w_z": norm((L, D, Hv * dv), next(keys)),
            "w_b": norm((L, D, Hv), next(keys)),
            "w_a": norm((L, D, Hv), next(keys)),
            # fan-in scaled: four taps of std 0.02 would shrink the
            # signal fifty-fold before the L2 and RMS norms
            "conv_w": norm((L, C, W), next(keys), std=W ** -0.5),
            "w_lin_out": norm((L, Hv * dv, D), next(keys),
                              std=cfg.init_std / (2 * depth) ** 0.5),
            "gdn_norm": jnp.ones((L, dv), cfg.dtype),
            # exp(g) = exp(-exp(A_log) * softplus(a + dt_bias)): with
            # A_log 0 and this dt_bias a head's decay at a = 0 is
            # uniform over (0.5, 0.999); the checkpoint's own init
            # (A ~ U(0, 16), dt_bias 1) forgets within a token
            "A_log": jnp.zeros((L, Hv), jnp.float32),
            "dt_bias": jnp.log(jnp.expm1(-jnp.log(
                0.5 + 0.499 * jax.random.uniform(
                    next(keys), (L, Hv), jnp.float32)))),
        })
    elif cfg.attn_output_gate:
        layers["w_ogate"] = to_out_major(norm((L, D, H, Dh), next(keys)))
    return layers


def init_params(rng: jax.Array, cfg: ModelConfig) -> Params:
    """Initialize parameters (normal init scaled like Llama pretraining).

    MoE models with first_k_dense (DeepSeek) get a separate
    "dense_layers" block for the leading dense-MLP layers.
    """
    D = cfg.hidden_size
    k_top, k_dense, k_moe = jax.random.split(rng, 3)
    keys = iter(jax.random.split(k_top, 4))

    def norm(shape, key, std=cfg.init_std):
        return (jax.random.normal(key, shape, jnp.float32) * std).astype(cfg.dtype)

    n_dense = cfg.first_k_dense if cfg.is_moe else 0
    params: Params = {
        "embed": norm((cfg.vocab_size, D), next(keys)),
        # a hybrid model: "layers" holds its full-attention layers
        # (every `full_attn_interval`-th), "linear_layers" the rest
        "layers": _init_layer_block(
            k_moe, cfg, (cfg.kv_cache_layers if cfg.is_hybrid
                         else cfg.num_layers) - n_dense, cfg.is_moe),
        "final_norm": (jnp.zeros if cfg.unit_offset_norm
                       else jnp.ones)((D,), cfg.dtype),
    }
    if n_dense:
        params["dense_layers"] = _init_layer_block(k_dense, cfg, n_dense,
                                                   moe=False)
    if cfg.is_hybrid:
        if n_dense or cfg.num_layers % cfg.full_attn_interval:
            raise ValueError(
                "a hybrid model needs whole periods of "
                f"{cfg.full_attn_interval} layers and no leading "
                "dense ones")
        params["linear_layers"] = _init_layer_block(
            k_dense, cfg, cfg.linear_layers, cfg.is_moe, linear=True)
    if not cfg.tie_word_embeddings:
        params["lm_head"] = norm((D, cfg.vocab_size), next(keys))
    return params


def param_count(params: Params) -> int:
    return sum(p.size for p in jax.tree.leaves(params))


# -- building blocks -------------------------------------------------------


def rms_norm(x: jax.Array, scale: jax.Array, eps: float,
             unit_offset: bool = False) -> jax.Array:
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    y = x.astype(jnp.float32) * lax.rsqrt(var + eps)
    w = scale.astype(jnp.float32)
    if unit_offset:  # gemma convention: weight stored as (scale - 1)
        w = 1.0 + w
    return (y * w).astype(x.dtype)


def layer_norm(x: jax.Array, scale: jax.Array,
               bias: Optional[jax.Array], eps: float) -> jax.Array:
    """Mean-centered LayerNorm in fp32. bias=None is the command-r
    (CohereLayerNorm) weight-only form; with bias it is torch
    LayerNorm (phimoe)."""
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mean), axis=-1, keepdims=True)
    y = (xf - mean) * lax.rsqrt(var + eps) * scale.astype(jnp.float32)
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return y.astype(x.dtype)


def block_norm(x: jax.Array, lp: Params, name: str,
               cfg: ModelConfig) -> jax.Array:
    """Per-block norm dispatched on cfg.norm_type; layernorm biases
    ride as `name`_bias leaves."""
    if cfg.norm_type == "rmsnorm":
        return rms_norm(x, lp[name], cfg.rms_norm_eps,
                        cfg.unit_offset_norm)
    bias = lp.get(name + "_bias") if cfg.norm_type == "layernorm" \
        else None
    return layer_norm(x, lp[name], bias, cfg.rms_norm_eps)


def _rope_frequencies(cfg: ModelConfig) -> jax.Array:
    # partial rotary (Qwen3-Next): frequencies for the leading share
    # of the head only; apply_rope passes the rest through
    half = int(cfg.head_dim * cfg.partial_rotary_factor) // 2
    freqs = 1.0 / cfg.rope_theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    sc = cfg.rope_scaling
    rtype = sc.get("rope_type", sc.get("type")) if sc else None
    if rtype not in (None, "default", "llama3", "yarn", "longrope",
                     "linear"):
        # silently unscaled frequencies serve wrong logits past the
        # original window — refuse instead (r5 review)
        raise ValueError(f"unsupported rope_scaling type {rtype!r}")
    if rtype == "yarn":
        # gpt-oss/qwen long-context; the cos/sin attention factor is
        # folded into query_scale at config parse (logits scale by
        # att^2 — equivalent, and the KV cache stays unscaled)
        from .mla import yarn_frequencies
        freqs, _ = yarn_frequencies(cfg, cfg.head_dim)
    elif rtype == "longrope":
        # phi3 family: per-dim extension factors; long list when the
        # deployed window exceeds the original training window
        orig = sc.get("original_max_position_embeddings",
                      cfg.max_seq_len)
        which = "long_factor" if cfg.max_seq_len > orig \
            else "short_factor"
        ext = jnp.asarray(sc[which], jnp.float32)
        freqs = freqs / ext
    elif rtype == "linear":
        freqs = freqs / sc.get("factor", 1.0)
    if rtype == "llama3":
        # Llama-3.1 NTK-by-parts frequency remapping
        factor = sc.get("factor", 8.0)
        lo = sc.get("low_freq_factor", 1.0)
        hi = sc.get("high_freq_factor", 4.0)
        orig = sc.get("original_max_position_embeddings", 8192)
        wavelen = 2 * jnp.pi / freqs
        ramp = (orig / wavelen - lo) / (hi - lo)
        ramp = jnp.clip(ramp, 0.0, 1.0)
        smoothed = freqs * (ramp + (1 - ramp) / factor)
        freqs = jnp.where(wavelen < orig / hi, freqs,          # high freq: keep
                          jnp.where(wavelen > orig / lo,
                                    freqs / factor,            # low freq: scale
                                    smoothed))                 # medium: blend
    return freqs


def apply_rope(x: jax.Array, positions: jax.Array, freqs: jax.Array,
               interleaved: bool = False) -> jax.Array:
    """RoPE. x: [B, S, N, Dh]. Default is rotate-half (HF Llama
    convention); `interleaved` pairs even/odd dims (command-r's
    repeat_interleave convention). Fewer frequencies than Dh / 2
    rotate the leading 2 * len(freqs) dims and leave the rest."""
    rot = 2 * freqs.shape[-1]
    if rot < x.shape[-1]:
        return jnp.concatenate(
            [apply_rope(x[..., :rot], positions, freqs, interleaved),
             x[..., rot:]], axis=-1)
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B, S, half]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    xf = x.astype(jnp.float32)
    if interleaved:
        x1, x2 = xf[..., ::2], xf[..., 1::2]
        out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                        axis=-1).reshape(x.shape)
    else:
        x1, x2 = jnp.split(xf, 2, axis=-1)
        out = jnp.concatenate(
            [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def _activate(gate: jax.Array, cfg: Optional[ModelConfig]) -> jax.Array:
    if cfg is not None and cfg.mlp_activation == "gelu_tanh":
        return jax.nn.gelu(gate, approximate=True)
    return jax.nn.silu(gate)


def _lora_delta(x: jax.Array, lp: Params, name: str,
                adapter_ids: Optional[jax.Array], flatten: int = 1):
    """Per-slot low-rank delta for the projection `name`.

    lp[name+"_lora_a"]: [n_slots, r, K], lp[..._b]: [n_slots, r, N] —
    per-layer slices of the engine's adapter stacks (scaling already
    folded into B; slot 0 is all-zero = base model). adapter_ids: [B].
    Returns [B, S, N] in x.dtype, or None when multi-LoRA is off.
    """
    a = lp.get(name + "_lora_a")
    if a is None or adapter_ids is None:
        return None
    b = lp.get(name + "_lora_b")
    import math
    B = x.shape[0]
    K = math.prod(x.shape[x.ndim - flatten:])
    x2 = x.reshape(B, -1, K)
    asel = jnp.take(a, adapter_ids, axis=0)          # [B, r, K]
    bsel = jnp.take(b, adapter_ids, axis=0)          # [B, r, N]
    h = jnp.einsum("bsk,brk->bsr", x2, asel.astype(x2.dtype))
    return jnp.einsum("bsr,brn->bsn", h, bsel.astype(x2.dtype))


def _proj_lora(x: jax.Array, lp: Params, name: str,
               adapter_ids: Optional[jax.Array], dtype,
               out_dims=None, flatten: int = 1, out_major: bool = False):
    """_proj + the slot's adapter delta (multi-LoRA serving; the
    factors are [r, K] / [r, N] however the base leaf lies)."""
    y = _proj(x, lp[name], dtype, flatten=flatten, out_major=out_major)
    d = _lora_delta(x, lp, name, adapter_ids, flatten=flatten)
    if d is not None:
        y = y + d.reshape(y.shape)
    if out_dims:
        y = y.reshape(*y.shape[:-1], *out_dims)
    return y


def dense_mlp(x: jax.Array, p: Params,
              cfg: Optional[ModelConfig] = None,
              adapter_ids: Optional[jax.Array] = None) -> jax.Array:
    dt = cfg.dtype if cfg else None
    gate = _proj_lora(x, p, "w_gate", adapter_ids, dt)
    up = _proj_lora(x, p, "w_up", adapter_ids, dt)
    return _proj_lora(_activate(gate, cfg) * up, p, "w_down",
                      adapter_ids, dt)


def _route(x: jax.Array, p: Params, cfg: ModelConfig):
    """Router: top-k expert ids + weights (fp32 routing).

    Three flavors (cfg.router_scoring):
      * "mixtral"    — softmax over the selected top-k logits
        (Mixtral/Qwen-MoE);
      * "softmax_v2" — full softmax scores, optional group-limited
        greedy selection (DeepseekV2TopkRouter);
      * "sigmoid_v3" — sigmoid scores, a selection-only correction
        bias, groups scored by their top-2 sum
        (DeepseekV3TopkRouter.get_topk_indices).
    """
    router_logits = jnp.einsum("bsd,de->bse", x,
                               p["router"]).astype(jnp.float32)
    k = cfg.experts_per_token
    if cfg.router_scoring == "mixtral":
        if cfg.moe_bias and "router_b" in p:
            # gpt_oss router: logits carry a bias BEFORE selection
            router_logits = router_logits + p["router_b"]
        weights, idx = lax.top_k(router_logits, k)
        return jax.nn.softmax(weights, axis=-1), idx  # [B,S,k] x2
    if cfg.router_scoring == "sparsemixer":
        return _route_sparsemixer(router_logits, cfg)
    if cfg.router_scoring == "sigmoid_v3":
        scores = jax.nn.sigmoid(router_logits)
        choice = scores + p["router_bias"] if "router_bias" in p \
            else scores
        def group_reduce(g):  # a group's merit: sum of its best two
            return jnp.sum(lax.top_k(g, 2)[0], axis=-1)
    else:  # softmax_v2
        scores = jax.nn.softmax(router_logits, axis=-1)
        choice = scores
        def group_reduce(g):
            return jnp.max(g, axis=-1)
    if cfg.n_group > 1 and 0 < cfg.topk_group < cfg.n_group:
        B, S, E = choice.shape
        g = choice.reshape(B, S, cfg.n_group, E // cfg.n_group)
        _, gidx = lax.top_k(group_reduce(g), cfg.topk_group)
        gmask = jnp.sum(jax.nn.one_hot(gidx, cfg.n_group,
                                       dtype=jnp.float32), axis=-2) > 0
        choice = jnp.where(
            jnp.repeat(gmask, E // cfg.n_group, axis=-1), choice, 0.0)
    _, idx = lax.top_k(choice, k)
    weights = jnp.take_along_axis(scores, idx, axis=-1)
    if cfg.norm_topk_prob:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True)
                             + 1e-20)
        if cfg.router_scoring == "softmax_v2":
            # HF DeepseekV2MoE applies routed_scaling_factor only in
            # the non-normalized branch; V3 (sigmoid) scales always
            return weights, idx
    return weights * cfg.routed_scaling_factor, idx


def _route_sparsemixer(scores: jax.Array, cfg: ModelConfig):
    """Phi-3.5-MoE inference-time sparsemixer (PhimoeSparseMoeBlock):
    top-1 twice with a jitter-eps sparsity mask; each multiplier is
    the pick's softmax weight over ITS masked logits (not normalized
    across the two picks)."""
    eps = cfg.router_jitter

    def pick(masked_from: jax.Array):
        # threshold mask uses the ORIGINAL scores in the numerator and
        # |scores| clamped to the candidate max as the denominator
        m = jnp.max(masked_from, axis=-1, keepdims=True)
        idx = jnp.argmax(masked_from, axis=-1)
        factor = jnp.maximum(jnp.abs(scores), m)
        drop = (m - scores) / factor > 2 * eps
        masked = jnp.where(drop, -jnp.inf, masked_from)
        gates = jax.nn.softmax(masked, axis=-1)
        w = jnp.take_along_axis(gates, idx[..., None], -1)[..., 0]
        return w, idx

    w1, i1 = pick(scores)
    masked_scores = jnp.where(
        jax.nn.one_hot(i1, scores.shape[-1], dtype=bool), -jnp.inf,
        scores)
    w2, i2 = pick(masked_scores)
    return (jnp.stack([w1, w2], axis=-1),
            jnp.stack([i1, i2], axis=-1).astype(jnp.int32))


def _moe_act(gate: jax.Array, up: jax.Array,
             cfg: ModelConfig) -> jax.Array:
    if cfg.moe_activation == "gptoss_glu":
        # GptOssExperts: clamped GLU — gate capped at +limit, up at
        # +-limit, glu = gate * sigmoid(1.702 * gate), out = (up+1)*glu
        gate = jnp.clip(gate, None, 7.0)
        up = jnp.clip(up, -7.0, 7.0)
        return (up + 1.0) * (gate * jax.nn.sigmoid(gate * 1.702))
    if cfg.moe_activation == "relu":
        return jax.nn.relu(gate) * up
    return _activate(gate, cfg) * up


def moe_mlp_dense(x: jax.Array, p: Params, cfg: ModelConfig,
                  routed: Optional["Routed"] = None) -> jax.Array:
    """Top-k MoE computing EVERY expert and mixing by router weight.

    O(E) FLOPs but fully static shapes and trivially GSPMD-shardable
    (experts on the tp/ep axis) — the training/pipeline path.
    `routed`: what `moe_decide` decided for these tokens (from another
    input than `x`, where the router reads the layer's); None routes
    from `x` here.
    """
    weights, idx = routed[:2] if routed is not None \
        else _route(x, p, cfg)
    gate = jnp.einsum("bsd,edf->bsef", x, _w(p, "we_gate", cfg.dtype))
    up = jnp.einsum("bsd,edf->bsef", x, _w(p, "we_up", cfg.dtype))
    if cfg.moe_bias:
        gate = gate + p["we_gate_b"]
        up = up + p["we_up_b"]
    h = _moe_act(gate, up, cfg)
    expert_out = jnp.einsum("bsef,efd->bsed", h,
                            _w(p, "we_down", cfg.dtype))  # [B,S,E,D]
    if cfg.moe_bias:
        # gpt_oss scales (out + down_bias) by the routing weight
        expert_out = expert_out + p["we_down_b"][None, None]
    onehot = jax.nn.one_hot(idx, cfg.num_experts, dtype=weights.dtype)  # [B,S,k,E]
    mix = jnp.einsum("bske,bsk->bse", onehot, weights)  # [B,S,E]
    return jnp.einsum("bsed,bse->bsd", expert_out,
                      mix.astype(expert_out.dtype))


class Dispatch(NamedTuple):
    """What the ragged dispatch DECIDES from the router's choice
    alone, before any expert's weights or any activation is touched:
    the T * k (token, expert) pairs sorted by expert, with the pairs
    of experts that are not held here behind every group, and where
    each pair went, by which its result is fetched back."""
    token_of: jax.Array     # [T*k] source token of each sorted pair
    counts: jax.Array       # [held] pairs of each held expert
    sorted_ids: jax.Array   # [T*k] held expert of each sorted pair
    place: jax.Array        # [k, T] sorted row of token t's j-th pair
    weights: jax.Array      # [k, T] the router's weight of each pair
    mine: jax.Array         # [k, T] the pair's expert is held here


class Routed(NamedTuple):
    """An expert layer's decision for a batch of tokens (`moe_decide`):
    `weights`, `idx` [B, S, k] from `_route`, and under the ragged
    dispatch its `Dispatch` (None under the dense one)."""
    weights: jax.Array
    idx: jax.Array
    plan: Optional[Dispatch] = None


def _held_experts(p: Params) -> int:
    """Experts `p["we_*"]` hold of ONE layer: all of a layer's leaf
    [E, ..], or of the layers' stacks [Ls, E, ..] that
    `p["expert_layer"]` indexes (`expert_compute`)."""
    return p["we_gate"].shape[0 if p.get("expert_layer") is None else 1]


def expert_dispatch(weights: jax.Array, idx: jax.Array, held: int,
                    lo=0) -> Dispatch:
    """Decide: sort the pairs `idx` [.., k] names (T tokens in all) by
    expert, for a device that holds experts lo .. lo + held - 1 of
    those the router chose among. A pair routed to an expert that is
    not held sorts behind every group, so it belongs to no group's
    rows. `place` is the inverse of that sort: the row at which a
    token's j-th pair's result will lie (`expert_compute` fetches it
    back from there)."""
    k = idx.shape[-1]
    ids = idx.reshape(-1) - lo
    mine = (ids >= 0) & (ids < held)
    local_ids = jnp.where(mine, ids, held)               # absent: last
    order = jnp.argsort(local_ids)                       # stable
    counts = jnp.bincount(local_ids, length=held + 1)[:held] \
        .astype(jnp.int32)
    return Dispatch(
        token_of=order // k,
        counts=counts,
        sorted_ids=jnp.minimum(jnp.take(local_ids, order), held - 1),
        place=jnp.argsort(order).reshape(-1, k).T,
        weights=weights.reshape(-1, k).T,
        mine=mine.reshape(-1, k).T)


def expert_compute(xf: jax.Array, plan: Dispatch, p: Params,
                   cfg: ModelConfig):
    """Compute: gather the sorted pairs' tokens from `xf` [T, D], run
    them as grouped matmuls over the experts held
    (lax.ragged_dot -> TPU grouped GEMM), fetch each token's k results
    back by `plan.place` and sum them, each times its weight, in
    float32 (a gather and a reduction: a TPU scatters rows at a small
    fraction of the rate at which it gathers them, and a scatter-add
    of the pairs into `[T, D]` was 29 % of a 16 384-token prefill at
    hidden 7680; ledger, PR 46). Returns (out [T, D], (held experts
    hit, pairs that landed here)).

    `p["expert_layer"]` (a traced layer index, set by a layer scan
    that keeps the experts out of its scanned leaves) says that
    `p["we_*"]` are the STACKS of every layer's experts, [Ls, E, ..]:
    they are then read as Ls * E groups of which only this layer's
    have rows. The grouped matmul reads the experts that have rows
    and nothing else; a layer's experts sliced out of the stack
    first would be copied whole, hit or not, every step (1.2 GB a
    layer at 128 experts of 2048 x 512, chip compiler, PR 27)."""
    layer = p.get("expert_layer")
    held = _held_experts(p)
    token_of, counts, sorted_ids, place, weights, mine = plan

    def experts(name):
        w = p[name]
        if layer is None:
            return _w(p, name, cfg.dtype)
        if isinstance(w, QTensor):
            # quantized stacks: dequantize this layer's slice only
            w = jax.tree.map(
                lambda a: lax.dynamic_index_in_dim(a, layer, 0, False), w)
            return w.dequant(cfg.dtype)
        return w.reshape(-1, *w.shape[2:])

    we_gate = experts("we_gate")
    # `token_of` (a permutation's entries over k) and `place` (a
    # permutation) are in bounds: neither gather fills or wraps
    xs = xf.at[token_of].get(mode="promise_in_bounds",
                             wrap_negative_indices=False)  # [T*k, D]
    group_sizes = counts
    if we_gate.shape[0] != held:
        # the whole stack: this layer's groups among empty ones
        group_sizes = lax.dynamic_update_slice(
            jnp.zeros((we_gate.shape[0],), jnp.int32), counts,
            (layer * held,))
    gate = lax.ragged_dot(xs, we_gate, group_sizes)
    up = lax.ragged_dot(xs, experts("we_up"), group_sizes)
    if cfg.moe_bias:
        gate = gate + jnp.take(p["we_gate_b"], sorted_ids, axis=0)
        up = up + jnp.take(p["we_up_b"], sorted_ids, axis=0)
    h = _moe_act(gate, up, cfg)  # same dtype flow as the dense path
    out_sorted = lax.ragged_dot(h, experts("we_down"),
                                group_sizes)             # [T*k, D]
    if cfg.moe_bias:
        out_sorted = out_sorted + jnp.take(p["we_down_b"], sorted_ids,
                                           axis=0)
    back = out_sorted.at[place].get(
        mode="promise_in_bounds", unique_indices=True,
        wrap_negative_indices=False)                     # [k, T, D]
    # rows behind the last group hold whatever the grouped matmul
    # leaves there: select, do not multiply by zero
    held_back = jnp.where(mine[..., None], back, 0)
    out = jnp.sum(held_back.astype(jnp.float32)
                  * weights[..., None].astype(jnp.float32), axis=0)
    out = out.astype(out_sorted.dtype)
    return out, (jnp.sum(counts > 0), jnp.sum(counts))


def ragged_experts(xf: jax.Array, weights: jax.Array, idx: jax.Array,
                   p: Params, cfg: ModelConfig, lo=0):
    """The routed experts' part of the result that the experts HELD
    HERE give: `p["we_*"]` hold experts lo .. lo + E_held - 1 of the
    `cfg.router_width` the router chose among (all of them on one
    device with every expert; a share under expert parallelism,
    parallel/moe.py, and in a cut configuration). Dropless ragged
    dispatch, decided (`expert_dispatch`) and computed
    (`expert_compute`) in one call.

    xf: [T, D]; weights, idx: [.., k] from `_route`, T tokens in
    all. Returns (out [T, D], (held experts hit, pairs that landed
    here))."""
    plan = expert_dispatch(weights, idx, _held_experts(p), lo)
    return expert_compute(xf, plan, p, cfg)


def moe_decide(x: jax.Array, p: Params, cfg: ModelConfig,
               ragged: Optional[bool] = None) -> Routed:
    """Everything an expert layer decides from the router's input `x`
    [B, S, D] alone: logits, top-k and weights (`_route`) and, under
    the ragged dispatch, the sort of the pairs by expert, the held
    range's mask and the group sizes (`expert_dispatch`). Nothing here
    reads an expert's weights or the activations the experts will be
    fed, so a model whose router reads the layer's input
    (`cfg.router_pre_attn`) decides ahead of its attention. `ragged`
    overrides `cfg.moe_impl` (`moe_mlp_ragged` called by name)."""
    if ragged is None:
        ragged = cfg.moe_impl == "ragged"
    with jax.named_scope("moe_router"):
        weights, idx = _route(x, p, cfg)
        plan = expert_dispatch(weights, idx, _held_experts(p),
                               lo=cfg.expert_offset) if ragged else None
    return Routed(weights, idx, plan)


def moe_mlp_ragged(x: jax.Array, p: Params, cfg: ModelConfig,
                   with_stats: bool = False,
                   routed: Optional[Routed] = None):
    """Dropless ragged dispatch over the experts held here: O(k/E) of
    the dense path's expert FLOPs with NO capacity dropping, static
    [T*k] shapes, so it jits cleanly. The sorts and the two gathers
    (tokens out to their pairs' rows, results back to their tokens)
    move T * k rows of the hidden size: tiny next to the expert
    weights in a decode step, which is weight-bound; NOT in a long
    prompt, where the pairs are 0.5 GB a layer and the rows must move
    by gathers: added back by a scatter-add they cost 24 ms a chunk
    of 4096 tokens at hidden 7680, twelve times the grouped matmul
    whose result they were, 29 % of a 16 384-token prefill (ledger,
    PR 46; `expert_compute`). Serving-path default (models/config.py
    moe_impl). `routed`: see `moe_mlp_dense`."""
    B, S, D = x.shape
    chunks = _moe_token_chunks(B * S, cfg.experts_per_token, D,
                               jnp.dtype(x.dtype).itemsize) \
        if routed is None else 1
    if chunks > 1:
        def one(xc):
            out, stats = moe_mlp_ragged(xc[None], p, cfg, with_stats=True)
            return out[0], jnp.stack(stats)
        out, stats = lax.map(one, x.reshape(chunks, -1, D))
        stats = tuple(jnp.sum(stats, axis=0))   # (a long prompt's: the
        # experts hit are counted a chunk; only decode steps are read)
        out = out.reshape(B, S, D)
        return (out, stats) if with_stats else out
    if routed is None:
        routed = moe_decide(x, p, cfg, ragged=True)
    with jax.named_scope("moe_experts"):
        out, stats = expert_compute(x.reshape(B * S, D), routed.plan,
                                    p, cfg)
    out = out.reshape(B, S, D).astype(x.dtype)
    return (out, stats) if with_stats else out


# the ragged dispatch gathers EVERY routed pair's token, T * k rows of
# the hidden size, held here or not (static shapes: all of them may
# land here), and its grouped matmuls hand back as many. Past
# `_MOE_PAIRS_LIMIT` bytes of them a layer runs its tokens in chunks
# of at most `_MOE_PAIRS_CHUNK`: at hidden 7680 and top-8 a 16 384-
# token prompt's pairs are 2 GB a copy and three copies live at once
# (chip compiler, PR 46: 4.5 GB of temporaries beside 12.4 GB held).
# Each chunk reads the experts it hits again, so chunks stay large.
_MOE_PAIRS_LIMIT = 1 << 30
_MOE_PAIRS_CHUNK = 1 << 29


def _moe_token_chunks(tokens: int, k: int, D: int, itemsize: int) -> int:
    """Chunks (a power of two that divides `tokens`) an expert layer
    runs its tokens in; 1 under `_MOE_PAIRS_LIMIT`."""
    pairs = tokens * k * D * itemsize
    n = 1
    if pairs > _MOE_PAIRS_LIMIT:
        while pairs // n > _MOE_PAIRS_CHUNK and tokens % (2 * n) == 0:
            n *= 2
    return n


def moe_mlp(x: jax.Array, p: Params, cfg: ModelConfig,
            with_stats: bool = False, routed: Optional[Routed] = None):
    """Top-k MoE block (Mixtral/Qwen-MoE/DeepSeek-style). With
    `with_stats` also (held experts hit, pairs landed here), which
    only the ragged dispatch counts. `routed`: `moe_decide`'s result
    where the router read another input than `x`; None decides from
    `x` here."""
    stats = None
    if cfg.moe_impl == "ragged":
        out, stats = moe_mlp_ragged(x, p, cfg, with_stats=True,
                                    routed=routed)
    elif cfg.num_experts_total not in (0, cfg.num_experts):
        raise ValueError("a share of the experts needs the ragged "
                         "dispatch (moe_impl='ragged')")
    else:
        out = moe_mlp_dense(x, p, cfg, routed)
    if cfg.num_shared_experts > 0:
        # DeepSeek-MoE shared experts: always-active dense branch
        with jax.named_scope("moe_shared"):
            shared = {"w_gate": p["ws_gate"], "w_up": p["ws_up"],
                      "w_down": p["ws_down"]}  # dense_mlp dequantizes via _w
            sh = dense_mlp(x, shared)
            if cfg.shared_expert_gate:
                # Qwen-MoE / Qwen3-Next: times sigmoid(h . w_sg)
                sg = jnp.einsum("bsd,do->bso", x, p["w_sg"])
                sh = sh * jax.nn.sigmoid(sg.astype(jnp.float32)) \
                    .astype(sh.dtype)
            out = out + sh
    return (out, stats) if with_stats else out


# -- forward ---------------------------------------------------------------


_WINDOW_FROM_CFG = object()  # sentinel: per-layer override unset


def _layer(x: jax.Array, lp: Params, cfg: ModelConfig, freqs: jax.Array,
           positions: jax.Array, kv_len: Optional[jax.Array],
           cache_kv: Optional[Tuple[jax.Array, jax.Array]],
           cache_index: Optional[jax.Array],
           window=_WINDOW_FROM_CFG, moe: Optional[bool] = None,
           adapter_ids: Optional[jax.Array] = None,
           use_rope: bool = True, moe_stats: bool = False,
           attn_scope: Optional[str] = None):
    """One transformer block. cache_kv: ([B,Smax,K,Dh], [B,Smax,K,Dh])
    (or merged rows, `KVCache`), or a `SlabLayer`: the whole stacked
    slabs a layer scan carries and the layer of them that is this
    block's (`_mha`, whose `attn_scope` is too; a latent model's
    `mla.mla_attention`).
    `window` overrides cfg.sliding_window (the gemma2 pair-scan passes
    the per-layer value; None = global attention). `moe` overrides
    cfg.is_moe (DeepSeek's first_k_dense leading dense layers).
    `adapter_ids` ([B]) selects each slot's LoRA delta (multi-adapter
    serving; None = no adapter stacks present). `moe_stats` adds
    the expert layer's counts (`moe_mlp`; None for a dense MLP) as a
    third result."""
    if window is _WINDOW_FROM_CFG:
        window = cfg.sliding_window
    uo = cfg.unit_offset_norm
    use_moe = cfg.is_moe if moe is None else moe
    routed = None
    if use_moe and cfg.router_pre_attn:
        # the router reads the layer's input: the routing and the
        # dispatch are decided here, ahead of `qkv`, and the experts
        # behind the attention take them as they are
        with jax.named_scope("mlp"):
            routed = moe_decide(x, lp, cfg)
    with jax.named_scope("qkv"):
        h = block_norm(x, lp, "attn_norm", cfg)
    if cfg.mla:
        from .mla import mla_attention  # (writes its own scopes)
        a, new_cache = mla_attention(h, lp, cfg, positions, kv_len,
                                     cache_kv, cache_index)
    else:
        a, new_cache = _mha(h, lp, cfg, freqs, positions, kv_len,
                            cache_kv, cache_index, window, uo,
                            adapter_ids, use_rope=use_rope,
                            attn_scope=attn_scope)
    if cfg.parallel_block:
        # command-r: attention and MLP both read the SAME normed
        # input and add into one residual (CohereDecoderLayer)
        with jax.named_scope("mlp"):
            mlp_out = moe_mlp(h, lp, cfg, routed=routed) if use_moe \
                else dense_mlp(h, lp, cfg, adapter_ids)
        x = x + a + mlp_out  # (a parallel block's experts go uncounted)
        return (x, new_cache, None) if moe_stats else (x, new_cache)
    if cfg.post_block_norms:
        with jax.named_scope("o_proj"):
            a = rms_norm(a, lp["attn_post_norm"], cfg.rms_norm_eps, uo)
    x = x + a
    stats = None
    with jax.named_scope("mlp"):
        h = block_norm(x, lp, "mlp_norm", cfg)
        if use_moe:
            mlp_out, stats = moe_mlp(h, lp, cfg, with_stats=True,
                                     routed=routed)
        else:
            mlp_out = dense_mlp(h, lp, cfg, adapter_ids)
        if cfg.post_block_norms:
            mlp_out = rms_norm(mlp_out, lp["mlp_post_norm"],
                               cfg.rms_norm_eps, uo)
    x = x + mlp_out
    return (x, new_cache, stats) if moe_stats else (x, new_cache)


def _moe_residual(x: jax.Array, lp: Params, cfg: ModelConfig):
    """x + MoE(norm(x)) with the expert layer's counts."""
    with jax.named_scope("mlp"):
        h = block_norm(x, lp, "mlp_norm", cfg)
        mlp_out, stats = moe_mlp(h, lp, cfg, with_stats=True)
    return x + mlp_out, stats


def _linear_layer(x: jax.Array, lp: Params, cfg: ModelConfig,
                  S: jax.Array, tail: jax.Array,
                  valid_len: Optional[jax.Array]):
    """One Gated DeltaNet block of a hybrid model (Qwen3-Next): the
    mixer (models/gdn.py holds the recurrence) and the MoE. S:
    [B, Hv, dk, dv] float32 and tail: [B, W-1, C] are what the
    sequence carries; positions from `valid_len` [B] on (None: none)
    leave both as they were. Returns (x, S, tail, moe counts)."""
    B, T, _ = x.shape
    Hk, Hv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
    dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
    valid = None if valid_len is None else \
        jnp.arange(T, dtype=jnp.int32)[None, :] < valid_len[:, None]
    with jax.named_scope("gdn_mixer"):
        with jax.named_scope("qkv"):
            h = block_norm(x, lp, "attn_norm", cfg)
            qkv = _proj(h, lp["w_qkv"], cfg.dtype)
            z = _proj(h, lp["w_z"], cfg.dtype, out_dims=(Hv, dv))
            b = _proj(h, lp["w_b"], cfg.dtype).astype(jnp.float32)
            a = _proj(h, lp["w_a"], cfg.dtype).astype(jnp.float32)
            y, tail = gdn.conv_seq(qkv, tail, lp["conv_w"], valid_len)
            y = jax.nn.silu(y)
            q, k, v = jnp.split(y, [Hk * dk, 2 * Hk * dk], axis=-1)
            q = gdn.l2norm(q.reshape(B, T, Hk, dk)) * dk ** -0.5
            k = gdn.l2norm(k.reshape(B, T, Hk, dk))
            # each key head serves Hv / Hk consecutive value heads
            q = jnp.repeat(q, Hv // Hk, axis=2)
            k = jnp.repeat(k, Hv // Hk, axis=2)
            v = v.reshape(B, T, Hv, dv)
            beta = jax.nn.sigmoid(b)
            g = -jnp.exp(lp["A_log"].astype(jnp.float32)) \
                * jax.nn.softplus(a + lp["dt_bias"].astype(jnp.float32))
        with jax.named_scope("attn"):
            if T == 1:
                o, S = gdn.step(q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                                beta[:, 0], S,
                                None if valid is None else valid[:, 0])
                o = o[:, None]
            else:
                o, S = gdn.chunked(q, k, v, g, beta, S, valid)
        with jax.named_scope("o_proj"):
            # per-head RMSNorm with a plain weight, times SiLU(z)
            o = rms_norm(o, lp["gdn_norm"], cfg.rms_norm_eps) \
                * jax.nn.silu(z.astype(jnp.float32))
            out = _proj(o.astype(cfg.dtype), lp["w_lin_out"], cfg.dtype,
                        flatten=2)
    x, stats = _moe_residual(x + out, lp, cfg)
    return x, S, tail, stats


def _hybrid_scan(params: Params, cfg: ModelConfig, x: jax.Array, freqs,
                 positions, kv_len, cache: Optional[KVCache],
                 adapter_ids: Optional[jax.Array],
                 valid_len: Optional[jax.Array]):
    """Scan over layer PERIODS of `cfg.full_attn_interval` (P): P - 1
    Gated DeltaNet layers, then one gated full-attention layer
    (Qwen3-Next). The two kinds have different leaves, so they are two
    stacked blocks (`linear_layers`, `layers`) and the body unrolls
    one period. KV rows exist for the full layers only; the DeltaNet
    layers carry `cache.rec` (zeros when there is no cache: every
    sequence then starts from an empty state).

    Both kinds of per-slot state are the scan's CARRY, as
    `_alt_window_scan`'s caches are: the full layers' slabs
    `cache.k / v` [G, B, Smax, K * Dh] whole, period g's layer handed
    a `SlabLayer(k, v, g)` that writes its rows in place and reads
    them by layer index (`_mha`), and the recurrent state, each layer
    updating its own rows. As scanned input and output either would
    be sliced out a period, stacked back and copied whole after the
    loop, every step. The layers' weights are scanned input (they are
    only read) and the experts' stacks closed over (`split`)."""
    L, P = cfg.num_layers, cfg.full_attn_interval
    G = L // P
    B = x.shape[0]
    EXPERTS = ("we_gate", "we_up", "we_down")
    ragged = cfg.moe_impl == "ragged"

    def split(block):
        """(leaves the scan slices a layer at a time, the experts'
        stacks whole): the grouped matmuls address the stack by layer
        (`ragged_experts`), so no layer's experts are ever sliced out
        and copied."""
        if not ragged:
            return block, {}
        return ({k: v for k, v in block.items() if k not in EXPERTS},
                {k: block[k] for k in EXPERTS})

    def group(a):
        return a.reshape(G, P - 1, *a.shape[1:])

    lin, lin_experts = split(params["linear_layers"])
    full, full_experts = split(params["layers"])
    rec = cache.rec if cache is not None else None
    if rec is None:
        rec = recurrent_state(cfg, B, cfg.dtype)
    index = cache.index if cache is not None else None

    def with_experts(lp, stacks, layer):
        return dict(lp, **stacks, expert_layer=layer) if stacks else lp

    def body(carry, per):
        x, rec, ck, cv = carry
        g, lin_g, full_g = per
        counts = jnp.zeros((3,), jnp.uint32)
        for j in range(P - 1):
            li = g * (P - 1) + j
            lp = with_experts(jax.tree.map(lambda a: a[j], lin_g),
                              lin_experts, li)
            x, S_j, tail_j, st = _linear_layer(
                x, lp, cfg,
                lax.dynamic_index_in_dim(rec["S"], li, 0, False),
                lax.dynamic_index_in_dim(rec["conv"], li, 0, False),
                valid_len)
            with jax.named_scope("kv_write"), \
                    jax.named_scope("gdn_state"):
                rec = {"S": lax.dynamic_update_index_in_dim(
                           rec["S"], S_j, li, 0),
                       "conv": lax.dynamic_update_index_in_dim(
                           rec["conv"], tail_j.astype(rec["conv"].dtype),
                           li, 0)}
            counts = counts + _moe_counts(st)
        slab = SlabLayer(ck, cv, g) if cache is not None else None
        x, nc, st = _layer(x, with_experts(full_g, full_experts, g), cfg,
                           freqs, positions, kv_len, slab, index,
                           adapter_ids=adapter_ids, moe_stats=True)
        if nc is not None:
            ck, cv = nc
        counts = counts + _moe_counts(st)
        return (x, rec, ck, cv), counts

    xs = (jnp.arange(G, dtype=jnp.int32), jax.tree.map(group, lin), full)
    carry = (x, rec) + ((cache.k, cache.v) if cache is not None
                        else (None, None))
    (x, rec, ck, cv), counts = lax.scan(body, carry, xs)
    if cache is None:
        return x, None
    S = positions.shape[1]
    stats = cache.stats
    if stats is not None:
        stats = stats + jnp.sum(counts, axis=0, dtype=jnp.uint32)
    return x, KVCache(k=ck, v=cv, index=cache.index + S,
                      rec=rec, stats=stats)


def counts_experts(cfg: ModelConfig) -> bool:
    """This model's layer scan adds to `KVCache.stats` what its expert
    layers are hit by: the ragged dispatch under a scan that carries
    the counters (`_hybrid_scan`, `_alt_window_scan`, `_latent_scan`).
    The engine keeps the counters for such a model."""
    return bool(cfg.is_moe and cfg.moe_impl == "ragged"
                and (cfg.is_hybrid or cfg.alt_sliding_window or cfg.mla))


def _latent_scan(params: Params, cfg: ModelConfig, x: jax.Array, freqs,
                 positions, kv_len, cache: Optional[KVCache],
                 adapter_ids: Optional[jax.Array] = None):
    """The layers of a latent-attention (MLA) model: the leading dense
    layers' block (`first_k_dense`), then the expert layers', each a
    scan over its stacked leaves.

    The latent slab `cache.k` [L, B, Smax, rank + rope] is both scans'
    CARRY, as `_hybrid_scan`'s and `_alt_window_scan`'s slabs are:
    layer i is handed `SlabLayer(k, v, i)`, writes its rows in place
    and the decode kernel reads the slab by layer index
    (models/mla.py). As scanned input and output the slab would be
    sliced out a layer, stacked back and copied whole after each
    loop, and the dense block's slab CONCATENATED onto the expert
    block's, every step. The zero-width v plane rides along untouched.
    The layers' weights are scanned input (they are only read) and a
    ragged model's experts closed over whole, addressed by layer
    (`expert_compute`)."""
    n_dense = cfg.first_k_dense if "dense_layers" in params else 0
    EXPERTS = ("we_gate", "we_up", "we_down")
    main, stacks = params["layers"], {}
    if cfg.is_moe and cfg.moe_impl == "ragged":
        stacks = {k: main[k] for k in EXPERTS}
        main = {k: v for k, v in main.items() if k not in EXPERTS}
    index = cache.index if cache is not None else None
    counting = cache is not None and cache.stats is not None

    def block(carry, leaves, first: int, moe):
        """Layers first .. first + n - 1, the n stacked in `leaves`."""
        def body(carry, per):
            x, ck, counts = carry
            lp, j = per
            if moe is not False and stacks:
                lp = dict(lp, **stacks, expert_layer=j)
            slab = SlabLayer(ck, cache.v, first + j) \
                if cache is not None else None
            x, nc, st = _layer(x, lp, cfg, freqs, positions, kv_len, slab,
                               index, moe=moe, adapter_ids=adapter_ids,
                               moe_stats=True)
            if nc is not None:
                ck = nc[0]
            if counting:
                counts = counts + _moe_counts(st)
            return (x, ck, counts), None

        n = jax.tree.leaves(leaves)[0].shape[0]
        carry, _ = lax.scan(body, carry,
                            (leaves, jnp.arange(n, dtype=jnp.int32)))
        return carry

    carry = (x, cache.k if cache is not None else None,
             jnp.zeros((3,), jnp.uint32) if counting else None)
    if n_dense:
        carry = block(carry, params["dense_layers"], 0, False)
    x, ck, counts = block(carry, main, n_dense, None)
    if cache is None:
        return x, None
    return x, KVCache(k=ck, v=cache.v,
                      index=cache.index + positions.shape[1],
                      stats=cache.stats + counts if counting else None)


def _moe_counts(stats) -> jax.Array:
    """[layer-steps, experts hit, pairs landed] of one expert layer."""
    if stats is None:
        return jnp.zeros((3,), jnp.uint32)
    return jnp.stack([jnp.ones((), jnp.uint32),
                      stats[0].astype(jnp.uint32),
                      stats[1].astype(jnp.uint32)])


def _qkv(h: jax.Array, lp: Params, cfg: ModelConfig, freqs: jax.Array,
         positions: jax.Array, uo: bool,
         adapter_ids: Optional[jax.Array] = None, rope: bool = True):
    """Projected + biased + normed + roped q/k/v — shared between the
    dense (_mha) and paged (forward_paged) attention paths.
    `rope=False` is cohere2's NoPE global layers."""
    q = _proj_lora(h, lp, "wq", adapter_ids, cfg.dtype,
                   out_dims=(cfg.num_heads, cfg.head_dim), out_major=True)
    k = _proj_lora(h, lp, "wk", adapter_ids, cfg.dtype,
                   out_dims=(cfg.num_kv_heads, cfg.head_dim),
                   out_major=True)
    v = _proj_lora(h, lp, "wv", adapter_ids, cfg.dtype,
                   out_dims=(cfg.num_kv_heads, cfg.head_dim),
                   out_major=True)
    if cfg.attn_bias:
        q = q + lp["bq"]
        k = k + lp["bk"]
        v = v + lp["bv"]
    if cfg.qk_norm:
        if cfg.norm_type == "layernorm_nobias":
            # command-r-plus: per-(head, dim) weighted LayerNorm
            q = layer_norm(q, lp["q_norm"], None, cfg.rms_norm_eps)
            k = layer_norm(k, lp["k_norm"], None, cfg.rms_norm_eps)
        else:
            q = rms_norm(q, lp["q_norm"], cfg.rms_norm_eps, uo)
            k = rms_norm(k, lp["k_norm"], cfg.rms_norm_eps, uo)
    if rope:
        q = apply_rope(q, positions, freqs, cfg.rope_interleaved)
        k = apply_rope(k, positions, freqs, cfg.rope_interleaved)
    return q, k, v


class SlabLayer(NamedTuple):
    """One layer's place in the stacked slabs that a layer scan
    carries (`_alt_window_scan`: the global layers' slabs and the
    window layers' rings; `_hybrid_scan`: the full-attention layers'
    slabs; `_latent_scan`: the latent rows), where `_layer`, `_mha`
    and `mla.mla_attention` otherwise take one layer's (k, v): the
    layer's rows are written in place and read through the index, and
    nothing slices a layer out (a slice of a carried slab is a copy of
    it, ops/paged.py)."""
    k: jax.Array                # [L, B, Smax, K * Dh] (or [.., K, Dh],
    v: jax.Array                # `KVCache`), all layers
    layer: Any                  # this block's: an int or a traced index
    # the slabs are the window layers' RINGS (`KVCache.wk / wv`,
    # `_ring_write`), not full-length rows
    ring: bool = False
    # [B] a ring's frozen slots: 0 keeps the slot's ring as it was (the
    # multi-token loop's `active`); for a prompt, its true length
    valid_len: Optional[jax.Array] = None


def _rows_as(rows: jax.Array, dtype, tail: Tuple[int, ...]) -> jax.Array:
    """Fresh rows [B, S, K, Dh] in a cache's dtype and row layout:
    `tail` is what follows the cache's row dimension, [K, Dh] or
    merged [K * Dh] (`KVCache`). A step's few rows are merged behind
    a barrier: without one the compiler folded the merge into the
    projection that made them, wanted that layer's `wk` / `wv`, then
    stored [hidden, K, Dh], as [hidden, K * Dh] and re-laid the
    weights out inside the layer scan, every layer of every step
    (chip compiler, PR 38), where a handful of rows cost nothing to
    re-lay. Since PR 41 the leaves lie [K, Dh, hidden] (`_proj`) and
    the text compiled without the barrier re-lays nothing either
    (chip compiler, PR 41: long-decode's `decode`); it stays until a
    chip run prices the programs without it (ROADMAP C15). A prompt's
    rows are more than the weights' and the compiler is left to
    choose."""
    rows = rows.astype(dtype)
    if rows.shape[2:] == tuple(tail):
        return rows
    if rows.shape[0] * rows.shape[1] < 2048:
        rows = lax.optimization_barrier(rows)
    return rows.reshape(rows.shape[:2] + tuple(tail))


def _write_rows(slab: jax.Array, rows: jax.Array, layer,
                index: jax.Array) -> jax.Array:
    """`rows` [B, S, K, Dh] (or as the slab's lie) into layer `layer`
    of the stacked slab [L, B, Smax, K, Dh] or [L, B, Smax, K * Dh],
    in place: from row `index` on (a scalar, the whole batch alike) or
    from `index[b]` on for batch row b."""
    rows = _rows_as(rows, slab.dtype, slab.shape[3:])
    if index.ndim == 0:
        return lax.dynamic_update_slice(
            slab, rows[None], (layer, 0, index) + (0,) * (slab.ndim - 3))
    B, S = rows.shape[:2]
    at = index[:, None] + jnp.arange(S, dtype=index.dtype)[None, :]
    return slab.at[layer, jnp.arange(B)[:, None], at].set(rows)


def _ring_rows_of(k: jax.Array, n: jax.Array, W: int, rows: int
                  ) -> jax.Array:
    """What a window layer's ring holds after a fresh prompt: of `k`
    [B, S, K, Dh] or [B, S, K * Dh], whose first `n[b]` positions are
    real, position p in row p % W for the last min(n, W) positions.
    Rows no position has reached hold position 0's: the slot's length
    hides them."""
    r = jnp.arange(rows, dtype=jnp.int32)[None, :]
    p = r + W * jnp.floor_divide(n[:, None] - 1 - r, W)
    p = jnp.maximum(p, 0)
    return jnp.take_along_axis(k, p.reshape(p.shape + (1,) * (k.ndim - 2)),
                               axis=1)


def _slab_write(k, v, cache_kv, index):
    """The fresh rows k, v [B, S, K, Dh] written into the cache from
    row `index` on (a scalar, or [B] per-slot positions): into one
    layer's (ck, cv), each [B, Smax, K, Dh] or merged [B, Smax,
    K * Dh], or into a `SlabLayer`'s layer of the whole stacked
    slabs, in place."""
    if isinstance(cache_kv, SlabLayer):
        return (_write_rows(cache_kv.k, k, cache_kv.layer, index),
                _write_rows(cache_kv.v, v, cache_kv.layer, index))
    ck, cv = cache_kv
    k = _rows_as(k, ck.dtype, ck.shape[2:])
    v = _rows_as(v, cv.dtype, cv.shape[2:])
    rest = (0,) * (ck.ndim - 2)
    if index.ndim == 1:
        # per-slot write positions (continuous batching): vmap the
        # update over the batch so each slot writes at its own length
        upd = jax.vmap(lambda c, u, i: lax.dynamic_update_slice(
            c, u, (i,) + rest))
        return upd(ck, k, index), upd(cv, v, index)
    return (lax.dynamic_update_slice(ck, k, (0, index) + rest),
            lax.dynamic_update_slice(cv, v, (0, index) + rest))


def _ring_write(k, v, slab: SlabLayer, index, window: int):
    """The fresh rows k, v [B, S, K, Dh] into `slab`'s layer of the
    window layers' rings [Lw, B, W, K, Dh] or [Lw, B, W, K * Dh], in
    place. S == 1: position `index[b]` to row index % W, unless the
    slot is frozen
    (`slab.valid_len` 0), whose ring stays as it was. S > 1 is a
    FRESH PROMPT only (nothing of the slot's ring is kept, whatever
    `index` says: a chunk on top of rows the ring holds cannot be
    had, `_mha`): the ring as its first `valid_len` positions leave
    it. The engine refuses the callers that would send such a chunk
    (a cached prefix, a verify step: core.SLOT_STATE_REFUSALS)."""
    B, S = k.shape[:2]
    W = slab.k.shape[2]
    valid_len = slab.valid_len
    if S > 1:
        n = jnp.broadcast_to(S if valid_len is None else valid_len, (B,))
        zero = jnp.zeros((), jnp.int32)
        return tuple(
            _write_rows(ring, _ring_rows_of(
                _rows_as(x, ring.dtype, ring.shape[3:]), n, window, W),
                slab.layer, zero)
            for ring, x in zip(slab[:2], (k, v)))
    row = jnp.broadcast_to(index, (B,)) % W
    out = []
    for ring, x in zip(slab[:2], (k, v)):
        x = _rows_as(x, ring.dtype, ring.shape[3:])
        if valid_len is not None:
            held = ring[slab.layer, jnp.arange(B), row][:, None]
            live = (valid_len > 0).reshape((B,) + (1,) * (x.ndim - 1))
            x = jnp.where(live, x, held)
        out.append(_write_rows(ring, x, slab.layer, row))
    return tuple(out)


def _mha(h: jax.Array, lp: Params, cfg: ModelConfig, freqs: jax.Array,
         positions: jax.Array, kv_len, cache_kv, cache_index, window,
         uo: bool, adapter_ids: Optional[jax.Array] = None,
         use_rope: bool = True, attn_scope: Optional[str] = None):
    """Standard multi-head (GQA) attention on the pre-normed input.

    `cache_kv` is one layer's (k, v), each [B, Smax, K, Dh] or merged
    [B, Smax, K * Dh] (`KVCache`), handed back updated; or a
    `SlabLayer`, the WHOLE stacked slabs of a layer scan that carries
    them and this layer's index, whose updated slabs are handed back.

    A `SlabLayer` of rings (`ring`): a decode step (S == 1) writes
    position p at row p % W and attends over the min(p + 1, W) rows
    the slot holds, with no window mask: keys are cached after
    rotary, and softmax does not care for their order. A slot whose
    `valid_len` is 0 (frozen in the multi-token loop) leaves its ring
    as it was. S > 1 is a fresh prompt: it attends inside itself
    under the window and leaves the ring as its first `valid_len`
    positions fill it (`_ring_rows_of`); a ring cannot take a chunk
    on top of rows it holds, because the chunk's first queries need
    rows its last keys overwrite."""
    with jax.named_scope("qkv"):
        q, k, v = _qkv(h, lp, cfg, freqs, positions, uo, adapter_ids,
                       rope=use_rope)
    slab = cache_kv if isinstance(cache_kv, SlabLayer) else None
    ring = slab is not None and slab.ring
    # a periodic window / global model names the layer's kind around
    # its cache write and attention (telemetry/scopes.py SUBPHASES)
    with jax.named_scope(attn_scope) if attn_scope \
            else contextlib.nullcontext():
        layer, new_cache = None, None
        if cache_kv is not None:
            with jax.named_scope("kv_write"):
                new_cache = _ring_write(k, v, slab, cache_index,
                                        cfg.sliding_window) if ring \
                    else _slab_write(k, v, cache_kv, cache_index)
            if not ring or k.shape[1] == 1:
                # attend over the cache (a ring's prompt attends
                # inside itself); stacked slabs are read by index
                k, v = new_cache
                layer = slab.layer if slab is not None else None
                if ring:    # the rows the slot holds, in any order
                    kv_len = jnp.minimum(kv_len, k.shape[2])
                    window = None
        with jax.named_scope("attn"):
            attn = attention(q, k, v, positions=positions,
                             kv_len=kv_len, sliding_window=window,
                             scale=cfg.query_scale,
                             logit_softcap=cfg.attn_logit_softcap,
                             sinks=lp.get("sinks") if cfg.attn_sinks
                             else None, layer=layer)
    with jax.named_scope("o_proj"):
        if cfg.attn_output_gate:
            # Qwen3-Next: a gate per head and dim, projected from the
            # same normed input as the query
            gate = _proj(h, lp["w_ogate"], cfg.dtype,
                         out_dims=(cfg.num_heads, cfg.head_dim),
                         out_major=True)
            attn = attn * jax.nn.sigmoid(gate.astype(jnp.float32)) \
                .astype(attn.dtype)
        a = _proj_lora(attn, lp, "wo", adapter_ids, cfg.dtype, flatten=2)
        if "bo" in lp:  # phimoe/gpt_oss: o_proj carries a bias too
            a = a + lp["bo"]
    return a, new_cache


def _embed(params: Params, cfg: ModelConfig,
           tokens: jax.Array) -> jax.Array:
    """Token embeddings in the compute dtype — shared by forward and
    forward_paged."""
    with jax.named_scope("embed"):
        emb = params["embed"]
        x = emb.take(tokens, cfg.dtype) if isinstance(emb, QTensor) \
            else jnp.take(emb, tokens, axis=0).astype(cfg.dtype)
        if cfg.embed_scale:  # gemma: normalizer in the compute dtype
            x = x * jnp.asarray(cfg.hidden_size ** 0.5, cfg.dtype)
    return x


def forward(params: Params, cfg: ModelConfig, tokens: jax.Array,
            positions: Optional[jax.Array] = None,
            cache: Optional[KVCache] = None,
            adapter_ids: Optional[jax.Array] = None,
            logits_at: Optional[jax.Array] = None,
            valid_len: Optional[jax.Array] = None,
            ) -> Tuple[jax.Array, Optional[KVCache]]:
    """Run the decoder.

    tokens: [B, S] int32. positions: [B, S] (defaults to arange).
    With `cache`, K/V are written at cache.index and attention spans the
    cache (serving decode/chunked prefill); without, plain causal prefill.
    `adapter_ids` ([B] int32) selects each row's LoRA adapter slot when
    the params carry multi-adapter factor stacks (engine/core.py).
    `logits_at` ([B] int32) asks for the logits of ONE row per
    sequence: the hidden state is cut to that row before the final
    norm and head (both act row by row, so the mathematics is the
    same) and logits come back [B, 1, vocab] — a serving prefill
    samples from one row, and [1, S, vocab] in f32 is gigabytes at a
    150k vocabulary.
    `valid_len` ([B] int32) says how many leading positions of each
    row are real (a right-padded prefill bucket; 0 for a frozen slot
    of the multi-token decode loop). KV rows hide a padded tail
    behind `kv_len`, and a prompt's attention (S > 1) is told so:
    its valid rows end at `cache.index + valid_len`, a query row past
    them is padding whose output is unspecified (`ops.attention`),
    and the prefill kernels do no work for blocks of such rows. A
    hybrid model's recurrent state cannot hide a tail, so its
    DeltaNet layers leave their state untouched from there on.
    Returns (logits [B, S, vocab], updated cache or None).
    """
    B, S = tokens.shape
    if positions is None:
        base = jnp.arange(S, dtype=jnp.int32)[None, :]
        if cache is not None:
            idx = cache.index
            base = base + (idx[:, None] if idx.ndim == 1 else idx)
        positions = jnp.broadcast_to(base, (B, S))
    x = _embed(params, cfg, tokens)
    freqs = _rope_frequencies(cfg)

    # a decode step (S == 1) keeps every row: a frozen slot's
    # `valid_len` 0 is the multi-token loop's, not a padded tail
    rows = S if valid_len is None or S == 1 else valid_len
    kv_len = jnp.broadcast_to(cache.index + rows, (B,)) \
        if cache is not None else None
    index = cache.index if cache is not None else None

    if cfg.is_hybrid:
        with jax.named_scope("layers"):
            x, new_cache = _hybrid_scan(params, cfg, x, freqs,
                                        positions, kv_len, cache,
                                        adapter_ids, valid_len)
    elif cfg.alt_sliding_window:
        with jax.named_scope("layers"):
            x, new_cache = _alt_window_scan(params, cfg, x, freqs,
                                            positions, kv_len, cache,
                                            adapter_ids, valid_len)
    elif cfg.mla:
        with jax.named_scope("layers"):
            x, new_cache = _latent_scan(params, cfg, x, freqs, positions,
                                        kv_len, cache, adapter_ids)
    else:
        if "dense_layers" in params:
            # (every architecture with leading dense layers is latent
            # or periodic; the block scan this branch had for them
            # concatenated the two blocks' slabs every step)
            raise ValueError(
                "leading dense layers (first_k_dense) are implemented "
                "for latent-attention and periodic window / global "
                "models only (_latent_scan, _alt_window_scan)")

        def body(x, per_layer):
            lp, layer_cache = per_layer
            x, nc = _layer(x, lp, cfg, freqs, positions, kv_len,
                           layer_cache, index, adapter_ids=adapter_ids)
            return x, nc

        carry_cache = (cache.k, cache.v) if cache is not None else None
        with jax.named_scope("layers"):
            x, nc = lax.scan(body, x, (params["layers"], carry_cache))
        new_cache = KVCache(k=nc[0], v=nc[1], index=cache.index + S) \
            if cache is not None else None

    if logits_at is not None:
        x = jnp.take_along_axis(x, logits_at[:, None, None], axis=1)
    return _final_logits(params, cfg, x), new_cache


def forward_paged(params: Params, cfg: ModelConfig, tokens: jax.Array,
                  cache: PagedKVCache,
                  adapter_ids: Optional[jax.Array] = None,
                  ) -> Tuple[jax.Array, PagedKVCache]:
    """Short-sequence decode over a paged (block-pool) KV cache.

    tokens: [B, S] with small S — 1 for plain decode, k+1 for a
    speculative verify step (engine/core.py). Each slot writes its S
    new K/V rows into pool blocks `table[b, (index[b]+s) // block]`
    at offsets `(index[b]+s) % block` (the engine pre-allocates the
    covering blocks), then attends over its block chain with
    per-query causal masking (ops/paged.py).

    The pool `[L, N, bs, K, D]` (and an int8 pool's scale planes) is
    the layer scan's CARRY, never its xs/ys: the scan runs over
    (stacked layers, arange(L)), layer `l` scatters its B x S rows in
    place at `(l, blk, off)` and the attention entries read layer `l`
    of the whole pool through the index. As xs/ys the scan would
    slice a layer's pool out, write it back into the stacked ys and
    copy the pool after the loop: four passes over it a step to write
    B x S rows a layer, and a temporary of its size beside the donated
    pool. One body for every caller (S == 1 and S > 1, bf16 and int8
    pools, with and without adapters).

    Standard GQA models only — MLA, MoE, sliding-window variants and
    hybrid models (whose DeltaNet layers carry recurrent state, not
    rows) keep the dense path (the engine guards). cite: vLLM
    PagedAttention, which the reference consumes via its SGLang/vLLM
    runtimes (SURVEY.md L0, /root/reference/config/runtimes/srt/*);
    here it is in-repo and TPU-static.
    """
    from ..ops.paged import paged_attention, paged_attention_multi
    B, S = tokens.shape
    L, _, bs = cache.k.shape[:3]
    M = cache.table.shape[1]
    positions = cache.index[:, None] + jnp.arange(S,
                                                  dtype=jnp.int32)[None, :]
    kv_len = cache.index + 1
    x = _embed(params, cfg, tokens)
    freqs = _rope_frequencies(cfg)
    uo = cfg.unit_offset_norm
    rows = jnp.arange(B)
    # clamp keeps a finished slot whose length outgrew its table row
    # in-bounds; its row points at the trash block by then
    blk = cache.table[rows[:, None],
                      jnp.minimum(positions // bs, M - 1)]  # [B, S]
    off = positions % bs
    quantized = cache.k_scale is not None

    def _append(pool, scale_pool, rows_new, layer):
        """Scatter the B x S fresh [K, D] rows into layer `layer` of
        the pool, in place; int8 pools quantize per (row, head) on the
        way in (amax/127 symmetric, the ops/flash.py quantize_kv_block
        discipline) and store the f32 scale at the same (layer, block,
        offset). A slot's S writes land on consecutive rows (distinct
        (block, offset) pairs), so the scatter's order doesn't matter;
        trash-block collisions between inactive slots are never read
        back."""
        if quantized:
            amax = jnp.max(jnp.abs(rows_new.astype(jnp.float32)),
                           axis=-1)                        # [B, S, K]
            sc = jnp.maximum(amax, 1e-8) / 127.0
            rows_new = jnp.clip(
                jnp.round(rows_new.astype(jnp.float32)
                          / sc[..., None]),
                -127, 127)
            scale_pool = scale_pool.at[layer, blk, :, off].set(sc)
        pool = pool.at[layer, blk, off].set(rows_new.astype(pool.dtype))
        return pool, scale_pool

    def body(carry, per):
        x, kp, vp, ksp, vsp = carry
        lp, layer = per
        with jax.named_scope("qkv"):
            h = rms_norm(x, lp["attn_norm"], cfg.rms_norm_eps, uo)
            q, k, v = _qkv(h, lp, cfg, freqs, positions, uo, adapter_ids)
        with jax.named_scope("kv_write"):
            kp, ksp = _append(kp, ksp, k, layer)
            vp, vsp = _append(vp, vsp, v, layer)
        with jax.named_scope("attn"):
            if S == 1:
                attn = paged_attention(
                    q, kp, vp, cache.table, kv_len, layer,
                    scale=cfg.query_scale,
                    logit_softcap=cfg.attn_logit_softcap,
                    k_scale=ksp, v_scale=vsp)
            else:
                attn = paged_attention_multi(
                    q, kp, vp, cache.table, positions, layer,
                    scale=cfg.query_scale,
                    logit_softcap=cfg.attn_logit_softcap,
                    k_scale=ksp, v_scale=vsp)
        with jax.named_scope("o_proj"):
            a = _proj_lora(attn, lp, "wo", adapter_ids, cfg.dtype,
                           flatten=2)
            if cfg.post_block_norms:
                a = rms_norm(a, lp["attn_post_norm"], cfg.rms_norm_eps,
                             uo)
        x = x + a
        with jax.named_scope("mlp"):
            h = rms_norm(x, lp["mlp_norm"], cfg.rms_norm_eps, uo)
            mlp_out = dense_mlp(h, lp, cfg, adapter_ids)
            if cfg.post_block_norms:
                mlp_out = rms_norm(mlp_out, lp["mlp_post_norm"],
                                   cfg.rms_norm_eps, uo)
        return (x + mlp_out, kp, vp, ksp, vsp), None

    with jax.named_scope("layers"):
        # a None scale plane (bf16 pool) is no leaf of the carry
        (x, nk, nv, nks, nvs), _ = lax.scan(
            body, (x, cache.k, cache.v, cache.k_scale, cache.v_scale),
            (params["layers"], jnp.arange(L, dtype=jnp.int32)))
    new_cache = PagedKVCache(k=nk, v=nv, index=cache.index + S,
                             table=cache.table,
                             k_scale=nks, v_scale=nvs)
    return _final_logits(params, cfg, x), new_cache


@scoped("lm_head")
def _final_logits(params: Params, cfg: ModelConfig,
                  x: jax.Array) -> jax.Array:
    """Final norm + LM head — shared by forward and forward_paged."""
    x = block_norm(x, params, "final_norm", cfg)
    head = params.get("lm_head")
    if head is None:
        head = params["embed"]
        head = head.dequant(cfg.dtype).T if isinstance(head, QTensor) \
            else head.T
    elif isinstance(head, QTensor):
        head = head.dequant(cfg.dtype)
    logits = jnp.einsum("bsd,dv->bsv", x, head,
                        preferred_element_type=jnp.float32)
    if "lm_head_bias" in params:
        logits = logits + params["lm_head_bias"]
    if cfg.logit_scale is not None:
        logits = logits * cfg.logit_scale
    if cfg.final_logit_softcap:
        logits = jnp.tanh(logits / cfg.final_logit_softcap) \
            * cfg.final_logit_softcap
    return logits


def _alt_window_scan(params: Params, cfg: ModelConfig, x: jax.Array,
                     freqs, positions, kv_len, cache: Optional[KVCache],
                     adapter_ids: Optional[jax.Array] = None,
                     valid_len: Optional[jax.Array] = None):
    """The layers of a periodic window / global model, in periods of
    `cfg.sliding_pattern` (P): one layer of a period is global, the
    one at `cfg.global_phase` in it, and the others use the sliding
    window. gemma2/gpt-oss: P=2, the global layer last;
    command-r7b/command-a (cohere2) and afmoe: P=4, the global layer
    last, and the global layers additionally skip RoPE
    (cfg.rope_skip_global); smallthinker: P=4, the global layer FIRST,
    rotary-free too. The period's body is unrolled, so every variant
    stays static: one compiled body, no dynamic masks.

    Leading dense layers (`first_k_dense`; the first period counts
    them) make the periods they lie in a head that runs unrolled
    ahead of the scan; layers past the last whole period a tail
    behind it. A layer's place in its cache follows from the phase
    (`cfg.globals_before`): a global layer's among the global layers'
    slabs, a window layer's among the rings.

    Both caches are the scan's CARRY, written in place (`_mha`): the
    global layers' full-length slabs `cache.k / v` [Lg, B, S, K * Dh]
    and the window layers' rings `cache.wk / wv` [Lw, B, W, K * Dh]
    (a slab engine's merged rows; [.., K, Dh] from a creator that
    asked for none, `KVCache`). A model whose configuration keeps no
    ring (`cfg.window_layers` 0: gemma2, cohere2, gpt-oss) has every
    layer's full-length rows in `cache.k / v` [L, B, S, ..], and a
    window layer reads its own under the window mask.
    As scanned input and output every step would slice each layer's
    slab out, stack it back and copy the whole after the loop. The
    weights are closed over whole and read by layer index for the
    same reason; a share of the experts keeps its stacks out of the
    slices altogether (`ragged_experts`)."""
    L, P = cfg.num_layers, cfg.sliding_pattern
    n_dense = cfg.first_k_dense if "dense_layers" in params else 0
    ringed = cfg.window_layers > 0
    head = min(-(-n_dense // P) * P, L)
    G = (L - head) // P
    EXPERTS = ("we_gate", "we_up", "we_down")
    main = params["layers"]
    stacks = {}
    if cfg.is_moe and cfg.moe_impl == "ragged":
        stacks = {k: main[k] for k in EXPERTS}
        main = {k: v for k, v in main.items() if k not in EXPERTS}
    index = cache.index if cache is not None else None
    counting = cache is not None and cache.stats is not None
    S = positions.shape[1]

    def leaves_of(i, dense):
        """Layer i's leaves: of the dense block, or of the expert
        layers' with the experts' stacks whole beside a layer index."""
        block, at = (params["dense_layers"], i) if dense \
            else (main, i - n_dense)
        lp = jax.tree.map(
            lambda a: a[at] if isinstance(at, int)
            else lax.dynamic_index_in_dim(a, at, 0, False), block)
        if stacks and not dense:
            lp = dict(lp, **stacks, expert_layer=at)
        return lp

    def layer(carry, i, j):
        x, gk, gv, wk, wv, counts = carry
        is_global = cfg.is_global_layer(j)
        in_ring = ringed and not is_global
        dense = isinstance(i, int) and i < n_dense
        slab = None
        if cache is not None and in_ring:
            slab = SlabLayer(wk, wv, i - cfg.globals_before(i), True,
                             valid_len)
        elif cache is not None:
            slab = SlabLayer(gk, gv,
                             cfg.globals_before(i) if ringed else i)
        x, nc, st = _layer(
            x, leaves_of(i, dense), cfg, freqs, positions, kv_len,
            slab, index, window=None if is_global else cfg.sliding_window,
            moe=False if dense else None, adapter_ids=adapter_ids,
            use_rope=not (is_global and cfg.rope_skip_global),
            moe_stats=True,
            attn_scope="attn_global" if is_global else "attn_window")
        if nc is not None and in_ring:
            wk, wv = nc
        elif nc is not None:
            gk, gv = nc
        if counting:
            counts = counts + _moe_counts(st)
        return x, gk, gv, wk, wv, counts

    carry = (x,) + ((cache.k, cache.v, cache.wk, cache.wv)
                    if cache is not None else (None,) * 4) \
        + (jnp.zeros((3,), jnp.uint32) if counting else None,)
    for i in range(head):
        carry = layer(carry, i, i % P)
    if G:
        def body(carry, g):
            for j in range(P):
                carry = layer(carry, head + g * P + j, j)
            return carry, None
        carry, _ = lax.scan(body, carry, jnp.arange(G, dtype=jnp.int32))
    for i in range(head + G * P, L):
        carry = layer(carry, i, i % P)
    x, gk, gv, wk, wv, counts = carry
    if cache is None:
        return x, None
    return x, KVCache(k=gk, v=gv, index=cache.index + S,
                      stats=cache.stats + counts if counting else None,
                      wk=wk, wv=wv)


def loss_fn(params: Params, cfg: ModelConfig, tokens: jax.Array,
            targets: jax.Array, mask: Optional[jax.Array] = None) -> jax.Array:
    """Next-token cross-entropy (fp32 logits), for the training step."""
    logits, _ = forward(params, cfg, tokens)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    if mask is not None:
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1)
    return jnp.mean(nll)
