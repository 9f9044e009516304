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

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..ops.attention import attention
from ..telemetry.scopes import scoped
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


def _proj(x: jax.Array, w, dtype, out_dims=None, flatten: int = 1):
    """Contract x's trailing `flatten` dims with weight `w`.

    int4 QTensor leaves route through the fused Pallas kernel
    (ops/int4_matmul.py) so the nibble unpack happens in VMEM and HBM
    streams packed bytes; everything else (bf16, int8, unsupported
    shapes, non-TPU) takes the dequant + einsum path, which XLA fuses
    for int8. Callers must only pass weights whose dims up to and
    including the pack axis are contraction dims (wq/wk/wv/wo,
    w_gate/w_up — not expert-stacked or per-head-factored leaves).
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
        y = jnp.einsum("...k,kn->...n", x2, wd.reshape(K, -1))
    if out_dims:
        y = y.reshape(*y.shape[:-1], *out_dims)
    return y


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class KVCache:
    """Fixed-capacity per-layer KV cache.

    k, v: [L, B, S_max, K, Dh]; index: next-write position — scalar
    int32 (shared by the whole batch: training-style chunked prefill)
    or [B] int32 (per-slot write positions: the serving engine's
    continuous-batching decode, where every slot is at a different
    sequence length).
    """

    k: jax.Array
    v: jax.Array
    index: jax.Array

    @classmethod
    def create(cls, cfg: ModelConfig, batch: int, max_seq: Optional[int] = None,
               dtype=None) -> "KVCache":
        S = max_seq or cfg.max_seq_len
        dtype = dtype or cfg.dtype
        # MLA caches one latent "head" of kv_lora_rank+rope dims and a
        # zero-width v plane (models/mla.py); dense models cache K/V
        K, Dk, Dv = (cfg.kv_cache_heads, cfg.kv_cache_k_dim,
                     cfg.kv_cache_v_dim)
        L = cfg.num_layers
        return cls(k=jnp.zeros((L, batch, S, K, Dk), dtype),
                   v=jnp.zeros((L, batch, S, K, Dv), dtype),
                   index=jnp.zeros((), jnp.int32))


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
                      moe: bool) -> Params:
    """One stacked block of L structurally-identical layers."""
    D, H, K, Dh, F = (cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads,
                      cfg.head_dim, cfg.intermediate_size)
    keys = iter(jax.random.split(rng, 24))
    depth = cfg.num_layers

    def norm(shape, key, std=0.02):
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
            layers["wq_b"] = norm((L, cfg.q_lora_rank, H, qk), next(keys))
        else:
            layers["wq"] = norm((L, D, H, qk), next(keys))
        layers["wkv_a"] = norm((L, D, r + cfg.qk_rope_head_dim),
                               next(keys))
        layers["kv_a_norm"] = norm_scale(L, r)
        layers["w_uk"] = norm((L, H, cfg.qk_nope_head_dim, r), next(keys))
        layers["w_uv"] = norm((L, H, r, cfg.v_head_dim), next(keys))
        layers["wo"] = norm((L, H, cfg.v_head_dim, D), next(keys),
                            std=0.02 / (2 * depth) ** 0.5)
    else:
        layers.update({
            "wq": norm((L, D, H, Dh), next(keys)),
            "wk": norm((L, D, K, Dh), next(keys)),
            "wv": norm((L, D, K, Dh), next(keys)),
            "wo": norm((L, H, Dh, D), next(keys),
                       std=0.02 / (2 * depth) ** 0.5),
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
            "router": norm((L, D, E), next(keys)),
            "we_gate": norm((L, E, D, Fm), next(keys)),
            "we_up": norm((L, E, D, Fm), next(keys)),
            "we_down": norm((L, E, Fm, D), next(keys),
                            std=0.02 / (2 * depth) ** 0.5),
        })
        if cfg.router_bias:
            layers["router_bias"] = jnp.zeros((L, E), jnp.float32)
        if cfg.num_shared_experts > 0:
            Fs = Fm * cfg.num_shared_experts
            layers.update({
                "ws_gate": norm((L, D, Fs), next(keys)),
                "ws_up": norm((L, D, Fs), next(keys)),
                "ws_down": norm((L, Fs, D), next(keys),
                                std=0.02 / (2 * depth) ** 0.5),
            })
    else:
        layers.update({
            "w_gate": norm((L, D, F), next(keys)),
            "w_up": norm((L, D, F), next(keys)),
            "w_down": norm((L, F, D), next(keys),
                           std=0.02 / (2 * depth) ** 0.5),
        })
    return layers


def init_params(rng: jax.Array, cfg: ModelConfig) -> Params:
    """Initialize parameters (normal init scaled like Llama pretraining).

    MoE models with first_k_dense (DeepSeek) get a separate
    "dense_layers" block for the leading dense-MLP layers.
    """
    D = cfg.hidden_size
    k_top, k_dense, k_moe = jax.random.split(rng, 3)
    keys = iter(jax.random.split(k_top, 4))

    def norm(shape, key, std=0.02):
        return (jax.random.normal(key, shape, jnp.float32) * std).astype(cfg.dtype)

    n_dense = cfg.first_k_dense if cfg.is_moe else 0
    params: Params = {
        "embed": norm((cfg.vocab_size, D), next(keys)),
        "layers": _init_layer_block(k_moe, cfg, cfg.num_layers - n_dense,
                                    cfg.is_moe),
        "final_norm": (jnp.zeros if cfg.unit_offset_norm
                       else jnp.ones)((D,), cfg.dtype),
    }
    if n_dense:
        params["dense_layers"] = _init_layer_block(k_dense, cfg, n_dense,
                                                   moe=False)
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
    half = cfg.head_dim // 2
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
    repeat_interleave convention)."""
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
               out_dims=None, flatten: int = 1):
    """_proj + the slot's adapter delta (multi-LoRA serving)."""
    y = _proj(x, lp[name], dtype, flatten=flatten)
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
    return _activate(gate, cfg) * up


def moe_mlp_dense(x: jax.Array, p: Params, cfg: ModelConfig) -> jax.Array:
    """Top-k MoE computing EVERY expert and mixing by router weight.

    O(E) FLOPs but fully static shapes and trivially GSPMD-shardable
    (experts on the tp/ep axis) — the training/pipeline path.
    """
    weights, idx = _route(x, p, cfg)
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


def moe_mlp_ragged(x: jax.Array, p: Params, cfg: ModelConfig) -> jax.Array:
    """Dropless ragged dispatch: sort token-expert pairs by expert and
    run grouped matmuls (lax.ragged_dot -> TPU grouped GEMM).

    O(k/E) of the dense path's expert FLOPs with NO capacity dropping —
    static [T*k] shapes, so it jits cleanly. The sort/gather/scatter
    costs bandwidth proportional to activations (tiny next to expert
    weights), which is the right trade on TPU where the MoE block is
    weight-bound. Serving-path default (models/config.py moe_impl).
    """
    B, S, D = x.shape
    k, E = cfg.experts_per_token, cfg.num_experts
    T = B * S
    weights, idx = _route(x, p, cfg)
    xf = x.reshape(T, D)
    expert_ids = idx.reshape(T * k)
    order = jnp.argsort(expert_ids)                      # stable
    token_of = order // k                                # source token
    xs = jnp.take(xf, token_of, axis=0)                  # [T*k, D]
    group_sizes = jnp.bincount(expert_ids, length=E).astype(jnp.int32)
    gate = lax.ragged_dot(xs, _w(p, "we_gate", cfg.dtype), group_sizes)
    up = lax.ragged_dot(xs, _w(p, "we_up", cfg.dtype), group_sizes)
    if cfg.moe_bias:
        gate = gate + jnp.take(p["we_gate_b"], expert_ids[order],
                               axis=0)
        up = up + jnp.take(p["we_up_b"], expert_ids[order], axis=0)
    h = _moe_act(gate, up, cfg)  # same dtype flow as the dense path
    out_sorted = lax.ragged_dot(h, _w(p, "we_down", cfg.dtype), group_sizes)  # [T*k, D]
    if cfg.moe_bias:
        out_sorted = out_sorted + jnp.take(p["we_down_b"],
                                           expert_ids[order], axis=0)
    w_sorted = jnp.take(weights.reshape(T * k), order, axis=0)
    contrib = out_sorted * w_sorted[:, None].astype(out_sorted.dtype)
    out = jnp.zeros((T, D), contrib.dtype).at[token_of].add(contrib)
    return out.reshape(B, S, D).astype(x.dtype)


def moe_mlp(x: jax.Array, p: Params, cfg: ModelConfig) -> jax.Array:
    """Top-k MoE block (Mixtral/Qwen-MoE/DeepSeek-style)."""
    if cfg.moe_impl == "ragged":
        out = moe_mlp_ragged(x, p, cfg)
    else:
        out = moe_mlp_dense(x, p, cfg)
    if cfg.num_shared_experts > 0:
        # DeepSeek-MoE shared experts: always-active dense branch
        shared = {"w_gate": p["ws_gate"], "w_up": p["ws_up"],
                  "w_down": p["ws_down"]}  # dense_mlp dequantizes via _w
        out = out + dense_mlp(x, shared)
    return out


# -- forward ---------------------------------------------------------------


_WINDOW_FROM_CFG = object()  # sentinel: per-layer override unset


def _layer(x: jax.Array, lp: Params, cfg: ModelConfig, freqs: jax.Array,
           positions: jax.Array, kv_len: Optional[jax.Array],
           cache_kv: Optional[Tuple[jax.Array, jax.Array]],
           cache_index: Optional[jax.Array],
           window=_WINDOW_FROM_CFG, moe: Optional[bool] = None,
           adapter_ids: Optional[jax.Array] = None,
           use_rope: bool = True):
    """One transformer block. cache_kv: ([B,Smax,K,Dh], [B,Smax,K,Dh]).
    `window` overrides cfg.sliding_window (the gemma2 pair-scan passes
    the per-layer value; None = global attention). `moe` overrides
    cfg.is_moe (DeepSeek's first_k_dense leading dense layers).
    `adapter_ids` ([B]) selects each slot's LoRA delta (multi-adapter
    serving; None = no adapter stacks present)."""
    if window is _WINDOW_FROM_CFG:
        window = cfg.sliding_window
    uo = cfg.unit_offset_norm
    with jax.named_scope("qkv"):
        h = block_norm(x, lp, "attn_norm", cfg)
    if cfg.mla:
        from .mla import mla_attention
        with jax.named_scope("attn"):
            a, new_cache = mla_attention(h, lp, cfg, positions, kv_len,
                                         cache_kv, cache_index)
    else:
        a, new_cache = _mha(h, lp, cfg, freqs, positions, kv_len,
                            cache_kv, cache_index, window, uo,
                            adapter_ids, use_rope=use_rope)
    use_moe = cfg.is_moe if moe is None else moe
    if cfg.parallel_block:
        # command-r: attention and MLP both read the SAME normed
        # input and add into one residual (CohereDecoderLayer)
        with jax.named_scope("mlp"):
            mlp_out = moe_mlp(h, lp, cfg) if use_moe \
                else dense_mlp(h, lp, cfg, adapter_ids)
        return x + a + mlp_out, new_cache
    if cfg.post_block_norms:
        with jax.named_scope("o_proj"):
            a = rms_norm(a, lp["attn_post_norm"], cfg.rms_norm_eps, uo)
    x = x + a

    with jax.named_scope("mlp"):
        h = block_norm(x, lp, "mlp_norm", cfg)
        mlp_out = moe_mlp(h, lp, cfg) if use_moe \
            else dense_mlp(h, lp, cfg, adapter_ids)
        if cfg.post_block_norms:
            mlp_out = rms_norm(mlp_out, lp["mlp_post_norm"],
                               cfg.rms_norm_eps, uo)
    return x + mlp_out, new_cache


def _qkv(h: jax.Array, lp: Params, cfg: ModelConfig, freqs: jax.Array,
         positions: jax.Array, uo: bool,
         adapter_ids: Optional[jax.Array] = None, rope: bool = True):
    """Projected + biased + normed + roped q/k/v — shared between the
    dense (_mha) and paged (forward_paged) attention paths.
    `rope=False` is cohere2's NoPE global layers."""
    q = _proj_lora(h, lp, "wq", adapter_ids, cfg.dtype,
                   out_dims=(cfg.num_heads, cfg.head_dim))
    k = _proj_lora(h, lp, "wk", adapter_ids, cfg.dtype,
                   out_dims=(cfg.num_kv_heads, cfg.head_dim))
    v = _proj_lora(h, lp, "wv", adapter_ids, cfg.dtype,
                   out_dims=(cfg.num_kv_heads, cfg.head_dim))
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


def _mha(h: jax.Array, lp: Params, cfg: ModelConfig, freqs: jax.Array,
         positions: jax.Array, kv_len, cache_kv, cache_index, window,
         uo: bool, adapter_ids: Optional[jax.Array] = None,
         use_rope: bool = True):
    """Standard multi-head (GQA) attention on the pre-normed input."""
    with jax.named_scope("qkv"):
        q, k, v = _qkv(h, lp, cfg, freqs, positions, uo, adapter_ids,
                       rope=use_rope)

    if cache_kv is not None:
        ck, cv = cache_kv
        with jax.named_scope("kv_write"):
            if cache_index.ndim == 1:
                # per-slot write positions (continuous batching): vmap
                # the update over the batch so each slot writes at its
                # own length
                upd = jax.vmap(
                    lambda c, u, i: lax.dynamic_update_slice(
                        c, u.astype(c.dtype), (i, 0, 0)))
                ck = upd(ck, k, cache_index)
                cv = upd(cv, v, cache_index)
            else:
                ck = lax.dynamic_update_slice(ck, k.astype(ck.dtype),
                                              (0, cache_index, 0, 0))
                cv = lax.dynamic_update_slice(cv, v.astype(cv.dtype),
                                              (0, cache_index, 0, 0))
        k_full, v_full = ck, cv
        new_cache = (ck, cv)
    else:
        k_full, v_full = k, v
        new_cache = None

    with jax.named_scope("attn"):
        attn = attention(q, k_full, v_full, positions=positions,
                         kv_len=kv_len, sliding_window=window,
                         scale=cfg.query_scale,
                         logit_softcap=cfg.attn_logit_softcap,
                         sinks=lp.get("sinks") if cfg.attn_sinks else None)
    with jax.named_scope("o_proj"):
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

    kv_len = jnp.broadcast_to(cache.index + S, (B,)) \
        if cache is not None else None
    index = cache.index if cache is not None else None

    if cfg.alt_sliding_window:
        with jax.named_scope("layers"):
            x, new_cache = _alt_window_scan(params, cfg, x, freqs,
                                            positions, kv_len, cache,
                                            adapter_ids)
    else:
        # DeepSeek first_k_dense: leading dense-MLP layers scan as
        # their own block; the cache's layer dim covers both blocks
        n_dense = cfg.first_k_dense if "dense_layers" in params else 0

        def scan_block(x, block, ck, cv, moe):
            def body(x, per_layer):
                lp, layer_cache = per_layer
                x, nc = _layer(x, lp, cfg, freqs, positions, kv_len,
                               layer_cache, index, moe=moe,
                               adapter_ids=adapter_ids)
                return x, nc

            carry_cache = (ck, cv) if cache is not None else None
            with jax.named_scope("layers"):
                x, nc = lax.scan(body, x, (block, carry_cache))
            return x, nc

        if cache is not None:
            dk, dv = cache.k[:n_dense], cache.v[:n_dense]
            mk, mv = cache.k[n_dense:], cache.v[n_dense:]
        else:
            dk = dv = mk = mv = None
        if n_dense:
            x, dnc = scan_block(x, params["dense_layers"], dk, dv,
                                moe=False)
        x, mnc = scan_block(x, params["layers"], mk, mv, moe=None)
        if cache is not None:
            nk, nv = mnc
            if n_dense:
                nk = jnp.concatenate([dnc[0], nk], axis=0)
                nv = jnp.concatenate([dnc[1], nv], axis=0)
            new_cache = KVCache(k=nk, v=nv, index=cache.index + S)
        else:
            new_cache = None

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
    per-query causal masking (ops/paged.py). Standard GQA models
    only — MLA, MoE, and sliding-window variants keep the dense path
    (the engine guards). cite: vLLM PagedAttention, which the
    reference consumes via its SGLang/vLLM runtimes (SURVEY.md L0,
    /root/reference/config/runtimes/srt/*); here it is in-repo and
    TPU-static.
    """
    from ..ops.paged import paged_attention, paged_attention_multi
    B, S = tokens.shape
    bs = cache.k.shape[2]
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

    def _append(pool, scale_pool, rows_new):
        """Write S fresh [B, K, D] rows into the pool; int8 pools
        quantize per (row, head) on the way in (amax/127 symmetric,
        the ops/flash.py quantize_kv_block discipline) and store the
        f32 scale at the same (block, offset). The S writes per slot
        land on consecutive rows (distinct (block, offset) pairs), so
        the unrolled scatter order doesn't matter; trash-block
        collisions between inactive slots are never read back."""
        if quantized:
            amax = jnp.max(jnp.abs(rows_new.astype(jnp.float32)),
                           axis=-1)                        # [B, S, K]
            sc = jnp.maximum(amax, 1e-8) / 127.0
            rows_new = jnp.clip(
                jnp.round(rows_new.astype(jnp.float32)
                          / sc[..., None]),
                -127, 127).astype(jnp.int8)
        for s in range(S):
            pool = pool.at[blk[:, s], off[:, s]].set(
                rows_new[:, s].astype(pool.dtype))
            if quantized:
                scale_pool = scale_pool.at[blk[:, s], :,
                                           off[:, s]].set(sc[:, s])
        return pool, scale_pool

    def body(x, per):
        if quantized:
            lp, kp, vp, ksp, vsp = per
        else:
            lp, kp, vp = per
            ksp = vsp = None
        with jax.named_scope("qkv"):
            h = rms_norm(x, lp["attn_norm"], cfg.rms_norm_eps, uo)
            q, k, v = _qkv(h, lp, cfg, freqs, positions, uo, adapter_ids)
        with jax.named_scope("kv_write"):
            kp, ksp = _append(kp, ksp, k)
            vp, vsp = _append(vp, vsp, v)
        with jax.named_scope("attn"):
            if S == 1:
                attn = paged_attention(
                    q, kp, vp, cache.table, kv_len,
                    scale=cfg.query_scale,
                    logit_softcap=cfg.attn_logit_softcap,
                    k_scale=ksp, v_scale=vsp)
            else:
                attn = paged_attention_multi(
                    q, kp, vp, cache.table, positions,
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
        out = (x + mlp_out, ((kp, vp, ksp, vsp) if quantized
                             else (kp, vp)))
        return out

    with jax.named_scope("layers"):
        if quantized:
            x, (nk, nv, nks, nvs) = lax.scan(
                body, x, (params["layers"], cache.k, cache.v,
                          cache.k_scale, cache.v_scale))
        else:
            x, (nk, nv) = lax.scan(body, x,
                                   (params["layers"], cache.k, cache.v))
            nks = nvs = None
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
                     adapter_ids: Optional[jax.Array] = None):
    """Scan over layer GROUPS of `cfg.sliding_pattern` (P): layers
    with (i+1) % P != 0 use the sliding window, every P-th layer is
    global. gemma2/gpt-oss: P=2; command-r7b/command-a (cohere2):
    P=4, and the global layers additionally skip RoPE
    (cfg.rope_skip_global — Cohere2Attention applies rotary only on
    sliding layers). The unrolled group body keeps every variant
    static — one compiled body, no dynamic masks."""
    L, P = cfg.num_layers, cfg.sliding_pattern
    assert L % P == 0, \
        f"alternating sliding window needs depth % {P} == 0"

    def group(a):
        return a.reshape(L // P, P, *a.shape[1:])

    layers_g = jax.tree.map(group, params["layers"])
    index = cache.index if cache is not None else None

    def body(x, per):
        lp_g, c_g = per
        nks, nvs = [], []
        for j in range(P):
            lp = jax.tree.map(lambda a: a[j], lp_g)
            cj = (c_g[0][j], c_g[1][j]) if c_g is not None else None
            is_global = (j + 1) % P == 0
            x, nc = _layer(
                x, lp, cfg, freqs, positions, kv_len, cj, index,
                window=None if is_global else cfg.sliding_window,
                adapter_ids=adapter_ids,
                use_rope=not (is_global and cfg.rope_skip_global))
            if nc is not None:
                nks.append(nc[0])
                nvs.append(nc[1])
        if not nks:
            return x, None
        return x, (jnp.stack(nks), jnp.stack(nvs))

    if cache is not None:
        x, (nk, nv) = lax.scan(
            body, x, (layers_g, (group(cache.k), group(cache.v))))
        S = positions.shape[1]
        new_cache = KVCache(k=nk.reshape(cache.k.shape),
                            v=nv.reshape(cache.v.shape),
                            index=cache.index + S)
    else:
        x, _ = lax.scan(body, x, (layers_g, None))
        new_cache = None
    return x, new_cache


def loss_fn(params: Params, cfg: ModelConfig, tokens: jax.Array,
            targets: jax.Array, mask: Optional[jax.Array] = None) -> jax.Array:
    """Next-token cross-entropy (fp32 logits), for the training step."""
    logits, _ = forward(params, cfg, tokens)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    if mask is not None:
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1)
    return jnp.mean(nll)
