"""Model configuration for the JAX data plane.

The reference operator parses HF config.json into metadata
(pkg/hfutil/modelconfig) and delegates math to SGLang/vLLM; here the data
plane is in-repo, so the same parsed config drives real JAX models.
Covers the Llama family superset: GQA, RoPE scaling, tied embeddings,
MoE (Mixtral/Qwen-MoE/DeepSeek-style) and sliding-window knobs.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Dict, Optional

import jax.numpy as jnp


@dataclass(frozen=True)
class ModelConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 8
    head_dim: int = 128
    intermediate_size: int = 14336
    rope_theta: float = 500000.0
    rope_scaling: Optional[Dict[str, Any]] = None
    rms_norm_eps: float = 1e-5
    max_seq_len: int = 8192
    tie_word_embeddings: bool = False
    dtype: Any = jnp.bfloat16
    init_std: float = 0.02  # `initializer_range`: seeded matrices' deviation
    # MoE (0 experts -> dense MLP)
    num_experts: int = 0
    experts_per_token: int = 0
    moe_intermediate_size: int = 0
    num_shared_experts: int = 0
    # "dense" computes every expert (GSPMD-shardable everywhere);
    # "ragged" sorts tokens by expert and runs grouped matmuls
    # (lax.ragged_dot) — O(k/E) of the dense FLOPs, the serving path
    moe_impl: str = "dense"
    # MLA (DeepSeek-V2/V3, Kimi-K2): compressed-KV attention — the KV
    # cache stores per-token latents [kv_lora_rank + qk_rope_head_dim]
    # instead of per-head K/V (models/mla.py)
    mla: bool = False
    q_lora_rank: Optional[int] = None
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    first_k_dense: int = 0     # leading dense layers before MoE blocks
    # routing flavor: "mixtral" (softmax over the selected top-k),
    # "softmax_v2" (full softmax, optional group-limited greedy),
    # "sigmoid_v3" (sigmoid + selection bias + top-2-sum group scores)
    router_scoring: str = "mixtral"
    n_group: int = 0
    topk_group: int = 0
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = False
    router_bias: bool = False  # e_score_correction_bias tensor present
    # attention extras
    sliding_window: Optional[int] = None
    attn_logit_softcap: Optional[float] = None
    qk_norm: bool = False
    attn_bias: bool = False  # qwen2-style q/k/v projection biases
    # gemma2-family block shape (models/llama.py pair-scan path)
    mlp_activation: str = "silu"      # "silu" | "gelu_tanh"
    alt_sliding_window: bool = False  # periodic sliding/global layers
    sliding_pattern: int = 2          # period P: one global layer in P
    # the period's global layer: layer i is global iff i % P ==
    # global_phase % P. -1, the period's LAST layer (gemma2, cohere2,
    # gpt-oss, afmoe); 0 a period that OPENS with its global layer
    global_phase: int = -1
    rope_skip_global: bool = False    # cohere2: global layers are NoPE
    # the window layers keep a RING of `sliding_window` KV rows a slot
    # and not every row (docs/window-cache.md). A ring cannot give back
    # rows it has overwritten, so a prefix cache, speculative decoding,
    # PD, the journal and --tp are refused with it (engine/core.py
    # SLOT_STATE_REFUSALS): set by the architectures that were first
    # served with one (afmoe, smallthinker), not by those served with
    # full-length rows and these features before it (gemma2, cohere2,
    # gpt-oss)
    window_ring: bool = False
    query_scale: Optional[float] = None  # overrides head_dim**-0.5
    post_block_norms: bool = False    # post-attn/post-mlp RMSNorms
    embed_scale: bool = False         # x *= sqrt(hidden) after embed
    unit_offset_norm: bool = False    # RMSNorm scales by (1 + w)
    final_logit_softcap: Optional[float] = None
    # round-5 architecture breadth (r4 verdict #5)
    # "rmsnorm" | "layernorm" (torch LayerNorm, affine+bias: phimoe) |
    # "layernorm_nobias" (mean-centered, weight-only: command-r)
    norm_type: str = "rmsnorm"
    parallel_block: bool = False   # command-r: x + attn(n(x)) + mlp(n(x))
    logit_scale: Optional[float] = None  # command-r final-logit mult
    rope_interleaved: bool = False  # command-r even/odd pair rotation
    attn_sinks: bool = False       # gpt_oss per-head learned sink logit
    lm_head_bias: bool = False     # phimoe
    router_jitter: float = 0.0     # phimoe sparsemixer threshold eps
    # "silu" | "gptoss_glu" (clamped) | "relu" (relu(gate) * up)
    moe_activation: str = "silu"
    moe_bias: bool = False         # gpt_oss expert + router biases
    # hybrid linear/full attention (Qwen3-Next; models/gdn.py): layer
    # i is gated full attention iff (i+1) % full_attn_interval == 0,
    # every other layer a Gated DeltaNet mixer whose per-sequence
    # state is a [Hv, dk, dv] float32 matrix and a conv tail, not KV
    # rows. 0 = every layer is full attention
    full_attn_interval: int = 0
    linear_num_key_heads: int = 0
    linear_num_value_heads: int = 0
    linear_key_head_dim: int = 0
    linear_value_head_dim: int = 0
    linear_conv_kernel: int = 0
    partial_rotary_factor: float = 1.0  # leading share of head_dim roped
    attn_output_gate: bool = False  # attention out * sigmoid(gate proj)
    shared_expert_gate: bool = False  # shared expert * sigmoid(h . w)
    # an expert-parallel share: the router stays `num_experts_total`
    # wide, the `num_experts` experts from `expert_offset` on are held
    # here and only pairs routed to them are computed; 0 = all held
    num_experts_total: int = 0
    expert_offset: int = 0
    # the router reads the block's INPUT (the residual stream as the
    # layer receives it, before the attention's norm), not the normed
    # activations behind attention: everything that follows from the
    # router's input alone is decided ahead of `qkv` (models/llama.py
    # `moe_decide`)
    router_pre_attn: bool = False

    @property
    def is_moe(self) -> bool:
        return self.num_experts > 0

    @property
    def is_hybrid(self) -> bool:
        return self.full_attn_interval > 1

    @property
    def router_width(self) -> int:
        return self.num_experts_total or self.num_experts

    def globals_before(self, i):
        """Global layers among layers 0 .. i-1 of a periodic window /
        global model (i an integer or a traced index): a global
        layer's place in the global layers' slab, and i less it a
        window layer's place in the rings."""
        P = self.sliding_pattern
        return (i + (P - 1 - self.global_phase % P)) // P

    def is_global_layer(self, i: int) -> bool:
        return i % self.sliding_pattern \
            == self.global_phase % self.sliding_pattern

    @property
    def window_layers(self) -> int:
        """Layers of a periodic window / global model that keep a
        ring of `sliding_window` KV rows a slot, not every row
        (`window_ring`; models/llama.py `_alt_window_scan`,
        docs/window-cache.md). 0 for every other model, one whose
        window layers keep full-length rows among them."""
        if not (self.window_ring and self.alt_sliding_window
                and self.sliding_window):
            return 0
        return self.num_layers - self.globals_before(self.num_layers)

    @property
    def kv_cache_layers(self) -> int:
        """Layers that own full-length KV rows: a hybrid model's
        linear layers carry recurrent state instead, a window layer a
        ring (engine/core.py DecodeState)."""
        if self.is_hybrid:
            return self.num_layers // self.full_attn_interval
        return self.num_layers - self.window_layers

    @property
    def linear_layers(self) -> int:
        return self.num_layers - self.kv_cache_layers

    @property
    def attn_layer_windows(self) -> tuple:
        """((window, layers), ...): the layers whose attention goes
        through ops.attention (not MLA's own, not a hybrid model's
        linear layers) by the sliding window they mask with, None for
        a global layer (models/llama.py `forward`'s three scans)."""
        if self.mla:
            return ()
        if self.is_hybrid:
            return ((self.sliding_window, self.kv_cache_layers),)
        if self.alt_sliding_window:
            n_global = self.globals_before(self.num_layers)
            return ((self.sliding_window, self.num_layers - n_global),
                    (None, n_global))
        return ((self.sliding_window, self.num_layers),)

    @property
    def linear_conv_dim(self) -> int:
        """Channels of the DeltaNet's depthwise conv: q | k | v."""
        return (2 * self.linear_num_key_heads * self.linear_key_head_dim
                + self.linear_num_value_heads * self.linear_value_head_dim)

    # KV-cache geometry (engine + KVCache.create): MLA caches ONE
    # latent "head" of kv_lora_rank+rope dims and no separate V rows
    @property
    def kv_cache_heads(self) -> int:
        return 1 if self.mla else self.num_kv_heads

    @property
    def kv_cache_k_dim(self) -> int:
        """Lanes of a cached row's k part. A latent row `[c | k_pe]`
        wider than one tile of 128 lanes is PADDED to whole tiles (576
        -> 640): the chip lays an array whose minor dimension is no
        multiple of 128 with its rows minor instead, and every decode
        step then copies the slab into the row-major order the kernel
        reads (chip compiler, PR 46: 2.5 GB a step at 24 x 16 384
        rows; row-major tiles pad to 640 in HBM anyway). The padding
        lanes are zero and read by nothing (models/mla.py)."""
        if self.mla:
            w = self.kv_lora_rank + self.qk_rope_head_dim
            return -(-w // 128) * 128 if w > 128 else w
        return self.head_dim

    @property
    def kv_cache_v_dim(self) -> int:
        return 0 if self.mla else self.head_dim

    @property
    def mla_scale(self) -> float:
        """qk_head_dim**-0.5, yarn-mscale-corrected when rope_scaling
        carries mscale_all_dim (DeepseekV3Attention.__init__)."""
        s = (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5
        rs = self.rope_scaling or {}
        mscale_all = rs.get("mscale_all_dim", 0)
        if mscale_all:
            factor = rs.get("factor", 1.0)
            if factor > 1.0:
                import math
                m = 0.1 * mscale_all * math.log(factor) + 1.0
                s *= m * m
        return s

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)

    @classmethod
    def from_hf_config(cls, cfg: Dict[str, Any]) -> "ModelConfig":
        """Build from a HuggingFace config.json dict (llama/qwen2/qwen3/
        mistral/mixtral families — the set models/checkpoint.py
        SUPPORTED_ARCHITECTURES accepts)."""
        hidden = cfg.get("hidden_size", 4096)
        heads = cfg.get("num_attention_heads", 32)
        archs = cfg.get("architectures") or [""]
        arch = archs[0]
        sc_raw = cfg.get("rope_scaling")
        if sc_raw and sc_raw.get("rope_type",
                                 sc_raw.get("type")) == "su":
            # normalize early Phi-3's original spelling ONCE so every
            # downstream reader (_rope_frequencies, the attention
            # factor, mla) sees the canonical name
            cfg = dict(cfg, rope_scaling=dict(sc_raw,
                                              rope_type="longrope"))
        deepseek = arch.startswith("Deepseek")
        pangu = arch == "PanguUltraMoEForCausalLM"
        mla_kw = {}
        if pangu:
            mla_kw = _pangu_ultra_fields(cfg)
        elif deepseek:
            # DeepSeek-V2/V3 family (Kimi-K2 ships the V3 architecture):
            # MLA attention + first-k-dense MoE + its routing flavor
            v3 = arch.startswith("DeepseekV3")
            mla_kw = dict(
                mla=True,
                q_lora_rank=cfg.get("q_lora_rank"),
                kv_lora_rank=cfg.get("kv_lora_rank", 512),
                qk_nope_head_dim=cfg.get("qk_nope_head_dim", 128),
                qk_rope_head_dim=cfg.get("qk_rope_head_dim", 64),
                v_head_dim=cfg.get("v_head_dim", 128),
                router_scoring="sigmoid_v3" if v3 else "softmax_v2",
                n_group=cfg.get("n_group", 0) or 0,
                topk_group=cfg.get("topk_group", 0) or 0,
                routed_scaling_factor=cfg.get("routed_scaling_factor",
                                              1.0),
                norm_topk_prob=bool(cfg.get("norm_topk_prob", v3)),
                router_bias=v3,
            )
            if not v3 and cfg.get("topk_method") == "greedy":
                mla_kw["n_group"] = 0  # V2-lite: plain greedy top-k
        # qwen2 uses qkv biases (not spelled out in its config.json);
        # qwen3 replaces them with per-head q/k RMS norms
        attn_bias = cfg.get("attention_bias",
                            cfg.get("qkv_bias", arch.startswith("Qwen2")))
        gemma2 = arch == "Gemma2ForCausalLM"
        qscale = None
        if gemma2 and cfg.get("query_pre_attn_scalar"):
            qscale = cfg["query_pre_attn_scalar"] ** -0.5
        # yarn/longrope multiply cos AND sin by an attention factor;
        # q and k both scale, so logits scale by att^2 — fold it into
        # the query scale (KV cache stays unscaled). MLA models apply
        # their own mscale (models/mla.py) and skip this.
        if not (deepseek or pangu):
            att = _rope_attention_factor(
                cfg.get("rope_scaling"),
                cfg.get("max_position_embeddings", 8192))
            if att != 1.0:
                head_dim = cfg.get("head_dim") or hidden // heads
                qscale = (qscale if qscale is not None
                          else head_dim ** -0.5) * att * att
        extra = {}
        if arch in ("PhimoeForCausalLM", "PhiMoEForCausalLM"):
            # the official Phi-3.5-MoE repo ships the capital-E
            # spelling; the transformers class uses Phimoe
            # Phi-3.5-MoE: torch LayerNorm (bias) everywhere, optional
            # lm_head bias, sparsemixer top-2 routing
            # (cite ref: pkg/hfutil/modelconfig parses phimoe configs)
            extra = dict(norm_type="layernorm",
                         lm_head_bias=bool(cfg.get("lm_head_bias")),
                         router_scoring="sparsemixer",
                         router_jitter=cfg.get("router_jitter_noise",
                                               0.01) or 0.0)
        elif arch == "Cohere2ForCausalLM":
            # command-r7b / command-a: the cohere parallel block plus
            # a period-4 sliding pattern whose global layers skip RoPE
            # (cite ref: pkg/hfutil/modelconfig parses cohere2)
            extra = dict(norm_type="layernorm_nobias",
                         parallel_block=True,
                         logit_scale=cfg.get("logit_scale", 1.0),
                         rope_interleaved=True,
                         rms_norm_eps=cfg.get("layer_norm_eps", 1e-5),
                         alt_sliding_window=True,
                         sliding_pattern=cfg.get(
                             "sliding_window_pattern", 4),
                         rope_skip_global=True)
        elif arch in ("CohereForCausalLM", "CohereModel"):
            # command-r: weight-only mean-centered LayerNorm, PARALLEL
            # attn+MLP residual off one shared norm, interleaved rope,
            # logit scaling, per-head q/k norms on R+
            # (cite ref: pkg/hfutil/modelconfig/commandr.go)
            extra = dict(norm_type="layernorm_nobias",
                         parallel_block=True,
                         logit_scale=cfg.get("logit_scale", 1.0),
                         rope_interleaved=True,
                         qk_norm=bool(cfg.get("use_qk_norm")),
                         rms_norm_eps=cfg.get("layer_norm_eps", 1e-5))
        elif arch == "Qwen3NextForCausalLM":
            # Qwen3-Next: 3 Gated DeltaNet layers to 1 gated full-
            # attention layer, zero-centred RMSNorms, rotary on a
            # share of the head, an MoE with a gated shared expert in
            # every layer. `mlp_only_layers` / `decoder_sparse_step`
            # other than the published ([] / 1) are not implemented
            if cfg.get("mlp_only_layers") or \
                    cfg.get("decoder_sparse_step", 1) != 1:
                raise ValueError(
                    "Qwen3Next: only decoder_sparse_step 1 with no "
                    "mlp_only_layers is implemented")
            shared = cfg.get("shared_expert_intermediate_size", 0) or 0
            moe_w = cfg.get("moe_intermediate_size", 0) or 0
            if shared and moe_w and shared % moe_w:
                raise ValueError(
                    "Qwen3Next: shared_expert_intermediate_size must "
                    "be a multiple of moe_intermediate_size")
            extra = dict(
                unit_offset_norm=True, attn_output_gate=True,
                full_attn_interval=cfg.get("full_attention_interval",
                                           4),
                linear_num_key_heads=cfg["linear_num_key_heads"],
                linear_num_value_heads=cfg["linear_num_value_heads"],
                linear_key_head_dim=cfg["linear_key_head_dim"],
                linear_value_head_dim=cfg["linear_value_head_dim"],
                linear_conv_kernel=cfg.get("linear_conv_kernel_dim", 4),
                partial_rotary_factor=cfg.get("partial_rotary_factor",
                                              1.0),
                num_shared_experts=(shared // moe_w) if moe_w else 0,
                shared_expert_gate=bool(shared),
                # softmax over all experts, top-k, renormalised: the
                # "mixtral" flavour (softmax over the selected logits)
                # is the same numbers when norm_topk_prob is set
                norm_topk_prob=bool(cfg.get("norm_topk_prob", True)),
                # a cut config (model-configs guide section 4):
                # `num_experts` counts the experts held here, these
                # two keys of this repo give the router's published
                # width and where the held range starts
                num_experts_total=cfg.get("ep_num_experts_total", 0)
                or 0,
                expert_offset=cfg.get("ep_expert_offset", 0) or 0)
            if not extra["norm_topk_prob"]:
                raise ValueError("Qwen3Next: norm_topk_prob false is "
                                 "not implemented")
        elif arch == "AfmoeForCausalLM":
            extra = _afmoe_fields(cfg)
        elif arch == "SmallThinkerForCausalLM":
            extra = _smallthinker_fields(cfg)
        elif arch == "GptOssForCausalLM":
            # gpt-oss: attention sinks, alternating sliding layers,
            # top-4 softmax router with bias, clamped-GLU experts with
            # biases (cite ref: pkg/hfutil/modelconfig/gpt_oss.go)
            extra = dict(attn_sinks=True, alt_sliding_window=True,
                         router_bias=True, moe_bias=True,
                         moe_activation="gptoss_glu",
                         moe_intermediate_size=cfg.get(
                             "intermediate_size", 4 * hidden))
        kw = dict(
            vocab_size=cfg.get("vocab_size", 32000),
            hidden_size=hidden,
            num_layers=cfg.get("num_hidden_layers", 32),
            num_heads=heads,
            num_kv_heads=cfg.get("num_key_value_heads", heads),
            head_dim=cfg.get("head_dim") or hidden // heads,
            intermediate_size=cfg.get("intermediate_size", 4 * hidden),
            rope_theta=cfg.get("rope_theta", 10000.0),
            rope_scaling=cfg.get("rope_scaling"),
            rms_norm_eps=cfg.get("rms_norm_eps", 1e-5),
            max_seq_len=cfg.get("max_position_embeddings", 8192),
            tie_word_embeddings=cfg.get("tie_word_embeddings", False),
            init_std=float(cfg.get("initializer_range") or 0.02),
            num_experts=cfg.get("num_local_experts",
                                cfg.get("num_experts",
                                        cfg.get("n_routed_experts", 0))) or 0,
            experts_per_token=cfg.get("num_experts_per_tok", 0) or 0,
            moe_intermediate_size=cfg.get("moe_intermediate_size", 0) or 0,
            num_shared_experts=cfg.get(
                "n_shared_experts", cfg.get("num_shared_experts", 0)) or 0,
            first_k_dense=cfg.get(
                "first_k_dense_replace", cfg.get("num_dense_layers", 0))
            or 0,
            sliding_window=cfg.get("sliding_window")
            if cfg.get("use_sliding_window", True) else None,
            attn_logit_softcap=cfg.get("attn_logit_softcapping"),
            qk_norm=arch.startswith("Qwen3"),
            attn_bias=bool(attn_bias),
            mlp_activation="gelu_tanh" if gemma2 else "silu",
            alt_sliding_window=gemma2,
            query_scale=qscale,
            post_block_norms=gemma2,
            embed_scale=gemma2,
            unit_offset_norm=gemma2,
            final_logit_softcap=cfg.get("final_logit_softcapping"),
        )
        kw.update(mla_kw)
        kw.update(extra)  # per-architecture overrides win
        return cls(**kw)


def _afmoe_fields(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """Arcee AFMoE (Trinity): a period of `global_attn_every_n_layers`
    whose last layer is global and rotary-free and whose others see
    `sliding_window` positions, per-head q/k RMSNorm, an output gate
    projected on its own, four norms a block, `sqrt(hidden)` on the
    embedding, `num_dense_layers` leading dense MLPs, then a sigmoid
    router with a selection-only bias over experts of
    `moe_intermediate_size` beside `num_shared_experts` shared ones.
    The gate, the four norms and the rotary-free global layers are in
    no key of config.json: they are the architecture's. Raises for
    the variants that are not implemented."""
    def refuse(what):
        raise ValueError(f"Afmoe: {what} is not implemented")

    P = cfg.get("global_attn_every_n_layers", 4)
    L = cfg.get("num_hidden_layers", 32)
    period = ["full_attention" if (i + 1) % P == 0
              else "sliding_attention" for i in range(L)]
    if cfg.get("layer_types") not in (None, period):
        refuse("a layer_types other than (global_attn_every_n_layers "
               "- 1) sliding layers, then one full layer,")
    if cfg.get("n_group", 1) not in (0, 1) \
            or cfg.get("topk_group", 1) not in (0, 1):
        refuse("group-limited routing (n_group / topk_group other "
               "than 1)")
    if cfg.get("score_func", "sigmoid") != "sigmoid":
        refuse(f"score_func {cfg['score_func']!r}")
    if cfg.get("rope_scaling"):
        refuse("rope_scaling")
    if not cfg.get("mup_enabled", True):
        refuse("mup_enabled false (an unscaled embedding)")
    if not cfg.get("sliding_window"):
        refuse("a period without sliding_window")
    return dict(
        qk_norm=True, attn_output_gate=True, post_block_norms=True,
        embed_scale=True, alt_sliding_window=True, sliding_pattern=P,
        rope_skip_global=True, window_ring=True,
        router_scoring="sigmoid_v3", router_bias=True,
        norm_topk_prob=bool(cfg.get("route_norm", True)),
        routed_scaling_factor=cfg.get("route_scale", 1.0),
        # a cut config: `num_experts` counts the experts held here
        # (see the Qwen3Next branch)
        num_experts_total=cfg.get("ep_num_experts_total", 0) or 0,
        expert_offset=cfg.get("ep_expert_offset", 0) or 0)


def _pangu_ultra_fields(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """openPangu-Ultra-MoE (`pangu_ultra_moe`): latent attention under
    DeepSeek's key names (`q_lora_rank`, `kv_lora_rank`, the three
    head dims; models/mla.py), `first_k_dense_replace` leading dense
    layers, then `n_routed_experts` routed experts beside
    `n_shared_experts` shared ones under a plain float32 sigmoid
    router: the top `num_experts_per_tok` scores, normalised, times
    `routed_scaling_factor`, with no selection bias and no groups
    (config.json has no `n_group`, `topk_group`, `scoring_func` or
    correction-bias key); `sandwich_norm`: four RMSNorms a block, the
    attention's and the MLP's outputs normed before the residual adds
    (its depth scaling is an initialisation of those norms' weights).
    `num_nextn_predict_layers` (a multi-token-prediction module that
    drafts a second token) is no part of the forward pass and is read
    by nothing. Raises for the variants that are not implemented."""
    def refuse(what):
        raise ValueError(f"PanguUltraMoE: {what} is not implemented")

    if cfg.get("rope_scaling"):
        refuse("rope_scaling")
    if (cfg.get("n_group") or 1) > 1 or (cfg.get("topk_group") or 1) > 1:
        refuse("group-limited routing (n_group / topk_group over 1)")
    if cfg.get("scoring_func", "sigmoid") != "sigmoid":
        refuse(f"scoring_func {cfg['scoring_func']!r}")
    if not cfg.get("norm_topk_prob", True):
        refuse("norm_topk_prob false")
    if cfg.get("attention_bias"):
        refuse("attention_bias")
    if not cfg.get("q_lora_rank"):
        refuse("a query projection without q_lora_rank")
    return dict(
        mla=True,
        q_lora_rank=cfg["q_lora_rank"],
        kv_lora_rank=cfg.get("kv_lora_rank", 512),
        qk_nope_head_dim=cfg.get("qk_nope_head_dim", 128),
        qk_rope_head_dim=cfg.get("qk_rope_head_dim", 64),
        v_head_dim=cfg.get("v_head_dim", 128),
        post_block_norms=bool(cfg.get("sandwich_norm", True)),
        router_scoring="sigmoid_v3", router_bias=False,
        n_group=0, topk_group=0, norm_topk_prob=True,
        routed_scaling_factor=cfg.get("routed_scaling_factor", 1.0),
        # a cut config: `n_routed_experts` counts the experts held here
        # (see the Qwen3Next branch)
        num_experts_total=cfg.get("ep_num_experts_total", 0) or 0,
        expert_offset=cfg.get("ep_expert_offset", 0) or 0)


def _smallthinker_fields(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """PowerInfer SmallThinker: `sliding_window_layout` (1 a window
    layer of `sliding_window_size` positions, 0 a global one) is one
    period repeated, `rope_layout` the same list (a global layer
    applies no rotary embedding); a router of
    `moe_num_primary_experts` that reads the layer's INPUT, top
    `moe_num_active_primary_experts` by logit and softmax over the
    kept; experts `relu(gate) * up` of `moe_ffn_hidden_size`; no
    shared expert, dense layer, q/k norm, bias or gate. Raises for
    the variants that are not implemented."""
    def refuse(what):
        raise ValueError(f"SmallThinker: {what} is not implemented")

    L = cfg.get("num_hidden_layers", 32)
    layout = cfg.get("sliding_window_layout")
    if not isinstance(layout, list) or len(layout) != L:
        refuse("a sliding_window_layout that is not one entry a layer")
    at = [i for i, kind in enumerate(layout) if not kind]
    P = at[1] - at[0] if len(at) > 1 else L
    if not at or P < 2 or at[0] >= P or \
            layout != [int(i % P != at[0]) for i in range(L)]:
        refuse("a sliding_window_layout other than one period "
               "repeated, with exactly one global layer in it,")
    if cfg.get("rope_layout", layout) != layout:
        refuse("a rope_layout that differs from sliding_window_layout "
               "(rotary on a global layer, or none on a window layer)")
    if not cfg.get("moe_primary_router_apply_softmax", True):
        refuse("moe_primary_router_apply_softmax false (a sigmoid "
               "router)")
    if not cfg.get("norm_topk_prob", True):
        refuse("norm_topk_prob false")
    if cfg.get("rope_scaling"):
        refuse("rope_scaling")
    if cfg.get("tie_word_embeddings"):
        refuse("tie_word_embeddings")
    if not cfg.get("sliding_window_size"):
        refuse("a period without sliding_window_size")
    return dict(
        num_experts=cfg.get("moe_num_primary_experts", 0),
        experts_per_token=cfg.get("moe_num_active_primary_experts", 0),
        moe_intermediate_size=cfg.get("moe_ffn_hidden_size", 0),
        sliding_window=cfg["sliding_window_size"],
        alt_sliding_window=True, sliding_pattern=P, global_phase=at[0],
        rope_skip_global=True, window_ring=True,
        router_scoring="mixtral", norm_topk_prob=True,
        router_pre_attn=True, moe_activation="relu",
        # a cut config: `moe_num_primary_experts` counts the experts
        # held here (see the Qwen3Next branch)
        num_experts_total=cfg.get("ep_num_experts_total", 0) or 0,
        expert_offset=cfg.get("ep_expert_offset", 0) or 0)


def _rope_attention_factor(sc: Optional[Dict[str, Any]],
                           max_pos: int) -> float:
    """cos/sin attention factor of yarn/longrope scaling (transformers
    _compute_{yarn,longrope}_parameters)."""
    if not sc:
        return 1.0
    import math
    t = sc.get("rope_type", sc.get("type"))
    if t == "yarn":
        att = sc.get("attention_factor")
        if att is not None:
            return float(att)
        f = sc.get("factor", 1.0)
        return 0.1 * math.log(f) + 1.0 if f > 1 else 1.0
    if t == "longrope":
        att = sc.get("attention_factor")
        if att is not None:
            return float(att)
        orig = sc.get("original_max_position_embeddings") or max_pos
        s = max_pos / orig
        if s <= 1.0:
            return 1.0
        return math.sqrt(1.0 + math.log(s) / math.log(orig))
    return 1.0


# -- presets ---------------------------------------------------------------

def llama3_8b() -> ModelConfig:
    return ModelConfig(vocab_size=128256, hidden_size=4096, num_layers=32,
                       num_heads=32, num_kv_heads=8, head_dim=128,
                       intermediate_size=14336, rope_theta=500000.0,
                       max_seq_len=8192)


def llama3_70b() -> ModelConfig:
    return ModelConfig(vocab_size=128256, hidden_size=8192, num_layers=80,
                       num_heads=64, num_kv_heads=8, head_dim=128,
                       intermediate_size=28672, rope_theta=500000.0,
                       max_seq_len=8192)


def qwen25_05b() -> ModelConfig:
    return ModelConfig(vocab_size=151936, hidden_size=896, num_layers=24,
                       num_heads=14, num_kv_heads=2, head_dim=64,
                       intermediate_size=4864, rope_theta=1000000.0,
                       tie_word_embeddings=True, max_seq_len=32768)


def mixtral_8x7b() -> ModelConfig:
    return ModelConfig(vocab_size=32000, hidden_size=4096, num_layers=32,
                       num_heads=32, num_kv_heads=8, head_dim=128,
                       intermediate_size=14336, rope_theta=1000000.0,
                       num_experts=8, experts_per_token=2,
                       moe_intermediate_size=14336, max_seq_len=32768)


def tiny_test(moe: bool = False) -> ModelConfig:
    """Structurally-faithful small config for tests and dry runs."""
    return ModelConfig(vocab_size=512, hidden_size=128, num_layers=4,
                       num_heads=8, num_kv_heads=4, head_dim=16,
                       intermediate_size=256, max_seq_len=256,
                       rope_theta=10000.0,
                       num_experts=8 if moe else 0,
                       experts_per_token=2 if moe else 0,
                       moe_intermediate_size=128 if moe else 0)
