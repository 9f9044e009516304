"""Checkpoint IO: safetensors parsing + HF-weights -> JAX param trees.

The reference operator never touches weights numerically — it stages
files on nodes and lets SGLang/vLLM load them (gopher.go download
paths, SURVEY.md §2.6). This repo owns a serving engine, so it owns
the conversion from HuggingFace safetensors checkpoints to the stacked
per-layer param pytree that models/llama.py scans over.

Pure-numpy safetensors reader/writer (no torch, no safetensors pip
package): the format is an 8-byte LE header length + JSON header of
{name: {dtype, shape, data_offsets}} + raw little-endian tensor bytes.
bf16 rides ml_dtypes (a JAX dependency). Reads are lazy and per-tensor
(seek + read) so a 70B checkpoint never needs 2x RAM; multi-shard
checkpoints resolve through model.safetensors.index.json exactly like
huggingface_hub does.

Name mapping covers the Llama superset the model implements: llama /
mistral / qwen2 (attention bias) / qwen3 (qk-norm) / gemma2 (softcap)
dense models, mixtral / qwen2-moe / deepseek-style MoE with shared
experts, qwen3-next (Gated DeltaNet layers beside gated attention),
afmoe and smallthinker (periodic window / global attention),
pangu_ultra_moe (latent attention inside sandwich norms).
"""

from __future__ import annotations

import json
import os
import struct
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

try:  # ml_dtypes ships with jax
    import ml_dtypes
    _BF16 = np.dtype(ml_dtypes.bfloat16)
except Exception:  # pragma: no cover
    _BF16 = None

_DTYPES = {
    "F64": np.dtype(np.float64), "F32": np.dtype(np.float32),
    "F16": np.dtype(np.float16), "I64": np.dtype(np.int64),
    "I32": np.dtype(np.int32), "I16": np.dtype(np.int16),
    "I8": np.dtype(np.int8), "U8": np.dtype(np.uint8),
    "BOOL": np.dtype(np.bool_),
}
if _BF16 is not None:
    _DTYPES["BF16"] = _BF16
_DTYPE_NAMES = {v: k for k, v in _DTYPES.items()}


class SafetensorsError(Exception):
    pass


class SafetensorsFile:
    """Lazy reader for one .safetensors file."""

    def __init__(self, path: str):
        self.path = path
        with open(path, "rb") as f:
            (hlen,) = struct.unpack("<Q", f.read(8))
            if hlen > 100 * 1024 * 1024:
                raise SafetensorsError(f"{path}: implausible header size")
            header = json.loads(f.read(hlen))
        self._data_start = 8 + hlen
        self._meta = header.pop("__metadata__", {})
        self._tensors: Dict[str, Tuple[np.dtype, tuple, int, int]] = {}
        for name, info in header.items():
            dt = _DTYPES.get(info["dtype"])
            if dt is None:
                raise SafetensorsError(
                    f"{path}: unsupported dtype {info['dtype']} for {name}")
            start, end = info["data_offsets"]
            self._tensors[name] = (dt, tuple(info["shape"]), start, end)

    def keys(self) -> List[str]:
        return list(self._tensors)

    def shape(self, name: str) -> tuple:
        return self._tensors[name][1]

    def read(self, name: str) -> np.ndarray:
        dt, shape, start, end = self._tensors[name]
        with open(self.path, "rb") as f:
            f.seek(self._data_start + start)
            buf = f.read(end - start)
        n = int(np.prod(shape)) if shape else 1
        if len(buf) != n * dt.itemsize:
            raise SafetensorsError(f"{self.path}: short read for {name}")
        return np.frombuffer(buf, dtype=dt).reshape(shape)


def save_safetensors(path: str, tensors: Dict[str, np.ndarray],
                     metadata: Optional[Dict[str, str]] = None) -> None:
    """Write a safetensors file (used by tests, replica, and export)."""
    header: Dict[str, Any] = {}
    if metadata:
        header["__metadata__"] = metadata
    offset = 0
    arrays = []
    for name, arr in tensors.items():
        arr = np.ascontiguousarray(arr)
        dt = _DTYPE_NAMES.get(arr.dtype)
        if dt is None:
            raise SafetensorsError(f"unsupported dtype {arr.dtype}")
        nbytes = arr.nbytes
        header[name] = {"dtype": dt, "shape": list(arr.shape),
                        "data_offsets": [offset, offset + nbytes]}
        arrays.append(arr)
        offset += nbytes
    hbytes = json.dumps(header).encode()
    with open(path, "wb") as f:
        f.write(struct.pack("<Q", len(hbytes)))
        f.write(hbytes)
        for arr in arrays:
            f.write(arr.tobytes())


class Checkpoint:
    """A model directory's full weight set (single- or multi-shard)."""

    def __init__(self, model_dir: str):
        self.model_dir = model_dir
        index = os.path.join(model_dir, "model.safetensors.index.json")
        self._files: Dict[str, SafetensorsFile] = {}
        self._where: Dict[str, str] = {}
        if os.path.exists(index):
            with open(index) as f:
                weight_map = json.load(f)["weight_map"]
            for name, fname in weight_map.items():
                self._where[name] = fname
        else:
            shards = sorted(fn for fn in os.listdir(model_dir)
                            if fn.endswith(".safetensors"))
            if not shards:
                raise SafetensorsError(
                    f"no .safetensors files in {model_dir}")
            for fname in shards:
                for name in self._file(fname).keys():
                    self._where[name] = fname

    def _file(self, fname: str) -> SafetensorsFile:
        if fname not in self._files:
            self._files[fname] = SafetensorsFile(
                os.path.join(self.model_dir, fname))
        return self._files[fname]

    def keys(self) -> List[str]:
        return list(self._where)

    def __contains__(self, name: str) -> bool:
        return name in self._where

    def read(self, name: str) -> np.ndarray:
        if name not in self._where:
            raise KeyError(name)
        return self._file(self._where[name]).read(name)


# -- HF -> llama.py param tree ---------------------------------------------


def _np_dtype(dtype) -> np.dtype:
    import jax.numpy as jnp
    if dtype in (jnp.bfloat16, "bfloat16"):
        return _BF16
    return np.dtype(dtype)


class _Stacker:
    """Fills [L, ...] stacked arrays one layer at a time (no 2x peak)."""

    def __init__(self, num_layers: int, dtype: np.dtype):
        self.L = num_layers
        self.dtype = dtype
        self.out: Dict[str, np.ndarray] = {}

    def put(self, key: str, layer: int, arr: np.ndarray,
            dtype: Optional[np.dtype] = None) -> None:
        dt = dtype or self.dtype
        if key not in self.out:
            self.out[key] = np.empty((self.L,) + arr.shape, dt)
        self.out[key][layer] = arr.astype(dt)


def convert_llama(ckpt: Checkpoint, cfg, dtype=None) -> Dict[str, Any]:
    """Map HF checkpoint names/layouts onto the llama.py param tree.

    HF linear weights are [out, in] (y = W x). The model's matrices
    are in-major, [in, out], so those projections transpose. The
    attention projections out of the hidden size (wq / wk / wv and
    the output gate w_ogate) are the exception: they lie OUT-MAJOR,
    [heads, Dh, D], which is HF's own [heads * Dh, D] with the fused
    head dim split and NO transpose. That is the order the decode
    step's dot reads them in, so no program re-lays them out before
    use (llama._proj, llama._init_layer_block). wo [H, Dh, D]
    transposes as before.

    DeepSeek (MLA) checkpoints additionally split kv_b_proj into the
    absorbed-path factors w_uk/w_uv, and route the first_k_dense
    leading layers into a separate "dense_layers" stack.
    """
    np_dt = _np_dtype(dtype or "bfloat16")
    L, D, H, K, Dh = (cfg.num_layers, cfg.hidden_size, cfg.num_heads,
                      cfg.num_kv_heads, cfg.head_dim)
    mla = getattr(cfg, "mla", False)
    n_dense = cfg.first_k_dense if (cfg.is_moe
                                    and cfg.first_k_dense) else 0
    hybrid = getattr(cfg, "is_hybrid", False)
    st_main = _Stacker((cfg.kv_cache_layers if hybrid else L) - n_dense,
                       np_dt)
    st_dense = _Stacker(n_dense, np_dt) if n_dense else None
    # hybrid (Qwen3-Next): the Gated DeltaNet layers stack apart
    st_lin = _Stacker(cfg.linear_layers, np_dt) if hybrid else None
    f32 = np.dtype(np.float32)

    def take(name: str) -> np.ndarray:
        if name not in ckpt and name.startswith("model."):
            # bare AutoModel checkpoints (MistralModel/Qwen2Model
            # embedding repos) drop the "model." prefix
            name = name[len("model."):]
        return ckpt.read(name).astype(np.float32)

    def linear_in_out(name: str) -> np.ndarray:
        return take(name).T  # [out,in] -> [in,out]

    def out_major(name: str, heads: int) -> np.ndarray:
        # [heads * Dh, D] -> [heads, Dh, D]: HF's order, heads split
        return take(name).reshape(heads, -1, D)

    for li in range(L):
        p = f"model.layers.{li}."
        linear = False
        if hybrid:
            P = cfg.full_attn_interval
            g, j = divmod(li, P)
            linear = j != P - 1
            st, i = (st_lin, g * (P - 1) + j) if linear else (st_main, g)
        elif li < n_dense:
            st, i = st_dense, li
        else:
            st, i = st_main, li - n_dense
        layer_is_moe = cfg.is_moe and li >= n_dense
        layernorm = getattr(cfg, "norm_type", "rmsnorm") == "layernorm"
        st.put("attn_norm", i, take(p + "input_layernorm.weight"))
        if layernorm:  # phimoe: torch LayerNorm biases ride along
            st.put("attn_norm_bias", i,
                   take(p + "input_layernorm.bias"))
        if getattr(cfg, "post_block_norms", False):
            # gemma2 block: post_attention_layernorm normalizes the
            # attention OUTPUT (pre-residual); the MLP pre-norm is
            # pre_feedforward_layernorm
            st.put("attn_post_norm", i,
                   take(p + "post_attention_layernorm.weight"))
            # (afmoe spells the MLP's two `pre_mlp` / `post_mlp`)
            pre, post = ("pre_mlp", "post_mlp") \
                if p + "pre_mlp_layernorm.weight" in ckpt \
                else ("pre_feedforward", "post_feedforward")
            st.put("mlp_norm", i, take(p + pre + "_layernorm.weight"))
            st.put("mlp_post_norm", i,
                   take(p + post + "_layernorm.weight"))
        elif getattr(cfg, "parallel_block", False):
            pass  # command-r: one shared input norm feeds attn AND mlp
        else:
            st.put("mlp_norm", i,
                   take(p + "post_attention_layernorm.weight"))
            if layernorm:
                st.put("mlp_norm_bias", i,
                       take(p + "post_attention_layernorm.bias"))
        if linear:
            # `in_proj_qkvz` and `in_proj_ba` are laid out per KEY
            # head: q, k, then that head's r value heads' v and z
            # (resp. b and a). The mixer here takes q | k | v flat,
            # the order its conv's channels have
            Hk, Hv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
            dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
            r = Hv // Hk
            la = p + "linear_attn."
            w = take(la + "in_proj_qkvz.weight").T.reshape(
                D, Hk, 2 * dk + 2 * r * dv)
            st.put("w_qkv", i, np.concatenate(
                [w[..., :dk].reshape(D, Hk * dk),
                 w[..., dk:2 * dk].reshape(D, Hk * dk),
                 w[..., 2 * dk:2 * dk + r * dv].reshape(D, Hv * dv)],
                axis=-1))
            st.put("w_z", i, w[..., 2 * dk + r * dv:].reshape(D, Hv * dv))
            ba = take(la + "in_proj_ba.weight").T.reshape(D, Hk, 2 * r)
            st.put("w_b", i, ba[..., :r].reshape(D, Hv))
            st.put("w_a", i, ba[..., r:].reshape(D, Hv))
            st.put("conv_w", i, take(la + "conv1d.weight")[:, 0, :])
            st.put("A_log", i, take(la + "A_log"), dtype=f32)
            st.put("dt_bias", i, take(la + "dt_bias"), dtype=f32)
            st.put("gdn_norm", i, take(la + "norm.weight"))
            st.put("w_lin_out", i, linear_in_out(la + "out_proj.weight"))
        elif hybrid:
            # gated attention: q_proj gives, per head, a query and a
            # gate of head_dim each
            qg = take(p + "self_attn.q_proj.weight").reshape(
                H, 2 * Dh, D)
            st.put("wq", i, qg[:, :Dh])
            st.put("w_ogate", i, qg[:, Dh:])
            st.put("wk", i, out_major(p + "self_attn.k_proj.weight", K))
            st.put("wv", i, out_major(p + "self_attn.v_proj.weight", K))
            st.put("wo", i,
                   take(p + "self_attn.o_proj.weight").T.reshape(H, Dh, D))
        elif mla:
            qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
            r, vd = cfg.kv_lora_rank, cfg.v_head_dim
            if cfg.q_lora_rank:
                st.put("wq_a", i,
                       linear_in_out(p + "self_attn.q_a_proj.weight"))
                st.put("q_a_norm", i,
                       take(p + "self_attn.q_a_layernorm.weight"))
                st.put("wq_b", i,
                       take(p + "self_attn.q_b_proj.weight").reshape(
                           H, qk, cfg.q_lora_rank))
            else:
                st.put("wq", i,
                       out_major(p + "self_attn.q_proj.weight", H))
            st.put("wkv_a", i, linear_in_out(
                p + "self_attn.kv_a_proj_with_mqa.weight"))
            st.put("kv_a_norm", i,
                   take(p + "self_attn.kv_a_layernorm.weight"))
            # kv_b_proj [H*(nope+v), r] carries both absorbed factors
            kv_b = take(p + "self_attn.kv_b_proj.weight").reshape(
                H, cfg.qk_nope_head_dim + vd, r)
            st.put("w_uk", i, kv_b[:, :cfg.qk_nope_head_dim])
            st.put("w_uv", i,
                   kv_b[:, cfg.qk_nope_head_dim:].transpose(0, 2, 1))
            st.put("wo", i,
                   take(p + "self_attn.o_proj.weight").T.reshape(
                       H, vd, D))
        elif p + "self_attn.qkv_proj.weight" in ckpt:
            # phi3: fused qkv — rows are [H*Dh | K*Dh | K*Dh]
            qkv = take(p + "self_attn.qkv_proj.weight")
            st.put("wq", i, qkv[:H * Dh].reshape(H, Dh, D))
            st.put("wk", i, qkv[H * Dh:(H + K) * Dh].reshape(K, Dh, D))
            st.put("wv", i, qkv[(H + K) * Dh:].reshape(K, Dh, D))
            st.put("wo", i,
                   take(p + "self_attn.o_proj.weight").T.reshape(H, Dh, D))
        else:
            st.put("wq", i, out_major(p + "self_attn.q_proj.weight", H))
            st.put("wk", i, out_major(p + "self_attn.k_proj.weight", K))
            st.put("wv", i, out_major(p + "self_attn.v_proj.weight", K))
            st.put("wo", i,
                   take(p + "self_attn.o_proj.weight").T.reshape(H, Dh, D))
        if p + "self_attn.gate_proj.weight" in ckpt:
            # afmoe: the output gate is a projection of its own
            st.put("w_ogate", i,
                   out_major(p + "self_attn.gate_proj.weight", H))
        if getattr(cfg, "attn_bias", False):
            st.put("bq", i,
                   take(p + "self_attn.q_proj.bias").reshape(H, Dh))
            st.put("bk", i,
                   take(p + "self_attn.k_proj.bias").reshape(K, Dh))
            st.put("bv", i,
                   take(p + "self_attn.v_proj.bias").reshape(K, Dh))
            if p + "self_attn.o_proj.bias" in ckpt:
                st.put("bo", i, take(p + "self_attn.o_proj.bias"))
        if getattr(cfg, "attn_sinks", False):
            st.put("sinks", i, take(p + "self_attn.sinks"),
                   dtype=np.dtype(np.float32))
        if cfg.qk_norm and not linear:
            st.put("q_norm", i, take(p + "self_attn.q_norm.weight"))
            st.put("k_norm", i, take(p + "self_attn.k_norm.weight"))
        if layer_is_moe and p + "mlp.experts.gate_up_proj" in ckpt:
            # gpt_oss: fused per-expert parameters, stored [in, out]
            # already (bmm layout); gate/up are INTERLEAVED on the
            # last dim, router is a biased linear
            st.put("router", i, linear_in_out(p + "mlp.router.weight"))
            st.put("router_b", i, take(p + "mlp.router.bias"),
                   dtype=np.dtype(np.float32))
            gu = take(p + "mlp.experts.gate_up_proj")    # [E, D, 2I]
            st.put("we_gate", i, gu[..., ::2])
            st.put("we_up", i, gu[..., 1::2])
            gub = take(p + "mlp.experts.gate_up_proj_bias")  # [E, 2I]
            st.put("we_gate_b", i, gub[..., ::2])
            st.put("we_up_b", i, gub[..., 1::2])
            st.put("we_down", i, take(p + "mlp.experts.down_proj"))
            st.put("we_down_b", i,
                   take(p + "mlp.experts.down_proj_bias"))
        elif layer_is_moe:
            # router: mixtral block_sparse_moe.gate / qwen-moe+deepseek
            # mlp.gate
            # (smallthinker: block_sparse_moe.primary_router)
            for rn in ("block_sparse_moe.gate.weight", "mlp.gate.weight",
                       "mlp.router.gate.weight",
                       "block_sparse_moe.primary_router.weight"):
                if p + rn in ckpt:
                    st.put("router", i, linear_in_out(p + rn))
                    break
            else:
                raise SafetensorsError(f"no MoE router for layer {li}")
            if getattr(cfg, "router_bias", False):
                # selection bias stays fp32: bf16 rounding could flip
                # expert choices
                bn = "mlp.gate.e_score_correction_bias"
                if p + bn not in ckpt:
                    bn = "mlp.expert_bias"  # afmoe's spelling
                # (whole, like the router, whatever share is held)
                st.put("router_bias", i, take(p + bn),
                       dtype=np.dtype(np.float32))
            gates, ups, downs = [], [], []
            # a cut config (`ep_num_experts_total`) holds the experts
            # from `expert_offset` on; the router above stays whole
            lo = getattr(cfg, "expert_offset", 0)
            for e in range(lo, lo + cfg.num_experts):
                if f"{p}block_sparse_moe.experts.{e}.w1.weight" in ckpt:
                    en = f"{p}block_sparse_moe.experts.{e}."
                    g, u, d = en + "w1.weight", en + "w3.weight", \
                        en + "w2.weight"
                elif f"{p}block_sparse_moe.experts.{e}.gate.weight" \
                        in ckpt:    # smallthinker
                    en = f"{p}block_sparse_moe.experts.{e}."
                    g, u, d = en + "gate.weight", en + "up.weight", \
                        en + "down.weight"
                else:
                    en = f"{p}mlp.experts.{e}."
                    g, u, d = en + "gate_proj.weight", \
                        en + "up_proj.weight", en + "down_proj.weight"
                gates.append(linear_in_out(g))
                ups.append(linear_in_out(u))
                downs.append(linear_in_out(d))
            st.put("we_gate", i, np.stack(gates))
            st.put("we_up", i, np.stack(ups))
            st.put("we_down", i, np.stack(downs))
            if cfg.num_shared_experts > 0:
                for sn in ("mlp.shared_experts.", "mlp.shared_expert."):
                    if p + sn + "gate_proj.weight" in ckpt:
                        st.put("ws_gate", i,
                               linear_in_out(p + sn + "gate_proj.weight"))
                        st.put("ws_up", i,
                               linear_in_out(p + sn + "up_proj.weight"))
                        st.put("ws_down", i,
                               linear_in_out(p + sn + "down_proj.weight"))
                        break
            if getattr(cfg, "shared_expert_gate", False):
                st.put("w_sg", i, linear_in_out(
                    p + "mlp.shared_expert_gate.weight"))
        elif p + "mlp.gate_up_proj.weight" in ckpt:
            # phi3: fused gate|up rows (Phi3MLP chunks in halves)
            guw = take(p + "mlp.gate_up_proj.weight")
            half = guw.shape[0] // 2
            st.put("w_gate", i, guw[:half].T)
            st.put("w_up", i, guw[half:].T)
            st.put("w_down", i, linear_in_out(p + "mlp.down_proj.weight"))
        else:
            st.put("w_gate", i, linear_in_out(p + "mlp.gate_proj.weight"))
            st.put("w_up", i, linear_in_out(p + "mlp.up_proj.weight"))
            st.put("w_down", i, linear_in_out(p + "mlp.down_proj.weight"))

    # a config whose vocabulary is a slice holds the first rows
    V = cfg.vocab_size
    params: Dict[str, Any] = {
        "embed": take("model.embed_tokens.weight")[:V].astype(np_dt),
        "final_norm": take("model.norm.weight").astype(np_dt),
        "layers": st_main.out,
    }
    if st_lin is not None:
        params["linear_layers"] = st_lin.out
    if getattr(cfg, "norm_type", "rmsnorm") == "layernorm":
        params["final_norm_bias"] = take("model.norm.bias").astype(np_dt)
    if st_dense is not None:
        params["dense_layers"] = st_dense.out
    if not cfg.tie_word_embeddings:
        if "lm_head.weight" in ckpt:
            params["lm_head"] = linear_in_out(
                "lm_head.weight")[:, :V].astype(np_dt)
        # some checkpoints omit lm_head despite tie=False in config:
        # fall back to tied embeddings (forward() handles the absence)
    if getattr(cfg, "lm_head_bias", False) and "lm_head.bias" in ckpt:
        params["lm_head_bias"] = take("lm_head.bias").astype(np.float32)
    return params


# architectures whose math models/llama.py implements faithfully; a
# config.json outside this list loads only with allow_unsupported
# (e.g. Mllama adds cross-attention vision layers — loading it here
# would produce garbage silently)
SUPPORTED_ARCHITECTURES = frozenset({
    "LlamaForCausalLM", "MistralForCausalLM", "Qwen2ForCausalLM",
    "Qwen3ForCausalLM", "MixtralForCausalLM", "Gemma2ForCausalLM",
    # MLA family (models/mla.py): DeepSeek-V2/V3; Kimi-K2 ships the
    # DeepseekV3ForCausalLM architecture
    "DeepseekV2ForCausalLM", "DeepseekV3ForCausalLM",
    # round 5 (r4 verdict #5): phi3 (fused qkv/gate_up), Phi-3.5-MoE
    # (LayerNorm + sparsemixer), command-r (parallel block, interleaved
    # rope, logit scale), gpt-oss (sinks, clamped-GLU biased experts)
    "Phi3ForCausalLM", "PhimoeForCausalLM", "PhiMoEForCausalLM",
    "CohereForCausalLM", "Cohere2ForCausalLM", "GptOssForCausalLM",
    # hybrid Gated DeltaNet / gated attention / sparse experts
    # (models/gdn.py); the checkpoint's `mtp.*` tensors (one multi-
    # token-prediction module) are not part of the forward pass and
    # are never read
    "Qwen3NextForCausalLM",
    # periodic window / global attention with an output gate, four
    # norms a block, leading dense layers and a sigmoid-routed expert
    # layer (Arcee Trinity)
    "AfmoeForCausalLM",
    # a period that OPENS with its rotary-free global layer, a router
    # that reads the layer's input, ReLU-gated experts (PowerInfer
    # SmallThinker)
    "SmallThinkerForCausalLM",
    # latent attention (models/mla.py, DeepSeek's tensor names) inside
    # four norms a block (`input_layernorm`, `post_attention_layernorm`
    # on the attention's OUTPUT, `pre_mlp_layernorm`,
    # `post_mlp_layernorm`), leading dense layers, a plain sigmoid
    # router (`mlp.gate.weight`, no correction bias) over routed and
    # shared experts (openPangu-Ultra-MoE). Served: the forward pass
    # that gives a token's logits. NOT served: the checkpoint's one
    # multi-token-prediction module (`num_nextn_predict_layers`: the
    # layer behind the last, a draft source), whose tensors are never
    # read
    "PanguUltraMoEForCausalLM",
    # decoder embedding models (engine/embed.py): bare AutoModel
    # checkpoints whose tensors lack the "model." prefix
    "MistralModel", "Qwen2Model", "Qwen3Model",
})


def unsupported_architectures(hf: Dict[str, Any]) -> List[str]:
    """The `architectures` a config.json NAMES when none of them is
    in SUPPORTED_ARCHITECTURES; [] when one is, or when it names none
    (a test's toy model)."""
    archs = hf.get("architectures") or []
    return [] if set(archs) & SUPPORTED_ARCHITECTURES else list(archs)


def load_params(model_dir: str, cfg=None, dtype=None,
                device_put: bool = True, allow_unsupported: bool = False,
                ) -> Tuple[Dict[str, Any], Any]:
    """Load (params, cfg) from a HF model directory.

    cfg defaults to ModelConfig.from_hf_config(config.json). With
    device_put the numpy tree is transferred to the default device as
    one jnp tree (the sharded path goes through parallel/sharding.py
    with the numpy tree instead).
    """
    from .config import ModelConfig
    if cfg is None:
        with open(os.path.join(model_dir, "config.json")) as f:
            hf = json.load(f)
        archs = unsupported_architectures(hf)
        if archs and not allow_unsupported:
            raise SafetensorsError(
                f"architecture {archs} is not faithfully implemented by "
                f"models/llama.py (supported: "
                f"{sorted(SUPPORTED_ARCHITECTURES)}); pass "
                f"allow_unsupported=True to force-load")
        cfg = ModelConfig.from_hf_config(hf)
    ckpt = Checkpoint(model_dir)
    params = convert_llama(ckpt, cfg, dtype=dtype)
    if dtype is not None:
        cfg = cfg.replace(dtype=dtype)  # compute dtype follows weights
    if device_put:
        import jax
        params = jax.tree.map(lambda a: jax.device_put(a), params)
    return params, cfg
