"""Checkpoint loading: safetensors IO + HF -> JAX param conversion.

The strongest check: build tiny random HF models with `transformers`
(torch CPU), save_pretrained them, load with our pure-numpy reader +
converter, and compare full-precision logits position-by-position.
That validates the name mapping, every transpose/reshape, biases,
tied embeddings, GQA head shapes, and MoE expert stacking against the
reference implementation of the architectures themselves.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ome_tpu.models import checkpoint as ck
from ome_tpu.models import llama
from ome_tpu.models.config import ModelConfig


def test_safetensors_roundtrip(tmp_path):
    path = str(tmp_path / "t.safetensors")
    tensors = {
        "a": np.arange(12, dtype=np.float32).reshape(3, 4),
        "b": np.ones((2, 2), np.float16),
        "c": (np.arange(8) % 3).astype(np.int64),
    }
    ck.save_safetensors(path, tensors, metadata={"format": "pt"})
    f = ck.SafetensorsFile(path)
    assert sorted(f.keys()) == ["a", "b", "c"]
    for name, arr in tensors.items():
        got = f.read(name)
        assert got.dtype == arr.dtype and got.shape == arr.shape
        np.testing.assert_array_equal(got, arr)


def test_safetensors_bf16_roundtrip(tmp_path):
    import ml_dtypes
    path = str(tmp_path / "t.safetensors")
    arr = np.asarray([[1.5, -2.25], [0.0, 3.0]], ml_dtypes.bfloat16)
    ck.save_safetensors(path, {"x": arr})
    got = ck.SafetensorsFile(path).read("x")
    np.testing.assert_array_equal(got.astype(np.float32),
                                  arr.astype(np.float32))


def test_multi_shard_checkpoint_via_index(tmp_path):
    d = str(tmp_path)
    ck.save_safetensors(os.path.join(d, "model-00001-of-00002.safetensors"),
                        {"w1": np.ones((2, 2), np.float32)})
    ck.save_safetensors(os.path.join(d, "model-00002-of-00002.safetensors"),
                        {"w2": np.zeros((3,), np.float32)})
    with open(os.path.join(d, "model.safetensors.index.json"), "w") as f:
        json.dump({"weight_map": {
            "w1": "model-00001-of-00002.safetensors",
            "w2": "model-00002-of-00002.safetensors"}}, f)
    c = ck.Checkpoint(d)
    assert "w1" in c and "w2" in c
    assert c.read("w2").shape == (3,)


# -- a Hugging Face-named projection through the loader and `_proj` -------


@pytest.mark.parametrize("arch", ["split", "fused_qkv", "gate_proj"])
def test_a_hf_named_projection_gives_x_times_w_through_proj(tmp_path, arch):
    """A Hugging Face `q_proj.weight` is [heads * Dh, D] and means
    `y = x @ W.T`. The loader stores it [heads, Dh, D] (out-major: no
    transpose, `checkpoint.convert_llama`) and `llama._proj` contracts
    its last dim, so the two together give HF's `y`, heads split, for
    the spellings the loader reads: separate q / k / v (llama), the
    fused `qkv_proj` (phi3) and an output gate's `gate_proj` (afmoe);
    Qwen3-Next's `q_proj` of a query and a gate a head is held against
    the published model in tests/test_qwen3_next.py."""
    D, H, K, Dh, V = 32, 4, 2, 8, 64
    rng = np.random.RandomState(0)
    t = {"model.embed_tokens.weight": rng.randn(V, D),
         "model.norm.weight": np.ones(D),
         "lm_head.weight": rng.randn(V, D)}
    pre = "model.layers.0."
    hf = dict(hidden_size=D, num_hidden_layers=1, num_attention_heads=H,
              num_key_value_heads=K, head_dim=Dh, intermediate_size=48,
              vocab_size=V, max_position_embeddings=64,
              tie_word_embeddings=False, rms_norm_eps=1e-6)
    q, k, v = (rng.randn(n * Dh, D) for n in (H, K, K))
    gate = rng.randn(H * Dh, D)
    want = {"wq": q, "wk": k, "wv": v}
    for n in ("input_layernorm", "post_attention_layernorm"):
        t[pre + n + ".weight"] = np.ones(D)
    for n, shape in (("gate_proj", (48, D)), ("up_proj", (48, D)),
                     ("down_proj", (D, 48))):
        t[pre + "mlp." + n + ".weight"] = rng.randn(*shape)
    t[pre + "self_attn.o_proj.weight"] = rng.randn(D, H * Dh)
    if arch == "fused_qkv":
        hf.update(architectures=["Phi3ForCausalLM"], model_type="phi3")
        t[pre + "self_attn.qkv_proj.weight"] = np.concatenate([q, k, v])
    else:
        t[pre + "self_attn.k_proj.weight"] = k
        t[pre + "self_attn.v_proj.weight"] = v
        t[pre + "self_attn.q_proj.weight"] = q
        hf.update(architectures=["LlamaForCausalLM"], model_type="llama")
    if arch == "gate_proj":
        t[pre + "self_attn.gate_proj.weight"] = gate
        want["w_ogate"] = gate
    ck.save_safetensors(str(tmp_path / "model.safetensors"),
                        {n: np.asarray(a, np.float32) for n, a in t.items()})
    cfg = ModelConfig.from_hf_config(hf).replace(dtype=jnp.float32)
    params = ck.convert_llama(ck.Checkpoint(str(tmp_path)), cfg,
                              dtype=jnp.float32)
    x = rng.randn(2, 5, D).astype(np.float32)
    for name, w in want.items():
        heads = w.shape[0] // Dh
        leaf = params["layers"][name]
        assert leaf.shape == (1, heads, Dh, D), (name, leaf.shape)
        got = llama._proj(jnp.asarray(x), jnp.asarray(leaf[0]), jnp.float32,
                          out_dims=(heads, Dh), out_major=True)
        np.testing.assert_allclose(
            np.asarray(got), (x @ w.T).reshape(2, 5, heads, Dh),
            atol=1e-5, err_msg=name)


# -- transformers equivalence ----------------------------------------------

transformers = pytest.importorskip("transformers")
torch = pytest.importorskip("torch")


def _save_hf(tmp_path, hf_cfg):
    model = transformers.AutoModelForCausalLM.from_config(hf_cfg)
    model = model.eval()
    d = str(tmp_path / "model")
    model.save_pretrained(d, safe_serialization=True)
    return model, d


def _compare_logits(model, model_dir, atol=2e-4):
    params, cfg = ck.load_params(model_dir, dtype=jnp.float32)
    tokens = np.array([[1, 5, 9, 2, 7, 3, 8, 4]], np.int32)
    logits, _ = llama.forward(params, cfg.replace(dtype=jnp.float32),
                              jnp.asarray(tokens))
    with torch.no_grad():
        ref = model(torch.tensor(tokens, dtype=torch.long)).logits
    np.testing.assert_allclose(
        np.asarray(logits, np.float32), ref.numpy(),
        atol=atol, rtol=1e-3)
    # greedy argmax agreement is what serving actually needs
    np.testing.assert_array_equal(
        np.argmax(np.asarray(logits), -1), ref.argmax(-1).numpy())


def test_llama_logits_match_transformers(tmp_path):
    hf_cfg = transformers.LlamaConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, max_position_embeddings=64, rope_theta=10000.0,
        tie_word_embeddings=False)
    model, d = _save_hf(tmp_path, hf_cfg)
    _compare_logits(model, d)


def test_qwen2_bias_tied_logits_match_transformers(tmp_path):
    hf_cfg = transformers.Qwen2Config(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, rope_theta=10000.0,
        tie_word_embeddings=True)
    model, d = _save_hf(tmp_path, hf_cfg)
    params, cfg = ck.load_params(d, dtype=jnp.float32)
    assert cfg.attn_bias and cfg.tie_word_embeddings
    assert "bq" in params["layers"]
    _compare_logits(model, d)


def test_mixtral_moe_logits_match_transformers(tmp_path):
    hf_cfg = transformers.MixtralConfig(
        vocab_size=128, hidden_size=64, intermediate_size=96,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        num_local_experts=4, num_experts_per_tok=2,
        max_position_embeddings=64, rope_theta=10000.0)
    model, d = _save_hf(tmp_path, hf_cfg)
    params, cfg = ck.load_params(d, dtype=jnp.float32)
    assert cfg.is_moe and cfg.num_experts == 4
    assert params["layers"]["we_gate"].shape[1] == 4
    _compare_logits(model, d, atol=5e-4)


def test_gemma2_logits_match_transformers(tmp_path):
    # the full gemma2 block shape: GeGLU, (1+w) norms, post-block
    # norms, alternating sliding window, query_pre_attn_scalar,
    # softcaps, scaled embeddings, tied head
    hf_cfg = transformers.Gemma2Config(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, max_position_embeddings=64, rope_theta=10000.0,
        sliding_window=4, query_pre_attn_scalar=16,
        attn_logit_softcapping=50.0, final_logit_softcapping=30.0)
    model, d = _save_hf(tmp_path, hf_cfg)
    params, cfg = ck.load_params(d, dtype=jnp.float32)
    assert cfg.alt_sliding_window and cfg.unit_offset_norm
    assert "attn_post_norm" in params["layers"]
    _compare_logits(model, d, atol=5e-4)


def test_llama3_rope_scaling_matches_transformers(tmp_path):
    hf_cfg = transformers.LlamaConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=4,
        head_dim=16, max_position_embeddings=256, rope_theta=10000.0,
        rope_scaling={"rope_type": "llama3", "factor": 8.0,
                      "low_freq_factor": 1.0, "high_freq_factor": 4.0,
                      "original_max_position_embeddings": 64})
    model, d = _save_hf(tmp_path, hf_cfg)
    _compare_logits(model, d)
