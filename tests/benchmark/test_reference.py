"""The plain reference against the program's own forward pass at a
toy size on the CPU, and the comparison that decides `correct` against
its controls: the statistic that passes the program at the stated
precision (bf16) fails the same program with int8 or int4 weights, and
fails the reference's own int8 pass, at one limit."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

import check  # noqa: E402
import cost  # noqa: E402
from reference import dense_gqa as ref  # noqa: E402

from ome_tpu.models import llama  # noqa: E402
from ome_tpu.models.config import ModelConfig  # noqa: E402
from ome_tpu.models.quant import quantize_params  # noqa: E402

QWEN = dict(architectures=["Qwen3ForCausalLM"], model_type="qwen3",
            hidden_size=128, num_hidden_layers=2, num_attention_heads=8,
            num_key_value_heads=4, head_dim=16, intermediate_size=256,
            vocab_size=1024, tie_word_embeddings=True, rope_theta=1000000,
            rms_norm_eps=1e-6, max_position_embeddings=512)
MISTRAL = dict(architectures=["MistralForCausalLM"], model_type="mistral",
               hidden_size=128, num_hidden_layers=4, num_attention_heads=8,
               num_key_value_heads=2, intermediate_size=256,
               vocab_size=8192, tie_word_embeddings=False,
               rope_theta=1000000.0, rms_norm_eps=1e-5,
               max_position_embeddings=512, sliding_window=None)

# Toy-size limit on the mean gap, placed as the chip's limits are
# placed, between the sound reading and the smallest control. Read at
# 16 x 256 positions (CPU, this file's seed): the bf16 program 5.5e-5;
# the reference's own int8 pass 1.85e-4; the program with int8 weights
# 3.8e-4; with int4 weights 1.7e-2. The widest gap separates them less
# (0.017 against 0.057, 0.057, 0.41), as a maximum does.
GAP_MEAN_LIMIT = 1.1e-4


def _program(hf, dtype):
    cfg = ModelConfig.from_hf_config(hf).replace(dtype=dtype)
    params = jax.jit(
        lambda: llama.init_params(jax.random.PRNGKey(0), cfg))()
    return cfg, params


@pytest.mark.parametrize("hf", [QWEN, MISTRAL], ids=["qwen3", "mistral"])
def test_reference_makes_the_servers_weights_from_its_own_code(hf):
    cfg, params = _program(hf, jnp.bfloat16)
    w = ref.init_weights(hf)
    leaves = dict(params["layers"], embed=params["embed"],
                  final_norm=params["final_norm"])
    if "lm_head" in params:
        leaves["lm_head"] = params["lm_head"]
    assert set(leaves) == set(w)
    for name, leaf in leaves.items():
        assert w[name].dtype == leaf.dtype
        assert (np.asarray(w[name].astype(jnp.float32))
                == np.asarray(leaf.astype(jnp.float32))).all(), name
    assert cost.param_count(hf) == llama.param_count(params)


@pytest.mark.parametrize("hf", [QWEN, MISTRAL], ids=["qwen3", "mistral"])
def test_reference_agrees_with_the_programs_forward_in_float32(hf):
    cfg, params = _program(hf, jnp.float32)
    w = ref.init_weights(hf, jnp.float32)
    toks = np.random.RandomState(0).randint(0, hf["vocab_size"], (1, 96))
    got, _ = llama.forward(params, cfg, jnp.asarray(toks, jnp.int32))
    want = np.asarray(ref.logits(w, hf, toks[0], 40, 56))
    diff = np.abs(np.asarray(got[0, 40:]) - want)
    assert diff.mean() < 1e-5 * want.std()
    assert diff.max() < 1e-4 * want.std()


def _gaps(served, want):
    """The statistic of check.py, on the host."""
    best, std = want.max(-1), want.std(-1)
    got = np.take_along_axis(want, served[:, None], -1)[:, 0]
    return (best - got) / std


@pytest.fixture(scope="module")
def mistral_gaps():
    """Mean gap of what each variant would serve greedily at 16 x 256
    positions, under the float32 reference."""
    hf = MISTRAL
    cfg, params = _program(hf, jnp.bfloat16)
    w = ref.init_weights(hf)
    toks = np.random.RandomState(4).randint(0, hf["vocab_size"], (16, 256))
    want = [np.asarray(ref.logits(w, hf, t, 0, 256)) for t in toks]
    out = {}
    variants = {"bf16": params,
                "int8": quantize_params(params, mode="int8"),
                "int4": quantize_params(params, mode="int4")}
    for name, p in variants.items():
        lg, _ = llama.forward(p, cfg, jnp.asarray(toks, jnp.int32))
        served = np.asarray(lg.astype(jnp.float32)).argmax(-1)
        out[name] = np.concatenate(
            [_gaps(s, r) for s, r in zip(served, want)])
    control = [np.asarray(ref.logits(w, hf, t, 0, 256, int8=True)).argmax(-1)
               for t in toks]
    out["reference-int8"] = np.concatenate(
        [_gaps(s, r) for s, r in zip(control, want)])
    return out


def test_the_stated_precision_passes(mistral_gaps):
    g = mistral_gaps["bf16"]
    assert (g >= 0).all()
    assert g.mean() <= GAP_MEAN_LIMIT / 1.5
    assert (g == 0).mean() > 0.97


@pytest.mark.parametrize("lever", ["int8", "int4", "reference-int8"])
def test_a_lower_precision_fails_at_the_same_limit(mistral_gaps, lever):
    assert mistral_gaps[lever].mean() >= 1.5 * GAP_MEAN_LIMIT


@pytest.mark.parametrize("prompt,n", [(5, 1), (40, 256), (300, 17),
                                      (1536, 256), (255, 2)])
def test_check_layout_scores_the_rows_that_predict_the_served_tokens(
        prompt, n):
    padded, first, off = check.layout(prompt, n)
    assert padded % check.PAD_TO == 0 and padded >= prompt + n - 1
    assert 0 <= first and first + check.ROWS <= padded
    # row `first + off` is the last prompt position, which predicts
    # served token 0; the last served token's row is inside the block
    assert first + off == prompt - 1
    assert off + n <= check.ROWS


def test_check_layout_refuses_more_tokens_than_it_scores():
    with pytest.raises(ValueError):
        check.layout(10, check.ROWS + 1)
