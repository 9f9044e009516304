"""Weight-only int8/int4 quantization: numerics, bytes, and the
serving path (QTensor leaves flowing through jit + lax.scan + the
engine)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ome_tpu.engine.core import InferenceEngine
from ome_tpu.models import llama
from ome_tpu.models.config import tiny_test
from ome_tpu.models.quant import (QTensor, quantize_params,
                                  quantize_tensor, quantize_tensor_int4,
                                  quantized_bytes)


def test_quantize_tensor_roundtrip_error_bounded():
    w = jax.random.normal(jax.random.PRNGKey(0), (64, 32), jnp.float32)
    qt = quantize_tensor(w, contract_axes=(0,))
    assert qt.q.dtype == jnp.int8 and qt.s.shape == (1, 32)
    err = np.abs(np.asarray(qt.dequant(jnp.float32)) - np.asarray(w))
    # per-channel symmetric int8: error <= scale/2 per element
    assert err.max() <= np.asarray(qt.s).max() * 0.51


@pytest.mark.parametrize("moe", [False, True])
def test_quantized_forward_close_to_fp(moe):
    cfg = tiny_test(moe=moe).replace(dtype=jnp.float32)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    qparams = quantize_params(params)
    tok = jnp.asarray([[1, 2, 3, 4, 5, 6, 7, 8]], jnp.int32)
    ref, _ = llama.forward(params, cfg, tok)
    got, _ = llama.forward(qparams, cfg, tok)
    ref, got = np.asarray(ref), np.asarray(got)
    # int8 weights shift logits, but direction must hold
    cos = (ref * got).sum() / (np.linalg.norm(ref)
                               * np.linalg.norm(got))
    assert cos > 0.999


def test_quantized_bytes_halve():
    cfg = tiny_test().replace(dtype=jnp.bfloat16)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    full = sum(p.size * p.dtype.itemsize
               for p in jax.tree.leaves(params))
    q = quantized_bytes(quantize_params(params))
    assert q < full * 0.62  # int8 + scales + fp norms


def test_quantized_engine_decodes():
    cfg = tiny_test().replace(dtype=jnp.bfloat16)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    qparams = quantize_params(params)
    eng = InferenceEngine(qparams, cfg, max_slots=2, max_seq=32,
                          prefill_buckets=[16])
    state = eng.new_state()
    tok, kv, true_len, bucket = eng.prefill([1, 2, 3, 4])
    state = eng.insert(state, kv, 0, true_len, tok, bucket)
    temp = np.zeros(2, np.float32)
    for _ in range(4):
        state, toks = eng.decode(state, temp, np.zeros(2, np.int32),
                                 np.ones(2, np.float32))
    assert 0 <= int(np.asarray(toks)[0]) < cfg.vocab_size


def test_quantized_tp_sharded_engine():
    """int8 weights must shard over the tp mesh (q splits like the
    full-precision weight; size-1 scale dims stay unsharded)."""
    from ome_tpu.engine.sharded import ShardedInferenceEngine
    cfg = tiny_test()
    qparams = quantize_params(llama.init_params(jax.random.PRNGKey(0),
                                                cfg))
    eng = ShardedInferenceEngine(qparams, cfg, tp=2, max_slots=2,
                                 max_seq=32)
    state = eng.new_state()
    tok, kv, tl, b = eng.prefill([1, 2, 3])
    state = eng.insert(state, kv, 0, tl, tok, b)
    state, toks = eng.decode(state, np.zeros(2, np.float32),
                             np.zeros(2, np.int32),
                             np.ones(2, np.float32))
    assert 0 <= int(np.asarray(toks)[0]) < cfg.vocab_size


def test_int4_roundtrip_error_bounded():
    w = jax.random.normal(jax.random.PRNGKey(0), (256, 32), jnp.float32)
    qt = quantize_tensor_int4(w, contract_axes=(0,), group=128)
    assert qt.q.shape == (128, 32) and qt.s.shape == (2, 32)
    err = np.abs(np.asarray(qt.dequant(jnp.float32)) - np.asarray(w))
    # groupwise symmetric int4: error <= scale/2 per element
    assert err.max() <= np.asarray(qt.s).max() * 0.51


def test_int4_multi_contract_axis():
    """wo-style [H, Dh, D] weight contracting over (Dh, H): packs along
    Dh, scales span the group slice x all of H."""
    w = jax.random.normal(jax.random.PRNGKey(1), (4, 128, 16),
                          jnp.float32)
    qt = quantize_tensor_int4(w, contract_axes=(1, 0), group=64)
    assert qt.q.shape == (4, 64, 16) and qt.s.shape == (1, 2, 16)
    deq = np.asarray(qt.dequant(jnp.float32))
    err = np.abs(deq - np.asarray(w))
    assert err.max() <= np.asarray(qt.s).max() * 0.51


def test_int4_forward_close_to_fp():
    cfg = tiny_test().replace(dtype=jnp.float32)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    qparams = quantize_params(params, mode="int4", group=64)
    tok = jnp.asarray([[1, 2, 3, 4, 5, 6, 7, 8]], jnp.int32)
    ref, _ = llama.forward(params, cfg, tok)
    got, _ = llama.forward(qparams, cfg, tok)
    ref, got = np.asarray(ref), np.asarray(got)
    cos = (ref * got).sum() / (np.linalg.norm(ref)
                               * np.linalg.norm(got))
    # random-init tiny models are the worst case for 4-bit (no weight
    # structure); real checkpoints land much closer
    assert cos > 0.98


def test_int4_bytes_quarter():
    cfg = tiny_test().replace(dtype=jnp.bfloat16)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    full = sum(p.size * p.dtype.itemsize
               for p in jax.tree.leaves(params))
    q8 = quantized_bytes(quantize_params(params))
    q4 = quantize_params(params, mode="int4", group=64)
    # layer matmul payloads are nibble-packed: half the int8 bytes
    assert (q4["layers"]["w_gate"].q.nbytes
            == params["layers"]["w_gate"].nbytes // 4)
    assert quantized_bytes(q4) < q8 * 0.85  # embed/lm_head stay int8


def test_int4_engine_decodes():
    cfg = tiny_test().replace(dtype=jnp.bfloat16)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    qparams = quantize_params(params, mode="int4", group=64)
    eng = InferenceEngine(qparams, cfg, max_slots=2, max_seq=32,
                          prefill_buckets=[16])
    state = eng.new_state()
    tok, kv, true_len, bucket = eng.prefill([1, 2, 3, 4])
    state = eng.insert(state, kv, 0, true_len, tok, bucket)
    temp = np.zeros(2, np.float32)
    for _ in range(4):
        state, toks = eng.decode(state, temp, np.zeros(2, np.int32),
                                 np.ones(2, np.float32))
    assert 0 <= int(np.asarray(toks)[0]) < cfg.vocab_size


def test_int4_tp_sharded_engine():
    from ome_tpu.engine.sharded import ShardedInferenceEngine
    cfg = tiny_test()
    qparams = quantize_params(
        llama.init_params(jax.random.PRNGKey(0), cfg), mode="int4",
        group=64)
    eng = ShardedInferenceEngine(qparams, cfg, tp=2, max_slots=2,
                                 max_seq=32)
    state = eng.new_state()
    tok, kv, tl, b = eng.prefill([1, 2, 3])
    state = eng.insert(state, kv, 0, tl, tok, b)
    state, toks = eng.decode(state, np.zeros(2, np.float32),
                             np.zeros(2, np.int32),
                             np.ones(2, np.float32))
    assert 0 <= int(np.asarray(toks)[0]) < cfg.vocab_size


def test_int4_scan_slices_keep_axis():
    """Stacked [L, D, F] int4 leaves must dequantize identically when
    lax.scan slices the layer dim (axis stored end-relative)."""
    w = jax.random.normal(jax.random.PRNGKey(2), (3, 64, 16),
                          jnp.float32)
    qt = quantize_tensor_int4(w, contract_axes=(1,), group=32)

    def body(c, lp):
        return c, lp.dequant(jnp.float32)

    _, per_layer = jax.lax.scan(body, (), qt)
    np.testing.assert_allclose(np.asarray(per_layer),
                               np.asarray(qt.dequant(jnp.float32)),
                               rtol=1e-5)


def test_qtensor_is_scan_compatible():
    """QTensor leaves in stacked [L, ...] form must slice through
    lax.scan like plain arrays (the model's layer scan)."""
    w = jax.random.normal(jax.random.PRNGKey(1), (4, 8, 16), jnp.float32)
    qt = quantize_tensor(w, contract_axes=(1,))

    def body(c, lp):
        return c + lp.dequant(jnp.float32).sum(), None

    total, _ = jax.lax.scan(body, jnp.zeros(()), qt)
    np.testing.assert_allclose(
        np.asarray(total),
        np.asarray(qt.dequant(jnp.float32).sum()), rtol=1e-5)


def test_fp8_roundtrip_and_forward():
    """fp8 (float8_e4m3 per-channel) mode: dequant error bounded by the
    4-bit mantissa, forward stays close to full precision, bytes match
    int8 (model.go:262-268 fp8 analog; v6e-targeted)."""
    from ome_tpu.models.quant import (QTensor, quantize_tensor_fp8,
                                      quantized_bytes)
    rng = np.random.default_rng(5)
    w = jnp.asarray(rng.standard_normal((256, 128)), jnp.float32)
    qt = quantize_tensor_fp8(w, (0,))
    assert qt.q.dtype == jnp.float8_e4m3fn
    err = np.abs(np.asarray(qt.dequant(jnp.float32)) - np.asarray(w))
    # e4m3: 3 mantissa bits -> relative step 2^-3; scaled per channel
    assert err.max() < np.abs(w).max() * 0.08

    cfg = tiny_test().replace(dtype=jnp.float32)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    qp = quantize_params(params, mode="fp8")
    toks = jnp.asarray([[1, 5, 9, 13]], jnp.int32)
    ref, _ = llama.forward(params, cfg, toks)
    got, _ = llama.forward(qp, cfg, toks)
    ref_p = jax.nn.softmax(np.asarray(ref)[0, -1])
    got_p = jax.nn.softmax(np.asarray(got)[0, -1])
    assert np.abs(np.asarray(ref_p) - np.asarray(got_p)).max() < 0.15
    # same byte footprint as int8 weights
    q8 = quantize_params(params, mode="int8")
    assert quantized_bytes(qp) == quantized_bytes(q8)


# -- out-major attention projections (PR 41) -----------------------------


@pytest.mark.parametrize("mode", ["int8", "int4"])
def test_out_major_leaves_quantize_to_the_parent_forms_numbers(mode):
    """`wq` / `wk` / `wv` lie [L, heads, Dh, D] and reduce over their
    LAST dim (`_LAYER_CONTRACT`): the scales span the same values as
    the parent's [L, D, heads, Dh] leaf reduced over D, an int4 leaf's
    groups are the same 64 of D, so the dequantized leaf is the
    parent's, re-laid, to the bit; and the logits through the
    out-major dot are the parent form's (float32: up to the order of
    a sum)."""
    from _parent_proj import in_major, parent_form
    cfg = tiny_test().replace(dtype=jnp.float32)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    q = quantize_params(params, mode=mode, group=64)
    quantize = quantize_tensor if mode == "int8" else \
        (lambda w, axes: quantize_tensor_int4(w, axes, group=64))
    for name in ("wq", "wk", "wv"):
        leaf = q["layers"][name]
        assert isinstance(leaf, QTensor) and leaf.shape \
            == params["layers"][name].shape
        assert leaf.bits == (8 if mode == "int8" else 4)
        parent = quantize(in_major(params["layers"][name]), (1,))
        np.testing.assert_array_equal(
            np.asarray(in_major(leaf.dequant(jnp.float32))),
            np.asarray(parent.dequant(jnp.float32)))
        if mode == "int4":
            # packed along D, the minor dim: what the kernel's
            # out-major form reads (ops/int4_matmul.py)
            assert leaf.axis == -1 and leaf.q.shape[-1] \
                == cfg.hidden_size // 2
    tok = jnp.asarray([[1, 2, 3, 4, 5, 6, 7, 8]], jnp.int32)
    got, _ = llama.forward(q, cfg, tok)
    with parent_form():
        want, _ = llama.forward(q, cfg, tok)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5)
    low = jax.tree.map(
        lambda a: a.astype(jnp.bfloat16) if a.dtype == jnp.float32
        and a.ndim > 1 else a, params)
    qb = quantize_params(low, mode=mode, group=64)
    cb = cfg.replace(dtype=jnp.bfloat16)
    got, _ = llama.forward(qb, cb, tok)
    with parent_form():
        want, _ = llama.forward(qb, cb, tok)
    np.testing.assert_array_equal(np.asarray(got.astype(jnp.float32)),
                                  np.asarray(want.astype(jnp.float32)))
