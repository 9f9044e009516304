"""Fused int4 matmul kernel (ops/int4_matmul.py): interpret-mode
numerics against the dequantized reference for every weight layout the
model routes through it, plus the dispatch (fallback) rules."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ome_tpu.models.quant import quantize_tensor_int4
from ome_tpu.ops.int4_matmul import flatten_qtensor, int4_matmul


def _check(x, w, contract_axes, group, out_major=False):
    qt = quantize_tensor_int4(jnp.asarray(w), contract_axes,
                              group=group)
    K = x.shape[-1]
    deq = np.asarray(qt.dequant(jnp.float32))
    want = x.astype(np.float32) @ (deq.reshape(-1, K).T if out_major
                                   else deq.reshape(K, -1))
    got = int4_matmul(jnp.asarray(x), qt, jnp.float32, interpret=True)
    assert got is not None, "kernel unexpectedly fell back"
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-2,
                               atol=2e-2 * np.abs(want).max())


@pytest.mark.parametrize("k", [1024, 2560, 4864])
def test_kernel_matches_dequant_gate_layout(k):
    # w_gate-style [K, N], pack axis leading. K=2560 (Qwen3-4B's
    # hidden) has 10 scale groups a nibble half in one k-step, 4864
    # has 19 in steps of one: neither is a multiple of 8 sublanes,
    # which the scale blocks' [half*steps, groups, N] view allows
    rng = np.random.default_rng(0)
    _check(rng.standard_normal((16, k), dtype=np.float32),
           rng.standard_normal((k, 256), dtype=np.float32),
           contract_axes=(0,), group=128)


@pytest.mark.parametrize("k,heads", [(1024, 2), (2560, 4), (4096, 2)])
def test_kernel_matches_dequant_out_major_layout(k, heads):
    # wq-style [heads, Dh, K], pack axis LAST (llama._proj's out-major
    # leaves): a row's nibbles along the lanes, the scales [N, K/G]
    # re-laid to a block's [N, groups]; 10 groups a half in one k-step
    # at K=2560, two k-steps of 8 at K=4096
    rng = np.random.default_rng(4)
    _check(rng.standard_normal((16, k), dtype=np.float32),
           rng.standard_normal((heads, 128, k), dtype=np.float32),
           contract_axes=(2,), group=128, out_major=True)


def test_wo_layout_falls_back_and_dequants_right():
    # wo-style [H, Dh, D] packs Dh UNDER the H dim: the half-packed
    # flattened rows aren't contiguous, so the kernel must decline
    # (quantize_params keeps wo at int8; this guards the dispatch) —
    # while plain dequant still reproduces the weight
    rng = np.random.default_rng(1)
    w = rng.standard_normal((8, 128, 256), dtype=np.float32)
    qt = quantize_tensor_int4(jnp.asarray(w), contract_axes=(1, 0),
                              group=128)
    x = rng.standard_normal((16, 8 * 128), dtype=np.float32)
    got = int4_matmul(jnp.asarray(x), qt, jnp.float32, interpret=True)
    assert got is None
    err = np.abs(np.asarray(qt.dequant(jnp.float32)) - w)
    # half a 4-bit grid step at the observed dynamic range
    assert err.max() <= np.abs(w).max() / 7 * 0.51


def test_kernel_pads_ragged_batch():
    rng = np.random.default_rng(2)
    _check(rng.standard_normal((5, 1024), dtype=np.float32),
           rng.standard_normal((1024, 256), dtype=np.float32),
           contract_axes=(0,), group=128)


def test_fallback_rules():
    rng = np.random.default_rng(3)
    w = rng.standard_normal((192, 256), dtype=np.float32)
    qt = quantize_tensor_int4(jnp.asarray(w), (0,), group=64)
    # K=192 not divisible by BK=8*64=512 -> fallback
    assert int4_matmul(jnp.ones((4, 192)), qt, interpret=True) is None
    # batch beyond MAX_M (prefill-sized) -> fallback
    w2 = rng.standard_normal((1024, 256), dtype=np.float32)
    qt2 = quantize_tensor_int4(jnp.asarray(w2), (0,), group=128)
    assert int4_matmul(jnp.ones((512, 1024)), qt2,
                       interpret=True) is None
    # int8 leaves never route here
    from ome_tpu.models.quant import quantize_tensor
    qt8 = quantize_tensor(jnp.asarray(w2), (0,))
    assert flatten_qtensor(qt8) is None


def test_flattened_views_dequantize_exactly():
    """flatten_qtensor's 2D views must reconstruct QTensor.dequant
    bit-for-bit for every layout _proj routes through the kernel."""
    from ome_tpu.models import llama
    from ome_tpu.models.config import tiny_test
    from ome_tpu.models.quant import quantize_params
    cfg = tiny_test().replace(hidden_size=1024, intermediate_size=1024,
                              num_layers=2, num_heads=8, num_kv_heads=8,
                              head_dim=128, dtype=jnp.float32)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    q4 = quantize_params(params, mode="int4", group=128)
    # wo stays int8 under mode="int4" (its pack axis sits under H) —
    # the kernel-eligible leaves pack their first dim (in-major: the
    # MLP's) or their last (out-major: the attention projections)
    for name in ("wq", "wk", "wv", "w_gate", "w_up"):
        qt = jax.tree.map(lambda a: a[0], q4["layers"][name])
        flat = flatten_qtensor(qt)
        assert flat is not None, name
        qp2, s2, K, N, gsize, out_major = flat
        assert out_major == (name in llama.OUT_MAJOR), name
        deq = np.asarray(qt.dequant(jnp.float32))
        # reconstruct from the 2D views exactly as the kernel does:
        # low nibbles = rows [0, K/2), high nibbles = rows [K/2, K)
        # of the contraction, which is the views' first dim in-major
        # and their last out-major
        ax = 1 if out_major else 0
        deq = deq.reshape((N, K) if out_major else (K, N))
        qp = np.asarray(qp2).astype(np.int32)
        lo = (qp << 28) >> 28
        hi = qp >> 4
        w = np.concatenate([lo, hi], axis=ax)
        rebuilt = w * np.repeat(np.asarray(s2), gsize, axis=ax)
        np.testing.assert_allclose(rebuilt, deq, rtol=1e-6)
    from ome_tpu.models.quant import QTensor
    assert isinstance(q4["layers"]["wo"], QTensor)
    assert q4["layers"]["wo"].bits == 8


def test_model_forward_via_kernel_matches_dequant_path(monkeypatch):
    """The REAL dispatch: with OME_INT4_KERNEL_INTERPRET the model
    forward runs _proj's kernel branch (q/k/v, the flatten=2 wo route,
    gate/up — out_dims reshapes included) and must match the XLA
    dequant path's logits. Catches wiring bugs that would otherwise
    only surface as corrupted logits on real hardware."""
    from ome_tpu.models import llama
    from ome_tpu.models.config import tiny_test
    from ome_tpu.models.quant import quantize_params
    cfg = tiny_test().replace(hidden_size=1024, intermediate_size=1024,
                              num_layers=2, num_heads=8, num_kv_heads=8,
                              head_dim=128, max_seq_len=64,
                              dtype=jnp.float32)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    q4 = quantize_params(params, mode="int4", group=128)
    tok = jnp.asarray([[1, 5, 9, 2]], jnp.int32)
    ref, _ = llama.forward(q4, cfg, tok)          # XLA dequant path
    monkeypatch.setenv("OME_INT4_KERNEL_INTERPRET", "1")
    got, _ = llama.forward(q4, cfg, tok)          # kernel path
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-2, atol=2e-2)
