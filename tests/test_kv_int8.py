"""int8-quantized paged KV blocks (--kv-dtype int8, docs/kv-hierarchy.md).

The pool stores 1 byte/element plus per-(row, head) f32 scales instead
of the model dtype — ~2x the resident sequences per HBM byte at
Dh=128. These tests pin the contract that makes the flag deployable:

  * numerics: the quantized XLA path is EXACTLY dense attention over
    the dequantized gather, the Pallas kernel agrees with it, and the
    whole path sits within int8 quantization error of the fp32 pool;
  * greedy streams are deterministic across runs (incl. slot reuse
    and block-boundary growth) and agree with the dense engine on
    every first token (prefill logits never see the quantized pool);
  * the multi-token device decode program (steps_per_dispatch > 1)
    carries the scale planes through its fused sample/append loop;
  * the state layout: int8 pool + two DISTINCT f32 scale buffers
    (donation refuses aliased arguments);
  * the byte model: kv_row_bytes() halves at bf16 (the capacity win
    bench.py's paged_sweep measures) and the accounting follows;
  * the flag is refused without the paged pool and for unknown dtypes.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ome_tpu.engine.core import InferenceEngine
from ome_tpu.engine.scheduler import Request, Scheduler
from ome_tpu.engine.tokenizer import ByteTokenizer
from ome_tpu.models import llama
from ome_tpu.models.config import tiny_test

CFG = tiny_test().replace(dtype=jnp.float32, max_seq_len=128)


@pytest.fixture(scope="module")
def params():
    return llama.init_params(jax.random.PRNGKey(0), CFG)


@pytest.fixture(scope="module")
def int8_eng(params):
    """One int8 paged engine shared by the stream tests (compiled
    programs are per-engine; sequential Scheduler runs on one engine
    are the production lifecycle)."""
    return InferenceEngine(params, CFG, max_slots=4,
                           prefill_buckets=[16, 32], kv_block=16,
                           kv_dtype="int8")


def _run(engine, prompts, max_new=24, steps_per_dispatch=1):
    tok = ByteTokenizer()
    sched = Scheduler(engine, steps_per_dispatch=steps_per_dispatch)
    reqs = [sched.submit(Request(prompt_ids=tok.encode(p),
                                 max_new_tokens=max_new,
                                 temperature=0.0,
                                 stop_ids=[tok.eos_id]))
            for p in prompts]
    while not all(r.done.is_set() for r in reqs):
        sched.step()
    return [r.output_ids for r in reqs]


PROMPTS = ["hello world", "a", "the quick brown fox jumps over",
           "xyzzy plugh abc", "short", "another prompt here",
           "yet more text", "z"]


def _quantize_pool(pool):
    """amax/127 per (row, head) over the feature axis; scales in the
    S-minor [L, N, K, bs] layout the kernel's BlockSpec streams."""
    x = np.asarray(pool, np.float32)                  # [L, N, bs, K, D]
    amax = np.abs(x).max(axis=-1)                     # [L, N, bs, K]
    sc = np.maximum(amax, 1e-8) / 127.0
    q = np.clip(np.rint(x / sc[..., None]), -127, 127).astype(np.int8)
    return jnp.asarray(q), jnp.asarray(np.swapaxes(sc, -1, -2))


LAYERS = 3


class TestQuantizedPagedNumerics:
    def _pool(self, rng, B, H, K, D, bs, M, N):
        q = jnp.asarray(rng.standard_normal((B, 1, H, D)), jnp.float32)
        kp = jnp.asarray(rng.standard_normal((LAYERS, N, bs, K, D)),
                         jnp.float32)
        vp = jnp.asarray(rng.standard_normal((LAYERS, N, bs, K, D)),
                         jnp.float32)
        # never block 0: the kernel ends a slot's walk at the trash block
        ids = (rng.permutation(N - 1)[:B * M] + 1).reshape(B, M)
        return q, kp, vp, jnp.asarray(ids, jnp.int32)

    @pytest.mark.parametrize("layer", range(LAYERS))
    def test_xla_quantized_is_exact_dequant_and_close_to_fp32(self,
                                                              layer):
        from ome_tpu.ops.attention import attention
        from ome_tpu.ops.paged import paged_attention_xla
        rng = np.random.default_rng(0)
        B, H, K, D, bs, M, N = 4, 16, 8, 128, 128, 4, 32
        q, kp, vp, table = self._pool(rng, B, H, K, D, bs, M, N)
        kv_len = jnp.asarray([5, 128, 200, 512], jnp.int32)
        kq, ksc = _quantize_pool(kp)
        vq, vsc = _quantize_pool(vp)
        out = paged_attention_xla(q, kq, vq, table, kv_len, layer,
                                  k_scale=ksc, v_scale=vsc)
        # exact: dense attention over the layer's explicitly
        # dequantized pool
        deq_k = (np.asarray(kq[layer], np.float32)
                 * np.swapaxes(np.asarray(ksc[layer]), 1, 2)[..., None])
        deq_v = (np.asarray(vq[layer], np.float32)
                 * np.swapaxes(np.asarray(vsc[layer]), 1, 2)[..., None])
        kg = jnp.take(jnp.asarray(deq_k), table,
                      axis=0).reshape(B, M * bs, K, D)
        vg = jnp.take(jnp.asarray(deq_v), table,
                      axis=0).reshape(B, M * bs, K, D)
        ref = attention(q, kg, vg, positions=(kv_len - 1)[:, None],
                        kv_len=kv_len, backend="xla")
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-6)
        # and within int8 quantization error of the fp32 pool
        full = paged_attention_xla(q, kp, vp, table, kv_len, layer)
        np.testing.assert_allclose(np.asarray(out), np.asarray(full),
                                   atol=5e-2)

    @pytest.mark.parametrize("layer", range(LAYERS))
    def test_pallas_kernel_matches_quantized_xla(self, layer):
        from ome_tpu.ops.paged import (paged_attention_xla,
                                       paged_flash_decode)
        rng = np.random.default_rng(1)
        B, H, K, D, bs, M, N = 4, 16, 8, 128, 128, 4, 32
        q, kp, vp, table = self._pool(rng, B, H, K, D, bs, M, N)
        kv_len = jnp.asarray([1, 100, 256, 512], jnp.int32)
        kq, ksc = _quantize_pool(kp)
        vq, vsc = _quantize_pool(vp)
        out = jax.jit(lambda l: paged_flash_decode(
            q, kq, vq, table, kv_len, l, k_scale=ksc, v_scale=vsc,
            interpret=True))(jnp.int32(layer))
        ref = paged_attention_xla(q, kq, vq, table, kv_len, layer,
                                  k_scale=ksc, v_scale=vsc)
        # same tolerance as the unquantized kernel-vs-XLA test: the
        # CPU build's default f32 matmul is reduced-precision
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-2)


def test_int8_streams_deterministic_first_tokens_match_dense(
        params, int8_eng):
    """Greedy int8 streams are run-to-run deterministic across slot
    reuse (8 requests through 4 slots) and block-boundary growth (18
    new tokens cross the 16-token block repeatedly); the first token
    of every request matches the dense engine exactly (prefill logits
    are computed in the model dtype before the pool quantizes). Later
    tokens sit within int8 error of dense — on a random tiny model
    near-tied logits may argmax differently, so token-level identity
    is pinned where it is guaranteed, numerics where it is not
    (TestQuantizedPagedNumerics)."""
    dense = InferenceEngine(params, CFG, max_slots=4,
                            prefill_buckets=[16, 32])
    out_d = _run(dense, PROMPTS, max_new=18)
    out_q = _run(int8_eng, PROMPTS, max_new=18)
    assert [o[0] for o in out_q] == [o[0] for o in out_d]
    assert all(len(o) == 18 for o in out_q)
    # every block returned to the pool after the last request
    assert int8_eng.kv_pool_stats["kv_blocks_free"] == \
        int8_eng.kv_blocks - 1
    # determinism: a fresh engine over the same params replays the
    # exact streams (the chaos oracle's byte-identity relies on this)
    int8b = InferenceEngine(params, CFG, max_slots=4,
                            prefill_buckets=[16, 32], kv_block=16,
                            kv_dtype="int8")
    assert _run(int8b, PROMPTS, max_new=18) == out_q


def test_int8_multistep_decode_matches_single_step(int8_eng):
    """The fused K-iteration decode program quantizes each appended
    row exactly like the single-step program: same tokens either
    way."""
    assert _run(int8_eng, PROMPTS[:4], max_new=17) == \
        _run(int8_eng, PROMPTS[:4], max_new=17, steps_per_dispatch=4)


def test_int8_pool_layout(params):
    """Pool dtype int8, per-(layer, block, head, row) f32 scales as
    two DISTINCT buffers (the decode programs donate the whole state;
    XLA refuses aliased donated arguments)."""
    eng = InferenceEngine(params, CFG, max_slots=2,
                          prefill_buckets=[16], kv_block=16,
                          kv_dtype="int8")
    st = eng.new_state()
    assert st.k.dtype == jnp.int8 and st.v.dtype == jnp.int8
    want = (CFG.num_layers, eng.kv_blocks, CFG.kv_cache_heads,
            eng.kv_block)
    assert st.k_scale.shape == want and st.k_scale.dtype == jnp.float32
    assert st.v_scale.shape == want and st.v_scale.dtype == jnp.float32
    assert st.k_scale is not st.v_scale
    # the bf16/fp32 pool carries no scale planes at all
    plain = InferenceEngine(params, CFG, max_slots=2,
                            prefill_buckets=[16], kv_block=16)
    stp = plain.new_state()
    assert stp.k_scale is None and stp.v_scale is None
    # at equal block counts the int8 pool plane is itemsize-times
    # smaller than the model-dtype plane
    ratio = jnp.dtype(CFG.dtype).itemsize
    assert stp.k.nbytes == ratio * st.k.nbytes * \
        (plain.kv_blocks / eng.kv_blocks)


def test_kv_row_bytes_byte_model(params):
    """kv_row_bytes() is the single per-token byte model shared by the
    cost ledger and HBM attribution: int8 rows cost bytes + 8 scale
    bytes per (layer, head); at bf16/Dh=128 the ratio is >= 1.9 (the
    ISSUE acceptance 'HBM per cached token halved')."""
    eng = InferenceEngine(params, CFG, max_slots=2,
                          prefill_buckets=[16], kv_block=16,
                          kv_dtype="int8")
    L, K = CFG.num_layers, CFG.kv_cache_heads
    dkv = CFG.kv_cache_k_dim + CFG.kv_cache_v_dim
    assert eng.kv_row_bytes() == L * K * (dkv + 8)
    plain = InferenceEngine(params, CFG, max_slots=2,
                            prefill_buckets=[16], kv_block=16)
    assert plain.kv_row_bytes() == L * K * dkv * 4  # fp32 test dtype
    # serving shape: bf16 model dtype, Dh=128 heads
    big = tiny_test().replace(dtype=jnp.bfloat16, head_dim=128,
                              max_seq_len=128)
    bparams = llama.init_params(jax.random.PRNGKey(0), big)
    b16 = InferenceEngine(bparams, big, max_slots=2,
                          prefill_buckets=[16], kv_block=16)
    bq = InferenceEngine(bparams, big, max_slots=2,
                         prefill_buckets=[16], kv_block=16,
                         kv_dtype="int8")
    cap = b16.kv_row_bytes() / bq.kv_row_bytes()
    assert cap >= 1.9, cap


def test_int8_refused_without_paged_pool(params):
    with pytest.raises(ValueError, match="kv-block|paged"):
        InferenceEngine(params, CFG, max_slots=2,
                        prefill_buckets=[16], kv_dtype="int8")
    with pytest.raises(ValueError, match="kv_dtype"):
        InferenceEngine(params, CFG, max_slots=2,
                        prefill_buckets=[16], kv_block=16,
                        kv_dtype="fp8")


def test_quantize_dequantize_value_stability():
    """The amax/127 rule is value-stable across a dequantize /
    re-quantize round trip — what makes a peer-fetched (wire-
    dequantized) prefix produce the same pool bytes as a locally
    computed one (docs/kv-hierarchy.md, 'Composing the tiers')."""
    rng = np.random.default_rng(3)
    x = rng.standard_normal((4, 8, 16)).astype(np.float32)

    def q(a):
        amax = np.max(np.abs(a), axis=-1, keepdims=True)
        sc = np.maximum(amax, 1e-8) / 127.0
        return np.clip(np.rint(a / sc), -127, 127).astype(np.int8), sc

    q1, s1 = q(x)
    q2, s2 = q(q1.astype(np.float32) * s1)
    np.testing.assert_array_equal(q1, q2)
    np.testing.assert_allclose(s1, s2, rtol=1e-6)
