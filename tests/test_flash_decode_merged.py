"""`flash_decode` over merged rows against the kernel it replaced, bit
for bit, and the writers and readers of a slab whose rows lie merged.

PR 38 re-laid the decode kernel's key / value blocks as dense
`[bs, K * D]` tiles of a slab `[.., S, K * D]` (a row's K heads side by
side in the lanes) and promised the parent's dots in the parent's
order: `tests/_parent_flash_decode.py` keeps the parent's kernel, and
both run here interpreted on the CPU. The layout is decided where a
cache is created (`llama.KVCache.create(merged=)`), the slab engine
asks for it and a paged engine's prefill does not: the last tests hold
the fence.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _parent_flash_decode import parent_flash_decode
from ome_tpu.engine.core import InferenceEngine
from ome_tpu.models import llama
from ome_tpu.models.config import ModelConfig
from ome_tpu.ops import flash
from ome_tpu.ops.attention import attention


def _rows(key, shape):
    return jax.random.normal(key, shape, jnp.float32).astype(jnp.bfloat16)


def _limits(lengths, window):
    pos = jnp.asarray(lengths, jnp.int32) - 1
    lo = jnp.maximum(pos - window + 1, 0) if window \
        else jnp.zeros_like(pos)
    return lo, pos + 1


# (H, K, D): smallthinker's 7 heads a KV head, trinity's 8, and the
# hybrid's full layers (16 heads on 2 KV heads of 256 dims)
SHAPES = [(28, 4, 128), (32, 4, 128), (16, 2, 256)]


@pytest.mark.parametrize("stacked", [False, True],
                         ids=["one_layer", "stacked"])
@pytest.mark.parametrize("S,window", [(8192, 4096), (4096, 2048),
                                      (2048, None)])
@pytest.mark.parametrize("H,K,D", SHAPES)
def test_merged_tiles_give_the_parents_bits(H, K, D, S, window, stacked):
    """A slot of length 0, one of a single row, two inside the slab
    (one past the window) and one at S: the new kernel on
    [.., S, K * D] equals the parent's on [.., S, K, D] to the bit."""
    lengths = [0, 1, S // 2 + 77, S - 3, S]
    B, L = len(lengths), 3
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(H + S), 3)
    q = _rows(kq, (B, 1, H, D))
    lead = (L, B, S) if stacked else (B, S)
    k, v = _rows(kk, lead + (K, D)), _rows(kv, lead + (K, D))
    lo, hi = _limits(lengths, window)
    layer = jnp.asarray(1, jnp.int32) if stacked else None
    want = parent_flash_decode(q, k, v, lo, hi, D ** -0.5, None, True,
                               layer=layer)
    got = flash._flash_decode(q, k.reshape(lead + (K * D,)),
                              v.reshape(lead + (K * D,)), lo, hi,
                              D ** -0.5, None, True, layer=layer)
    assert want is not None and got is not None
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))
    assert not np.asarray(got[0], np.float32).any()     # length 0


def test_softcap_and_scale_ride_along():
    """The logit softcap (gemma2) through the same dots."""
    H, K, D, S = 8, 4, 128, 512
    lengths = [5, 130, 200, 512]
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(7), 3)
    q, k, v = (_rows(kq, (4, 1, H, D)), _rows(kk, (4, S, K, D)),
               _rows(kv, (4, S, K, D)))
    lo, hi = _limits(lengths, 64)
    want = parent_flash_decode(q, k, v, lo, hi, 0.11, 30.0, True)
    got = flash._flash_decode(q, k.reshape(4, S, K * D),
                              v.reshape(4, S, K * D), lo, hi, 0.11, 30.0,
                              True)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


@pytest.mark.parametrize("case", ["H<8", "D%128", "S%128", "K*D"])
def test_declines_what_it_declined(case):
    """Shapes outside the kernel's coverage still come back None (the
    caller's `note_decline` counts them and XLA's attention runs)."""
    H, K, D, S = {"H<8": (4, 2, 128, 256), "D%128": (8, 4, 64, 256),
                  "S%128": (8, 4, 128, 200),
                  "K*D": (8, 4, 128, 256)}[case]
    width = K * D + (64 if case == "K*D" else 0)
    q = jnp.zeros((2, 1, H, D), jnp.bfloat16)
    kv = jnp.zeros((2, S, width), jnp.bfloat16)
    lo, hi = _limits([3, 9], None)
    assert flash._flash_decode(q, kv, kv, lo, hi, 1.0, None, True) is None


def test_int8_slab_takes_either_rank():
    """`flash_decode_quantized` keeps its quantizer's [B, S, K, D] and
    merges it on the way in; handed merged rows it reads them as they
    are: the same output."""
    B, S, H, K, D = 2, 256, 8, 4, 128
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(3), 3)
    q, k, v = (_rows(kq, (B, 1, H, D)), _rows(kk, (B, S, K, D)),
               _rows(kv, (B, S, K, D)))
    k8, ks = flash.quantize_kv_block(k)
    v8, vs = flash.quantize_kv_block(v)
    pos = jnp.asarray([[100], [255]], jnp.int32)
    apart = flash.flash_decode_quantized(q, k8, v8, ks, vs, pos,
                                         interpret=True)
    merged = flash.flash_decode_quantized(
        q, k8.reshape(B, S, K * D), v8.reshape(B, S, K * D), ks, vs, pos,
        interpret=True)
    np.testing.assert_array_equal(np.asarray(apart, np.float32),
                                  np.asarray(merged, np.float32))


# -- the writers, in both layouts ------------------------------------------


def _slabs(L, B, S, K, D, key):
    apart = _rows(key, (L, B, S, K, D))
    return apart, apart.reshape(L, B, S, K * D)


@pytest.mark.parametrize("per_slot", [False, True],
                         ids=["one_index", "per_slot"])
def test_write_rows_round_trip(per_slot):
    """Fresh [B, S, K, D] rows land in the merged slab where they land
    in the apart one, in place of the same rows and no others."""
    L, B, S, K, D = 3, 4, 32, 2, 16
    apart, merged = _slabs(L, B, S, K, D, jax.random.PRNGKey(0))
    rows = _rows(jax.random.PRNGKey(1), (B, 3, K, D))
    index = jnp.asarray([0, 5, 29, 11], jnp.int32) if per_slot \
        else jnp.asarray(7, jnp.int32)
    a = llama._write_rows(apart, rows, 1, index)
    m = llama._write_rows(merged, rows, 1, index)
    assert m.shape == merged.shape
    np.testing.assert_array_equal(np.asarray(m, np.float32),
                                  np.asarray(a, np.float32)
                                  .reshape(m.shape))
    at = np.asarray(index) if per_slot else np.full(B, 7)
    for b in range(B):
        np.testing.assert_array_equal(
            np.asarray(m[1, b, at[b]:at[b] + 3], np.float32),
            np.asarray(rows[b], np.float32).reshape(3, K * D))
    assert int((np.asarray(m, np.float32)
                != np.asarray(merged, np.float32)).any(-1).sum()) <= B * 3


@pytest.mark.parametrize("frozen", [False, True], ids=["live", "frozen"])
def test_ring_write_wraps_in_both_layouts(frozen):
    """Decode steps from position W - 3 on wrap the ring: position p in
    row p % W of the merged ring as of the apart one, a frozen slot's
    ring untouched, and `flash_decode` over the merged ring gives the
    parent's bits over the apart one at every step."""
    Lw, B, W, K, D, H = 2, 2, 128, 2, 128, 8
    apart, merged = _slabs(Lw, B, W, K, D, jax.random.PRNGKey(4))
    valid = jnp.asarray([1, 0], jnp.int32) if frozen else None
    for step, p in enumerate(range(W - 3, W + 4)):
        kk, kv, kq = jax.random.split(jax.random.PRNGKey(100 + step), 3)
        k, v = _rows(kk, (B, 1, K, D)), _rows(kv, (B, 1, K, D))
        index = jnp.asarray([p, p - 40], jnp.int32)
        a = llama._ring_write(
            k, v, llama.SlabLayer(apart, apart, 1, True, valid), index, W)
        m = llama._ring_write(
            k, v, llama.SlabLayer(merged, merged, 1, True, valid), index,
            W)
        np.testing.assert_array_equal(
            np.asarray(m[0], np.float32),
            np.asarray(a[0], np.float32).reshape(m[0].shape))
        np.testing.assert_array_equal(
            np.asarray(m[0][1, 0, p % W], np.float32),
            np.asarray(k[0, 0], np.float32).reshape(K * D))
        if frozen:
            np.testing.assert_array_equal(
                np.asarray(m[0][:, 1], np.float32),
                np.asarray(merged[:, 1], np.float32))
        apart, merged = a[0], m[0]
        q = _rows(kq, (B, 1, H, D))
        lo = jnp.zeros((B,), jnp.int32)
        hi = jnp.minimum(index + 1, W)
        layer = jnp.asarray(1, jnp.int32)
        np.testing.assert_array_equal(
            np.asarray(flash._flash_decode(
                q, merged, merged, lo, hi, D ** -0.5, None, True,
                layer=layer), np.float32),
            np.asarray(parent_flash_decode(
                q, apart, apart, lo, hi, D ** -0.5, None, True,
                layer=layer), np.float32))


@pytest.mark.parametrize("n", [5, 8, 13, 21])
def test_a_prompts_ring_in_both_layouts(n):
    """What a ring holds after a fresh prompt of n positions (W = 8:
    under, at and past a wrap) is the same rows merged."""
    Lw, B, W, K, D, S = 2, 1, 8, 2, 16, 24
    apart, merged = _slabs(Lw, B, W, K, D, jax.random.PRNGKey(5))
    k, v = (_rows(jax.random.PRNGKey(6), (B, S, K, D)),
            _rows(jax.random.PRNGKey(7), (B, S, K, D)))
    valid, index = jnp.asarray([n], jnp.int32), jnp.zeros((), jnp.int32)
    a = llama._ring_write(
        k, v, llama.SlabLayer(apart, apart, 0, True, valid), index, W)
    m = llama._ring_write(
        k, v, llama.SlabLayer(merged, merged, 0, True, valid), index, W)
    for x, y in zip(a, m):
        np.testing.assert_array_equal(
            np.asarray(y, np.float32),
            np.asarray(x, np.float32).reshape(y.shape))
    for p in range(max(n - W, 0), n):
        np.testing.assert_array_equal(
            np.asarray(m[0][0, 0, p % W], np.float32),
            np.asarray(k[0, p], np.float32).reshape(K * D))


# -- the engine: who asks for which layout ----------------------------------


def _tiny(**over):
    hf = dict(architectures=[], hidden_size=64, num_hidden_layers=2,
              num_attention_heads=4, num_key_value_heads=2, head_dim=16,
              intermediate_size=128, vocab_size=97,
              max_position_embeddings=256, rms_norm_eps=1e-6,
              torch_dtype="float32")
    hf.update(over)
    return ModelConfig.from_hf_config(hf)


@pytest.fixture(scope="module")
def tiny():
    cfg = _tiny()
    return cfg, llama.init_params(jax.random.PRNGKey(0), cfg)


def _prefill_shapes(engine, bucket):
    V = engine.cfg.vocab_size
    one = lambda dt: jax.ShapeDtypeStruct((1,), dt)     # noqa: E731
    return jax.eval_shape(
        lambda *a: engine._prefill_fn(*a, bucket=bucket), engine.params,
        jax.ShapeDtypeStruct((1, bucket), jnp.int32), one(jnp.int32),
        one(jnp.float32), one(jnp.int32), one(jnp.float32),
        jax.ShapeDtypeStruct((2,), jnp.uint32), one(jnp.int32))


@pytest.mark.parametrize("bucket", [16, 64])
def test_a_paged_engines_prefill_keeps_its_heads_apart(tiny, bucket):
    """The fence: a paged engine's prefill hands `_insert_paged`
    [L, 1, bucket, K, D], the pool's blocks [L, N, block, K, D] stay,
    and a slab engine's hands merged rows to a merged slab."""
    cfg, p = tiny
    L, K, D = cfg.num_layers, cfg.num_kv_heads, cfg.head_dim
    paged = InferenceEngine(p, cfg, max_slots=2, max_seq=64, kv_block=16,
                            kv_blocks=9, prefill_buckets=[16, 64])
    _, k, v = _prefill_shapes(paged, bucket)
    assert k.shape == v.shape == (L, 1, bucket, K, D)
    pool = jax.eval_shape(paged.new_state)
    assert pool.k.shape == (L, 9, 16, K, D)
    slab = InferenceEngine(p, cfg, max_slots=2, max_seq=64,
                           prefill_buckets=[16, 64])
    _, k, v = _prefill_shapes(slab, bucket)
    assert k.shape == v.shape == (L, 1, bucket, K * D)
    assert jax.eval_shape(slab.new_state).k.shape == (L, 2, 64, K * D)


def test_insert_round_trip_in_the_merged_slab(tiny):
    """A prompt's rows reach the slot they were inserted into, merged,
    whether the prefill hands them merged (its own) or heads apart (off
    the wire, engine/pd.py), and decode over them gives the tokens of
    the same prompt decoded from a plain heads-apart cache."""
    from ome_tpu.engine import pd
    cfg, p = tiny
    K, D = cfg.num_kv_heads, cfg.head_dim
    eng = InferenceEngine(p, cfg, max_slots=3, max_seq=64,
                          prefill_buckets=[16, 64])
    ids = [int(t) for t in np.random.RandomState(0).randint(1, 97, 11)]
    tok, kv, n, bucket = eng.prefill(ids)
    assert kv[0].shape == (cfg.num_layers, 1, 16, K * D)
    st = eng.insert(eng.new_state(), kv, 1, n, tok, bucket)
    np.testing.assert_array_equal(np.asarray(st.k[:, 1, :n]),
                                  np.asarray(kv[0][:, 0, :n]))
    wire = tuple(pd.wire_rows(x, K) for x in kv[:2])
    assert wire[0].shape == (cfg.num_layers, 1, 16, K, D)
    st2 = eng.insert(eng.new_state(), wire, 1, n, tok, bucket)
    np.testing.assert_array_equal(np.asarray(st2.k), np.asarray(st.k))
    np.testing.assert_array_equal(np.asarray(st2.v), np.asarray(st.v))
    # the same prompt through the plain cache, heads apart
    cache = llama.KVCache.create(cfg, 1, 64)
    lg, cache = llama.forward(p, cfg, jnp.asarray([ids]), cache=cache)
    assert tok == int(lg[0, -1].argmax())
    np.testing.assert_allclose(
        np.asarray(st.k[:, 1, :n]),
        np.asarray(cache.k[:, 0, :n]).reshape(cfg.num_layers, n, K * D),
        atol=1e-5)
    greedy = (np.zeros(3, np.float32), np.zeros(3, np.int32),
              np.ones(3, np.float32))
    seq = [tok]
    for _ in range(4):
        st, toks = eng.decode(st, *greedy)
        seq.append(int(np.asarray(toks)[1]))
        lg, cache = llama.forward(p, cfg, jnp.asarray([[seq[-2]]]),
                                  cache=cache)
        assert seq[-1] == int(lg[0, -1].argmax())


@pytest.mark.parametrize("stacked", [False, True],
                         ids=["one_layer", "stacked"])
def test_xla_attention_reads_merged_rows(stacked):
    """The path the kernel's declines take: `attention` over merged
    rows equals itself over the same rows heads apart."""
    B, S, H, K, D = 2, 48, 4, 2, 16
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(9), 3)
    lead = (3, B, S) if stacked else (B, S)
    q = jax.random.normal(kq, (B, 1, H, D))
    k = jax.random.normal(kk, lead + (K, D))
    v = jax.random.normal(kv, lead + (K, D))
    pos = jnp.asarray([[40], [7]], jnp.int32)
    kw = dict(positions=pos, kv_len=pos[:, 0] + 1, sliding_window=16,
              backend="xla", layer=2 if stacked else None)
    np.testing.assert_array_equal(
        np.asarray(attention(q, k.reshape(lead + (K * D,)),
                             v.reshape(lead + (K * D,)), **kw)),
        np.asarray(attention(q, k, v, **kw)))


def test_a_peers_prefix_joins_the_merged_trie(tiny):
    """KV a peer sent (the wire's rows, heads apart) seeds the prefix
    cache of a slab engine as merged blocks: a prompt that extends it
    hits them, runs only its suffix and hands back the rows of the
    same prompt prefilled whole, and blocks of both origins
    concatenate in one later hit."""
    from ome_tpu.engine import pd
    cfg, p = tiny
    kw = dict(max_slots=2, max_seq=256, prefill_buckets=[64, 128, 256])
    eng = InferenceEngine(p, cfg, prefix_cache_bytes=1 << 24, **kw)
    donor = InferenceEngine(p, cfg, **kw)
    ids = [int(t) for t in np.random.RandomState(0).randint(1, 97, 100)]
    _, kv, n, bucket = donor.prefill(ids[:70])
    wire = [jnp.asarray(pd.wire_rows(x, cfg.num_kv_heads)) for x in kv[:2]]
    assert wire[0].ndim == 5
    eng.prefix_cache.put(ids[:70], wire[0], wire[1], n, bucket)
    tok, got, n, _ = eng.prefill(ids)
    want_tok, want, _, _ = donor.prefill(ids)
    assert eng.prefix_cache.hits == 1 and tok == want_tok
    assert got[0].shape == want[0].shape and got[0].ndim == 4
    np.testing.assert_allclose(np.asarray(got[0][:, :, :n]),
                               np.asarray(want[0][:, :, :n]), atol=1e-5)
    tok, got, n, _ = eng.prefill(ids + [5, 6, 7])
    assert eng.prefix_cache.hits == 2
    want_tok, want, _, _ = donor.prefill(ids + [5, 6, 7])
    assert tok == want_tok
    np.testing.assert_allclose(np.asarray(got[1][:, :, :n]),
                               np.asarray(want[1][:, :, :n]), atol=1e-5)
