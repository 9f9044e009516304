"""Numerics tests: Pallas flash-attention kernels vs the XLA reference.

The kernels run in interpret mode on the CPU test mesh — same code
path that compiles on TPU, checked here for numerical agreement with
ops.attention.xla_attention across the model-relevant cases: decode
(Sq=1, per-slot lengths), causal prefill, chunked prefill (nonzero
position base into a longer cache), sliding window, logit softcap,
and GQA group sizes.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ome_tpu.ops.attention import attention

ATOL = {jnp.bfloat16: 2e-2, jnp.float32: 2e-4}


def _mk(key, B, Sq, Skv, H, K, D, dtype):
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, Sq, H, D), jnp.float32).astype(dtype)
    k = jax.random.normal(kk, (B, Skv, K, D), jnp.float32).astype(dtype)
    v = jax.random.normal(kv, (B, Skv, K, D), jnp.float32).astype(dtype)
    return q, k, v


def _check(q, k, v, positions, kv_len, atol, **kw):
    out = attention(q, k, v, positions=positions, kv_len=kv_len,
                    backend="pallas_interpret", **kw)
    ref = attention(q, k, v, positions=positions, kv_len=kv_len,
                    backend="xla", **kw)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=atol)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_flash_decode_matches_xla(dtype):
    B, S, H, K, D = 4, 256, 8, 4, 128
    q, k, v = _mk(jax.random.PRNGKey(0), B, 1, S, H, K, D, dtype)
    lengths = jnp.asarray([1, 77, 128, 256], jnp.int32)
    positions = (lengths - 1)[:, None]
    _check(q, k, v, positions, lengths, ATOL[dtype])


def test_flash_decode_sliding_window_and_softcap():
    B, S, H, K, D = 4, 256, 8, 8, 128
    q, k, v = _mk(jax.random.PRNGKey(1), B, 1, S, H, K, D, jnp.bfloat16)
    lengths = jnp.asarray([5, 130, 200, 256], jnp.int32)
    positions = (lengths - 1)[:, None]
    _check(q, k, v, positions, lengths, ATOL[jnp.bfloat16],
           sliding_window=64, logit_softcap=30.0)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_flash_prefill_causal_matches_xla(dtype):
    B, S, H, K, D = 2, 64, 8, 4, 128
    q, k, v = _mk(jax.random.PRNGKey(2), B, S, S, H, K, D, dtype)
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    _check(q, k, v, positions, None, ATOL[dtype])


def test_flash_prefill_chunked_into_cache():
    # chunk of 32 queries writing at per-batch offsets into a 128-slot
    # cache: attends to everything before it plus itself, causally
    B, Sq, Skv, H, K, D = 2, 32, 128, 8, 4, 128
    q, k, v = _mk(jax.random.PRNGKey(3), B, Sq, Skv, H, K, D, jnp.bfloat16)
    base = jnp.asarray([0, 64], jnp.int32)
    positions = base[:, None] + jnp.arange(Sq, dtype=jnp.int32)[None, :]
    kv_len = base + Sq
    _check(q, k, v, positions, kv_len, ATOL[jnp.bfloat16])


def test_flash_prefill_sliding_window_softcap_mha():
    B, S, H, K, D = 2, 64, 8, 8, 128
    q, k, v = _mk(jax.random.PRNGKey(4), B, S, S, H, K, D, jnp.bfloat16)
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    _check(q, k, v, positions, None, ATOL[jnp.bfloat16],
           sliding_window=16, logit_softcap=50.0)


def test_flash_fallback_on_unsupported_shapes():
    # head_dim 64 isn't covered -> flash returns None -> XLA result
    B, S, H, K, D = 2, 64, 8, 4, 64
    q, k, v = _mk(jax.random.PRNGKey(5), B, S, S, H, K, D, jnp.float32)
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    out = attention(q, k, v, positions=positions, backend="pallas_interpret")
    ref = attention(q, k, v, positions=positions, backend="xla")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6)


def test_flash_decode_quantized_matches_xla():
    """int8 KV cache decode kernel (--kv-cache-dtype int8): dequantized
    attention must match the XLA reference over the SAME dequantized
    values (quantization error itself is excluded by comparing against
    dequant(kq) rather than the original k)."""
    from ome_tpu.ops.attention import attention
    from ome_tpu.ops.flash import flash_decode_quantized, quantize_kv_block
    B, S, H, K, D = 4, 256, 8, 4, 128
    q, k, v = _mk(jax.random.PRNGKey(3), B, 1, S, H, K, D, jnp.float32)
    lengths = jnp.asarray([1, 77, 190, 256], jnp.int32)
    positions = (lengths - 1)[:, None]
    kq, ks = quantize_kv_block(k)
    vq, vs = quantize_kv_block(v)
    out = flash_decode_quantized(q, kq, vq, ks, vs,
                                 positions=positions, kv_len=lengths,
                                 interpret=True)
    # reference: XLA attention over the dequantized cache
    kd = kq.astype(jnp.float32) * jnp.swapaxes(ks, -1, -2)[..., None]
    vd = vq.astype(jnp.float32) * jnp.swapaxes(vs, -1, -2)[..., None]
    ref = attention(q, kd, vd, positions=positions, kv_len=lengths,
                    backend="xla")
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=2e-4)


def test_flash_decode_quantized_tracks_full_precision():
    """End-to-end quantization error stays small: int8-KV attention vs
    full-precision attention over the original values."""
    from ome_tpu.ops.attention import attention
    from ome_tpu.ops.flash import flash_decode_quantized, quantize_kv_block
    B, S, H, K, D = 2, 128, 8, 8, 128
    q, k, v = _mk(jax.random.PRNGKey(4), B, 1, S, H, K, D, jnp.float32)
    lengths = jnp.asarray([64, 128], jnp.int32)
    positions = (lengths - 1)[:, None]
    kq, ks = quantize_kv_block(k)
    vq, vs = quantize_kv_block(v)
    out = flash_decode_quantized(q, kq, vq, ks, vs,
                                 positions=positions, kv_len=lengths,
                                 interpret=True)
    ref = attention(q, k, v, positions=positions, kv_len=lengths,
                    backend="xla")
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=3e-2)


# -- flash_prefill's three kinds of block ------------------------------
#
# The kernel sorts each (query block, key block) into none / whole /
# edge from its scalars and masks only the edge blocks. The sorting is
# held to the full boolean mask; the numerics run where whole blocks
# exist (several key blocks a query block), which the single-block
# cases above never reach.


def _seen(base, kv_hi, window, Sq, S):
    """The [Sq, S] mask the XLA path builds (ops/attention.py)."""
    from ome_tpu.ops.attention import make_causal_mask
    q_pos = (base + np.arange(Sq))[None, :]
    kv_pos = np.arange(S)
    m = np.asarray(make_causal_mask(q_pos, kv_pos, np.asarray([kv_hi])))[0]
    if window is not None:
        m = m & (kv_pos[None, :] > q_pos[0][:, None] - window)
    return m


@pytest.mark.parametrize("seed", range(16))
def test_prefill_block_kinds_match_the_mask(seed):
    """30 draws a seed: every grid step's kind equals the kind read
    off the mask, each key block in which a REAL row (a position under
    `kv_hi`; the rest are a padded prompt's tail) sees a column is
    given to exactly one step, a query block of padded rows alone to
    none, and a query block's range fits the trimmed grid. Seeds from
    12 on draw a right-padded prompt: `kv_hi` inside the query rows."""
    from ome_tpu.ops import flash
    rng = np.random.default_rng(seed)
    for _ in range(30):
        bq = int(rng.choice([16, 32, 64]))
        bs = int(rng.choice([16, 32, 64, 128]))
        Sq = bq * int(rng.integers(1, 6))
        S = bs * int(rng.integers(1, 9))
        base = int(rng.integers(0, S + 1))
        kv_hi = int(rng.choice([base + Sq, rng.integers(0, S + 1), S]))
        if seed >= 12:
            base = int(rng.integers(0, max(S - Sq, 0) + 1))
            kv_hi = base + int(rng.integers(1, Sq + 1))
        kv_hi = min(kv_hi, S)
        window = None if rng.random() < 0.4 \
            else int(rng.integers(1, S + bq))
        seen = _seen(base, kv_hi, window, Sq, S)
        real = base + np.arange(Sq) < kv_hi
        nk = flash._prefill_key_steps(S, bq, bs, window)
        draw = dict(base=base, kv_hi=kv_hi, window=window, bq=bq, bs=bs,
                    Sq=Sq, S=S)
        for qi in range(Sq // bq):
            first, last = flash._prefill_block_range(base, kv_hi, qi, bq,
                                                     bs, window)
            assert last - first + 1 <= nk, draw
            rows = seen[qi * bq:(qi + 1) * bq]
            # what the real rows of the block see
            live = rows[real[qi * bq:(qi + 1) * bq]]
            given = []
            for ki in range(nk):
                start, some, whole = flash._prefill_block_kind(
                    base, kv_hi, qi, ki, bq, bs, window)
                assert whole == (some and rows[:, start:start + bs].all()), \
                    (draw, qi, ki)
                if not some:
                    continue
                assert live[:, start:start + bs].any(), (draw, qi, ki)
                given.append(start // bs)
            want = [j for j in range(S // bs)
                    if live[:, j * bs:(j + 1) * bs].any()]
            assert given == want, (draw, qi)
            if not live.size:       # padding alone: one block, repeated
                assert first == last == max((kv_hi - 1) // bs, 0), draw
        # the host's count takes the whole grid at once
        kinds = flash._count_kinds(Sq // bq, nk, bq, bs, base, kv_hi, window)
        steps = [flash._prefill_block_kind(base, kv_hi, qi, ki, bq, bs,
                                           window)[1:]
                 for qi in range(Sq // bq) for ki in range(nk)]
        assert kinds == {
            "none": sum(not some for some, _ in steps),
            "whole": sum(whole for _, whole in steps),
            "edge": sum(some and not whole for some, whole in steps)}, draw


@pytest.mark.parametrize("window,kv_hi,work,edge,none", [
    # a KV head a layer at the 16 384 bucket of trinity-mini's cell:
    # the causal triangle of 64 query blocks over 32 key blocks, and
    # a window of 2048 (five key blocks a query block past the ramp,
    # two of them crossed by an edge: the diagonal one, and from the
    # ninth query block on the one the window's lower edge crosses) in
    # a grid of six
    (None, 16384, 1056, 64, 992),
    (2048, 16384, 300, 120, 84),
    # a prompt of 12 544 tokens in that bucket: 49 query blocks of 256
    # hold it to the row, the 15 behind them do nothing, so the work
    # is the triangle of the prompt (query block i sees i // 2 + 1 key
    # blocks) and the band of its 49 query blocks
    (None, 12544, 625, 49, 2048 - 625),
    (2048, 12544, 225, 90, 384 - 225),
])
def test_prefill_block_kinds_at_the_long_doc_bucket(window, kv_hi, work,
                                                    edge, none):
    from ome_tpu.ops import flash
    K = 4
    kinds = flash.prefill_block_kinds(16384, 16384, K, 8, 128, 0, kv_hi,
                                      window)
    nk = flash._prefill_key_steps(16384, 256, 512, window)
    assert nk == (32 if window is None else 6)
    assert sum(kinds.values()) == K * 64 * nk
    assert kinds == {"none": K * none, "whole": K * (work - edge),
                     "edge": K * edge}


@pytest.mark.parametrize("kv_hi,work,edge", [
    # the latent kernel at that bucket, a group of 4 heads: blocks of
    # 512 by 512, so the triangle of 32 and of the prompt's 25 query
    # blocks, whose diagonal blocks are the edge ones
    (16384, 528, 32),
    (12544, 325, 25),
    (12800, 325, 25),       # to the end of the 25th query block
    (12801, 351, 26),       # one row into the next block: it works
])
def test_latent_prefill_block_kinds_at_the_long_doc_bucket(kv_hi, work,
                                                           edge):
    from ome_tpu.ops import flash
    kinds = flash.latent_prefill_block_kinds(16384, 16384, 128, 0, kv_hi)
    assert kinds == {"none": 32 * (1024 - work),
                     "whole": 32 * (work - edge), "edge": 32 * edge}


def test_prefill_block_kinds_declines_with_the_kernel():
    from ome_tpu.ops import flash
    assert flash.prefill_block_kinds(64, 64, 4, 2, 64, 0, 64, None) is None
    # a short prompt's single block is an edge block: today's body
    assert flash.prefill_block_kinds(64, 64, 4, 2, 128, 0, 64, None) == \
        {"none": 0, "whole": 0, "edge": 4}


@pytest.mark.parametrize("case", [
    dict(S=1024),
    dict(S=2048),
    # a window under bq + bs - 1 = 767 covers no block whole: every
    # block that holds work is an edge block, in a grid of 3 for 4
    dict(S=2048, sliding_window=600, whole=False),
    dict(S=2048, sliding_window=1024),
    dict(S=2048, Sq=512, base=512, kv_len=1100),
    dict(S=2048, Sq=512, base=1536, sliding_window=900),
    dict(S=1024, H=8, D=256),                 # bq 128
    dict(S=1024, logit_softcap=30.0),
    dict(S=2048, sliding_window=1024, logit_softcap=50.0),
], ids=lambda c: "-".join(f"{k}{v}" for k, v in c.items()))
def test_flash_prefill_whole_blocks_match_xla(case):
    """Several key blocks a query block, so whole blocks (no mask
    arithmetic) and edge blocks (the mask) both run, and with a window
    the grid's key dimension is trimmed."""
    from ome_tpu.ops import flash
    case = dict(case)
    S, H, D = case.pop("S"), case.pop("H", 2), case.pop("D", 128)
    Sq, base = case.pop("Sq", S), case.pop("base", 0)
    kv_len, whole = case.pop("kv_len", None), case.pop("whole", True)
    kinds = flash.prefill_block_kinds(
        Sq, S, 1, H, D, base, S if kv_len is None else kv_len,
        case.get("sliding_window"))
    assert kinds["edge"] and bool(kinds["whole"]) == whole, kinds
    q, k, v = _mk(jax.random.PRNGKey(6), 1, Sq, S, H, 1, D, jnp.bfloat16)
    positions = base + jnp.arange(Sq, dtype=jnp.int32)[None, :]
    if kv_len is not None:
        kv_len = jnp.asarray([kv_len], jnp.int32)
    _check(q, k, v, positions, kv_len, ATOL[jnp.bfloat16], **case)


@pytest.mark.parametrize("window", [None, 1024])
def test_flash_prefill_whole_blocks_are_the_masked_body(monkeypatch,
                                                        window):
    """A mask changes nothing in a block every pair of which is seen:
    with every block sent through the masked body (the kernel as it
    was before it sorted them) the output is the same, to float32's
    last places (the CPU's compiler fuses the two bodies apart, so
    not to the bit here)."""
    from ome_tpu.ops import flash
    S = 2048
    q, k, v = _mk(jax.random.PRNGKey(7), 1, S, S, 2, 1, 128, jnp.float32)
    positions = jnp.arange(S, dtype=jnp.int32)[None, :]
    kw = dict(positions=positions, sliding_window=window, interpret=True)
    sorted_ = flash.flash_attention(q, k, v, **kw)
    kind = flash._prefill_block_kind

    def all_edge(*a):
        start, some, whole = kind(*a)
        return start, some, jnp.zeros_like(whole)

    monkeypatch.setattr(flash, "_prefill_block_kind", all_edge)
    flash._prefill_call.clear_cache()    # the kernel's jit holds the trace
    try:
        masked = flash.flash_attention(q, k, v, **kw)
    finally:
        flash._prefill_call.clear_cache()
    np.testing.assert_allclose(np.asarray(sorted_), np.asarray(masked),
                               atol=2e-6, rtol=0)


# -- a right-padded prompt: rows at positions >= kv_len are padding ----
#
# llama.forward hands a bucketed prompt's attention its TRUE length as
# kv_len. A query block that stands wholly at or past it does nothing
# at any step and comes back zero; the real rows see the same key
# blocks in the same order under the same masks as when the whole
# bucket is valid, so they do not move by a bit.


def _padded_prompt(kernel, Sq, S, base, window):
    """(run(kv_len, backend) -> [Sq, ...] rows-major output of one
    sequence, query block size): `Sq` rows at positions `base` on
    over `S` key rows, through flash_prefill or latent_prefill."""
    from ome_tpu.ops import flash
    from ome_tpu.ops.attention import latent_prefill
    positions = base + jnp.arange(Sq, dtype=jnp.int32)[None, :]
    if kernel == "flash":
        q, k, v = _mk(jax.random.PRNGKey(8), 1, Sq, S, 2, 1, 128,
                      jnp.bfloat16)
        bq = flash._prefill_blocks(Sq, S, 2, 128)[0]

        def run(kv_len, backend):
            return attention(q, k, v, positions=positions,
                             kv_len=jnp.asarray([kv_len], jnp.int32),
                             sliding_window=window, backend=backend)[0]
        return run, bq
    keys = jax.random.split(jax.random.PRNGKey(9), 5)
    q_nope, q_pe, k_nope, k_pe, v = (
        jax.random.normal(key, shape, jnp.float32).astype(jnp.bfloat16)
        for key, shape in zip(keys, (
            (1, 4, Sq, 128), (1, 4, Sq, 64), (1, 4, S, 128), (1, S, 64),
            (1, 4, S, 128))))
    bq = flash._latent_prefill_blocks(Sq, S, 4)[0]

    def run(kv_len, backend):
        out = latent_prefill(q_nope, q_pe, k_nope, k_pe, v, positions,
                             jnp.asarray([kv_len], jnp.int32), scale=0.07,
                             backend=backend)
        return jnp.swapaxes(out[0], 0, 1)            # [Sq, H, dv]
    return run, bq


@pytest.mark.parametrize("T", [0.5, 0.68, 1.0],
                         ids=["block-edge", "inside-a-block", "no-padding"])
@pytest.mark.parametrize("kernel,Sq,S,base,window", [
    ("flash", 1024, 1024, 0, None),
    ("flash", 1024, 1024, 0, 600),
    ("flash", 512, 1024, 256, None),       # a padded suffix atop a prefix
    ("flash", 512, 1024, 512, 300),
    ("latent", 1024, 1024, 0, None),
    ("latent", 1024, 2048, 512, None),
], ids=["flash", "flash-window", "flash-base", "flash-base-window",
        "latent", "latent-base"])
def test_prefill_padded_tail_does_nothing(kernel, Sq, S, base, window, T):
    T = int(T * Sq)
    run, bq = _padded_prompt(kernel, Sq, S, base, window)
    got = np.asarray(run(base + T, "pallas_interpret"), np.float32)
    assert np.isfinite(got).all()
    # the real rows are those of the whole bucket held valid, to the
    # bit, and XLA's under the same mask
    whole = np.asarray(run(base + Sq, "pallas_interpret"), np.float32)
    np.testing.assert_array_equal(got[:T], whole[:T])
    ref = np.asarray(run(base + T, "xla"), np.float32)
    np.testing.assert_allclose(got[:T], ref[:T], atol=ATOL[jnp.bfloat16])
    # query blocks of padded rows alone come back zero
    first_padded = -(-T // bq) * bq
    assert not got[first_padded:].any()
    if first_padded < Sq:
        assert whole[first_padded:].any()


# -- the counter of those kinds, from a prefill's shape ----------------


@pytest.mark.parametrize("name,bucket,valid,want", [
    # long-doc's larger bucket: 4 global layers (whole 0.94 of the
    # work) and 12 window layers (0.60), 4 KV heads: 0.78 over both
    ("trinity-mini-ep4", 16384, None,
     dict(none=16 * 992 + 48 * 84, whole=16 * 992 + 48 * 180,
          edge=16 * 64 + 48 * 120)),
    ("trinity-mini-ep4", 8192, None,
     dict(none=6336, whole=7872, edge=3200)),
    # 3 full layers of head_dim 256 (bq 128): 0.78
    ("qwen3-next-80b-a3b-ep4", 4096, None,
     dict(none=672, whole=672, edge=192)),
    # its 2048 bucket's float32 logits are the cap to the byte: XLA
    ("qwen3-next-80b-a3b-ep4", 2048, None, dict(none=0, whole=0, edge=0)),
    # the one bucket of chat-steady over the cap: 36 layers, 0.60
    ("qwen3-4b", 2048, None, dict(none=3456, whole=3456, edge=2304)),
    ("qwen3-4b", 1024, None, dict(none=0, whole=0, edge=0)),
    # the latent kernel's own steps: 5 layers, 128 heads in 32 groups
    # of 4, the triangle of 32 blocks of 512 (528 of 1024 work)
    ("openpangu-ultra-moe-718b-ep16", 16384, None,
     dict(none=160 * 496, whole=160 * 496, edge=160 * 32)),
    # a prompt of 12 544 tokens in the 16 384 bucket does the triangle
    # of its 25 / 49 query blocks and the band of the 49: none goes
    # from 0.48 to 0.68 of the latent grid, 0.38 to 0.59 of trinity's
    ("openpangu-ultra-moe-718b-ep16", 16384, 12544,
     dict(none=160 * 699, whole=160 * 300, edge=160 * 25)),
    ("trinity-mini-ep4", 16384, 12544,
     dict(none=16 * 1423 + 48 * 159, whole=16 * 576 + 48 * 135,
          edge=16 * 49 + 48 * 90)),
    ("smallthinker-21b-a3b-ep4", 16384, 12544,
     dict(none=53664, whole=34488, edge=7080)),
])
def test_prefill_attn_block_kinds_of_the_cells(monkeypatch, name, bucket,
                                               valid, want):
    """One prefill's grid steps by kind, summed over the layers by
    their window (a latent model: over its layers and groups of
    heads), at the benchmark's configurations and, with `valid`, at a
    prompt shorter than its bucket; nothing where the prompt takes
    XLA's attention, as every prompt does off the chip."""
    import json
    import os
    from ome_tpu import device
    from ome_tpu.engine.core import prefill_attn_block_kinds
    from ome_tpu.models.config import ModelConfig
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           f"{name}.json")) as f:
        cfg = ModelConfig.from_hf_config(json.load(f))
    zero = dict(none=0, whole=0, edge=0)
    assert prefill_attn_block_kinds(cfg, bucket, bucket, 0, valid) == zero
    monkeypatch.setattr(device, "on_tpu", lambda: True)
    assert prefill_attn_block_kinds(cfg, bucket, bucket, 0, valid) == want
    if valid is None:       # the whole bucket valid is the same call
        assert prefill_attn_block_kinds(cfg, bucket, bucket, 0,
                                        bucket) == want


def test_prefill_attn_blocks_counter_follows_the_prefills(monkeypatch):
    """`ome_engine_prefill_attn_blocks_total{kind=}`: the engine adds
    a prefill's grid steps where it runs one, by the prompt's own
    length, and the scheduler mirrors the tallies at scrape. A 2-layer
    model with one KV head: a prompt in the 32 bucket is one edge
    block a layer; the 512 bucket is two query blocks of 256 over one
    key block, of which a prompt of 300 tokens fills both and one of
    8 or 40 the first alone."""
    from ome_tpu.engine import InferenceEngine, Scheduler
    from ome_tpu.models import config as cfgs
    from ome_tpu.models import llama
    monkeypatch.setenv("OME_ATTN_BACKEND", "pallas_interpret")
    cfg = cfgs.tiny_test().replace(
        num_layers=2, num_heads=2, num_kv_heads=1, head_dim=128,
        max_seq_len=1024, dtype=jnp.float32)
    engine = InferenceEngine(llama.init_params(jax.random.PRNGKey(0), cfg),
                             cfg, max_slots=2, prefill_buckets=[32, 512])
    sched = Scheduler(engine)

    def scraped():
        sched.update_gauges()
        text = sched.registry.render()
        return {k: int(float(text.split(
            'ome_engine_prefill_attn_blocks_total{kind="%s"} ' % k)[1]
            .split()[0])) for k in ("none", "whole", "edge")}

    assert scraped() == dict(none=0, whole=0, edge=0)
    engine.prefill(list(range(1, 20)))
    assert scraped() == dict(none=0, whole=0, edge=2)
    engine.prefill(list(range(1, 9)))
    assert scraped() == dict(none=0, whole=0, edge=4)
    engine.prefill(list(range(1, 301)))
    assert scraped() == dict(none=0, whole=0, edge=8)
    engine.prefill(list(range(1, 41)))
    assert scraped() == dict(none=2, whole=0, edge=10)
