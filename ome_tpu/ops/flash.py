"""Pallas TPU flash-attention kernels (prefill + decode).

TPU-first replacement for the attention math the reference delegates to
SGLang/vLLM CUDA kernels (SURVEY.md L0): here attention is an in-repo
Pallas kernel pair designed around the TPU memory system:

  * **decode** (`Sq == 1`): grid (B, kv_blocks); the per-sequence
    [lo, hi) valid-row window rides scalar prefetch so the K/V
    BlockSpec index maps *clamp* past-the-end block indices — Pallas
    skips the DMA when the block index repeats, so a sequence at
    length 300 in a 2048-slot cache streams ~300 rows of KV through
    VMEM, not 2048 (decode is HBM-bandwidth-bound; this is the win).
    A cache row's K heads lie side by side in the lanes, [S, K * D],
    so a key block [bs, K * D] is a dense tile of the slab as it
    lies in HBM and head `kh` the lane slice [:, kh * D:(kh + 1) * D]
    (a [bs, K, D] block with K = 4 in the second-minor place is half
    padding: 3.1 us a 512-row block for 1.3 us of DMA, PR 32).
  * **prefill**: grid (B, K, q_blocks, kv_steps) with the same
    clamping on the causal frontier, so upper-triangle KV blocks are
    neither fetched nor computed. GQA: the G query heads of a KV
    head lie side by side in the lanes of one query block
    [bq, G * D] and meet the one key block [bs, D] a head at a time
    — no K/V duplication in VMEM, and every block is a dense tile of
    the array as it lies in HBM (a [.., 1, D] key block is a
    (1, 128) tile a row, loaded a sublane at a time and shuffled
    dense every step: most of this kernel's time before PR 33, by
    its compiled bundles). Each (query block, key block) step is
    sorted from the scalars the kernel already has
    (`_prefill_block_kind`) into one of three kinds:
    **none**: no (row, column) pair is seen: nothing runs. Under a
    sliding window the grid's key dimension holds only as many steps
    as a query block's windows can reach (`_prefill_key_steps`), not
    one a key block of the sequence. A query block whose first row
    stands at or past the valid length (`kv_len`: the padded tail of
    a prompt in its bucket) is `none` at every step: the kernel does
    the triangle of the prompt, not of its bucket, and such a block's
    output is zeros;
    **whole**: every pair is seen (the causal edge, the cache's
    length and the window's lower edge all pass outside the block):
    the dots and the softmax update, with no iota, compare or select;
    a mask would change nothing there, and nearly all blocks of a
    long prompt are such blocks;
    **edge**: an edge crosses the block: the same update under the
    mask. `prefill_block_kinds` counts a call's steps by kind on the
    host, for the engine's `ome_engine_prefill_attn_blocks_total`.

Both kernels keep fp32 online-softmax state (m, l, acc) in VMEM
scratch across the innermost grid dimension and never materialize a
mask in HBM: causality, per-sequence KV length, and sliding windows
are iota comparisons against scalar limits, made only in the blocks
they cross (prefill). Supports GQA (H % K == 0), logit softcap
(Gemma-2), and chunked prefill (nonzero per-batch position base
writing into a pre-filled cache).

Returns None for shapes the kernels don't cover (tiny heads, ragged
sizes) — callers fall back to the XLA path (ops/attention.py), which
is also the CPU-mesh path; `interpret=True` runs the same kernels on
CPU for the numerics tests.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

M_INIT = -1.0e30  # finite lowest running max: exp(x - M_INIT) underflows to 0


def _pick_block(n: int, candidates) -> Optional[int]:
    for c in candidates:
        if n % c == 0:
            return c
    return None


# -- decode kernel ---------------------------------------------------------


def _decode_block_range(lo, hi, bs):
    """[first, last] block indices holding rows of [lo, hi) — the SAME
    mapping the BlockSpec index maps use, so the kernel can recover the
    absolute start of the block it was actually given."""
    first = jnp.maximum(lax.div(lo, bs), 0)
    last = jnp.maximum(lax.div(hi - 1, bs), first)
    return first, last


def _decode_kernel(lim_ref, q_ref, k_ref, v_ref, *refs, bs: int,
                   scale: float, softcap: Optional[float],
                   quantized: bool = False):
    if quantized:
        # int8 KV cache: per-(row, head) f32 scales ([K, bs] blocks —
        # S minor keeps the plane lane-aligned) ride as two extra
        # inputs. K/V convert to bf16 UNSCALED for the MXU dots; the
        # scales multiply the small [K*G, bs] logits/probs tiles
        # instead of the [bs, K, D] value blocks (128x fewer
        # multiplies), so HBM streams 1 byte/element + a tiny plane
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = refs
    else:
        ks_ref = vs_ref = None
        o_ref, m_ref, l_ref, acc_ref = refs
    s = pl.program_id(1)
    ns = pl.num_programs(1)

    @pl.when(s == 0)
    def _():
        m_ref[:] = jnp.full_like(m_ref, M_INIT)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    lo = lim_ref[pl.program_id(0), 0]
    hi = lim_ref[pl.program_id(0), 1]
    first, last = _decode_block_range(lo, hi, bs)
    start = jnp.minimum(first + s, last) * bs  # matches kv_index below

    # `first + s <= last` keeps the clamped (repeated, DMA-skipped)
    # grid steps beyond the range from double-counting the last block
    @pl.when((first + s <= last) & (start < hi) & (start + bs > lo))
    def _():
        q = q_ref[0]            # [K, G, D]
        K, G, D = q.shape

        def head(ref, kh):
            """Head kh of a [1, bs, K * D] block, [bs, D]: a slice on
            a lane tile's edge (D % 128 == 0)."""
            x = ref[0, :, kh * D:(kh + 1) * D]
            # int8 KV: raw values; the scales go on logits and probs
            return x.astype(q.dtype) if quantized else x

        # per-KV-head 2D dots (Mosaic's matmul wants batch dims aligned;
        # K is small and static, so unroll): [G,D] x [bs,D]^T -> [G,bs]
        logits = jnp.concatenate(
            [lax.dot_general(q[kh], head(k_ref, kh),
                             (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
             for kh in range(K)], axis=0)                   # [K*G, bs]
        if quantized:
            sk = ks_ref[0]                                  # [K, bs]
            logits = (logits.reshape(K, G, bs)
                      * sk[:, None, :]).reshape(K * G, bs)
        logits = logits * scale
        if softcap:
            logits = jnp.tanh(logits / softcap) * softcap
        col = start + lax.broadcasted_iota(jnp.int32, (K * G, bs), 1)
        valid = (col >= lo) & (col < hi)
        logits = jnp.where(valid, logits, M_INIT)

        m_prev = m_ref[:, :1]                                   # [KG, 1]
        m_new = jnp.maximum(m_prev, jnp.max(logits, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(logits - m_new)
        p = jnp.where(valid, p, 0.0)
        l_new = alpha * l_ref[:, :1] + jnp.sum(p, axis=1, keepdims=True)
        if quantized:
            sv = vs_ref[0]                                  # [K, bs]
            p = (p.reshape(K, G, bs) * sv[:, None, :]).reshape(
                K * G, bs)
        pb = p.astype(q.dtype if quantized else v_ref.dtype)
        pv = jnp.concatenate(
            [lax.dot_general(pb[kh * G:(kh + 1) * G], head(v_ref, kh),
                             (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
             for kh in range(K)], axis=0)                   # [K*G, D]
        acc_ref[:] = acc_ref[:] * alpha + pv
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(s == ns - 1)
    def _():
        K, G, D = o_ref.shape[1:]
        l = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0] = (acc_ref[:] / l).reshape(K, G, D).astype(o_ref.dtype)


def _decode_walk(lo, hi, layer, B: int, bs: int):
    """What both decode kernels walk a slot's rows by: the scalar-
    prefetch `limits` [B, 2] (lo, hi; a third column the layer, the
    same for every row, where the cache is a STACKED slab read by
    layer index) and the block index map over a `[(L,) B, S, lanes]`
    cache. The walk starts at the sliding window's first valid block
    and clamps at the last block holding a valid row: repeated indices
    make Pallas skip the DMA for both the pre-window head
    (long-context sliding window) and the cache tail (short
    sequences)."""
    stacked = layer is not None
    limits = [lo.astype(jnp.int32), hi.astype(jnp.int32)]
    if stacked:
        limits.append(jnp.broadcast_to(jnp.asarray(layer, jnp.int32),
                                       (B,)))

    def kv_index(b, s, lim):
        first, last = _decode_block_range(lim[b, 0], lim[b, 1], bs)
        at = (b, jnp.minimum(first + s, last), 0)
        return (lim[b, 2],) + at if stacked else at

    return jnp.stack(limits, axis=1), kv_index


def _flash_decode(q, k, v, lo, hi, scale, softcap, interpret,
                  k_scale=None, v_scale=None, layer=None):
    """k, v: [B, S, K * D], a row's K heads side by side in the lanes
    (`llama.KVCache.create(..., merged=True)`: what a slab engine's
    state is). `layer` (an int32 scalar, traced or not): k and v are
    the STACKED slabs [L, B, S, K * D] of a layer scan that carries
    them, and the kernel reads layer `layer` of them where they lie:
    the index rides scalar prefetch into the block index maps, so no
    layer is sliced out first (a slice of a carried slab is a copy of
    it; ops/paged.py does the same for the pool)."""
    B, _, H, D = q.shape
    S, KD = k.shape[-2], k.shape[-1]
    K = KD // D
    bs = _pick_block(S, (512, 256, 128))
    if bs is None or H < 8 or D % 128 != 0 or K * D != KD or H % K \
            or v.shape != k.shape:
        return None
    G = H // K
    ns = S // bs
    quantized = k_scale is not None
    stacked = layer is not None
    assert not (stacked and quantized)
    limits, kv_index = _decode_walk(lo, hi, layer, B, bs)    # [B, 2 or 3]
    qh = q.reshape(B, K, G, D)

    # the layer's dimension is squeezed out of the block: the kernel
    # sees [1, bs, K * D] either way, bs whole rows as they lie in HBM
    kv_block = ((None,) if stacked else ()) + (1, bs, KD)

    def sc_index(b, s, lim):
        first, last = _decode_block_range(lim[b, 0], lim[b, 1], bs)
        return (b, 0, jnp.minimum(first + s, last))

    in_specs = [
        pl.BlockSpec((1, K, G, D), lambda b, s, lim: (b, 0, 0, 0)),
        pl.BlockSpec(kv_block, kv_index),
        pl.BlockSpec(kv_block, kv_index),
    ]
    args = [limits, qh, k, v]
    if quantized:
        # scales are [B, K, S] — S minor so each [K, bs] block is
        # lane-aligned (K=8 minor would DMA 8-lane vectors)
        in_specs += [pl.BlockSpec((1, K, bs), sc_index),
                     pl.BlockSpec((1, K, bs), sc_index)]
        args += [k_scale, v_scale]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, ns),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, K, G, D), lambda b, s, lim: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((H, 128), jnp.float32),
            pltpu.VMEM((H, 128), jnp.float32),
            pltpu.VMEM((H, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_decode_kernel, bs=bs, scale=scale,
                          softcap=softcap, quantized=quantized),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, K, G, D), q.dtype),
        interpret=interpret,
        name="flash_decode",
    )(*args)
    return out.reshape(B, 1, H, D)


def _merged(x: jax.Array) -> jax.Array:
    """[.., S, K, D] as [.., S, K * D]. Free for a handful of fresh
    rows; of a whole slab it is a copy on the chip (the minor tiles
    differ), so state that lives across decode steps is created
    merged and never passes here."""
    return x.reshape(x.shape[:-2] + (x.shape[-2] * x.shape[-1],))


def quantize_kv_block(x: jax.Array):
    """Per-(row, head) symmetric int8 for a KV slab [B, S, K, D] ->
    (int8 values [B, S, K, D], f32 scales [B, K, S]). One scale per
    token-head tracks each token's dynamic range (activation stats
    vary token to token far more than channel to channel); scales are
    stored S-minor so the decode kernel's [K, bs] scale blocks are
    lane-aligned."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)  # [B,S,K]
    s = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / s[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, jnp.swapaxes(s, -1, -2)


def flash_decode_quantized(q: jax.Array, kq: jax.Array, vq: jax.Array,
                           k_scale: jax.Array, v_scale: jax.Array,
                           positions: jax.Array,
                           kv_len: Optional[jax.Array] = None,
                           sliding_window: Optional[int] = None,
                           scale: Optional[float] = None,
                           logit_softcap: Optional[float] = None,
                           interpret: bool = False):
    """Decode attention over an int8 KV cache (quantize_kv_block
    layout). q: [B, 1, H, D] bf16; kq/vq: [B, S, K, D] int8 (taken
    to the kernel's [B, S, K * D] here: this slab keeps the layout
    its quantizer and the paged pool share) or [B, S, K * D]; scales
    [B, K, S] f32. Returns [B, 1, H, D] or None if shapes uncovered.

    Experimental building block, NOT wired into the engine: the KV
    read is the second-largest term in the decode step's HBM budget
    after the weights and int8 halves it; whether the in-kernel
    int8->bf16 convert costs more than the halved read saves is not
    measured on the chip. It ships numerics-tested (tests/test_ops.py)
    but unreachable from serving (the paged pool's --kv-dtype int8 is
    the served int8 KV path).
    """
    B, Sq, H, D = q.shape
    assert Sq == 1
    scale = scale if scale is not None else D ** -0.5
    pos = positions[:, 0]
    if kv_len is None:
        kv_hi = jnp.full((B,), kq.shape[1], jnp.int32)
    else:
        kv_hi = jnp.broadcast_to(kv_len, (B,)).astype(jnp.int32)
    hi = jnp.minimum(pos + 1, kv_hi)
    lo = jnp.maximum(pos - sliding_window + 1, 0) if sliding_window \
        else jnp.zeros_like(pos)
    if kq.ndim == 4:
        kq, vq = _merged(kq), _merged(vq)
    return _flash_decode(q, kq, vq, lo, hi, scale, logit_softcap,
                         interpret, k_scale=k_scale, v_scale=v_scale)


# -- prefill kernel --------------------------------------------------------
#
# The scalar arithmetic that sorts a (query block, key block) pair is
# written once and runs twice: traced, on the int32 scalars the kernel
# and its index maps read from SMEM, and on Python integers, for the
# host's count of grid steps by kind (`prefill_block_kinds`).


def _lib(*xs):
    """What computes on `xs`: None for Python integers, numpy for a
    host's arrays (`prefill_block_kinds`: a whole grid at once), else
    jax.numpy (traced: the kernels and their index maps)."""
    if all(isinstance(x, int) for x in xs):
        return None
    if all(isinstance(x, (int, np.ndarray, np.generic)) for x in xs):
        return np
    return jnp


def _div(a, b):
    """a // b for a >= 0. Below 0 the traced quotient rounds to 0 and
    the integer one down: every caller clamps it to 0 next."""
    return lax.div(a, b) if _lib(a, b) is jnp else a // b


def _min(a, b):
    lib = _lib(a, b)
    return lib.minimum(a, b) if lib else min(a, b)


def _max(a, b):
    lib = _lib(a, b)
    return lib.maximum(a, b) if lib else max(a, b)


def _where(c, a, b):
    lib = _lib(c, a, b)
    return lib.where(c, a, b) if lib else (a if c else b)


def _prefill_block_range(base, kv_hi, qi, bq, bs, window):
    """[first, last] KV block indices a q block can attend — the same
    mapping the prefill BlockSpec index maps use. A query block whose
    first row stands at or past `kv_hi` is padding (its own keys are
    not valid rows): its range is the one block the last valid row
    lies in, where the query block before it ended, so every step of
    it repeats that index and fetches nothing."""
    causal_last = _div(base + (qi + 1) * bq - 1, bs)
    len_last = _max(_div(kv_hi - 1, bs), 0)
    last = _min(causal_last, len_last)
    first = 0 if window is None else \
        _max(_div(base + qi * bq - window + 1, bs), 0)
    first = _where(base + qi * bq >= kv_hi, last, first)
    return _min(first, last), _max(last, 0)


def _prefill_key_steps(S: int, bq: int, bs: int, window) -> int:
    """The grid's key dimension: every key block without a window;
    with one, the most blocks `last - first + 1` can be. A query
    block's rows see the `window + bq - 1` columns from its first
    row's window to its last row, and n columns that start on a
    block's last column touch (n + bs - 2) // bs + 1 blocks."""
    if window is None:
        return S // bs
    return min((window + bq + bs - 3) // bs + 1, S // bs)


def _prefill_block_kind(base, kv_hi, qi, ki, bq, bs, window):
    """Where the mask's edges lie against grid step (qi, ki):
    (start, some, whole). `start` is the first column of the key block
    the step was given (the index map's clamp, undone); `some`: a
    (row, column) pair of it is seen; `whole`: every pair is: the
    causal edge, the cache's length and the window's lower edge all
    pass outside the block, so the mask would change nothing. A query
    block whose first row stands at or past `kv_hi` holds padded rows
    only (a right-padded prompt's tail: no row's own key is valid) and
    has nothing at any step; the block that `kv_hi` crosses keeps its
    padded rows under the mask as every other row."""
    first, last = _prefill_block_range(base, kv_hi, qi, bq, bs, window)
    start = _min(first + ki, last) * bs
    q_lo = base + qi * bq            # absolute position of first q row
    q_hi = q_lo + bq - 1
    # `first + ki <= last` keeps clamped (repeated, DMA-skipped) steps
    # from double-counting the boundary block
    given = (first + ki <= last) & (q_lo < kv_hi)
    some = given & (start <= q_hi) & (start < kv_hi)
    whole = given & (start + bs - 1 <= q_lo) & (start + bs <= kv_hi)
    if window is not None:
        some = some & (start + bs > q_lo - window + 1) \
            & (kv_hi > q_lo - window + 1)
        whole = whole & (start > q_hi - window)
    return start, some, whole


def _lanes(x, n: int):
    """[rows, 128] whose lanes are alike, as [rows, n]."""
    return jnp.tile(x, (1, n // 128)) if n % 128 == 0 else x[:, :n]


def _prefill_kernel(lim_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                    acc_ref, *, bq: int, bs: int, g: int, d: int,
                    scale: float, softcap: Optional[float],
                    window: Optional[int]):
    """q_ref, o_ref: [1, bq, G * D], the G query heads of this KV head
    side by side in the lanes; k_ref, v_ref: [1, bs, D]. The running
    softmax state is a row a (head, query row), head-major: m_ref and
    l_ref [G * bq, 128] with a row's value in every lane (so it meets
    a [bq, 128] tile with no broadcast across lanes), acc_ref
    [G * bq, D]."""
    b, qi, ki = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _():
        m_ref[:] = jnp.full_like(m_ref, M_INIT)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    base = lim_ref[b, 0]             # absolute position of q row 0
    kv_hi = lim_ref[b, 1]            # valid KV rows
    start, some, whole = _prefill_block_kind(base, kv_hi, qi, ki, bq, bs,
                                             window)

    def update(valid=None):
        """This key block into the running softmax of every head;
        `valid` [bq, bs] masks it (the same for every head), None is
        a block of which every pair is seen."""
        kb = k_ref[0]                # [bs, D]
        vb = v_ref[0]
        for h in range(g):           # a head: [bq, D] x [D, bs]
            rows = pl.ds(h * bq, bq)
            x = lax.dot_general(
                q_ref[0, :, h * d:(h + 1) * d], kb,
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale   # [bq, bs]
            if softcap:
                x = jnp.tanh(x / softcap) * softcap
            if valid is not None:
                x = jnp.where(valid, x, M_INIT)
            m_prev = m_ref[rows, :]
            m_new = jnp.maximum(m_prev, jnp.max(x, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(x - _lanes(m_new, bs))
            if valid is not None:
                p = jnp.where(valid, p, 0.0)
            l_new = alpha * l_ref[rows, :] \
                + jnp.sum(p, axis=1, keepdims=True)
            pv = lax.dot_general(
                p.astype(vb.dtype), vb, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)            # [bq, D]
            acc_ref[rows, :] = acc_ref[rows, :] * _lanes(alpha, d) + pv
            m_ref[rows, :] = m_new
            l_ref[rows, :] = l_new

    @pl.when(whole)
    def _():
        update()

    @pl.when(some & jnp.logical_not(whole))
    def _():
        q_lo = base + qi * bq        # absolute position of first q row
        col = start + lax.broadcasted_iota(jnp.int32, (bq, bs), 1)
        qpos = q_lo + lax.broadcasted_iota(jnp.int32, (bq, bs), 0)
        valid = (col <= qpos) & (col < kv_hi)
        if window is not None:
            valid = valid & (col > qpos - window)
        update(valid)

    @pl.when(ki == nk - 1)
    def _():
        for h in range(g):
            rows = pl.ds(h * bq, bq)
            l = jnp.maximum(l_ref[rows, :], 1e-30)
            o_ref[0, :, h * d:(h + 1) * d] = \
                (acc_ref[rows, :] / _lanes(l, d)).astype(o_ref.dtype)


def _prefill_blocks(Sq: int, S: int, G: int, D: int):
    """(bq, bs), or None for shapes the kernel does not cover."""
    # the query block holds whole GQA groups: bq rows of G * D. It is
    # kept at what 8 heads a KV head of 128 dims take: a wider head
    # or group halves bq. The rule dates from the kernel that took all
    # G heads' [bq * G, bs] logits at once (head_dim 256 with 8 heads
    # a KV head needed 24 MB of VMEM's 16 at bq 256, chip compiler,
    # PR 27); a head at a time needs a sixteenth of that, and the
    # blocks were not retuned with it (ROADMAP A8 (e))
    bq = _pick_block(Sq, tuple(c for c in (256, 128, 64, 32, 16)
                               if c * G * D <= 256 * 8 * 128))
    bs = _pick_block(S, (512, 256, 128, 64, 32, 16))
    if bq is None or bs is None or bq * G < 8 or D % 128 != 0:
        return None
    return bq, bs


def _count_kinds(n_q: int, n_k: int, bq: int, bs: int, base: int,
                 kv_hi: int, window: Optional[int]):
    """A (query blocks, key steps) grid's steps by kind, the whole
    grid through `_prefill_block_kind` at once on the host."""
    _, some, whole = _prefill_block_kind(
        base, kv_hi, np.arange(n_q)[:, None], np.arange(n_k)[None, :], bq,
        bs, window)
    work, whole = (int(np.count_nonzero(np.broadcast_to(x, (n_q, n_k))))
                   for x in (some, whole))
    return {"none": n_q * n_k - work, "whole": whole, "edge": work - whole}


def prefill_block_kinds(Sq: int, S: int, K: int, G: int, D: int,
                        base: int, kv_hi: int, window: Optional[int]):
    """Grid steps of one sequence's `flash_prefill` call by kind,
    {"none", "whole", "edge"}, on the host from the kernel's own
    tests; None where the kernel declines the shape.
    `whole` steps run the softmax with no mask arithmetic, `edge`
    steps build the mask, `none` steps do nothing (their key block's
    DMA is skipped too; every step of a query block past `kv_hi`, a
    padded prompt's tail, is one): whole / (whole + edge) is how often
    the cheap body engages, none what is left of the grid."""
    blocks = _prefill_blocks(Sq, S, G, D)
    if blocks is None:
        return None
    bq, bs = blocks
    kinds = _count_kinds(Sq // bq, _prefill_key_steps(S, bq, bs, window),
                         bq, bs, base, kv_hi, window)
    return {kind: K * n for kind, n in kinds.items()}


def _kv_heads(k, D: int, stacked: bool = False) -> int:
    """KV heads of [B, S, K, D] rows, or of merged [B, S, K * D]
    ones (`stacked`: a layer dimension leads either), told apart by
    the array's rank."""
    return k.shape[-1] // D if k.ndim - stacked == 3 else k.shape[-2]


def _flash_prefill(q, k, v, base, kv_hi, scale, softcap, window, interpret):
    G = q.shape[2] // _kv_heads(k, q.shape[3])
    blocks = _prefill_blocks(q.shape[1], k.shape[1], G, q.shape[3])
    if blocks is None:
        return None
    return _prefill_call(q, k, v, base, kv_hi, blocks=blocks, scale=scale,
                         softcap=softcap, window=window, interpret=interpret)


# A jit of its own: a model's layers call the kernel at one shape from
# several places (the periods of a window / global scan, unrolled and
# scanned) and a program is traced twice at its first dispatch (the
# ledger's `lower`, then the call). The kernel's body is a few hundred
# equations a trace, seconds of a warm start over a model's buckets;
# under the jit's cache it is traced once a shape and lowered once a
# module. XLA inlines the call.
@functools.partial(jax.jit, static_argnames=(
    "blocks", "scale", "softcap", "window", "interpret"))
def _prefill_call(q, k, v, base, kv_hi, *, blocks, scale, softcap, window,
                  interpret):
    B, Sq, H, D = q.shape
    S, K = k.shape[1], _kv_heads(k, D)
    G = H // K
    bq, bs = blocks
    limits = jnp.stack(
        [base.astype(jnp.int32), kv_hi.astype(jnp.int32)], axis=1)
    # heads side by side in the lanes: a query block is [bq, G * D]
    # and a key block [bs, D], both dense tiles of the arrays as they
    # lie in HBM (no [.., 1, D] or [.., G, D] minor tiles to repack);
    # a slab engine's cache rows come merged already
    q3 = q.reshape(B, Sq, H * D)
    k3 = k.reshape(B, S, K * D)
    v3 = v.reshape(B, S, K * D)

    def kv_index(b, kh, qi, ki, lim):
        # clamp to [first, last]: the upper causal triangle, the cache
        # tail, and (with a sliding window) the pre-window head are all
        # mapped to repeated indices -> Pallas skips their DMA
        first, last = _prefill_block_range(lim[b, 0], lim[b, 1], qi, bq,
                                           bs, window)
        return (b, jnp.minimum(first + ki, last), kh)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, K, Sq // bq, _prefill_key_steps(S, bq, bs, window)),
        in_specs=[
            pl.BlockSpec((1, bq, G * D),
                         lambda b, kh, qi, ki, lim: (b, qi, kh)),
            pl.BlockSpec((1, bs, D), kv_index),
            pl.BlockSpec((1, bs, D), kv_index),
        ],
        out_specs=pl.BlockSpec(
            (1, bq, G * D), lambda b, kh, qi, ki, lim: (b, qi, kh)),
        scratch_shapes=[
            pltpu.VMEM((bq * G, 128), jnp.float32),
            pltpu.VMEM((bq * G, 128), jnp.float32),
            pltpu.VMEM((bq * G, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_prefill_kernel, bq=bq, bs=bs, g=G, d=D,
                          scale=scale, softcap=softcap, window=window),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Sq, H * D), q.dtype),
        interpret=interpret,
        name="flash_prefill",
    )(limits, q3, k3, v3)
    return out.reshape(B, Sq, H, D)


# -- public entry ----------------------------------------------------------


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    positions: Optional[jax.Array] = None,
                    kv_len: Optional[jax.Array] = None,
                    sliding_window: Optional[int] = None,
                    scale: Optional[float] = None,
                    logit_softcap: Optional[float] = None,
                    interpret: bool = False,
                    layer=None) -> Optional[jax.Array]:
    """Flash attention or None when the kernels don't cover the shapes.

    q: [B, Sq, H, D]; k, v: [B, Skv, K, D], H % K == 0, or a slab
    engine's merged rows [B, Skv, K * D] (told apart by rank); with
    `layer` (decode only, Sq == 1) the stacked [L, B, Skv, K * D] of
    which the kernel reads that layer in place (`_flash_decode`).
    positions: [B, Sq] absolute query positions, assumed contiguous per
    row (base + arange — what the model forward produces); None means
    non-causal full attention (not covered here -> None).
    kv_len: [B] valid KV rows (None = all Skv rows valid). The output
    of a query row at a position >= kv_len is unspecified: such a row
    is padding (its own key is no valid row), and the prefill kernel
    does no work for query blocks made only of them (they come back
    zero; a padded row beside real ones comes back as the mask leaves
    it).
    """
    if positions is None:
        return None  # non-causal: XLA path
    B, Sq, H, D = q.shape
    stacked = layer is not None
    if k.ndim - stacked == 4 and Sq == 1:
        # rows apart (a caller with no state of its own: tests, bench)
        k, v = _merged(k), _merged(v)
    K = _kv_heads(k, D, stacked)
    if K == 0 or H % K != 0 or (stacked and Sq != 1):
        return None
    scale = scale if scale is not None else D ** -0.5
    base = positions[:, 0]
    if kv_len is None:
        kv_hi = jnp.full((B,), k.shape[2 if stacked else 1], jnp.int32)
    else:
        kv_hi = jnp.broadcast_to(kv_len, (B,)).astype(jnp.int32)
    if Sq == 1:
        pos = positions[:, 0]
        hi = jnp.minimum(pos + 1, kv_hi)
        lo = jnp.maximum(pos - sliding_window + 1, 0) if sliding_window \
            else jnp.zeros_like(pos)
        return _flash_decode(q, k, v, lo, hi, scale, logit_softcap,
                             interpret, layer=layer)
    return _flash_prefill(q, k, v, base, kv_hi, scale, logit_softcap,
                          sliding_window, interpret)


# -- latent attention (MLA) kernels ----------------------------------------
#
# A latent model (models/mla.py) caches ONE row a token, `[c | k_pe]`
# of `rank + rope` lanes (512 + 64), which every head reads as its key
# and, in its first `rank` lanes, as its value. Decode never leaves
# latent space (the queries come absorbed, `[q_lat | q_pe]`); a prompt
# materialises per-head keys and values, whose widths differ (nope +
# rope against v_head_dim) and of which the rotary part is shared.


def _latent_decode_kernel(lim_ref, q_ref, qpe_ref, kv_ref, o_ref, m_ref,
                          l_ref, acc_ref, *, bs: int, rank: int,
                          scale: float):
    """q_ref [1, H, rank], qpe_ref [1, H, rope]: a slot's absorbed
    queries; kv_ref [1, bs, W]: bs cached rows `[c | k_pe | padding]`,
    brought from HBM once and used twice: the `rank + rope` lanes
    against the queries for the scores, the first `rank` for the
    weighted sum."""
    rope = qpe_ref.shape[-1]
    s = pl.program_id(1)
    ns = pl.num_programs(1)

    @pl.when(s == 0)
    def _():
        m_ref[:] = jnp.full_like(m_ref, M_INIT)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    lo = lim_ref[pl.program_id(0), 0]
    hi = lim_ref[pl.program_id(0), 1]
    first, last = _decode_block_range(lo, hi, bs)
    start = jnp.minimum(first + s, last) * bs   # as the index map's
    given = (first + s <= last) & (start < hi) & (start + bs > lo)
    whole = (start >= lo) & (start + bs <= hi)

    def update(masked: bool):
        c = kv_ref[0, :, :rank]                 # [bs, rank]
        x = lax.dot_general(q_ref[0], c, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
        x = x + lax.dot_general(
            qpe_ref[0], kv_ref[0, :, rank:rank + rope],
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        x = x * scale                           # [H, bs]
        if masked:
            col = start + lax.broadcasted_iota(jnp.int32, x.shape, 1)
            valid = (col >= lo) & (col < hi)
            x = jnp.where(valid, x, M_INIT)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(x, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(x - _lanes(m_new, bs))
        if masked:
            p = jnp.where(valid, p, 0.0)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        pv = lax.dot_general(p.astype(c.dtype), c, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * _lanes(alpha, rank) + pv
        m_ref[...] = m_new

    @pl.when(given & whole)
    def _():
        update(False)

    @pl.when(given & jnp.logical_not(whole))
    def _():
        update(True)

    @pl.when(s == ns - 1)
    def _():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / _lanes(l, rank)).astype(o_ref.dtype)


def latent_decode(q_lat: jax.Array, q_pe: jax.Array, rows: jax.Array,
                  lo: jax.Array, hi: jax.Array, *, scale: float,
                  layer=None, interpret: bool = False
                  ) -> Optional[jax.Array]:
    """One absorbed query a head and slot against the slot's cached
    latent rows [lo, hi): q_lat [B, H, rank], q_pe [B, H, rope]; rows
    [B, S, W] (`[c | k_pe]` and, where W > rank + rope, padding to
    whole lane tiles: a slab engine's merged rows), or with `layer`
    the STACKED slab [L, B, S, W] of a layer scan that carries it,
    read where it lies (`_flash_decode`). Online
    softmax in float32. Returns [B, H, rank] in latent space (`w_uv`
    lifts it), or None for shapes the kernel does not cover."""
    B, H, rank = q_lat.shape
    rope = q_pe.shape[-1]
    S, width = rows.shape[-2], rows.shape[-1]
    stacked = layer is not None
    bs = _pick_block(S, (512, 256, 128))
    if bs is None or H % 8 or rank % 128 or width < rank + rope \
            or rows.ndim != 3 + stacked:
        return None
    limits, kv_index = _decode_walk(lo, hi, layer, B, bs)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, S // bs),
        in_specs=[
            pl.BlockSpec((1, H, rank), lambda b, s, lim: (b, 0, 0)),
            pl.BlockSpec((1, H, rope), lambda b, s, lim: (b, 0, 0)),
            pl.BlockSpec(((None,) if stacked else ()) + (1, bs, width),
                         kv_index),
        ],
        out_specs=pl.BlockSpec((1, H, rank), lambda b, s, lim: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((H, 128), jnp.float32),
            pltpu.VMEM((H, 128), jnp.float32),
            pltpu.VMEM((H, rank), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_latent_decode_kernel, bs=bs, rank=rank,
                          scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, rank), q_lat.dtype),
        interpret=interpret,
        name="latent_decode",
    )(limits, q_lat, q_pe, rows)


def _latent_prefill_kernel(lim_ref, q_ref, qpe_ref, k_ref, kpe_ref, v_ref,
                           o_ref, m_ref, l_ref, acc_ref, *, bq: int,
                           bs: int, g: int, scale: float):
    """q_ref [1, g, bq, nope], qpe_ref [1, g, bq, rope]: g heads'
    queries; k_ref [1, g, bs, nope], v_ref [1, g, bs, dv]: their keys
    and values; kpe_ref [1, bs, rope]: the rotary key all heads share.
    Running softmax state a row a (head, query row), head-major."""
    b, qi, ki = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)
    dv = v_ref.shape[-1]

    @pl.when(ki == 0)
    def _():
        m_ref[:] = jnp.full_like(m_ref, M_INIT)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    base = lim_ref[b, 0]             # absolute position of q row 0
    kv_hi = lim_ref[b, 1]            # valid key rows
    start, some, whole = _prefill_block_kind(base, kv_hi, qi, ki, bq, bs,
                                             None)

    def update(valid=None):
        kpe = kpe_ref[0]             # [bs, rope]
        for h in range(g):
            rows = pl.ds(h * bq, bq)
            x = lax.dot_general(
                q_ref[0, h], k_ref[0, h], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            x = x + lax.dot_general(
                qpe_ref[0, h], kpe, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            x = x * scale                                   # [bq, bs]
            if valid is not None:
                x = jnp.where(valid, x, M_INIT)
            m_prev = m_ref[rows, :]
            m_new = jnp.maximum(m_prev, jnp.max(x, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(x - _lanes(m_new, bs))
            if valid is not None:
                p = jnp.where(valid, p, 0.0)
            l_ref[rows, :] = alpha * l_ref[rows, :] \
                + jnp.sum(p, axis=1, keepdims=True)
            vb = v_ref[0, h]
            pv = lax.dot_general(
                p.astype(vb.dtype), vb, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)         # [bq, dv]
            acc_ref[rows, :] = acc_ref[rows, :] * _lanes(alpha, dv) + pv
            m_ref[rows, :] = m_new

    @pl.when(whole)
    def _():
        update()

    @pl.when(some & jnp.logical_not(whole))
    def _():
        q_lo = base + qi * bq
        col = start + lax.broadcasted_iota(jnp.int32, (bq, bs), 1)
        qpos = q_lo + lax.broadcasted_iota(jnp.int32, (bq, bs), 0)
        update((col <= qpos) & (col < kv_hi))

    @pl.when(ki == nk - 1)
    def _():
        for h in range(g):
            rows = pl.ds(h * bq, bq)
            l = jnp.maximum(l_ref[rows, :], 1e-30)
            o_ref[0, h] = (acc_ref[rows, :] / _lanes(l, dv)) \
                .astype(o_ref.dtype)


# heads a grid step: the shared rotary key block is fetched once for
# them and the step's fixed cost paid once
_LATENT_PREFILL_HEADS = 4


def _latent_prefill_blocks(Sq: int, S: int, H: int):
    """(bq, bs, heads a step), or None for shapes the kernel does not
    cover. A head's keys serve its own queries alone (no group shares
    them), so a key block's operations a byte are bq: 512 rows where
    the GQA kernel takes 256."""
    bq = _pick_block(Sq, (512, 256, 128, 64, 32, 16))
    bs = _pick_block(S, (512, 256, 128, 64, 32, 16))
    g = _pick_block(H, (_LATENT_PREFILL_HEADS, 2, 1))
    if bq is None or bs is None:
        return None
    return bq, bs, g


def latent_prefill(q_nope, q_pe, k_nope, k_pe, v, base, kv_hi, *,
                   scale: float, interpret: bool = False
                   ) -> Optional[jax.Array]:
    """Causal blocked attention over a latent model's MATERIALISED
    heads, head-major: q_nope [B, H, Sq, nope] and q_pe [B, H, Sq,
    rope] against k_nope [B, H, S, nope], the one shared k_pe [B, S,
    rope] (two operands: no [S, H, nope + rope] concatenation exists)
    and v [B, H, S, dv], whose width is not the keys'. Query row i of
    batch b stands at position base[b] + i and sees key rows
    <= its position and < kv_hi[b]; the output of a query row at a
    position >= kv_hi[b] is unspecified (padding: a query block made
    only of such rows does no work and comes back zero). Block kinds
    and skipped blocks as `_flash_prefill` has them. Returns [B, H,
    Sq, dv], or None for shapes the kernel does not cover."""
    B, H, Sq, nope = q_nope.shape
    S, dv, rope = k_nope.shape[2], v.shape[-1], q_pe.shape[-1]
    blocks = _latent_prefill_blocks(Sq, S, H)
    if blocks is None or nope % 128 or dv % 128 or rope % 8:
        return None
    return _latent_prefill_call(q_nope, q_pe, k_nope, k_pe, v, base, kv_hi,
                                blocks=blocks, scale=scale,
                                interpret=interpret)


def latent_prefill_block_kinds(Sq: int, S: int, H: int, base: int,
                               kv_hi: int):
    """Grid steps by kind of one sequence's `latent_prefill` call
    over H heads, as `prefill_block_kinds` counts `flash_prefill`'s:
    a step is a (group of heads, query block, key block); None where
    the blocks do not fit the shape."""
    blocks = _latent_prefill_blocks(Sq, S, H)
    if blocks is None:
        return None
    bq, bs, g = blocks
    kinds = _count_kinds(Sq // bq, _prefill_key_steps(S, bq, bs, None),
                         bq, bs, base, kv_hi, None)
    return {kind: H // g * n for kind, n in kinds.items()}


# a jit of its own, as `_prefill_call` is
@functools.partial(jax.jit, static_argnames=("blocks", "scale", "interpret"))
def _latent_prefill_call(q_nope, q_pe, k_nope, k_pe, v, base, kv_hi, *,
                         blocks, scale, interpret):
    B, H, Sq, nope = q_nope.shape
    S, dv, rope = k_nope.shape[2], v.shape[-1], q_pe.shape[-1]
    bq, bs, g = blocks
    limits = jnp.stack(
        [base.astype(jnp.int32), kv_hi.astype(jnp.int32)], axis=1)

    def key_block(b, hg, qi, ki, lim):
        first, last = _prefill_block_range(lim[b, 0], lim[b, 1], qi, bq,
                                           bs, None)
        return jnp.minimum(first + ki, last)

    def q_index(b, hg, qi, ki, lim):
        return (b, hg, qi, 0)

    def kv_index(b, hg, qi, ki, lim):
        return (b, hg, key_block(b, hg, qi, ki, lim), 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, H // g, Sq // bq, _prefill_key_steps(S, bq, bs, None)),
        in_specs=[
            pl.BlockSpec((1, g, bq, nope), q_index),
            pl.BlockSpec((1, g, bq, rope), q_index),
            pl.BlockSpec((1, g, bs, nope), kv_index),
            pl.BlockSpec((1, bs, rope),
                         lambda b, hg, qi, ki, lim:
                         (b, key_block(b, hg, qi, ki, lim), 0)),
            pl.BlockSpec((1, g, bs, dv), kv_index),
        ],
        out_specs=pl.BlockSpec((1, g, bq, dv), q_index),
        scratch_shapes=[
            pltpu.VMEM((g * bq, 128), jnp.float32),
            pltpu.VMEM((g * bq, 128), jnp.float32),
            pltpu.VMEM((g * bq, dv), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_latent_prefill_kernel, bq=bq, bs=bs, g=g,
                          scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, dv), q_nope.dtype),
        interpret=interpret,
        name="latent_prefill",
    )(limits, q_nope, q_pe, k_nope, k_pe, v)
