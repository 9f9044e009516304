"""Attention ops with a pluggable backend.

`attention()` is the single entry point the models call. On TPU it
dispatches to the Pallas flash-attention kernels (ome_tpu/ops/flash.py);
elsewhere (CPU test mesh) it uses an XLA reference implementation. The
interface is *structural* — query positions, valid-KV length, sliding
window — never a materialized mask: the flash kernels turn these into
iota comparisons against scalar limits, and only the XLA fallback
builds a boolean mask. Both compute GQA attention with fp32 softmax
accumulation.
"""

from __future__ import annotations

import contextlib
import os
from contextvars import ContextVar
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .. import device
from . import note_decline

NEG_INF = -2.0e38

# fp32 logits bytes above which prefill switches to the flash kernel
# (materialized [B, H, Sq, Skv] attention stops fitting comfortably)
_XLA_PREFILL_CAP = 256 * 1024 * 1024


# The mesh whose "tp" axis the heads of the programs being traced are
# sharded over (engine/sharded.py sets it around its traces, the same
# way it scopes int4_matmul.kernel_disabled); None = one device.
_tp_mesh: ContextVar = ContextVar("ome_attn_tp_mesh", default=None)


@contextlib.contextmanager
def heads_sharded_over(mesh):
    token = _tp_mesh.set(mesh)
    try:
        yield
    finally:
        _tp_mesh.reset(token)


def _flash(q, k, v, positions, kv_len, **kw) -> Optional[jax.Array]:
    """flash.flash_attention, run per device on its own heads when the
    trace is head-sharded. GSPMD cannot partition a Mosaic kernel
    ("wrap the call in a shard_map"), and attention mixes nothing
    across heads: q is sharded on H, the cache on KV heads, each
    device's kernel sees whole GQA groups and no collective is
    needed."""
    from . import flash

    def local(q, k, v, positions, kv_len):
        return flash.flash_attention(q, k, v, positions=positions,
                                     kv_len=kv_len, **kw)

    mesh = _tp_mesh.get()
    if mesh is None or positions is None:
        return local(q, k, v, positions, kv_len)
    tp = mesh.shape["tp"]
    H, K = q.shape[2], flash._kv_heads(k, q.shape[3])
    if H % tp or K % tp:
        return None
    if kv_len is None:
        kv_len = jnp.full((q.shape[0],), k.shape[1], jnp.int32)

    def per_device(x):
        shape = x.shape[:2] + (x.shape[2] // tp,) + x.shape[3:]
        return jax.ShapeDtypeStruct(shape, x.dtype)

    # the kernels decline by returning None, which shard_map cannot
    # carry: ask them first, abstractly, at the per-device shapes
    if jax.eval_shape(local, per_device(q), per_device(k),
                      per_device(v), positions, kv_len) is None:
        return None

    def heads(x):
        # [B, S, heads, D], or a cache's merged rows [B, S, K * D]:
        # a chip's KV heads are contiguous lanes of those
        return P(None, None, "tp", *(None,) * (x.ndim - 3))

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(heads(q), heads(k), heads(v), P(), P()),
        out_specs=heads(q), check_vma=False)(q, k, v, positions, kv_len)


def _layer_of(slab: jax.Array, layer) -> jax.Array:
    """Layer `layer` of a stacked [L, B, S, ..]: a copy of it."""
    return jax.lax.dynamic_index_in_dim(slab, layer, 0, keepdims=False)


def _auto_backend(B: int, Sq: int, H: int, Skv: int) -> str:
    """The backend `attention` takes when none is asked for."""
    backend = os.environ.get("OME_ATTN_BACKEND")
    if backend is not None:
        return backend
    if not device.on_tpu():
        return "xla"
    if Sq > 1 and B * H * Sq * Skv * 4 <= _XLA_PREFILL_CAP:
        # SHORT-sequence prefill: XLA's materialized-mask attention
        # beats the flash kernel (measured 249 vs 320 ms on the
        # bench shape — at small S the [Sq, Skv] float32 logits are
        # cheap and XLA's fusion wins; flash earns its keep when the
        # materialization would blow HBM, i.e. long context)
        return "xla"
    return "pallas"


def prefill_block_kinds(Sq: int, Skv: int, H: int, K: int, D: int,
                        base: int, window: Optional[int],
                        valid: Optional[int] = None):
    """Grid steps by kind ({"none", "whole", "edge"}:
    flash.prefill_block_kinds) of the kernel call that `attention`
    makes for one sequence's `Sq` prompt rows at positions `base`
    on, the first `valid` of them real (None: all), over `Skv` cache
    rows of which `base + valid` are valid (what llama.forward
    passes); None where that call takes XLA's attention or the kernel
    declines the shape. Host arithmetic."""
    if Sq == 1 or H % K or \
            not _auto_backend(1, Sq, H, Skv).startswith("pallas"):
        return None
    from . import flash
    return flash.prefill_block_kinds(
        Sq, Skv, K, H // K, D, base, base + (Sq if valid is None else valid),
        window)


def latent_prefill_block_kinds(Sq: int, Skv: int, H: int, nope: int,
                               rope: int, dv: int, base: int,
                               valid: Optional[int] = None):
    """The same of the kernel call that `latent_prefill` makes for H
    heads of one sequence (flash.latent_prefill_block_kinds: a step
    is a group of heads, a query block and a key block)."""
    if not _latent_backend(None, 1, Sq, H, Skv).startswith("pallas") \
            or nope % 128 or dv % 128 or rope % 8:
        return None
    from . import flash
    return flash.latent_prefill_block_kinds(
        Sq, Skv, H, base, base + (Sq if valid is None else valid))


def make_causal_mask(q_pos: jax.Array, kv_pos: jax.Array,
                     kv_len: Optional[jax.Array] = None) -> jax.Array:
    """Boolean mask [.., Sq, Skv]: True = attend.

    q_pos: [B, Sq] absolute positions of queries
    kv_pos: [Skv] absolute positions of kv slots
    kv_len: optional [B] number of valid kv slots (for fixed-size caches)
    """
    m = kv_pos[None, None, :] <= q_pos[:, :, None]  # [B, Sq, Skv]
    if kv_len is not None:
        m = m & (kv_pos[None, None, :] < kv_len[:, None, None])
    return m


def xla_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                  mask: Optional[jax.Array] = None,
                  scale: Optional[float] = None,
                  logit_softcap: Optional[float] = None,
                  sinks: Optional[jax.Array] = None) -> jax.Array:
    """Reference GQA attention.

    q: [B, Sq, H, D]; k, v: [B, Skv, K, D] with H % K == 0.
    mask: [B, Sq, Skv] boolean (True = attend) or None for full causal-free.
    sinks: [H] per-head learned sink logits (gpt_oss): a virtual extra
    key whose probability mass is dropped after the softmax.
    Returns [B, Sq, H, D] in q.dtype.
    """
    B, Sq, H, D = q.shape
    K = k.shape[2]
    G = H // K
    scale = scale if scale is not None else D ** -0.5
    qg = q.reshape(B, Sq, K, G, D)
    logits = jnp.einsum("bskgd,btkd->bkgst", qg, k,
                        preferred_element_type=jnp.float32) * scale
    if logit_softcap:
        logits = jnp.tanh(logits / logit_softcap) * logit_softcap
    if mask is not None:
        logits = jnp.where(mask[:, None, None, :, :], logits, NEG_INF)
    if sinks is not None:
        s = sinks.astype(jnp.float32).reshape(K, G)
        col = jnp.broadcast_to(s[None, :, :, None, None],
                               (B, K, G, Sq, 1))
        aug = jnp.concatenate([logits, col], axis=-1)
        probs = jax.nn.softmax(aug, axis=-1)[..., :-1]
    else:
        probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgst,btkd->bskgd", probs.astype(v.dtype), v)
    return out.reshape(B, Sq, H, D)


def attention(q: jax.Array, k: jax.Array, v: jax.Array,
              positions: Optional[jax.Array] = None,
              kv_len: Optional[jax.Array] = None,
              sliding_window: Optional[int] = None,
              scale: Optional[float] = None,
              logit_softcap: Optional[float] = None,
              backend: Optional[str] = None,
              sinks: Optional[jax.Array] = None,
              layer=None) -> jax.Array:
    """Dispatching attention entry point used by all models.

    k, v: [B, Skv, K, D], or a slab engine's merged cache rows
    [B, Skv, K * D] (`llama.KVCache`), told apart by rank.
    layer: k and v are the stacked slabs [L, B, Skv, K * D] (or
    [.., K, D]) that a layer scan carries, and attention is over layer
    `layer` of them (an int or a traced index). The decode kernel
    reads it where it lies (ops/flash.py); every other path takes the
    layer out first.
    positions: [B, Sq] absolute query positions (contiguous per row);
    None disables causal masking entirely (bidirectional attention).
    kv_len: [B] valid KV rows for fixed-capacity caches. The output
    of a query row at a position >= kv_len is unspecified: the row is
    padding (a right-padded prompt's tail, whose own key is no valid
    row); the prefill kernel skips query blocks made only of them and
    hands back zeros, XLA's path some value under the same mask.
    backend: None (auto), "xla", "pallas", or "pallas_interpret" (the
    Pallas kernels run interpreted on CPU — for numerics tests).
    sinks: [H] gpt_oss attention-sink logits — handled by the XLA
    path only (the flash kernels decline and fall back).
    """
    if layer is not None and (
            q.shape[1] > 1 or (_tp_mesh.get() is not None
                               and positions is not None)):
        # not the decode kernel's call on one device (a prompt, or a
        # head-sharded trace whose per-device kernel takes one
        # layer's slab): take the layer out here, once
        k, v, layer = _layer_of(k, layer), _layer_of(v, layer), None
    if sinks is not None:
        backend = "xla"
    elif backend is None:
        backend = _auto_backend(q.shape[0], q.shape[1], q.shape[2],
                                k.shape[1])
    if backend in ("pallas", "pallas_interpret"):
        out = _flash(
            q, k, v, positions, kv_len,
            sliding_window=sliding_window, scale=scale,
            logit_softcap=logit_softcap,
            interpret=(backend == "pallas_interpret"), layer=layer)
        if out is not None:
            return out
        note_decline("flash_attention",
                     f"q{tuple(q.shape)} kv{tuple(k.shape)} outside "
                     f"the kernels' coverage")
    if layer is not None:       # the XLA path, or a kernel that declined
        k, v = _layer_of(k, layer), _layer_of(v, layer)
    if k.ndim == 3:             # merged rows: the heads apart again
        heads = k.shape[2] // q.shape[3]
        k = k.reshape(k.shape[:2] + (heads, -1))
        v = v.reshape(v.shape[:2] + (heads, -1))
    mask = None
    if positions is not None:
        kv_pos = jnp.arange(k.shape[1], dtype=jnp.int32)
        mask = make_causal_mask(positions, kv_pos, kv_len)
        if sliding_window is not None:
            mask = mask & (kv_pos[None, None, :]
                           > positions[:, :, None] - sliding_window)
    elif kv_len is not None:
        kv_pos = jnp.arange(k.shape[1], dtype=jnp.int32)
        mask = jnp.broadcast_to(
            kv_pos[None, None, :] < kv_len[:, None, None],
            (q.shape[0], q.shape[1], k.shape[1]))
    return xla_attention(q, k, v, mask=mask, scale=scale,
                         logit_softcap=logit_softcap, sinks=sinks)


# -- latent attention (MLA: models/mla.py) ---------------------------------


def _latent_backend(backend: Optional[str], B: int, Sq: int, H: int,
                    Skv: int) -> str:
    """As `attention` chooses, but a head-sharded trace takes the
    einsums: GSPMD cannot partition a Mosaic kernel and the latent
    kernels have no per-device wrapper."""
    if backend is None:
        backend = _auto_backend(B, Sq, H, Skv)
    if backend == "pallas" and _tp_mesh.get() is not None:
        return "xla"
    return backend


def xla_latent_decode(q_lat: jax.Array, q_pe: jax.Array, rows: jax.Array,
                      lo: jax.Array, hi: jax.Array, scale: float
                      ) -> jax.Array:
    """The einsum path of `latent_decode`: a [B, H, S] array of scores
    over every cached row, those outside [lo, hi) masked."""
    rank, rope = q_lat.shape[-1], q_pe.shape[-1]
    c, k_pe = rows[..., :rank], rows[..., rank:rank + rope]
    scores = (jnp.einsum("bhr,btr->bht", q_lat, c,
                         preferred_element_type=jnp.float32)
              + jnp.einsum("bhp,btp->bht", q_pe, k_pe,
                           preferred_element_type=jnp.float32)) * scale
    t = jnp.arange(rows.shape[1], dtype=jnp.int32)[None, None, :]
    seen = (t >= lo[:, None, None]) & (t < hi[:, None, None])
    probs = jax.nn.softmax(jnp.where(seen, scores, NEG_INF), axis=-1)
    return jnp.einsum("bht,btr->bhr", probs.astype(c.dtype), c)


def latent_decode(q_lat: jax.Array, q_pe: jax.Array, rows: jax.Array,
                  positions: jax.Array, kv_len: Optional[jax.Array], *,
                  rank: int, scale: float, layer=None,
                  backend: Optional[str] = None) -> jax.Array:
    """A decode step's latent attention: the absorbed queries q_lat
    [B, H, rank] and q_pe [B, H, rope] of each slot against its cached
    rows `[c | k_pe]` up to its position; returns [B, H, rank], still
    in latent space. rows: [B, S, W] (or [B, S, 1, W]), W >= rank +
    rope, the lanes behind `k_pe` padding no one reads; with `layer`, the stacked [L, B, S, ..] of a layer scan
    that carries the slab, which the kernel reads where it lies and
    every other path takes the layer out of first."""
    B, H, _ = q_lat.shape
    stacked = layer is not None
    if rows.ndim - stacked == 4:    # the one latent "head" apart
        rows = rows.reshape(rows.shape[:-2] + (-1,))
    S = rows.shape[-2]
    pos = positions[:, 0]
    hi = pos + 1 if kv_len is None else \
        jnp.minimum(pos + 1, jnp.broadcast_to(kv_len, (B,)))
    lo = jnp.zeros_like(hi)
    backend = _latent_backend(backend, B, 1, H, S)
    if backend in ("pallas", "pallas_interpret"):
        from . import flash
        out = flash.latent_decode(
            q_lat, q_pe.astype(q_lat.dtype), rows, lo, hi, scale=scale,
            layer=layer, interpret=(backend == "pallas_interpret"))
        if out is not None:
            return out
        note_decline("latent_decode",
                     f"q{tuple(q_lat.shape)} rows{tuple(rows.shape)} "
                     f"outside the kernel's coverage")
    if stacked:
        rows = _layer_of(rows, layer)
    return xla_latent_decode(q_lat, q_pe, rows, lo, hi, scale)


def xla_latent_prefill(q_nope, q_pe, k_nope, k_pe, v, base, kv_hi,
                       scale: float) -> jax.Array:
    """The einsum path of `latent_prefill`: a whole [B, H, Sq, S]
    array of scores under the causal and length mask."""
    scores = (jnp.einsum("bhsk,bhtk->bhst", q_nope, k_nope,
                         preferred_element_type=jnp.float32)
              + jnp.einsum("bhsp,btp->bhst", q_pe, k_pe,
                           preferred_element_type=jnp.float32)) * scale
    q_pos = base[:, None] + jnp.arange(q_nope.shape[2], dtype=jnp.int32)
    t = jnp.arange(k_nope.shape[2], dtype=jnp.int32)
    seen = (t[None, None, :] <= q_pos[:, :, None]) \
        & (t[None, None, :] < kv_hi[:, None, None])
    probs = jax.nn.softmax(jnp.where(seen[:, None], scores, NEG_INF),
                           axis=-1)
    return jnp.einsum("bhst,bhtv->bhsv", probs.astype(v.dtype), v)


def latent_prefill(q_nope: jax.Array, q_pe: jax.Array, k_nope: jax.Array,
                   k_pe: jax.Array, v: jax.Array, positions: jax.Array,
                   kv_len: Optional[jax.Array], *, scale: float,
                   backend: Optional[str] = None) -> jax.Array:
    """A prompt's latent attention over MATERIALISED heads, head-major:
    q_nope [B, H, Sq, nope], q_pe [B, H, Sq, rope], k_nope [B, H, S,
    nope], the one k_pe [B, S, rope] all heads share, v [B, H, S, dv];
    returns [B, H, Sq, dv]. With `kv_len` ([B] valid rows) the keys
    are a cache's rows, row t at position t, and the queries stand at
    `positions` (contiguous per row); with None the keys are the
    queries' own rows (plain causal). The output of a query row at a
    position >= kv_len is unspecified, as `attention`'s is."""
    B, H, Sq, _ = q_nope.shape
    S = k_nope.shape[2]
    if kv_len is None:
        base = jnp.zeros((B,), jnp.int32)
        kv_hi = jnp.full((B,), S, jnp.int32)
    else:
        base = positions[:, 0].astype(jnp.int32)
        kv_hi = jnp.broadcast_to(kv_len, (B,)).astype(jnp.int32)
    backend = _latent_backend(backend, B, Sq, H, S)
    if backend in ("pallas", "pallas_interpret"):
        from . import flash
        out = flash.latent_prefill(
            q_nope, q_pe, k_nope, k_pe, v, base, kv_hi, scale=scale,
            interpret=(backend == "pallas_interpret"))
        if out is not None:
            return out
        note_decline("latent_prefill",
                     f"q{tuple(q_nope.shape)} k{tuple(k_nope.shape)} "
                     f"outside the kernel's coverage")
    return xla_latent_prefill(q_nope, q_pe, k_nope, k_pe, v, base, kv_hi,
                              scale)
