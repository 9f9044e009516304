"""Weight-only quantization (int8 per-channel, int4 groupwise) for serving.

Decode is HBM-bandwidth-bound: every generated token streams all
weights once (bench.py roofline). Symmetric per-output-channel int8
halves the bytes per step vs bf16; groupwise int4 halves them again.
XLA fuses the dequant (nibble unpack, convert, scale) into the matmul
operand read, so the MXU still computes in bf16 while HBM traffic
drops 2x/4x. This is the runtime analog of the reference catalog's
int4/fp8 model-format entries (model.go:262-268) for checkpoints that
ship full precision.

int4 packing is TPU-deliberate: two nibbles per int8 byte, paired as
[first half | second half] of the WHOLE packing axis (byte j holds
rows j and K/2+j), so dequant is two arithmetic shifts + ONE
concatenate — no stride-2 interleave, which XLA:TPU cannot fuse into
the matmul read (measured 1.8x slower on v5e) — and the fused Pallas
kernel (ops/int4_matmul.py) reads each half's matching x slice and
scale rows as CONTIGUOUS blocks (group-interleaved pairing forced a
strided in-kernel shuffle that crashed or starved Mosaic). Scales are
per-(group x output-channel), GPTQ-style, groups contiguous along the
axis.

QTensor is a registered pytree (scan/jit/shard-friendly), dequantized
at use by models/llama.py's weight accessor `_w`.
"""

from __future__ import annotations

import dataclasses
import functools
import logging
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax


@dataclasses.dataclass
class QTensor:
    """Quantized weight + broadcastable f32 scale.

    bits=8: `q` int8 in the original shape, `s` with contraction dims
    of size 1 (per-output-channel).
    bits=4: `q` int8 carrying two nibbles, with the packing axis
    halved; `s` with the packing axis sized n_groups and other
    contraction dims 1.
    """

    q: jax.Array
    s: jax.Array
    bits: int = 8            # static
    # static: packing/group axis for bits=4, stored NEGATIVE (offset
    # from the last dim) so it survives lax.scan slicing layer leaves
    # off the stacked [L, ...] tree and gather prepending index dims
    axis: int = -1

    def dequant(self, dtype=jnp.bfloat16) -> jax.Array:
        if self.bits == 8:
            return (self.q.astype(jnp.float32) * self.s).astype(dtype)
        return _unpack4(self.q, self.s, self.axis).astype(dtype)

    def take(self, idx: jax.Array, dtype=jnp.bfloat16) -> jax.Array:
        """Row gather (embedding lookup) without full dequant."""
        rows = jnp.take(self.q, idx, axis=0)
        scales = jnp.take(self.s, idx, axis=0)
        if self.bits == 8:
            return (rows.astype(jnp.float32) * scales).astype(dtype)
        return _unpack4(rows, scales, self.axis).astype(dtype)

    @property
    def shape(self):
        if self.bits == 8:
            return self.q.shape
        sh = list(self.q.shape)
        sh[self.axis] *= 2
        return tuple(sh)

    @property
    def size(self):
        n = 1
        for d in self.shape:
            n *= d
        return n


jax.tree_util.register_dataclass(
    QTensor, data_fields=("q", "s"), meta_fields=("bits", "axis"))


def _unpack4(q: jax.Array, s: jax.Array, axis: int) -> jax.Array:
    """Dequantize half-packed int4: q [..., K/2, ...] -> f32 [..., K, ...].

    Byte j holds original rows j (low nibble) and K/2+j (high nibble),
    so unpack is one concatenate of the two nibble planes along the
    axis; s has n_groups contiguous groups along the axis.
    """
    axis = axis % q.ndim
    n_groups = s.shape[axis]
    pre, post = q.shape[:axis], q.shape[axis + 1:]
    lo = jnp.left_shift(q, 4) >> 4                # sign-extended nibble
    hi = q >> 4                                   # arithmetic shift
    full = jnp.concatenate([lo, hi], axis=axis).astype(jnp.float32)
    K = 2 * q.shape[axis]
    gsize = K // n_groups
    fr = full.reshape(pre + (n_groups, gsize) + post)
    sr = s.reshape(s.shape[:axis] + (n_groups, 1) + s.shape[axis + 1:])
    out = fr * sr
    return out.reshape(pre + (K,) + post)


# The three quantizers are jitted (contract_axes, a tuple, is static):
# run op by op, one [36, 2560, 9728] leaf keeps four float32 copies of
# itself alive at once, 14 GB, and quantizing a 4 B model on a 16 GB
# chip runs out of memory at load. Fused, a leaf is read twice in its
# own dtype and only the quantized tensor is written.


@functools.partial(jax.jit, static_argnames=("contract_axes",))
def quantize_tensor(w: jax.Array, contract_axes) -> QTensor:
    """Per-output-channel symmetric int8: scales span `contract_axes`
    (the dims the matmul sums over), so each output channel gets its
    own dynamic range."""
    w32 = jnp.asarray(w, jnp.float32)
    amax = jnp.max(jnp.abs(w32), axis=tuple(contract_axes),
                   keepdims=True)
    s = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(w32 / s), -127, 127).astype(jnp.int8)
    return QTensor(q=q, s=s, bits=8)


@functools.partial(jax.jit, static_argnames=("contract_axes",))
def quantize_tensor_fp8(w: jax.Array, contract_axes) -> QTensor:
    """Per-output-channel scaled float8_e4m3: same byte footprint as
    int8 but a floating 4-bit mantissa — the v6e-native weight format
    (v6e converts fp8 in the MXU datapath; on v5e it lowers to the
    same convert+scale XLA fuses for int8). Scale to the e4m3 max so
    the channel's range uses the format's full span."""
    w32 = jnp.asarray(w, jnp.float32)
    amax = jnp.max(jnp.abs(w32), axis=tuple(contract_axes),
                   keepdims=True)
    s = jnp.maximum(amax, 1e-8) / 448.0  # e4m3 finite max
    q = (w32 / s).astype(jnp.float8_e4m3fn)
    return QTensor(q=q, s=s, bits=8)


@functools.partial(jax.jit, static_argnames=("contract_axes", "group"))
def quantize_tensor_int4(w: jax.Array, contract_axes,
                         group: int = 128) -> QTensor:
    """Groupwise symmetric int4, concat-packed along the first
    contraction axis. Falls back to one group when the axis doesn't
    split evenly into even-sized groups."""
    w = jnp.asarray(w)
    axis = contract_axes[0]
    K = w.shape[axis]
    if K % group == 0 and group % 2 == 0:
        n_groups = K // group
    elif K % 2 == 0:
        n_groups = 1  # axis too small/ragged for groups: one scale
    else:
        raise ValueError(f"int4 needs an even packing dim, got {K}")
    gsize = K // n_groups
    pre, post = w.shape[:axis], w.shape[axis + 1:]
    # reshapes and slices stay in the leaf's own dtype and float32
    # begins inside the fused passes: converting first made XLA keep
    # a float32 copy of the leaf (3.6 GB for one stacked MLP
    # projection of a 4 B model; tests/test_aot_tpu_compile.py)
    wg = w.reshape(pre + (n_groups, gsize) + post)
    # scales span the group slice plus the OTHER contraction dims
    other = tuple(a + 1 if a > axis else a
                  for a in contract_axes[1:])
    amax = jnp.max(jnp.abs(wg.astype(jnp.float32)),
                   axis=(axis + 1,) + other, keepdims=True)
    s = jnp.maximum(amax, 1e-8) / 7.0

    def quantized(x, scale):
        return jnp.clip(jnp.round(x.astype(jnp.float32) / scale),
                        -7, 7).astype(jnp.int8)

    if n_groups % 2 == 0:
        # each nibble half is a run of whole groups: quantize the two
        # runs where they lie and pack the second over the first
        half = n_groups // 2
        lo, hi = (quantized(
            lax.slice_in_dim(wg, i * half, (i + 1) * half, axis=axis),
            lax.slice_in_dim(s, i * half, (i + 1) * half, axis=axis))
            for i in (0, 1))
    else:
        # one group (or an odd number): the halves cut through a
        # group, so quantize whole and split the result
        lo, hi = jnp.split(
            quantized(wg, s).reshape(pre + (K,) + post), 2, axis=axis)
    packed = ((hi << 4) | (lo & 0x0F)).reshape(
        pre + (K // 2,) + post)                   # [., K/2, .]
    s = jnp.squeeze(s, axis=axis + 1)             # [., n_groups, .(1s)]
    return QTensor(q=packed, s=s, bits=4, axis=axis - w.ndim)


# contraction axes per stacked-layer leaf ([L, ...]; axis 0 = layer)
_LAYER_CONTRACT = {
    # the projections out of the hidden size lie out-major (llama.
    # _init_layer_block): [L, heads, Dh, D], sum over the LAST dim; an
    # int4 leaf packs that dim, a row's nibbles side by side
    "wq": (3,), "wk": (3,), "wv": (3,),
    "wo": (2, 1),                          # [L, H, Dh, D]: sum over H,Dh
    "w_gate": (1,), "w_up": (1,),          # [L, D, F]
    "w_down": (1,),                        # [L, F, D]
    "we_gate": (2,), "we_up": (2,),        # [L, E, D, F]
    "we_down": (2,),                       # [L, E, F, D]
    "ws_gate": (1,), "ws_up": (1,), "ws_down": (1,),
    # MLA projections (models/mla.py); norms/biases stay fp
    "wq_a": (1,),                          # [L, D, q_rank]
    "wq_b": (3,),                          # [L, H, qk, q_rank], as wq
    "wkv_a": (1,),                         # [L, D, r+rope]
    "w_uk": (2,),                          # [L, H, nope, r]
    "w_uv": (2,),                          # [L, H, r, v]
    # hybrid models (Qwen3-Next): the attention's output gate and the
    # DeltaNet mixer's projections; conv, router, w_b / w_a stay fp
    "w_ogate": (3,),                       # [L, H, Dh, D], as wq
    "w_qkv": (1,), "w_z": (1,),            # [L, D, C] / [L, D, Hv*dv]
    "w_lin_out": (1,),                     # [L, Hv*dv, D]
}
_TOP_CONTRACT = {
    "embed": (1,),     # per-ROW scales: rows are both lookup outputs
    "lm_head": (0,),   # [D, V]: sum over D
}


def quantize_params(params: Dict[str, Any], mode: str = "int8",
                    group: int = 128) -> Dict[str, Any]:
    """Quantize the big matmul weights; norms/biases/router stay full
    precision (tiny, and routing is precision-sensitive).

    mode="int8": per-output-channel symmetric int8 everywhere.
    mode="fp8": per-output-channel scaled float8_e4m3 everywhere —
    the catalog's fp8 model-format analog (model.go:262-268) for
    full-precision checkpoints, v6e-targeted (same bytes as int8;
    v6e's MXU consumes fp8 natively).
    mode="int4": groupwise int4 for the layer matmuls; embed/lm_head
    stay int8 (their error feeds every position — the GPTQ convention
    of keeping embeddings at higher precision), and so do the
    down-projections (w_down/ws_down): their packing axis F is the
    tp-sharded row dim (parallel/sharding._LAYER_RULES), and nibble
    pairs spanning device shards would force GSPMD to all-gather the
    weight every step — worse than the bytes saved. wo also stays
    int8: its pack axis (Dh) sits under the H head dim, so the
    half-packed flattened layout the fused kernel streams can't stay
    contiguous for it.
    """
    if mode not in ("int8", "int4", "fp8"):
        raise ValueError(f"unknown quantization mode {mode!r}")
    int4 = mode == "int4"
    base_q = quantize_tensor_fp8 if mode == "fp8" else quantize_tensor
    _INT8_ONLY = {"w_down", "ws_down", "wo", "w_lin_out"}
    log = logging.getLogger("ome.models.quant")

    def q_layer(k: str, v):
        if k not in _LAYER_CONTRACT:
            return v
        axes = _LAYER_CONTRACT[k]
        if int4 and k not in _INT8_ONLY:
            try:
                return quantize_tensor_int4(v, axes, group=group)
            except ValueError as e:
                log.info("int4: %s falls back to int8 (%s)", k, e)
                return quantize_tensor(v, axes)
        return base_q(v, axes)

    out: Dict[str, Any] = {}
    for name, leaf in params.items():
        if name in ("layers", "dense_layers", "linear_layers"):
            out[name] = {k: q_layer(k, v) for k, v in leaf.items()}
        elif name in _TOP_CONTRACT:
            out[name] = base_q(leaf, _TOP_CONTRACT[name])
        else:
            out[name] = leaf
    return out


def quantized_bytes(params: Dict[str, Any]) -> int:
    """Weight bytes per full read (the decode-roofline numerator)."""
    total = 0
    for leaf in jax.tree.leaves(
            params, is_leaf=lambda x: isinstance(x, QTensor)):
        if isinstance(leaf, QTensor):
            total += leaf.q.size + leaf.s.size * 4
        else:
            total += leaf.size * leaf.dtype.itemsize
    return total
