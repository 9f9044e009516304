"""Fused int4 weight-only matmul (Pallas TPU kernel).

XLA cannot keep the int4 nibble unpack fused into a matmul operand
read — the dequantized bf16 weight round-trips through HBM. This
kernel streams the PACKED bytes (plus the
small group scales) into VMEM, unpacks with i32 shifts (Mosaic has no
i8 vector shifts), scales per group, and feeds the MXU — HBM traffic
is the packed 0.5 byte/weight, the decode roofline's whole point.

Layout contract (models/quant.py concat-pack): byte j of the packing
axis holds original rows j and K/2 + j. A leaf flattens to a 2D view
one of two ways, told apart by where its pack axis is
(`flatten_qtensor`):

  * in-major, `[K/2, N]`: the pack axis is the leaf's FIRST dim and a
    contraction dim, the trailing dims are output channels (the MLP's
    gate / up projections, `[D, F]`). Scales flatten to `[K/G, N]`.
  * out-major, `[N, K/2]`: the pack axis is the leaf's LAST dim, every
    dim before it an output channel. This is how the attention
    projections wq / wk / wv / w_ogate lie, `[heads, Dh, D]`
    (llama._init_layer_block): the decode step's bf16 dot reads them
    with the hidden size minor, and a leaf stored the other way was
    re-laid out before every use. A row's nibbles lie side by side in
    the lanes, the dot contracts both operands' minor dim, and the
    scales `[N, K/G]` are re-laid to `[K/G.., N, groups]` blocks in
    XLA ahead of the call (a sixteenth of the packed bytes).

wo `[H, Dh, D]` packs Dh under H, flattens neither way, and stays int8
(quant.quantize_params).

Dispatch rules (the kernel declines with None otherwise, the caller
takes the XLA dequant path, and the decline is noted — ops/__init__.py):
  * the half-packed axis splits into whole scale groups (K/2 % G == 0)
    and G is a multiple of 128 lanes, N divisible by 128;
  * the k-block is the largest whole number of groups that divides
    K/2 and stays within MAX_BKP packed rows (the unpacked tiles must
    fit Mosaic's scoped VMEM): 1024 rows at K=4096, all 1280 at
    K=2560, 256 at K=9728;
  * M (flattened batch) <= MAX_M — the kernel is for DECODE steps;
    big prefill matmuls are compute-bound and stay on the MXU-tiled
    XLA path.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import device
from . import note_decline

MAX_M = 256
# packed rows per k-step: (MAX_BKP, 512) int8 plus its i32/f32/bf16
# unpack tiles is what compiles inside v5e's 16 MiB scoped VMEM
# (tests/test_aot_tpu_compile.py holds the widths that proved it)
MAX_BKP = 1280

# Per-context kernel gate: a tp>1 engine disables the un-partitioned
# kernel around ITS traces only (contextvar — not a sticky process
# global, so tp=1 engines in the same process keep the fused path).
import contextlib
from contextvars import ContextVar

_kernel_enabled: ContextVar[bool] = ContextVar("ome_int4_kernel",
                                               default=True)


@contextlib.contextmanager
def kernel_disabled():
    token = _kernel_enabled.set(False)
    try:
        yield
    finally:
        _kernel_enabled.reset(token)


def _kernel(xl_ref, xh_ref, qp_ref, sl_ref, sh_ref, o_ref, acc_ref, *,
            gsize: int, out_major: bool = False):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    qp = qp_ref[...].astype(jnp.int32)
    # nibble extraction in i32: arithmetic shifts sign-extend
    hi = qp >> 4
    lo = (qp << 28) >> 28
    # half-packed layout (models/quant.py): packed row j of this block
    # holds original rows at the SAME offset in the axis' low half (lo
    # nibble) and high half (hi nibble). The matching x slices and
    # scale rows arrive as separate contiguous blocks (xl/xh, sl/sh),
    # so the unpack is shift -> scale -> dot twice: no concatenate
    # (a full-tile VMEM round-trip) and no strided shuffles.
    # f32 unpack-scale measured FASTER than bf16 on v5e Mosaic (bf16
    # VPU packing overhead outweighs the halved element width)
    if out_major:
        # qp [bn, bkp]: a row an output channel, its nibbles along the
        # lanes; sl / sh [bn, ng]. A group is gsize lanes (a multiple
        # of 128) times its column of the scales, and the dot
        # contracts both operands' minor dim
        ng = qp_ref.shape[1] // gsize

        def scaled(nib, s_ref):
            s = s_ref[...]
            return jnp.concatenate(
                [nib[:, g * gsize:(g + 1) * gsize].astype(jnp.float32)
                 * s[:, g:g + 1] for g in range(ng)],
                axis=1).astype(jnp.bfloat16)
        w_contract = 1
    else:
        bkp, bn = qp_ref.shape
        ng = bkp // gsize

        def scaled(nib, s_ref):
            return (nib.reshape(ng, gsize, bn).astype(jnp.float32)
                    * s_ref[...][:, None, :]
                    ).reshape(bkp, bn).astype(jnp.bfloat16)
        w_contract = 0
    for x_ref, w in ((xl_ref, scaled(lo, sl_ref)),
                     (xh_ref, scaled(hi, sh_ref))):
        acc_ref[...] += jax.lax.dot_general(
            x_ref[...], w, (((1,), (w_contract,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(k == pl.num_programs(1) - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("gsize", "bkp", "bn", "out_dtype",
                                    "interpret", "out_major"))
def _mm4(x2, qp2, s2, gsize: int, bkp: int, bn: int, out_dtype,
         interpret: bool = False, out_major: bool = False):
    """x2 [m, K] @ half-packed qp2 [K/2, N] with scales s2 [K/G, N]
    (`out_major`: qp2 [N, K/2], s2 [N, K/G]).

    Grid steps walk the PACKED rows in blocks of bkp; each step reads
    the two matching x column-blocks (low half: cols [kk*bkp, ...);
    high half: offset by K/2) and the two matching scale row-blocks —
    all contiguous, all expressed as separate BlockSpecs over the same
    arrays. The scales are viewed [2*nkb, ngb, N] — one leading index
    per (half, k-step) — so a block's last two dims are the array's
    own (ngb, ·) whatever ngb is: Mosaic asks for sublane blocks of 8
    or the whole dim, and K=2560 has 10 groups a half."""
    m, k = x2.shape
    n = qp2.shape[0 if out_major else 1]
    kp = k // 2
    nkb = kp // bkp               # x/scale block offset of the high half
    ngb = bkp // gsize            # scale rows per block
    if out_major:
        # the same view with the channels ahead of a block's groups:
        # [N, K/G] -> [2 * nkb, N, ngb], a block's last two dims the
        # array's own (bn a multiple of 8, ngb whole)
        s3 = s2.reshape(n, 2 * nkb, ngb).transpose(1, 0, 2)
        w_specs = [
            pl.BlockSpec((bn, bkp), lambda i, kk: (i, kk)),
            pl.BlockSpec((None, bn, ngb), lambda i, kk: (kk, i, 0)),
            pl.BlockSpec((None, bn, ngb),
                         lambda i, kk: (nkb + kk, i, 0)),
        ]
    else:
        s3 = s2.reshape(2 * nkb, ngb, n)
        w_specs = [
            pl.BlockSpec((bkp, bn), lambda i, kk: (kk, i)),
            pl.BlockSpec((None, ngb, bn), lambda i, kk: (kk, 0, i)),
            pl.BlockSpec((None, ngb, bn),
                         lambda i, kk: (nkb + kk, 0, i)),
        ]
    return pl.pallas_call(
        functools.partial(_kernel, gsize=gsize, out_major=out_major),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        grid=(n // bn, nkb),
        in_specs=[
            pl.BlockSpec((m, bkp), lambda i, kk: (0, kk)),
            pl.BlockSpec((m, bkp), lambda i, kk: (0, nkb + kk)),
            *w_specs,
        ],
        out_specs=pl.BlockSpec((m, bn), lambda i, kk: (0, i)),
        scratch_shapes=[pltpu.VMEM((m, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="int4_matmul",
    )(x2, x2, qp2, s3, s3)


def flatten_qtensor(qt) -> Optional[tuple]:
    """(qp2, s2, K, N, G, out_major): 2D views of a packed leaf.
    In-major, qp2 [K/2, N] and s2 [K/G, N]: the pack axis is the
    leaf's first dim; out-major, qp2 [N, K/2] and s2 [N, K/G]: it is
    the leaf's last (wq / wk / wv / w_ogate, [heads, Dh, D]). None if
    the leaf lies neither way or its shapes don't flatten cleanly."""
    q, s = qt.q, qt.s
    if getattr(qt, "bits", 8) != 4:
        return None
    a = qt.axis % q.ndim
    pre, post = q.shape[:a], q.shape[a + 1:]
    if not post and q.ndim > 1:
        # the pack axis is the minor dim: a row's nibbles are
        # contiguous, whatever the output channels' dims are
        kp, n, n_groups = q.shape[a], int(np.prod(pre)), s.shape[a]
        gsize = 2 * kp // n_groups
        if gsize < 2 or gsize % 2 or s.shape[:a] != pre:
            return None
        return (q.reshape(n, kp), s.reshape(n, n_groups), 2 * kp, n,
                gsize, True)
    if int(np.prod(pre)) != 1:
        # the half-packed layout is contiguous in the flattened
        # contraction only when the pack axis is OUTERMOST or
        # innermost (quant.py packs axes[0])
        return None
    kp = q.shape[a]
    n = int(np.prod(post))
    k = 2 * kp
    n_groups = s.shape[a]
    gsize = (2 * q.shape[a]) // n_groups
    if gsize < 2 or gsize % 2:
        return None
    # broadcast collapsed (size-1) contract dims of the scales to the
    # weight's, so groups stay contiguous after flattening
    s_target = pre + (n_groups,) + post
    try:
        s_full = jnp.broadcast_to(s, s_target)
    except Exception:
        return None
    qp2 = q.reshape(kp, n)
    s2 = s_full.reshape(int(np.prod(pre)) * n_groups, n)
    return qp2, s2, k, n, gsize, False


def _pick_bkp(kp: int, gsize: int) -> int:
    """Largest k-block (packed rows) that is a whole number of scale
    groups, divides the packed half kp (a multiple of gsize), and
    stays within MAX_BKP; one group (gsize <= MAX_BKP) always does."""
    n_half = kp // gsize          # groups per nibble half
    return gsize * next(
        ngb for ngb in range(min(n_half, MAX_BKP // gsize), 0, -1)
        if n_half % ngb == 0)


def int4_matmul(x: jax.Array, qt, out_dtype=jnp.bfloat16,
                interpret: bool = False) -> Optional[jax.Array]:
    """y[..., N] = x[..., K] @ dequant(qt), nibble-unpacked in VMEM.

    Returns None when the kernel doesn't apply (layout, alignment,
    batch size, or platform) — the caller falls back to the XLA
    dequant path. Off a TPU that is the plain gate; on one, every
    None is a decline and is noted with its reason. Never raises for
    a shape: what Mosaic would refuse is declined here first.
    """
    import os
    if os.environ.get("OME_INT4_KERNEL_INTERPRET"):
        interpret = True  # tests: run the kernel path on CPU
    if not interpret and not device.on_tpu():
        return None

    def decline(reason: str):
        note_decline("int4_matmul", reason)
        return None

    if not _kernel_enabled.get() and not interpret:
        # GSPMD-partitioned jits (tp>1 sharded serving) would have to
        # replicate this un-partitioned custom call — all-gathering the
        # packed weight every step, negating int4's HBM savings. Weight
        # sharding isn't visible on tracers, so the sharded engine
        # wraps its traces in kernel_disabled() and takes the XLA
        # dequant path instead.
        return decline("inside kernel_disabled() (a tp>1 engine's "
                       "sharded weights, or a reference run)")
    flat = flatten_qtensor(qt)
    if flat is None:
        return decline("leaf does not flatten to the half-packed "
                       "[K/2, N] layout")
    qp2, s2, k, n, gsize, out_major = flat
    if x.shape[-1] != k:
        return decline(f"x contracts {x.shape[-1]}, weight {k}")
    kp = k // 2
    if kp % gsize or gsize % 128 or gsize > MAX_BKP:
        # a nibble half must hold whole scale groups (one-group
        # leaves share their scale across halves), and the x block's
        # lane dim is a whole number of groups that fits a k-step
        return decline(f"K={k} with group {gsize}: halves do not "
                       f"split into 128-lane groups of <= {MAX_BKP}")
    bkp = _pick_bkp(kp, gsize)
    bn = min(512, n)
    if n % bn or bn % 128:
        return decline(f"N={n} is not a multiple of 128")
    lead = x.shape[:-1]
    m = int(np.prod(lead)) if lead else 1
    if m > MAX_M:
        # prefill: stay on the XLA path
        return decline(f"m={m} rows > {MAX_M} (prefill-sized)")
    x2 = x.reshape(m, k)
    pad = (-m) % 8
    if pad:
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))
    y = _mm4(x2.astype(jnp.bfloat16), qp2, s2, gsize, bkp, bn,
             out_dtype, interpret, out_major)
    if pad:
        y = y[:m]
    return y.reshape(*lead, n)
