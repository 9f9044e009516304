"""Fused int4 weight-only matmul (Pallas TPU kernel).

XLA cannot keep the int4 nibble unpack fused into a matmul operand
read — the dequantized bf16 weight round-trips through HBM. This
kernel streams the PACKED bytes (plus the
small group scales) into VMEM, unpacks with i32 shifts (Mosaic has no
i8 vector shifts), scales per group, and feeds the MXU — HBM traffic
is the packed 0.5 byte/weight, the decode roofline's whole point.

Layout contract (models/quant.py concat-pack): the packing axis holds
pairs (g, g+G/2) within each scale group; flattened 2D view
`[K/2, N]` where every dim up to and including the pack axis is a
CONTRACTION dim (callers guarantee this — true for wq/wk/wv/wo and
the MLP gate/up projections) and the trailing dims are output
channels. Scales flatten to `[K/G, N]` after broadcasting collapsed
contract dims.

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
            gsize: int):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    qp = qp_ref[...].astype(jnp.int32)
    # nibble extraction in i32: arithmetic shifts sign-extend
    hi = qp >> 4
    lo = (qp << 28) >> 28
    bkp, bn = qp_ref.shape
    ng = bkp // gsize
    # half-packed layout (models/quant.py): packed row j of this block
    # holds original rows at the SAME offset in the axis' low half (lo
    # nibble) and high half (hi nibble). The matching x slices and
    # scale rows arrive as separate contiguous blocks (xl/xh, sl/sh),
    # so the unpack is shift -> scale -> dot twice: no concatenate
    # (a full-tile VMEM round-trip) and no strided shuffles.
    # f32 unpack-scale measured FASTER than bf16 on v5e Mosaic (bf16
    # VPU packing overhead outweighs the halved element width)
    wl = (lo.reshape(ng, gsize, bn).astype(jnp.float32)
          * sl_ref[...][:, None, :]).reshape(bkp, bn).astype(jnp.bfloat16)
    wh = (hi.reshape(ng, gsize, bn).astype(jnp.float32)
          * sh_ref[...][:, None, :]).reshape(bkp, bn).astype(jnp.bfloat16)
    acc_ref[...] += jax.lax.dot_general(
        xl_ref[...], wl, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    acc_ref[...] += jax.lax.dot_general(
        xh_ref[...], wh, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(k == pl.num_programs(1) - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("gsize", "bkp", "bn", "out_dtype",
                                    "interpret"))
def _mm4(x2, qp2, s2, gsize: int, bkp: int, bn: int, out_dtype,
         interpret: bool = False):
    """x2 [m, K] @ half-packed qp2 [K/2, N] with scales s2 [K/G, N].

    Grid steps walk the PACKED rows in blocks of bkp; each step reads
    the two matching x column-blocks (low half: cols [kk*bkp, ...);
    high half: offset by K/2) and the two matching scale row-blocks —
    all contiguous, all expressed as separate BlockSpecs over the same
    arrays. The scales are viewed [2*nkb, ngb, N] — one leading index
    per (half, k-step) — so a block's last two dims are the array's
    own (ngb, ·) whatever ngb is: Mosaic asks for sublane blocks of 8
    or the whole dim, and K=2560 has 10 groups a half."""
    m, k = x2.shape
    n = qp2.shape[1]
    kp = k // 2
    nkb = kp // bkp               # x/scale block offset of the high half
    ngb = bkp // gsize            # scale rows per block
    s3 = s2.reshape(2 * nkb, ngb, n)
    return pl.pallas_call(
        functools.partial(_kernel, gsize=gsize),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        grid=(n // bn, nkb),
        in_specs=[
            pl.BlockSpec((m, bkp), lambda i, kk: (0, kk)),
            pl.BlockSpec((m, bkp), lambda i, kk: (0, nkb + kk)),
            pl.BlockSpec((bkp, bn), lambda i, kk: (kk, i)),
            pl.BlockSpec((None, ngb, bn), lambda i, kk: (kk, 0, i)),
            pl.BlockSpec((None, ngb, bn),
                         lambda i, kk: (nkb + kk, 0, i)),
        ],
        out_specs=pl.BlockSpec((m, bn), lambda i, kk: (0, i)),
        scratch_shapes=[pltpu.VMEM((m, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="int4_matmul",
    )(x2, x2, qp2, s3, s3)


def flatten_qtensor(qt) -> Optional[tuple]:
    """(qp2 [K/2, N], s2 [K/G, N], K, N, G) — 2D views of a packed
    leaf whose pre-pack dims are all contraction dims; None if the
    shapes don't flatten cleanly."""
    q, s = qt.q, qt.s
    if getattr(qt, "bits", 8) != 4:
        return None
    a = qt.axis % q.ndim
    pre, post = q.shape[:a], q.shape[a + 1:]
    if int(np.prod(pre)) != 1:
        # the half-packed layout is contiguous in the flattened
        # contraction only when the pack axis is OUTERMOST (true for
        # every kernel-eligible leaf: quant.py packs axes[0])
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
    return qp2, s2, k, n, gsize


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
    qp2, s2, k, n, gsize = flat
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
             out_dtype, interpret)
    if pad:
        y = y[:m]
    return y.reshape(*lead, n)
