"""Gated DeltaNet: the linear-attention recurrence of Qwen3-Next's
mixer layers, in two forms that compute the same thing.

Per value head, with a state `S` [dk, dv] kept in float32:

    S_t = exp(g_t) * S_{t-1} + k_t (x) beta_t * (v_t - (exp(g_t) * S_{t-1})^T k_t)
    o_t = S_t^T q_t

`step` is that equation for one token (decode, and the multi-token
decode loop); `chunked` runs a whole prompt in chunks of `chunk`
tokens: inside a chunk the delta rule is a unit-lower-triangular
solve (the WY form of "Gated Delta Networks", arXiv:2412.06464),
across chunks a `lax.scan` carries `S`. Both take a validity mask: an
invalid position (right padding of a prefill bucket, a frozen slot of
the multi-token loop) is run with beta = 0 and g = 0, which leaves
`S` exactly as it was, so the state handed back is the state at the
last valid token whatever the bucket.

Also here: the depthwise causal conv over the mixer's q|k|v channels
with its tail (the last `width - 1` inputs), which is the other half
of what a sequence carries through a DeltaNet layer.

q and k arrive L2-normalised per head, q scaled by dk^-1/2, and
already repeated to the value heads. The small matrix products run at
`Precision.HIGHEST`: on a TPU a float32 product is otherwise rounded
to bfloat16 passes, and the state is the one thing here that
accumulates over the whole sequence.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

_HI = lax.Precision.HIGHEST


def l2norm(x: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    return xf * lax.rsqrt(jnp.sum(xf * xf, axis=-1, keepdims=True) + eps)


def step(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
         beta: jax.Array, S: jax.Array,
         valid: Optional[jax.Array] = None
         ) -> Tuple[jax.Array, jax.Array]:
    """One token. q, k: [B, H, dk]; v: [B, H, dv]; g, beta: [B, H];
    S: [B, H, dk, dv] float32; valid: [B] bool or None (all valid).
    Returns (o [B, H, dv] float32, new S)."""
    q, k, v = (a.astype(jnp.float32) for a in (q, k, v))
    g, beta = g.astype(jnp.float32), beta.astype(jnp.float32)
    if valid is not None:
        g = jnp.where(valid[:, None], g, 0.0)
        beta = jnp.where(valid[:, None], beta, 0.0)
    with jax.named_scope("kv_write"), jax.named_scope("gdn_state"):
        S = S * jnp.exp(g)[..., None, None]
        kv = jnp.einsum("bhkv,bhk->bhv", S, k, precision=_HI)
        delta = (v - kv) * beta[..., None]
        S = S + k[..., :, None] * delta[..., None, :]
    o = jnp.einsum("bhkv,bhk->bhv", S, q, precision=_HI)
    return o, S


def chunked(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
            beta: jax.Array, S: jax.Array,
            valid: Optional[jax.Array] = None, chunk: int = 64
            ) -> Tuple[jax.Array, jax.Array]:
    """A whole sequence. q, k: [B, T, H, dk]; v: [B, T, H, dv]; g,
    beta: [B, T, H]; S: [B, H, dk, dv] float32; valid: [B, T] bool or
    None. Returns (o [B, T, H, dv] float32, S after the last valid
    position)."""
    B, T, H, dk = q.shape
    dv = v.shape[-1]
    C = min(chunk, T)
    pad = -T % C
    q, k, v = (a.astype(jnp.float32) for a in (q, k, v))
    g, beta = g.astype(jnp.float32), beta.astype(jnp.float32)
    if valid is not None:
        g = jnp.where(valid[..., None], g, 0.0)
        beta = jnp.where(valid[..., None], beta, 0.0)
    if pad:
        q, k, v = (jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
                   for a in (q, k, v))
        g, beta = (jnp.pad(a, ((0, 0), (0, pad), (0, 0)))
                   for a in (g, beta))
    N = (T + pad) // C

    def chunks(a):      # [B, N*C, H, ...] -> [N, B, H, C, ...]
        a = a.reshape(B, N, C, *a.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(a, 3, 2), 1, 0)

    qc, kc, vc, gc, bc = (chunks(a) for a in (q, k, v, g, beta))
    gc = jnp.cumsum(gc, axis=-1)                    # [N, B, H, C]
    lower = jnp.tril(jnp.ones((C, C), bool))
    strict = jnp.tril(jnp.ones((C, C), bool), -1)
    # decay[i, j] = exp(g_i - g_j) for j <= i (the difference is <= 0
    # there; the upper half is masked before it can overflow)
    diff = gc[..., :, None] - gc[..., None, :]
    decay = jnp.exp(jnp.where(lower, diff, -jnp.inf))
    kb = kc * bc[..., None]
    vb = vc * bc[..., None]
    # (I + A) X = [v*beta | k*beta*exp(g)], A strictly lower: every
    # token's correction by the tokens of its chunk before it
    A = jnp.where(strict, jnp.einsum("nbhik,nbhjk->nbhij", kb, kc,
                                     precision=_HI) * decay, 0.0)
    rhs = jnp.concatenate([vb, kb * jnp.exp(gc)[..., None]], axis=-1)
    X = lax.linalg.triangular_solve(
        A + jnp.eye(C, dtype=A.dtype), rhs, left_side=True, lower=True,
        unit_diagonal=True)
    u, w = X[..., :dv], X[..., dv:]
    qk = jnp.where(lower, jnp.einsum("nbhik,nbhjk->nbhij", qc, kc,
                                     precision=_HI) * decay, 0.0)

    def body(S, per):
        q_i, k_i, u_i, w_i, g_i, qk_i = per
        with jax.named_scope("kv_write"), jax.named_scope("gdn_state"):
            v_new = u_i - jnp.einsum("bhck,bhkv->bhcv", w_i, S,
                                     precision=_HI)
            g_last = g_i[..., -1]
            k_dec = k_i * jnp.exp(g_last[..., None] - g_i)[..., None]
            S_new = S * jnp.exp(g_last)[..., None, None] + jnp.einsum(
                "bhck,bhcv->bhkv", k_dec, v_new, precision=_HI)
        o = jnp.einsum("bhck,bhkv->bhcv", q_i * jnp.exp(g_i)[..., None],
                       S, precision=_HI) \
            + jnp.einsum("bhij,bhjv->bhiv", qk_i, v_new, precision=_HI)
        return S_new, o

    S, o = lax.scan(body, S, (qc, kc, u, w, gc, qk))
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 3, 2)   # [B, N, C, H, dv]
    return o.reshape(B, N * C, H, dv)[:, :T], S


def conv_seq(x: jax.Array, tail: jax.Array, w: jax.Array,
             valid_len: Optional[jax.Array] = None
             ) -> Tuple[jax.Array, jax.Array]:
    """Depthwise causal conv over a sequence (T = 1: one decode
    token). x: [B, T, C]; tail: [B, W-1, C], the last W-1 inputs,
    oldest first; w: [C, W]; valid_len: [B] number of leading valid
    positions (None = T). Returns (y [B, T, C] float32, the tail after
    the last valid position)."""
    B, T, _ = x.shape
    W = w.shape[1]
    xp = jnp.concatenate([tail, x.astype(tail.dtype)], axis=1)
    wf = w.astype(jnp.float32)
    y = sum(xp[:, j:j + T].astype(jnp.float32) * wf[:, j]
            for j in range(W))
    if valid_len is None:
        return y, xp[:, T:]
    # xp[n : n + W - 1] are the W-1 inputs before position n
    new_tail = jax.vmap(
        lambda a, n: lax.dynamic_slice_in_dim(a, n, W - 1, axis=0))(
            xp, valid_len.astype(jnp.int32))
    return y, new_tail
