"""Plain reference for the dense grouped-query decoder family
(Llama-style blocks: Qwen3, Mistral): RMSNorm, rotary embeddings in
the rotate-half convention, per-head q/k RMS norms where the config
has them, causal softmax attention with grouped KV heads, SwiGLU, tied
or untied head.

Straight `jax.numpy` in float32 under
`jax.default_matmul_precision("highest")`: no kernels, no cache, no
batching, one sequence at a time. It imports nothing of the program.

Weights. The server is started with `--random-weights`, which draws
every leaf from `PRNGKey(0)` (the program's `llama.init_params`);
`init_weights` below makes the SAME leaves by the same published recipe
(normal, std 0.02, residual outputs scaled by 1/sqrt(2 L), rounded to
the served dtype) from its own code, so the reference takes nothing
the program has made. If the program's recipe ever changes, the two
stop agreeing and `correct` says so.

`int8=True` is the control: the same forward pass with every matrix
(projections, MLP, head) rounded to int8 with one scale per output
channel, the nearest precision below the bf16 the configurations
state.
"""

from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
from jax import lax

MATRICES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


def dims(cfg: Dict) -> Dict[str, int]:
    heads = cfg["num_attention_heads"]
    return dict(D=cfg["hidden_size"], L=cfg["num_hidden_layers"],
                H=heads, K=cfg.get("num_key_value_heads", heads),
                Dh=cfg.get("head_dim") or cfg["hidden_size"] // heads,
                F=cfg["intermediate_size"], V=cfg["vocab_size"])


def has_qk_norm(cfg: Dict) -> bool:
    return cfg.get("model_type") == "qwen3"


def init_weights(cfg: Dict, dtype=jnp.bfloat16, shardings=None):
    """Seeded weights as `--random-weights` serves them, in one jitted
    call on the device, stacked over layers."""
    d = dims(cfg)
    D, L, H, K, Dh, F, V = (d[k] for k in "D L H K Dh F V".split())
    tied = bool(cfg.get("tie_word_embeddings", False))

    def normal(key, shape, std=0.02):
        return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)

    def make():
        k_top, _k_dense, k_layers = jax.random.split(jax.random.PRNGKey(0), 3)
        top = jax.random.split(k_top, 4)
        lk = jax.random.split(k_layers, 24)
        out_std = 0.02 / (2 * L) ** 0.5
        w = {
            "embed": normal(top[0], (V, D)),
            "final_norm": jnp.ones((D,), dtype),
            "attn_norm": jnp.ones((L, D), dtype),
            "mlp_norm": jnp.ones((L, D), dtype),
            "wq": normal(lk[0], (L, D, H, Dh)),
            "wk": normal(lk[1], (L, D, K, Dh)),
            "wv": normal(lk[2], (L, D, K, Dh)),
            "wo": normal(lk[3], (L, H, Dh, D), out_std),
            "w_gate": normal(lk[4], (L, D, F)),
            "w_up": normal(lk[5], (L, D, F)),
            "w_down": normal(lk[6], (L, F, D), out_std),
        }
        if has_qk_norm(cfg):
            w["q_norm"] = jnp.ones((L, Dh), dtype)
            w["k_norm"] = jnp.ones((L, Dh), dtype)
        if not tied:
            w["lm_head"] = normal(top[1], (D, V))
        return w

    if shardings is not None:
        shardings = shardings(jax.eval_shape(make))
    return jax.jit(make, out_shardings=shardings)()


def _fake_int8(w, contract_axes):
    """Round to int8 with one scale per output channel."""
    scale = jnp.max(jnp.abs(w), axis=contract_axes, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.round(w / scale).clip(-127, 127) * scale


def _rms(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * lax.rsqrt(var + eps) * scale


def _rope(x, theta):
    """x: [S, N, Dh]; rotate-half convention, positions 0..S-1."""
    S, _, Dh = x.shape
    half = Dh // 2
    freqs = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    angles = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("eps", "theta", "qk_norm",
                                             "int8"))
def _layer(x, w, l, *, eps, theta, qk_norm, int8):
    """One block on x [S, D] float32; `w` holds the stacked leaves and
    `l` picks the layer, which is upcast here: one layer's float32
    copy lives beside the served-dtype model."""
    def leaf(name, contract_axes=None):
        v = lax.dynamic_index_in_dim(w[name], l, 0, keepdims=False)
        v = v.astype(jnp.float32)
        if int8 and contract_axes is not None:
            v = _fake_int8(v, contract_axes)
        return v

    S = x.shape[0]
    wq, wk, wv = leaf("wq", (0,)), leaf("wk", (0,)), leaf("wv", (0,))
    H, K = wq.shape[1], wk.shape[1]
    h = _rms(x, leaf("attn_norm"), eps)
    q = jnp.einsum("sd,dhk->shk", h, wq)
    k = jnp.einsum("sd,dhk->shk", h, wk)
    v = jnp.einsum("sd,dhk->shk", h, wv)
    if qk_norm:
        q = _rms(q, leaf("q_norm"), eps)
        k = _rms(k, leaf("k_norm"), eps)
    q, k = _rope(q, theta), _rope(k, theta)
    group = H // K
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("shk,thk->hst", q, k) * (q.shape[-1] ** -0.5)
    causal = jnp.tril(jnp.ones((S, S), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    attn = jnp.einsum("hst,thk->shk", probs, v)
    x = x + jnp.einsum("shk,hkd->sd", attn, leaf("wo", (0, 1)))
    h = _rms(x, leaf("mlp_norm"), eps)
    gate = jax.nn.silu(h @ leaf("w_gate", (0,)))
    x = x + (gate * (h @ leaf("w_up", (0,)))) @ leaf("w_down", (0,))
    return x


@functools.partial(jax.jit, static_argnames=("eps", "tied", "int8"))
def _head(x, w, *, eps, tied, int8):
    x = _rms(x, w["final_norm"].astype(jnp.float32), eps)
    if tied:
        m = w["embed"].astype(jnp.float32)          # [V, D]
        if int8:
            m = _fake_int8(m, (1,))
        return x @ m.T
    m = w["lm_head"].astype(jnp.float32)            # [D, V]
    if int8:
        m = _fake_int8(m, (0,))
    return x @ m


def logits(w, cfg: Dict, tokens, first: int, count: int,
           int8: bool = False):
    """Float32 logits [count, V] of the rows first .. first+count-1 of
    one sequence `tokens` [S] (causal, so padding after the last row
    wanted changes nothing)."""
    eps = float(cfg.get("rms_norm_eps", 1e-6))
    theta = float(cfg.get("rope_theta", 10000.0))
    with jax.default_matmul_precision("highest"):
        x = jnp.take(w["embed"], jnp.asarray(tokens, jnp.int32),
                     axis=0).astype(jnp.float32)
        for l in range(cfg["num_hidden_layers"]):
            x = _layer(x, w, l, eps=eps, theta=theta,
                       qk_norm=has_qk_norm(cfg), int8=int8)
        x = lax.dynamic_slice_in_dim(x, first, count, axis=0)
        return _head(x, w, eps=eps,
                     tied=bool(cfg.get("tie_word_embeddings", False)),
                     int8=int8)
