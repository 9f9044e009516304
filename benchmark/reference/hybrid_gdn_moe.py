"""Plain reference for the hybrid Gated-DeltaNet / gated-attention /
sparse-expert decoder family (Qwen3-Next).

Layer `i` of `num_hidden_layers` is gated full attention iff
`(i + 1) % full_attention_interval == 0`, every other layer a Gated
DeltaNet mixer; every layer's MLP is the mixture of experts. D the
hidden size, every RMSNorm zero-centred (`x * rsqrt(mean x^2 + eps) *
(1 + w)`) unless said:

  * Gated DeltaNet layer (Hk key heads, Hv value heads, dk, dv, conv
    width W). `q|k|v = h W_qkv`, `z = h W_z`, `b = h W_b`, `a = h W_a`.
    q|k|v pass together through a depthwise causal conv1d of width W
    over the sequence, then SiLU. q, k are L2-normalised per head, q
    scaled by dk^-1/2, each key head serves Hv / Hk consecutive value
    heads. Per value head, with `beta_t = sigmoid(b_t)` and `g_t =
    -exp(A_log) * softplus(a_t + dt_bias)`:
        S_t = exp(g_t) S_{t-1} + k_t (x) beta_t (v_t - (exp(g_t) S_{t-1})^T k_t)
        o_t = S_t^T q_t
    run here as a token-by-token `lax.scan` of exactly that. Output:
    per-head `RMSNorm(o_t) * SiLU(z_t)` (plain weight, not
    zero-centred), then `W_o`.
  * Gated full-attention layer: a query and a gate per head;
    zero-centred RMSNorm on q and k per head; rotary (rotate-half) on
    the first `partial_rotary_factor` of the head; causal softmax
    attention with grouped KV heads; the output times `sigmoid(gate)`
    before `W_o`.
  * MoE: router logits over ALL `ep_num_experts_total` experts (no
    bias), softmax, top-k, renormalised; experts SwiGLU; plus one
    shared SwiGLU expert times `sigmoid(h . w_sg)`.

The chip's share (model-configs guide, section 4): the configuration
holds `num_experts` of the `ep_num_experts_total` routed experts, from
`ep_expert_offset` on, and a slice of the vocabulary. The router keeps
its published width; every HELD expert is computed for every token
and mixed by the router's weights, which are zero for an expert that
was not chosen and absent for one that is not held. What the absent
experts would have added is left out, here as in the program.

Departure from the published model: the checkpoint's one
multi-token-prediction module is not part of the forward pass and is
not served.

Straight `jax.numpy` in float32 under
`jax.default_matmul_precision("highest")`: no kernels, no cache, no
batching, one sequence at a time. It imports nothing of the program.

Weights. The server is started with `--random-weights`;
`init_weights` makes the SAME leaves from its own copy of the recipe:
from `PRNGKey(0)`, normal std 0.02, residual outputs scaled by
1/sqrt(2 L), norm weights at identity, the conv's taps at std W^-1/2,
`A_log` 0 and `dt_bias` such that a head's decay at a = 0 is uniform
over (0.5, 0.999), rounded to the served dtype. If the program's
recipe ever changes, the two stop agreeing and `correct` says so.

`int8=True` is the control: the same forward pass with every matrix
(projections, experts, shared expert, head) rounded to int8 with one
scale per output channel, the nearest precision below the bf16 the
configuration states. The router, the conv and the two [D, Hv]
projections stay as they are, as under the program's own
`--quantization int8`.
"""

from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
from jax import lax


def dims(cfg: Dict) -> Dict[str, int]:
    Hk, Hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    P = cfg["full_attention_interval"]
    L = cfg["num_hidden_layers"]
    return dict(
        D=cfg["hidden_size"], L=L, P=P, G=L // P, N=L - L // P,
        H=cfg["num_attention_heads"], K=cfg["num_key_value_heads"],
        Dh=cfg["head_dim"], Hk=Hk, Hv=Hv, dk=dk, dv=dv,
        C=2 * Hk * dk + Hv * dv, W=cfg["linear_conv_kernel_dim"],
        E=cfg["num_experts"],
        Et=cfg.get("ep_num_experts_total") or cfg["num_experts"],
        lo=cfg.get("ep_expert_offset") or 0,
        k=cfg["num_experts_per_tok"], F=cfg["moe_intermediate_size"],
        Fs=cfg["shared_expert_intermediate_size"], V=cfg["vocab_size"])


def init_weights(cfg: Dict, dtype=jnp.bfloat16, shardings=None):
    """Seeded weights as `--random-weights` serves them, in one jitted
    call on the device: `full` stacks the G full-attention layers,
    `linear` the N DeltaNet layers, in layer order."""
    d = dims(cfg)
    D, L, G, N, H, K, Dh = (d[x] for x in "D L G N H K Dh".split())
    Hv, dv, C, W, E, Et, F, Fs, V = (d[x] for x in
                                     "Hv dv C W E Et F Fs V".split())
    out_std = 0.02 / (2 * L) ** 0.5

    def normal(key, shape, std=0.02):
        return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)

    def moe(n, lk):
        return {
            "attn_norm": jnp.zeros((n, D), dtype),
            "mlp_norm": jnp.zeros((n, D), dtype),
            "router": normal(lk[4], (n, D, Et)),
            "we_gate": normal(lk[5], (n, E, D, F)),
            "we_up": normal(lk[6], (n, E, D, F)),
            "we_down": normal(lk[7], (n, E, F, D), out_std),
            "ws_gate": normal(lk[8], (n, D, Fs)),
            "ws_up": normal(lk[9], (n, D, Fs)),
            "ws_down": normal(lk[10], (n, Fs, D), out_std),
            "w_sg": normal(lk[11], (n, D, 1)),
        }

    def make():
        k_top, k_lin, k_full = jax.random.split(jax.random.PRNGKey(0), 3)
        top = jax.random.split(k_top, 4)
        fk = jax.random.split(k_full, 24)
        nk = jax.random.split(k_lin, 24)
        full = dict(
            moe(G, fk),
            wq=normal(fk[0], (G, D, H, Dh)),
            wk=normal(fk[1], (G, D, K, Dh)),
            wv=normal(fk[2], (G, D, K, Dh)),
            wo=normal(fk[3], (G, H, Dh, D), out_std),
            q_norm=jnp.zeros((G, Dh), dtype),
            k_norm=jnp.zeros((G, Dh), dtype),
            w_ogate=normal(fk[12], (G, D, H, Dh)))
        decay = 0.5 + 0.499 * jax.random.uniform(nk[18], (N, Hv),
                                                 jnp.float32)
        linear = dict(
            moe(N, nk),
            w_qkv=normal(nk[12], (N, D, C)),
            w_z=normal(nk[13], (N, D, Hv * dv)),
            w_b=normal(nk[14], (N, D, Hv)),
            w_a=normal(nk[15], (N, D, Hv)),
            conv_w=normal(nk[16], (N, C, W), W ** -0.5),
            w_lin_out=normal(nk[17], (N, Hv * dv, D), out_std),
            gdn_norm=jnp.ones((N, dv), dtype),
            A_log=jnp.zeros((N, Hv), jnp.float32),
            dt_bias=jnp.log(jnp.expm1(-jnp.log(decay))))
        return {"embed": normal(top[0], (V, D)),
                "lm_head": normal(top[1], (D, V)),
                "final_norm": jnp.zeros((D,), dtype),
                "full": full, "linear": linear}

    if shardings is not None:
        shardings = shardings(jax.eval_shape(make))
    return jax.jit(make, out_shardings=shardings)()


def _fake_int8(w, contract_axes):
    """Round to int8 with one scale per output channel."""
    scale = jnp.max(jnp.abs(w), axis=contract_axes, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.round(w / scale).clip(-127, 127) * scale


def _rms(x, w, eps, zero_centred=True):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * lax.rsqrt(var + eps) * ((1.0 + w) if zero_centred else w)


def _rope(x, theta, share):
    """x: [S, N, Dh]; rotate-half on the first `share` of the head,
    positions 0..S-1."""
    S, _, Dh = x.shape
    rot = int(Dh * share)
    half = rot // 2
    freqs = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    angles = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2, rest = x[..., :half], x[..., half:rot], x[..., rot:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], -1)


def _leaf(w, l, int8, name, contract_axes=None):
    """Layer `l` of a stacked leaf, upcast here: one layer's float32
    copy lives beside the served-dtype model."""
    v = lax.dynamic_index_in_dim(w[name], l, 0, keepdims=False)
    v = v.astype(jnp.float32)
    if int8 and contract_axes is not None:
        v = _fake_int8(v, contract_axes)
    return v


def _moe(x, w, l, *, eps, top_k, lo, int8):
    """x + MoE(norm(x)) on x [S, D]."""
    leaf = functools.partial(_leaf, w, l, int8)
    h = _rms(x, leaf("mlp_norm"), eps)
    probs = jax.nn.softmax(h @ leaf("router"), axis=-1)     # [S, Et]
    top, idx = lax.top_k(probs, top_k)
    top = top / jnp.sum(top, axis=-1, keepdims=True)
    E = w["we_gate"].shape[1]
    # the router's weight of every HELD expert: zero unless chosen
    held = jnp.arange(E) + lo
    mix = jnp.sum(jnp.where(idx[:, :, None] == held[None, None, :],
                            top[:, :, None], 0.0), axis=1)  # [S, E]

    def one_expert(acc, e):
        def ex(name, axes):
            v = lax.dynamic_index_in_dim(
                lax.dynamic_index_in_dim(w[name], l, 0, keepdims=False),
                e, 0, keepdims=False).astype(jnp.float32)
            return _fake_int8(v, axes) if int8 else v
        y = (jax.nn.silu(h @ ex("we_gate", (0,)))
             * (h @ ex("we_up", (0,)))) @ ex("we_down", (0,))
        w_e = lax.dynamic_index_in_dim(mix, e, 1, keepdims=True)
        return acc + y * w_e, None

    routed, _ = lax.scan(one_expert, jnp.zeros_like(x), jnp.arange(E))
    shared = (jax.nn.silu(h @ leaf("ws_gate", (0,)))
              * (h @ leaf("ws_up", (0,)))) @ leaf("ws_down", (0,))
    shared = shared * jax.nn.sigmoid(h @ leaf("w_sg"))
    return x + routed + shared


@functools.partial(jax.jit, static_argnames=(
    "eps", "theta", "rope_share", "top_k", "lo", "int8"))
def _full_layer(x, w, l, *, eps, theta, rope_share, top_k, lo, int8):
    leaf = functools.partial(_leaf, w, l, int8)
    S = x.shape[0]
    wq, wk = leaf("wq", (0,)), leaf("wk", (0,))
    H, K = wq.shape[1], wk.shape[1]
    h = _rms(x, leaf("attn_norm"), eps)
    q = jnp.einsum("sd,dhk->shk", h, wq)
    k = jnp.einsum("sd,dhk->shk", h, wk)
    v = jnp.einsum("sd,dhk->shk", h, leaf("wv", (0,)))
    gate = jnp.einsum("sd,dhk->shk", h, leaf("w_ogate", (0,)))
    q = _rope(_rms(q, leaf("q_norm"), eps), theta, rope_share)
    k = _rope(_rms(k, leaf("k_norm"), eps), theta, rope_share)
    k = jnp.repeat(k, H // K, axis=1)
    v = jnp.repeat(v, H // K, axis=1)
    scores = jnp.einsum("shk,thk->hst", q, k) * (q.shape[-1] ** -0.5)
    causal = jnp.tril(jnp.ones((S, S), bool))
    probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), -1)
    attn = jnp.einsum("hst,thk->shk", probs, v) * jax.nn.sigmoid(gate)
    x = x + jnp.einsum("shk,hkd->sd", attn, leaf("wo", (0, 1)))
    return _moe(x, w, l, eps=eps, top_k=top_k, lo=lo, int8=int8)


@functools.partial(jax.jit, static_argnames=(
    "eps", "Hk", "dk", "top_k", "lo", "int8"))
def _linear_layer(x, w, l, *, eps, Hk, dk, top_k, lo, int8):
    leaf = functools.partial(_leaf, w, l, int8)
    S = x.shape[0]
    dv = w["gdn_norm"].shape[1]
    Hv = w["A_log"].shape[1]
    h = _rms(x, leaf("attn_norm"), eps)
    qkv = h @ leaf("w_qkv", (0,))                           # [S, C]
    z = (h @ leaf("w_z", (0,))).reshape(S, Hv, dv)
    beta = jax.nn.sigmoid(h @ leaf("w_b"))                  # [S, Hv]
    g = -jnp.exp(leaf("A_log")) * jax.nn.softplus(
        h @ leaf("w_a") + leaf("dt_bias"))
    # depthwise causal conv: y_t = sum_j w[:, j] * x_{t - (W-1) + j}
    conv_w = leaf("conv_w")                                 # [C, W]
    W = conv_w.shape[1]
    padded = jnp.concatenate([jnp.zeros((W - 1, qkv.shape[1])), qkv])
    y = sum(padded[j:j + S] * conv_w[:, j] for j in range(W))
    y = jax.nn.silu(y)
    q, k, v = jnp.split(y, [Hk * dk, 2 * Hk * dk], axis=-1)

    def l2(a):
        return a * lax.rsqrt(jnp.sum(a * a, -1, keepdims=True) + 1e-6)

    q = jnp.repeat(l2(q.reshape(S, Hk, dk)) * dk ** -0.5, Hv // Hk, 1)
    k = jnp.repeat(l2(k.reshape(S, Hk, dk)), Hv // Hk, 1)
    v = v.reshape(S, Hv, dv)

    def token(state, per):
        q_t, k_t, v_t, g_t, b_t = per
        state = state * jnp.exp(g_t)[:, None, None]         # [Hv, dk, dv]
        mem = jnp.einsum("hkv,hk->hv", state, k_t)
        state = state + k_t[:, :, None] \
            * ((v_t - mem) * b_t[:, None])[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, q_t)

    _, o = lax.scan(token, jnp.zeros((Hv, dk, dv)), (q, k, v, g, beta))
    o = _rms(o, leaf("gdn_norm"), eps, zero_centred=False) \
        * jax.nn.silu(z)
    x = x + o.reshape(S, Hv * dv) @ leaf("w_lin_out", (0,))
    return _moe(x, w, l, eps=eps, top_k=top_k, lo=lo, int8=int8)


@functools.partial(jax.jit, static_argnames=("eps", "int8"))
def _head(x, w, *, eps, int8):
    x = _rms(x, w["final_norm"].astype(jnp.float32), eps)
    m = w["lm_head"].astype(jnp.float32)                    # [D, V]
    if int8:
        m = _fake_int8(m, (0,))
    return x @ m


def logits(w, cfg: Dict, tokens, first: int, count: int,
           int8: bool = False):
    """Float32 logits [count, V] of the rows first .. first+count-1 of
    one sequence `tokens` [S] (causal, so padding after the last row
    wanted changes nothing)."""
    d = dims(cfg)
    eps = float(cfg.get("rms_norm_eps", 1e-6))
    common = dict(eps=eps, top_k=d["k"], lo=d["lo"], int8=int8)
    with jax.default_matmul_precision("highest"):
        x = jnp.take(w["embed"], jnp.asarray(tokens, jnp.int32),
                     axis=0).astype(jnp.float32)
        for i in range(d["L"]):
            g, j = divmod(i, d["P"])
            if j == d["P"] - 1:
                x = _full_layer(
                    x, w["full"], g, theta=float(cfg["rope_theta"]),
                    rope_share=float(cfg.get("partial_rotary_factor",
                                             1.0)), **common)
            else:
                x = _linear_layer(x, w["linear"], g * (d["P"] - 1) + j,
                                  Hk=d["Hk"], dk=d["dk"], **common)
        x = lax.dynamic_slice_in_dim(x, first, count, axis=0)
        return _head(x, w, eps=eps, int8=int8)
