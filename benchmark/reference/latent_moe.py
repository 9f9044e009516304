"""Plain reference for the latent-attention (MLA), sandwich-norm,
sigmoid-routed sparse-expert decoder family (openPangu-Ultra-MoE,
`model_type` `pangu_ultra_moe`).

Written from the published config.json's keys and the DeepSeek-V3
latent attention they name (`q_lora_rank`, `kv_lora_rank`,
`qk_nope_head_dim`, `qk_rope_head_dim`, `v_head_dim`); what the keys
do not say is listed under `assumed` in the configuration's file. D
the hidden size, H heads, every RMSNorm `x * rsqrt(mean x^2 + eps) *
w`:

  * Embedding `x0 = E[t]`. Final: RMSNorm, then an untied head.
  * Block l (`sandwich_norm`): `h = RMS_in(x)`; `x' = x +
    RMS_post_attn(MLA_l(h))`; `u = RMS_pre_mlp(x')`; `x_next = x' +
    RMS_post_mlp(FFN_l(u))`.
  * MLA_l: `c_q = RMS(h W_qa)` (`q_lora_rank`); `q = c_q W_qb` as H
    heads of `[q_nope | q_pe]` (`qk_nope_head_dim` +
    `qk_rope_head_dim`). `[c | k_pe] = h W_kva` (`kv_lora_rank` +
    `qk_rope_head_dim`); `c = RMS(c)`; rotary (theta `rope_theta`, no
    scaling; pairs (2j, 2j+1) rotated by `pos * theta^(-2j/d)`) on
    every head's `q_pe` and on the ONE `k_pe` all heads share.
    `k_nope = c W_uk`, `v = c W_uv` (the two halves of `kv_b_proj`)
    as H heads of `qk_nope_head_dim` and `v_head_dim`. Head i, query
    t, key s <= t: `(q_nope . k_nope + q_pe . k_pe) * (nope +
    rope)^-1/2`; softmax in float32; output `concat_i(sum_s p v)
    W_o`. Nothing is absorbed here: keys and values are materialised
    for every head, which is the side the program's decode path
    (queries taken into latent space, the cached row read as key and
    as value) is compared against.
  * FFN_l, `l < first_k_dense_replace`: SwiGLU of width
    `intermediate_size`. Later layers: `s = sigmoid(u W_r)` in
    float32 over ALL `ep_num_experts_total` experts; the
    `num_experts_per_tok` largest `s` are picked (no bias, no
    groups); `w_e = routed_scaling_factor * s_e / (sum of the picked
    s + 1e-20)`; `FFN = SwiGLU_shared(u) + sum_e w_e SwiGLU_e(u)`,
    each of width `moe_intermediate_size` (the shared one times
    `n_shared_experts`).

The chip's share (model-configs guide, section 4): the configuration
holds `n_routed_experts` of the `ep_num_experts_total` routed experts,
from `ep_expert_offset` on, and a slice of the vocabulary. The router
keeps its published width; every HELD expert is computed for every
token and mixed by the router's weights, which are zero for an expert
that was not picked and absent for one that is not held. What the
absent experts would have added is left out, here as in the program.

Straight `jax.numpy` in float32 under
`jax.default_matmul_precision("highest")`: no kernels, no cache, no
batching, one sequence at a time, masked attention over the WHOLE
sequence, a block of queries and a group of heads at a time so that
16 k positions at 128 heads fit beside the weights. It imports
nothing of the program.

Weights. The server is started with `--random-weights`;
`init_weights` makes the SAME leaves from its own copy of the recipe:
from `PRNGKey(0)`, normal std 0.02, residual outputs (`wo`, `w_down`,
`we_down`, `ws_down`) scaled by 1/sqrt(2 L), norm weights one,
rounded to the served dtype. If the program's recipe ever changes,
the two stop agreeing and `correct` says so.

`int8=True` is the control: the same forward pass with every matrix
(the latent projections, `W_o`, dense MLPs, experts, shared expert,
head) rounded to int8 with one scale per output channel, the nearest
precision below the bf16 the configuration states. The router stays
as it is, as under the program's own `--quantization int8`.
"""

from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
from jax import lax

Q_BLOCK = 256       # queries attended at a time (sequences are padded
                    # to a multiple of it by check.py; any length works)
HEAD_GROUP = 16     # heads materialised at a time: 16 x 256 x 16 384
                    # float32 scores are 0.27 GB a copy, and the
                    # float32 reference of a 16 k sequence has 2 GB to
                    # spare beside 9.84 GB of served-dtype weights
ROW_BLOCK = 2048    # rows an MLP takes at a time
PAD_TO = 4096       # a long sequence is padded to a multiple of this
                    # before the layers run, so that a window's
                    # sequences of 4 k to 16 k compile three shapes of
                    # every layer and not one a sequence (a layer takes
                    # half a minute to compile at these widths; causal:
                    # rows after the last one wanted change nothing)

def dims(cfg: Dict) -> Dict[str, int]:
    return dict(
        D=cfg["hidden_size"], L=cfg["num_hidden_layers"],
        nd=cfg.get("first_k_dense_replace", 0),
        H=cfg["num_attention_heads"], rq=cfg["q_lora_rank"],
        r=cfg["kv_lora_rank"], nope=cfg["qk_nope_head_dim"],
        rope=cfg["qk_rope_head_dim"], dv=cfg["v_head_dim"],
        Fd=cfg["intermediate_size"], E=cfg["n_routed_experts"],
        Et=cfg.get("ep_num_experts_total") or cfg["n_routed_experts"],
        lo=cfg.get("ep_expert_offset") or 0,
        k=cfg["num_experts_per_tok"], F=cfg["moe_intermediate_size"],
        Fs=cfg["moe_intermediate_size"] * cfg.get("n_shared_experts", 0),
        V=cfg["vocab_size"])


def init_weights(cfg: Dict, dtype=jnp.bfloat16, shardings=None):
    """Seeded weights as `--random-weights` serves them, in one jitted
    call on the device: `dense` stacks the leading dense layers,
    `moe` the expert layers, in layer order."""
    d = dims(cfg)
    D, L, nd, H, rq, r = (d[x] for x in "D L nd H rq r".split())
    nope, rope, dv = d["nope"], d["rope"], d["dv"]
    Fd, E, Et, F, Fs, V = (d[x] for x in "Fd E Et F Fs V".split())
    std = float(cfg.get("initializer_range") or 0.02)
    out_std = std / (2 * L) ** 0.5

    def normal(key, shape, s=std):
        return (jax.random.normal(key, shape, jnp.float32) * s).astype(dtype)

    def block(n, lk):
        return {
            "attn_norm": jnp.ones((n, D), dtype),
            "attn_post_norm": jnp.ones((n, D), dtype),
            "mlp_norm": jnp.ones((n, D), dtype),
            "mlp_post_norm": jnp.ones((n, D), dtype),
            "wq_a": normal(lk[0], (n, D, rq)),
            "q_a_norm": jnp.ones((n, rq), dtype),
            "wq_b": normal(lk[1], (n, rq, H, nope + rope)),
            "wkv_a": normal(lk[2], (n, D, r + rope)),
            "kv_a_norm": jnp.ones((n, r), dtype),
            "w_uk": normal(lk[3], (n, H, nope, r)),
            "w_uv": normal(lk[4], (n, H, r, dv)),
            "wo": normal(lk[5], (n, H, dv, D), out_std),
        }

    def make():
        k_top, k_dense, k_moe = jax.random.split(jax.random.PRNGKey(0), 3)
        top = jax.random.split(k_top, 4)
        dk = jax.random.split(k_dense, 24)
        mk = jax.random.split(k_moe, 24)
        n = L - nd
        moe = dict(
            block(n, mk),
            router=normal(mk[6], (n, D, Et)),
            we_gate=normal(mk[7], (n, E, D, F)),
            we_up=normal(mk[8], (n, E, D, F)),
            we_down=normal(mk[9], (n, E, F, D), out_std))
        if Fs:
            moe.update(
                ws_gate=normal(mk[10], (n, D, Fs)),
                ws_up=normal(mk[11], (n, D, Fs)),
                ws_down=normal(mk[12], (n, Fs, D), out_std))
        out = {"embed": normal(top[0], (V, D)),
               "lm_head": normal(top[1], (D, V)),
               "final_norm": jnp.ones((D,), dtype), "moe": moe}
        if nd:
            out["dense"] = dict(
                block(nd, dk),
                w_gate=normal(dk[6], (nd, D, Fd)),
                w_up=normal(dk[7], (nd, D, Fd)),
                w_down=normal(dk[8], (nd, Fd, D), out_std))
        return out

    if shardings is not None:
        shardings = shardings(jax.eval_shape(make))
    return jax.jit(make, out_shardings=shardings)()


def _fake_int8(w, contract_axes):
    """Round to int8 with one scale per output channel."""
    scale = jnp.max(jnp.abs(w), axis=contract_axes, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.round(w / scale).clip(-127, 127) * scale


def _rms(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * lax.rsqrt(var + eps) * w


def _rope(x, theta):
    """x: [S, N, d]; pairs (2j, 2j+1) rotated by pos * theta^(-2j/d),
    positions 0..S-1. The rotated pairs come back as [evens | odds]:
    one fixed permutation of the lanes, applied to queries and keys
    alike, which no score sees."""
    S, _, d = x.shape
    freqs = 1.0 / theta ** (jnp.arange(d // 2, dtype=jnp.float32) * 2 / d)
    angles = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x0, x1 = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([x0 * cos - x1 * sin, x0 * sin + x1 * cos], -1)


def _leaf(w, l, int8, name, contract_axes=None):
    """Layer `l` of a stacked leaf, upcast here: one layer's float32
    copy lives beside the served-dtype model."""
    v = lax.dynamic_index_in_dim(w[name], l, 0, keepdims=False)
    v = v.astype(jnp.float32)
    if int8 and contract_axes is not None:
        v = _fake_int8(v, contract_axes)
    return v


def _swiglu(u, gate, up, down):
    """SwiGLU of u [S, D], `ROW_BLOCK` rows at a time where they
    divide S: 16 384 rows of an 18 432-wide MLP are 1.2 GB a float32
    intermediate."""
    def rows(x):
        return (jax.nn.silu(x @ gate) * (x @ up)) @ down
    S = u.shape[0]
    if S <= ROW_BLOCK or S % ROW_BLOCK:
        return rows(u)
    return lax.map(rows, u.reshape(-1, ROW_BLOCK, u.shape[1])) \
        .reshape(S, -1)


def _attention(q_nope, q_pe, k_nope, k_pe, v, scale):
    """Causal softmax attention of one sequence for a group of heads,
    a block of queries at a time. q_nope, k_nope: [S, G, nope]; q_pe:
    [S, G, rope]; k_pe: [S, rope], the one rotary key every head
    shares; v: [S, G, dv]."""
    S = q_nope.shape[0]
    block = Q_BLOCK if S % Q_BLOCK == 0 else S
    cols = jnp.arange(S)

    def one(q0):
        qn = lax.dynamic_slice_in_dim(q_nope, q0, block, axis=0)
        qp = lax.dynamic_slice_in_dim(q_pe, q0, block, axis=0)
        rows = q0 + jnp.arange(block)
        scores = (jnp.einsum("sgk,tgk->gst", qn, k_nope)
                  + jnp.einsum("sgk,tk->gst", qp, k_pe)) * scale
        seen = cols[None, :] <= rows[:, None]
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), -1)
        return jnp.einsum("gst,tgk->sgk", probs, v)

    out = lax.map(one, jnp.arange(0, S, block))
    return out.reshape(S, *out.shape[2:])


def _mixer(x, w, l, *, eps, theta, int8):
    """x + RMS_post_attn(MLA(RMS_in(x))), a group of heads at a time:
    a group's queries, keys, values and its part of the output
    projection, summed over the groups."""
    leaf = functools.partial(_leaf, w, l, int8)
    h = _rms(x, leaf("attn_norm"), eps)
    c_q = _rms(h @ leaf("wq_a", (0,)), leaf("q_a_norm"), eps)
    ckv = h @ leaf("wkv_a", (0,))
    r = w["kv_a_norm"].shape[-1]
    c = _rms(ckv[:, :r], leaf("kv_a_norm"), eps)
    k_pe = _rope(ckv[:, None, r:], theta)[:, 0]
    wq_b = leaf("wq_b", (0,))                       # [rq, H, nope+rope]
    w_uk = leaf("w_uk", (2,))                       # [H, nope, r]
    w_uv = leaf("w_uv", (1,))                       # [H, r, dv]
    wo = leaf("wo", (0, 1))                         # [H, dv, D]
    H, nope = w_uk.shape[:2]
    scale = float(wq_b.shape[-1]) ** -0.5
    G = HEAD_GROUP if H % HEAD_GROUP == 0 else H

    def group(a, g0):
        def of(m):
            return lax.dynamic_slice_in_dim(m, g0, G, axis=0)
        q = jnp.einsum("sr,rgk->sgk", c_q,
                       lax.dynamic_slice_in_dim(wq_b, g0, G, axis=1))
        k_nope = jnp.einsum("tr,gkr->tgk", c, of(w_uk))
        v = jnp.einsum("tr,grk->tgk", c, of(w_uv))
        attn = _attention(q[..., :nope], _rope(q[..., nope:], theta),
                          k_nope, k_pe, v, scale)
        return a + jnp.einsum("sgk,gkd->sd", attn, of(wo)), None

    a, _ = lax.scan(group, jnp.zeros_like(x), jnp.arange(0, H, G))
    return x + _rms(a, leaf("attn_post_norm"), eps)


@functools.partial(jax.jit, static_argnames=("eps", "theta", "int8"))
def _dense_layer(x, w, l, *, eps, theta, int8):
    leaf = functools.partial(_leaf, w, l, int8)
    x = _mixer(x, w, l, eps=eps, theta=theta, int8=int8)
    u = _rms(x, leaf("mlp_norm"), eps)
    y = _swiglu(u, leaf("w_gate", (0,)), leaf("w_up", (0,)),
                leaf("w_down", (0,)))
    return x + _rms(y, leaf("mlp_post_norm"), eps)


def _moe_ffn(u, w, l, *, top_k, lo, route_scale, int8):
    """FFN_l(u) of an expert layer, before the post norm: the held
    experts' part of the routed sum, and the shared expert."""
    leaf = functools.partial(_leaf, w, l, int8)
    s = jax.nn.sigmoid(u @ leaf("router"))                  # [S, Et]
    top, idx = lax.top_k(s, top_k)
    top = route_scale * top / (jnp.sum(top, -1, keepdims=True) + 1e-20)
    E = w["we_gate"].shape[1]
    # the router's weight of every HELD expert: zero unless picked
    held = jnp.arange(E) + lo
    mix = jnp.sum(jnp.where(idx[:, :, None] == held[None, None, :],
                            top[:, :, None], 0.0), axis=1)  # [S, E]

    def one_expert(acc, e):
        def ex(name):
            m = lax.dynamic_index_in_dim(
                lax.dynamic_index_in_dim(w[name], l, 0, keepdims=False),
                e, 0, keepdims=False).astype(jnp.float32)
            return _fake_int8(m, (0,)) if int8 else m
        y = _swiglu(u, ex("we_gate"), ex("we_up"), ex("we_down"))
        return acc + y * lax.dynamic_index_in_dim(mix, e, 1), None

    y, _ = lax.scan(one_expert, jnp.zeros_like(u), jnp.arange(E))
    if "ws_gate" in w:
        y = y + _swiglu(u, leaf("ws_gate", (0,)), leaf("ws_up", (0,)),
                        leaf("ws_down", (0,)))
    return y


@functools.partial(jax.jit, static_argnames=(
    "eps", "theta", "top_k", "lo", "route_scale", "int8"))
def _moe_layer(x, w, l, *, eps, theta, top_k, lo, route_scale, int8):
    leaf = functools.partial(_leaf, w, l, int8)
    x = _mixer(x, w, l, eps=eps, theta=theta, int8=int8)
    y = _moe_ffn(_rms(x, leaf("mlp_norm"), eps), w, l, top_k=top_k, lo=lo,
                 route_scale=route_scale, int8=int8)
    return x + _rms(y, leaf("mlp_post_norm"), eps)


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
    common = dict(eps=float(cfg.get("rms_norm_eps", 1e-5)),
                  theta=float(cfg["rope_theta"]), int8=int8)
    if not cfg.get("norm_topk_prob", True):
        raise ValueError("norm_topk_prob false is not written here")
    if not cfg.get("sandwich_norm", True):
        raise ValueError("a block without sandwich_norm is not written here")
    if cfg.get("rope_scaling"):
        raise ValueError("rope_scaling is not written here")
    tokens = jnp.asarray(tokens, jnp.int32)
    if tokens.shape[0] > PAD_TO:
        tokens = jnp.pad(tokens, (0, -tokens.shape[0] % PAD_TO))
    with jax.default_matmul_precision("highest"):
        x = jnp.take(w["embed"], tokens, axis=0).astype(jnp.float32)
        for i in range(d["L"]):
            if i < d["nd"]:
                x = _dense_layer(x, w["dense"], i, **common)
            else:
                x = _moe_layer(
                    x, w["moe"], i - d["nd"], top_k=d["k"], lo=d["lo"],
                    route_scale=float(cfg.get("routed_scaling_factor", 1.0)),
                    **common)
        x = lax.dynamic_slice_in_dim(x, first, count, axis=0)
        return _head(x, w, eps=common["eps"], int8=int8)
