"""Parameter and activation sharding rules.

Maps the llama param pytree onto the (dp, pp, tp) mesh:
  * attention heads, MLP hidden, vocab         -> tp (Megatron layout)
  * MoE expert dim                             -> tp (expert parallelism
    over the same group, DeepSpeed-MoE style)
  * stacked-layer leading dim (pipeline mode)  -> pp
  * batch / optimizer state                    -> dp (ZeRO-1 style for
    optimizer state; params stay replicated across dp)

Rules are keyed by param name, not position, so every model family that
follows the llama.py naming gets sharded consistently.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# spec for each stacked-layer leaf, WITHOUT the leading layer/stage dims.
_LAYER_RULES: Dict[str, tuple] = {
    "attn_norm": (None,),
    "mlp_norm": (None,),
    "q_norm": (None,),
    "k_norm": (None,),
    "attn_post_norm": (None,),   # gemma2 post-block norms
    "mlp_post_norm": (None,),
    "bq": ("tp", None),          # qwen2 attention biases: heads on tp
    "bk": ("tp", None),          # [H|K, Dh] — follows wq/wk/wv
    "bv": ("tp", None),
    "wq": ("tp", None, None),      # [H, Dh, D]: out-major, heads first
    "wk": ("tp", None, None),      # [K, Dh, D]  (llama._proj)
    "wv": ("tp", None, None),
    "wo": ("tp", None, None),      # [H, Dh, D]
    "w_gate": (None, "tp"),        # [D, F]
    "w_up": (None, "tp"),
    "w_down": ("tp", None),        # [F, D]
    "router": (None, None),        # [D, E] replicated
    "we_gate": ("tp", None, None),  # [E, D, F] — experts sharded (EP)
    "we_up": ("tp", None, None),
    "we_down": ("tp", None, None),
    "ws_gate": (None, "tp"),        # shared experts: dense Megatron split
    "ws_up": (None, "tp"),
    "ws_down": ("tp", None),
    # MLA (models/mla.py): heads shard on tp; the latent projections
    # and the shared rope key are replicated (they are tiny, and the
    # latent cache itself is replicated — kv_cache_heads == 1)
    "wq_a": (None, None),           # [D, q_rank]
    "q_a_norm": (None,),
    "wq_b": ("tp", None, None),     # [H, qk_dim, q_rank] (out-major)
    "wkv_a": (None, None),          # [D, r + rope]
    "kv_a_norm": (None,),
    "w_uk": ("tp", None, None),     # [H, nope, r]
    "w_uv": ("tp", None, None),     # [H, r, v_dim]
    "router_bias": (None,),
}

_TOP_RULES: Dict[str, tuple] = {
    "embed": ("tp", None),         # vocab-sharded
    "final_norm": (None,),
    "lm_head": (None, "tp"),
}


def param_specs(params: Dict[str, Any], pipeline: bool = False) -> Dict[str, Any]:
    """PartitionSpec pytree matching `params`.

    pipeline=True expects layer leaves reshaped to [pp, L/pp, ...] and
    shards the stage dim on "pp"; otherwise layer leaves are [L, ...].
    """
    layer_prefix = ("pp", None) if pipeline else (None,)
    out: Dict[str, Any] = {}
    for name, leaf in params.items():
        if name in ("layers", "dense_layers"):
            out[name] = {
                k: P(*layer_prefix, *_LAYER_RULES[k]) for k in leaf
            }
        else:
            out[name] = P(*_TOP_RULES[name])
    return out


def param_shardings(params, mesh: Mesh):
    """NamedSharding pytree matching an unquantized `params` tree (or
    its `jax.eval_shape`) — the out_shardings that make random weights
    sharded from birth."""
    return jax.tree.map(lambda spec: NamedSharding(mesh, spec),
                        param_specs(params),
                        is_leaf=lambda x: isinstance(x, P))


def shard_params(params, mesh: Mesh, pipeline: bool = False):
    from ..models.quant import QTensor

    specs = param_specs(params, pipeline)

    def put(leaf, spec):
        if isinstance(leaf, QTensor):
            # the int8 payload shards like the full-precision weight;
            # the per-output-channel scale keeps size-1 (contraction)
            # dims unsharded
            s_spec = P(*[
                None if dim == 1 else ax
                for ax, dim in zip(tuple(spec) + (None,) * 8,
                                   leaf.s.shape)])
            if leaf.bits == 4:
                # int4 leaves pack along an UNSHARDED contraction dim
                # (quantize_params keeps w_down/ws_down — whose rows
                # are on tp — at int8), so the q spec carries over;
                # group scales keep size-1 dims + the group axis
                # unsharded
                gaxis = leaf.axis % leaf.q.ndim
                s_spec = P(*[
                    None if dim == 1 or i == gaxis else ax
                    for i, (ax, dim) in enumerate(
                        zip(tuple(spec) + (None,) * 8, leaf.s.shape))])
            return QTensor(
                q=jax.device_put(leaf.q, NamedSharding(mesh, spec)),
                s=jax.device_put(leaf.s, NamedSharding(mesh, s_spec)),
                bits=leaf.bits, axis=leaf.axis)
        return jax.device_put(leaf, NamedSharding(mesh, spec))

    flat_specs = jax.tree.map(lambda s: s, specs,
                              is_leaf=lambda x: isinstance(x, P))
    return jax.tree.map(put, params, flat_specs,
                        is_leaf=lambda x: isinstance(x, QTensor))


def logical(x, mesh: Optional[Mesh], *spec):
    """with_sharding_constraint if inside a mesh context, else identity."""
    if mesh is None or mesh.empty:
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*spec)))


def stack_to_stages(params: Dict[str, Any], pp: int) -> Dict[str, Any]:
    """Reshape stacked layer leaves [L, ...] -> [pp, L/pp, ...]."""
    out = dict(params)
    out["layers"] = jax.tree.map(
        lambda x: x.reshape(pp, x.shape[0] // pp, *x.shape[1:]),
        params["layers"])
    return out


def unstack_stages(params: Dict[str, Any]) -> Dict[str, Any]:
    out = dict(params)
    out["layers"] = jax.tree.map(
        lambda x: x.reshape(x.shape[0] * x.shape[1], *x.shape[2:]),
        params["layers"])
    return out
