"""Expert-parallel ragged MoE over a mesh axis (shard_map).

The dense MoE path shards experts on the tp/ep axis through plain
GSPMD (every expert computed, sharding.py rules). This module is the
*ragged* EP path: each device holds E/ep experts and runs grouped
GEMMs (lax.ragged_dot) only over the token-expert pairs routed to its
local experts — compute O(k) instead of O(E/ep) per token, weights
memory sharded, one psum over the ep axis to combine contributions
(rides ICI; the XLA analog of the reference engines' all-to-all
dispatch, SURVEY.md §2.9 "--moe-a2a-backend deepep").

Routing is computed redundantly on every device (cheap: one [T, E]
matmul) so there is no dispatch collective at all: non-local pairs are
weighted to zero and psum sums each pair's contribution exactly once.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from ..models.config import ModelConfig


def moe_mlp_ragged_ep(x: jax.Array, lp, cfg: ModelConfig, mesh: Mesh,
                      axis: str = "tp") -> jax.Array:
    """x: [B, S, D] replicated; lp: one layer's params with we_* sharded
    on `axis` along the expert dim. Returns [B, S, D] replicated."""
    ep = mesh.shape[axis]
    E = cfg.num_experts
    assert E % ep == 0, f"experts {E} must divide over {axis}={ep}"

    def local(x, router, we_gate, we_up, we_down):
        local_e = we_gate.shape[0]
        rank = lax.axis_index(axis)
        lo = rank * local_e
        B, S, D = x.shape
        k = cfg.experts_per_token
        T = B * S
        logits = jnp.einsum("bsd,de->bse", x, router).astype(jnp.float32)
        weights, idx = lax.top_k(logits, k)
        weights = jax.nn.softmax(weights, axis=-1)
        ids = idx.reshape(T * k)
        w = weights.reshape(T * k)
        mine = (ids >= lo) & (ids < lo + local_e)
        # non-local pairs: route to local expert 0 with weight 0 — they
        # compute garbage that contributes nothing, and psum over the ep
        # axis counts every pair exactly once on its owner
        local_ids = jnp.where(mine, ids - lo, 0)
        w = jnp.where(mine, w, 0.0)
        order = jnp.argsort(local_ids)
        token_of = order // k
        xs = jnp.take(x.reshape(T, D), token_of, axis=0)
        group_sizes = jnp.bincount(local_ids, length=local_e) \
            .astype(jnp.int32)
        gate = lax.ragged_dot(xs, we_gate, group_sizes)
        up = lax.ragged_dot(xs, we_up, group_sizes)
        out_sorted = lax.ragged_dot(jax.nn.silu(gate) * up, we_down,
                                    group_sizes)
        w_sorted = jnp.take(w, order, axis=0)
        contrib = out_sorted * w_sorted[:, None].astype(out_sorted.dtype)
        out = jnp.zeros((T, D), contrib.dtype).at[token_of].add(contrib)
        out = lax.psum(out, axis)
        return out.reshape(B, S, D).astype(x.dtype)

    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(), P(), P(axis), P(axis), P(axis)),
        out_specs=P(),
        check_vma=False)
    return fn(x, lp["router"], lp["we_gate"], lp["we_up"], lp["we_down"])
