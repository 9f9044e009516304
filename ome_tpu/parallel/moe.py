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
matmul) so there is no dispatch collective at all: a pair routed to
an expert another device holds sorts behind every local group, takes
no grouped-matmul rows here, and psum sums each pair's contribution
exactly once.
"""

from __future__ import annotations

import jax
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from ..models import llama
from ..models.config import ModelConfig


def moe_mlp_ragged_ep(x: jax.Array, lp, cfg: ModelConfig, mesh: Mesh,
                      axis: str = "tp") -> jax.Array:
    """x: [B, S, D] replicated; lp: one layer's params with we_* sharded
    on `axis` along the expert dim. Returns [B, S, D] replicated."""
    ep = mesh.shape[axis]
    E = cfg.num_experts
    assert E % ep == 0, f"experts {E} must divide over {axis}={ep}"

    def local(x, router, we_gate, we_up, we_down):
        # the layer of models/llama.py that is told which experts it
        # holds: the same body serves one chip of a cut configuration,
        # there without the exchange
        B, S, D = x.shape
        weights, idx = llama._route(x, {"router": router}, cfg)
        held = {"we_gate": we_gate, "we_up": we_up, "we_down": we_down}
        out, _ = llama.ragged_experts(
            x.reshape(B * S, D), weights, idx, held, cfg,
            lo=lax.axis_index(axis) * we_gate.shape[0])
        out = lax.psum(out, axis)
        return out.reshape(B, S, D).astype(x.dtype)

    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(), P(), P(axis), P(axis), P(axis)),
        out_specs=P(),
        check_vma=False)
    return fn(x, lp["router"], lp["we_gate"], lp["we_up"], lp["we_down"])
