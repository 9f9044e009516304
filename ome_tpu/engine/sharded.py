"""Tensor-parallel serving engine over a jax.sharding.Mesh.

Connects parallel/ to the serving engine (the reference delegates this
to SGLang/vLLM's NCCL tensor parallelism via --tp-size args,
SURVEY.md §2.9; here TP is GSPMD over the mesh's "tp" axis):

  * weights shard Megatron-style (attention heads / MLP hidden / vocab
    on "tp" — parallel/sharding.py rules);
  * the KV cache shards on the KV-head dim, so each device holds its
    own heads' cache and decode attention needs NO collective at all —
    the only cross-device traffic per step is the psum XLA inserts
    after the o-projection and MLP down-projection (ride ICI);
  * prefill/insert/decode are the same three compiled programs as the
    single-chip InferenceEngine — GSPMD propagates shardings from the
    committed inputs, so the host-side scheduler code is unchanged;
  * the Pallas attention kernels run per device under shard_map over
    "tp" (ops/attention.heads_sharded_over): GSPMD cannot partition a
    Mosaic kernel. The int4 matmul kernel is not wrapped and stays
    off under tp (XLA dequant), which /debug/programs lists as a
    decline.

This is what the LWS multi-host contract (controllers/reconcilers/
multinode.py) targets: the same engine, mesh spanning hosts.
"""

from __future__ import annotations

import contextlib
from typing import List, Optional

import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models import llama
from ..models.config import ModelConfig
from ..parallel.mesh import MeshConfig, build_mesh
from ..parallel.sharding import shard_params
from .core import DecodeState, InferenceEngine


class ShardedInferenceEngine(InferenceEngine):
    """InferenceEngine with params + KV cache sharded over a tp mesh."""

    def __init__(self, params, cfg: ModelConfig, tp: int = 1,
                 max_slots: int = 8, max_seq: Optional[int] = None,
                 prefill_buckets: Optional[List[int]] = None,
                 mesh: Optional[Mesh] = None,
                 prefix_cache_bytes: int = 0,
                 lora_slots: int = 0, lora_rank: int = 16,
                 ledger=None):
        if not cfg.mla and cfg.num_kv_heads % tp != 0:
            raise ValueError(
                f"tp={tp} must divide num_kv_heads={cfg.num_kv_heads} "
                f"(KV cache shards on the head dim)")
        if cfg.num_heads % tp != 0:
            raise ValueError(
                f"tp={tp} must divide num_heads={cfg.num_heads}")
        self.mesh = mesh or build_mesh(MeshConfig(tp=tp))
        self.tp = tp
        params = shard_params(params, self.mesh)
        # multi-LoRA under tp: the adapter factor stacks ([L, n, r, K],
        # a few MB) stay REPLICATED — GSPMD treats the unannotated
        # leaves as replicated operands of the delta einsums, and
        # register_adapter's host-side .at[].set updates every replica
        super().__init__(params, cfg, max_slots=max_slots, max_seq=max_seq,
                         prefill_buckets=prefill_buckets,
                         prefix_cache_bytes=prefix_cache_bytes,
                         lora_slots=lora_slots, lora_rank=lora_rank,
                         ledger=ledger)

    @contextlib.contextmanager
    def _tp_trace(self):
        """Scope the two per-trace kernel decisions of a sharded
        engine (contextvars, so tp=1 engines in the same process are
        untouched): attention kernels run per device on their own
        heads, and the un-partitioned int4 matmul kernel stays off —
        GSPMD would replicate it and all-gather the packed weight
        every step."""
        from ..ops.attention import heads_sharded_over
        from ..ops.int4_matmul import kernel_disabled
        with heads_sharded_over(self.mesh), kernel_disabled():
            yield

    # every op that can trace a program runs inside the scope; GSPMD
    # propagates the committed shardings (KV head-sharded, tokens and
    # lengths replicated) through each of them, fori_loop carries and
    # the multi-token verify forward included

    def prefill(self, *a, **kw):
        with self._tp_trace():
            return super().prefill(*a, **kw)

    def insert(self, *a, **kw):
        with self._tp_trace():
            return super().insert(*a, **kw)

    def decode(self, *a, **kw):
        with self._tp_trace():
            return super().decode(*a, **kw)

    def verify(self, *a, **kw):
        with self._tp_trace():
            return super().verify(*a, **kw)

    def decode_multi(self, *a, **kw):
        with self._tp_trace():
            return super().decode_multi(*a, **kw)

    def _kv_sharding(self) -> NamedSharding:
        # [L, B, S, K * Dh], a row's heads merged in the lanes
        # (llama.KVCache): KV heads on tp, the heads of a chip
        # contiguous lanes of the merged axis, so it shards as the
        # head axis did. MLA caches ONE latent head
        # (kv_cache_heads == 1) — replicated; the latent cache is tiny
        # (kv_lora_rank+rope per token) so replication is the right
        # trade vs collectives in the absorbed decode path
        if self.cfg.mla:
            return self._replicated()
        return NamedSharding(self.mesh, P(None, None, None, "tp"))

    def _replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    def new_state(self) -> DecodeState:
        # zeros are born sharded (`device=`): building the full
        # [L, B, S, K * Dh] slab on one device and moving it would
        # need the whole cache to fit on that device first
        cfg = self.cfg
        L, B, S = cfg.num_layers, self.max_slots, self.max_seq
        ks, vs = llama.kv_rows_shapes(cfg, (L, B, S),
                                      self.kv_rows_merged)
        kv = self._kv_sharding()
        rep = self._replicated()
        return DecodeState(
            k=jnp.zeros(ks, cfg.dtype, device=kv),
            v=jnp.zeros(vs, cfg.dtype, device=kv),
            lengths=jnp.zeros((B,), jnp.int32, device=rep),
            tokens=jnp.zeros((B,), jnp.int32, device=rep),
            adapters=jnp.zeros((B,), jnp.int32, device=rep))
